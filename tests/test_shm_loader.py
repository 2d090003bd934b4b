"""Multiprocess shared-memory DataLoader tests.

Parity target: fluid/reader.py:469 DygraphGeneratorLoader
(use_multiprocess=True) — worker processes + shared-memory queue.
Key assertions: batch ORDER matches the serial reader, worker crashes
propagate, no shared-memory segments leak, and the batches of a
sharded reader are produced in every worker process.
"""

import glob
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.reader import DataLoader
from paddle_tpu.reader.shm import ShmBatchLoader


def _batches(n=8, size=256, seed=1):
    def reader():
        rng = np.random.default_rng(seed)
        for i in range(n):
            yield {"x": rng.normal(size=(size,)).astype(np.float32),
                   "i": np.array([i], np.int64)}

    return reader


def test_order_and_values_match_serial():
    reader = _batches()
    serial = list(reader())
    for workers in (1, 2, 3):
        got = list(ShmBatchLoader(reader, num_workers=workers))
        assert len(got) == len(serial)
        for a, b in zip(got, serial):
            assert int(a["i"][0]) == int(b["i"][0])   # order preserved
            np.testing.assert_array_equal(a["x"], b["x"])


def test_tuple_batches_roundtrip():
    def reader():
        for i in range(4):
            yield (np.full((3,), i, np.float32), np.array([i]))

    got = list(ShmBatchLoader(reader, num_workers=2))
    assert len(got) == 4
    for i, item in enumerate(got):
        assert isinstance(item, list)
        np.testing.assert_array_equal(item[0], np.full((3,), i,
                                                       np.float32))


def test_worker_error_propagates():
    def reader():
        yield {"x": np.zeros(4, np.float32)}
        raise ValueError("reader blew up in worker")

    with pytest.raises(RuntimeError, match="reader blew up"):
        list(ShmBatchLoader(reader, num_workers=2))


def test_no_segment_leak():
    from paddle_tpu.reader import shm as shm_mod

    loader = ShmBatchLoader(_batches(n=6), num_workers=2)
    for _ in range(2):
        list(loader)
    assert not shm_mod._LIVE_SEGMENTS
    # early consumer exit must also clean up
    it = iter(ShmBatchLoader(_batches(n=6), num_workers=2))
    next(it)
    it.close()
    time.sleep(0.2)
    assert not shm_mod._LIVE_SEGMENTS


def test_uneven_shard_aware_reader_drains_fully():
    # worker 0: 2 batches, worker 1: 5 batches — nothing may be dropped
    def reader(worker_id, num_workers):
        counts = [2, 5]
        for j in range(counts[worker_id]):
            yield {"w": np.array([worker_id], np.int64),
                   "j": np.array([j], np.int64)}

    got = [(int(b["w"][0]), int(b["j"][0]))
           for b in ShmBatchLoader(reader, num_workers=2)]
    assert sorted(got) == sorted(
        [(0, j) for j in range(2)] + [(1, j) for j in range(5)])


def test_dataloader_multiprocess_integration():
    x_data = np.arange(32, dtype=np.float32).reshape(8, 4)

    def reader():
        for i in range(8):
            yield {"x": x_data[i:i + 1]}

    loader = DataLoader.from_generator(use_multiprocess=True,
                                       num_workers=2)
    loader.set_batch_generator(reader)
    got = np.concatenate([b["x"] for b in loader])
    np.testing.assert_array_equal(got, x_data)


def _stamped_batch(i):
    return {"x": np.full((4,), np.float32(i)),
            "pid": np.array([os.getpid()])}


def _stamped_reader(n=9):
    def reader():
        for i in range(n):
            yield _stamped_batch(i)

    return reader


def _stamped_sharded(n=9):
    # shard-aware form: worker w generates only batches w, w+N, ...
    def reader(worker_id, num_workers):
        for i in range(worker_id, n, num_workers):
            yield _stamped_batch(i)

    return reader


def test_multiprocess_batches_come_from_every_worker_process_in_order():
    """What a CPU run can show of the multiprocess loader: the batches
    are the threaded loader's, in its order, and each was produced in
    one of `num_workers` processes that are not this one (the reader
    stamps its pid into the batch).  How much faster that is, is a
    question for a machine whose cores the suite does not share."""
    threaded = DataLoader.from_generator(capacity=4)
    threaded.set_batch_generator(_stamped_reader())
    serial = list(threaded)

    shm = DataLoader.from_generator(use_multiprocess=True, num_workers=3,
                                    capacity=6)
    shm.set_batch_generator(_stamped_sharded())
    got = list(shm)

    assert len(serial) == len(got) == 9
    for a, b in zip(serial, got):       # same order, same values
        np.testing.assert_array_equal(a["x"], b["x"])
    assert {int(b["pid"][0]) for b in serial} == {os.getpid()}
    pids = [int(b["pid"][0]) for b in got]
    assert len(set(pids)) == 3 and os.getpid() not in pids
    assert pids[:3] == pids[3:6] == pids[6:]    # worker w made w, w+3, w+6


def test_feeds_static_training():
    import paddle_tpu as fluid
    from paddle_tpu import layers

    with fluid.scope_guard(fluid.Scope()):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 4])
            y = fluid.data("y", [None, 1])
            loss = layers.mean(layers.square_error_cost(
                fluid.layers.fc(x, 1), y))
            fluid.optimizer.SGD(0.05).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)

        def reader():
            rng = np.random.default_rng(0)
            w = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
            for _ in range(20):
                xb = rng.normal(size=(16, 4)).astype(np.float32)
                yield {"x": xb, "y": xb @ w}

        loader = DataLoader.from_generator(use_multiprocess=True,
                                           num_workers=2)
        loader.set_batch_generator(reader)
        losses = [float(exe.run(main, feed=b, fetch_list=[loss])[0])
                  for b in loader]
        assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


# ---------------------------------------------------------------------
# producer-death guard (ISSUE 8 satellite)
# ---------------------------------------------------------------------

def test_producer_death_raises_classified_instead_of_hanging():
    """A worker PROCESS killed without a sentinel (the OOM-killer /
    SIGKILL shape, injected via the fault harness: crash_point in a
    forked child exits hard with no cleanup) must unblock the consumer
    with a CLASSIFIED transient error — not hang it forever on a queue
    nobody will ever feed again."""
    from paddle_tpu.reader.shm import ProducerDeadError
    from paddle_tpu.resilience import faultinject, taxonomy

    with faultinject.plan_scope(crash_points={"shm.worker": 2}):
        loader = ShmBatchLoader(_batches(n=8), num_workers=1,
                                death_poll_s=0.2)
        got = []
        t0 = time.time()
        with pytest.raises(ProducerDeadError) as ei:
            for b in loader:
                got.append(int(b["i"][0]))
        # batches before the injected kill arrived in order...
        assert got == [0, 1]
        # ...the guard detected the death promptly (no 300s hang)
        assert time.time() - t0 < 30
        assert "died" in str(ei.value)
    # a dead producer is a dead-peer shape: PREEMPTION in the taxonomy
    # (ConnectionError by type, ISSUE 11) but still retry-worthy —
    # re-running the loader is the recovery, like the reference fleet
    # re-launching a worker
    assert taxonomy.classify(ei.value) == taxonomy.PREEMPTION
    assert taxonomy.is_transient(ei.value)
    assert isinstance(ei.value, ConnectionError)


def test_producer_death_guard_does_not_fire_on_healthy_worker():
    """The liveness poll must be invisible to a healthy run: same
    batches, same order, no spurious ProducerDeadError."""
    loader = ShmBatchLoader(_batches(n=6), num_workers=1,
                            death_poll_s=0.1)
    got = [int(b["i"][0]) for b in loader]
    assert got == list(range(6))
