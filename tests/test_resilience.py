"""Fault-tolerant training runtime (ISSUE 4): anomaly guard policies,
retry/backoff over the error taxonomy, preemption-safe checkpointing
with auto-resume, checkpoint manifest/GC hardening — all driven by the
deterministic fault-injection harness (resilience.faultinject), so
every recovery path in here fails loudly if the fault never fired."""

import os
import signal

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, resilience
from paddle_tpu.checkpoint import (CheckpointManager, latest_step,
                                   load_extras, save_checkpoint)
from paddle_tpu.resilience import faultinject, retry, taxonomy


@pytest.fixture(autouse=True)
def _clean_resilience_state():
    """No test may leak guards/retries/faults/preemption into the next."""
    yield
    resilience.disable_anomaly_guard()
    resilience.disable_retry()
    resilience.clear_preemption()
    faultinject.disarm()


@pytest.fixture()
def mon():
    was = monitor.is_enabled()
    monitor.reset()
    monitor.enable()
    yield monitor
    monitor.disable()
    monitor.reset()
    if was:
        monitor.enable()


def _counters():
    return monitor.snapshot().get("counters", {})


# ---------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------

def test_taxonomy_transient_status_codes():
    for msg in ("RESOURCE_EXHAUSTED: out of memory allocating",
                "DEADLINE_EXCEEDED: slept too long",
                "ABORTED: cross-replica op cancelled"):
        assert taxonomy.classify(RuntimeError(msg)) == taxonomy.TRANSIENT, msg


def test_taxonomy_preemption_category():
    """ISSUE 11: rank-death shapes (coordination service, barrier
    timeout, lost heartbeat, dead-peer transports, preempted workers)
    classify PREEMPTION — still retry-worthy (is_transient), but the
    elastic coordinator and the retry path agree on what "a rank died"
    looks like instead of these falling through to a blind TRANSIENT."""
    for msg in ("UNAVAILABLE: coordination service error",
                "worker was preempted by the scheduler",
                "Socket closed before handshake",
                "barrier timed out waiting for 1 of 2 tasks",
                "coordinator detected missing heartbeats from task 1",
                "connection reset by peer",
                "peer process terminated unexpectedly"):
        exc = RuntimeError(msg)
        assert taxonomy.classify(exc) == taxonomy.PREEMPTION, msg
        assert taxonomy.is_transient(exc), msg       # still retryable
        assert taxonomy.is_preemption(exc), msg


def test_taxonomy_fatal_status_codes_and_types():
    # fatal status code wins even though the same message also says
    # ABORTED (first-match ordering in the table)
    assert taxonomy.classify(RuntimeError(
        "INVALID_ARGUMENT: computation was ABORTED")) == taxonomy.FATAL
    # programming-error TYPES fail fast regardless of message content
    assert taxonomy.classify(
        KeyError("RESOURCE_EXHAUSTED")) == taxonomy.FATAL
    assert taxonomy.classify(TypeError("preempted")) == taxonomy.FATAL
    # unknown errors default to fatal — retrying blind is worse
    assert taxonomy.classify(RuntimeError("huh")) == taxonomy.FATAL


def test_taxonomy_injected_and_os_errors_transient():
    assert taxonomy.is_transient(taxonomy.InjectedTransientError("x"))
    assert taxonomy.is_transient(ConnectionResetError("peer gone"))
    assert taxonomy.is_transient(TimeoutError("slow"))


# ---------------------------------------------------------------------
# retry / backoff
# ---------------------------------------------------------------------

def test_retry_backoff_sequence_deterministic():
    delays = []
    pol = retry.RetryPolicy(max_retries=4, base_delay=1.0, multiplier=2.0,
                            max_delay=5.0, jitter=0.5,
                            sleep=delays.append, seed=7)
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] <= 4:
            raise taxonomy.InjectedTransientError("RESOURCE_EXHAUSTED")
        return "ok"

    assert retry.call_with_retry(flaky, pol) == "ok"
    assert calls[0] == 5 and len(delays) == 4
    # jittered exponential: each delay within +-50% of 1,2,4,5(capped)
    for d, base in zip(delays, (1.0, 2.0, 4.0, 5.0)):
        assert 0.5 * base <= d <= 1.5 * base, (d, base)
    # deterministic under the same seed
    delays2 = []
    pol2 = retry.RetryPolicy(max_retries=4, base_delay=1.0, multiplier=2.0,
                             max_delay=5.0, jitter=0.5,
                             sleep=delays2.append, seed=7)
    calls[0] = 0
    retry.call_with_retry(flaky, pol2)
    assert delays2 == delays


def test_retry_fatal_fails_fast():
    pol = retry.RetryPolicy(max_retries=5, sleep=lambda d: pytest.fail(
        "must not back off on a fatal error"))
    with pytest.raises(ValueError):
        retry.call_with_retry(
            lambda: (_ for _ in ()).throw(ValueError("bad shape")), pol)


def test_retry_exhaustion_chains_last_error(mon):
    pol = retry.RetryPolicy(max_retries=2, sleep=lambda d: None)

    def always():
        raise taxonomy.InjectedTransientError("UNAVAILABLE")

    with pytest.raises(retry.RetriesExhausted) as ei:
        retry.call_with_retry(always, pol)
    assert isinstance(ei.value.last_error, taxonomy.InjectedTransientError)
    assert ei.value.attempts == 3
    c = _counters()
    assert c.get("resilience.retries") == 2
    assert c.get("resilience.retry_giveup") == 1


# ---------------------------------------------------------------------
# checkpoint hardening: manifest, orphan GC, crash-during-save
# ---------------------------------------------------------------------

def _st(v):
    return {"w": np.full((4,), float(v), np.float32)}


def test_manifest_detects_truncated_checkpoint(tmp_path):
    save_checkpoint(tmp_path, _st(1), 1)
    save_checkpoint(tmp_path, _st(2), 2)
    assert latest_step(tmp_path) == 2
    # truncate one payload file of step_2 AFTER its marker was written
    step2 = os.path.join(tmp_path, "step_2")
    victim = None
    for root, _, files in os.walk(step2):
        for f in files:
            if not f.startswith("_") and os.path.getsize(
                    os.path.join(root, f)) > 0:
                victim = os.path.join(root, f)
                break
        if victim:
            break
    assert victim, "no payload file found to truncate"
    with open(victim, "r+b") as f:
        f.truncate(max(0, os.path.getsize(victim) - 1))
    # markered-but-truncated is NOT a checkpoint: fall back to step 1
    assert latest_step(tmp_path) == 1


def test_manifest_detects_bitflip(tmp_path):
    save_checkpoint(tmp_path, _st(1), 1)
    step1 = os.path.join(tmp_path, "step_1")
    victim = None
    for root, _, files in os.walk(step1):
        for f in files:
            p = os.path.join(root, f)
            if not f.startswith("_") and os.path.getsize(p) > 0:
                victim = p
                break
        if victim:
            break
    with open(victim, "r+b") as f:
        b = f.read(1)
        f.seek(0)
        f.write(bytes([b[0] ^ 0xFF]))
    # same size, corrupt bytes: only the crc catches it
    assert latest_step(tmp_path) is None


def test_gc_removes_orphaned_incomplete_dirs(tmp_path):
    mgr = CheckpointManager(tmp_path, max_to_keep=3)
    # a crashed attempt: step dir without marker, OLDER than the best
    os.makedirs(os.path.join(tmp_path, "step_2", "state"))
    with open(os.path.join(tmp_path, "step_2", "state", "junk"), "w") as f:
        f.write("partial")
    # an in-flight attempt NEWER than the best complete: must survive
    os.makedirs(os.path.join(tmp_path, "step_9", "state"))
    mgr.save(_st(5), 5)
    assert not os.path.isdir(os.path.join(tmp_path, "step_2"))
    assert os.path.isdir(os.path.join(tmp_path, "step_9"))
    assert latest_step(tmp_path) == 5


def test_crash_between_write_and_marker_falls_back(tmp_path, mon):
    """ISSUE 4 satellite: kill between array write and _COMPLETE via
    the harness; restore_latest must fall back to the previous
    checkpoint and training must resume at the right step."""
    mgr = CheckpointManager(tmp_path, save_interval_steps=1)
    mgr.save(_st(1), 1)
    with pytest.raises(faultinject.InjectedCrash):
        with faultinject.plan_scope(
                crash_points={"checkpoint.before_marker": 0}):
            mgr.save(_st(2), 2)
    # the torn dir exists but is invisible to latest_step
    assert os.path.isdir(os.path.join(tmp_path, "step_2"))
    assert latest_step(tmp_path) == 1
    state, step = mgr.restore_latest(_st(0))
    assert step == 1
    np.testing.assert_array_equal(state["w"], _st(1)["w"])
    # resumed training overwrites/GCs the torn attempt
    mgr.save(_st(2), 2)
    assert latest_step(tmp_path) == 2
    assert faultinject.active_plan() is None  # plan_scope disarmed
    assert _counters().get("resilience.injected_crash") == 1


# ---------------------------------------------------------------------
# executor integration: a tiny deterministic training problem
# ---------------------------------------------------------------------

def _build_program():
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 8])
            y = fluid.data("y", [None, 1])
            pred = fluid.layers.fc(x, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"x": rng.standard_normal((16, 8)).astype(np.float32),
             "y": rng.standard_normal((16, 1)).astype(np.float32)}
            for _ in range(n)]


def _reference_weights(main, startup, loss, batches, train_loop=False):
    """Uninterrupted reference for the recovery tests.  train_loop=True
    routes it through train_from_dataset itself, so a test whose body
    trains through the dataset loop compares against the SAME dispatch
    path (including the ISSUE-14 AMP/fusion train tier that loop
    applies by default) and its bitwise assertion pins the recovery
    machinery, not a path difference; tests driving bare exe.run loops
    keep the bare-loop reference."""
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    if train_loop:
        exe.train_from_dataset(main, list(batches), scope=sc,
                               fetch_list=[loss], print_period=100,
                               prefetch=False)
    else:
        for b in batches:
            exe.run(main, feed=b, fetch_list=[loss], scope=sc)
    return np.asarray(sc.find_var("fc_0.w_0"))


def test_guard_skip_step_commits_nothing(mon):
    main, startup, loss = _build_program()
    batches = _batches(5)
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.enable_anomaly_guard(policy="skip_step")
    with faultinject.plan_scope(nan_at_steps=[2]):
        snaps = []
        for b in batches:
            snaps.append(np.asarray(sc.find_var("fc_0.w_0")))
            out = exe.run(main, feed=b, fetch_list=[loss], scope=sc)
    w = np.asarray(sc.find_var("fc_0.w_0"))
    # the NaN step (index 2) changed nothing; neighbours trained
    np.testing.assert_array_equal(snaps[3], snaps[2])
    assert not np.array_equal(snaps[2], snaps[1])
    assert not np.array_equal(w, snaps[4])
    assert np.isfinite(w).all()
    c = _counters()
    assert c.get("resilience.injected_nan") == 1
    assert c.get("resilience.anomaly_steps") == 1
    assert c.get("resilience.skipped_steps") == 1


def test_guard_raise_policy(mon):
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.enable_anomaly_guard(policy="raise")
    b = _batches(1)[0]
    exe.run(main, feed=b, fetch_list=[loss], scope=sc)   # clean step OK
    with faultinject.plan_scope(nan_at_steps=[0]):
        with pytest.raises(resilience.AnomalyError):
            exe.run(main, feed=b, fetch_list=[loss], scope=sc)


def test_guard_escalates_after_max_consecutive(mon):
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.enable_anomaly_guard(policy="skip_step", max_consecutive=2)
    b = _batches(1)[0]
    with faultinject.plan_scope(nan_at_steps=[0, 1, 2]):
        exe.run(main, feed=b, fetch_list=[loss], scope=sc)
        exe.run(main, feed=b, fetch_list=[loss], scope=sc)
        with pytest.raises(resilience.AnomalyError):
            exe.run(main, feed=b, fetch_list=[loss], scope=sc)


def test_guard_rollback_bitwise_identical(mon, tmp_path):
    """Acceptance: injected NaN under rollback recovers to params
    bitwise-identical to an uninterrupted run."""
    main, startup, loss = _build_program()
    batches = _batches(6)
    ref_w = _reference_weights(main, startup, loss, batches)

    mgr = CheckpointManager(tmp_path, save_interval_steps=1)
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    persist = sorted(v.name for v in main.list_vars() if v.persistable)

    def state():
        return {n: sc.find_var(n) for n in persist
                if sc.find_var(n) is not None}

    resilience.enable_anomaly_guard(policy="rollback", manager=mgr)
    rollbacks = []
    with faultinject.plan_scope(nan_at_steps=[4]):
        i = 0
        while i < len(batches):
            try:
                exe.run(main, feed=batches[i], fetch_list=[loss], scope=sc)
            except resilience.RollbackPerformed as rb:
                rollbacks.append((i, rb.step))
                i = rb.step          # rewind the data cursor
                continue
            i += 1
            mgr.save(state(), i)
    assert rollbacks == [(4, 4)]
    np.testing.assert_array_equal(np.asarray(sc.find_var("fc_0.w_0")),
                                  ref_w)
    c = _counters()
    assert c.get("resilience.rollbacks") == 1
    assert c.get("resilience.checkpoint_restores") == 1


def test_transient_error_retried_with_backoff(mon):
    """Acceptance: an injected transient error inside the dispatch is
    retried with backoff and the step completes; counters visible."""
    main, startup, loss = _build_program()
    batches = _batches(3)
    ref_w = _reference_weights(main, startup, loss, batches)
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    delays = []
    resilience.enable_retry(resilience.RetryPolicy(
        max_retries=4, base_delay=0.01, sleep=delays.append, seed=3))
    with faultinject.plan_scope(transient_at_step=1, transient_times=2):
        for b in batches:
            exe.run(main, feed=b, fetch_list=[loss], scope=sc)
    assert len(delays) == 2          # two raises -> two backoffs
    np.testing.assert_array_equal(np.asarray(sc.find_var("fc_0.w_0")),
                                  ref_w)
    c = _counters()
    assert c.get("resilience.retries") == 2
    assert c.get("resilience.injected_transient") == 2


def test_retry_gives_up_on_persistent_transient(mon):
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.enable_retry(resilience.RetryPolicy(
        max_retries=1, sleep=lambda d: None))
    with faultinject.plan_scope(transient_at_step=0, transient_times=99):
        with pytest.raises(resilience.RetriesExhausted):
            exe.run(main, feed=_batches(1)[0], fetch_list=[loss], scope=sc)


# ---------------------------------------------------------------------
# train_from_dataset: checkpoint cadence, preemption, auto-resume,
# in-loop rollback replay
# ---------------------------------------------------------------------

def test_train_from_dataset_checkpoint_cadence(tmp_path):
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    exe.train_from_dataset(main, _batches(7), scope=sc, fetch_list=[loss],
                           checkpoint={"directory": str(tmp_path),
                                       "save_interval_steps": 3},
                           print_period=100)
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps == [3, 6]
    # the rng sidecar rides along for exact resume
    assert "executor_rng_key" in load_extras(tmp_path)


def test_preempt_then_auto_resume_bitwise_identical(mon, tmp_path):
    """Acceptance: preemption force-checkpoints at the next step
    boundary and exits cleanly; auto_resume skips consumed batches and
    finishes bitwise-identical to an uninterrupted run."""
    main, startup, loss = _build_program()
    batches = _batches(8)
    ref_w = _reference_weights(main, startup, loss, batches, train_loop=True)

    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)

    def preempting():
        for i, b in enumerate(batches):
            if i == 5:
                resilience.request_preemption()
            yield b

    ck = {"directory": str(tmp_path), "save_interval_steps": 1000}
    exe.train_from_dataset(main, preempting(), scope=sc,
                           fetch_list=[loss], checkpoint=ck,
                           print_period=100, prefetch=False)
    assert latest_step(tmp_path) == 5       # force-saved off-interval
    resilience.clear_preemption()

    # fresh process analogue: new executor + scope, same command
    exe2 = fluid.Executor()
    sc2 = fluid.Scope()
    exe2.run(startup, scope=sc2)
    exe2.train_from_dataset(main, batches, scope=sc2, fetch_list=[loss],
                            checkpoint=ck, auto_resume=True,
                            print_period=100, prefetch=False)
    np.testing.assert_array_equal(np.asarray(sc2.find_var("fc_0.w_0")),
                                  ref_w)
    c = _counters()
    assert c.get("resilience.preempt_checkpoint") == 1
    assert c.get("resilience.auto_resume") == 1
    assert c.get("resilience.batches_skipped") == 5
    # the forced save left its duration on the gauge the trace plots
    assert monitor.snapshot()["gauges"].get(
        "resilience.last_save_s") is not None


def test_sigterm_requests_preemption():
    with resilience.PreemptionHandler():
        assert not resilience.preemption_requested()
        os.kill(os.getpid(), signal.SIGTERM)
        # delivery is between-bytecode; poll briefly
        for _ in range(1000):
            if resilience.preemption_requested():
                break
        assert resilience.preemption_requested()
    # handler restored: a fresh SIGTERM would now hit the default
    assert signal.getsignal(signal.SIGTERM) == signal.SIG_DFL


def test_train_from_dataset_rollback_replays_cursor(mon, tmp_path):
    """In-loop rollback: the guard restores the newest checkpoint and
    train_from_dataset replays its buffered batches — the caller sees
    one uninterrupted-equivalent run."""
    main, startup, loss = _build_program()
    batches = _batches(7)
    ref_w = _reference_weights(main, startup, loss, batches, train_loop=True)

    mgr = CheckpointManager(tmp_path, save_interval_steps=2)
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.enable_anomaly_guard(policy="rollback", manager=mgr)
    # faultinject counts run() dispatches: step 5 here is batch index 5
    # (startup ran before arming)
    with faultinject.plan_scope(nan_at_steps=[5]):
        exe.train_from_dataset(main, batches, scope=sc, fetch_list=[loss],
                               checkpoint=mgr, print_period=100,
                               prefetch=False)
    np.testing.assert_array_equal(np.asarray(sc.find_var("fc_0.w_0")),
                                  ref_w)
    c = _counters()
    assert c.get("resilience.rollbacks") == 1
    assert c.get("resilience.injected_nan") == 1


def test_train_from_dataset_rollback_without_checkpoint_kwarg(mon,
                                                              tmp_path):
    """Review regression: a rollback-policy guard without checkpoint=
    must still be handled in-loop (the loop adopts the guard's own
    manager — including an up-front save so even a first-step anomaly
    has a restore point), never letting RollbackPerformed escape."""
    main, startup, loss = _build_program()
    batches = _batches(5)
    ref_w = _reference_weights(main, startup, loss, batches, train_loop=True)
    mgr = CheckpointManager(tmp_path, save_interval_steps=2)
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.enable_anomaly_guard(policy="rollback", manager=mgr)
    with faultinject.plan_scope(nan_at_steps=[0]):   # FIRST batch NaN
        exe.train_from_dataset(main, batches, scope=sc,
                               fetch_list=[loss], print_period=100,
                               prefetch=False)
    np.testing.assert_array_equal(np.asarray(sc.find_var("fc_0.w_0")),
                                  ref_w)
    assert _counters().get("resilience.rollbacks") == 1


def test_train_from_dataset_rejects_mismatched_managers(tmp_path):
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    resilience.enable_anomaly_guard(
        policy="rollback",
        manager=CheckpointManager(tmp_path / "a"))
    with pytest.raises(ValueError, match="same one"):
        exe.train_from_dataset(
            main, _batches(1), fetch_list=[loss],
            checkpoint=CheckpointManager(tmp_path / "b"))


def test_rollback_before_any_checkpoint_escalates(mon, tmp_path):
    """Review regression: an anomaly under rollback with an EMPTY
    manager must raise AnomalyError with the real story, not a bare
    FileNotFoundError from deep inside the loader."""
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.enable_anomaly_guard(
        policy="rollback", manager=CheckpointManager(tmp_path))
    with faultinject.plan_scope(nan_at_steps=[0]):
        with pytest.raises(resilience.AnomalyError,
                           match="before any complete checkpoint"):
            exe.run(main, feed=_batches(1)[0], fetch_list=[loss],
                    scope=sc)


def test_preemption_flag_cleared_after_handling(tmp_path):
    """Review regression: once the loop has force-checkpointed and
    exited, the flag must come down — a later train_from_dataset in
    the same process must actually train."""
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.request_preemption()
    exe.train_from_dataset(main, _batches(3), scope=sc,
                           fetch_list=[loss],
                           checkpoint=str(tmp_path), print_period=100,
                           prefetch=False)
    assert not resilience.preemption_requested()
    w0 = np.asarray(sc.find_var("fc_0.w_0"))
    exe.train_from_dataset(main, _batches(3), scope=sc,
                           fetch_list=[loss], print_period=100,
                           prefetch=False)
    assert not np.array_equal(np.asarray(sc.find_var("fc_0.w_0")), w0)


def test_auto_resume_without_checkpoint_rejected():
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    with pytest.raises(ValueError, match="auto_resume"):
        exe.train_from_dataset(main, _batches(1), fetch_list=[loss],
                               auto_resume=True)


def test_save_does_not_recrc_fresh_checkpoint(tmp_path, monkeypatch):
    """Review regression: the manager's post-save _gc must serve the
    just-written checkpoint's verification from the seeded memo, not
    re-read every payload byte (write + 2x read per save)."""
    from paddle_tpu import checkpoint as ck

    mgr = CheckpointManager(tmp_path, save_interval_steps=1)
    mgr.save(_st(1), 1)
    mgr.save(_st(2), 2)
    calls = []
    real = ck._file_crc32
    monkeypatch.setattr(ck, "_file_crc32",
                        lambda p, **kw: calls.append(p) or real(p, **kw))
    # reads after the saves: verification is served from the memo the
    # writer seeded (the one read-back inside _write_manifest is the
    # only CRC pass a checkpoint ever pays)
    assert latest_step(tmp_path) == 2
    assert calls == []
    mgr.save(_st(3), 3)      # _gc re-lists steps 1..3
    assert not [c for c in calls if "step_1" in c or "step_2" in c], calls
    writer_reads = [c for c in calls if "step_3" in c]
    assert latest_step(tmp_path) == 3
    assert [c for c in calls if "step_3" in c] == writer_reads


def test_gated_steps_do_not_touch_save_path(tmp_path):
    """Review regression: interval-gated steps must not even build the
    checkpoint state dict (per-var scope lookups + rng host copy on the
    no-sync loop)."""
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)

    calls = []

    class CountingManager(CheckpointManager):
        def save(self, state, step, **kw):
            calls.append(step)
            return super().save(state, step, **kw)

    mgr = CountingManager(tmp_path, save_interval_steps=3)
    exe.train_from_dataset(main, _batches(7), scope=sc,
                           fetch_list=[loss], checkpoint=mgr,
                           print_period=100, prefetch=False)
    assert calls == [3, 6]


def test_rollback_keeps_replay_batches_on_host(monkeypatch):
    """Review regression: the rollback replay buffer retains every
    feed since the last save — those must be HOST batches (the device
    double-buffer would pin the whole recovery window in HBM)."""
    from paddle_tpu import reader as reader_mod

    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    used = []
    real = reader_mod.device_prefetch
    monkeypatch.setattr(reader_mod, "device_prefetch",
                        lambda gen, **kw: used.append(1) or real(gen, **kw))
    # no guard: dense path uses the device double-buffer
    exe.train_from_dataset(main, _batches(2), scope=sc,
                           fetch_list=[loss], print_period=100)
    assert used
    # rollback guard active: device prefetch must stay off
    del used[:]
    import tempfile

    resilience.enable_anomaly_guard(
        policy="rollback",
        manager=CheckpointManager(tempfile.mkdtemp()))
    exe.train_from_dataset(main, _batches(2), scope=sc,
                           fetch_list=[loss], print_period=100)
    assert not used


def test_skip_step_does_not_push_nan_sparse_grads(mon):
    """Review regression: 'commits nothing' must cover the sparse half
    — the NaN step's gradient rows never reach the embedding table."""
    from paddle_tpu import layers
    from paddle_tpu.backward import append_backward
    from paddle_tpu.distributed.ps import SparseEmbedding

    dim = 4
    table = SparseEmbedding(dim=dim, num_shards=2, lr=0.2, seed=0)
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            emb = fluid.data("emb", [None, 2, dim])
            label = fluid.data("label", [None, 1])
            flat = layers.reshape(emb, [-1, 2 * dim])
            logit = fluid.layers.fc(flat, 1)
            loss = layers.mean(
                layers.sigmoid_cross_entropy_with_logits(logit, label))
            params = [p.name for p in main.all_parameters()]
            append_backward(loss, parameter_list=params + [emb.name])
            opt = fluid.optimizer.SGD(0.2)
            opt.apply_gradients([(main.global_block().var(p),
                                  main.global_block().var(p + "@GRAD"))
                                 for p in params])
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    r = np.random.default_rng(0)
    batches = [{"ids": r.integers(0, 20, (8, 2)).astype(np.int64),
                "label": r.integers(0, 2, (8, 1)).astype(np.float32)}
               for _ in range(3)]
    resilience.enable_anomaly_guard(policy="skip_step")
    # the only float feed is "emb" (the pulled rows) -> NaN batch 1
    with faultinject.plan_scope(nan_at_steps=[1]):
        exe.train_from_dataset(
            main, batches, scope=sc, fetch_list=[loss], print_period=100,
            sparse_config={"table": table, "ids_var": "ids",
                           "emb_var": "emb"})
    assert _counters().get("resilience.skipped_steps") == 1
    assert len(table) > 0                      # clean steps DID push
    all_ids = np.unique(np.concatenate([b["ids"].ravel()
                                        for b in batches]))
    rows = table.pull(all_ids)
    assert np.isfinite(np.asarray(rows)).all()  # no NaN row committed


def test_infer_from_dataset_ignores_rollback_manager(tmp_path):
    """Review regression: an eval drain under an active rollback guard
    must not adopt the guard's manager — eval vars interval-saved into
    the TRAINING store would rotate out real restore points."""
    from paddle_tpu import layers

    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 8])
            pred = fluid.layers.fc(x, 1)
            score = layers.mean(pred)
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    mgr = CheckpointManager(tmp_path, save_interval_steps=1)
    resilience.enable_anomaly_guard(policy="rollback", manager=mgr)
    exe.infer_from_dataset(main, _batches(3), scope=sc,
                           fetch_list=[score], print_period=100)
    assert mgr.latest_step() is None
    assert list(tmp_path.iterdir()) == []


def test_preempt_skips_rewrite_of_durable_checkpoint(mon, tmp_path):
    """Review regression: preemption at a boundary that is ALREADY
    checkpointed must not rmtree+rewrite it (a SIGKILL mid-rewrite
    would lose the only fresh restore point)."""
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)

    forced = []

    class SpyManager(CheckpointManager):
        def save(self, state, step, force=False, **kw):
            if force:
                forced.append(step)
            return super().save(state, step, force=force, **kw)

    mgr = SpyManager(tmp_path, save_interval_steps=1)   # saves EVERY step
    batches = _batches(5)

    def preempting():
        for i, b in enumerate(batches):
            if i == 3:
                resilience.request_preemption()
            yield b

    exe.train_from_dataset(main, preempting(), scope=sc,
                           fetch_list=[loss], checkpoint=mgr,
                           print_period=100, prefetch=False)
    assert forced == []        # step 3 was already durable: no rewrite
    assert mgr.latest_step() == 3
    assert _counters().get("resilience.preempt_checkpoint") == 1


def test_checkpointless_drain_leaves_preemption_flag_set():
    """Review regression: a loop with no checkpoint store must stop on
    preemption but NOT clear the flag — the enclosing training loop
    still has to see the request and take the real force-checkpoint."""
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.request_preemption()
    out = exe.train_from_dataset(main, _batches(3), scope=sc,
                                 fetch_list=[loss], print_period=100,
                                 prefetch=False)
    assert out is None                      # stopped before any step
    assert resilience.preemption_requested()  # flag survives


def test_request_preemption_is_flag_only(mon):
    """Review regression: the signal-handler entry point must be
    async-signal-safe.  A SIGTERM can interrupt a frame that HOLDS the
    monitor registry lock; if request_preemption touched a counter it
    would deadlock right here (counting happens in the loop that
    observes the flag instead)."""
    with monitor._registry._lock:      # the interrupted frame's lock
        resilience.request_preemption()
    assert resilience.preemption_requested()


def test_cold_latest_step_verifies_only_newest(tmp_path, monkeypatch):
    """Review regression: a fresh-process resume must CRC only the
    newest checkpoint, not every retained one."""
    from paddle_tpu import checkpoint as ck

    for s in (1, 2, 3):
        save_checkpoint(tmp_path, _st(s), s)
    ck._verify_memo.clear()                 # fresh-process analogue
    calls = []
    real = ck._file_crc32
    monkeypatch.setattr(ck, "_file_crc32",
                        lambda p, **kw: calls.append(p) or real(p, **kw))
    assert latest_step(tmp_path) == 3
    assert all("step_3" in c for c in calls), calls
    assert calls                            # it DID verify the newest


def test_retry_catches_runtime_transient_by_message(mon):
    """A transient failure raised by the compiled callable itself —
    classified by the UNAVAILABLE message, not by the harness's
    injected type — is retried through the public run().  The failure
    strikes BEFORE execution consumes the donated inputs (the
    allocation/rendezvous class the retry layer targets; a mid-
    execution failure that consumed donations fails fast by design)."""
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    b = _batches(1)[0]
    exe.run(main, feed=b, fetch_list=[loss], scope=sc)  # warm the cache

    fails = [1]

    def make_flaky(fn):
        def flaky_compiled(state, feeds, key):
            if fails and fails.pop():
                raise RuntimeError(
                    "UNAVAILABLE: failed to allocate device buffers")
            return fn(state, feeds, key)

        return flaky_compiled

    for k, (fn, p) in list(exe._cache.items()):
        exe._cache[k] = (make_flaky(fn), p)
    delays = []
    resilience.enable_retry(resilience.RetryPolicy(
        max_retries=2, base_delay=0.01, sleep=delays.append, seed=0))
    out = exe.run(main, feed=b, fetch_list=[loss], scope=sc)
    assert len(delays) == 1
    assert np.isfinite(np.asarray(out[0])).all()
    assert _counters().get("resilience.retries") == 1


def test_first_sigint_after_sigterm_does_not_escalate():
    """Review regression: escalation counts SIGINTs specifically — an
    orchestrator's SIGTERM (or programmatic request) must not turn the
    user's FIRST Ctrl-C into a mid-step KeyboardInterrupt."""
    h = resilience.PreemptionHandler()
    h._on_signal(signal.SIGTERM, None)         # orchestrator notice
    assert resilience.preemption_requested()
    h._on_signal(signal.SIGINT, None)          # first Ctrl-C: graceful
    with pytest.raises(KeyboardInterrupt):
        h._on_signal(signal.SIGINT, None)      # second: the user means it


def test_gc_does_not_cold_crc_retained_checkpoints(tmp_path,
                                                   monkeypatch):
    """Review regression: the first save of a resumed process must not
    CRC-read every retained checkpoint for the retention decision —
    _gc trusts markers; corruption is caught at restore-target
    selection (latest_step)."""
    from paddle_tpu import checkpoint as ck

    mgr = CheckpointManager(tmp_path, max_to_keep=5,
                            save_interval_steps=1)
    for s in (1, 2, 3):
        mgr.save(_st(s), s)
    ck._verify_memo.clear()                    # fresh-process analogue
    calls = []
    real = ck._file_crc32
    monkeypatch.setattr(ck, "_file_crc32",
                        lambda p, **kw: calls.append(p) or real(p, **kw))
    mgr.save(_st(4), 4)
    old_reads = [c for c in calls if "step_4" not in c]
    assert old_reads == [], old_reads          # no retained-dir re-reads


def test_all_finite_catches_python_float_nan():
    """Review regression: dtype-less Python-float leaves must be
    promoted and checked — float('nan') slipping through would let the
    loss scaler commit a poisoned update."""
    assert not bool(resilience.all_finite({"loss": float("nan")}))
    assert not bool(resilience.all_finite({"loss": float("inf")}))
    assert bool(resilience.all_finite({"loss": 1.5, "n": 3}))


def test_gc_rotation_never_deletes_last_good_checkpoint(tmp_path):
    """Review regression: on a store whose NEWER markered dirs were
    corrupted after their marker, rotation must not delete the oldest
    (only verified-good) checkpoint."""
    from paddle_tpu import checkpoint as ck

    mgr = CheckpointManager(tmp_path, max_to_keep=2,
                            save_interval_steps=1)
    for s in (2, 3, 4):
        save_checkpoint(tmp_path, _st(s), s)
    # corrupt the two NEWEST after their markers landed
    for s in (3, 4):
        d = os.path.join(tmp_path, f"step_{s}")
        for root, _, files in os.walk(d):
            for f in files:
                p = os.path.join(root, f)
                if not f.startswith("_") and os.path.getsize(p) > 0:
                    with open(p, "r+b") as fh:
                        b = fh.read(1)
                        fh.seek(0)
                        fh.write(bytes([b[0] ^ 0xFF]))
                    break
    ck._verify_memo.clear()
    mgr._gc()          # rotation wants to drop step_2 (beyond keep-2)
    assert os.path.isdir(os.path.join(tmp_path, "step_2"))
    assert latest_step(tmp_path) == 2      # the survivor restores


def test_checkpointless_preempt_warns(tmp_path):
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.request_preemption()
    with pytest.warns(RuntimeWarning, match="no checkpoint="):
        exe.train_from_dataset(main, _batches(2), scope=sc,
                               fetch_list=[loss], print_period=100,
                               prefetch=False)
    assert resilience.preemption_requested()   # still up for the owner


def test_rollback_with_sparse_push_rejected(tmp_path):
    main, startup, loss = _build_program()
    mgr = CheckpointManager(tmp_path)
    exe = fluid.Executor()
    resilience.enable_anomaly_guard(policy="rollback", manager=mgr)

    class _Table:
        def pull(self, ids):
            return np.zeros((len(ids), 4), np.float32)

        def push(self, ids, g):
            pass

    with pytest.raises(ValueError, match="rollback"):
        exe.train_from_dataset(
            main, _batches(1), fetch_list=[loss], checkpoint=mgr,
            sparse_config={"table": _Table(), "ids_var": "x",
                           "emb_var": "x"})


# ---------------------------------------------------------------------
# guard + AMP functional path
# ---------------------------------------------------------------------

def test_amp_all_finite_shared_implementation():
    from paddle_tpu import amp

    assert amp.all_finite is resilience.all_finite
    import jax.numpy as jnp

    assert bool(amp.all_finite({"a": jnp.ones(3)}))
    assert not bool(amp.all_finite({"a": jnp.asarray([1.0, np.nan])}))
    # non-float leaves (rng keys, int counters) don't break the check
    assert bool(amp.all_finite({"k": jnp.zeros((2,), jnp.uint32)}))


def test_guarded_step_skip_and_rollback(mon, tmp_path):
    from paddle_tpu.amp import make_amp_train_step
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.optimizer.functional import SGD

    m = GPT(GPTConfig(vocab_size=32, hidden_size=16, num_layers=1,
                      num_heads=2, max_seq_len=8))
    step, make_state = make_amp_train_step(m, SGD(0.1), jit=True,
                                           donate=False)
    state = make_state()
    r = np.random.default_rng(0)
    x = r.integers(0, 32, (2, 8)).astype(np.int32)

    mgr = CheckpointManager(tmp_path, save_interval_steps=1)
    guard = resilience.enable_anomaly_guard(policy="skip_step")
    gstep = resilience.guarded_step(step, guard)
    state, loss, ok = gstep(state, x, x)
    assert ok
    mgr.save(state, 1)

    # poison params -> skip policy returns the scaler-selected state
    import jax.numpy as jnp
    ts, sc = state
    from paddle_tpu.models.train import TrainState

    bad_params = dict(ts.params)
    k = next(iter(bad_params))
    bad_params[k] = ts.params[k] * jnp.nan
    poisoned = (TrainState(params=bad_params, opt_state=ts.opt_state,
                           buffers=ts.buffers, step=ts.step, rng=ts.rng),
                sc)
    st2, loss2, ok2 = gstep(poisoned, x, x)
    assert not ok2
    assert _counters().get("resilience.skipped_steps") == 1

    # rollback policy restores from the manager
    guard = resilience.enable_anomaly_guard(policy="rollback", manager=mgr)
    gstep = resilience.guarded_step(step, guard)
    with pytest.raises(resilience.RollbackPerformed) as ei:
        gstep(poisoned, x, x)
    assert ei.value.step == 1
    restored_ts, _ = ei.value.state
    np.testing.assert_array_equal(np.asarray(restored_ts.params[k]),
                                  np.asarray(ts.params[k]))


# ---------------------------------------------------------------------
# telemetry surfaces
# ---------------------------------------------------------------------

def test_recovery_counters_in_merged_trace(mon):
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.enable_anomaly_guard(policy="skip_step")
    b = _batches(1)[0]
    with faultinject.plan_scope(nan_at_steps=[0]):
        exe.run(main, feed=b, fetch_list=[loss], scope=sc)
    exe.run(main, feed=b, fetch_list=[loss], scope=sc)
    events = monitor.merged_trace_events([])
    resil = [e for e in events if e.get("name") == "resilience"
             and e.get("ph") == "C"]
    assert resil, "recovery events missing from the merged trace"
    assert any(e["args"].get("skipped_steps") for e in resil)


def test_guard_toggle_recompiles_not_stale(mon):
    """The compiled-step cache keys on the guard: enabling it after a
    cached unguarded run must produce the fused check, and disabling
    must drop back — no stale artifact either way."""
    main, startup, loss = _build_program()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    b = _batches(1)[0]
    exe.run(main, feed=b, fetch_list=[loss], scope=sc)   # unguarded cached
    resilience.enable_anomaly_guard(policy="skip_step")
    with faultinject.plan_scope(nan_at_steps=[0]):
        exe.run(main, feed=b, fetch_list=[loss], scope=sc)
    assert _counters().get("resilience.skipped_steps") == 1
    resilience.disable_anomaly_guard()
    # unguarded again: a NaN feed now flows through unchecked (the
    # guarded artifact with its flag fetch must NOT be served)
    out = exe.run(main, feed=b, fetch_list=[loss], scope=sc)
    assert len(out) == 1
