"""Real multi-process collective test (VERDICT r3 #3; 4-proc r4 #9).

Spawns an N-worker localhost cluster through distributed.launch
.start_procs (the PADDLE_* env contract), whose workers run
jax.distributed.initialize via distributed/env.py — the path no
in-process mesh test can cover.  Numerics parity:
test_collective_base.py:34,123 (psum/allgather values) inside the
worker; test_dist_base.py:935 (2-trainer dist-vs-local loss delta
<= 1e-3) asserted here against a single-process run of the same
problem.  A wrong coordinator/port/rank wiring fails the worker's
process_count/psum asserts and surfaces as a nonzero exit.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu.distributed.launch import _wait, start_procs

WORKER = os.path.join(os.path.dirname(__file__),
                      "dist_worker_collective.py")


def _local_reference_losses(steps=5):
    """Single-process full-batch run of the worker's training problem
    (equal shards make the mean-of-shard-means equal the full-batch
    gradient, so ONE reference serves every world size)."""
    rng = np.random.default_rng(0)
    true_w = rng.normal(size=(8, 1)).astype(np.float32)
    X = rng.normal(size=(32, 8)).astype(np.float32)
    Y = (X @ true_w).astype(np.float32)
    prng = np.random.default_rng(1)
    w = (prng.normal(size=(8, 1)) * 0.1).astype(np.float32)
    b = np.zeros((1,), np.float32)
    losses = []
    for _ in range(steps):
        pred = X @ w + b
        err = pred - Y
        losses.append(float((err ** 2).mean()))
        gw = 2.0 * X.T @ err / err.size
        gb = np.full((1,), 2.0 * err.mean(), np.float32)
        w = w - 0.1 * gw
        b = b - 0.1 * gb
    return losses


@pytest.mark.parametrize("nproc", [2, 4])
def test_cluster_collectives_and_dist_vs_local(nproc, tmp_path):
    out = tmp_path / "rank0.json"
    log_dir = tmp_path / "logs"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs, logs = start_procs(
        node_ips=["127.0.0.1"], node_ip="127.0.0.1",
        nproc_per_node=nproc,
        training_script=WORKER, script_args=(str(out),),
        log_dir=str(log_dir),
        # prepend (not replace) the caller's PYTHONPATH; bound the
        # rendezvous so a wiring bug fails fast, not at JAX's
        # 300s default
        env_extra={"PYTHONPATH": repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""),
                   "PADDLE_RENDEZVOUS_TIMEOUT": "60"})

    def _dump():
        return "\n".join(
            f"--- {p}:\n" + open(os.path.join(log_dir, p)).read()[-2000:]
            for p in sorted(os.listdir(log_dir)))

    # deadline watchdog: a post-rendezvous collective deadlock (e.g. one
    # worker killed mid-psum) would otherwise hang the suite forever
    deadline = time.time() + 180
    while time.time() < deadline:
        if all(p.poll() is not None for p in procs):
            break
        time.sleep(0.5)
    else:
        for p in procs:
            p.kill()
        _wait(procs, logs)
        raise AssertionError(f"cluster hung past deadline\n{_dump()}")
    rc = _wait(procs, logs)
    if rc != 0:
        raise AssertionError(f"worker failed rc={rc}\n{_dump()}")
    result = json.loads(out.read_text())
    assert result["world"] == nproc
    dist_losses = result["losses"]
    local_losses = _local_reference_losses(len(dist_losses))
    # test_dist_base.py:935 delta contract
    for i, (d, l) in enumerate(zip(dist_losses, local_losses)):
        assert abs(d - l) <= 1e-3, (i, d, l)

    # --- rank-tagged telemetry merge (ISSUE 10 satellite) --------------
    # each worker wrote its own JSONL stream into the shared dir with
    # rank-distinct payloads; the fleet merge must attribute every
    # record to the rank that wrote it (REAL multi-process stamps, not
    # the single-process default of 0)
    sys.path.insert(0, repo)
    from tools.telemetry_report import fleet_merge, summarize_fleet

    tdir = tmp_path / "telemetry"
    streams = sorted(os.path.join(tdir, p) for p in os.listdir(tdir))
    assert len(streams) == nproc, streams
    by_rank, merged = fleet_merge(streams)
    assert len(by_rank) == nproc, list(by_rank)
    for label, records in by_rank.items():
        steps = [r for r in records if r.get("kind") == "step"]
        assert steps, label
        ranks = {r["process_index"] for r in steps}
        assert len(ranks) == 1, (label, ranks)
        r = ranks.pop()
        assert label.endswith(f":p{r}")
        # the payload the worker wrote for THIS rank, on every record
        assert all(s["host_dispatch_us"] == 100.0 + r for s in steps)
        assert all(s["examples"] == 8 * (r + 1) for s in steps)
    summary = summarize_fleet(by_rank, merged)
    assert summary["ranks"] == nproc
    assert set(summary["by_rank"]) == set(by_rank)


def test_bad_rank_wiring_fails(tmp_path):
    """Anti-green-on-broken check: a cluster whose PADDLE_TRAINERS_NUM
    lies about the world size must NOT come up quietly — the worker's
    process_count assert (or the rendezvous timeout) kills it."""
    out = tmp_path / "never.json"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.update({
        "PADDLE_TRAINER_ID": "0",
        "PADDLE_TRAINERS_NUM": "2",
        # both "endpoints" are the same port: rank 1 never exists
        "PADDLE_CURRENT_ENDPOINT": "127.0.0.1:6199",
        "PADDLE_TRAINER_ENDPOINTS": "127.0.0.1:6199,127.0.0.1:6199",
    })
    env["PADDLE_RENDEZVOUS_TIMEOUT"] = "15"
    p = subprocess.run(
        [sys.executable, WORKER, str(out)], env=env, timeout=240,
        capture_output=True)
    assert p.returncode != 0
    assert not out.exists()
    # the death must be the BOUNDED RENDEZVOUS firing, not an unrelated
    # crash (else the timeout plumbing could regress silently)
    err = p.stderr.decode(errors="replace")
    assert "DEADLINE_EXCEEDED" in err or "imeout" in err, err[-800:]
