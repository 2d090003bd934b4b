"""Fleet-wide observability tests (ISSUE 10).

Covers the three tentpole pillars in-process — rank-tagged telemetry
(JSONL stamping + rotation + merge tools), straggler/skew attribution
(the on-device probe's numerics via shard_map, the rolling table math
against hand-computed values, executor integration on a 2-device dp
mesh), and the live /metrics + /healthz exporter (Prometheus text
round-trip, scrape == snapshot, serving outcome-ledger identity on the
scrape itself, breaker-driven health) — plus the flight-recorder rank
tagging satellite.  The REAL 2-process wiring is covered by
tests/test_dist_collective.py (rank-stream merge) and, at the end of
this file, by a 2-process run with an injected straggler
(tests/dist_worker_fleet.py).
"""

import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.monitor import exporter, fleet
from paddle_tpu.monitor.jsonl_writer import JsonlWriter, read_jsonl
from paddle_tpu.transpiler.collective import emit_skew_probe


@pytest.fixture(autouse=True)
def _clean():
    monitor.reset()
    fleet.clear()
    yield
    monitor.disable()
    monitor.reset()
    fleet.clear()
    exporter.stop()


# ---------------------------------------------------------------------------
# rank identity
# ---------------------------------------------------------------------------

def test_rank_info_complete_once_backend_up():
    # before any device query the stamp falls back to the PADDLE_* env
    # contract; once the backend is up a LATER read is enriched with
    # jax's own identity (reading must never itself init the backend)
    monitor.rank_info()
    jax.devices()               # ensure the backend is initialized
    info = monitor.rank_info()
    assert info["process_index"] == jax.process_index()
    assert info["process_count"] == jax.process_count()
    assert info["local_device_ids"] == [d.id for d in jax.local_devices()]
    assert info["host"] and info["pid"] == os.getpid()


def test_rank_tag_is_compact():
    tag = monitor.rank_tag()
    assert set(tag) <= {"host", "process_index", "local_device_ids"}
    assert tag["process_index"] == jax.process_index()


def test_host_timestamp_encoding():
    sec, usec = fleet.host_timestamp()
    assert 0 <= sec < fleet.EPOCH_MOD
    assert 0 <= usec < 10 ** 6


# ---------------------------------------------------------------------------
# the on-device probe (emit_skew_probe numerics)
# ---------------------------------------------------------------------------

def _probe(sec_vals, usec_vals):
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    f = jax.jit(jax.shard_map(
        lambda s, u: emit_skew_probe(s, u, "dp"), mesh=mesh,
        in_specs=(P("dp"), P("dp")), out_specs=P(), check_vma=False))
    out = f(jnp.asarray(sec_vals, jnp.int32),
            jnp.asarray(usec_vals, jnp.int32))
    return np.asarray(out)


def test_probe_same_second_microsecond_delta():
    # device1 arrived 500 us later: device0 waited 500, device1 waited 0
    waits = _probe([100, 100], [100, 600])
    assert waits.tolist() == [500.0, 0.0]


def test_probe_cross_second_is_exact():
    # 5.999999 vs 6.000003 — only a LEXICOGRAPHIC max gives the exact
    # 4 us gap (a plain pmax over usec would pick 999999)
    waits = _probe([5, 6], [999999, 3])
    assert waits.tolist() == [4.0, 0.0]


def test_probe_simultaneous_is_zero():
    assert _probe([7, 7], [42, 42]).tolist() == [0.0, 0.0]


# ---------------------------------------------------------------------------
# the rolling skew table
# ---------------------------------------------------------------------------

def _feed_rows(waits_list, step_time_s=0.01):
    for i, w in enumerate(waits_list):
        fleet.note_sync(np.asarray(w, np.float64),
                        step_record={"step": i + 1,
                                     "step_time_s": step_time_s})


def test_wrap_boundary_sample_discarded():
    # the EPOCH_MOD seconds-wrap landing between two ranks' timestamps
    # yields a ~EPOCH_MOD-second wait; that one sample must not poison
    # the rolling window (wrong straggler, absurd max_skew_us)
    _feed_rows([[800.0, 0.0]] * 3
               + [[fleet.EPOCH_MOD * 1e6, 0.0]]      # wrapped step
               + [[800.0, 0.0]])
    t = fleet.fleet_skew()
    assert t["steps"] == 4                           # bogus row dropped
    assert t["max_skew_us"] == 800.0
    assert t["straggler"]["dp_index"] == 1
    assert monitor.snapshot()["counters"]["fleet.wrap_discards"] == 1


def test_skew_table_names_the_straggler():
    # rank1 always arrives 800us late: rank0 waits 800, rank1 waits 0
    _feed_rows([[800.0, 0.0]] * 4, step_time_s=0.002)
    t = fleet.fleet_skew()
    assert t["steps"] == 4
    assert t["straggler"]["dp_index"] == 1
    r0, r1 = t["ranks"]
    assert r0["wait_us_mean"] == 800.0 and r0["behind_us_mean"] == 0.0
    assert r1["wait_us_mean"] == 0.0 and r1["behind_us_mean"] == 800.0
    assert r1["slowest_steps"] == 4 and r0["slowest_steps"] == 0
    # wait_frac = mean wait / mean step time = 800us / 2000us
    assert r0["wait_frac"] == pytest.approx(0.4)
    assert r1["straggler_score"] == pytest.approx(0.4)
    assert t["max_skew_us"] == 800.0


def test_skew_table_window_and_rows():
    _feed_rows([[100.0, 0.0]] * 6 + [[0.0, 300.0]] * 2)
    rows = fleet.skew_rows()
    assert len(rows) == 8
    assert rows[0]["waits_us"] == [100.0, 0.0]
    t = fleet.fleet_skew(window=2)
    # inside the window rank0 is now the slow one
    assert t["steps"] == 2
    assert t["straggler"]["dp_index"] == 0


def test_skew_counters_and_gauge():
    _feed_rows([[650.0, 0.0]] * 3)
    fleet.fleet_skew()
    snap = monitor.snapshot()
    assert snap["counters"]["fleet.sync_probes"] == 3
    assert snap["gauges"]["fleet.skew_us"] == 650.0
    assert snap["fleet"]["skew"]["straggler"]["dp_index"] == 1
    assert snap["fleet"]["rank"]["process_index"] == jax.process_index()


def test_record_fleet_skew_rides_the_stream(tmp_path):
    path = str(tmp_path / "t.jsonl")
    monitor.enable(jsonl_path=path)
    _feed_rows([[120.0, 0.0]] * 2)
    rec = monitor.record_fleet_skew(key="prog")
    assert rec["kind"] == "fleet_skew" and rec["key"] == "prog"
    assert monitor.fleet_skew_records()[-1]["straggler"]["dp_index"] == 1
    monitor.disable()
    kinds = [r["kind"] for r in read_jsonl(path)]
    assert "fleet_skew" in kinds
    monitor.reset()
    assert monitor.fleet_skew_records() == []
    assert fleet.fleet_skew() is None   # reset cleared the ring too


# ---------------------------------------------------------------------------
# JSONL rank stamping + rotation
# ---------------------------------------------------------------------------

def test_jsonl_lines_are_rank_stamped(tmp_path):
    path = str(tmp_path / "s.jsonl")
    w = JsonlWriter(path)
    w.emit({"kind": "step", "step": 1})
    w.close()
    (rec,) = read_jsonl(path)
    assert rec["host"] == monitor.rank_tag()["host"]
    assert rec["process_index"] == jax.process_index()
    assert rec["local_device_ids"] == [d.id for d in jax.local_devices()]


def test_jsonl_rank_tag_off_writes_clean_lines(tmp_path):
    path = str(tmp_path / "s.jsonl")
    w = JsonlWriter(path, rank_tag=False)
    w.emit({"kind": "step", "step": 1})
    w.close()
    assert read_jsonl(path) == [{"kind": "step", "step": 1}]


def test_jsonl_rotation_keeps_last_k(tmp_path):
    path = str(tmp_path / "r.jsonl")
    w = JsonlWriter(path, max_bytes=120, keep=2, rank_tag=False)
    for i in range(20):
        w.emit({"seq": i, "pad": "x" * 40})
    w.close()
    assert os.path.exists(f"{path}.1") and os.path.exists(f"{path}.2")
    assert not os.path.exists(f"{path}.3")   # beyond keep: deleted
    # transparent read, oldest first, a contiguous SUFFIX of the writes
    seqs = [r["seq"] for r in read_jsonl(path)]
    assert seqs == list(range(seqs[0], 20))
    assert len(seqs) < 20                    # something WAS dropped


def test_jsonl_failed_rename_never_churns_segments(tmp_path,
                                                   monkeypatch):
    # a persistently failing ACTIVE-file rename (reader holding the
    # file on an odd filesystem) must not re-run the delete-and-shift
    # per emit — that would churn away every retained segment; it also
    # must not crash the emitting thread
    path = str(tmp_path / "f.jsonl")
    w = JsonlWriter(path, max_bytes=120, keep=2, rank_tag=False)
    for i in range(6):                       # one healthy rotation
        w.emit({"seq": i, "pad": "x" * 40})
    assert os.path.exists(f"{path}.1")
    kept = open(f"{path}.1").read()

    real_replace = os.replace

    def flaky_replace(src, dst):
        if src == path:                      # only the final rename
            raise OSError("held open")
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", flaky_replace)
    for i in range(6, 30):                   # many owed rotations
        w.emit({"seq": i, "pad": "x" * 40})
    # the retained segment shifted up ONCE and then survived
    assert open(f"{path}.2").read() == kept
    monkeypatch.setattr(os, "replace", real_replace)
    w.emit({"seq": 99, "pad": "x" * 120})    # rename works again
    w.close()
    assert os.path.exists(f"{path}.1")       # rotation resumed
    assert any(r["seq"] == 99 for r in read_jsonl(path))


def test_jsonl_no_rotation_when_disabled(tmp_path):
    path = str(tmp_path / "n.jsonl")
    w = JsonlWriter(path, max_bytes=0, keep=2, rank_tag=False)
    for i in range(50):
        w.emit({"seq": i, "pad": "x" * 40})
    w.close()
    assert not os.path.exists(f"{path}.1")
    assert [r["seq"] for r in read_jsonl(path)] == list(range(50))


# ---------------------------------------------------------------------------
# executor integration: the dp probe on a 2-device mesh
# ---------------------------------------------------------------------------

def _dp_program():
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


def _run_steps(prog, startup, loss, n=3, batch=8):
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    rng = np.random.default_rng(0)
    for _ in range(n):
        exe.run(prog, feed={
            "x": rng.standard_normal((batch, 8)).astype(np.float32),
            "y": rng.standard_normal((batch, 1)).astype(np.float32)},
            fetch_list=[loss], scope=sc)
    return exe, sc


def test_dp_step_carries_the_probe():
    main, startup, loss = _dp_program()
    prog = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=2)
    monitor.enable()
    _run_steps(prog, startup, loss, n=3)
    rows = fleet.skew_rows()
    assert len(rows) == 3
    # single process: every shard shares one host timestamp -> 0 waits
    assert all(r["waits_us"] == [0.0, 0.0] for r in rows)
    assert monitor.snapshot()["counters"]["fleet.sync_probes"] == 3
    # the probe's reserved feeds never pollute byte/example accounting
    rec = monitor.step_records()[-1]
    assert rec["feed_bytes"] == 8 * 8 * 4 + 8 * 1 * 4
    assert rec["examples"] == 8


def test_probe_off_by_flag_and_for_non_dp():
    main, startup, loss = _dp_program()
    prog = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=2)
    monitor.enable()
    fluid.set_flags({"FLAGS_fleet_skew": False})
    try:
        _run_steps(prog, startup, loss, n=2)
        assert fleet.skew_rows() == []
    finally:
        fluid.set_flags({"FLAGS_fleet_skew": True})
    # non-dp programs never carry the probe, whatever the flag says
    _run_steps(main, startup, loss, n=2)
    assert fleet.skew_rows() == []


# ---------------------------------------------------------------------------
# exporter: /metrics + /healthz
# ---------------------------------------------------------------------------

def test_prometheus_text_round_trip():
    monitor.counter("fleet.sync_probes").add(7)
    monitor.gauge("dp_devices").set(2)
    parsed = exporter.parse_prometheus(exporter.prometheus_text())
    assert parsed[("paddle_tpu_fleet_sync_probes_total", ())] == 7.0
    assert parsed[("paddle_tpu_dp_devices", ())] == 2.0


def test_scrape_matches_snapshot_over_http():
    monitor.counter("run_plan.hit").add(3)
    monitor.counter("resilience.retries").add(2)
    monitor.gauge("dp_devices").set(2)
    _feed_rows([[900.0, 0.0]] * 2, step_time_s=0.003)
    srv = exporter.start(0, host="127.0.0.1")
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10) as r:
            assert r.status == 200
            text = r.read().decode()
        parsed = exporter.parse_prometheus(text)
        snap = monitor.snapshot()
        for name, v in snap["counters"].items():
            if name in {"serving.requests", "serving.queue_depth",
                        "serving.in_flight", "fleet.process_count"}:
                # ledger-owned: the exporter skips the bare registry
                # copy and exports the {runtime=...}-labeled family
                # instead (registry names survive monitor.reset() with
                # value 0, so any earlier serving test leaves them)
                continue
            key = ("paddle_tpu_"
                   + exporter._sanitize(name) + "_total", ())
            assert parsed[key] == float(v), name
        # the fleet table rides as per-rank labeled gauges (no mesh in
        # the synthetic feed, so no process_index label)
        lab = (("dp_index", "0"),)
        assert parsed[("paddle_tpu_fleet_wait_us_mean", lab)] == 900.0
        assert parsed[("paddle_tpu_fleet_straggler_dp_index", ())] == 1.0
    finally:
        exporter.stop()


def test_prometheus_families_contiguous():
    """All samples of one metric family must form a single contiguous
    group (exposition-format requirement promtool/OpenMetrics enforce)
    — with >=2 serving runtimes and >=2 fleet ranks the per-row loops
    must not interleave families."""
    from paddle_tpu.serving.stats import ServingStats

    for key in ("t_contig_a", "t_contig_b"):
        s = ServingStats(label=key, register=True)
        s.note_admitted(depth=1)
        s.note_outcome("completed", latency_s=0.01)
    _feed_rows([[100.0, 0.0, 50.0]] * 2, step_time_s=0.002)
    monitor.counter("run_plan.hit").add(1)
    # what enabled telemetry's serving hooks bump: these registry names
    # sanitize to the ledger-owned families and must be skipped, not
    # emitted as a second (unlabeled) copy of the family
    monitor.counter("serving.requests").add(2)
    monitor.gauge("serving.queue_depth").set(1)
    monitor.gauge("serving.in_flight").set(0)
    seen, last = [], None
    for line in exporter.prometheus_text().splitlines():
        if not line.strip() or line.startswith("#"):
            continue
        name = line.split("{")[0].split(" ")[0]
        if name != last:
            seen.append(name)
            last = name
    dupes = [n for n in set(seen) if seen.count(n) > 1]
    assert not dupes, dupes


def test_healthz_and_unknown_path():
    srv = exporter.start(0, host="127.0.0.1")
    base = f"http://127.0.0.1:{srv.port}"
    with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
        assert r.status == 200
        assert json.loads(r.read())["ok"] is True
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(base + "/nope", timeout=10)
    assert e.value.code == 404


class _FakeBreaker:
    def __init__(self, state):
        self.state = state

    def summary(self):
        return {"state": self.state, "transitions": []}


def test_serving_ledger_identity_on_the_scrape():
    from paddle_tpu.serving.stats import ServingStats

    stats = ServingStats(label="t_fleet_exp", register=True)
    for _ in range(5):
        stats.note_admitted(depth=1)
    for outcome, lat in (("completed", 0.01), ("completed", 0.02),
                         ("failed", 0.03), ("shed", None)):
        stats.note_outcome(outcome, latency_s=lat)
    stats.note_outcome("rejected")        # rejected self-admits
    parsed = exporter.parse_prometheus(exporter.prometheus_text())
    lab = ("runtime", "t_fleet_exp")
    requests = parsed[("paddle_tpu_serving_requests_total", (lab,))]
    outcomes = sum(v for (n, labels), v in parsed.items()
                   if n == "paddle_tpu_serving_outcome_total"
                   and lab in labels)
    pending = parsed[("paddle_tpu_serving_pending", (lab,))]
    # the zero-silent-loss identity, asserted ON THE SCRAPE: every
    # admitted request is either resolved or still pending
    assert requests == 6.0
    assert outcomes == 5.0 and pending == 1.0
    assert requests == outcomes + pending
    assert parsed[("paddle_tpu_serving_latency_p50_ms", (lab,))] == 20.0


def test_healthz_degrades_when_breaker_opens():
    from paddle_tpu.serving.stats import ServingStats

    stats = ServingStats(label="t_fleet_hz", register=True)
    stats.attach_breaker(_FakeBreaker("open"))
    ok, checks = exporter.health()
    assert ok is False and checks["breaker_open"] is True
    srv = exporter.start(0, host="127.0.0.1")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/healthz", timeout=10)
    assert e.value.code == 503
    assert json.loads(e.value.read())["checks"]["breaker_open"] is True
    stats.attach_breaker(_FakeBreaker("closed"))
    ok, _ = exporter.health()
    assert ok is True


def test_exporter_off_by_default_and_idempotent_start():
    assert exporter.active() is None
    assert exporter.ensure_started() is None    # FLAGS_metrics_port=0
    srv = exporter.start(0, host="127.0.0.1")
    assert exporter.start(12345) is srv         # already running wins
    exporter.stop()
    assert exporter.active() is None


# ---------------------------------------------------------------------------
# flight recorder rank tagging + skew table in dumps
# ---------------------------------------------------------------------------

def test_flight_dump_is_rank_tagged(tmp_path):
    from paddle_tpu.monitor import flight_recorder

    fr = flight_recorder.get()
    fr.note_step()
    _feed_rows([[0.0, 700.0]] * 2)
    path = fr.dump("test", directory=str(tmp_path))
    tag = monitor.rank_tag()
    assert os.path.basename(path) == (
        f"flight_{tag['host']}_p{tag['process_index']}_{os.getpid()}"
        ".jsonl")
    recs = read_jsonl(path)
    meta = recs[0]
    assert meta["kind"] == "meta"
    assert meta["host"] == tag["host"]
    assert meta["process_index"] == tag["process_index"]
    skews = [r for r in recs if r["kind"] == "fleet_skew"]
    assert skews and skews[0]["straggler"]["dp_index"] == 0
    fr.clear()


# ---------------------------------------------------------------------------
# trace metadata + fleet merge tools
# ---------------------------------------------------------------------------

def test_trace_process_metadata_carries_rank():
    events = monitor.merged_trace_events([])
    procs = [e for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"]
    assert procs
    for e in procs:
        assert e["args"]["process_index"] == jax.process_index()
        assert e["args"]["host"] == monitor.rank_tag()["host"]


def test_parse_xplane_fleet_merge(tmp_path):
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.parse_xplane import merge_fleet_traces

    def trace(rank, host, ts0):
        return [
            {"name": "process_name", "ph": "M", "pid": 1,
             "args": {"name": "train steps", "host": host,
                      "process_index": rank}},
            {"name": "step", "ph": "X", "ts": ts0, "dur": 5.0,
             "pid": 1, "tid": 0},
            {"name": "examples/s", "ph": "C", "ts": ts0 + 5,
             "pid": 1, "args": {"examples/s": 100 + rank}},
        ]

    for r, host, ts0 in ((0, "hostA", 1000.0), (1, "hostB", 5000.0)):
        with open(tmp_path / f"r{r}.trace.json", "w") as f:
            json.dump({"traceEvents": trace(r, host, ts0)}, f)
    merged = merge_fleet_traces(
        [str(tmp_path / "r0.trace.json"),
         str(tmp_path / "r1.trace.json")])
    from tools.parse_xplane import _PID_STRIDE

    pids = {e["pid"] for e in merged if "pid" in e}
    # rank-major remap: rank*_PID_STRIDE + pid, stride above pid_max
    assert pids == {_PID_STRIDE + 1, 1}
    assert _PID_STRIDE > (1 << 22)
    names = {e["args"]["name"] for e in merged
             if e.get("ph") == "M"}
    assert names == {"rank0@hostA:train steps", "rank1@hostB:train steps"}
    # each trace aligned to its own window start
    steps = sorted(e["ts"] for e in merged if e.get("ph") == "X")
    assert steps == [0.0, 0.0]
    counters = {e["name"] for e in merged if e.get("ph") == "C"}
    assert counters == {"rank0@hostA:examples/s",
                        "rank1@hostB:examples/s"}


def test_telemetry_report_fleet_merge(tmp_path):
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.telemetry_report import fleet_merge, summarize_fleet

    for r in (0, 1):
        with open(tmp_path / f"telemetry_r{r}.jsonl", "w") as f:
            for i in range(3):
                f.write(json.dumps({
                    "kind": "step", "step": i + 1, "ts_us": i * 1e4,
                    "step_time_s": 0.01 * (r + 1),
                    "host_dispatch_us": 100.0 + r,
                    "host": "hostX", "process_index": r}) + "\n")
            if r == 0:
                f.write(json.dumps({
                    "kind": "fleet_skew", "steps": 3,
                    "max_skew_us": 9000.0,
                    "straggler": {"dp_index": 1, "process_index": 1},
                    "ranks": [{"dp_index": 0, "process_index": 0,
                               "wait_us_mean": 9000.0},
                              {"dp_index": 1, "process_index": 1,
                               "wait_us_mean": 0.0}],
                    "host": "hostX", "process_index": 0}) + "\n")
    by_rank, merged = fleet_merge(
        [str(tmp_path / "telemetry_r0.jsonl"),
         str(tmp_path / "telemetry_r1.jsonl")])
    assert set(by_rank) == {"hostX:p0", "hostX:p1"}
    s = summarize_fleet(by_rank, merged)
    assert s["ranks"] == 2
    assert s["by_rank"]["hostX:p0"]["host_dispatch_us"]["mean"] == 100.0
    assert s["by_rank"]["hostX:p1"]["host_dispatch_us"]["mean"] == 101.0
    # the wall-clock straggler call from the per-rank streams...
    assert s["step_time_straggler"]["rank"] == "hostX:p1"
    # ...and the probe's own table, riding the merged stream
    assert s["fleet_skew"]["straggler"]["process_index"] == 1


# ---------------------------------------------------------------------------
# two real processes, one of them slow
# ---------------------------------------------------------------------------

def test_two_processes_name_their_straggler(tmp_path):
    """A REAL 2-process CPU-mesh dp train through the public Executor
    path (tests/dist_worker_fleet.py), rank 1 stalled on every step by
    `faultinject.stall_point("executor.step")`.  One test for the whole
    run: a failure names every thing the two ranks wrote down that did
    not hold."""
    from paddle_tpu.distributed.launch import _wait, start_procs

    steps = 12
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = str(tmp_path / "out.json")
    log_dir = str(tmp_path / "logs")
    procs, logs = start_procs(
        node_ips=["127.0.0.1"], node_ip="127.0.0.1", nproc_per_node=2,
        training_script=os.path.join(repo, "tests",
                                     "dist_worker_fleet.py"),
        script_args=(out, "0.08", str(steps)), log_dir=log_dir,
        env_extra={"PYTHONPATH": repo + os.pathsep
                   + os.environ.get("PYTHONPATH", ""),
                   "PADDLE_RENDEZVOUS_TIMEOUT": "60"})
    deadline = time.time() + 240
    while time.time() < deadline and any(p.poll() is None for p in procs):
        time.sleep(0.5)
    for p in procs:
        if p.poll() is None:
            p.kill()
    rc = _wait(procs, logs)
    assert rc == 0, "\n".join(
        f"--- {name}:\n" + open(os.path.join(log_dir, name)).read()[-2000:]
        for name in sorted(os.listdir(log_dir)))
    results = {}
    for r in (0, 1):
        with open(f"{out}.r{r}") as f:
            results[r] = json.load(f)
    r0 = results[0]
    checks = {}

    # the straggler is named, on BOTH ranks' own tables
    for r in (0, 1):
        st = (results[r]["table"] or {}).get("straggler") or {}
        checks[f"straggler_named_r{r}"] = (
            st.get("dp_index") == 1 and st.get("process_index") == 1)

    # the rolling table recomputes EXACTLY from the raw per-step wait
    # vectors, with the formulas and rounding monitor.fleet uses
    rows = (r0.get("rows") or [])
    checks["rows_complete"] = len(rows) == steps
    rows = rows[-r0["window"]:]
    times = [r["step_time_s"] for r in rows
             if (r.get("step_time_s") or 0) > 0]
    mean_step_us = sum(times) / len(times) * 1e6 if times else None
    recomputed = []
    for i in range(2):
        waits = [r["waits_us"][i] for r in rows]
        behind = [max(r["waits_us"]) - r["waits_us"][i] for r in rows]
        row = {"wait_us_mean": round(sum(waits) / len(waits), 1),
               "behind_us_mean": round(sum(behind) / len(behind), 1)}
        if mean_step_us:
            row["wait_frac"] = round(
                sum(waits) / len(waits) / mean_step_us, 4)
            row["straggler_score"] = round(
                sum(behind) / len(behind) / mean_step_us, 4)
        recomputed.append(row)
    table_ranks = (r0.get("table") or {}).get("ranks") or []
    checks["wait_frac_recomputed_exactly"] = (
        len(table_ranks) == 2 and all(
            all(trow.get(k) == rrow[k] for k in rrow)
            for trow, rrow in zip(table_ranks, recomputed)))

    # rank 0's live /metrics scrape against its own snapshot()
    parsed = (r0.get("metrics") or {}).get("parsed") or {}
    checks["metrics_scrape_parses"] = len(parsed) > 0

    def prom(name, kind=None):
        return exporter.metric_key(exporter.exported_name(name, kind))

    counters = r0.get("snapshot_counters") or {}
    checks["scrape_matches_snapshot"] = bool(counters) and all(
        parsed.get(prom(n, "counter")) == float(v)
        for n, v in counters.items()) and all(
        parsed.get(prom(n)) == float(v)
        for n, v in (r0.get("snapshot_gauges") or {}).items())
    health = (r0.get("metrics") or {}).get("health") or {}
    checks["healthz_ok"] = (health.get("ok") is True
                            and health.get("status") == 200)

    # the rank-tagged streams merge with the right attribution
    sys.path.insert(0, repo)
    try:
        from tools.telemetry_report import fleet_merge, summarize_fleet
    finally:
        sys.path.pop(0)
    tdir = str(tmp_path / "telemetry")
    by_rank, merged = fleet_merge(sorted(
        os.path.join(tdir, p) for p in os.listdir(tdir)
        if p.endswith(".jsonl")))
    fsum = summarize_fleet(by_rank, merged)
    checks["fleet_merge_two_ranks"] = fsum.get("ranks") == 2
    checks["fleet_merge_names_straggler"] = (
        ((fsum.get("fleet_skew") or {}).get("straggler") or {})
        .get("process_index") == 1)
    assert not [name for name, held in checks.items() if not held], checks
