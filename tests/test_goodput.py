"""Goodput ledger tests (ISSUE 20): the wall-clock attribution ledger's
exact-sum-by-construction accounting (fake-clock units: nesting,
retag, reclassify, thread affinity, finish idempotence, flight
snapshots), the executor integration through the PUBLIC
train_from_dataset (kind="goodput" record, categories summing EXACTLY
to wall, fraction re-derivation), the FLAGS_goodput=off pin (no ledger
object ever exists and the numerics are byte-for-byte those of a run
that never heard of the ledger), the reader.prefetch_depth gauge
satellite, and the record's ride through every surface: JSONL round
trip, monitor snapshot, flight dump, /metrics families, and the
telemetry_report goodput section (single stream and --fleet merge).
"""

import glob
import json
import os
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, resilience
from paddle_tpu.monitor import goodput
from paddle_tpu.monitor.goodput import (BADPUT_CATEGORIES, CATEGORIES,
                                        GoodputLedger, compute_fractions)

def _report_mod():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "telemetry_report.py")
    spec = importlib.util.spec_from_file_location("telemetry_report",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_state():
    resilience.faultinject.disarm()
    monitor.disable()
    monitor.reset()
    led = goodput.active()
    if led is not None:
        goodput.abandon(led)
    old = fluid.get_flags("FLAGS_goodput")
    yield
    resilience.faultinject.disarm()
    led = goodput.active()
    if led is not None:
        goodput.abandon(led)
    fluid.set_flags(old)
    monitor.disable()
    monitor.reset()


class FakeClock:
    """Deterministic ns clock: tests advance it by hand, so every
    bucket value is asserted exactly — no sleeps, no tolerance."""

    def __init__(self):
        self.now = 1_000

    def __call__(self):
        return self.now

    def tick(self, ns):
        self.now += ns


# ---------------------------------------------------------------------
# ledger units (fake clock: every number exact)
# ---------------------------------------------------------------------

def test_partition_is_exact_and_exhaustive():
    clk = FakeClock()
    led = GoodputLedger(key="unit", clock=clk)
    clk.tick(5)                     # nothing open -> unattributed
    assert led.push("host_dispatch")
    clk.tick(10)
    assert led.push("compile")      # nested: innermost wins
    clk.tick(100)
    assert led.pop() == 100
    clk.tick(7)                     # back to host_dispatch
    led.pop()
    clk.tick(3)                     # unattributed again
    rec = led.finish()
    assert rec["wall_ns"] == 125
    assert rec["categories"] == {
        "productive_step": 0, "compile": 100, "data_wait": 0,
        "host_dispatch": 17, "checkpoint_save": 0, "recovery": 0,
        "elastic_transition": 0, "dp_sync_wait": 0, "unattributed": 8}
    assert sum(rec["categories"].values()) == rec["wall_ns"]
    assert set(rec["categories"]) == set(CATEGORIES)


def test_span_context_manager_reports_own_ns():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    with led.span("checkpoint_save") as sp:
        clk.tick(42)
    assert sp.ns == 42
    assert led.finish()["categories"]["checkpoint_save"] == 42


def test_retag_keeps_past_charge_and_relabels_future():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    led.push("host_dispatch")
    clk.tick(30)                    # still host_dispatch
    assert led.retag("compile")
    clk.tick(50)                    # now compile
    led.pop()
    cats = led.finish()["categories"]
    assert cats["host_dispatch"] == 30
    assert cats["compile"] == 50


def test_reclassify_clamps_and_preserves_sum():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    with led.span("productive_step"):
        clk.tick(100)
    assert led.reclassify("productive_step", "recovery", 40) == 40
    # clamp: only 60 remain in the source bucket
    assert led.reclassify("productive_step", "recovery", 10 ** 9) == 60
    assert led.reclassify("productive_step", "recovery", 5) == 0
    assert led.reclassify("nope", "recovery", 5) == 0
    rec = led.finish()
    assert rec["categories"]["recovery"] == 100
    assert rec["categories"]["productive_step"] == 0
    assert sum(rec["categories"].values()) == rec["wall_ns"]


def test_fold_dp_sync_moves_mean_wait_times_steps():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    with led.span("productive_step"):
        clk.tick(10_000_000)
    moved = led.fold_dp_sync({
        "steps": 4,
        "ranks": [{"wait_us_mean": 100.0}, {"wait_us_mean": 300.0}]})
    assert moved == 200 * 1000 * 4          # mean 200us * 4 steps
    cats = led.finish()["categories"]
    assert cats["dp_sync_wait"] == moved
    assert cats["productive_step"] == 10_000_000 - moved
    # empty / malformed tables are no-ops
    led2 = GoodputLedger(clock=FakeClock())
    assert led2.fold_dp_sync(None) == 0
    assert led2.fold_dp_sync({"ranks": [], "steps": 3}) == 0


def test_other_threads_cannot_mutate_the_ledger():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    results = {}

    def attack():
        results["push"] = led.push("recovery")
        results["pop"] = led.pop()
        results["retag"] = led.retag("compile")

    t = threading.Thread(target=attack)
    t.start()
    t.join()
    assert results == {"push": False, "pop": 0, "retag": False}
    clk.tick(9)
    rec = led.finish()
    assert rec["categories"]["recovery"] == 0
    assert rec["categories"]["unattributed"] == 9


def test_finish_is_idempotent_and_owner_only():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    clk.tick(5)
    rec = led.finish()
    clk.tick(50)
    assert led.finish() is rec              # repeat returns same record
    assert led.wall_ns() == 5               # frozen at finish time
    # a different thread may not finish an UNfinished ledger
    led2 = GoodputLedger(clock=FakeClock())
    err = {}

    def finisher():
        try:
            led2.finish()
        except RuntimeError as e:
            err["e"] = e

    t = threading.Thread(target=finisher)
    t.start()
    t.join()
    assert "e" in err


def test_flight_record_charges_pending_without_mutating():
    clk = FakeClock()
    led = GoodputLedger(key="fr", clock=clk)
    led.push("compile")
    clk.tick(70)
    snap = led.flight_record()
    assert snap["in_flight"] is True
    assert snap["categories"]["compile"] == 70
    assert sum(snap["categories"].values()) == snap["wall_ns"] == 70
    # the snapshot did NOT book the pending time into the ledger
    clk.tick(30)
    led.pop()
    assert led.finish()["categories"]["compile"] == 100


def test_compute_fractions_rederives_with_equality():
    clk = FakeClock()
    led = GoodputLedger(clock=clk)
    with led.span("productive_step"):
        clk.tick(61)
    with led.span("recovery"):
        clk.tick(39)
    rec = led.finish()
    frac = compute_fractions(rec)
    assert frac["goodput_fraction"] == rec["goodput_fraction"] == 0.61
    assert frac["badput_fraction"] == rec["badput_fraction"]
    assert compute_fractions({"wall_ns": 0, "categories": {}}) == {
        "goodput_fraction": 0.0, "badput_fraction": 0.0}


def test_badput_categories_are_everything_but_productive():
    assert "productive_step" not in BADPUT_CATEGORIES
    assert set(BADPUT_CATEGORIES) | {"productive_step"} \
        == set(CATEGORIES)


def test_start_run_gates_on_flag_and_single_slot():
    fluid.set_flags({"FLAGS_goodput": False})
    assert goodput.start_run() is None          # flag off, no force
    led = goodput.start_run(key="a", force=True)
    assert led is not None and goodput.active() is led
    assert goodput.start_run(key="b", force=True) is None  # slot taken
    goodput.abandon(led)
    assert goodput.active() is None


def test_retry_backoff_lands_in_recovery_bucket():
    from paddle_tpu.resilience.retry import RetryPolicy, call_with_retry
    led = goodput.start_run(key="retry", force=True)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise resilience.faultinject.InjectedTransientError(
                "injected: RESOURCE_EXHAUSTED: synthetic")
        return "ok"

    policy = RetryPolicy(max_retries=2, base_delay=0.01, jitter=0.0,
                         seed=0)
    assert call_with_retry(flaky, policy=policy) == "ok"
    rec = goodput.finish_run(led)
    assert rec["categories"]["recovery"] >= int(0.01 * 1e9)
    assert sum(rec["categories"].values()) == rec["wall_ns"]
    # finish_run retained the record even though telemetry was never
    # enabled: dropping a whole run's attribution because enable()
    # wasn't called would be a silent loss (the retained copy carries
    # the stream stamps on top of the ledger's fields)
    kept = monitor.goodput_records()[-1]
    assert kept["key"] == "retry"
    assert kept["categories"] == rec["categories"]
    assert "wall_time" in kept


# ---------------------------------------------------------------------
# executor integration: train_from_dataset end to end
# ---------------------------------------------------------------------

def _mlp(seed_dim=6):
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, seed_dim])
            y = fluid.data("y", [None, 1])
            h = fluid.layers.fc(x, 8, act="relu")
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _batches(n=4, rows=8, dim=6, seed=0):
    rng = np.random.default_rng(seed)
    return [{"x": rng.standard_normal((rows, dim)).astype(np.float32),
             "y": rng.standard_normal((rows, 1)).astype(np.float32)}
            for _ in range(n)]


def _train(main, startup, loss, batches, **kw):
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    out = exe.train_from_dataset(main, batches, scope=sc,
                                 fetch_list=[loss],
                                 print_period=10 ** 6, **kw)
    w = np.asarray(sc.find_var("fc_0.w_0"))
    return out, w


def test_train_from_dataset_emits_exact_record():
    fluid.set_flags({"FLAGS_goodput": True})
    main, startup, loss = _mlp()
    batches = _batches()
    _train(main, startup, loss, batches, prefetch=False)
    recs = monitor.goodput_records()
    assert len(recs) == 1
    rec = recs[-1]
    assert rec["kind"] == "goodput"
    assert rec["steps"] == len(batches)
    assert rec["outcome"] == "ok"
    assert sum(rec["categories"].values()) == rec["wall_ns"]
    assert rec["categories"]["compile"] > 0        # first invocation
    assert rec["categories"]["host_dispatch"] > 0
    frac = compute_fractions(rec)
    assert frac["goodput_fraction"] == rec["goodput_fraction"]
    assert goodput.active() is None                # slot released


@pytest.fixture(scope="module")
def injected_badput(tmp_path_factory):
    """One ledgered `train_from_dataset` on the dp mesh with badput of
    known length injected: a data stall at `reader.prepare`
    (prefetch=False, so it lands on the consumer thread), one transient
    under a fixed jitter-free backoff, and a stall inside the
    checkpoint save.  Run once; a case below for each place a delay
    must land.  A sleep lasts at least as long as asked, so each bucket
    is held to its injection from below; how much genuine work shares
    it is not a CPU run's to say."""
    import jax

    from paddle_tpu.checkpoint import CheckpointManager

    steps, batch = 8, 16
    stall_s, backoff_s, ck_stall_s = 0.06, 0.04, 0.10
    old_flag = fluid.get_flags("FLAGS_goodput")
    monitor.reset()
    monitor.enable()
    fluid.set_flags({"FLAGS_goodput": True})
    try:
        main, startup, loss = _mlp(seed_dim=16)
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=len(jax.devices()))
        mgr = CheckpointManager(
            str(tmp_path_factory.mktemp("goodput_ckpt")),
            save_interval_steps=6)
        exe = fluid.Executor()
        sc = fluid.Scope()
        exe.run(startup, scope=sc)
        resilience.enable_retry(resilience.RetryPolicy(
            max_retries=3, base_delay=backoff_s, jitter=0.0, seed=0))
        with resilience.plan_scope(
                transient_at_step=5, transient_times=1,
                stall_points={"reader.prepare": (3, stall_s),
                              "checkpoint.save": ck_stall_s}):
            exe.train_from_dataset(
                prog, _batches(steps, rows=batch, dim=16), scope=sc,
                fetch_list=[loss], checkpoint=mgr,
                print_period=10 ** 6, prefetch=False)
            fired = dict(resilience.faultinject.active_plan().fired)
        rec = monitor.goodput_records()[-1]
        cats, wall = rec["categories"], rec["wall_ns"]
        return {
            "injections_fired": fired.get("transient") == 1
            and fired.get("stall") == 2,
            "sum_exact": wall > 0 and sum(cats.values()) == wall,
            "unattributed_le_1pct": cats["unattributed"] <= 0.01 * wall,
            "data_stall_in_data_wait": cats["data_wait"] >= stall_s * 1e9,
            "retry_backoff_in_recovery":
                cats["recovery"] >= backoff_s * 1e9,
            "checkpoint_stall_in_checkpoint_save":
                cats["checkpoint_save"] >= ck_stall_s * 1e9,
            "steps_counted": rec["steps"] == steps,
        }
    finally:
        resilience.disable_retry()
        resilience.faultinject.disarm()
        fluid.set_flags(old_flag)
        monitor.disable()
        monitor.reset()


@pytest.mark.parametrize("check", [
    "injections_fired", "sum_exact", "unattributed_le_1pct",
    "data_stall_in_data_wait", "retry_backoff_in_recovery",
    "checkpoint_stall_in_checkpoint_save", "steps_counted"])
def test_injected_badput_lands_in_its_own_category(injected_badput, check):
    assert injected_badput[check], injected_badput


def test_flag_off_is_byte_for_byte_never_ledgered():
    """The FLAGS_goodput=off pin (FLAGS_static_check=off style): the
    off path creates NO ledger, emits NO record, and its numerics are
    bitwise those of the instrumented path — the wrapper split must
    not perturb the run."""
    main, startup, loss = _mlp()
    batches = _batches()
    fluid.set_flags({"FLAGS_goodput": False})
    out_off, w_off = _train(main, startup, loss, batches,
                            prefetch=False)
    assert monitor.goodput_records() == []         # never ledgered
    assert goodput.active() is None
    # same program over a FRESH scope with the ledger on: identical
    # numerics, record present
    fluid.set_flags({"FLAGS_goodput": True})
    out_on, w_on = _train(main, startup, loss, batches, prefetch=False)
    assert len(monitor.goodput_records()) == 1
    np.testing.assert_array_equal(w_off, w_on)
    np.testing.assert_array_equal(np.asarray(out_off[0]),
                                  np.asarray(out_on[0]))


def test_nested_run_joins_outer_ledger_single_record():
    """An Executor.run issued while a run ledger is open must NOT try
    to own the wall clock — one run, one record."""
    fluid.set_flags({"FLAGS_goodput": True})
    main, startup, loss = _mlp()
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    led = goodput.start_run(key="outer")
    assert led is not None
    feed = _batches(1)[0]
    exe.run(main, feed=feed, fetch_list=[loss], scope=sc)
    rec = goodput.finish_run(led)
    assert rec["key"] == "outer"
    assert len(monitor.goodput_records()) == 1
    assert sum(rec["categories"].values()) == rec["wall_ns"]
    # the inner run's dispatch was charged onto the OUTER ledger
    assert rec["categories"]["host_dispatch"] \
        + rec["categories"]["compile"] > 0


def test_guard_skip_reclassifies_into_recovery():
    fluid.set_flags({"FLAGS_goodput": True})
    main, startup, loss = _mlp()
    batches = _batches(4)
    exe = fluid.Executor()
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    resilience.enable_anomaly_guard(policy="skip_step")
    try:
        with resilience.plan_scope(nan_at_steps=[1]):
            exe.train_from_dataset(main, batches, scope=sc,
                                   fetch_list=[loss],
                                   print_period=10 ** 6,
                                   prefetch=False)
    finally:
        resilience.disable_anomaly_guard()
    rec = monitor.goodput_records()[-1]
    assert rec["categories"]["recovery"] > 0
    assert sum(rec["categories"].values()) == rec["wall_ns"]


def test_prefetch_depth_gauge_visible_with_goodput_off():
    fluid.set_flags({"FLAGS_goodput": False})
    monitor.enable()
    main, startup, loss = _mlp()
    _train(main, startup, loss, _batches(), prefetch=True)
    snap = monitor.snapshot()
    assert "reader.prefetch_depth" in snap.get("gauges", {})


def test_snapshot_and_metrics_surfaces():
    fluid.set_flags({"FLAGS_goodput": True})
    monitor.enable()
    main, startup, loss = _mlp()
    _train(main, startup, loss, _batches(), prefetch=False)
    snap = monitor.snapshot()
    assert snap["goodput"]["kind"] == "goodput"
    assert snap["goodput"]["steps"] == 4
    gauges = snap.get("gauges", {})
    assert gauges.get("goodput.fraction") is not None
    assert gauges.get("goodput.wall_s") > 0
    counters = snap.get("counters", {})
    assert counters.get("goodput.productive_ns", 0) > 0
    badput_ns = [k for k in counters
                 if k.startswith("badput.") and k.endswith("_ns")]
    assert badput_ns                        # at least compile fired
    # the registry rides /metrics wholesale: goodput gauges and
    # per-category badput counters are scrape-visible
    from paddle_tpu.monitor import exporter
    text = exporter.prometheus_text()
    assert "paddle_tpu_goodput_fraction" in text
    assert "paddle_tpu_badput_compile_ns" in text
    # in-flight ledgers surface too (crash-hook view)
    led = goodput.start_run(key="inflight", force=True)
    snap2 = monitor.snapshot()
    assert snap2["goodput"]["in_flight"] is True
    goodput.abandon(led)


def test_flight_dump_carries_goodput_lines(tmp_path):
    fluid.set_flags({"FLAGS_goodput": True,
                     "FLAGS_flight_recorder_dir": str(tmp_path)})
    monitor.enable()
    monitor.flight_recorder.get().clear()
    main, startup, loss = _mlp()
    _train(main, startup, loss, _batches(), prefetch=False)
    # an ACTIVE ledger at dump time rides along as in_flight
    led = goodput.start_run(key="mid_crash", force=True)
    monitor.flight_recorder.dump("test_goodput")
    goodput.abandon(led)
    path = monitor.flight_recorder.get().last_dump
    assert path and os.path.exists(path)
    lines = [json.loads(ln) for ln in open(path)
             if ln.strip() and ln.strip().startswith("{")]
    gp = [r for r in lines if r.get("kind") == "goodput"]
    assert any(not r.get("in_flight") for r in gp)     # finished run
    assert any(r.get("in_flight") and r.get("key") == "mid_crash"
               for r in gp)


# ---------------------------------------------------------------------
# report surfaces: JSONL round trip, goodput section, fleet merge
# ---------------------------------------------------------------------

def test_jsonl_roundtrip_and_report_section(tmp_path):
    from paddle_tpu.monitor.jsonl_writer import read_jsonl

    fluid.set_flags({"FLAGS_goodput": True})
    stream = str(tmp_path / "telemetry.jsonl")
    monitor.enable(jsonl_path=stream)
    main, startup, loss = _mlp()
    _train(main, startup, loss, _batches(), prefetch=False)
    monitor.disable()
    records = read_jsonl(stream)
    gp = [r for r in records if r.get("kind") == "goodput"]
    assert len(gp) == 1
    rec = gp[0]
    # integer-ns exactness survives the serialization round trip
    assert sum(rec["categories"].values()) == rec["wall_ns"]
    assert compute_fractions(rec)["goodput_fraction"] \
        == rec["goodput_fraction"]
    tr = _report_mod()
    out = tr.summarize(records)
    sec = out["goodput"]
    assert sec["runs"] == 1
    run = list(sec["by_run"].values())[0]
    assert "SUM_MISMATCH_NS" not in run
    assert "FRACTION_MISMATCH" not in run
    assert run["steps"] == 4
    assert run["categories"]        # nonzero buckets rendered
    assert 0.0 <= run["goodput_pct"] <= 100.0
    assert run.get("top_badput") in BADPUT_CATEGORIES


def test_report_flags_violated_invariants():
    tr = _report_mod()
    lossy = {"kind": "goodput", "key": "k", "wall_ns": 1000,
             "steps": 1, "goodput_fraction": 0.9,
             "categories": {"productive_step": 500,
                            "unattributed": 400}}
    out = tr.summarize([lossy])
    run = out["goodput"]["by_run"]["k"]
    assert run["SUM_MISMATCH_NS"] == -100
    assert run["FRACTION_MISMATCH"] is True
    # in-flight snapshots are exempt (their sum is an estimate)
    inflight = dict(lossy, in_flight=True)
    run2 = tr.summarize([inflight])["goodput"]["by_run"]["k"]
    assert "SUM_MISMATCH_NS" not in run2


def test_fleet_merge_reports_per_rank_and_fleet_goodput(tmp_path):
    tr = _report_mod()

    def stream(path, host, wall, productive, key="train"):
        cats = {c: 0 for c in CATEGORIES}
        cats["productive_step"] = productive
        cats["compile"] = wall - productive
        rec = {"kind": "goodput", "key": key, "wall_ns": wall,
               "steps": 2, "categories": cats,
               "goodput_fraction": productive / wall,
               "host": host, "process_index": 0,
               "wall_time": 100.0}
        step = {"kind": "step", "steps": 2, "step_time_s": 0.01,
                "ts_us": 0, "host": host, "process_index": 0}
        with open(path, "w") as f:
            f.write(json.dumps(step) + "\n")
            f.write(json.dumps(rec) + "\n")

    stream(str(tmp_path / "a.jsonl"), "hostA", 1_000_000, 800_000)
    stream(str(tmp_path / "b.jsonl"), "hostB", 1_000_000, 600_000)
    by_rank, merged = tr.fleet_merge(
        sorted(glob.glob(str(tmp_path / "*.jsonl"))))
    out = tr.summarize_fleet(by_rank, merged)
    assert out["fleet_goodput_pct"] == 70.0
    rows = out["by_rank"]
    assert rows["hostA:p0"]["goodput"]["goodput_pct"] == 80.0
    assert rows["hostB:p0"]["goodput"]["goodput_pct"] == 60.0
    assert rows["hostB:p0"]["goodput"]["top_badput"] == "compile"
