"""Executor steady-state dispatch fast path (ISSUE 2): run-plan cache
hits skip per-call program analysis, invalidation is sound, fetches can
stay on device, and train_from_dataset performs no host sync between
print_period boundaries.

Parity model: the reference keeps its hot loop fast by doing feed/fetch
analysis once (executor.py:236/274 pruning) and overlapping host work
with the device (buffered_reader.cc); these tests pin the TPU-native
analogues.
"""

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
import paddle_tpu.framework.executor as executor_mod
from paddle_tpu import layers


def _scale_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [None, 4])
        out = fluid.layers.scale(x, scale=3.0, bias=1.0)
    return main, startup, out


def _train_program():
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            feat = fluid.data("feat", [None, 3])
            label = fluid.data("label", [None, 1])
            h = fluid.layers.fc(feat, 8, act="relu")
            logit = fluid.layers.fc(h, 1)
            loss = layers.mean(
                layers.sigmoid_cross_entropy_with_logits(logit, label))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _batches(n=6, batch=16, seed=7):
    rng = np.random.default_rng(seed)
    return [{"feat": rng.normal(size=(batch, 3)).astype(np.float32),
             "label": rng.integers(0, 2, (batch, 1)).astype(np.float32)}
            for _ in range(n)]


# ---------------------------------------------------------------------------
# run-plan cache
# ---------------------------------------------------------------------------

def test_cached_hit_skips_listvars_and_repruning(monkeypatch):
    """Acceptance: a cached-hit Executor.run performs no per-call
    list_vars() scan and no live-op re-pruning."""
    main, startup, out = _scale_program()
    exe = fluid.Executor()
    xb = np.random.rand(2, 4).astype(np.float32)
    r1 = exe.run(main, feed={"x": xb}, fetch_list=[out])  # warm both caches

    calls = {"list_vars": 0, "live_ops": 0}
    orig_lv = fluid.Program.list_vars
    orig_lo = fluid.Executor._live_ops

    def counting_lv(self):
        calls["list_vars"] += 1
        return orig_lv(self)

    def counting_lo(program, fetch_names):
        calls["live_ops"] += 1
        return orig_lo(program, fetch_names)

    monkeypatch.setattr(fluid.Program, "list_vars", counting_lv)
    monkeypatch.setattr(fluid.Executor, "_live_ops",
                        staticmethod(counting_lo))
    r2 = exe.run(main, feed={"x": xb}, fetch_list=[out])
    assert calls == {"list_vars": 0, "live_ops": 0}
    np.testing.assert_allclose(r2[0], r1[0])


def test_program_mutation_bumps_version_and_rebuilds_plan():
    main, startup, out = _scale_program()
    exe = fluid.Executor()
    xb = np.random.rand(2, 4).astype(np.float32)
    exe.run(main, feed={"x": xb}, fetch_list=[out])
    plan1 = main._run_plan_cache
    assert plan1 is not None and plan1.version == main._version

    with fluid.program_guard(main, startup):
        x = main.global_block().var("x")
        out2 = fluid.layers.scale(x, scale=2.0)
    assert main._version > plan1.version  # mutation bumped

    r = exe.run(main, feed={"x": xb}, fetch_list=[out2])
    plan2 = main._run_plan_cache
    assert plan2 is not plan1 and plan2.version == main._version
    np.testing.assert_allclose(r[0], 2 * xb, rtol=1e-6)


def test_persistable_toggle_invalidates_plan():
    """Flipping a var's persistable flag after a run (a plain attribute
    write, the idiom layers use) must invalidate the cached plan: the
    var joins the persist set and survives into the scope."""
    main, startup, out = _scale_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    xb = np.ones((2, 4), np.float32)
    exe.run(main, feed={"x": xb}, fetch_list=[out], scope=scope)
    assert scope.find_var(out.name) is None        # not persistable yet

    main.global_block().var(out.name).persistable = True
    exe.run(main, feed={"x": xb}, fetch_list=[out], scope=scope)
    saved = scope.find_var(out.name)
    assert saved is not None
    np.testing.assert_allclose(np.asarray(saved), 3 * xb + 1, rtol=1e-6)


def test_use_program_cache_false_bypasses_both_caches():
    main, startup, out = _scale_program()
    exe = fluid.Executor()
    xb = np.ones((2, 4), np.float32)
    r = exe.run(main, feed={"x": xb}, fetch_list=[out],
                use_program_cache=False)
    np.testing.assert_allclose(r[0], 3 * xb + 1, rtol=1e-6)
    assert main._run_plan_cache is None      # plan never stored
    assert exe._cache == {}                  # compiled fn never stored

    # and a warmed cache is not READ either: a stale-but-valid-looking
    # plan must not shield a mutated analysis from a bypassing call
    exe.run(main, feed={"x": xb}, fetch_list=[out])
    plan = main._run_plan_cache
    exe.run(main, feed={"x": xb}, fetch_list=[out], use_program_cache=False)
    assert main._run_plan_cache is plan      # untouched, not replaced


def test_foreign_plan_is_never_served():
    """The id()-collision guard: a plan whose .program is a DIFFERENT
    Program object (the same-address-after-GC scenario) is rebuilt, not
    served."""
    p1, s1, out1 = _scale_program()
    exe = fluid.Executor()
    xb = np.random.rand(2, 4).astype(np.float32)
    exe.run(p1, feed={"x": xb}, fetch_list=[out1])
    stale = p1._run_plan_cache

    p2, s2, out2 = _scale_program()
    p2._run_plan_cache = stale               # simulate recycled identity
    p2._version = stale.version              # even versions colliding
    r = exe.run(p2, feed={"x": xb}, fetch_list=[out2])
    np.testing.assert_allclose(r[0], 3 * xb + 1, rtol=1e-6)
    assert p2._run_plan_cache is not stale
    assert p2._run_plan_cache.program is p2


# ---------------------------------------------------------------------------
# non-blocking fetches + device-side feed casts
# ---------------------------------------------------------------------------

def test_return_numpy_false_returns_device_arrays_with_parity():
    main, startup, out = _scale_program()
    exe = fluid.Executor()
    xb = np.random.rand(3, 4).astype(np.float32)
    r_block = exe.run(main, feed={"x": xb}, fetch_list=[out])
    r_async = exe.run(main, feed={"x": xb}, fetch_list=[out],
                      return_numpy=False)
    assert isinstance(r_async[0], jax.Array)
    np.testing.assert_array_equal(np.asarray(r_async[0]), r_block[0])


def test_device_resident_feed_cast_happens_in_step():
    """An already-on-device feed with a mismatched dtype is NOT cast on
    the dispatch path (no host astype, no separate cast dispatch); the
    compiled step casts it, with identical numerics."""
    main, startup, out = _scale_program()
    exe = fluid.Executor()
    xi = np.arange(8).reshape(2, 4).astype(np.int32)

    casts = []
    orig_build = fluid.Executor._build

    def spy_build(self, program, fetch_names, persist_names, **kw):
        casts.append(dict(kw.get("feed_casts") or {}))
        return orig_build(self, program, fetch_names, persist_names, **kw)

    fluid.Executor._build = spy_build
    try:
        r_dev = exe.run(main, feed={"x": jax.device_put(xi)},
                        fetch_list=[out])
    finally:
        fluid.Executor._build = orig_build
    assert casts and "x" in casts[-1]        # cast staged into the step
    r_host = exe.run(main, feed={"x": xi.astype(np.float32)},
                     fetch_list=[out])
    assert r_dev[0].dtype == np.float32
    np.testing.assert_allclose(r_dev[0], r_host[0], rtol=1e-6)


def test_eager_executor_casts_device_feed_too():
    main, startup, out = _scale_program()
    exe = fluid.Executor()
    xi = jax.device_put(np.arange(8).reshape(2, 4).astype(np.int32))
    fluid.set_flags({"FLAGS_eager_executor": True})
    try:
        r = exe.run(main, feed={"x": xi}, fetch_list=[out])
    finally:
        fluid.set_flags({"FLAGS_eager_executor": False})
    np.testing.assert_allclose(
        r[0], 3 * np.arange(8).reshape(2, 4).astype(np.float32) + 1)


def test_persist_var_fetch_is_decoupled_from_donated_state():
    """A device fetch (return_numpy=False) of a persistable var must NOT
    alias the scope-bound state buffer: the next run donates that buffer
    and would invalidate the still-held fetch.  The executor decouples
    it with a device-side copy, so the old fetch survives later steps
    with its pre-update value."""
    main, startup, loss = _train_program()
    pname = main.all_parameters()[0].name
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    b = _batches(2)
    out = exe.run(main, feed=b[0], fetch_list=[loss, pname], scope=scope,
                  return_numpy=False)
    fetched_param = out[1]
    assert fetched_param is not scope.find_var(pname)   # decoupled
    before = np.asarray(fetched_param)
    exe.run(main, feed=b[1], fetch_list=[loss], scope=scope,
            return_numpy=False)                          # donates state
    np.testing.assert_array_equal(np.asarray(fetched_param), before)
    assert not np.allclose(before, np.asarray(scope.find_var(pname)))


# ---------------------------------------------------------------------------
# train_from_dataset no-sync steady state
# ---------------------------------------------------------------------------

def _count_materialize(monkeypatch):
    calls = []
    real = executor_mod._materialize

    def counting(fetches):
        calls.append(len(fetches))
        return real(fetches)

    monkeypatch.setattr(executor_mod, "_materialize", counting)
    return calls


def test_train_from_dataset_syncs_only_on_final_batch(monkeypatch):
    main, startup, loss = _train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    calls = _count_materialize(monkeypatch)
    out = exe.train_from_dataset(main, _batches(6), scope=scope,
                                 fetch_list=[loss], print_period=100)
    # print_period never reached -> exactly ONE materialization (final)
    assert calls == [1]
    assert np.isfinite(float(np.asarray(out[0])))


def test_train_from_dataset_syncs_at_print_period_boundaries(
        monkeypatch, capsys):
    main, startup, loss = _train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    calls = _count_materialize(monkeypatch)
    exe.train_from_dataset(main, _batches(6), scope=scope,
                           fetch_list=[loss], print_period=3)
    # boundaries at steps 3 and 6, plus the final batch
    assert len(calls) == 3
    printed = capsys.readouterr().out
    assert printed.count("[train_from_dataset]") == 2


def test_train_from_dataset_deferred_fetches_match_blocking_loop():
    """Acceptance: deferred fetches are numerically identical to the
    pre-change blocking path (same program, same init, same batches,
    one exe.run per step in both).  The ISSUE-14 AMP/fusion train tier
    is pinned off: it applies to the dataset loop but not to a bare
    exe.run loop, and this test's contract is the fetch-deferral
    machinery, not the train tier's (documented) numerics change."""
    entry = fluid.get_flags(["FLAGS_amp", "FLAGS_graph_opt_fuse"])
    fluid.set_flags({"FLAGS_amp": "off",
                     "FLAGS_graph_opt_fuse": "off"})
    try:
        _deferred_matches_blocking()
    finally:
        fluid.set_flags(entry)


def _deferred_matches_blocking():
    batches = _batches(5)

    main, startup, loss = _train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    deferred = exe.train_from_dataset(main, batches, scope=scope,
                                      fetch_list=[loss], print_period=100)

    main2, startup2, loss2 = _train_program()
    exe2 = fluid.Executor()
    scope2 = fluid.Scope()
    exe2.run(startup2, scope=scope2)
    blocking = None
    for b in batches:
        blocking = exe2.run(main2, feed=b, fetch_list=[loss2],
                            scope=scope2)
    np.testing.assert_allclose(np.asarray(deferred[0]), blocking[0],
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# dispatch-path profiler spans
# ---------------------------------------------------------------------------

def test_dispatch_spans_only_recorded_while_profiling(tmp_path):
    from paddle_tpu import profiler

    main, startup, out = _scale_program()
    exe = fluid.Executor()
    xb = np.ones((2, 4), np.float32)
    exe.run(main, feed={"x": xb}, fetch_list=[out])

    profiler.reset_profiler()
    exe.run(main, feed={"x": xb}, fetch_list=[out])
    assert profiler._all_events() == []      # steady state: no events

    with profiler.profiler(state="CPU",
                           profile_path=str(tmp_path / "prof")):
        exe.run(main, feed={"x": xb}, fetch_list=[out])
    names = {e["name"] for e in profiler._all_events()}
    assert {"executor.run.prepare", "executor.run.dispatch",
            "executor.run.fetch"} <= names


# ---------------------------------------------------------------------------
# end to end: the dispatch modes of one train program, by what they count
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dispatch_scenario():
    """One small train program through the PUBLIC Executor.run on
    device-resident feeds, in the three steady states an operator can
    be in: the run plan rebuilt every call (dropped by hand between
    calls), both caches hot with fetches left on the device, and both
    hot with every fetch brought to the host.  A CPU run says nothing
    of their times; it does say which cache each call hit and where
    each fetch lives, from the monitor's counters."""
    from paddle_tpu import monitor

    steps = 12
    main, startup, loss = _train_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {k: jax.device_put(v) for k, v in _batches(n=1)[0].items()}

    def run_once(return_numpy=False):
        return exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                       return_numpy=return_numpy)[0]

    def counted(prep=None, return_numpy=False):
        before = dict(monitor.snapshot().get("counters", {}))
        outs = []
        for _ in range(steps):
            if prep is not None:
                prep()
            outs.append(run_once(return_numpy))
        after = monitor.snapshot().get("counters", {})
        return outs, {k: after.get(k, 0) - before.get(k, 0)
                      for k in ("run_plan.hit", "run_plan.miss",
                                "compiled_step.hit", "compiled_step.miss")}

    def drop_plan():
        main._run_plan_cache = None

    monitor.reset()
    monitor.enable()
    try:
        first = float(np.asarray(run_once()))       # trace + compile
        dropped, n_dropped = counted(prep=drop_plan)
        hot, n_hot = counted()
        blocking, n_blocking = counted(return_numpy=True)
        losses = [first] + [float(np.asarray(v))
                            for v in dropped + hot + blocking]
        return {
            "a_dropped_plan_is_rebuilt_and_the_step_is_not_recompiled":
                n_dropped == {"run_plan.hit": 0, "run_plan.miss": steps,
                              "compiled_step.hit": steps,
                              "compiled_step.miss": 0},
            "hot_caches_miss_nothing":
                n_hot["run_plan.hit"] == steps
                and n_hot["run_plan.miss"] == 0
                and n_hot["compiled_step.miss"] == 0
                and n_blocking["run_plan.miss"] == 0
                and n_blocking["compiled_step.miss"] == 0,
            "fetches_stay_on_the_device_unless_asked_for":
                all(isinstance(v, jax.Array) for v in dropped + hot)
                and all(isinstance(v, np.ndarray) for v in blocking),
            "one_loss_trajectory_across_the_modes":
                all(np.isfinite(losses)) and losses[-1] < losses[0],
        }
    finally:
        monitor.disable()
        monitor.reset()


@pytest.mark.parametrize("check", [
    "a_dropped_plan_is_rebuilt_and_the_step_is_not_recompiled",
    "hot_caches_miss_nothing",
    "fetches_stay_on_the_device_unless_asked_for",
    "one_loss_trajectory_across_the_modes"])
def test_dispatch_modes_of_one_train_program(dispatch_scenario, check):
    assert dispatch_scenario[check], dispatch_scenario
