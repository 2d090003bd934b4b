"""GSPMD runtime-tier tests (ISSUE 16).

Covers the tentpole in-process on the 8-device virtual CPU mesh: the
``sharding.lower`` plan (optimizer-moment inheritance, body specs, the
collective table the executor notes verbatim), the shared
``distributed.mesh.mesh_layout`` cache all feed paths read, the
compiled-step cache rekeying on (rule fingerprint, mesh device
identity), a REAL ``{dp=2, mp=2}`` train run with verifiably sharded
leaves and predicted==executed model collectives, and the
``program_lint --lower`` CLI, and the same run against a pure-dp
reference from the same init and feed (loss, peak memory, and the TP
checkpoint resharded onto a {dp=4} mesh).
"""

import json

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu.analysis import sharding as sh
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.framework.executor import Scope
from paddle_tpu.models import static_zoo
from paddle_tpu.monitor import fleet
from paddle_tpu.transpiler import collective as coll


def _bert():
    with fluid.unique_name.guard():
        return static_zoo.build("bert")


@pytest.fixture(scope="module")
def bert_plan():
    """One lowering of bert's default Megatron rule set, shared by the
    plan-shape tests (pure analysis — no device work)."""
    m = _bert()
    feed_shapes = m.smoke_feed_shapes()
    plan = sh.lower(m.main, m.partition_rules(),
                    fetch_names=[m.loss_name],
                    feed_names=sorted(feed_shapes),
                    feed_shapes=feed_shapes)
    return m, plan


# ---------------------------------------------------------------------
# lowering plan
# ---------------------------------------------------------------------

def test_lower_plan_record_shape(bert_plan):
    _, plan = bert_plan
    rec = plan.to_record()
    assert rec["kind"] == "sharding_plan"
    assert rec["mesh"] == {"dp": 2, "mp": 2}
    assert rec["data_axis"] == "dp"
    assert rec["sharded_state_vars"] > 0
    assert rec["constraints"] > 0
    assert rec["static_peak_bytes"] > 0
    assert rec["static_state_bytes"] > 0
    # the Megatron price: all-reduce over mp, what PR-12 predicted
    assert rec["model_collectives"]["all_reduce@mp"] == {
        "count": 3, "bytes": 24576}


def test_lower_moments_inherit_param_layout(bert_plan):
    """Optimizer slots are placed WITH their parameter — the per-shard
    state shrink is the tentpole's memory claim."""
    _, plan = bert_plan
    specs = plan.state_specs
    for param in ("fc_0.w_0", "embedding_0.w_0", "fc_0.b_0"):
        pspec = specs[param]
        for slot in (f"{param}_adam_0_moment1", f"{param}_adam_0_moment2"):
            assert specs[slot].dims == pspec.dims, (slot, pspec)
    # column-parallel: weight [None, mp], its bias [mp]
    assert specs["fc_0.w_0"].dims == (None, "mp")
    assert specs["fc_0.b_0"].dims == ("mp",)
    # row-parallel fc_3 adds AFTER the psum: bias stays replicated
    assert not any(d for d in (specs["fc_3.b_0"].dims or ()))


def test_body_spec_strips_data_axis(bert_plan):
    """Inside the shard_map body the data axis is manual — constraints
    there may only name model axes."""
    _, plan = bert_plan
    assert plan.body_spec(sh.ShardSpec(("dp", "mp"))).dims == (None, "mp")
    assert plan.body_spec(sh.ShardSpec(None)).dims is None
    for _, _, spec in plan.constraints:
        body = plan.body_spec(spec)
        assert "dp" not in (body.dims or ())


def test_model_sync_records_match_collective_table(bert_plan):
    """The records the executor notes verbatim sum to the table the
    analyzer renders — one source of truth."""
    _, plan = bert_plan
    recs = plan.model_sync_records()
    assert len(recs) == 3
    assert sum(r["bytes"] for r in recs) == 24576
    assert all(r["axes"] == ["mp"] for r in recs)


# ---------------------------------------------------------------------
# shared mesh-layout cache (satellite 1)
# ---------------------------------------------------------------------

def test_mesh_layout_shared_cache_and_data_rows():
    m2d = mesh_mod.build_rule_mesh({"dp": 2, "mp": 2})
    lay = mesh_mod.mesh_layout(m2d)
    assert mesh_mod.mesh_layout(m2d) is lay          # cache hit
    # one row per dp SHARD, not per device
    assert lay.data_rows == 2
    assert len(lay.data_procs) == 2
    assert lay.local_rows == 4
    assert lay.data_sharding.spec == P("dp")
    # fingerprint participates in the key: distinct entries
    lay_fp = mesh_mod.mesh_layout(m2d, fingerprint="abc")
    assert lay_fp is not lay and lay_fp.fingerprint == "abc"
    assert lay_fp.key == lay.key                     # same devices


def test_fleet_layout_reads_shared_cache():
    """The skew probe's feed path sizes its timestamp rows per dp
    shard on a 2-D mesh (the wait vector has one slot per dp rank)."""
    m2d = mesh_mod.build_rule_mesh({"dp": 2, "mp": 2})
    rows, procs, sharding = fleet._mesh_layout(m2d)
    assert rows == 2 and procs == [0, 0]
    assert sharding.spec == P("dp")
    feeds = fleet.add_timestamp_feeds({}, m2d)
    assert feeds[fleet.FLEET_TS_SEC].shape == (2,)


# ---------------------------------------------------------------------
# compiled-step cache identity
# ---------------------------------------------------------------------

def test_spmd_key_rekeys_on_rule_fingerprint():
    """Re-attaching a DIFFERENT rule set retraces; re-attaching the
    same one (even on a fresh CompiledProgram) hits the cache — the
    key is (mesh device identity, rule fingerprint), not object id."""
    m = _bert()
    rules = m.partition_rules()
    prog = fluid.CompiledProgram(m.main).with_sharding_rules(
        rules, execute=True)
    k1 = prog._spmd_key()
    assert fluid.CompiledProgram(m.main).with_sharding_rules(
        rules, execute=True)._spmd_key() == k1
    other = sh.PartitionRules([[r".*", []]], {"dp": 2, "mp": 2})
    k2 = prog.with_sharding_rules(other, execute=True)._spmd_key()
    assert k2 != k1
    assert k2[0] == k1[0]        # same mesh devices, new fingerprint


# ---------------------------------------------------------------------
# executor: the real {dp=2, mp=2} run
# ---------------------------------------------------------------------

def test_executor_tp_run_shards_leaves_and_conforms():
    """Acceptance (in-process half): a real {dp=2, mp=2} bert train
    step has (a) per-leaf sharded params/biases/moments exactly as the
    plan placed them, and (b) executed model collectives EQUAL to the
    plan's prediction.  Loss-vs-dp and memory: `tp_against_dp` below."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices for {dp=2, mp=2}")
    m = _bert()
    rules = m.partition_rules()
    feed = m.smoke_feed(batch=8, seed=5)
    feed_shapes = {n: tuple(v.shape) for n, v in feed.items()}
    plan = sh.lower(m.main, rules, fetch_names=[m.loss_name],
                    feed_names=sorted(feed_shapes),
                    feed_shapes=feed_shapes)

    exe = fluid.Executor()
    scope = Scope()
    exe.run(m.startup, scope=scope)
    prog = fluid.CompiledProgram(m.main).with_sharding_rules(
        rules, execute=True)
    losses = [float(np.mean(exe.run(prog, feed=feed,
                                    fetch_list=[m.loss_name],
                                    scope=scope)[0]))
              for _ in range(2)]
    assert all(np.isfinite(losses))
    # it is actually training: both steps track the unsharded program
    # on the same feed (step 2 only matches if step 1's update landed)
    # (a fresh executor: parameter init draws from its rng stream)
    ref_exe, ref_scope = fluid.Executor(), Scope()
    ref_exe.run(m.startup, scope=ref_scope)
    ref = [float(np.mean(ref_exe.run(m.main, feed=feed,
                                     fetch_list=[m.loss_name],
                                     scope=ref_scope)[0]))
           for _ in range(2)]
    assert ref[1] != ref[0]
    np.testing.assert_allclose(losses, ref, rtol=1e-5)

    # (a) placement per plan leaf: sharded specs land sharded, with
    # per-shard bytes strictly below the replicated size
    mp = 2
    for row in plan.per_var_table():
        v = scope.vars.get(row["var"])
        if v is None or not hasattr(v, "sharding"):
            continue
        want = tuple(row["partition_spec"]) or None
        got = tuple(v.sharding.spec)
        got = got + (None,) * (len(v.shape) - len(got))
        if want and any(d == "mp" for d in want):
            assert "mp" in got, (row["var"], got)
            shard = v.addressable_shards[0].data.nbytes
            assert shard * mp == v.nbytes, (row["var"], shard, v.nbytes)
    # moments really inherited on device, not just in the plan
    w = scope.vars["fc_0.w_0"]
    m1 = scope.vars["fc_0.w_0_adam_0_moment1"]
    assert tuple(m1.sharding.spec) == tuple(w.sharding.spec)

    # (b) conformance by construction
    model = coll.last_sync_stats().get("model") or {}
    pred = plan.collective_table()[("all_reduce", ("mp",))]
    assert model.get("psums") == pred["count"] == 3
    assert model.get("total_bytes") == pred["bytes"] == 24576
    assert model.get("axes") == ["mp"]


@pytest.fixture(scope="module")
def tp_against_dp(tmp_path_factory):
    """bert trained three steps on a REAL {dp=2, mp=2} mesh under its
    default Megatron rules, against a pure-dp {dp=2} run from the SAME
    init and feed (same local batch, so the memory delta isolates the
    mp sharding), telemetry on; then the TP state saved with the npz
    writer and restored onto {dp=4}.  Run once; each comparison is a
    case below."""
    from paddle_tpu import checkpoint as ckpt
    from paddle_tpu import monitor
    from paddle_tpu.distributed.mesh import build_rule_mesh

    m = _bert()
    feed = m.smoke_feed(batch=8, seed=11)
    feed_shapes = {n: tuple(v.shape) for n, v in feed.items()}
    plan_rec = sh.lower(m.main, m.partition_rules(),
                        fetch_names=[m.loss_name],
                        feed_names=sorted(feed_shapes),
                        feed_shapes=feed_shapes).to_record()
    exe = fluid.Executor()
    init_scope = Scope()
    exe.run(m.startup, scope=init_scope)
    init_state = {n: np.asarray(v) for n, v in init_scope.vars.items()
                  if v is not None}

    def train(rules, key):
        scope = Scope()
        for n, v in init_state.items():
            scope.set_var(n, v)
        prog = fluid.CompiledProgram(m.main).with_sharding_rules(
            rules, execute=True).with_telemetry(key)
        losses = [float(np.mean(exe.run(
            prog, feed=feed, fetch_list=[m.loss_name],
            scope=scope)[0])) for _ in range(3)]
        prof = monitor.mem_profile_split(key=f"{key}:dp") or {}
        return scope, losses, (prof.get("peak") or {}).get(
            "model_bytes") or 0

    monitor.reset()
    monitor.enable()
    try:
        _, dp_losses, dp_peak = train(
            sh.PartitionRules([(r".*", [])], {"dp": 2}), "tp_vs_dp_dp")
        tp_scope, tp_losses, tp_peak = train(m.partition_rules(),
                                             "tp_vs_dp_tp")
    finally:
        monitor.disable()
        monitor.reset()
    static_peak = (plan_rec["static_peak_bytes"]
                   + plan_rec["static_state_bytes"])

    # the TP checkpoint (sharded leaves; npz, the collective-free writer
    # an elastic survivor would use) onto {dp=4}
    ckpt_dir = str(tmp_path_factory.mktemp("tp_ckpt"))
    tp_state = {n: v for n, v in tp_scope.vars.items() if v is not None}
    ckpt.save_checkpoint(ckpt_dir, tp_state, 3, writer="npz")
    template = {n: np.empty(np.shape(v), v.dtype)
                for n, v in tp_state.items()}
    restored, _ = ckpt.restore_resharded(
        ckpt_dir, template, mesh=build_rule_mesh({"dp": 4}))
    return {
        "loss_allclose_vs_dp": bool(np.allclose(
            dp_losses, tp_losses, rtol=2e-3, atol=2e-5)),
        "mem_within_25pct": tp_peak > 0 and abs(
            static_peak - tp_peak) / tp_peak <= 0.25,
        "tp_peak_below_dp_peak": 0 < tp_peak < dp_peak,
        "topology_mesh_axes": (ckpt.load_topology(ckpt_dir) or {}).get(
            "mesh_axes") == {"dp": 2, "mp": 2},
        "ckpt_reshard_bitwise": all(
            np.array_equal(np.asarray(restored[n]), np.asarray(v))
            for n, v in tp_state.items()),
    }


@pytest.mark.parametrize("check", [
    "loss_allclose_vs_dp", "mem_within_25pct", "tp_peak_below_dp_peak",
    "topology_mesh_axes", "ckpt_reshard_bitwise"])
def test_tp_run_against_its_dp_reference(tp_against_dp, check):
    assert tp_against_dp[check], tp_against_dp


# ---------------------------------------------------------------------
# program_lint --lower CLI (satellite 2)
# ---------------------------------------------------------------------

def test_cli_lower_prints_plan(capsys):
    import tools.program_lint as pl

    rc = pl.main(["--model", "bert", "--sharding-rules", "default",
                  "--lower"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "bert/main: lowering plan" in out
    assert "fc_0.w_0" in out and "[-, mp]" in out
    assert "implied all_reduce over mp: 3 x, 24576 bytes" in out
    assert "static per-shard peak:" in out


def test_cli_lower_json_record(capsys):
    import tools.program_lint as pl

    rc = pl.main(["--model", "bert", "--sharding-rules", "default",
                  "--lower", "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    recs = json.loads(out)
    low = next(r["lower"] for r in recs if "lower" in r)
    assert low["kind"] == "sharding_plan"
    assert low["model_collectives"]["all_reduce@mp"] == {
        "count": 3, "bytes": 24576}
    # startup programs carry no rules, hence no plan
    assert sum(1 for r in recs if "lower" in r) == 1


def test_cli_lower_without_rules_is_usage_error(capsys):
    import tools.program_lint as pl

    assert pl.main(["--model", "mlp", "--lower"]) == 2
    assert "--lower needs --sharding-rules" in capsys.readouterr().err
