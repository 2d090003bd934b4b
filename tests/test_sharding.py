"""Static sharding analyzer tests (ISSUE 12).

Covers the partition-rule engine (first-match-wins, scalar exemption,
zero-match did-you-mean), the per-op-family spec propagation (matmul
pending-psum, elementwise join, reshape factor mapping, reduce/conv/
lookup), every new PT3xx code via a dedicated seeded-bug program with
exact code + op index + creation-callsite assertions, the zoo sweep
under the shipped default rule sets, the implied-collective plan's
agreement with transpiler.collective's bucket planner, the static
memory estimate's invariants, and the verifier/executor wiring
(merge into check_program, rule-fingerprint cache keys, off-path
no-regression)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis
from paddle_tpu import layers as L
from paddle_tpu.analysis import sharding as sh
from paddle_tpu.models import static_zoo
from paddle_tpu.transpiler import collective

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# core lattice / rule engine
# ---------------------------------------------------------------------------

def test_shard_spec_basics():
    s = sh.ShardSpec(("mp", None))
    assert s.sharded_axes() == ["mp"]
    assert not s.is_replicated
    assert sh.REPLICATED.is_replicated
    assert s.render() == "[mp, -]"
    p = s.with_partial(["dp"])
    assert p.partial == frozenset({"dp"})
    assert "partial(dp)" in p.render()
    assert p.clear_partial().partial == frozenset()


def test_at_rank_pads_right_partition_spec_semantics():
    # P('dp') on a rank-2 array shards dim 0, NOT dim 1
    s = sh.ShardSpec(("dp",)).at_rank(2)
    assert s.dims == ("dp", None)
    assert sh.ShardSpec(("a", "b")).at_rank(1).dims == ("a",)


def test_mesh_and_shard_factor():
    mesh = sh.MeshSpec({"dp": 2, "mp": 4})
    assert mesh.total == 8
    assert sh.ShardSpec(("mp", None)).shard_factor(mesh) == 4
    assert sh.ShardSpec(("dp", "mp")).shard_factor(mesh) == 8
    with pytest.raises(ValueError):
        sh.MeshSpec({"dp": 0})


def test_rules_first_match_wins_and_axis_validation():
    rules = sh.PartitionRules(
        [(r"w_special", ["mp", None]), (r"w_.*", [None, "mp"]),
         (r".*", [])],
        {"mp": 2})
    assert rules.match("w_special")[0] == 0
    assert rules.match("w_other")[0] == 1
    assert rules.match("bias")[0] == 2
    with pytest.raises(ValueError):
        sh.PartitionRules([(r".*", ["ghost_axis"])], {"mp": 2})


def test_rules_roundtrip_and_fingerprint():
    doc = {"mesh": {"dp": 2, "mp": 2}, "data_axis": "dp",
           "rules": [["w", [None, "mp"]], [".*", []]]}
    rules = sh.PartitionRules.from_dict(doc)
    assert rules.to_dict()["mesh"] == doc["mesh"]
    same = sh.PartitionRules.from_dict(doc)
    assert rules.fingerprint() == same.fingerprint()
    other = sh.PartitionRules.from_dict(
        {**doc, "rules": [["w", ["mp", None]], [".*", []]]})
    assert rules.fingerprint() != other.fingerprint()


def test_load_rules_file(tmp_path):
    p = tmp_path / "rules.json"
    p.write_text(json.dumps({"mesh": {"mp": 2},
                             "rules": [[".*", [None, "mp"]]]}))
    rules = sh.load_rules_file(str(p))
    assert rules.mesh.axes == {"mp": 2}
    assert rules.data_axis is None       # no dp axis in this mesh


def _mlp_model():
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 8])
            y = fluid.data("y", [None, 1])
            pred = L.fc(L.fc(x, 16, act="relu"), 1)
            loss = L.mean(L.square_error_cost(pred, y))
            fluid.optimizer.Adam(1e-3).minimize(loss)
    return main, startup, loss


def test_match_report_claims_and_fallthrough():
    main, _, _ = _mlp_model()
    rules = sh.PartitionRules([(r"fc_0\.w_0$", [None, "mp"])],
                              {"dp": 2, "mp": 2})
    rep = sh.match_report(main, rules)
    assert rep["claimed"]["fc_0.w_0"]["rule"] == 0
    assert "fc_1.w_0" in rep["fallthrough"]
    # data vars are not part of the rule-matched pytree; they take the
    # mesh's data axis on their leading dim
    assert "x" not in rep["claimed"] and "x" not in rep["fallthrough"]
    assert rep["specs"]["x"].dims == ("dp",)


def test_match_report_scalar_vars_never_partitioned():
    main, _, _ = _mlp_model()
    # adam beta-pow accumulators are (1,)-shaped and substring-match
    # any 'fc_0.w_0' prefix rule — the scalar exemption keeps them
    # replicated instead of tripping PT304
    rules = sh.PartitionRules([(r"fc_0\.w_0", ["mp"]), (r".*", [])],
                              {"mp": 2})
    rep = sh.match_report(main, rules)
    scalars = [n for n in rep["claimed"]
               if "beta" in n and "pow" in n and "fc_0.w_0" in n]
    assert scalars, "expected adam beta-pow accumulators in the report"
    for n in scalars:
        assert rep["specs"][n].is_replicated


def test_unmatched_rule_gets_did_you_mean():
    main, _, _ = _mlp_model()
    rules = sh.PartitionRules([(r"fc_0\.w_9$", [None, "mp"]),
                               (r".*", [])], {"mp": 2})
    rep = sh.match_report(main, rules)
    assert len(rep["unmatched_rules"]) == 1
    um = rep["unmatched_rules"][0]
    assert um["pattern"] == r"fc_0\.w_9$"
    assert "did you mean" in um["suggestion"]
    assert "fc_0.w_0" in um["suggestion"]


def test_block_var_did_you_mean_still_works():
    main, _, _ = _mlp_model()
    with pytest.raises(ValueError) as ei:
        main.global_block().var("fc_0.w_9")
    assert "did you mean" in str(ei.value)
    assert "fc_0.w_0" in str(ei.value)


# ---------------------------------------------------------------------------
# propagation families
# ---------------------------------------------------------------------------

def _analyze(main, rules_list, mesh, fetches, feed_shapes=None,
             data_axis="dp"):
    rules = sh.PartitionRules(rules_list, mesh, data_axis=data_axis)
    return sh.analyze(main, rules, fetch_names=fetches,
                      feed_shapes=feed_shapes)


def test_matmul_row_parallel_pends_then_resolves():
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [4, 8])
            w = main.global_block().create_parameter(
                name="w", shape=[8, 6])
            h = L.matmul(x, w)          # partial over mp
            out = L.relu(h)             # consumer implies the psum
    a = _analyze(main, [("^w$", ["mp", None]), (".*", [])], {"mp": 2},
                 [out.name])
    assert not [d for d in a.diagnostics if d.code == "PT306"]
    ars = [r for r in a.collectives if r["kind"] == "all_reduce"
           and r["axes"] == ["mp"]]
    assert len(ars) == 1
    assert ars[0]["var"] == h.name
    assert ars[0]["bytes"] == 4 * 6 * 4       # resolved (full) h bytes
    # post-resolution the edge is clean
    assert a.specs[h.name].partial == frozenset()


def test_matmul_column_parallel_shards_output_no_collective():
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [4, 8])
            w = main.global_block().create_parameter(
                name="w", shape=[8, 6])
            h = L.matmul(x, w)
    a = _analyze(main, [("^w$", [None, "mp"]), (".*", [])], {"mp": 2},
                 None)
    assert a.specs[h.name].axis_of(1) == "mp"
    assert a.specs[h.name].partial == frozenset()
    assert not a.collectives


def test_reshape_carries_major_split_dim():
    # the transformer _split_heads pattern: [8, 16, 32] -> [8, 16,
    # 4, 8] with dim 2 sharded — the shard rides to the major head dim
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            w = main.global_block().create_parameter(
                name="emb", shape=[8, 16, 32])
            r = L.reshape(w, shape=[8, 16, 4, 8])
            t = L.transpose(r, perm=[0, 2, 1, 3])
    a = _analyze(main, [("^emb$", [None, None, "mp"]), (".*", [])],
                 {"mp": 2}, None)
    assert a.specs[r.name].dims == (None, None, "mp", None)
    assert a.specs[t.name].dims == (None, "mp", None, None)
    assert not a.collectives


def test_reshape_minor_shard_gathers():
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            w = main.global_block().create_parameter(
                name="emb", shape=[4, 6])
            r = L.reshape(w, shape=[24])     # merge with MINOR sharded
    a = _analyze(main, [("^emb$", [None, "mp"]), (".*", [])],
                 {"mp": 2}, None)
    gathers = [c for c in a.collectives if c["kind"] == "all_gather"]
    assert len(gathers) == 1
    assert a.specs[r.name].is_replicated


def test_reduce_over_sharded_dim_pends_psum():
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            w = main.global_block().create_parameter(
                name="w", shape=[8, 6])
            s = L.reduce_sum(w, dim=[0])
            out = L.relu(s)
    a = _analyze(main, [("^w$", ["mp", None]), (".*", [])], {"mp": 2},
                 [out.name])
    ars = [c for c in a.collectives if c["kind"] == "all_reduce"]
    assert len(ars) == 1 and ars[0]["var"] == s.name


def test_lookup_vocab_shard_is_pending_psum_embedding():
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            ids = fluid.data("ids", [None, 4], dtype="int64")
            emb = L.embedding(ids, size=(100, 8))
            out = L.relu(emb)
    a = _analyze(main, [(r"embedding_0\.w_0$", ["mp", None]),
                        (".*", [])], {"mp": 2}, [out.name],
                 feed_shapes={"ids": (6, 4)}, data_axis=None)
    ars = [c for c in a.collectives if c["kind"] == "all_reduce"
           and c["axes"] == ["mp"]]
    assert len(ars) == 1 and ars[0]["var"] == emb.name


def test_unknown_family_degrades_with_note_never_error():
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            w = main.global_block().create_parameter(
                name="w", shape=[8, 6])
            out = main.global_block().create_var(name="o", shape=[8, 6])
            main.global_block().append_op(
                "sequence_reverse", inputs={"X": w},
                outputs={"Out": out})
    a = _analyze(main, [("^w$", ["mp", None]), (".*", [])], {"mp": 2},
                 None)
    assert not [d for d in a.diagnostics
                if d.code in ("PT305", "PT306")]
    assert a.notes and "sequence_reverse" in a.notes[0]
    assert a.specs["o"].is_replicated


# ---------------------------------------------------------------------------
# one seeded-bug program per new PT code (exact code + callsite)
# ---------------------------------------------------------------------------

def _codes(a):
    out = {}
    for d in a.diagnostics:
        out.setdefault(d.code, []).append(d)
    return out


def test_seeded_pt301_rule_miss_on_trainable_param():
    main, _, loss = _mlp_model()
    a = _analyze(main, [(r"fc_0\.w_0$", [None, "mp"])],
                 {"dp": 2, "mp": 2}, [loss.name])
    codes = _codes(a)
    assert set(codes) == {"PT301"}
    missed = {d.var for d in codes["PT301"]}
    assert "fc_1.w_0" in missed and "fc_0.w_0" not in missed
    # frozen/optimizer state falls through QUIETLY
    assert not any("moment" in v for v in missed)
    # the diagnostic blames WHERE the parameter was made
    sites = [d.callsite for d in codes["PT301"] if d.callsite]
    assert sites and any("test_sharding.py" in s for s in sites)


def test_seeded_pt302_replicated_giant_param():
    before = fluid.get_flags("replicated_param_bytes")
    fluid.set_flags({"FLAGS_replicated_param_bytes": 1024})
    try:
        with fluid.unique_name.guard():
            main = fluid.Program()
            with fluid.program_guard(main, fluid.Program()):
                ids = fluid.data("ids", [None, 4], dtype="int64")
                emb = L.embedding(ids, size=(1000, 64))  # 256 KB
                out = L.reduce_sum(emb)
        a = _analyze(main, [(r".*", [])], {"dp": 2}, None)
        codes = _codes(a)
        assert "PT302" in codes
        assert codes["PT302"][0].var == "embedding_0.w_0"
        assert "replicated" in codes["PT302"][0].message
    finally:
        fluid.set_flags(before)


def test_seeded_pt303_hot_edge_reshard():
    # a TRAIN program whose TP'd head feeds softmax_with_cross_entropy:
    # the class-axis shard must gather on a forward edge
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 8])
            label = fluid.data("label", [None, 1], dtype="int64")
            logits = L.fc(x, 10)
            loss = L.mean(L.softmax_with_cross_entropy(logits, label))
            fluid.optimizer.SGD(0.1).minimize(loss)
    a = _analyze(main, [(r"fc_0\.w_0$", [None, "mp"]), (".*", [])],
                 {"dp": 2, "mp": 2}, [loss.name],
                 feed_shapes={"x": (8, 8), "label": (8, 1)})
    codes = _codes(a)
    assert "PT303" in codes
    d = codes["PT303"][0]
    assert d.op_type == "softmax_with_cross_entropy"
    assert d.op_index is not None
    assert d.callsite and "test_sharding.py" in d.callsite
    assert "->" in d.message            # source -> dest spec pair
    assert "[" in d.message and "mp" in d.message


def test_seeded_pt304_divisibility():
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            w = main.global_block().create_parameter(
                name="w", shape=[13, 4])       # 13 % 2 != 0
            out = L.relu(w)
    a = _analyze(main, [("^w$", ["mp", None]), (".*", [])], {"mp": 2},
                 [out.name])
    codes = _codes(a)
    assert set(codes) == {"PT304"}
    assert codes["PT304"][0].var == "w"
    assert "13" in codes["PT304"][0].message


def test_seeded_pt305_conflicting_join():
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            a_ = main.global_block().create_parameter(
                name="pa", shape=[8, 4])
            b_ = main.global_block().create_parameter(
                name="pb", shape=[8, 4])
            out = L.elementwise_add(a_, b_)
    # the same DIM sharded over two different mesh axes cannot join
    # (a row/col 2D split on DIFFERENT dims would be fine)
    a = _analyze(main,
                 [("^pa$", ["row", None]), ("^pb$", ["col", None]),
                  (".*", [])],
                 {"row": 2, "col": 2}, [out.name])
    codes = _codes(a)
    assert "PT305" in codes
    d = codes["PT305"][0]
    assert d.op_type == "elementwise_add"
    assert d.callsite and "test_sharding.py" in d.callsite


def test_seeded_pt306_unresolved_pending_psum():
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [4, 8])
            w = main.global_block().create_parameter(
                name="w", shape=[8, 6])
            h = L.matmul(x, w)          # partial over mp, FETCHED raw
    a = _analyze(main, [("^w$", ["mp", None]), (".*", [])], {"mp": 2},
                 [h.name])
    codes = _codes(a)
    assert set(codes) == {"PT306"}
    d = codes["PT306"][0]
    assert d.var == h.name
    assert "partial" in d.message
    # blames the producing op, with index + creation callsite
    assert d.op_type == "matmul" and d.op_index is not None
    assert d.callsite and "test_sharding.py" in d.callsite


def test_dp_scalar_loss_fetch_is_resolved_not_pt306():
    # the executor pmeans rank-0 fetches (update/dp_fetch_sync_0):
    # a dp-partial scalar loss is legitimate, not a PT306
    main, _, loss = _mlp_model()
    a = _analyze(main, [(".*", [])], {"dp": 2}, [loss.name],
                 feed_shapes={"x": (8, 8), "y": (8, 1)})
    assert not _codes(a)
    sync = [c for c in a.collectives
            if c["scope"] == "update/dp_fetch_sync_0"]
    assert len(sync) == 1 and sync[0]["var"] == loss.name


# ---------------------------------------------------------------------------
# zoo sweep under the shipped default rule sets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(static_zoo.BUILDERS))
def test_zoo_model_pt3xx_clean_under_default_rules(name):
    m = static_zoo.build(name)
    a = sh.analyze(m.main, m.partition_rules(),
                   fetch_names=m.fetches,
                   feed_shapes=m.smoke_feed_shapes())
    assert not a.diagnostics, a.result().render()
    assert not a.report["unmatched_rules"], a.report["unmatched_rules"]
    # the full verifier agrees (PT3xx merge does not disturb PT1xx/2xx)
    r = analysis.check_program(m.main, fetch_names=m.fetches,
                               sharding=m.partition_rules())
    assert r.ok, r.render()
    assert r.sharding is not None


def test_zoo_transformers_price_the_megatron_collectives():
    # bert/gpt default TP layout: vocab-sharded embedding + 2 row-
    # parallel projections = exactly 3 mp all-reduces in the forward
    for name in ("bert", "gpt"):
        m = static_zoo.build(name)
        a = sh.analyze(m.main, m.partition_rules(),
                       fetch_names=m.fetches,
                       feed_shapes=m.smoke_feed_shapes())
        table = a.collective_table()
        assert table[("all_reduce", ("mp",))]["count"] == 3, (name,
                                                             table)
        assert table[("all_reduce", ("mp",))]["bytes"] > 0


# ---------------------------------------------------------------------------
# implied dp grad-sync plan == transpiler.collective's planner
# ---------------------------------------------------------------------------

def test_dp_sync_plan_uses_bucket_planner_math():
    main, _, loss = _mlp_model()
    before = fluid.get_flags("dp_bucket_bytes")
    try:
        fluid.set_flags({"FLAGS_dp_bucket_bytes": 4 << 20})
        a = _analyze(main, [(".*", [])], {"dp": 2}, [loss.name],
                     feed_shapes={"x": (8, 8), "y": (8, 1)})
        plan = a.dp_sync_plan()
        grads = [p for bs in main.backward_sections
                 for p in bs.param_names]
        total = sum(
            int(np.prod(main.global_block().var(p).shape)) * 4
            for p in grads)
        assert plan["count"] == 1          # one 4MiB bucket holds all
        assert plan["bytes"] == total
        # tiny buckets: exactly ceil(total / bucket) all-reduces
        fluid.set_flags({"FLAGS_dp_bucket_bytes": 64})
        a2 = _analyze(main, [(".*", [])], {"dp": 2}, [loss.name],
                      feed_shapes={"x": (8, 8), "y": (8, 1)})
        plan2 = a2.dp_sync_plan()
        assert plan2["count"] == -(-total // 64)
        assert plan2["bytes"] == total
        # per-grad mode
        fluid.set_flags({"FLAGS_dp_bucket_bytes": 0})
        a3 = _analyze(main, [(".*", [])], {"dp": 2}, [loss.name],
                      feed_shapes={"x": (8, 8), "y": (8, 1)})
        assert a3.dp_sync_plan()["count"] == len(grads)
    finally:
        fluid.set_flags(before)


def test_implied_collective_plan_matches_plan_buckets():
    entries = [("a@GRAD", 100, 4, "float32"),
               ("b@GRAD", 60, 4, "float32"),
               ("c@GRAD", 10, 8, "float64")]
    plan = collective.implied_collective_plan(entries, axes=["dp"],
                                              bucket_bytes=256)
    buckets = collective.plan_buckets(entries, 256)
    assert len(plan) == len(buckets)
    assert [p["bytes"] for p in plan] == [b["bytes"] for b in buckets]
    assert all(p["kind"] == "all_reduce" and p["axes"] == ["dp"]
               for p in plan)
    legacy = collective.implied_collective_plan(entries, axes=["dp"],
                                                bucket_bytes=0)
    assert len(legacy) == 3
    assert legacy[0]["bytes"] == 400


# ---------------------------------------------------------------------------
# static memory estimate
# ---------------------------------------------------------------------------

def test_memory_estimate_invariants():
    m = static_zoo.build("bert")
    a = sh.analyze(m.main, m.partition_rules(),
                   fetch_names=m.fetches,
                   feed_shapes=m.smoke_feed_shapes())
    mem = a.memory
    assert mem["peak_bytes"] > 0 and mem["state_bytes"] > 0
    tl = mem["timeline"]
    assert all(tl[i][0] < tl[i + 1][0] for i in range(len(tl) - 1))
    assert any(pos == mem["peak_pos"] for pos, _ in tl)
    # buffers live at the peak sum EXACTLY to the peak
    assert sum(mem["per_scope"].values()) == mem["peak_bytes"]
    assert mem["top_buffers"]
    assert mem["per_shard"] is True


def test_memory_estimate_shrinks_with_sharding():
    # TP-sharding the big matrices must shrink the per-shard estimate
    m = static_zoo.build("bert")
    tp = sh.analyze(m.main, m.partition_rules(),
                    fetch_names=m.fetches,
                    feed_shapes=m.smoke_feed_shapes())
    repl = sh.analyze(
        m.main, sh.PartitionRules([(".*", [])], {"dp": 2}),
        fetch_names=m.fetches, feed_shapes=m.smoke_feed_shapes())
    assert tp.memory["state_bytes"] < repl.memory["state_bytes"]


# ---------------------------------------------------------------------------
# verifier / executor wiring
# ---------------------------------------------------------------------------

def test_check_program_merges_pt3xx_into_errors():
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [4, 8])
            w = main.global_block().create_parameter(
                name="w", shape=[8, 6])
            h = L.matmul(x, w)
    rules = sh.PartitionRules([("^w$", ["mp", None]), (".*", [])],
                              {"mp": 2})
    r = analysis.check_program(main, fetch_names=[h.name],
                               sharding=rules)
    assert not r.ok
    assert any(d.code == "PT306" for d in r.errors)
    # without rules the same program is clean — no false PT3xx
    r2 = analysis.check_program(main, fetch_names=[h.name])
    assert r2.ok and r2.sharding is None


def test_cached_check_rekeys_on_rule_fingerprint():
    main, _, loss = _mlp_model()
    from paddle_tpu.analysis import verifier

    base = verifier.analysis_runs
    rules_a = sh.PartitionRules([(".*", [])], {"dp": 2})
    sh.attach(main, rules_a)
    r1, fresh1 = analysis.cached_check(main, fetch_names=[loss.name])
    r1b, fresh1b = analysis.cached_check(main, fetch_names=[loss.name])
    assert fresh1 and not fresh1b
    # a DIFFERENT rule set must re-analyze, not serve the stale result
    rules_b = sh.PartitionRules([(r"fc_0\.w_0$", [None, "mp"])],
                                {"dp": 2, "mp": 2})
    sh.attach(main, rules_b)
    r2, fresh2 = analysis.cached_check(main, fetch_names=[loss.name])
    assert fresh2
    assert any(d.code == "PT301" for d in r2.errors)
    assert verifier.analysis_runs == base + 2
    sh.attach(main, None)


def test_attach_does_not_bump_program_version():
    main, _, _ = _mlp_model()
    v = main._version
    sh.attach(main, sh.PartitionRules([(".*", [])], {"dp": 2}))
    assert main._version == v
    sh.attach(main, None)


@pytest.fixture
def static_check_flag():
    before = fluid.get_flags("static_check")["FLAGS_static_check"]
    yield
    fluid.set_flags({"FLAGS_static_check": before})


def test_executor_error_mode_raises_pt3xx_pre_trace(static_check_flag):
    from paddle_tpu.framework.executor import Scope

    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 8])
            w = main.global_block().create_parameter(
                name="w", shape=[8, 4])
            out = L.matmul(x, w)
    prog = fluid.CompiledProgram(main).with_sharding_rules(
        [("^w$", ["mp", None]), (".*", [])], mesh={"mp": 2})
    fluid.set_flags({"FLAGS_static_check": "error"})
    exe = fluid.Executor()
    scope = Scope()
    exe.run(startup, scope=scope)
    scope.set_var("w", np.ones((8, 4), np.float32))
    with pytest.raises(analysis.ProgramLintError) as ei:
        exe.run(prog, feed={"x": np.ones((4, 8), np.float32)},
                fetch_list=[out.name], scope=scope)
    assert "PT306" in str(ei.value)


def test_graph_opt_substitute_keeps_sharding_rules(static_check_flag):
    """FLAGS_graph_opt=on traces an optimized CLONE — the rule
    attachment must ride along or the PT3xx lints silently vanish."""
    from paddle_tpu.framework.executor import Scope

    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 8])
            w = main.global_block().create_parameter(
                name="w", shape=[8, 4])
            out = L.matmul(x, w)
    prog = fluid.CompiledProgram(main).with_sharding_rules(
        [("^w$", ["mp", None]), (".*", [])], mesh={"mp": 2})
    before = fluid.get_flags("graph_opt")
    fluid.set_flags({"FLAGS_graph_opt": "on",
                     "FLAGS_static_check": "error"})
    try:
        exe = fluid.Executor()
        scope = Scope()
        exe.run(startup, scope=scope)
        scope.set_var("w", np.ones((8, 4), np.float32))
        with pytest.raises(analysis.ProgramLintError) as ei:
            exe.run(prog, feed={"x": np.ones((4, 8), np.float32)},
                    fetch_list=[out.name], scope=scope)
        assert "PT306" in str(ei.value)
    finally:
        fluid.set_flags(before)


def test_static_check_off_path_no_regression(static_check_flag):
    """Dispatch-overhead contract: with FLAGS_static_check=off an
    attached rule set costs the hot path NOTHING — the verifier never
    runs (analysis_runs pinned), exactly as before this PR."""
    from paddle_tpu.analysis import verifier
    from paddle_tpu.framework.executor import Scope

    main, startup, loss = _mlp_model()
    sh.attach(main, sh.PartitionRules([(".*", [])], {"dp": 2}))
    fluid.set_flags({"FLAGS_static_check": "off"})
    exe = fluid.Executor()
    scope = Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.zeros((4, 8), np.float32),
            "y": np.zeros((4, 1), np.float32)}
    base = verifier.analysis_runs
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
    assert verifier.analysis_runs == base
    sh.attach(main, None)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_default_rules_exit_zero():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "program_lint.py"),
         "--model", "bert", "--sharding-rules", "default", "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stderr
    recs = json.loads(out.stdout)
    main_rec = next(r for r in recs if r["key"] == "bert/main")
    assert main_rec["errors"] == 0
    assert main_rec["sharding"]["collectives"]
    assert main_rec["memory"]["peak_bytes"] > 0


def test_concat_conflicting_later_operand_is_pt305():
    """Review regression: a later concat operand's conflicting layout
    must PT305 + reshard, not silently vanish from the cost model."""
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            pa = main.global_block().create_parameter(name="pa",
                                                      shape=[8, 4])
            pb = main.global_block().create_parameter(name="pb",
                                                      shape=[8, 4])
            out = L.concat([pa, pb], axis=1)
    a = _analyze(main,
                 [("^pa$", ["row", None]), ("^pb$", ["col", None]),
                  (".*", [])],
                 {"row": 2, "col": 2}, [out.name])
    codes = _codes(a)
    assert "PT305" in codes
    assert codes["PT305"][0].op_type == "concat"
    assert any(c["kind"] in ("all_gather", "all_to_all")
               for c in a.collectives)
    assert a.specs[out.name].axis_of(0) == "row"


def test_partial_gather_priced_as_all_gather():
    """Review regression: dropping ONE of two mesh axes is an
    all-gather over the dropped axis at the GATHERED (per-remaining-
    shard) size, not an all-to-all at per-shard source size."""
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            w = main.global_block().create_parameter(
                name="w", shape=[8, 4])
            y = L.layer_norm(w, begin_norm_axis=1)
    a = _analyze(main, [("^w$", ["dp", "mp"]), (".*", [])],
                 {"dp": 2, "mp": 2}, [y.name])
    recs = [c for c in a.collectives if c["var"] == "w"]
    assert len(recs) == 1
    assert recs[0]["kind"] == "all_gather"
    assert recs[0]["axes"] == ["mp"]
    # gathered size: full 8*4*4 bytes / dp(2) — mp is gathered back
    assert recs[0]["bytes"] == 8 * 4 * 4 // 2


def test_cli_exit_code_sees_shape_dependent_errors(tmp_path):
    """Review regression: a PT3xx error only decidable once the smoke
    feed pins the batch dim (batch 8 on a dp=3 mesh) must drive the
    exit code, not just the printed text."""
    rules_path = tmp_path / "rules.json"
    rules_path.write_text(json.dumps({
        "mesh": {"dp": 3}, "data_axis": "dp",
        "rules": [[".*", []]]}))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "program_lint.py"),
         "--model", "mlp", "--sharding-rules", str(rules_path)],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 1, (out.stdout, out.stderr)
    assert "PT304" in out.stdout


def test_sum_conflicting_operands_is_pt305():
    """Review regression: sum (autodiff's grad-accumulate op) folds
    operands through the same merge as elementwise — conflicts are
    PT305, not first-operand-wins."""
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            pa = main.global_block().create_parameter(name="pa",
                                                      shape=[8, 4])
            pb = main.global_block().create_parameter(name="pb",
                                                      shape=[8, 4])
            out = main.global_block().create_var(name="s",
                                                 shape=[8, 4])
            main.global_block().append_op(
                "sum", inputs={"X": [pa, pb]}, outputs={"Out": out})
    a = _analyze(main,
                 [("^pa$", ["row", None]), ("^pb$", ["col", None]),
                  (".*", [])],
                 {"row": 2, "col": 2}, ["s"])
    codes = _codes(a)
    assert "PT305" in codes and codes["PT305"][0].op_type == "sum"


def test_mul_contraction_mismatch_is_pt305_like_matmul():
    """Review regression: 'mul' (what fc lowers to) diagnoses a
    contraction-axis mismatch exactly like the matmul branch."""
    for op_type in ("matmul", "mul"):
        with fluid.unique_name.guard():
            main = fluid.Program()
            with fluid.program_guard(main, fluid.Program()):
                x = main.global_block().create_parameter(
                    name="px", shape=[4, 8])
                w = main.global_block().create_parameter(
                    name="pw", shape=[8, 6])
                out = main.global_block().create_var(name="o",
                                                     shape=[4, 6])
                main.global_block().append_op(
                    op_type, inputs={"X": x, "Y": w},
                    outputs={"Out": out})
        a = _analyze(main,
                     [("^px$", [None, "a"]), ("^pw$", ["b", None]),
                      (".*", [])],
                     {"a": 2, "b": 2}, None)
        codes = _codes(a)
        assert "PT305" in codes, op_type
        # partial only over X's contraction axis — Y was gathered
        assert a.specs["o"].partial == frozenset({"a"}), op_type


def test_shard_spec_hash_eq_contract():
    """Review regression: equal specs hash equal (all-None dims is
    canonical replicated)."""
    assert sh.REPLICATED == sh.ShardSpec((None, None))
    assert hash(sh.REPLICATED) == hash(sh.ShardSpec((None, None)))
    assert len({sh.REPLICATED, sh.ShardSpec((None,)),
                sh.ShardSpec((None, None))}) == 1


def test_clone_for_test_keeps_sharding_rules(static_check_flag):
    """Review regression: the for_test eval twin lints PT3xx like its
    parent — clone() carries the rule attachment."""
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [4, 8])
            w = main.global_block().create_parameter(
                name="w", shape=[8, 6])
            h = L.matmul(x, w)
    rules = sh.PartitionRules([("^w$", ["mp", None]), (".*", [])],
                              {"mp": 2})
    sh.attach(main, rules)
    eval_prog = main.clone(for_test=True)
    assert sh.attached(eval_prog) is rules
    r = analysis.check_program(eval_prog, fetch_names=[h.name])
    assert any(d.code == "PT306" for d in r.errors)


@pytest.fixture(scope="module")
def dp_conformance():
    """What the analyzer predicts of a data-parallel run against what
    the run does, on a 2-device mesh, for the two transformer models of
    the zoo: three steps each through the PUBLIC Executor.run with
    telemetry on; run once, each comparison is a case below."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import monitor
    from paddle_tpu.framework.executor import Scope

    ndev = 2
    checks = {}
    monitor.reset()
    monitor.enable()
    try:
        dp_rules = sh.PartitionRules([(r".*", [])], {"dp": ndev})
        for name in ("bert", "gpt"):
            with fluid.unique_name.guard():
                m = static_zoo.build(name)
            feed = m.smoke_feed(batch=4 * ndev)
            a = sh.analyze(m.main, dp_rules, fetch_names=[m.loss_name],
                           feed_shapes={n: tuple(v.shape)
                                        for n, v in feed.items()})
            plan = a.dp_sync_plan()
            key = f"dp_conformance_{name}"
            exe = fluid.Executor()
            scope = Scope()
            exe.run(m.startup, scope=scope)
            prog = fluid.CompiledProgram(m.main).with_data_parallel(
                loss_name=m.loss_name, places=ndev).with_telemetry(key)
            for _ in range(3):
                exe.run(prog, feed=feed, fetch_list=[m.loss_name],
                        scope=scope)
            stats = collective.last_sync_stats()
            scopes = (monitor.op_profile_split(key=f"{key}:dp")
                      or {}).get("scopes", {})
            measured = ((monitor.mem_profile_split(key=f"{key}:dp")
                         or {}).get("peak", {}).get("model_bytes")) or 0
            # the executor's shard_map contract IS the analyzer's spec
            # set: feeds P("dp") on the batch dim, state replicated
            checks[f"{name}_feed_specs_match_executor"] = all(
                a.specs[n].to_jax() == P("dp") for n in feed) and all(
                a.specs[p].to_jax() == P()
                for bs in m.main.backward_sections
                for p in bs.param_names)
            # the implied dp grad-sync plan, count AND bytes, against
            # what the executed program emitted
            checks[f"{name}_collectives_exact"] = (
                plan["count"] == stats.get("psums")
                and plan["bytes"] == stats.get("total_bytes"))
            # the op-profile attribution sees the scopes the plan named
            checks[f"{name}_scope_attributed"] = all(
                any(s.endswith(r["scope"].split("/")[-1])
                    or s == r["scope"] for s in scopes)
                for r in plan["records"]) \
                and "fwd0/dp_grad_sync_0" in scopes
            # static per-shard peak estimate against the measured peak
            checks[f"{name}_mem_within_25pct"] = measured > 0 and abs(
                a.memory["peak_bytes"] - measured) / measured <= 0.25
        return checks
    finally:
        monitor.disable()
        monitor.reset()


@pytest.mark.parametrize("check", [
    "feed_specs_match_executor", "collectives_exact", "scope_attributed",
    "mem_within_25pct"])
@pytest.mark.parametrize("model", ["bert", "gpt"])
def test_analyzer_predicts_what_a_dp_run_does(dp_conformance, model,
                                              check):
    assert dp_conformance[f"{model}_{check}"], dp_conformance


def test_cli_sharding_errors_exit_one(tmp_path):
    # serialized program + rule file seeding PT306 -> exit 1
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [4, 8])
            w = main.global_block().create_parameter(
                name="w", shape=[8, 6])
            h = L.matmul(x, w)
    prog_path = tmp_path / "prog.json"
    prog_path.write_text(main.to_json())
    rules_path = tmp_path / "rules.json"
    rules_path.write_text(json.dumps({
        "mesh": {"mp": 2},
        "rules": [["^w$", ["mp", None]], [".*", []]]}))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "program_lint.py"),
         str(prog_path), "--fetch", h.name,
         "--sharding-rules", str(rules_path)],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 1, (out.stdout, out.stderr)
    assert "PT306" in out.stdout
