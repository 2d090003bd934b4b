"""Fusion pass tier + AMP-by-default (ISSUE 14).

Covers: per-pattern matching on the static zoo, numerics parity of
every fused pattern vs its unfused subgraph at fp32 AND bf16,
idempotence, lint-cleanliness (PT1xx + PT3xx under the default
Megatron rules), folded_from provenance through Program.clone and the
executor substitutes, the canonical AMP -> fusion -> structural order
enforcement, the executor's FLAGS_amp / FLAGS_graph_opt_fuse train
tier (default "train": fires in train_from_dataset, stays out of bare
Executor.run), and the flags-off bitwise-stability contract.

Tolerances (documented per kernel):
- fp32 fusion: the fused kernels compose the exact unfused primitives
  (elementwise_add + act, conv2d + batch_norm, add + layer_norm) or
  the same dot/softmax sequence (attention), so losses and params
  match at rtol 1e-4 / atol 1e-6 — observed exact on CPU.
- bf16 AMP configs: white-list dots compute in bf16 against the fp32
  reference -> rtol 7e-2 / atol 5e-2 on losses.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import amp, analysis, monitor, passes
from paddle_tpu.framework.executor import Scope, op_scope_names
from paddle_tpu.models import static_zoo


@pytest.fixture(autouse=True)
def _flags_off():
    """Default every test to the no-tier executor; tests that exercise
    the tier set their own flags."""
    entry = fluid.get_flags(["FLAGS_amp", "FLAGS_graph_opt_fuse",
                             "FLAGS_graph_opt"])
    fluid.set_flags({"FLAGS_amp": "off", "FLAGS_graph_opt_fuse": "off",
                     "FLAGS_graph_opt": "off"})
    yield
    fluid.set_flags(entry)


def _build(name):
    with fluid.unique_name.guard():
        return static_zoo.build(name)


def _train(model, program, steps=3, batch=8, scope=None):
    exe = fluid.Executor()
    sc = scope or Scope()
    exe.run(model.startup, scope=sc)
    losses = []
    for s in range(steps):
        out = exe.run(program, feed=model.smoke_feed(batch=batch,
                                                     seed=s),
                      fetch_list=[model.loss_name], scope=sc)
        losses.append(float(np.asarray(out[0])))
    params = {n: np.asarray(v) for n, v in sc.vars.items()
              if v is not None}
    return losses, params


def _fused_types(program):
    return [op.type for op in program.global_block().ops
            if op.type in passes.FUSED_TIER_TYPES]


# ---------------------------------------------------------------------------
# pattern matching
# ---------------------------------------------------------------------------

def test_pattern_match_counts_per_model():
    """Each matcher fires on the zoo family built to exercise it, with
    the expected multiplicity."""
    expect = {
        "bert": {"fuse_attention": 1, "fuse_bias_act": 1,
                 "fuse_layer_norm": 2},
        "gpt": {"fuse_attention": 1, "fuse_bias_act": 1,
                "fuse_layer_norm": 2},
        "resnet": {"fuse_bottleneck": 6},
        "lenet": {"fuse_bias_act": 2},
        "mlp": {"fuse_bias_act": 1},
    }
    for name, want in expect.items():
        m = _build(name)
        _, rep = passes.fuse_program(m.main,
                                     fetch_names=[m.loss_name],
                                     record=False)
        got = {r["name"]: r["matched"] for r in rep["passes"]
               if r.get("matched")}
        assert got == want, (name, got)
        assert rep["patterns_matched"] == sum(want.values())


def test_attention_ring_absorbed_and_kernel_dispatch():
    """The zoo's split-heads reshape/transpose ring is absorbed into
    the fused op (head_number recorded), and the anchor keeps the
    ring's output name so downstream reads are untouched."""
    m = _build("bert")
    fused, _ = passes.fuse_program(m.main, fetch_names=[m.loss_name],
                                   record=False)
    fa = next(op for op in fused.global_block().ops
              if op.type == "fused_attention")
    assert fa.attrs["head_number"] == 4
    assert fa.attrs["compute_dtype"] == ""
    assert set(fa.inputs) == {"Q", "K", "V"}
    types = [op.type for op in fused.global_block().ops]
    # the matmul/scale/softmax core and the 8 split + 2 merge ops are
    # gone from the forward
    assert "softmax" not in types[:fused.backward_sections[0].pos]


def test_fusion_idempotent_zoo_wide():
    for name in sorted(static_zoo.BUILDERS):
        m = _build(name)
        fused, _ = passes.fuse_program(m.main,
                                       fetch_names=[m.loss_name],
                                       record=False)
        _, rep2 = passes.fuse_program(fused,
                                      fetch_names=[m.loss_name],
                                      record=False)
        assert rep2["patterns_matched"] == 0, name
        assert rep2["ops_removed"] == 0, name


# ---------------------------------------------------------------------------
# numerics parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["bert", "resnet", "mlp"])
def test_fused_fp32_parity_losses_and_params(name):
    """fp32 fusion: fused kernels compose the exact unfused primitives
    -> losses and trained params match tightly over 3 train steps.
    For resnet this covers the STATEFUL half of fused_bottleneck too:
    the moving mean/variance must both move off their init values and
    match the unfused conv+bn+relu chain's updates."""
    m = _build(name)
    l0, p0 = _train(m, m.main)
    m2 = _build(name)
    fused, _ = passes.fuse_program(m2.main, fetch_names=[m2.loss_name],
                                   record=False)
    l1, p1 = _train(m2, fused)
    assert np.allclose(l0, l1, rtol=1e-4, atol=1e-6), (l0, l1)
    assert set(p0) == set(p1)
    for n in p0:
        assert np.allclose(p0[n], p1[n], rtol=1e-3, atol=1e-5), n
    if name == "resnet":
        moving = [n for n in p0 if "moving" in n]
        assert moving, "resnet should carry moving stats"
        for n in moving:
            init = 0.0 if "mean" in n else 1.0
            assert not np.allclose(p1[n], init), f"{n} never updated"


def test_fused_bf16_parity_vs_fp32_reference():
    """AMP configs stay allclose to the unfused fp32 reference at bf16
    tolerance (acceptance: every fused config allclose) — bert covers
    the attention/bias_act/layer_norm patterns, resnet the bottleneck;
    the remaining families are covered unfused-vs-fused at fp32 above
    and by the zoo-wide bench sweep."""
    for name in ("bert", "resnet"):
        m = _build(name)
        l_ref, _ = _train(m, m.main)
        m2 = _build(name)
        prog = m2.main.clone()
        amp.rewrite_train_program(prog)
        fused, _ = passes.fuse_program(prog,
                                       fetch_names=[m2.loss_name],
                                       clone=False, record=False)
        l_amp, _ = _train(m2, fused)
        assert np.allclose(l_amp, l_ref, rtol=7e-2, atol=5e-2), \
            (name, l_amp, l_ref)


# ---------------------------------------------------------------------------
# AMP transparency + canonical order
# ---------------------------------------------------------------------------

def test_fusion_fires_on_bf16_graph():
    """The matcher sees through AMP's inserted casts: the bf16 graph
    fuses with the SAME pattern counts as fp32, and the fused ops
    record the compute dtype the absorbed casts carried."""
    m = _build("bert")
    _, rep_fp32 = passes.fuse_program(m.main,
                                      fetch_names=[m.loss_name],
                                      record=False)
    m2 = _build("bert")
    prog = m2.main.clone()
    amp.rewrite_train_program(prog)
    fused, rep_bf16 = passes.fuse_program(prog,
                                          fetch_names=[m2.loss_name],
                                          clone=False, record=False)
    counts = lambda rep: {r["name"]: r.get("matched", 0)
                          for r in rep["passes"]}
    assert counts(rep_bf16) == counts(rep_fp32)
    fa = next(op for op in fused.global_block().ops
              if op.type == "fused_attention")
    assert fa.attrs["compute_dtype"] == "bfloat16"


def test_amp_rewrite_train_program_remaps_sections():
    """Cast insertion shifts op positions; the backward-section marker
    must still split the list at the same logical boundary."""
    m = _build("mlp")
    prog = m.main.clone()
    before_pos = prog.backward_sections[0].pos
    before_ops = len(prog.global_block().ops)
    amp.rewrite_train_program(prog)
    casts = sum(1 for op in prog.global_block().ops
                if op.type == "cast")
    assert casts > 0 and prog.amp_enabled
    after_pos = prog.backward_sections[0].pos
    assert after_pos > before_pos
    # the op AT the boundary is unchanged (first update-section op)
    assert len(prog.global_block().ops) == before_ops + casts


def test_canonical_order_enforced():
    """AMP after fusion is a loud error naming the flag; AMP before
    fusion (the executor's order) and re-AMP idempotence both work;
    the public rewrite still refuses minimized programs."""
    m = _build("bert")
    fused, _ = passes.fuse_program(m.main, fetch_names=[m.loss_name],
                                   record=False)
    with pytest.raises(ValueError, match="FLAGS_graph_opt_fuse"):
        amp.rewrite_train_program(fused)
    with pytest.raises(ValueError, match="canonical order"):
        amp.rewrite_train_program(fused)
    # correct order passes, and is idempotent
    m2 = _build("bert")
    prog = m2.main.clone()
    amp.rewrite_train_program(prog)
    n_ops = len(prog.global_block().ops)
    amp.rewrite_train_program(prog)          # no-op, no double casts
    assert len(prog.global_block().ops) == n_ops
    # public pre-minimize contract unchanged
    with pytest.raises(ValueError, match="before minimize"):
        amp.rewrite_program(m2.main.clone())


# ---------------------------------------------------------------------------
# lint cleanliness
# ---------------------------------------------------------------------------

def test_fused_zoo_lint_clean_pt1xx_and_executes():
    """All 8 zoo models lint PT1xx-clean AMP'd+fused; the families not
    already executed fused elsewhere in this file (bert/gpt/resnet/
    mlp/lenet are) additionally run one train step to a finite loss —
    the acceptance's zoo-wide executable sweep."""
    execute = {"seq2seq", "wide_deep", "word2vec"}
    for name in sorted(static_zoo.BUILDERS):
        m = _build(name)
        prog = m.main.clone()
        amp.rewrite_train_program(prog)
        fused, _ = passes.fuse_program(prog, fetch_names=m.fetches,
                                       clone=False, record=False)
        res = analysis.check_program(fused, fetch_names=m.fetches)
        assert not res.errors, (name, [str(d) for d in res.errors])
        if name in execute:
            losses, _ = _train(m, fused, steps=1)
            assert np.isfinite(losses[0]), name


def test_fused_bert_pt3xx_clean_under_megatron_rules():
    """The fused bf16 bert lints PT3xx-clean under its default
    Megatron tensor-parallel rules — the fused_attention /
    fused_layer_norm / fused_bias_act propagation handlers carry the
    mp shards through."""
    from paddle_tpu.analysis.sharding import attach

    for name in ("bert", "gpt"):
        m = _build(name)
        prog = m.main.clone()
        amp.rewrite_train_program(prog)
        fused, _ = passes.fuse_program(prog, fetch_names=m.fetches,
                                       clone=False, record=False)
        attach(fused, m.partition_rules())
        res = analysis.check_program(fused, fetch_names=m.fetches)
        assert not res.errors, (name, [str(d) for d in res.errors])


# ---------------------------------------------------------------------------
# provenance
# ---------------------------------------------------------------------------

def test_folded_from_carries_source_scopes_and_survives_clone():
    """Every fused op records the absorbed ops' scope names PLUS its
    own pre-rewrite identity, and Program.clone() preserves it (the
    PR-9 invariant extended to fusion)."""
    m = _build("bert")
    fused, _ = passes.fuse_program(m.main, fetch_names=[m.loss_name],
                                   record=False)
    fa = next(op for op in fused.global_block().ops
              if op.type == "fused_attention")
    assert fa.folded_from
    joined = " ".join(fa.folded_from)
    for src in ("matmul", "softmax", "scale"):
        assert src in joined, (src, fa.folded_from)
    cl = fused.clone()
    fa2 = next(op for op in cl.global_block().ops
               if op.type == "fused_attention")
    assert fa2.folded_from == fa.folded_from
    # test-mode clone keeps the forward's fused ops + provenance too
    ev = fused.clone(for_test=True)
    assert any(getattr(op, "folded_from", ())
               for op in ev.global_block().ops)


def test_op_scope_names_resolves_train_tier():
    """op_scope_names(train_loop=True) resolves the SAME substitute a
    train_from_dataset dispatch compiles, so attribution ground truth
    includes the fused scopes with their provenance."""
    fluid.set_flags({"FLAGS_amp": "train",
                     "FLAGS_graph_opt_fuse": "train"})
    m = _build("bert")
    plain = op_scope_names(m.main, [m.loss_name])
    assert not any("fused" in s for s, _ in plain)
    tier = op_scope_names(m.main, [m.loss_name], train_loop=True)
    fused_scopes = [(s, op) for s, op in tier if "fused" in s]
    assert fused_scopes
    assert all(op.folded_from for _, op in fused_scopes)


# ---------------------------------------------------------------------------
# executor integration
# ---------------------------------------------------------------------------

def test_train_loop_substitutes_and_bare_run_does_not():
    """Default flags ("train"): bare Executor.run is untouched; the
    dataset train loop routes through AMP+fusion, emits the tagged
    pass record, and caches ONE substitute (no per-step rebuild)."""
    fluid.set_flags({"FLAGS_amp": "train",
                     "FLAGS_graph_opt_fuse": "train"})
    m = _build("bert")
    exe = fluid.Executor()
    sc = Scope()
    exe.run(m.startup, scope=sc)
    exe.run(m.main, feed=m.smoke_feed(batch=8),
            fetch_list=[m.loss_name], scope=sc)
    assert not getattr(m.main, "_opt_cache", None)

    monitor.enable()
    try:
        def ds():
            for s in range(4):
                yield m.smoke_feed(batch=8, seed=s)

        out = exe.train_from_dataset(program=m.main, dataset=ds(),
                                     scope=sc,
                                     fetch_list=[m.loss_name])
        assert np.isfinite(float(np.asarray(out[0])))
        cache = m.main._opt_cache
        assert cache and len(cache) == 1
        sub = next(iter(cache.values()))
        assert "fused_attention" in _fused_types(sub)
        assert sub.amp_enabled
        recs = [r for r in monitor.pass_pipeline_records()
                if r.get("tier") == "fusion"]
        assert recs and recs[-1]["patterns_matched"] >= 4
    finally:
        monitor.disable()


def test_fused_amp_program_is_attributed_op_by_op():
    """A multi-op fused kernel is ONE scope of the op profile, not a
    hole in it: under AMP + fusion the FLOPs that no scope owns stay
    under 1% of bert's step, casts and fused ops included."""
    fluid.set_flags({"FLAGS_amp": "on", "FLAGS_graph_opt_fuse": "on"})
    m = _build("bert")
    monitor.reset()
    monitor.enable()
    try:
        prog = fluid.CompiledProgram(m.main).with_telemetry("fused_amp")
        losses, _ = _train(m, prog)
        split = monitor.op_profile_split(key="fused_amp")
    finally:
        monitor.disable()
        monitor.reset()
    assert np.all(np.isfinite(losses))
    sub = next(iter(m.main._opt_cache.values()))
    assert "fused_attention" in _fused_types(sub)
    assert any(op.type == "cast" for op in sub.global_block().ops)
    assert any("fused" in scope for scope in split["scopes"])
    assert split["unattributed"]["flops_pct"] <= 1.0, split["unattributed"]


def test_flag_on_extends_to_bare_run_and_off_is_clean():
    fluid.set_flags({"FLAGS_amp": "on", "FLAGS_graph_opt_fuse": "on"})
    m = _build("mlp")
    exe = fluid.Executor()
    sc = Scope()
    exe.run(m.startup, scope=sc)
    out = exe.run(m.main, feed=m.smoke_feed(batch=8),
                  fetch_list=[m.loss_name], scope=sc)
    assert np.isfinite(float(np.asarray(out[0])))
    sub = next(iter(m.main._opt_cache.values()))
    assert _fused_types(sub) == ["fused_bias_act"]
    assert any(op.type == "cast" for op in sub.global_block().ops)
    # startup programs / eval clones never hit the tier
    assert not getattr(m.startup, "_opt_cache", None)
    ev = m.main.clone(for_test=True)
    exe.run(ev, feed=m.smoke_feed(batch=8),
            fetch_list=[m.loss_name], scope=sc)
    assert not getattr(ev, "_opt_cache", None)


def test_flags_off_bitwise_stable_no_substitution():
    """FLAGS_amp=off + FLAGS_graph_opt_fuse=off: the train loop never
    substitutes and two identical runs are bitwise identical — the
    acceptance's 'remains bitwise-identical to today' contract."""
    def once():
        m = _build("mlp")
        exe = fluid.Executor()
        sc = Scope()
        exe.run(m.startup, scope=sc)

        def ds():
            for s in range(3):
                yield m.smoke_feed(batch=8, seed=s)

        exe.train_from_dataset(program=m.main, dataset=ds(), scope=sc,
                               fetch_list=[m.loss_name])
        assert not getattr(m.main, "_opt_cache", None)
        return {n: np.asarray(v) for n, v in sc.vars.items()}

    a, b = once(), once()
    assert set(a) == set(b)
    for n in a:
        assert np.array_equal(a[n], b[n]), n


def test_graph_opt_composes_structural_after_fusion():
    """FLAGS_graph_opt=on + FLAGS_graph_opt_fuse=on: one substitute
    carries the fused ops AND the structural pipeline's cleanups, in
    canonical order, with outputs still matching."""
    fluid.set_flags({"FLAGS_graph_opt": "on",
                     "FLAGS_graph_opt_fuse": "on"})
    m = _build("bert")
    l1, _ = _train(m, m.main)
    fluid.set_flags({"FLAGS_graph_opt": "off",
                     "FLAGS_graph_opt_fuse": "off"})
    m2 = _build("bert")
    l0, _ = _train(m2, m2.main)
    assert np.allclose(l0, l1, rtol=1e-4, atol=1e-6)


def test_attention_mask_variant_fused():
    """An additive mask between scale and softmax rides into the fused
    op's Mask input (the masked-attention form the zoo builders don't
    emit but saved transformer programs do)."""
    from paddle_tpu import layers as L

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            q = fluid.data("q", [None, 4, 8, 8])
            k = fluid.data("k", [None, 4, 8, 8])
            v = fluid.data("v", [None, 4, 8, 8])
            mask = fluid.data("mask", [None, 4, 8, 8])
            scores = L.scale(L.matmul(q, k, transpose_y=True),
                             scale=8 ** -0.5)
            probs = L.softmax(L.elementwise_add(scores, mask))
            ctx = L.matmul(probs, v)
            loss = L.mean(ctx)
    fused, rep = passes.fuse_program(main, fetch_names=[loss.name],
                                     record=False)
    fa = next(op for op in fused.global_block().ops
              if op.type == "fused_attention")
    assert fa.inputs.get("Mask") == ["mask"]
    exe = fluid.Executor()
    rng = np.random.default_rng(0)
    feed = {n: rng.standard_normal((2, 4, 8, 8)).astype(np.float32)
            for n in ("q", "k", "v", "mask")}
    sc1, sc2 = Scope(), Scope()
    ref = exe.run(main, feed=feed, fetch_list=[loss.name], scope=sc1)
    out = exe.run(fused, feed=feed, fetch_list=[loss.name], scope=sc2)
    assert np.allclose(np.asarray(ref[0]), np.asarray(out[0]),
                       rtol=1e-5, atol=1e-6)


def test_bias_act_preserves_activation_attrs():
    """Review regression: the absorbed activation op's attrs ride into
    the fused op (a gelu(approximate=True) must stay approximate — the
    fused kernel delegating with empty attrs silently computed exact
    gelu, a ~4e-6 numerics drift the fp32-bitwise contract forbids)."""
    from paddle_tpu import layers as L

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 8])
            h = L.fc(x, 8)
            g = L.gelu(h, approximate=True)
            loss = L.mean(g)
    fused, rep = passes.fuse_program(main, fetch_names=[loss.name],
                                     record=False)
    fb = next(op for op in fused.global_block().ops
              if op.type == "fused_bias_act")
    assert fb.attrs["act_attrs"].get("approximate") is True
    import jax.numpy as jnp

    exe = fluid.Executor()
    sc1, sc2 = Scope(), Scope()
    exe.run(startup, scope=sc1)
    for n, v in sc1.vars.items():
        # host-copied params: same values, donation-decoupled buffers
        sc2.set_var(n, jnp.asarray(np.asarray(v)))
    feed = {"x": np.random.default_rng(0).standard_normal(
        (4, 8)).astype(np.float32)}
    ref = exe.run(main, feed=feed, fetch_list=[loss.name], scope=sc1)
    out = exe.run(fused, feed=feed, fetch_list=[loss.name], scope=sc2)
    assert np.array_equal(np.asarray(ref[0]), np.asarray(out[0]))


# ---------------------------------------------------------------------------
# tooling
# ---------------------------------------------------------------------------

def test_program_opt_fuse_flag(capsys):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "program_opt", os.path.join(os.path.dirname(__file__), "..",
                                    "tools", "program_opt.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--all-models", "--fuse"])
    assert rc == 0
    text = capsys.readouterr().out
    assert "fuse_attention" in text and "matched" in text


def test_telemetry_report_fusion_section():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(os.path.dirname(__file__),
                                         "..", "tools",
                                         "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    recs = [
        {"kind": "pass_pipeline", "tier": "fusion", "key": "bert",
         "patterns_matched": 4, "ops_removed": 14,
         "total_wall_ms": 3.2,
         "passes": [{"name": "fuse_attention", "matched": 1,
                     "before_ops": 58, "after_ops": 47,
                     "wall_ms": 1.1}]},
        {"kind": "pass_pipeline", "key": "bert",
         "before_ops": 44, "after_ops": 43, "ops_removed": 1,
         "passes": [{"name": "dce", "before_ops": 44,
                     "after_ops": 43, "wall_ms": 0.2}]},
    ]
    fusion = mod._fusion_section(recs)
    assert fusion["patterns_matched_total"] == 4
    assert fusion["ops_removed_total"] == 14
    assert fusion["by_program"]["bert"]["patterns"][
        "fuse_attention"]["matched"] == 1
    # the structural section must not double-book the fusion removals
    structural = mod._passes_section(recs)
    assert structural["ops_removed_total"] == 1
