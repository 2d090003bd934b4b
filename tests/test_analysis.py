"""Static Program verifier tests (ISSUE 7 tentpole).

Every seeded-bug program yields EXACTLY its expected PT code with the
op's callsite attached; all bundled static-zoo models lint with zero
errors; the Executor integration honors FLAGS_static_check=off|warn|
error with per-(program, _version) caching and no steady-state
regression; the registry drift/audit tests pin the metadata the
verifier relies on."""

import inspect
import re
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis
from paddle_tpu import layers as L
from paddle_tpu.analysis import verifier
from paddle_tpu.analysis.shape_rules import VarSpec, broadcast, ShapeError
from paddle_tpu.models import static_zoo
from paddle_tpu.ops import registry as op_registry


def _codes(result):
    return result.by_code()


def _fresh_program(build):
    """Build a program via `build(main)` inside its own guards; returns
    (main, build's return)."""
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            ret = build(main)
    return main, startup, ret


# ---------------------------------------------------------------------------
# per-code seeded-bug programs
# ---------------------------------------------------------------------------

def test_shape_mismatch_pt101_with_callsite():
    def build(main):
        a = fluid.data("a", [2, 3])
        b = fluid.data("b", [5, 4])
        out = main.global_block().create_var(name="o")
        main.global_block().append_op("mul", inputs={"X": a, "Y": b},
                                      outputs={"Out": out})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=["o"])
    assert _codes(r) == {"PT101": 1}
    d = r.errors[0]
    assert d.op_type == "mul" and d.op_index == 0
    assert d.callsite and "test_analysis.py" in d.callsite


def test_dtype_mismatch_pt102_float_ids_into_lookup():
    def build(main):
        ids = fluid.data("ids", [4, 3], dtype="float32")  # wrong
        return L.embedding(ids, size=(10, 8))

    main, _, emb = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=[emb.name])
    assert "PT102" in _codes(r)
    assert r.errors[0].op_type == "lookup_table_v2"


def test_use_before_def_pt103_undeclared():
    def build(main):
        out = main.global_block().create_var(name="o")
        main.global_block().append_op("relu", inputs={"X": "ghost"},
                                      outputs={"Out": out})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=["o"])
    assert "PT103" in _codes(r)
    assert r.errors[0].var == "ghost"


def test_use_before_def_pt103_produced_later():
    def build(main):
        a = fluid.data("a", [2, 2])
        blk = main.global_block()
        blk.create_var(name="late")
        blk.append_op("relu", inputs={"X": "late"}, outputs={"Out": "o"})
        blk.append_op("sigmoid", inputs={"X": a},
                      outputs={"Out": "late"})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=["o", "late"])
    [d] = [d for d in r.errors if d.code == "PT103"]
    assert "before the op that produces it" in d.message


def test_missing_fetch_pt104():
    def build(main):
        a = fluid.data("a", [2, 2])
        return L.relu(a)

    main, _, out = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=[out.name, "nope"])
    assert _codes(r) == {"PT104": 1}
    assert r.errors[0].var == "nope"


def test_unregistered_op_pt105():
    def build(main):
        a = fluid.data("a", [2, 2])
        main.global_block().append_op("frobnicate", inputs={"X": a},
                                      outputs={"Out": "o"})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=["o"])
    assert "PT105" in _codes(r)
    assert r.errors[0].op_type == "frobnicate"


def test_stateful_alias_hazard_pt106():
    def build(main):
        blk = main.global_block()
        p = blk.create_parameter(name="w", shape=[4], dtype="float32")
        g = fluid.data("g", [4])
        lr = fluid.data("lr", [1])
        blk.create_var(name="not_w", shape=[4])
        blk.append_op("sgd",
                      inputs={"Param": p, "Grad": g,
                              "LearningRate": lr},
                      outputs={"ParamOut": "not_w"})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=["not_w"])
    assert "PT106" in _codes(r)
    assert r.errors[0].var == "w"
    # the well-formed alias (ParamOut=Param) is clean
    def build_ok(main):
        blk = main.global_block()
        p = blk.create_parameter(name="w", shape=[4], dtype="float32")
        g = fluid.data("g", [4])
        lr = fluid.data("lr", [1])
        blk.append_op("sgd",
                      inputs={"Param": p, "Grad": g,
                              "LearningRate": lr},
                      outputs={"ParamOut": p})

    main_ok, _, _ = _fresh_program(build_ok)
    assert analysis.check_program(main_ok, fetch_names=[]).ok


def test_dp_divisibility_pt107():
    def build(main):
        a = fluid.data("a", [6, 4])
        return L.relu(a)

    main, _, out = _fresh_program(build)
    bad = analysis.check_program(main, fetch_names=[out.name],
                                 dp_ndev=4)
    assert "PT107" in _codes(bad) and bad.errors[0].var == "a"
    ok = analysis.check_program(main, fetch_names=[out.name], dp_ndev=2)
    assert ok.ok
    # dynamic batch dim (None) can't be checked statically -> clean
    def build_dyn(main):
        a = fluid.data("a2", [None, 4])
        return L.relu(a)

    main2, _, out2 = _fresh_program(build_dyn)
    assert analysis.check_program(main2, fetch_names=[out2.name],
                                  dp_ndev=4).ok


def test_backward_loss_undefined_pt108():
    def build(main):
        a = fluid.data("a", [2, 2])
        h = L.relu(a)
        from paddle_tpu.framework.program import BackwardSection

        main.backward_sections.append(
            BackwardSection(len(main.global_block().ops),
                            "no_such_loss", []))
        return h

    main, _, h = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=[h.name])
    assert "PT108" in _codes(r)


def test_dead_op_pt201_and_dead_var_pt202():
    def build(main):
        a = fluid.data("a", [2, 2])
        kept = L.relu(a)
        L.sigmoid(a)                      # dead op
        main.global_block().create_var(name="lonely")  # dead var
        return kept

    main, _, kept = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=[kept.name])
    codes = _codes(r)
    assert codes.get("PT201") == 1 and codes.get("PT202") == 1
    assert not r.errors
    # without fetch info the fetch-dependent lints are suppressed
    assert analysis.check_program(main, fetch_names=None).ok


def test_write_after_write_pt203():
    def build(main):
        a = fluid.data("a", [2, 2])
        blk = main.global_block()
        blk.append_op("relu", inputs={"X": a}, outputs={"Out": "w"})
        blk.append_op("tanh", inputs={"X": a}, outputs={"Out": "w"})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=["w"])
    assert "PT203" in _codes(r)
    assert r.warnings[0].var == "w"


def test_opaque_fallback_pt204_warning_not_error():
    def build(main):
        a = fluid.data("a", [2, 3, 4])
        blk = main.global_block()
        # registered kernel, deliberately no shape rule + not opaque
        blk.append_op("kron", inputs={"X": a, "Y": a},
                      outputs={"Out": "k"})
        blk.append_op("relu", inputs={"X": "k"}, outputs={"Out": "o"})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=["o"])
    assert not r.errors          # degraded, never a false error
    assert "PT204" in _codes(r)


def test_nonscalar_loss_pt205():
    def build(main):
        a = fluid.data("a", [4, 3])
        y = fluid.data("y", [4, 3])
        loss = L.square_error_cost(L.relu(a), y)   # [4, 3], no mean
        fluid.backward.append_backward(loss)
        return loss

    main, _, loss = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=[loss.name])
    assert "PT205" in _codes(r)


def test_param_unreachable_pt206():
    def build(main):
        x = fluid.data("x", [4, 3])
        y = fluid.data("y", [4, 1])
        pred = L.fc(x, 1)
        # an unrelated parameter, not on the loss path
        main.global_block().create_parameter(
            name="orphan_w", shape=[3, 3], dtype="float32")
        loss = L.mean(L.square_error_cost(pred, y))
        fluid.backward.append_backward(loss)
        return loss

    main, _, loss = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=[loss.name])
    [d] = [d for d in r.warnings if d.code == "PT206"]
    assert d.var == "orphan_w"


def test_collective_outside_mesh_pt207():
    def build(main):
        a = fluid.data("a", [2, 2])
        main.global_block().append_op(
            "c_allreduce_sum", inputs={"X": a}, outputs={"Out": "o"})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=["o"])
    assert "PT207" in _codes(r)
    # with a mesh the collective is expected
    r2 = analysis.check_program(main, fetch_names=["o"], dp_ndev=2)
    assert "PT207" not in _codes(r2)


def test_donated_then_fetched_pt208():
    def build(main):
        x = fluid.data("x", [4, 3])
        y = fluid.data("y", [4, 1])
        pred = L.fc(x, 1)
        loss = L.mean(L.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
        w = [p for p in main.all_parameters()][0]
        return loss, w

    main, _, (loss, w) = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=[loss.name, w.name])
    [d] = [d for d in r.warnings if d.code == "PT208"]
    assert d.var == w.name
    # fetching only the loss is clean
    assert analysis.check_program(main, fetch_names=[loss.name]).ok


def test_rule_crash_degrades_pt209(monkeypatch):
    def boom(op, ins, attrs):
        raise RuntimeError("kaboom")

    monkeypatch.setitem(verifier.sr._RULES, "relu", boom)

    def build(main):
        a = fluid.data("a", [2, 2])
        return L.relu(a)

    main, _, out = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=[out.name])
    assert not r.errors
    assert "PT209" in _codes(r)


# ---------------------------------------------------------------------------
# rule-level unit tests
# ---------------------------------------------------------------------------

def test_broadcast_axis_semantics():
    # axis=1 aligns a [C] bias into [N, C, H, W]
    assert broadcast((2, 3, 4, 5), (3,), 1) == (2, 3, 4, 5)
    # trailing numpy broadcast
    assert broadcast((2, 3), (3,), -1) == (2, 3)
    # unknown dims stay unknown but compatible
    assert broadcast((None, 3), (3,), -1) == (None, 3)
    with pytest.raises(ShapeError):
        broadcast((2, 3), (4,), -1)


def test_conv_pool_shape_rules_match_runtime():
    def build(main):
        img = fluid.data("img", [8, 3, 17, 17])
        c = L.conv2d(img, 6, 5, stride=2, padding=1)
        return L.pool2d(c, 2, "max", 2)

    main, startup, out = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=[out.name])
    assert r.ok
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    got = exe.run(main,
                  feed={"img": np.zeros((8, 3, 17, 17), "float32")},
                  fetch_list=[out.name], scope=scope)
    # rule and runtime agree: conv (17+2-5)//2+1=8 -> pool 8//2=4
    assert got[0].shape == (8, 6, 4, 4)


def test_conv_channel_mismatch_is_error():
    def build(main):
        img = fluid.data("img", [2, 3, 8, 8])
        blk = main.global_block()
        w = blk.create_parameter(name="wconv", shape=[4, 5, 3, 3],
                                 dtype="float32")   # wants 5 channels
        blk.create_var(name="co")
        blk.append_op("conv2d", inputs={"Input": img, "Filter": w},
                      outputs={"Output": "co"},
                      attrs={"strides": [1, 1], "paddings": [1, 1],
                             "dilations": [1, 1], "groups": 1,
                             "data_format": "NCHW"})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=["co"])
    assert "PT101" in _codes(r)


def test_reshape_rule_semantics():
    def build(main):
        a = fluid.data("a", [4, 6])
        return L.reshape(a, shape=[0, 2, 3])     # 0 copies dim 0

    main, _, out = _fresh_program(build)
    assert analysis.check_program(main, fetch_names=[out.name]).ok

    def build_bad(main):
        a = fluid.data("b", [4, 6])
        return L.reshape(a, shape=[5, 5])        # 25 != 24

    main2, _, out2 = _fresh_program(build_bad)
    r = analysis.check_program(main2, fetch_names=[out2.name])
    assert "PT101" in _codes(r)


def test_concat_mismatch_is_error():
    def build(main):
        a = fluid.data("a", [2, 3])
        b = fluid.data("b", [3, 3])
        return L.concat([a, b], axis=1)          # dim 0 differs

    main, _, out = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=[out.name])
    assert "PT101" in _codes(r)
    assert r.errors[0].op_type == "concat"


def test_optimizer_grad_shape_mismatch():
    def build(main):
        blk = main.global_block()
        p = blk.create_parameter(name="w", shape=[4, 4],
                                 dtype="float32")
        g = fluid.data("g", [2, 2])
        lr = fluid.data("lr", [1])
        blk.append_op("sgd",
                      inputs={"Param": p, "Grad": g,
                              "LearningRate": lr},
                      outputs={"ParamOut": p})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=[])
    assert "PT101" in _codes(r)


def test_opaque_operand_never_false_errors_downstream():
    # an OPAQUE producer feeding elementwise_add must leave the result
    # unknown — inferring the known side's shape would raise a false
    # PT101 at the reshape below (the program is valid)
    def build(main):
        a = fluid.data("a", [16, 10])
        blk = main.global_block()
        blk.append_op("kron", inputs={"X": a, "Y": a},
                      outputs={"Out": "h"})        # no rule -> opaque
        bias = fluid.data("bias", [10])
        blk.append_op("elementwise_add",
                      inputs={"X": "h", "Y": bias},
                      outputs={"Out": "o"}, attrs={"axis": -1})
        blk.append_op("reshape2", inputs={"X": "o"},
                      outputs={"Out": "r"},
                      attrs={"shape": [256, 100]})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=["r"])
    assert not r.errors, r.render()


def test_sub_block_shape_mismatch_is_caught():
    # control-flow sub-blocks get the reduced shape pass: a blatant
    # inner mul mismatch is reported, not silently skipped
    def build(main):
        a = fluid.data("a", [2, 3])
        b = fluid.data("b", [5, 4])
        sub = main.create_block()
        sub.append_op("mul", inputs={"X": a, "Y": b},
                      outputs={"Out": "inner_o"})
        main.rollback()
        main.global_block().append_op(
            "cond", inputs={"Pred": a}, outputs={"Out": ["o"]},
            attrs={"true_block": sub.idx, "false_block": sub.idx,
                   "true_outs": ["inner_o"], "false_outs": ["inner_o"]})

    main, _, _ = _fresh_program(build)
    r = analysis.check_program(main, fetch_names=["o"])
    [d] = [d for d in r.errors if d.code == "PT101"]
    assert d.op_type == "mul" and "block 1" in d.message


def test_static_zoo_build_does_not_mask_builder_keyerror(monkeypatch):
    def bad_builder():
        raise KeyError("inner-lookup")

    monkeypatch.setitem(static_zoo.BUILDERS, "mlp", bad_builder)
    with pytest.raises(KeyError, match="inner-lookup"):
        static_zoo.build("mlp")
    with pytest.raises(KeyError, match="unknown static model"):
        static_zoo.build("no_such_model")


def test_matmul_batch_rank_broadcast_matches_runtime():
    # differing batch ranks broadcast numpy-style: [5,4,6]@[2,5,6,7]
    import jax.numpy as jnp

    from paddle_tpu.analysis import shape_rules as sr

    class _Op:
        type = "matmul"
        inputs = {"X": ["x"], "Y": ["y"]}
        outputs = {"Out": ["o"]}

    out = sr._matmul_rule(
        _Op(), {"X": [VarSpec((5, 4, 6), "float32")],
                "Y": [VarSpec((2, 5, 6, 7), "float32")]}, {})
    real = jnp.matmul(jnp.zeros((5, 4, 6)),
                      jnp.zeros((2, 5, 6, 7))).shape
    assert out["Out"].shape == real


def test_conv_padding_forms_match_runtime():
    # asymmetric 4-element paddings + padding_algorithm=VALID both
    # mirror the runtime's _conv_pad normalization
    import jax.numpy as jnp

    from paddle_tpu.analysis import shape_rules as sr
    from paddle_tpu.ops.registry import get_op

    class _Op:
        type = "conv2d"
        inputs = {"Input": ["x"], "Filter": ["w"]}
        outputs = {"Output": ["o"]}

    x = jnp.zeros((1, 3, 8, 8))
    w = jnp.zeros((4, 3, 3, 3))
    for attrs in (
            {"strides": [1, 1], "paddings": [2, 0, 2, 0],
             "dilations": [1, 1], "groups": 1, "data_format": "NCHW"},
            {"strides": [1, 1], "paddings": [2, 2],
             "dilations": [1, 1], "groups": 1, "data_format": "NCHW",
             "padding_algorithm": "VALID"}):
        real = get_op("conv2d").fn(
            {"Input": x, "Filter": w}, attrs)["Output"].shape
        inf = sr._conv2d_rule(
            _Op(), {"Input": [VarSpec((1, 3, 8, 8), "float32")],
                    "Filter": [VarSpec((4, 3, 3, 3), "float32")]},
            attrs)["Output"].shape
        assert inf == real, (attrs, inf, real)


def test_varspec_lattice_basics():
    s = VarSpec((None, 3), "float32")
    assert s.rank == 2 and s.numel() is None
    assert VarSpec((2, 3), "f4").numel() == 6
    assert VarSpec((-1, 3)).shape == (None, 3)   # -1 normalized
    assert analysis.OPAQUE.shape is None and analysis.OPAQUE.dtype is None


# ---------------------------------------------------------------------------
# bundled model zoo: clean lints + registry drift
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(static_zoo.BUILDERS))
def test_zoo_model_lints_clean(name):
    m = static_zoo.build(name)
    r = analysis.check_program(m.main, fetch_names=m.fetches)
    assert r.ok, r.render()
    rs = analysis.check_program(m.startup, fetch_names=[])
    assert rs.ok, rs.render()


def test_zoo_smoke_executes():
    # the zoo is a real artifact, not a lint prop: one smoke step
    m = static_zoo.build("mlp")
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(m.startup, scope=scope)
    out = exe.run(m.main, feed=m.smoke_feed(batch=4),
                  fetch_list=m.fetches, scope=scope)
    assert np.isfinite(np.asarray(out[0])).all()


def test_registry_drift_every_zoo_op_has_kernel_and_rule():
    """Every op type emitted by the bundled model builders has a
    registered kernel AND a shape rule or an explicit OPAQUE entry —
    new layers can't silently outrun the verifier."""
    missing_kernel, missing_rule = [], []
    for name, model in static_zoo.build_all().items():
        for t in sorted(model.op_types()):
            if not op_registry.has_op(t):
                missing_kernel.append((name, t))
            if not (analysis.has_shape_rule(t) or analysis.is_opaque(t)):
                missing_rule.append((name, t))
    assert not missing_kernel, missing_kernel
    assert not missing_rule, missing_rule


def test_registry_drift_no_stale_opaque_entries():
    """The drift test fails on STALE opaque entries too (ISSUE 12
    satellite): an op family marked register_opaque that now has a
    real shape rule means the rule silently never runs (infer_specs
    checks is_opaque first) — retire the opaque marker when the rule
    lands."""
    from paddle_tpu.analysis import shape_rules

    stale = shape_rules.stale_opaque_entries()
    assert not stale, (
        f"register_opaque entries shadowing real shape rules "
        f"(remove them from the opaque list): {stale}")


def test_stale_opaque_audit_detects_seeded_overlap():
    """The audit itself works: seed one overlap, see it reported,
    clean up."""
    from paddle_tpu.analysis import shape_rules

    assert "relu" in shape_rules._RULES
    shape_rules._OPAQUE_OPS.add("relu")
    try:
        assert shape_rules.stale_opaque_entries() == ["relu"]
    finally:
        shape_rules._OPAQUE_OPS.discard("relu")
    assert not shape_rules.stale_opaque_entries()


def test_stateful_audit_every_out_aliasing_kernel_is_tagged():
    """Registry audit (ISSUE 7 satellite): any kernel whose source
    returns a '<X>Out' slot while reading ins['<X>'] performs a
    logical in-place update and MUST be tagged stateful=True, or the
    donation-hazard pass (PT106) is blind to it."""
    untagged = []
    for name in op_registry.list_ops():
        od = op_registry._OPS[name]
        try:
            src = inspect.getsource(od.fn)
        except (OSError, TypeError):
            continue
        ins = set(re.findall(r"ins\[\s*['\"](\w+)['\"]\s*\]", src))
        ins |= set(re.findall(r"ins\.get\(\s*['\"](\w+)['\"]", src))
        outs = set(re.findall(r"['\"](\w+Out)['\"]", src))
        if any(o[:-3] in ins for o in outs) and not od.stateful:
            untagged.append(name)
    assert not untagged, (
        f"*Out-aliasing kernels missing stateful=True: {untagged}")


# ---------------------------------------------------------------------------
# executor integration: off | warn | error + caching
# ---------------------------------------------------------------------------

def _mlp_program():
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 8])
            y = fluid.data("y", [None, 1])
            pred = L.fc(x, 1)
            loss = L.mean(L.square_error_cost(pred, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _feed(batch=4):
    rng = np.random.default_rng(0)
    return {"x": rng.standard_normal((batch, 8)).astype("float32"),
            "y": rng.standard_normal((batch, 1)).astype("float32")}


@pytest.fixture
def static_check_flag():
    before = fluid.get_flags("static_check")["FLAGS_static_check"]
    yield
    fluid.set_flags({"FLAGS_static_check": before})


def test_flag_error_raises_pre_trace_with_op_and_callsite(
        static_check_flag):
    def build(main):
        a = fluid.data("a", [2, 3])
        b = fluid.data("b", [5, 4])
        out = main.global_block().create_var(name="o")
        main.global_block().append_op("mul", inputs={"X": a, "Y": b},
                                      outputs={"Out": out})

    main, _, _ = _fresh_program(build)
    fluid.set_flags({"FLAGS_static_check": "error"})
    exe = fluid.Executor()
    with pytest.raises(analysis.ProgramLintError) as ei:
        exe.run(main, feed={"a": np.zeros((2, 3), "f"),
                            "b": np.zeros((5, 4), "f")},
                fetch_list=["o"], scope=fluid.Scope())
    msg = str(ei.value)
    assert "PT101" in msg and "mul" in msg
    assert "test_analysis.py" in msg          # callsite survives


def test_flag_warn_warns_once_and_still_runs(static_check_flag):
    def build(main):
        a = fluid.data("a", [2, 2])
        kept = L.relu(a)
        L.sigmoid(a)                          # dead op -> warning
        return kept

    main, startup, kept = _fresh_program(build)
    fluid.set_flags({"FLAGS_static_check": "warn"})
    exe = fluid.Executor()
    scope = fluid.Scope()
    feed = {"a": np.ones((2, 2), "f")}
    with pytest.warns(analysis.ProgramLintWarning, match="PT201"):
        out = exe.run(main, feed=feed, fetch_list=[kept.name],
                      scope=scope)
    assert np.allclose(out[0], 1.0)
    # second run: cache hit, NO second warning
    import warnings as w

    with w.catch_warnings(record=True) as caught:
        w.simplefilter("always")
        exe.run(main, feed=feed, fetch_list=[kept.name], scope=scope)
    assert not [c for c in caught
                if issubclass(c.category, analysis.ProgramLintWarning)]


def test_flag_off_matches_never_linted_byte_for_byte(static_check_flag):
    main, startup, loss = _mlp_program()
    feed = _feed()
    fluid.set_flags({"FLAGS_static_check": "off"})
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    baseline = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    n0 = verifier.analysis_runs
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert verifier.analysis_runs == n0      # verifier never invoked
    assert not hasattr(main, "_lint_cache")
    # identical numerics to a warn-mode executor over a fresh scope
    main2, startup2, loss2 = _mlp_program()
    fluid.set_flags({"FLAGS_static_check": "warn"})
    exe2 = fluid.Executor()
    scope2 = fluid.Scope()
    exe2.run(startup2, scope=scope2)
    checked = exe2.run(main2, feed=feed, fetch_list=[loss2],
                       scope=scope2)
    np.testing.assert_array_equal(np.asarray(baseline[0]),
                                  np.asarray(checked[0]))


def test_lint_cache_hits_across_runs_and_invalidates_on_bump(
        static_check_flag):
    main, startup, loss = _mlp_program()
    fluid.set_flags({"FLAGS_static_check": "warn"})
    exe = fluid.Executor()
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed = _feed()
    n0 = verifier.analysis_runs
    for _ in range(5):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert verifier.analysis_runs - n0 == 1   # one analysis, 4 hits
    main._bump()
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert verifier.analysis_runs - n0 == 2   # bump invalidated


def test_cached_check_fresh_flag_and_cache_cap():
    main, _, loss = _mlp_program()
    r1, fresh1 = analysis.cached_check(main, fetch_names=[loss.name])
    r2, fresh2 = analysis.cached_check(main, fetch_names=[loss.name])
    assert fresh1 and not fresh2 and r1 is r2
    # distinct fetch tuples are distinct entries; cap keeps it bounded
    for i in range(20):
        analysis.cached_check(main, fetch_names=[loss.name, str(i)])
    assert len(main._lint_cache) <= verifier._CACHE_CAP


def test_no_steady_state_dispatch_regression(static_check_flag):
    """dispatch_overhead-style check: with the lint cache hot, warn
    mode's per-run overhead is bounded (a dict probe, not a re-lint)."""
    import time as _t

    main, startup, loss = _mlp_program()
    exe = fluid.Executor()
    scope = fluid.Scope()
    feed = _feed()

    def loop(n=30):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                return_numpy=False)          # warm: trace+lint
        t0 = _t.perf_counter()
        for _ in range(n):
            out = exe.run(main, feed=feed, fetch_list=[loss],
                          scope=scope, return_numpy=False)
        dt = (_t.perf_counter() - t0) / n
        np.asarray(out[0])
        return dt

    fluid.set_flags({"FLAGS_static_check": "off"})
    exe.run(startup, scope=scope)
    t_off = min(loop() for _ in range(3))
    fluid.set_flags({"FLAGS_static_check": "warn"})
    t_warn = min(loop() for _ in range(3))
    n0 = verifier.analysis_runs
    loop()
    assert verifier.analysis_runs == n0       # steady state: 0 lints
    # generous bound: cache-hit overhead must stay in the noise, not
    # reintroduce a per-step analysis (which costs ~1000x more)
    assert t_warn < t_off * 3 + 2e-3, (t_off, t_warn)


def test_kind_lint_record_rides_telemetry_stream(tmp_path,
                                                static_check_flag):
    from paddle_tpu import monitor

    def build(main):
        a = fluid.data("a", [2, 2])
        kept = L.relu(a)
        L.sigmoid(a)                          # dead op -> 1 warning
        return kept

    main, _, kept = _fresh_program(build)
    jsonl = str(tmp_path / "tele.jsonl")
    monitor.reset()
    monitor.enable(jsonl_path=jsonl)
    fluid.set_flags({"FLAGS_static_check": "warn"})
    try:
        exe = fluid.Executor()
        scope = fluid.Scope()
        import warnings as w

        with w.catch_warnings():
            w.simplefilter("ignore")
            for _ in range(3):
                exe.run(main, feed={"a": np.ones((2, 2), "f")},
                        fetch_list=[kept.name], scope=scope)
        recs = [r for r in monitor.read_jsonl(jsonl)
                if r.get("kind") == "lint"]
        assert len(recs) == 1                 # once per program version
        assert recs[0]["warnings"] == 1
        assert recs[0]["codes"] == {"PT201": 1}
        for r in recs:
            # serialized lines are rank-stamped (ISSUE 10); the
            # in-process records stay clean
            for k in monitor.rank_tag():
                r.pop(k, None)
        assert monitor.lint_records() == recs
    finally:
        monitor.disable()
        monitor.reset()


def test_flight_recorder_carries_lint_record(static_check_flag):
    from paddle_tpu import monitor

    fr = monitor.flight_recorder.get()
    if not fr.enabled:
        pytest.skip("flight recorder disabled")
    fr.clear()

    def build(main):
        a = fluid.data("a", [2, 2])
        kept = L.relu(a)
        L.sigmoid(a)                          # dead op -> 1 warning
        return kept

    main, _, kept = _fresh_program(build)
    fluid.set_flags({"FLAGS_static_check": "warn"})
    exe = fluid.Executor()
    import warnings as w

    with w.catch_warnings():
        w.simplefilter("ignore")
        exe.run(main, feed={"a": np.ones((2, 2), "f")},
                fetch_list=[kept.name], scope=fluid.Scope())
    try:
        snap = fr.snapshot()
        [rec] = snap["lints"]
        assert rec["kind"] == "lint" and rec["codes"] == {"PT201": 1}
        assert any(e.get("event") == "lint" for e in snap["events"])
    finally:
        fr.clear()


def test_telemetry_report_lint_section(tmp_path):
    sys.path.insert(0, "tools")
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    records = [
        {"kind": "lint", "key": "progA:v1", "errors": 0,
         "warnings": 2, "codes": {"PT201": 2}},
        {"kind": "lint", "key": "progA:v2", "errors": 1,
         "warnings": 0, "codes": {"PT103": 1},
         "first_error": "PT103 error: ..."},
        {"kind": "step", "ts_us": 1.0, "step_time_s": 0.1},
    ]
    out = telemetry_report.summarize(records)
    lint = out["lint"]
    assert lint["programs"] == 2
    assert lint["errors_total"] == 1 and lint["warnings_total"] == 2
    assert lint["codes_total"] == {"PT103": 1, "PT201": 2}


# ---------------------------------------------------------------------------
# satellites: did-you-mean, CLI, the zoo in one pass
# ---------------------------------------------------------------------------

def test_block_var_did_you_mean():
    def build(main):
        fluid.data("learning_rate", [1])
        fluid.data("labels", [None, 1])

    main, _, _ = _fresh_program(build)
    with pytest.raises(ValueError) as ei:
        main.global_block().var("learing_rate")   # typo
    assert "did you mean" in str(ei.value)
    assert "learning_rate" in str(ei.value)
    # no close match -> plain error, no noise
    with pytest.raises(ValueError) as ei2:
        main.global_block().var("zzz_qqq")
    assert "did you mean" not in str(ei2.value)


def test_program_lint_cli_all_models_and_json_roundtrip(tmp_path):
    r = subprocess.run(
        [sys.executable, "tools/program_lint.py", "--model", "mlp"],
        capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "mlp/main" in r.stdout and "0 error(s)" in r.stdout

    # serialized-program path: seed a bug, expect exit 1 + the code
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            a = fluid.data("a", [2, 3])
            b = fluid.data("b", [5, 4])
            main.global_block().create_var(name="o")
            main.global_block().append_op(
                "mul", inputs={"X": a, "Y": b}, outputs={"Out": "o"})
    path = tmp_path / "bad.json"
    path.write_text(main.to_json())
    r2 = subprocess.run(
        [sys.executable, "tools/program_lint.py", str(path),
         "--fetch", "o"],
        capture_output=True, text=True, timeout=240)
    assert r2.returncode == 1
    assert "PT101" in r2.stdout


@pytest.fixture(scope="module")
def zoo_lint():
    """The whole bundled zoo through the verifier in one pass (main and
    startup, keyed `<model>/<section>`), and one program seeded with
    four different bugs through it once; each statement about the pass
    is a case below."""
    results, ops = {}, {}
    for name, model in sorted(static_zoo.build_all().items()):
        for section, prog, fetches in (("main", model.main, model.fetches),
                                       ("startup", model.startup, [])):
            key = f"{name}/{section}"
            results[key] = analysis.check_program(
                prog, fetch_names=fetches, program_key=key)
            ops[key] = sum(len(b.ops) for b in prog.blocks)

    def build(main):
        a = fluid.data("a", [2, 3])
        b = fluid.data("b", [5, 4])
        block = main.global_block()
        block.append_op("mul", inputs={"X": a, "Y": b},
                        outputs={"Out": block.create_var(name="o")})
        block.append_op("relu", inputs={"X": "ghost"},
                        outputs={"Out": block.create_var(name="r")})
        block.append_op("no_such_op", inputs={"X": a},
                        outputs={"Out": block.create_var(name="n")})
        L.sigmoid(a)                      # never fetched, never read

    seeded, _, _ = _fresh_program(build)
    codes = set(_codes(analysis.check_program(
        seeded, fetch_names=["o", "r", "n"])))
    return {
        "zoo_covered": {k.split("/")[0] for k in results}
        == set(static_zoo.BUILDERS) and len(results) == 2 * len(
            static_zoo.BUILDERS),
        "zoo_zero_errors": all(not r.errors for r in results.values()),
        "every_linted_program_has_ops": all(n > 0 for n in ops.values()),
        # the verifier does not stop at a program's first error
        "one_pass_reports_every_seeded_bug":
            {"PT101", "PT103", "PT105", "PT201"} <= codes,
    }


@pytest.mark.parametrize("check", [
    "zoo_covered", "zoo_zero_errors", "every_linted_program_has_ops",
    "one_pass_reports_every_seeded_bug"])
def test_one_lint_pass_over_the_zoo(zoo_lint, check):
    assert zoo_lint[check], zoo_lint
