"""Static numerics analyzer tests (ISSUE 15, PT4xx).

Covers the numerics classification registry (full-partition audit
against ops.registry, drift detection, AMP-list consistency), every
PT4xx code via a dedicated seeded-bug program with exact code + op
index + creation-callsite assertions, the PT406 fusion near-miss
explain mode (the named guard is the REAL blocker: flipping the guard
condition re-matches the pattern), the zoo sweep over the AMP+fused
train-tier substitutes the executor actually dispatches, the verifier/
executor wiring (pass 7 merge, amp-dtype cache re-key, off-path
byte-for-byte no-regression), the CLI's --amp/--fuse substitute
linting, and the telemetry lint-record extensions (PT4xx breakout +
top near-miss guards)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import amp, analysis, passes
from paddle_tpu import layers as L
from paddle_tpu.analysis import numerics as nu
from paddle_tpu.models import static_zoo
from paddle_tpu.ops import registry as op_registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(result):
    out = {}
    for d in result.diagnostics:
        out.setdefault(d.code, []).append(d)
    return out


def _lint(build, fetch=None, feed=()):
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            built = build(main)
    fetches = built if fetch is None else fetch
    return main, analysis.check_program(main, fetch_names=fetches,
                                        feed_names=feed)


# ---------------------------------------------------------------------------
# classification registry audit (satellite: registry drift)
# ---------------------------------------------------------------------------

def test_every_registered_op_carries_a_numerics_class():
    """Registry-drift audit: a kernel registered without a numerics
    class (white/black/neutral or an explicit opaque entry) fails —
    new ops can't silently outrun the PT4xx analyzer."""
    unclassified = sorted(
        t for t in op_registry._OPS if nu.numerics_class(t) is None)
    assert not unclassified, (
        f"ops missing a numerics class in analysis/numerics.py: "
        f"{unclassified}")


def test_numerics_classes_are_disjoint():
    sets = {"WHITE": nu.WHITE, "BLACK": nu.BLACK,
            "NEUTRAL": nu.NEUTRAL, "OPAQUE": nu.OPAQUE}
    names = sorted(sets)
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            overlap = sets[a] & sets[b]
            assert not overlap, (a, b, sorted(overlap))


def test_audit_detects_seeded_unclassified_op():
    op_registry._OPS["zz_seeded_drift_op"] = op_registry.OpDef(
        "zz_seeded_drift_op", lambda ins, attrs: {})
    try:
        unclassified = [t for t in op_registry._OPS
                        if nu.numerics_class(t) is None]
        assert "zz_seeded_drift_op" in unclassified
    finally:
        del op_registry._OPS["zz_seeded_drift_op"]


def test_amp_lists_never_contradict_numerics_classes():
    """The rewrite-time lists and the verifier's classification must
    agree: an AMP-white op the analyzer calls fragile (or vice versa)
    would make the default path flag itself."""
    assert not (amp.WHITE_LIST & nu.BLACK), \
        sorted(amp.WHITE_LIST & nu.BLACK)
    assert not (amp.BLACK_LIST & nu.WHITE), \
        sorted(amp.BLACK_LIST & nu.WHITE)
    # every AMP-black REGISTERED op is one the analyzer also treats as
    # fragile — the lists protect exactly what PT401/PT404 would flag
    registered_black = amp.BLACK_LIST & set(op_registry._OPS)
    assert registered_black <= nu.BLACK, \
        sorted(registered_black - nu.BLACK)


def test_accum_reductions_are_black_subset():
    assert nu.ACCUM_REDUCTIONS <= nu.BLACK


# ---------------------------------------------------------------------------
# one seeded-bug program per PT4xx code (exact code + index + callsite)
# ---------------------------------------------------------------------------

def test_seeded_pt401_fragile_op_in_bf16():
    def build(main):
        x = fluid.data("x", [None, 8])
        return [L.log(L.cast(x, "bfloat16")).name]

    _, r = _lint(build, feed=["x"])
    codes = _codes(r)
    assert set(codes) == {"PT401"}
    d = codes["PT401"][0]
    assert d.op_type == "log" and d.op_index == 1
    assert "bfloat16" in d.message
    assert d.callsite and "test_numerics.py" in d.callsite
    assert not r.ok                      # PT401 is an ERROR


def test_seeded_pt402_lost_master_copy():
    def build(main):
        p = main.global_block().create_parameter(
            name="w", shape=[4], dtype="bfloat16")
        g = fluid.data("g", [4])
        lr = fluid.data("lr", [1])
        main.global_block().append_op(
            "sgd", inputs={"Param": p, "Grad": g, "LearningRate": lr},
            outputs={"ParamOut": p})
        return None

    _, r = _lint(build, fetch=None, feed=["g", "lr"])
    codes = _codes(r)
    assert "PT402" in codes
    d = codes["PT402"][0]
    assert d.op_type == "sgd" and d.op_index == 0 and d.var == "w"
    assert "master" in d.message
    assert d.callsite and "test_numerics.py" in d.callsite


def test_seeded_pt402_low_precision_accumulator():
    """The accumulator chain counts too: a bf16 Moment under an fp32
    param is still a broken master chain."""
    def build(main):
        p = main.global_block().create_parameter(name="w", shape=[4])
        m = main.global_block().create_parameter(
            name="w_moment", shape=[4], dtype="bfloat16")
        g = fluid.data("g", [4])
        lr = fluid.data("lr", [1])
        main.global_block().append_op(
            "momentum",
            inputs={"Param": p, "Grad": g, "Velocity": m,
                    "LearningRate": lr},
            outputs={"ParamOut": p, "VelocityOut": m},
            attrs={"mu": 0.9})
        return None

    _, r = _lint(build, fetch=None, feed=["g", "lr"])
    codes = _codes(r)
    assert "PT402" in codes
    assert {d.var for d in codes["PT402"]} == {"w_moment"}


def test_seeded_pt403_duplicate_and_identity_churn():
    def build(main):
        x = fluid.data("x", [None, 8])
        a = L.cast(x, "bfloat16")
        b = L.cast(x, "bfloat16")          # duplicate of `a`'s cast
        c = L.cast(a, "bfloat16")          # identity (already bf16)
        out = L.elementwise_add(L.relu(a), L.relu(b))
        return [out.name, L.relu(c).name]

    main, r = _lint(build, feed=["x"])
    codes = _codes(r)
    assert "PT403" in codes and not r.errors
    kinds = {d.message.split("(")[1].split(")")[0]
             for d in codes["PT403"]}
    assert kinds == {"duplicate", "identity"}
    assert all(d.op_type == "cast" and d.op_index is not None
               for d in codes["PT403"])
    # both churn kinds are what the structural pipeline removes
    assert r.numerics.churn_removable == 2
    assert r.numerics.churn_bytes > 0


def test_seeded_pt403_round_trip_survives_structural_passes():
    """A down-up round trip is churn the structural pipeline CANNOT
    remove (neither cast is an identity): counted, flagged, but
    excluded from churn_removable — the conformance row's equality
    depends on that split."""
    def build(main):
        x = fluid.data("x", [None, 8])
        down = L.cast(x, "bfloat16")
        up = L.cast(down, "float32")       # straight back up
        return [L.relu(up).name]

    _, r = _lint(build, feed=["x"])
    codes = _codes(r)
    assert "PT403" in codes
    assert "round_trip" in codes["PT403"][0].message
    assert "mantissa" in codes["PT403"][0].message
    assert r.numerics.churn_removable == 0


def test_seeded_pt404_overflow_prone_accumulation():
    def build(main):
        x = fluid.data("x", [4, 100000])
        return [L.reduce_sum(L.cast(x, "bfloat16"), dim=[1]).name]

    _, r = _lint(build, feed=["x"])
    codes = _codes(r)
    assert set(codes) == {"PT404"}
    d = codes["PT404"][0]
    assert d.op_type == "reduce_sum" and d.op_index == 1
    assert "100000" in d.message
    assert d.callsite and "test_numerics.py" in d.callsite


def test_pt404_small_reduction_is_fine():
    """A small bf16 sum is exactly what AMP promises works — no lint."""
    def build(main):
        x = fluid.data("x", [4, 32])
        return [L.reduce_sum(L.cast(x, "bfloat16"), dim=[1]).name]

    _, r = _lint(build, feed=["x"])
    assert not _codes(r), r.render()


def test_seeded_pt405_fp16_without_loss_scaling():
    def build(main):
        x = fluid.data("x", [None, 8])
        y = fluid.data("y", [None, 1])
        loss = L.mean(L.square_error_cost(L.fc(x, 1), y))
        fluid.optimizer.SGD(0.1).minimize(loss)
        amp.rewrite_train_program(main, dest_dtype="float16")
        return [loss.name]

    _, r = _lint(build, feed=["x", "y"])
    codes = _codes(r)
    assert "PT405" in codes
    d = codes["PT405"][0]
    assert "loss scaling" in d.message and "anomaly" in d.message
    assert d.var and d.var.startswith("mean")


def test_pt405_silent_when_loss_is_scaled_or_bf16():
    # scaled fp16: the section loss is produced by a scale op != 1.0
    def scaled(main):
        x = fluid.data("x", [None, 8])
        y = fluid.data("y", [None, 1])
        loss = L.mean(L.square_error_cost(L.fc(x, 1), y))
        scaled_loss = L.scale(loss, scale=1024.0)
        fluid.optimizer.SGD(0.1).minimize(scaled_loss)
        amp.rewrite_train_program(main, dest_dtype="float16")
        return [scaled_loss.name]

    _, r = _lint(scaled, feed=["x", "y"])
    assert "PT405" not in _codes(r)

    # bf16 needs no scaling (fp32 exponent range)
    def bf16(main):
        x = fluid.data("x", [None, 8])
        y = fluid.data("y", [None, 1])
        loss = L.mean(L.square_error_cost(L.fc(x, 1), y))
        fluid.optimizer.SGD(0.1).minimize(loss)
        amp.rewrite_train_program(main, dest_dtype="bfloat16")
        return [loss.name]

    _, r = _lint(bf16, feed=["x", "y"])
    assert "PT405" not in _codes(r)


def _attention_program(leak):
    """matmul·scale·softmax·matmul, with an optional second consumer
    of the softmax probs that blocks fusion (the multi_consumer
    guard)."""
    main = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, fluid.Program()):
            q = fluid.data("q", [2, 4, 8, 16])
            k = fluid.data("k", [2, 4, 8, 16])
            v = fluid.data("v", [2, 4, 8, 16])
            probs = L.softmax(L.scale(L.matmul(q, k, transpose_y=True),
                                      scale=0.25))
            out = L.matmul(probs, v)
            extra = L.relu(probs) if leak else None
    fetches = [out.name] + ([extra.name] if leak else [])
    return main, fetches


def test_seeded_pt406_near_miss_names_the_real_guard():
    main, fetches = _attention_program(leak=True)
    fused, report = passes.fuse_program(main, fetch_names=fetches,
                                        record=False)
    r = analysis.check_program(fused, fetch_names=fetches)
    codes = _codes(r)
    assert "PT406" in codes
    d = codes["PT406"][0]
    assert "fuse_attention" in d.message
    assert "multi_consumer" in d.message
    assert d.callsite and "test_numerics.py" in d.callsite
    # exact anchor index in the FINAL (post-fusion) op list
    nm = fused._fusion_near_misses[0]
    ops = fused.global_block().ops
    assert ops[nm["anchor_index"]].type == "softmax"
    assert d.op_index == nm["anchor_index"]
    # the report carries the guard tally for the telemetry surfaces
    assert report["near_miss_guards"] == {"multi_consumer": 1}


def test_pt406_guard_flip_rematches():
    """The explanation names the REAL blocker: removing the second
    consumer (flipping the guard's condition) re-matches the pattern
    and the near-miss disappears."""
    main, fetches = _attention_program(leak=False)
    fused, _ = passes.fuse_program(main, fetch_names=fetches,
                                   record=False)
    assert any(op.type == "fused_attention"
               for op in fused.global_block().ops)
    assert not getattr(fused, "_fusion_near_misses", [])
    r = analysis.check_program(fused, fetch_names=fetches)
    assert "PT406" not in _codes(r)


def test_pt406_section_boundary_guard_named():
    """A pattern straddling a backward-section boundary is refused by
    the section_boundary guard — and the explanation says so."""
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [None, 8])
            h = L.fc(x, 8)
            res = L.elementwise_add(x, h)
            loss0 = L.mean(res)
            fluid.optimizer.SGD(0.1).minimize(loss0)
            # layer_norm lands AFTER the section: add -> ln straddles
            out = L.layer_norm(res)
    fused, _ = passes.fuse_program(main,
                                   fetch_names=[loss0.name, out.name],
                                   record=False)
    misses = getattr(fused, "_fusion_near_misses", [])
    ln = [m for m in misses if m["pattern"] == "fuse_layer_norm"]
    assert ln and ln[0]["guard"] in ("section_boundary",
                                     "multi_consumer")


def test_seeded_pt407_fetch_drift():
    def build(main):
        x = fluid.data("x", [None, 8])
        o = main.global_block().create_var(
            name="drift", shape=[None, 8], dtype="float32")
        main.global_block().append_op(
            "relu", inputs={"X": L.cast(x, "bfloat16")},
            outputs={"Out": o})
        return ["drift"]

    _, r = _lint(build, feed=["x"])
    codes = _codes(r)
    assert set(codes) == {"PT407"}
    d = codes["PT407"][0]
    assert d.var == "drift"
    assert "bfloat16" in d.message and "float32" in d.message


def test_seeded_pt407_feed_drift():
    def build(main):
        x = fluid.data("x", [None, 8], dtype="bfloat16")
        return [L.relu(L.cast(x, "float32")).name]

    _, r = _lint(build, feed=["x"])
    codes = _codes(r)
    assert "PT407" in codes
    assert codes["PT407"][0].var == "x"


# ---------------------------------------------------------------------------
# dtype-flow semantics
# ---------------------------------------------------------------------------

def test_promotion_keeps_mixed_elementwise_fp32():
    """bf16 × fp32 promotes to fp32 (jnp semantics): a black op fed
    one fp32 operand is NOT in low precision — no false PT401."""
    def build(main):
        x = fluid.data("x", [None, 8])
        y = fluid.data("y", [None, 8])
        mixed = L.elementwise_add(L.cast(x, "bfloat16"), y)
        return [L.log(mixed).name]

    _, r = _lint(build, feed=["x", "y"])
    assert "PT401" not in _codes(r), r.render()


def test_fused_compute_dtype_is_followed():
    """A fused op's recorded compute_dtype drives downstream flow: a
    fragile op consuming a bf16 fused output lints PT401."""
    main, fetches = _attention_program(leak=False)
    # make the fused op bf16 by AMP-rewriting first (canonical order)
    amp.rewrite_program(main)
    fused, _ = passes.fuse_program(main, fetch_names=fetches,
                                   record=False)
    ops = fused.global_block().ops
    fa = next(op for op in ops if op.type == "fused_attention")
    assert fa.attrs.get("compute_dtype") == "bfloat16"
    blk = fused.global_block()
    out = blk.create_var(name="fragile")
    blk.append_op("exp", inputs={"X": fa.outputs["Out"][0]},
                  outputs={"Out": out})
    r = analysis.check_program(fused,
                               fetch_names=fetches + ["fragile"])
    codes = _codes(r)
    assert "PT401" in codes
    assert codes["PT401"][0].op_type == "exp"


def test_amp_inserted_pins_are_never_churn():
    """amp.rewrite_train_program's casts are REQUIRED static pins —
    the default bf16 train path must lint PT4xx-silent even where a
    pin turns out to be a runtime identity."""
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [None, 16])
            y = fluid.data("y", [None, 1])
            h = L.fc(L.fc(x, 32, act="relu"), 1)
            loss = L.mean(L.square_error_cost(h, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        amp.rewrite_train_program(main)
    assert any(op.attrs.get("_amp_inserted")
               for op in main.global_block().ops if op.type == "cast")
    r = analysis.check_program(main, fetch_names=[loss.name],
                               feed_names=["x", "y"])
    pt4 = [c for c in r.by_code() if c.startswith("PT4")]
    assert not pt4, r.render()


# ---------------------------------------------------------------------------
# zoo sweep: the substitute the executor dispatches is PT4xx-clean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(static_zoo.BUILDERS))
def test_zoo_train_substitute_pt4xx_clean(name):
    from paddle_tpu.framework.executor import Executor

    with fluid.unique_name.guard():
        m = static_zoo.build(name)
    sub = Executor._resolve_train_optimized(m.main, m.fetches,
                                            True, True)
    r = analysis.check_program(sub, fetch_names=m.fetches,
                               program_key=f"{name}/train_tier")
    pt4 = {c: n for c, n in r.by_code().items() if c.startswith("PT4")}
    assert not pt4, r.render()
    assert r.ok, r.render()


# ---------------------------------------------------------------------------
# verifier / executor wiring
# ---------------------------------------------------------------------------

@pytest.fixture
def static_check_flag():
    before = fluid.get_flags("static_check")["FLAGS_static_check"]
    yield
    fluid.set_flags({"FLAGS_static_check": before})


def test_executor_error_mode_raises_pt401_pre_trace(static_check_flag):
    """PT401 rides the same FLAGS_static_check=error fail-fast as
    PT1xx: the compile never starts."""
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [None, 8])
            out = L.log(L.cast(x, "bfloat16"))
    fluid.set_flags({"FLAGS_static_check": "error"})
    exe = fluid.Executor()
    with pytest.raises(analysis.ProgramLintError) as ei:
        exe.run(main, feed={"x": np.ones((4, 8), np.float32)},
                fetch_list=[out.name], scope=fluid.Scope())
    assert "PT401" in str(ei.value)
    assert "test_numerics.py" in str(ei.value)


def test_lint_cache_rekeys_on_amp_dtype(static_check_flag):
    """The cached_check key carries (amp dtype, fusion config): a flag
    flip re-analyzes instead of serving the stale verdict."""
    from paddle_tpu.analysis.verifier import cached_check

    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [None, 8])
            out = L.relu(x)
    _, fresh1 = cached_check(main, fetch_names=[out.name])
    _, fresh2 = cached_check(main, fetch_names=[out.name])
    assert fresh1 and not fresh2
    before = fluid.get_flags("amp_dtype")
    fluid.set_flags({"FLAGS_amp_dtype": "float16"})
    try:
        _, fresh3 = cached_check(main, fetch_names=[out.name])
        assert fresh3
    finally:
        fluid.set_flags(before)


def test_static_check_off_stays_byte_for_byte(static_check_flag):
    """With FLAGS_static_check=off the numerics pass NEVER runs — the
    analyzer adds zero work to the default dispatch path (analysis_runs
    pinned across train-tier dispatches)."""
    from paddle_tpu.analysis import verifier
    from paddle_tpu.framework.executor import Scope

    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 8])
            y = fluid.data("y", [None, 1])
            loss = L.mean(L.square_error_cost(L.fc(x, 4), y))
            fluid.optimizer.SGD(0.1).minimize(loss)
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


# ---------------------------------------------------------------------------
# telemetry record + report extensions
# ---------------------------------------------------------------------------

def test_lint_record_carries_pt4xx_and_near_miss_guards():
    main, fetches = _attention_program(leak=True)
    fused, _ = passes.fuse_program(main, fetch_names=fetches,
                                   record=False)
    r = analysis.check_program(fused, fetch_names=fetches)
    rec = r.to_record()
    assert rec["kind"] == "lint"
    assert rec["codes"].get("PT406") == 1
    assert rec["near_miss_guards"] == {"multi_consumer": 1}
    json.dumps(rec)                      # JSONL-stream clean


def test_telemetry_report_lint_section_numerics_breakout():
    from tools.telemetry_report import summarize

    records = [
        {"kind": "lint", "key": "m1", "errors": 1, "warnings": 2,
         "codes": {"PT401": 1, "PT403": 2},
         "near_miss_guards": {"multi_consumer": 2,
                              "section_boundary": 1},
         "cast_churn_bytes": 4096},
        {"kind": "lint", "key": "m2", "errors": 0, "warnings": 1,
         "codes": {"PT406": 1},
         "near_miss_guards": {"multi_consumer": 1}},
    ]
    out = summarize(records)
    lint = out["lint"]
    assert lint["by_program"]["m1"]["numerics"] == {"PT401": 1,
                                                    "PT403": 2}
    assert lint["by_program"]["m1"]["cast_churn_bytes"] == 4096
    assert lint["numerics_total"] == {"PT401": 1, "PT403": 2,
                                      "PT406": 1}
    assert lint["near_miss_guards_top"] == {"multi_consumer": 3,
                                            "section_boundary": 1}


# ---------------------------------------------------------------------------
# CLI --amp / --fuse
# ---------------------------------------------------------------------------

def test_cli_amp_fuse_lints_the_substitute():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "program_lint.py"),
         "--model", "bert", "--amp", "--fuse", "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stderr
    recs = json.loads(out.stdout)
    main_rec = next(r for r in recs if r["key"] == "bert/main")
    assert main_rec["train_tier"] == {"amp": True, "fuse": True}
    assert main_rec["errors"] == 0 and main_rec["warnings"] == 0
    # startup programs pass through the train-tier gate untouched
    start_rec = next(r for r in recs if r["key"] == "bert/startup")
    assert "train_tier" not in start_rec


def test_cli_amp_on_serialized_amp_program_is_not_double_cast(tmp_path):
    """amp_enabled round-trips through to_json/from_json (and the
    _amp_inserted pin tags survive), so `--amp` on an
    already-rewritten serialized program lints the SAME graph instead
    of double-casting it."""
    from paddle_tpu.framework.program import Program

    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [None, 8])
            y = fluid.data("y", [None, 1])
            loss = L.mean(L.square_error_cost(L.fc(x, 1), y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        amp.rewrite_train_program(main)
    rt = Program.from_json(main.to_json())
    assert rt.amp_enabled
    casts = [op for op in rt.global_block().ops if op.type == "cast"]
    assert casts and all(op.attrs.get("_amp_inserted") for op in casts)
    amp.rewrite_train_program(rt)          # idempotent: no second layer
    assert sum(1 for op in rt.global_block().ops
               if op.type == "cast") == len(casts)
    path = tmp_path / "amp_prog.json"
    path.write_text(main.to_json())
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "program_lint.py"),
         str(path), "--fetch", loss.name, "--amp"],
        capture_output=True, text=True, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PT403" not in res.stdout


def test_cli_pt401_errors_exit_one(tmp_path):
    with fluid.unique_name.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data("x", [None, 8])
            out = L.log(L.cast(x, "bfloat16"))
    path = tmp_path / "prog.json"
    path.write_text(main.to_json())
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "program_lint.py"),
         str(path), "--fetch", out.name],
        capture_output=True, text=True, cwd=REPO)
    assert res.returncode == 1
    assert "PT401" in res.stdout


# ---------------------------------------------------------------------------
# conformance: what the lint says against what running and optimizing do
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def numerics_conformance():
    """Two places where the analyzer's verdict can be held against the
    system itself, each run once.

    Divergence: mean(log(x)) at x = 1.001.  In bf16 1.001 rounds to 1.0
    (spacing 2^-8), so log gives 0 where ~1e-3 is due: a relative error
    near 1, far past the bf16 tolerance the AMP tests use (rtol 7e-2).
    The lint flags exactly that program (PT401) and not its fp32 twin,
    which matches numpy.

    Churn: the PT403 removable count of a program with a duplicate and
    an identity cast equals the number of cast ops the structural
    passes (cse + identity_elim) then delete: the lint and the
    optimizer share one definition of "redundant cast"."""
    from paddle_tpu.framework.executor import Scope

    def log_prog(low):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard():
            with fluid.program_guard(main, startup):
                x = fluid.data("x", [None, 64])
                h = L.cast(x, "bfloat16") if low else x
                out = L.mean(L.log(h))
        return main, out.name

    xb = np.full((4, 64), 1.001, np.float32)
    ref = float(np.mean(np.log(xb.astype(np.float64))))
    exe = fluid.Executor()
    rel, flagged = {}, {}
    for tag, low in (("bf16", True), ("fp32", False)):
        main, out_name = log_prog(low)
        flagged[tag] = "PT401" in analysis.check_program(
            main, fetch_names=[out_name], feed_names=["x"]).by_code()
        got = float(np.asarray(exe.run(
            main, feed={"x": xb}, fetch_list=[out_name],
            scope=Scope())[0]))
        rel[tag] = abs(got - ref) / abs(ref)

    def casts(prog):
        return sum(op.type == "cast" for op in prog.global_block().ops)

    with fluid.unique_name.guard():
        churn_main = fluid.Program()
        with fluid.program_guard(churn_main, fluid.Program()):
            x = fluid.data("x", [None, 8])
            a = L.cast(x, "bfloat16")
            b = L.cast(x, "bfloat16")       # duplicate (cse removes)
            c = L.cast(a, "bfloat16")       # identity (identity_elim)
            fetches = [L.elementwise_add(L.relu(a), L.relu(b)).name,
                       L.relu(c).name]
    removable = analysis.check_program(
        churn_main, fetch_names=fetches,
        feed_names=["x"]).numerics.churn_removable
    opt, _ = passes.optimize_program(churn_main, fetch_names=fetches,
                                     record=False)
    return {
        "seeded_pt401_diverges_past_tolerance": rel["bf16"] > 7e-2,
        "lint_clean_twin_within_tolerance": rel["fp32"] <= 7e-2,
        "the_lint_flags_the_program_that_diverges":
            flagged == {"bf16": True, "fp32": False},
        "churn_count_equals_structural_removal":
            removable > 0
            and removable == casts(churn_main) - casts(opt),
    }


@pytest.mark.parametrize("check", [
    "seeded_pt401_diverges_past_tolerance",
    "lint_clean_twin_within_tolerance",
    "the_lint_flags_the_program_that_diverges",
    "churn_count_equals_structural_removal"])
def test_the_lint_agrees_with_the_system(numerics_conformance, check):
    assert numerics_conformance[check], numerics_conformance
