"""Graph-optimizer pass pipeline tests (ISSUE 9).

Covers: per-pass seeded programs with exact expected op diffs, pipeline
idempotence, zoo models optimize + lint clean + execute with parity,
the bucketed dp gradient sync (bitwise parity, ceil bucket bound,
sparse fallback counter), the Program._bump atomic cache invalidation
regression, op_scope_names folded_from provenance, folded-constant
serialization, and the Predictor folding path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import analysis, monitor, passes
from paddle_tpu import layers as L
from paddle_tpu.framework.executor import Scope, op_scope_names
from paddle_tpu.framework.program import Program
from paddle_tpu.models import static_zoo
from paddle_tpu.selected_rows import SelectedRows
from paddle_tpu.transpiler import collective


def _build(fn):
    """Build a (main, startup, result) triple under fresh name scope."""
    with fluid.unique_name.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            out = fn()
    return main, startup, out


def _op_types(program):
    return [op.type for op in program.global_block().ops]


# ---------------------------------------------------------------------------
# per-pass seeded programs: exact expected op diffs
# ---------------------------------------------------------------------------

def test_cse_dedups_identical_subexpression():
    def build():
        x = fluid.data("x", [None, 4])
        a = L.relu(x)
        b = L.relu(x)
        return L.elementwise_add(a, b)

    main, _, out = _build(build)
    assert _op_types(main) == ["relu", "relu", "elementwise_add"]
    opt, rep = passes.optimize_program(main, fetch_names=[out.name],
                                       passes=["cse"], record=False)
    assert _op_types(opt) == ["relu", "elementwise_add"]
    assert rep["ops_removed"] == 1
    add = opt.global_block().ops[-1]
    xs = add.inputs["X"] + add.inputs["Y"]
    assert xs[0] == xs[1]          # both reads rewired to the keeper


def test_cse_respects_backward_segments():
    # an op before the section position and its twin after it trace
    # into different closures — CSE must not merge across the boundary
    def build():
        x = fluid.data("x", [4, 4])
        w = fluid.default_main_program().global_block().create_parameter(
            name="w", shape=[4, 4], dtype="float32")
        h = L.elementwise_mul(x, w)
        loss = L.mean(h)
        fluid.backward.append_backward(loss)
        dup = L.elementwise_mul(x, w)   # same key, after the section
        return loss, dup

    main, _, (loss, dup) = _build(build)
    opt, _ = passes.optimize_program(
        main, fetch_names=[loss.name, dup.name], passes=["cse"],
        record=False)
    assert _op_types(opt).count("elementwise_mul") == 2


def test_const_fold_creates_initialized_persistable():
    def build():
        x = fluid.data("x", [None, 2])
        t = L.fill_constant([2], "float32", 3.0)
        s = L.scale(t, scale=2.0)       # const chain: fill -> scale
        return L.elementwise_add(x, s), s

    main, startup, (out, s) = _build(build)
    opt, rep = passes.optimize_program(main, fetch_names=[out.name],
                                       passes=["const_fold"],
                                       record=False)
    assert _op_types(opt) == ["elementwise_add"]
    assert rep["ops_removed"] == 2
    fc = opt._folded_constants
    # the constant gets a process-unique name (shared-scope seeding
    # must never collide across programs) derived from the source var
    folded_name, = fc
    assert folded_name.startswith(s.name + ".folded_")
    np.testing.assert_allclose(fc[folded_name], np.full((2,), 6.0))
    assert opt.global_block().vars[folded_name].persistable
    add = opt.global_block().ops[0]
    assert folded_name in add.input_names()
    # executor seeds the folded value into the scope
    exe = fluid.Executor()
    scope = Scope()
    xb = np.ones((3, 2), np.float32)
    ref = exe.run(main, feed={"x": xb}, fetch_list=[out.name],
                  scope=Scope())
    got = exe.run(opt, feed={"x": xb}, fetch_list=[out.name],
                  scope=scope)
    np.testing.assert_allclose(got[0], ref[0])


def test_identity_reshape_eliminated_with_symbolic_batch():
    def build():
        x = fluid.data("x", [None, 8])
        r = L.reshape(x, shape=[-1, 8])
        return L.relu(r)

    main, _, out = _build(build)
    opt, rep = passes.optimize_program(main, fetch_names=[out.name],
                                       passes=["identity_elim"],
                                       record=False)
    assert _op_types(opt) == ["relu"]
    relu = opt.global_block().ops[0]
    assert relu.inputs["X"] == ["x"]


def test_non_identity_reshape_survives():
    def build():
        x = fluid.data("x", [None, 8])
        r = L.reshape(x, shape=[-1, 4, 2])
        return L.relu(r)

    main, _, out = _build(build)
    opt, _ = passes.optimize_program(main, fetch_names=[out.name],
                                     passes=["identity_elim"],
                                     record=False)
    assert "reshape2" in _op_types(opt)


def test_fold_scale_chain_exact():
    def build():
        x = fluid.data("x", [None, 3])
        s1 = L.scale(x, scale=2.0, bias=1.0)
        return L.scale(s1, scale=3.0, bias=0.5)

    main, _, out = _build(build)
    opt, _ = passes.optimize_program(main, fetch_names=[out.name],
                                     passes=["fold_scale_chain"],
                                     record=False)
    kinds = _op_types(opt)
    assert kinds == ["scale"]
    op = opt.global_block().ops[0]
    assert op.attrs["scale"] == pytest.approx(6.0)
    assert op.attrs["bias"] == pytest.approx(3.5)   # 3*1.0 + 0.5
    exe = fluid.Executor()
    xb = np.arange(6, dtype=np.float32).reshape(2, 3)
    ref = exe.run(main, feed={"x": xb}, fetch_list=[out.name],
                  scope=Scope())
    got = exe.run(opt, feed={"x": xb}, fetch_list=[out.name],
                  scope=Scope())
    np.testing.assert_allclose(got[0], ref[0], rtol=1e-6)


def test_dce_exact_diff():
    def build():
        x = fluid.data("x", [None, 4])
        kept = L.relu(x)
        L.sigmoid(x)                     # dead: never fetched or read
        return kept

    main, _, out = _build(build)
    opt, rep = passes.optimize_program(main, fetch_names=[out.name],
                                       passes=["dce"], record=False)
    assert _op_types(opt) == ["relu"]
    assert rep["passes"][0]["dead_ops"] == 1


def _conv_bn_model(nonzero_stats):
    def build():
        img = fluid.data("img", [None, 3, 8, 8])
        c = L.conv2d(img, 4, 3, padding=1, bias_attr=False)
        b = L.batch_norm(c, is_test=True)
        return L.relu(b)

    main, startup, out = _build(build)
    exe = fluid.Executor()
    scope = Scope()
    exe.run(startup, scope=scope)
    if nonzero_stats:
        rng = np.random.default_rng(3)
        for n, v in list(scope.vars.items()):
            if v is None:
                continue
            a = np.asarray(v)
            if a.ndim == 1:              # scale/bias/moving stats
                scope.set_var(n, jnp.asarray(
                    rng.uniform(0.5, 1.5, a.shape).astype(a.dtype)))
    params = {n: np.asarray(v) for n, v in scope.vars.items()
              if v is not None}
    return main, out, exe, scope, params


def test_fold_batch_norm_zero_stats_removes_op():
    main, out, exe, scope, params = _conv_bn_model(nonzero_stats=False)
    test = main.clone(for_test=True)
    opt, opt_params, rep = passes.fold_inference(
        test, params, fetch_names=[out.name], record=False)
    # fresh moving stats (mean 0, beta 0): the +b add elides entirely
    assert _op_types(opt) == ["conv2d", "relu"]
    feed = {"img": np.random.default_rng(0).standard_normal(
        (2, 3, 8, 8)).astype(np.float32)}
    ref = exe.run(test, feed=feed, fetch_list=[out.name], scope=scope)
    s2 = Scope()
    for n, v in opt_params.items():
        s2.set_var(n, jnp.asarray(v))
    got = exe.run(opt, feed=feed, fetch_list=[out.name], scope=s2)
    np.testing.assert_allclose(got[0], ref[0], rtol=1e-4, atol=1e-5)


def test_fold_batch_norm_nonzero_stats_becomes_bias_add():
    main, out, exe, scope, params = _conv_bn_model(nonzero_stats=True)
    test = main.clone(for_test=True)
    opt, opt_params, rep = passes.fold_inference(
        test, params, fetch_names=[out.name], record=False)
    kinds = _op_types(opt)
    assert "batch_norm" not in kinds
    assert "elementwise_add" in kinds    # the residual +b channel add
    add = next(op for op in opt.global_block().ops
               if op.type == "elementwise_add")
    # provenance: the repurposed op maps back to the source bn scope
    assert any("batch_norm" in s for s in add.folded_from)
    feed = {"img": np.random.default_rng(1).standard_normal(
        (2, 3, 8, 8)).astype(np.float32)}
    ref = exe.run(test, feed=feed, fetch_list=[out.name], scope=scope)
    s2 = Scope()
    for n, v in opt_params.items():
        s2.set_var(n, jnp.asarray(v))
    got = exe.run(opt, feed=feed, fetch_list=[out.name], scope=s2)
    np.testing.assert_allclose(got[0], ref[0], rtol=1e-4, atol=1e-5)


def test_fold_batch_norm_absorbs_conv_bias():
    """conv WITH bias + BN: the fold lands entirely in the existing
    weights/bias (W*=a, b' = a*b + shift) — one op removed, no
    residual add."""
    def build():
        img = fluid.data("img", [None, 3, 8, 8])
        c = L.conv2d(img, 4, 3, padding=1)       # bias add, axis=1
        b = L.batch_norm(c, is_test=True)
        return L.relu(b)

    main, startup, out = _build(build)
    exe = fluid.Executor()
    scope = Scope()
    exe.run(startup, scope=scope)
    rng = np.random.default_rng(9)
    for n, v in list(scope.vars.items()):
        a = np.asarray(v)
        if a.ndim == 1:
            scope.set_var(n, jnp.asarray(
                rng.uniform(0.5, 1.5, a.shape).astype(a.dtype)))
    params = {n: np.asarray(v) for n, v in scope.vars.items()
              if v is not None}
    test = main.clone(for_test=True)
    opt, p2, _ = passes.fold_inference(
        test, params, fetch_names=[out.name], record=False)
    assert _op_types(opt) == ["conv2d", "elementwise_add", "relu"]
    feed = {"img": rng.standard_normal((2, 3, 8, 8)).astype(
        np.float32)}
    ref = exe.run(test, feed=feed, fetch_list=[out.name], scope=scope)
    s2 = Scope()
    for n, v in p2.items():
        s2.set_var(n, jnp.asarray(v))
    got = exe.run(opt, feed=feed, fetch_list=[out.name], scope=s2)
    np.testing.assert_allclose(got[0], ref[0], rtol=1e-4, atol=1e-5)


def test_fold_batch_norm_skips_train_mode():
    main, out, exe, scope, params = _conv_bn_model(nonzero_stats=False)
    # TRAIN program (is_test never set on the clone): batch stats
    # depend on activations — no fold
    def build():
        img = fluid.data("img", [None, 3, 8, 8])
        c = L.conv2d(img, 4, 3, padding=1, bias_attr=False)
        b = L.batch_norm(c)
        return L.relu(b)

    train_main, _, out2 = _build(build)
    opt, _, rep = passes.fold_inference(
        train_main, params, fetch_names=[out2.name], record=False)
    assert "batch_norm" in _op_types(opt)


def test_const_read_only_by_subblock_survives_folding():
    """A constant whose only consumer lives inside a control-flow
    sub-block is invisible to global-block def-use; const_fold must
    still materialize it (protected names are boundary consumers), not
    delete its producer and leave the sub-block read dangling."""
    def build():
        x = fluid.data("x", [2, 2])
        t = L.fill_constant([2, 2], "float32", 3.0)
        pred = L.fill_constant([1], "bool", True)
        return fluid.layers.cond(pred,
                                 lambda: L.elementwise_add(x, t),
                                 lambda: x)

    main, _, out = _build(build)
    opt, _ = passes.optimize_program(main, fetch_names=[out.name],
                                     record=False)
    exe = fluid.Executor()
    r = exe.run(opt, feed={"x": np.zeros((2, 2), np.float32)},
                fetch_list=[out.name], scope=Scope())
    np.testing.assert_allclose(r[0], 3.0)


def test_fold_batch_norm_skips_non_channel_bias():
    """A positional (non-(C,)) bias between conv and BN must not fold —
    the channel scale would broadcast wrongly — and, critically, the
    conv WEIGHTS must be left untouched when the fold is rejected."""
    def build():
        img = fluid.data("img", [None, 3, 8, 8])
        c = L.conv2d(img, 4, 3, padding=1, bias_attr=False)
        blk = fluid.default_main_program().global_block()
        posb = blk.create_parameter(name="pos_bias", shape=[4, 8, 8],
                                    dtype="float32")
        s = L.elementwise_add(c, posb, axis=1)
        b = L.batch_norm(s, is_test=True)
        return L.relu(b)

    main, startup, out = _build(build)
    exe = fluid.Executor()
    scope = Scope()
    exe.run(startup, scope=scope)
    params = {n: np.asarray(v) for n, v in scope.vars.items()
              if v is not None}
    before = {n: v.copy() for n, v in params.items()}
    test = main.clone(for_test=True)
    opt, opt_params, _ = passes.fold_inference(
        test, params, fetch_names=[out.name], record=False)
    assert "batch_norm" in _op_types(opt)     # fold rejected
    for n, v in before.items():
        np.testing.assert_array_equal(opt_params[n], v)


def test_fold_batch_norm_skips_fetched_intermediate():
    """Fetches are consumers the consumer map can't see: folding BN
    into the fc weights would change the fetched pre-BN activation's
    value, so a protected intermediate blocks the fold entirely."""
    def build():
        x = fluid.data("x", [None, 4])
        h = L.fc(x, 3)                   # mul + elementwise_add
        b = L.batch_norm(h, is_test=True)
        return h, b

    main, startup, (h, b) = _build(build)
    exe = fluid.Executor()
    scope = Scope()
    exe.run(startup, scope=scope)
    params = {n: np.asarray(v) for n, v in scope.vars.items()
              if v is not None}
    test = main.clone(for_test=True)
    opt, p2, _ = passes.fold_inference(
        test, params, fetch_names=[h.name, b.name], record=False)
    assert "batch_norm" in _op_types(opt)
    feed = {"x": np.random.default_rng(0).standard_normal(
        (2, 4)).astype(np.float32)}
    ref = exe.run(test, feed=feed, fetch_list=[h.name], scope=scope)
    s2 = Scope()
    for n, v in p2.items():
        s2.set_var(n, jnp.asarray(v))
    got = exe.run(opt, feed=feed, fetch_list=[h.name], scope=s2)
    np.testing.assert_array_equal(ref[0], got[0])   # h untouched


def test_fold_scale_chain_blocked_by_waw_input():
    """Collapsing scale(scale(u)) moves the read of `u` later; a
    rewrite of `u` between the two scales must block the collapse."""
    def build():
        x = fluid.data("x", [None, 2])
        u = fluid.default_main_program().global_block().create_var(
            name="u", shape=[None, 2], dtype="float32")
        L.assign(x, output=u)
        a = L.scale(u, scale=2.0)
        L.assign(L.scale(x, scale=-1.0), output=u)   # WAW on u
        return L.scale(a, scale=3.0)

    main, _, out = _build(build)
    opt, _ = passes.optimize_program(main, fetch_names=[out.name],
                                     passes=["fold_scale_chain"],
                                     record=False)
    # the chain must NOT collapse (it would read the second write)
    assert _op_types(opt).count("scale") == _op_types(main).count(
        "scale")
    exe = fluid.Executor()
    f = {"x": np.ones((1, 2), np.float32)}
    ref = exe.run(main, feed=f, fetch_list=[out.name], scope=Scope())
    got = exe.run(opt, feed=f, fetch_list=[out.name], scope=Scope())
    np.testing.assert_allclose(got[0], ref[0])      # 1*2*3 = 6
    np.testing.assert_allclose(got[0], 6.0)


def test_section_loss_producer_survives_scale_collapse():
    """A BackwardSection resolves its loss by NAME at trace time — a
    name no consumer map can see.  Regression: fold_scale_chain used
    to delete the producer of a loss that was only read by another
    scale, leaving the section's loss reference dangling."""
    def build():
        x = fluid.data("x", [4, 2])
        blk = fluid.default_main_program().global_block()
        w = blk.create_parameter(name="w2", shape=[4, 2],
                                 dtype="float32")
        base = L.mean(L.elementwise_mul(x, w))
        loss = L.scale(base, scale=2.0)          # the section's loss
        scaled = L.scale(loss, scale=0.5)        # loss's ONLY reader
        fluid.optimizer.SGD(0.1).minimize(loss)
        return loss, scaled

    main, startup, (loss, scaled) = _build(build)
    opt, _ = passes.optimize_program(main, fetch_names=[scaled.name],
                                     record=False)
    produced = {n for op in opt.global_block().ops
                for n in op.output_names()}
    assert loss.name in produced
    exe = fluid.Executor()
    scope = Scope()
    exe.run(startup, scope=scope)
    scope.set_var("w2", jnp.ones((4, 2), jnp.float32))
    got = exe.run(opt, feed={"x": np.ones((4, 2), np.float32)},
                  fetch_list=[scaled.name], scope=scope)
    np.testing.assert_allclose(np.asarray(got[0]), 1.0)  # 0.5*2*mean(1)


def test_single_writer_persistable_is_waw_barrier():
    """A persistable has a value BEFORE the program runs, so its first
    in-program write (the optimizer update) is already a second
    definition: a pre-update snapshot read must not be aliased across
    it.  Regression for the miscompile where identity_elim renamed
    scale(w, 1.0) to w and the post-update reader saw the new
    weight."""
    def build():
        x = fluid.data("x", [4, 1])
        blk = fluid.default_main_program().global_block()
        w = blk.create_parameter(name="w", shape=[4, 1],
                                 dtype="float32")
        snap = L.scale(w, scale=1.0)            # pre-update snapshot
        loss = L.mean(L.elementwise_mul(x, w))
        fluid.optimizer.SGD(0.25).minimize(loss)
        return L.elementwise_add(snap, snap)    # read AFTER the update

    main, startup, out = _build(build)
    exe = fluid.Executor()
    ref_scope, opt_scope = Scope(), Scope()
    exe.run(startup, scope=ref_scope)       # optimizer lr var
    exe.run(startup, scope=opt_scope)
    # raw create_parameter has no startup initializer; two SEPARATE
    # arrays — the compiled step donates its state buffers
    ref_scope.set_var("w", jnp.ones((4, 1), jnp.float32))
    opt_scope.set_var("w", jnp.ones((4, 1), jnp.float32))
    opt, _ = passes.optimize_program(main, fetch_names=[out.name],
                                     record=False)
    f = {"x": np.ones((4, 1), np.float32)}
    ref = exe.run(main, feed=f, fetch_list=[out.name], scope=ref_scope)
    got = exe.run(opt, feed=f, fetch_list=[out.name], scope=opt_scope)
    np.testing.assert_array_equal(np.asarray(ref[0]),
                                  np.asarray(got[0]))


def test_waw_names_are_rewrite_barriers():
    """A variable written twice (write-after-write) breaks the
    name==value assumption every rewrite reasons with: CSE must not
    merge the two relu(a) reads (they see different writes), and
    identity_elim must not alias the assigns away.  Regression for the
    miscompile where renaming rewired readers across the second
    write."""
    def build():
        x0 = fluid.data("x0", [None, 4])
        x1 = fluid.data("x1", [None, 4])
        a = fluid.default_main_program().global_block().create_var(
            name="a", shape=[None, 4], dtype="float32")
        L.assign(x0, output=a)
        r1 = L.relu(a)
        L.assign(x1, output=a)
        r2 = L.relu(a)
        return L.elementwise_add(r1, r2)

    main, _, out = _build(build)
    opt, _ = passes.optimize_program(main, fetch_names=[out.name],
                                     record=False)
    # both writes of `a` and both reads survive
    assert _op_types(opt).count("assign") == 2
    assert _op_types(opt).count("relu") == 2
    exe = fluid.Executor()
    f = {"x0": np.full((2, 4), -1.0, np.float32),
         "x1": np.full((2, 4), 2.0, np.float32)}
    ref = exe.run(main, feed=f, fetch_list=[out.name], scope=Scope())
    got = exe.run(opt, feed=f, fetch_list=[out.name], scope=Scope())
    np.testing.assert_allclose(got[0], ref[0])          # 0 + 2 = 2
    np.testing.assert_allclose(got[0], 2.0)


def test_folded_constant_names_unique_across_programs():
    """Two programs built under separate unique_name guards repeat
    auto-generated var names; their folded constants must not collide
    when both run against ONE shared scope (the default global-scope
    pattern)."""
    def make(value):
        def build():
            x = fluid.data("x", [None, 2])
            t = L.fill_constant([2], "float32", value)
            return L.elementwise_add(x, t)

        main, _, out = _build(build)
        opt, _ = passes.optimize_program(main, fetch_names=[out.name],
                                         passes=["const_fold"],
                                         record=False)
        return opt, out.name

    opt_a, fetch_a = make(3.0)
    opt_b, fetch_b = make(5.0)
    assert not (set(opt_a._folded_constants)
                & set(opt_b._folded_constants))
    exe = fluid.Executor()
    shared = Scope()
    xb = np.zeros((1, 2), np.float32)
    ra = exe.run(opt_a, feed={"x": xb}, fetch_list=[fetch_a],
                 scope=shared)
    rb = exe.run(opt_b, feed={"x": xb}, fetch_list=[fetch_b],
                 scope=shared)
    ra2 = exe.run(opt_a, feed={"x": xb}, fetch_list=[fetch_a],
                  scope=shared)
    np.testing.assert_allclose(ra[0], 3.0)
    np.testing.assert_allclose(rb[0], 5.0)
    np.testing.assert_allclose(ra2[0], 3.0)     # not clobbered by B


# ---------------------------------------------------------------------------
# pipeline-level properties on the zoo
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(static_zoo.BUILDERS))
def test_zoo_optimized_lint_clean(name):
    m = static_zoo.build(name)
    test = m.main.clone(for_test=True)
    opt, _ = passes.optimize_program(test, fetch_names=[m.loss_name],
                                     record=False)
    result = analysis.check_program(opt, fetch_names=[m.loss_name])
    assert not result.errors, result.render()


@pytest.mark.parametrize("name", ["lenet", "resnet", "word2vec"])
def test_zoo_pipeline_idempotent(name):
    m = static_zoo.build(name)
    test = m.main.clone(for_test=True)
    opt, rep1 = passes.optimize_program(test, fetch_names=[m.loss_name],
                                        record=False)
    opt2, rep2 = passes.optimize_program(opt, fetch_names=[m.loss_name],
                                         record=False)
    assert rep2["ops_removed"] == 0
    assert _op_types(opt) == _op_types(opt2)


@pytest.mark.parametrize("name", ["mlp", "lenet", "word2vec"])
def test_zoo_optimize_execute_parity(name):
    m = static_zoo.build(name)
    exe = fluid.Executor()
    scope = Scope()
    exe.run(m.startup, scope=scope)
    test = m.main.clone(for_test=True)
    opt, _ = passes.optimize_program(test, fetch_names=[m.loss_name],
                                     record=False)
    feed = m.smoke_feed(batch=8)
    ref = exe.run(test, feed=feed, fetch_list=[m.loss_name], scope=scope)
    got = exe.run(opt, feed=feed, fetch_list=[m.loss_name], scope=scope)
    # structural passes only — bit-level parity expected
    np.testing.assert_allclose(got[0], ref[0], rtol=0, atol=0)


@pytest.fixture(scope="module")
def zoo_fold_sweep():
    """Every zoo model's inference clone through `fold_inference` with
    its real startup-initialized parameter values (so conv+BN folding
    is live), run beside the unoptimized program on one feed: per model
    the op-count reduction and whether the outputs agree."""
    out = {}
    for name in sorted(static_zoo.BUILDERS):
        with fluid.unique_name.guard():
            m = static_zoo.build(name)
        exe = fluid.Executor()
        scope = Scope()
        exe.run(m.startup, scope=scope)
        test = m.main.clone(for_test=True)
        params = {n: np.asarray(v) for n, v in scope.vars.items()
                  if v is not None}
        opt, opt_params, rep = passes.fold_inference(
            test, params, fetch_names=[m.loss_name], record=False)
        feed = m.smoke_feed(batch=8)
        ref = exe.run(test, feed=feed, fetch_list=[m.loss_name],
                      scope=scope)
        opt_scope = Scope()
        for n, v in opt_params.items():
            opt_scope.set_var(n, jnp.asarray(v))
        got = exe.run(opt, feed=feed, fetch_list=[m.loss_name],
                      scope=opt_scope)
        out[name] = {
            "allclose": all(np.allclose(a, b, rtol=1e-4, atol=1e-5)
                            for a, b in zip(ref, got)),
            "reduction": (rep["before_ops"] - rep["after_ops"])
            / rep["before_ops"],
        }
    return out


@pytest.mark.parametrize("name", sorted(static_zoo.BUILDERS))
def test_zoo_fold_inference_keeps_the_outputs(zoo_fold_sweep, name):
    assert zoo_fold_sweep[name]["allclose"], zoo_fold_sweep


def test_zoo_fold_inference_drops_a_tenth_of_the_ops_on_3_models(
        zoo_fold_sweep):
    reduced = [n for n, r in zoo_fold_sweep.items()
               if r["reduction"] >= 0.10]
    assert len(reduced) >= 3, zoo_fold_sweep


def test_pass_pipeline_record_emitted():
    monitor.reset()
    monitor.enable()
    try:
        m = static_zoo.build("lenet")
        passes.optimize_program(m.main.clone(for_test=True),
                                fetch_names=[m.loss_name],
                                program_key="rec_test")
        recs = monitor.pass_pipeline_records()
        assert recs and recs[-1]["key"] == "rec_test"
        names = [p["name"] for p in recs[-1]["passes"]]
        assert list(passes.DEFAULT_PIPELINE) == names
        assert all("wall_ms" in p for p in recs[-1]["passes"])
    finally:
        monitor.disable()
        monitor.reset()


def test_unknown_pass_name_raises():
    m = static_zoo.build("mlp")
    with pytest.raises(KeyError):
        passes.optimize_program(m.main, passes=["no_such_pass"],
                                record=False)
    with pytest.raises(KeyError):
        passes.enabled_passes(disable=["no_such_pass"])


# ---------------------------------------------------------------------------
# satellite 1: _bump invalidates run-plan + lint + opt caches atomically
# ---------------------------------------------------------------------------

def test_bump_drops_all_derived_caches():
    def build():
        x = fluid.data("x", [None, 2])
        return L.relu(x)

    main, _, out = _build(build)
    exe = fluid.Executor()
    exe.run(main, feed={"x": np.ones((2, 2), np.float32)},
            fetch_list=[out.name], scope=Scope())
    analysis.cached_check(main, fetch_names=[out.name])
    main._opt_cache = {"sentinel": object()}
    assert main._run_plan_cache is not None
    assert main._lint_cache
    main._bump()
    assert main._run_plan_cache is None
    assert not main._lint_cache
    assert main._opt_cache is None


def test_mutate_optimize_rerun_serves_no_stale_plan():
    def build():
        x = fluid.data("x", [None, 2])
        return L.relu(x)

    main, _, out = _build(build)
    exe = fluid.Executor()
    scope = Scope()
    xb = np.full((2, 2), -3.0, np.float32)
    fluid.set_flags({"FLAGS_graph_opt": "on"})
    try:
        r1 = exe.run(main, feed={"x": xb}, fetch_list=[out.name],
                     scope=scope)
        np.testing.assert_allclose(r1[0], 0.0)
        # mutate: append a scale over the relu output, then re-run
        # fetching the NEW output — a stale run-plan/opt-program would
        # either miss the var or serve the old graph
        with fluid.program_guard(main):
            out2 = L.scale(out, scale=2.0, bias=1.0)
        r2 = exe.run(main, feed={"x": np.ones((2, 2), np.float32)},
                     fetch_list=[out2.name], scope=scope)
        np.testing.assert_allclose(r2[0], 3.0)
    finally:
        fluid.set_flags({"FLAGS_graph_opt": "off"})


# ---------------------------------------------------------------------------
# satellite 2: op_scope_names maps folded ops to source scopes
# ---------------------------------------------------------------------------

def test_op_scope_names_optimized_with_folded_from():
    def build():
        x = fluid.data("x", [None, 4])
        a = L.relu(x)
        b = L.relu(x)
        return L.elementwise_add(a, b)

    main, _, out = _build(build)
    fluid.set_flags({"FLAGS_graph_opt": "on"})
    try:
        pairs = op_scope_names(main, fetch_names=[out.name])
        scopes = [s for s, _ in pairs]
        assert len(scopes) == len(set(scopes))       # all attributable
        assert len(pairs) == 2                       # relu deduped
        keeper = pairs[0][1]
        assert keeper.type == "relu"
        # the keeper remembers the eliminated twin's source scope
        assert any("relu" in s for s in keeper.folded_from)
        # executed scopes == declared scopes (attribution never lands
        # in (unattributed)): the executor traces the same optimized
        # program the map resolved
        exe = fluid.Executor()
        r = exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[out.name], scope=Scope())
        np.testing.assert_allclose(r[0], 2.0)
    finally:
        fluid.set_flags({"FLAGS_graph_opt": "off"})


# ---------------------------------------------------------------------------
# bucketed dp gradient sync
# ---------------------------------------------------------------------------

def test_plan_buckets_ceil_bound_and_spanning():
    entries = [("a", 100, 4, "float32"), ("b", 50, 4, "float32")]
    buckets = collective.plan_buckets(entries, 256)   # 64 elems/bucket
    assert len(buckets) == 3                          # ceil(150/64)
    assert buckets[0]["names"] == ["a"]               # a[0:64]
    assert buckets[1]["names"] == ["a", "b"]          # a[64:], b[0:28]
    assert buckets[2]["names"] == ["b"]               # b[28:]
    assert sum(b["elems"] for b in buckets) == 150
    assert all(b["elems"] <= 64 for b in buckets)


def test_plan_buckets_dtype_segregated():
    entries = [("a", 10, 4, "float32"), ("b", 10, 2, "bfloat16"),
               ("c", 10, 4, "float32")]
    buckets = collective.plan_buckets(entries, 1 << 20)
    dtypes = [b["dtype"] for b in buckets]
    assert sorted(dtypes) == ["bfloat16", "float32"]
    f32 = next(b for b in buckets if b["dtype"] == "float32")
    assert f32["names"] == ["a", "c"]


def test_dp_bucketed_training_bitwise():
    """Train the same dp program per-grad (bucket 0), tiny-bucket, and
    one-big-bucket, in ONE test so the cross-config bitwise assertion
    ALWAYS runs (a parametrized accumulator would silently skip it
    under -k selection or test sharding)."""
    from paddle_tpu import flags as _flags

    entry = _flags.flag("dp_bucket_bytes")

    def train(bucket_bytes):
        fluid.set_flags({"FLAGS_dp_bucket_bytes": bucket_bytes})
        try:
            with fluid.unique_name.guard():
                m = static_zoo.build("mlp")
            exe = fluid.Executor()
            scope = Scope()
            exe.run(m.startup, scope=scope)
            prog = fluid.CompiledProgram(m.main).with_data_parallel(
                loss_name=m.loss_name, places=2)
            rng = np.random.default_rng(11)
            for _ in range(3):
                exe.run(prog, feed={
                    "x": rng.standard_normal((8, 13)).astype(
                        np.float32),
                    "y": rng.standard_normal((8, 1)).astype(
                        np.float32)},
                    fetch_list=[m.loss_name], scope=scope)
            stats = collective.last_sync_stats()
            return ({n: np.asarray(v) for n, v in scope.vars.items()},
                    stats)
        finally:
            fluid.set_flags({"FLAGS_dp_bucket_bytes": entry})

    base, s0 = train(0)
    tiny, s1 = train(256)
    big, s2 = train(4 << 20)
    assert s0["mode"] == "per_grad" and s0["psums"] == s0["grads"] == 4
    assert s1["mode"] == "bucketed"
    assert 0 < s1["psums"] <= -(-s1["total_bytes"] // 256)
    assert s2["mode"] == "bucketed" and s2["psums"] == 1
    assert s1["fallbacks"] == 0 and s2["fallbacks"] == 0
    for name, params_k in (("tiny", tiny), ("big", big)):
        assert set(params_k) == set(base)
        for n in base:
            assert np.array_equal(base[n], params_k[n]), \
                f"{name} bucket param {n} not bitwise-identical"


def test_sparse_grads_fall_back_with_counter():
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    before = monitor.counter("passes.bucket_fallbacks").value
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

    def step(x):
        grads = {
            "dense_w": x * 2.0,
            "dense_b": jnp.sum(x, axis=0),
            "table": SelectedRows(jnp.array([0, 1]),
                                  jnp.ones((2, 3)), height=10),
            "tree": (x, x * 3.0),
        }
        out = collective.sync_gradients(grads, "dp", bucket_bytes=1024)
        assert isinstance(out["table"], SelectedRows)
        return out["dense_w"]

    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=P("dp"),
                           out_specs=P("dp"), check_vma=False))
    res = np.asarray(fn(jnp.ones((4, 2), jnp.float32)))
    np.testing.assert_allclose(res, 2.0)
    stats = collective.last_sync_stats()
    assert stats["mode"] == "bucketed"
    assert stats["fallbacks"] == 2           # SelectedRows + the tuple
    # collective accounting: 1 bucketed psum for the dense grads, 2
    # per-leaf psums for the tuple, 0 for the pass-through SelectedRows
    assert stats["psums"] == 3
    assert monitor.counter("passes.bucket_fallbacks").value \
        == before + 2


def test_bucket_flag_change_retraces_same_program():
    """FLAGS_dp_bucket_bytes is read at trace time, so flipping it must
    re-key the compiled step — a cached bucketed trace silently serving
    a disabled-bucketing run would make the telemetry lie."""
    from paddle_tpu import flags as _flags

    entry = _flags.flag("dp_bucket_bytes")
    with fluid.unique_name.guard():
        m = static_zoo.build("mlp")
    exe = fluid.Executor()
    scope = Scope()
    exe.run(m.startup, scope=scope)
    prog = fluid.CompiledProgram(m.main).with_data_parallel(
        loss_name=m.loss_name, places=2)
    feed = {"x": np.ones((4, 13), np.float32),
            "y": np.ones((4, 1), np.float32)}
    try:
        fluid.set_flags({"FLAGS_dp_bucket_bytes": 4 << 20})
        exe.run(prog, feed=feed, fetch_list=[m.loss_name], scope=scope)
        assert collective.last_sync_stats()["mode"] == "bucketed"
        fluid.set_flags({"FLAGS_dp_bucket_bytes": 0})
        exe.run(prog, feed=feed, fetch_list=[m.loss_name], scope=scope)
        assert collective.last_sync_stats()["mode"] == "per_grad"
    finally:
        fluid.set_flags({"FLAGS_dp_bucket_bytes": entry})


# ---------------------------------------------------------------------------
# folded constants: serialization + scope seeding
# ---------------------------------------------------------------------------

def test_folded_constants_survive_json_roundtrip():
    def build():
        x = fluid.data("x", [None, 2])
        t = L.fill_constant([2], "float32", 4.0)
        return L.elementwise_add(x, t)

    main, _, out = _build(build)
    opt, _ = passes.optimize_program(main, fetch_names=[out.name],
                                     passes=["const_fold"],
                                     record=False)
    clone = Program.from_json(opt.to_json())
    assert clone._folded_constants
    for n, v in opt._folded_constants.items():
        np.testing.assert_allclose(clone._folded_constants[n], v)
    exe = fluid.Executor()
    got = exe.run(clone, feed={"x": np.zeros((1, 2), np.float32)},
                  fetch_list=[out.name], scope=Scope())
    np.testing.assert_allclose(got[0], 4.0)


# ---------------------------------------------------------------------------
# Predictor folding path
# ---------------------------------------------------------------------------

def test_predictor_folds_batch_norm(tmp_path):
    from paddle_tpu.inference import Predictor

    def build():
        img = fluid.data("img", [None, 3, 8, 8])
        c = L.conv2d(img, 4, 3, padding=1, bias_attr=False)
        b = L.batch_norm(c)
        return L.relu(b)

    main, startup, out = _build(build)
    exe = fluid.Executor()
    scope = Scope()
    exe.run(startup, scope=scope)
    # non-trivial moving stats so the fold has real work
    rng = np.random.default_rng(5)
    for n, v in list(scope.vars.items()):
        a = np.asarray(v)
        if a.ndim == 1:
            scope.set_var(n, jnp.asarray(
                rng.uniform(0.5, 1.5, a.shape).astype(a.dtype)))
    with fluid.framework.executor.scope_guard(scope):
        fluid.io.save_inference_model(str(tmp_path), ["img"], [out],
                                      exe, main_program=main)
    xb = rng.standard_normal((2, 3, 8, 8)).astype(np.float32)
    fluid.set_flags({"FLAGS_inference_fold": False})
    try:
        plain = Predictor(str(tmp_path))
        ref = plain.run({"img": xb})
        plain_ops = _op_types(plain._program)
    finally:
        fluid.set_flags({"FLAGS_inference_fold": True})
    folded = Predictor(str(tmp_path))
    assert folded._fold_report is not None
    assert "batch_norm" not in _op_types(folded._program)
    assert len(_op_types(folded._program)) <= len(plain_ops)
    got = folded.run({"img": xb})
    np.testing.assert_allclose(got[0], ref[0], rtol=1e-4, atol=1e-5)
    # the degraded (eager) path serves the same folded program
    eager = folded.run_eager({"img": xb})
    np.testing.assert_allclose(eager[0], ref[0], rtol=1e-4, atol=1e-5)
