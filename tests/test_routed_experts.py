"""`distributed/moe.py` `routed_experts` over its kept rows, the default
wherever the shape has the case (`_rows_kept`), on the CPU at toy widths
against the float64 layer of `moe_reference.py`.

Two geometries, the shares of the two expert cells: 4 of 32 experts held
(Trinity's eighth) and 4 of 128 (K2's thirty-second), top 8 either way;
a sequence (a prefill's tokens) and a decode batch (a step's slots);
and four cases: the usual one, an overflow (a planted bias sends every
token to the held experts, more than the kept rows hold, so every row
runs), padding that makes no assignment, and the grouped product
through `moe_grouped_mm` in the interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.distributed.moe import _rows_kept, routed_experts

from moe_reference import dense_moe  # noqa: E402

HELD, TOP_K, SCALE = 4, 8, 2.826
# tokens of each kind of program, and of the interpreted kernel's (whose
# widths are whole lanes)
TOKENS = {"sequence": 300, "decode": 64}
KERNEL_TOKENS = {"sequence": 256, "decode": 128}
# the kept rows for those shapes (rows = tokens x 8): twice the held
# experts' share, in whole tiles of 128 rows
KEPT = {(32, "sequence"): 640, (32, "decode"): 128,
        (128, "sequence"): 256, (128, "decode"): 128}
KERNEL_KEPT = {(32, "sequence"): 512, (32, "decode"): 256,
               (128, "sequence"): 128, (128, "decode"): 128}


def _rand(rng, shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)


def _layer(router, bias, experts, routed, valid, kernel):
    return jax.jit(lambda h: routed_experts(
        h, router, bias, experts, 8, routed, TOP_K, SCALE, valid=valid,
        use_kernel=kernel or None))


@pytest.mark.parametrize("case", ["usual", "overflow", "padding", "kernel"])
@pytest.mark.parametrize("program", ["sequence", "decode"])
@pytest.mark.parametrize("routed", [32, 128], ids=["eighth", "32nd"])
def test_kept_rows_run_the_held_rows_only_and_drop_nothing(routed, program,
                                                           case):
    """Where the held assignments fit the kept rows the layer runs over
    those alone and equals the float64 layer over every token (also
    with padding that makes none, also through `moe_grouped_mm`); where
    a planted bias sends more, every row runs and nothing is dropped.
    The counts are the reference's either way, and the third result says
    which of the two ran.  The `cond` is in the layer where `_rows_kept`
    gives a case, and not at a shape where it gives none."""
    kernel = case == "kernel"
    n = (KERNEL_TOKENS if kernel else TOKENS)[program]
    d, f = (128, 128) if kernel else (32, 16)
    kept = _rows_kept(n * TOP_K, HELD / routed)
    assert kept == (KERNEL_KEPT if kernel else KEPT)[routed, program]
    assert 2 * kept <= n * TOP_K
    rng = np.random.default_rng(21 + routed + n)
    router = _rand(rng, (d, routed), 0.5)
    bias = _rand(rng, (routed,), 0.05)
    if case == "overflow":
        bias = bias.at[8:8 + HELD].add(10.0)
    experts = (_rand(rng, (HELD, d, 2 * f), 0.2),
               _rand(rng, (HELD, f, d), 0.2))
    h = _rand(rng, (n, d))
    live = n - 50 if case == "padding" else n
    valid = np.arange(n) < live
    layer = _layer(router, bias, experts, routed,
                   jnp.asarray(valid) if case == "padding" else None, kernel)

    text = str(jax.make_jaxpr(layer)(h))
    assert "cond[" in text and "scatter-add" in text
    assert ("name=moe_grouped_mm" in text) == kernel
    # 16 tokens: 128 rows, of which a tile is more than half
    assert _rows_kept(16 * TOP_K, HELD / routed) is None
    small = str(jax.make_jaxpr(
        _layer(router, bias, experts, routed, None, False))(h[:16]))
    assert "cond[" not in small and "scatter-add" not in small

    with jax.default_matmul_precision("highest"):
        got, counts, ran_kept = layer(h)
    want, want_counts = dense_moe(h, router, bias, *experts, TOP_K, SCALE,
                                  first=8, valid=valid)
    assert list(np.asarray(counts)) == list(want_counts)
    assert (int(counts.sum()) > kept) == (case == "overflow")
    assert int(ran_kept) == (case != "overflow")
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=2e-4 if kernel else 1e-5)
    assert want.any()
    if case == "padding":
        assert not np.asarray(got)[live:].any()
