"""The decode engine's KV cache in its one resident layout
`[L, S, H, D, T]` (serving/decode.py): the two Pallas calls that write
and read it where it lies, in interpret mode on the CPU, and the engine
on the kernel path, token for token against generate()."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.attention import (decode_attention,
                                          resident_decode_attention)
from paddle_tpu.kernels.flash_attention import (flash_decode_resident,
                                                kv_append)
from paddle_tpu.models import generate as G
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

LAYERS, SLOTS, HEADS, DIM, DEPTH = 3, 5, 2, 64, 256


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.fixture(scope="module")
def caches():
    rng = np.random.default_rng(28)
    shape = (LAYERS, SLOTS, HEADS, DIM, DEPTH)
    return {dt: (_rand(rng, shape, dt), _rand(rng, shape, dt))
            for dt in (jnp.float32, jnp.bfloat16)}


# ---------------------------------------------------------------------
# kv_append
# ---------------------------------------------------------------------

# slot 4 plays the inactive slot: the engine clamps its stale
# pos >= T to the slot's own last column before the write
POSITIONS = [0, 127, 128, DEPTH - 1, DEPTH + 7]


@pytest.mark.parametrize("layer", range(LAYERS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_append_writes_its_columns_and_nothing_else(caches, dtype, layer):
    k_cache, v_cache = caches[dtype]
    rng = np.random.default_rng(layer)
    k_new = _rand(rng, (SLOTS, HEADS, DIM), dtype)
    v_new = _rand(rng, (SLOTS, HEADS, DIM), dtype)
    cols = np.minimum(POSITIONS, DEPTH - 1)
    got_k, got_v = jax.jit(kv_append)(k_cache, v_cache, k_new, v_new,
                                      jnp.int32(layer), jnp.asarray(cols))
    for got, cache, new in ((got_k, k_cache, k_new),
                            (got_v, v_cache, v_new)):
        want = np.asarray(cache).copy()
        # every other layer, every other slot's tile and every other
        # column of the tile come back as they went in
        want[layer, np.arange(SLOTS), :, :, cols] = np.asarray(new)
        assert got.dtype == cache.dtype
        np.testing.assert_array_equal(np.asarray(got), want)


def test_append_refuses_a_depth_that_is_no_lane_multiple():
    cache = jnp.zeros((1, 2, 2, 64, 96), jnp.float32)
    new = jnp.zeros((2, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 128"):
        kv_append(cache, cache, new, new, 0, jnp.zeros(2, jnp.int32))


# ---------------------------------------------------------------------
# flash_decode_resident
# ---------------------------------------------------------------------

@pytest.mark.parametrize("lengths", [
    [1, 128, 129, DEPTH, 40], [DEPTH] * SLOTS, [1] * SLOTS],
    ids=["ragged", "full", "one"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_resident_attention_matches_the_xla_math(caches, dtype, tol,
                                                 lengths):
    k_cache, v_cache = caches[dtype]
    q = _rand(np.random.default_rng(7), (SLOTS, HEADS, 1, DIM), dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    for layer in range(LAYERS):
        got = jax.jit(flash_decode_resident)(
            q, k_cache, v_cache, jnp.int32(layer), lengths)
        want = decode_attention(
            q, jnp.swapaxes(k_cache[layer], -1, -2),
            jnp.swapaxes(v_cache[layer], -1, -2), pos=lengths - 1,
            use_flash=False)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol)


def test_resident_attention_refuses_a_cache_of_another_shape(caches):
    k_cache, v_cache = caches[jnp.float32]
    q = jnp.zeros((SLOTS + 1, HEADS, 1, DIM), jnp.float32)
    with pytest.raises(ValueError, match="resident caches"):
        flash_decode_resident(q, k_cache, v_cache, 0,
                              jnp.ones(SLOTS + 1, jnp.int32))


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "pallas"])
def test_engine_layer_appends_then_attends(caches, monkeypatch, kernels):
    """`resident_decode_attention`, the engine's one call a layer, on
    either side of its predicate: the new column is in the cache and is
    attended; a clamped slot writes into its own last column."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH_DECODE", str(int(kernels)))
    k_cache, v_cache = caches[jnp.float32]
    rng = np.random.default_rng(3)
    q, k_new, v_new = (_rand(rng, (SLOTS, HEADS, 1, DIM), jnp.float32)
                       for _ in range(3))
    pos = jnp.asarray(POSITIONS, jnp.int32)
    layer = 1
    o, got_k, got_v = jax.jit(resident_decode_attention)(
        q, k_new, v_new, k_cache, v_cache, jnp.int32(layer), pos)
    cols = np.minimum(POSITIONS, DEPTH - 1)
    want_k, want_v = np.asarray(k_cache).copy(), np.asarray(v_cache).copy()
    want_k[layer, np.arange(SLOTS), :, :, cols] = np.asarray(k_new)[:, :, 0]
    want_v[layer, np.arange(SLOTS), :, :, cols] = np.asarray(v_new)[:, :, 0]
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    want = decode_attention(
        q, jnp.swapaxes(jnp.asarray(want_k[layer]), -1, -2),
        jnp.swapaxes(jnp.asarray(want_v[layer]), -1, -2), pos=pos,
        use_flash=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------
# the engine on the kernel path
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def kernel_model():
    """Two heads of 64 and a cache 256 deep: shapes the kernels tile."""
    np.random.seed(28)
    return GPT(GPTConfig(vocab_size=97, hidden_size=128, num_layers=2,
                         num_heads=2, max_seq_len=256, dropout=0.0))


def _drain(eng, futs, max_steps=400):
    for _ in range(max_steps):
        if all(f.done() for f in futs):
            return
        eng.step()
    raise AssertionError("engine did not drain")


def test_refilled_slot_never_attends_the_old_tenants_columns(
        kernel_model, monkeypatch):
    """One slot.  Its first tenant fills columns 0..149 (bucket 128,
    then 30 decode steps); the second, a shorter prompt in the smaller
    bucket, overwrites columns 0..31 only and then decodes through the
    old tenant's columns up to 140, where the stale values still lie
    beyond each step's position.  With the kernels forced (interpret
    mode), token for token what generate() emits."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH_DECODE", "1")
    eng = DecodeEngine(kernel_model, config=DecodeConfig(
        slots=1, max_len=256, buckets=(32, 128), watchdog_stall_s=60.0,
        label=f"resident_{time.time_ns() % 100000}"), auto_start=False)
    step = str(jax.make_jaxpr(functools.partial(
        decode_mod._decode_step_impl, cfg=eng.params.cfg))(
        jax.eval_shape(eng._fresh_state), eng._trees, np.zeros(1, bool)))
    assert "name=kv_append" in step and "name=flash_decode" in step
    rng = np.random.default_rng(5)
    first = rng.integers(0, 97, size=120)
    second = rng.integers(0, 97, size=20)
    try:
        f1 = eng.submit(first, 30)
        _drain(eng, [f1])
        stale = np.asarray(eng._state["k"])[:, 0, :, :, 32:149]
        assert (np.abs(stale).max(axis=(0, 1, 2)) > 0).all()
        f2 = eng.submit(second, 121)
        _drain(eng, [f2])
        assert int(eng._state["pos"][0]) == 140
    finally:
        eng.close()
    for prompt, n, fut in ((first, 30, f1), (second, 121, f2)):
        ref = np.asarray(G.generate(kernel_model, prompt[None, :],
                                    max_new_tokens=n))[0]
        np.testing.assert_array_equal(fut.result(timeout=0), ref)
