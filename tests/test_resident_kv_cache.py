"""The decode engine's KV cache in its one resident layout
`[L, S, H, D, T]` (serving/decode.py): the Pallas calls that write and
read it where it lies (`kv_append` + `flash_decode` for equal heads;
`gqa_decode`, which walks a slot's live tiles and writes the column
itself, for grouped ones), in interpret mode on the CPU, and the engine
on the kernel path, token for token against generate()."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.attention import (_grouped_decode, decode_attention,
                                          resident_decode_attention,
                                          resident_decode_walk)
from paddle_tpu.kernels.flash_attention import (GqaTiling, _visits,
                                                flash_decode_resident,
                                                gqa_decode, gqa_tiling,
                                                gqa_walk, kv_append,
                                                tiles_walked)
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
# gqa_decode: the walk over live tiles that writes the step's column
# ---------------------------------------------------------------------

# name: (ring, depth, tile, each slot's position).  A cache that is no
# ring clamps a stale position into its last column; a ring writes
# column pos mod depth and, once wrapped, reads its whole depth.
WALKS = {
    # lengths 1, a tile's edge on either side, the full depth
    "ragged": (False, 512, 128, [0, 127, 128, 300, 511, 40]),
    # idle slots whose stale pos >= T, beside live ones
    "stale": (False, 256, 128, [5, 256 + 7, 255, 1000]),
    "ring_not_wrapped": (True, 384, 128, [0, 100, 127, 128, 383]),
    "ring_just_wrapped": (True, 384, 128, [384, 385, 511, 767]),
    # wrapped, the write position in a first, a middle and a last tile
    "ring_wrapped": (True, 384, 128, [3 * 384 + 5, 2 * 384 + 200,
                                      5 * 384 + 380, 384 + 128]),
    # eight visits in a slot: the four buffers turn twice
    "turns": (False, 1024, 128, [1023, 700, 64]),
    # the tiling's own tile, 512 of a depth of 1,024
    "own_tile": (False, 1024, None, [1023, 511, 512, 3]),
}
# name: (K/V heads, query heads, head_dim)
GROUPS = {"group8": (4, 32, 128), "group1": (16, 16, 64)}
GQA_CASES = [(h, w, jnp.float32, 1e-5) for h, w in [
    ("group8", "ragged"), ("group8", "stale"),
    ("group8", "ring_not_wrapped"), ("group8", "ring_just_wrapped"),
    ("group8", "ring_wrapped"), ("group8", "turns"), ("group8", "own_tile"),
    ("group1", "ragged"), ("group1", "stale"), ("group1", "ring_wrapped")]
] + [("group8", "ring_wrapped", jnp.bfloat16, 2e-2),
     ("group1", "turns", jnp.bfloat16, 2e-2)]


@pytest.mark.parametrize(
    "heads,walk,dtype,tol", GQA_CASES,
    ids=[f"{h}-{w}-{jnp.dtype(d).name}" for h, w, d, _ in GQA_CASES])
def test_gqa_decode_writes_its_column_and_attends_the_live_tiles(
        heads, walk, dtype, tol):
    """The kernel (interpreted) against the XLA mathematics, a scatter
    of the column and `_grouped_decode`: the result within the type's
    rounding, both caches equal to the reference everywhere, the written
    column included and nothing else touched (the other layer, the other
    slots, the other columns)."""
    kvh, h, d = GROUPS[heads]
    ring, depth, tile, pos = WALKS[walk]
    s = len(pos)
    rng = np.random.default_rng(37)
    q = _rand(rng, (s, h, 1, d), dtype)
    k_new, v_new = (_rand(rng, (s, kvh, d), dtype) for _ in range(2))
    k_cache, v_cache = (_rand(rng, (2, s, kvh, d, depth), dtype)
                        for _ in range(2))
    pos = np.asarray(pos)
    at = pos % depth if ring else np.minimum(pos, depth - 1)
    live = np.minimum(pos + 1, depth)
    o, got_k, got_v = jax.jit(
        lambda *a: gqa_decode(*a, jnp.int32(1), jnp.asarray(at),
                              jnp.asarray(live), block_k=tile))(
        q, k_new, v_new, k_cache, v_cache)
    want_k, want_v = np.asarray(k_cache).copy(), np.asarray(v_cache).copy()
    want_k[1, np.arange(s), :, :, at] = np.asarray(k_new)
    want_v[1, np.arange(s), :, :, at] = np.asarray(v_new)
    assert got_k.dtype == k_cache.dtype and o.dtype == q.dtype
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)
    want = _grouped_decode(q, jnp.asarray(want_k[1]), jnp.asarray(want_v[1]),
                           jnp.asarray(live), None)
    assert o.shape == want.shape == (s, h, 1, d)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_gqa_decode_takes_the_walk_its_caller_built():
    """The tables depend on the lengths alone: handed in (`walk=`), they
    give the bits the call computes for itself, and the call then holds
    no table of its own."""
    rng = np.random.default_rng(5)
    kvh, h, d, depth = 2, 8, 64, 256
    q = _rand(rng, (3, h, 1, d), jnp.float32)
    k_new, v_new = (_rand(rng, (3, kvh, d), jnp.float32) for _ in range(2))
    k_cache, v_cache = (_rand(rng, (2, 3, kvh, d, depth), jnp.float32)
                        for _ in range(2))
    at = jnp.asarray([0, 130, 255], jnp.int32)
    live = at + 1
    walk = gqa_walk(live, kvh, d, depth, block_k=128)

    def call(walk):
        return lambda *a: gqa_decode(*a, 0, at, live, walk=walk, block_k=128)

    args = (q, k_new, v_new, k_cache, v_cache)
    for a, b in zip(call(walk)(*args), call(None)(*args)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "cumsum" in str(jax.make_jaxpr(call(None))(*args))
    assert "cumsum" not in str(jax.make_jaxpr(call(walk))(*args))


@pytest.mark.parametrize("kvh,d,depth,tile", [
    (4, 128, 9728, 512), (4, 128, 2048, 512),     # the Trinity cell's caches
    (4, 128, 4096, 512), (16, 64, 1024, 512),     # the smoke's; GPT's, group 1
    (2, 64, 384, 128), (2, 64, 256, 256), (8, 256, 2048, 256)])
def test_gqa_tiling_over_the_shapes_in_use(kvh, d, depth, tile):
    assert gqa_tiling(kvh, d, depth) == GqaTiling(tile, 128, 4)
    assert gqa_tiling(kvh, d, depth, block_k=128) == GqaTiling(128, 128, 4)
    # four buffers of K and of V stay inside 8 MiB of bfloat16
    assert 2 * 4 * kvh * d * tile * 2 <= 8 << 20
    lengths = [1, tile, min(tile + 1, depth), depth]
    first, slot = _visits(jnp.asarray(lengths, jnp.int32), depth, tile)
    tiles = [1, 1, min(2, depth // tile), depth // tile]
    assert tiles_walked(lengths, tile) == sum(tiles) == int(first[-1])
    assert list(np.asarray(first)) == list(np.cumsum([0] + tiles))
    assert list(np.asarray(slot)[:sum(tiles)]) == [
        i for i, n in enumerate(tiles) for _ in range(n)]


@pytest.mark.parametrize("depth,block_k", [(9728, 1024), (300, None),
                                           (512, 192)])
def test_gqa_tiling_refuses_what_does_not_tile(depth, block_k):
    with pytest.raises(ValueError, match="multiple of"):
        gqa_tiling(4, 128, depth, block_k=block_k)


def test_gqa_decode_refuses_a_cache_of_another_shape():
    cache = jnp.zeros((1, 2, 2, 64, 128), jnp.float32)
    new = jnp.zeros((2, 2, 64), jnp.float32)
    lens = jnp.ones(2, jnp.int32)
    with pytest.raises(ValueError, match="resident caches"):
        gqa_decode(jnp.zeros((2, 3, 1, 64), jnp.float32), new, new, cache,
                   cache, 0, lens - 1, lens)
    with pytest.raises(ValueError, match="q_len"):
        gqa_decode(jnp.zeros((2, 4, 2, 64), jnp.float32), new, new, cache,
                   cache, 0, lens - 1, lens)


def test_decode_walk_is_built_where_the_kernel_is_taken(monkeypatch):
    """`resident_decode_walk`: the tables over min(pos + 1, T) columns
    where `resident_decode_attention` takes the kernel, nothing where it
    runs the XLA mathematics."""
    cache = jnp.zeros((2, 3, 2, 64, 256), jnp.float32)
    pos = jnp.asarray([0, 255, 1000], jnp.int32)
    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH_DECODE", "0")
    assert resident_decode_walk(pos, cache) is None
    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH_DECODE", "1")
    first, slot = resident_decode_walk(pos, cache)
    want = gqa_walk(jnp.asarray([1, 256, 256]), 2, 64, 256)
    np.testing.assert_array_equal(np.asarray(first), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(want[1]))
    assert list(np.asarray(first)) == [0, 1, 2, 3]      # one tile of 256
    assert resident_decode_walk(pos, jnp.zeros((2, 3, 2, 64, 96))) is None


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
