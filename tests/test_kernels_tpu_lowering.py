"""Every Pallas kernel the package ships, lowered for the TPU platform
from the CPU suite (jaxpr -> Mosaic: block shapes, memory spaces,
primitives the lowering knows) and, where libtpu can describe a v5e
without a chip, compiled by the real Mosaic/XLA TPU compiler too.
A kernel that can be selected on the chip and cannot compile there must
fail here first."""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.kernels import attention, backend
from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                flash_attention_with_lse,
                                                flash_decode,
                                                flash_decode_resident,
                                                kv_append)
from paddle_tpu.kernels.layer_norm import layer_norm_pallas
from paddle_tpu.kernels.topk_threshold import dgc_topk_mask_pallas

BF16, F32 = jnp.bfloat16, jnp.float32


def _sum32(fn):
    return lambda *a: jnp.sum(fn(*a).astype(F32))


def _cases():
    """(name, fn, [(shape, dtype), ...]) at shapes of the models the
    repo runs: the bert and transformer_flash GPT geometries."""
    att = [((2, 12, 2048, 64), BF16)] * 3

    def causal(q, k, v):
        return flash_attention(q, k, v, causal=True)

    # the benchmark's own shapes (BENCHMARK.json): the train cell's 8 x
    # 16 heads at seq 1024, the K2 cell's widest prefill bucket
    train = [((8, 16, 1024, 64), BF16)] * 3
    k2_prefill = [((1, 64, 2048, 256), BF16)] * 3

    ln = [((8192, 768), BF16), ((768,), BF16), ((768,), BF16)]
    dec = [((8, 12, 1, 64), BF16), ((8, 12, 2048, 64), BF16),
           ((8, 12, 2048, 64), BF16), ((8,), jnp.int32)]
    # the decode engine's resident cache [L, S, H, D, T] at the serve
    # cell's widths (BENCHMARK.json), two layers of it
    cache = ((2, 64, 16, 64, 1024), BF16)
    scalar, per_slot = ((), jnp.int32), ((64,), jnp.int32)
    return [
        ("flash_fwd", causal, att),
        ("flash_fwd_bwd", jax.grad(_sum32(causal), argnums=(0, 1, 2)), att),
        ("flash_train_cell", jax.grad(_sum32(causal), argnums=(0, 1, 2)),
         train),
        ("flash_k2_prefill", causal, k2_prefill),
        ("flash_with_lse_fwd_bwd", jax.grad(
            lambda q, k, v: sum(jnp.sum(o.astype(F32)) for o in
                                flash_attention_with_lse(q, k, v)),
            argnums=(0, 1, 2)), [((1, 4, 1024, 128), F32)] * 3),
        ("flash_decode", flash_decode, dec),
        ("flash_decode_f32_d128", flash_decode,
         [((3, 4, 1, 128), F32), ((3, 4, 1280, 128), F32),
          ((3, 4, 1280, 128), F32), ((3,), jnp.int32)]),
        ("flash_decode_resident", flash_decode_resident,
         [((64, 16, 1, 64), BF16), cache, cache, scalar, per_slot]),
        ("flash_decode_resident_f32_d128", flash_decode_resident,
         [((3, 4, 1, 128), F32), ((2, 3, 4, 128, 1280), F32),
          ((2, 3, 4, 128, 1280), F32), scalar, ((3,), jnp.int32)]),
        ("kv_append", lambda *a: kv_append(*a)[0],
         [cache, cache, ((64, 16, 64), BF16), ((64, 16, 64), BF16),
          scalar, per_slot]),
        ("layer_norm_fwd", layer_norm_pallas, ln),
        ("layer_norm_fwd_bwd",
         jax.grad(_sum32(layer_norm_pallas), argnums=(0, 1, 2)), ln),
        ("topk_threshold", lambda g: dgc_topk_mask_pallas(g, 0.999),
         [((768, 3072), F32)]),
    ]


CASES = _cases()
IDS = [c[0] for c in CASES]
# the names the compiled program's Mosaic calls carry, which is what a
# device trace lists them under
KERNELS = {"flash_fwd": ["flash_fwd"],
           "flash_fwd_bwd": ["flash_fwd", "flash_dq", "flash_dkv"],
           "flash_train_cell": ["flash_fwd", "flash_dq", "flash_dkv"],
           "flash_k2_prefill": ["flash_fwd"],
           "flash_with_lse_fwd_bwd": ["flash_fwd", "flash_dq", "flash_dkv"],
           "flash_decode": ["flash_decode"],
           "flash_decode_f32_d128": ["flash_decode"],
           "flash_decode_resident": ["flash_decode"],
           "flash_decode_resident_f32_d128": ["flash_decode"],
           "kv_append": ["kv_append"],
           "layer_norm_fwd": ["layer_norm_fwd"],
           # the gradient of a sum needs no forward output
           "layer_norm_fwd_bwd": ["layer_norm_bwd"],
           "topk_threshold": ["topk_threshold"]}


@pytest.fixture
def compiled_kernels(monkeypatch):
    """interpret=False, as on the chip."""
    monkeypatch.setattr(backend, "is_tpu_backend", lambda: True)


@pytest.fixture(scope="module")
def v5e():
    """A compile-only description of a 2x2 v5e from libtpu; no chip."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 — any libtpu refusal: skip
        pytest.skip(f"libtpu cannot describe a v5e here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


@pytest.mark.parametrize("name,fn,args", CASES, ids=IDS)
def test_kernel_lowers_for_tpu(compiled_kernels, name, fn, args):
    avals = [jax.ShapeDtypeStruct(s, d) for s, d in args]
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name,fn,args", CASES, ids=IDS)
def test_kernel_compiles_for_v5e(compiled_kernels, v5e, name, fn, args):
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in args]
    compiled = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert sorted(set(_mosaic_calls(text))) == sorted(KERNELS[name])


def _mosaic_calls(text):
    return re.findall(r"%([\w-]+?)(?:\.\d+)? = [^\n]*"
                      r'custom_call_target="tpu_custom_call"', text)


def test_flash_row_statistics_stay_lane_dense(compiled_kernels, v5e):
    """The saved logsumexp and delta cross the kernels' boundary with
    the sequence minor: at the train cell's shape no instruction of the
    compiled forward + backward has a result with a minor dimension of
    1 (which the chip pads to 128 lanes, and XLA then copies)."""
    name, fn, args = next(c for c in CASES if c[0] == "flash_train_cell")
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in args]
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert re.search(r"f32\[128,1,1024\]\{2,1,0:T\(1,128\)", text), \
        "the statistics are not laid out [heads, 1, seq] in 128-lane tiles"
    padded = [line.strip()[:120] for line in text.splitlines()
              if re.search(r"= \(?f32\[[\d,]*,1\]", line)]
    assert not padded, padded


# ---------------------------------------------------------------------
# the decode engine's programs, compiled whole: the cache is held once
# and no instruction copies, slices or re-lays a layer of it
# ---------------------------------------------------------------------

SLOTS, HEADS, HEAD_DIM, DEPTH, LAYERS = 64, 16, 64, 1024, 4
LAYER_ELEMS = SLOTS * HEADS * DEPTH * HEAD_DIM
CACHE_BYTES = 2 * LAYERS * LAYER_ELEMS * 2          # K and V, bfloat16
# results that may be as large as a layer's cache: the donated buffers
# handed along, and the Mosaic calls that read and write them in place
PASSES_ALONG = {"parameter", "get-tuple-element", "tuple", "bitcast",
                "while", "tpu_custom_call"}
_INSTR = re.compile(r"^\s*(?:ROOT )?%[\w.-]+ = (.*?) ([a-z][\w-]*)\(")


def _results(text):
    """[(opcode, dtype, dims, line)] for every array an instruction of
    the compiled program yields (each element of a tuple result), those
    inside fused computations too."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = m.group(2)
        if op == "custom-call" and "tpu_custom_call" in line:
            op = "tpu_custom_call"
        out += [(op, dtype, tuple(int(n) for n in dims.split(",") if n),
                 line.strip()[:160])
                for dtype, dims in re.findall(r"([a-z]\w*)\[([\d,]*)\]",
                                              m.group(1))]
    return out


def _large_results(text, layer_elems=LAYER_ELEMS):
    """[(opcode, line)] of every instruction of the compiled program
    whose result, or an element of whose tuple result, holds a layer's
    cache or more."""
    return list(dict.fromkeys((op, line) for op, _, dims, line
                              in _results(text)
                              if math.prod(dims) >= layer_elems))


@pytest.fixture(scope="module")
def engine_programs(v5e):
    """(decode step, prefill at bucket 64) of `DecodeEngine`, at the
    serve cell's widths and four layers, compiled for the described
    v5e with the state donated, as the engine jits them."""
    import functools

    from paddle_tpu.models import generate as G
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.nn.parameter import default_rng
    from paddle_tpu.serving import decode as D

    one = jax.sharding.SingleDeviceSharding(v5e[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    box = {}

    def trees():
        # shapes only; the layers draw their initial values from a key
        # of this trace's own, not from the process's generator
        with default_rng.key_context(jax.random.PRNGKey(0)):
            p = G.build_decode_params(GPT(GPTConfig(
                vocab_size=50257, hidden_size=HEADS * HEAD_DIM,
                num_layers=LAYERS, num_heads=HEADS, max_seq_len=DEPTH,
                dropout=0.0, dtype="bfloat16")))
        box["cfg"] = p.cfg
        return p.emb, p.blocks, p.head

    trees = jax.tree.map(lambda a: aval(a.shape, a.dtype),
                         jax.eval_shape(trees))
    kv = aval((LAYERS, SLOTS, HEADS, HEAD_DIM, DEPTH), BF16)
    i32, f32 = jnp.int32, jnp.float32
    state = {"k": kv, "v": kv, "pos": aval((SLOTS,), i32),
             "active": aval((SLOTS,), bool), "token": aval((SLOTS,), i32),
             "stop": aval((SLOTS,), i32), "eos": aval((SLOTS,), i32),
             "temp": aval((SLOTS,), f32),
             "key": aval((SLOTS, 2), jnp.uint32)}

    def compiled(impl, *args):
        return jax.jit(functools.partial(impl, cfg=box["cfg"]),
                       donate_argnums=(0,)).trace(
            state, trees, *args).lower(
            lowering_platforms=("tpu",)).compile()

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(backend, "is_tpu_backend", lambda: True)
        return {
            "decode_step": compiled(D._decode_step_impl,
                                    aval((SLOTS,), bool)),
            "prefill_b64": compiled(
                D._prefill_impl, aval((1, 64), i32), aval((), i32),
                aval((), i32), aval((), i32), aval((), i32),
                aval((), f32), aval((2,), jnp.uint32)),
        }


@pytest.mark.parametrize("program,in_place", [
    ("decode_step", set()),
    # the prefill's one write into the slot's region, in place on the
    # donated cache, alone or as the root of its fusion
    ("prefill_b64", {"dynamic-update-slice", "fusion"})])
def test_engine_program_moves_no_layer_of_the_cache(engine_programs,
                                                    program, in_place):
    text = engine_programs[program].as_text()
    large = _large_results(text)
    assert large, "the cache is not in the program at all"
    odd = [(op, line) for op, line in large
           if op not in PASSES_ALONG | in_place]
    assert not odd, odd
    for op, line in large:
        if op == "fusion":
            assert "dynamic-update-slice_fusion" in line, line


@pytest.mark.parametrize("program", ["decode_step", "prefill_b64"])
def test_engine_program_holds_one_copy_of_the_cache(engine_programs,
                                                    program):
    mem = engine_programs[program].memory_analysis()
    assert mem.temp_size_in_bytes < 0.05 * CACHE_BYTES
    # every byte of K and V goes out in the buffer it came in
    assert mem.alias_size_in_bytes >= CACHE_BYTES
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 1 << 20


def test_decode_step_names_its_mosaic_calls(engine_programs):
    calls = _mosaic_calls(engine_programs["decode_step"].as_text())
    assert sorted(calls) == ["flash_decode", "kv_append"]


def _attn_grads(q, k, v):
    return jax.grad(_sum32(lambda q, k, v: attention.dot_product_attention(
        q, k, v, is_causal=True)), argnums=(0, 1, 2))(q, k, v)


def test_sharded_flash_compiles_for_a_v5e_mesh(compiled_kernels, v5e):
    """GSPMD refuses to partition a Mosaic call; under a mesh the
    dispatch must hand it per-shard work through shard_map, or a jit
    over dp/tp-sharded arrays cannot compile on the chip at all."""
    mesh = Mesh(np.array(v5e[:4]).reshape(2, 2), ("dp", "tp"))
    aval = jax.ShapeDtypeStruct((4, 8, 1024, 64), BF16,
                                sharding=NamedSharding(mesh, P("dp", "tp")))
    with jax.set_mesh(mesh):
        lowered = jax.jit(_attn_grads).trace(aval, aval, aval).lower(
            lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_sharded_flash_matches_xla_on_the_cpu_mesh(monkeypatch):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    rng = np.random.default_rng(0)
    q, k, v = (jax.device_put(
        jnp.asarray(rng.standard_normal((2, 4, 128, 64)), F32),
        NamedSharding(mesh, P("dp", "tp"))) for _ in range(3))
    want = jax.jit(_attn_grads)(q, k, v)          # XLA composition
    monkeypatch.setenv("PADDLE_TPU_FORCE_FLASH", "1")
    with jax.set_mesh(mesh):
        got = jax.jit(_attn_grads)(q, k, v)       # kernel, interpreted
    for g, w in zip(got, want):
        assert g.sharding.spec == P("dp", "tp")
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-5)


def test_decode_dispatch_picks_the_kernel_on_default_serving_depth(
        compiled_kernels):
    """FLAGS_decode_max_len = 2048 puts the default DecodeEngine on
    flash_decode: that exact call must lower."""
    from paddle_tpu import flags

    t = flags.flag("decode_max_len")
    q = jax.ShapeDtypeStruct((8, 16, 1, 64), BF16)
    kv = jax.ShapeDtypeStruct((8, 16, t, 64), BF16)
    pos = jax.ShapeDtypeStruct((8,), jnp.int32)
    text = jax.jit(lambda q, k, v, p: attention.decode_attention(
        q, k, v, pos=p, scale=1.0 / math.sqrt(64))).trace(
        q, kv, kv, pos).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


# ---------------------------------------------------------------------
# Kimi-K2 behind the same engine (ISSUE 29): the latent kernels and the
# grouped product at the published widths, and the decode step and a
# prefill compiled whole over the latent cache
# ---------------------------------------------------------------------

K2_SLOTS, K2_DEPTH, K2_LAYERS = 64, 2048, 2          # 1 dense + 1 expert
K2_LAYER_ELEMS = K2_SLOTS * 576 * K2_DEPTH
K2_CACHE_BYTES = K2_LAYERS * K2_LAYER_ELEMS * 2


def _k2_cases():
    from paddle_tpu.distributed.moe import routed_experts
    from paddle_tpu.kernels.mla import mla_decode

    def latent_step(layers, slots, depth):
        # the queries, this step's columns, the cache, the positions
        return (lambda ql, qr, new, c, pos: mla_decode(
                    ql, qr, new, c, 1, pos, 0.1),
                [((slots, 64, 512), BF16), ((slots, 64, 64), BF16),
                 ((slots, 576), BF16), ((layers, slots, 576, depth), BF16),
                 ((slots,), jnp.int32)], ["mla_decode"])

    return {
        # the K2 cell's latent cache (BENCHMARK.json), chip_smoke.py's,
        # and two layers of 64 slots
        "mla_decode_cell": latent_step(5, 256, 4096),
        "mla_decode_smoke": latent_step(2, 8, 2048),
        "mla_decode": latent_step(2, 64, 1024),
        # a decode step's tokens over 12 of 384 experts of width 2048
        "routed_experts": (
            lambda h, r, b, gu, d: routed_experts(
                h, r, b, (gu, d), 0, 384, 8, 2.827)[0],
            [((256, 7168), BF16), ((7168, 384), BF16), ((384,), F32),
             ((12, 7168, 4096), BF16), ((12, 2048, 7168), BF16)],
            ["moe_grouped_mm"]),
    }


@pytest.mark.parametrize("name", ["mla_decode_cell", "mla_decode_smoke",
                                  "mla_decode", "routed_experts"])
def test_k2_kernel_compiles_for_v5e(compiled_kernels, v5e, name):
    fn, args, calls = _k2_cases()[name]
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in args]
    donated = [i for i, (s, _) in enumerate(args) if len(s) == 4]
    compiled = jax.jit(fn, donate_argnums=donated).trace(*avals).lower(
        lowering_platforms=("tpu",)).compile()
    assert sorted(set(_mosaic_calls(compiled.as_text()))) == calls
    if donated:
        # the cache is written where it lies: no second one
        cache = math.prod(args[donated[0]][0]) * 2
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= cache
        assert mem.temp_size_in_bytes < cache // 2


@pytest.fixture(scope="module")
def k2_programs(v5e):
    """(decode step, prefills at buckets 256 and 2,048) of
    `DecodeEngine` over `models/kimi_k2.py` at the published widths, one
    dense and one expert layer, compiled for the described v5e with the
    state donated, as the engine jits them."""
    import functools
    import json
    import os

    from paddle_tpu.models import kimi_k2
    from paddle_tpu.serving import decode as D

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "kimi-k2.6.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=K2_LAYERS)
    kcfg = kimi_k2.K2Cfg.from_hf(cfg, max_seq_len=K2_DEPTH)
    one = jax.sharding.SingleDeviceSharding(v5e[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    # the published leaves through the engine's load-time layout
    flat = {n: aval(s, F32 if kind == "bias" else BF16)
            for n, (s, kind) in kimi_k2.param_shapes(kcfg).items()}
    trees = jax.tree.map(
        lambda a: aval(a.shape, a.dtype),
        jax.eval_shape(lambda f: kimi_k2.K2Params.from_flat(kcfg, f).trees,
                       flat))
    i32 = jnp.int32
    state = {"latent": aval((K2_LAYERS, K2_SLOTS, 576, K2_DEPTH), BF16),
             "pos": aval((K2_SLOTS,), i32),
             "active": aval((K2_SLOTS,), bool),
             "token": aval((K2_SLOTS,), i32), "stop": aval((K2_SLOTS,), i32),
             "eos": aval((K2_SLOTS,), i32), "temp": aval((K2_SLOTS,), F32),
             "key": aval((K2_SLOTS, 2), jnp.uint32)}

    def compiled(impl, *args):
        return jax.jit(functools.partial(impl, cfg=kcfg),
                       donate_argnums=(0,)).trace(
            state, trees, *args).lower(
            lowering_platforms=("tpu",)).compile()

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(backend, "is_tpu_backend", lambda: True)
        return {
            "decode_step": compiled(D._decode_step_impl,
                                    aval((K2_SLOTS,), bool)),
            **{f"prefill_b{bucket}": compiled(
                D._prefill_impl, aval((1, bucket), i32), aval((), i32),
                aval((), i32), aval((), i32), aval((), i32),
                aval((), F32), aval((2,), jnp.uint32))
               for bucket in (256, 2048)},
        }


@pytest.mark.parametrize("program,in_place", [
    ("decode_step", set()),
    ("prefill_b256", {"dynamic-update-slice", "fusion"}),
    ("prefill_b2048", {"dynamic-update-slice", "fusion"})])
def test_k2_program_moves_no_layer_of_the_latent_cache(k2_programs, program,
                                                       in_place):
    text = k2_programs[program].as_text()
    large = [(op, line) for op, line in _large_results(text, K2_LAYER_ELEMS)
             # weights are larger than a layer of this small cache: only
             # results with the cache's own dimensions are judged
             if f"[{K2_LAYERS},{K2_SLOTS},576,{K2_DEPTH}]" in line]
    assert large, "the cache is not in the program at all"
    odd = [(op, line) for op, line in large
           if op not in PASSES_ALONG | in_place]
    assert not odd, odd
    for op, line in large:
        if op == "fusion":
            # the prefill's one write, in place: the fusion's root is a
            # dynamic-update-slice of the donated cache itself (the
            # stacking of the layers' latents may be fused into it)
            name = line.split(" = ")[0].lstrip("ROOT %")
            called = re.search(
                rf"%{re.escape(name)} = [^\n]*calls=%([\w.-]+)", text)
            body = text[text.index(f"%{called.group(1)} ("):]
            root = next(ln for ln in body.splitlines() if "ROOT" in ln)
            assert re.search(r"dynamic-update-slice\(%param_0[.\d]*,",
                             root), root
    # no slice of it either: nothing holds one layer, or one slot's part
    for dims in (f"[{K2_SLOTS},576,{K2_DEPTH}]",
                 f"[1,{K2_SLOTS},576,{K2_DEPTH}]"):
        assert dims not in text, dims


@pytest.mark.parametrize("program", ["decode_step", "prefill_b256",
                                     "prefill_b2048"])
def test_k2_program_holds_one_copy_of_the_latent_cache(k2_programs,
                                                       program):
    mem = k2_programs[program].memory_analysis()
    assert mem.alias_size_in_bytes >= K2_CACHE_BYTES
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 1 << 20
    # temporaries: activations of 64 tokens (256 and 2,048 in the
    # prefills: every row of the 2,048 bucket's expert layer in float32,
    # [16384, 7168], is 1.56 times this cache) at these widths, never a
    # second cache (three eighths of this cache is three quarters of
    # one half as deep)
    limit = 3.5 if program == "prefill_b2048" else 0.375
    assert mem.temp_size_in_bytes < limit * K2_CACHE_BYTES


@pytest.mark.parametrize("program", ["decode_step", "prefill_b2048"])
def test_k2_program_runs_the_kept_rows_where_they_fit(k2_programs, program):
    """`routed_experts`' kept case is in the compiled program: 128 of
    the decode step's 512 rows (64 slots x 8, a thirty-second held) and
    1,024 of the prefill's 16,384, under a conditional whose other
    branch runs every row."""
    from paddle_tpu.distributed.moe import _rows_kept

    tokens = K2_SLOTS if program == "decode_step" else 2048
    assert _rows_kept(tokens * 8, 12 / 384) == (
        128 if program == "decode_step" else 1024)
    assert "conditional(" in k2_programs[program].as_text()


# what may re-lay an array: copies and transposes, fused or not, slices
# and gathers
RELAYOUTS = {"copy", "transpose", "slice", "gather"}


@pytest.mark.parametrize("program,tokens", [
    ("decode_step", K2_SLOTS), ("prefill_b256", 256),
    ("prefill_b2048", 2048)])
def test_k2_program_re_lays_no_attention_weight(k2_programs, program,
                                                tokens):
    """The trees hold `kv_b` as W_uk and W_uv in the layout the products
    read, and the rotary columns in halves (`K2Params.from_flat`): no
    instruction copies, transposes, slices or gathers a bfloat16 array
    of `kv_b`'s size or of one of its halves along the rank of 512, and
    none gathers, copies or transposes the rotation's [32, tokens, 64]
    lanes."""
    kv_b = 512 * 64 * (128 + 128)
    results = _results(k2_programs[program].as_text())
    weights = [line for op, dtype, dims, line in results
               if op in RELAYOUTS and dtype == "bf16" and 512 in dims
               and math.prod(dims) in (kv_b, kv_b // 2)]
    assert not weights, weights
    lanes = sorted((32, tokens, 64))
    rotary = [line for op, _, dims, line in results
              if op in RELAYOUTS - {"slice"} and sorted(dims) == lanes]
    assert not rotary, rotary


def test_k2_decode_step_names_its_calls(k2_programs):
    calls = set(_mosaic_calls(k2_programs["decode_step"].as_text()))
    assert calls == {"mla_decode", "moe_grouped_mm"}


# ---------------------------------------------------------------------
# AFMoE (Trinity) behind the same engine: grouped heads, a window, and
# two caches of different depth, each held once
# ---------------------------------------------------------------------

AF_SLOTS, AF_DEPTH, AF_WINDOW = 16, 4096, 2048
AF_KVH, AF_HEADS, AF_DIM = 4, 32, 128
AF_TYPES = ["sliding_attention"] * 3 + ["full_attention"]
AF_RING_ELEMS = AF_SLOTS * AF_KVH * AF_DIM * AF_WINDOW     # one ring layer
AF_CACHE_BYTES = 2 * (3 * AF_RING_ELEMS
                      + AF_SLOTS * AF_KVH * AF_DIM * AF_DEPTH) * 2


def _afmoe_cases():
    from paddle_tpu.kernels.flash_attention import (flash_attention_fwd,
                                                    gqa_decode)

    def step(layers, slots, kvh, heads, d, depth):
        # the queries, this step's columns, the caches, the column each
        # slot writes and its live length
        cache = ((layers, slots, kvh, d, depth), BF16)
        new, per_slot = ((slots, kvh, d), BF16), ((slots,), jnp.int32)
        return (lambda q, kn, vn, kc, vc, at, live: gqa_decode(
                    q, kn, vn, kc, vc, 1, at, live),
                [((slots, heads, 1, d), BF16), new, new, cache, cache,
                 per_slot, per_slot], ["gqa_decode"])

    out = {
        # the cell's caches (BENCHMARK.json, trinity-mini): 64 slots, 3
        # full layers 9,728 deep, 9 rings of 2,048; chip_smoke.py's; and
        # one K/V head a query head at the GPT cell's widths
        "gqa_decode_full": step(3, 64, AF_KVH, AF_HEADS, AF_DIM, 9728),
        "gqa_decode_ring": step(9, 64, AF_KVH, AF_HEADS, AF_DIM, 2048),
        "gqa_decode_smoke": step(2, 8, AF_KVH, AF_HEADS, AF_DIM, 4096),
        "gqa_decode_group1": step(2, 64, 16, 16, 64, 1024),
    }
    for seq in (2048, 8192):
        for window in (None, AF_WINDOW):
            out[f"flash_fwd_{seq}_{window}"] = (
                lambda q, k, v, window=window: flash_attention_fwd(
                    q, k, v, window=window),
                [((1, AF_HEADS, seq, AF_DIM), BF16)]
                + [((1, AF_KVH, seq, AF_DIM), BF16)] * 2, ["flash_fwd"])
    return out


@pytest.mark.parametrize("name", [
    "gqa_decode_full", "gqa_decode_ring", "gqa_decode_smoke",
    "gqa_decode_group1", "flash_fwd_2048_None", "flash_fwd_2048_2048",
    "flash_fwd_8192_None", "flash_fwd_8192_2048"])
def test_afmoe_kernel_compiles_for_v5e(compiled_kernels, v5e, name):
    fn, args, calls = _afmoe_cases()[name]
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in args]
    donated = [i for i, (s, _) in enumerate(args) if len(s) == 5]
    compiled = jax.jit(fn, donate_argnums=donated).trace(*avals).lower(
        lowering_platforms=("tpu",)).compile()
    assert sorted(set(_mosaic_calls(compiled.as_text()))) == calls
    if donated:
        # both caches are written where they lie: no second one
        caches = 2 * math.prod(args[donated[0]][0]) * 2
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= caches
        assert mem.temp_size_in_bytes < caches // 8


@pytest.fixture(scope="module")
def afmoe_programs(v5e):
    """(decode step, prefill at bucket 4096) of `DecodeEngine` over
    `models/afmoe.py` at the published widths, one period of the layer
    pattern (three window layers and a full one; the first dense),
    compiled for the described v5e with the state donated, as the
    engine jits them."""
    import functools
    import json
    import os

    from paddle_tpu.models import afmoe
    from paddle_tpu.serving import decode as D

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "trinity-mini.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=4, layer_types=AF_TYPES)
    acfg = afmoe.AfmoeCfg.from_hf(cfg, max_seq_len=AF_DEPTH)
    one = jax.sharding.SingleDeviceSharding(v5e[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    trees = afmoe.AfmoeParams.from_flat(acfg, {
        n: aval(s, F32 if kind == "bias" else BF16)
        for n, (s, kind) in afmoe.param_shapes(acfg).items()}).trees
    i32 = jnp.int32
    cache = {n: aval(a.shape, a.dtype) for n, a in jax.eval_shape(
        lambda: acfg.cache_arrays(AF_SLOTS, AF_DEPTH)).items()}
    assert {n: a.shape for n, a in cache.items()} == {
        "k_full": (1, AF_SLOTS, AF_KVH, AF_DIM, AF_DEPTH),
        "v_full": (1, AF_SLOTS, AF_KVH, AF_DIM, AF_DEPTH),
        "k_window": (3, AF_SLOTS, AF_KVH, AF_DIM, AF_WINDOW),
        "v_window": (3, AF_SLOTS, AF_KVH, AF_DIM, AF_WINDOW)}
    state = dict(cache, pos=aval((AF_SLOTS,), i32),
                 active=aval((AF_SLOTS,), bool),
                 token=aval((AF_SLOTS,), i32), stop=aval((AF_SLOTS,), i32),
                 eos=aval((AF_SLOTS,), i32), temp=aval((AF_SLOTS,), F32),
                 key=aval((AF_SLOTS, 2), jnp.uint32))

    def compiled(impl, *args):
        return jax.jit(functools.partial(impl, cfg=acfg),
                       donate_argnums=(0,)).trace(
            state, trees, *args).lower(
            lowering_platforms=("tpu",)).compile()

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(backend, "is_tpu_backend", lambda: True)
        return {
            "decode_step": compiled(D._decode_step_impl,
                                    aval((AF_SLOTS,), bool)),
            "prefill_b4096": compiled(
                D._prefill_impl, aval((1, 4096), i32), aval((), i32),
                aval((), i32), aval((), i32), aval((), i32),
                aval((), F32), aval((2,), jnp.uint32)),
        }


_AF_CACHE = re.compile(rf"\[[13],{AF_SLOTS},{AF_KVH},{AF_DIM},"
                       rf"(?:{AF_DEPTH}|{AF_WINDOW})\]")


@pytest.mark.parametrize("program,in_place", [
    ("decode_step", set()),
    ("prefill_b4096", {"dynamic-update-slice", "fusion"})])
def test_afmoe_program_moves_no_layer_of_either_cache(afmoe_programs,
                                                      program, in_place):
    text = afmoe_programs[program].as_text()
    large = [(op, line) for op, line in _large_results(text, AF_RING_ELEMS)
             # the prefill's activations over 4,096 tokens are as large
             # as a ring's layer: only results with a cache's own
             # dimensions are judged
             if _AF_CACHE.search(line.split(" = ", 1)[1].split("(")[0])]
    assert large, "the caches are not in the program at all"
    odd = [(op, line) for op, line in large
           if op not in PASSES_ALONG | in_place]
    assert not odd, odd
    for op, line in large:
        if op == "fusion":
            assert "dynamic-update-slice_fusion" in line, line
    # nothing holds one layer of a cache (or one ring sliced out of the
    # three), and K and V are nowhere repeated over the 32 query heads
    depths = (AF_DEPTH, AF_WINDOW)
    for dims in (
            [f"[{AF_SLOTS},{AF_KVH},{AF_DIM},{d}]" for d in depths]
            + [f"[1,{AF_SLOTS},{AF_KVH},{AF_DIM},{AF_WINDOW}]"]
            + [f"{AF_SLOTS},{AF_HEADS},{AF_DIM},{d}]" for d in depths]
            + [f"{AF_SLOTS},{AF_KVH},8,{AF_DIM},{d}]" for d in depths]):
        assert dims not in text, dims


@pytest.mark.parametrize("program", ["decode_step", "prefill_b4096"])
def test_afmoe_program_holds_one_copy_of_each_cache(afmoe_programs,
                                                    program):
    mem = afmoe_programs[program].memory_analysis()
    assert mem.alias_size_in_bytes >= AF_CACHE_BYTES
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 1 << 20
    # temporaries: activations of 16 tokens (4,096 in the prefill, with
    # the grouped product's 32,768 rows) at these widths, never a
    # second cache
    limit = 0.05 if program == "decode_step" else 2.0
    assert mem.temp_size_in_bytes < limit * AF_CACHE_BYTES


def test_afmoe_decode_step_names_its_calls(afmoe_programs):
    calls = _mosaic_calls(afmoe_programs["decode_step"].as_text())
    assert sorted(set(calls)) == ["gqa_decode", "moe_grouped_mm"]
    assert calls.count("gqa_decode") == 4
    fills = _mosaic_calls(afmoe_programs["prefill_b4096"].as_text())
    assert fills.count("flash_fwd") == 4


# ---------------------------------------------------------------------
# Brumby behind the same engine: a recurrent state a slot, held once
# ---------------------------------------------------------------------

BR_SLOTS, BR_LAYERS, BR_HEADS, BR_KVH, BR_DIM = 16, 2, 40, 8, 128
BR_WIDE = (BR_DIM // 2 + 1) * BR_DIM                  # 65 rows of phi
BR_STATE = (BR_LAYERS, BR_SLOTS, BR_KVH, BR_DIM, BR_WIDE)
BR_NORM = (BR_LAYERS, BR_SLOTS, BR_KVH, BR_DIM, BR_DIM)
BR_LAYER_ELEMS = math.prod(BR_STATE[1:])
BR_STATE_BYTES = (math.prod(BR_STATE) + math.prod(BR_NORM)) * 4


def _brumby_cases():
    from paddle_tpu.kernels.retention import (retention_decode,
                                              retention_prefill)

    # the cell's state (BENCHMARK.json, brumby-14b): 16 slots, 8 K/V
    # heads of 128 under 40 query heads; two layers of it
    state, norm = (BR_STATE, F32), (BR_NORM, F32)
    out = {"retention_decode": (
        lambda q, k, v, g, st, nm, act: retention_decode(
            q, k, v, g, st, nm, 1, act)[0],
        [((BR_SLOTS, BR_HEADS, BR_DIM), BF16)]
        + [((BR_SLOTS, BR_KVH, BR_DIM), BF16)] * 2
        + [((BR_SLOTS, BR_KVH), F32), state, norm, ((BR_SLOTS,), bool)],
        ["retention_decode"])}
    for bucket in (6144, 8192):
        for operands in ("float32", "bfloat16"):
            out[f"retention_prefill_{bucket}_{operands}"] = (
                lambda q, k, v, g, n, st, nm, slot, operands=operands:
                retention_prefill(q, k, v, g, n, st, nm, 1, slot,
                                  operands=operands)[0],
                [((bucket, BR_HEADS, BR_DIM), BF16)]
                + [((bucket, BR_KVH, BR_DIM), BF16)] * 2
                + [((bucket, BR_KVH), F32), ((), jnp.int32), state, norm,
                   ((), jnp.int32)], ["retention_prefill"])
    return out


@pytest.mark.parametrize("name", [
    "retention_decode", "retention_prefill_6144_float32",
    "retention_prefill_8192_float32", "retention_prefill_6144_bfloat16",
    "retention_prefill_8192_bfloat16"])
def test_brumby_kernel_compiles_for_v5e(compiled_kernels, v5e, name):
    fn, args, calls = _brumby_cases()[name]
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in args]
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert sorted(set(_mosaic_calls(text))) == calls


@pytest.fixture(scope="module")
def brumby_programs(v5e):
    """(decode step, prefill at bucket 6144) of `DecodeEngine` over
    `models/brumby.py` at the published widths, two layers and the
    cell's 16 slots, compiled for the described v5e with the state
    donated, as the engine jits them."""
    import functools
    import json
    import os

    from paddle_tpu.models import brumby
    from paddle_tpu.serving import decode as D

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "brumby-14b.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=BR_LAYERS)
    bcfg = brumby.BrumbyCfg.from_hf(cfg, max_seq_len=10240)
    one = jax.sharding.SingleDeviceSharding(v5e[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    trees = brumby.BrumbyParams.from_flat(bcfg, {
        n: aval(s, F32 if kind == "gate_bias" else BF16)
        for n, (s, kind) in brumby.param_shapes(bcfg).items()}).trees
    i32 = jnp.int32
    cache = {n: aval(a.shape, a.dtype) for n, a in jax.eval_shape(
        lambda: bcfg.cache_arrays(BR_SLOTS, 10240)).items()}
    assert {n: a.shape for n, a in cache.items()} == {
        "state": BR_STATE, "norm": BR_NORM}
    state = dict(cache, pos=aval((BR_SLOTS,), i32),
                 active=aval((BR_SLOTS,), bool),
                 token=aval((BR_SLOTS,), i32), stop=aval((BR_SLOTS,), i32),
                 eos=aval((BR_SLOTS,), i32), temp=aval((BR_SLOTS,), F32),
                 key=aval((BR_SLOTS, 2), jnp.uint32))

    def compiled(impl, *args):
        return jax.jit(functools.partial(impl, cfg=bcfg),
                       donate_argnums=(0,)).trace(
            state, trees, *args).lower(
            lowering_platforms=("tpu",)).compile()

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(backend, "is_tpu_backend", lambda: True)
        return {
            "decode_step": compiled(D._decode_step_impl,
                                    aval((BR_SLOTS,), bool)),
            "prefill_b6144": compiled(
                D._prefill_impl, aval((1, 6144), i32), aval((), i32),
                aval((), i32), aval((), i32), aval((), i32),
                aval((), F32), aval((2,), jnp.uint32)),
        }


@pytest.mark.parametrize("program", ["decode_step", "prefill_b6144"])
def test_brumby_program_moves_no_layer_of_the_state(brumby_programs,
                                                    program):
    """No copy, slice, update or re-layout of the state or of a layer of
    it: only the aliased Mosaic calls touch it."""
    text = brumby_programs[program].as_text()
    dims = f"{BR_SLOTS},{BR_KVH},{BR_DIM},{BR_WIDE}]"
    large = [(op, line) for op, line in _large_results(text, BR_LAYER_ELEMS)
             # the prefill's activations over 6,144 tokens and the
             # vocabulary's matrices are larger than a layer of the
             # state: only results with the state's own dimensions are
             # judged
             if dims in line.split(" = ", 1)[1].split("(")[0]]
    assert large, "the state is not in the program at all"
    odd = [(op, line) for op, line in large if op not in PASSES_ALONG]
    assert not odd, odd
    # nothing holds one layer or one slot of it sliced out
    assert f"f32[{dims}" not in text.replace(f"f32[{BR_LAYERS},{dims}", "")


@pytest.mark.parametrize("program", ["decode_step", "prefill_b6144"])
def test_brumby_program_holds_one_copy_of_the_state(brumby_programs,
                                                    program):
    mem = brumby_programs[program].memory_analysis()
    assert mem.alias_size_in_bytes >= BR_STATE_BYTES
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 1 << 20
    # temporaries: activations of 16 tokens, or of 6,144 (the SwiGLU's
    # [6144, 34816] in float32 is 0.86 GB); never a second state
    limit = 0.05 if program == "decode_step" else 1.0
    assert mem.temp_size_in_bytes < limit * BR_STATE_BYTES
    if program == "prefill_b6144":
        assert mem.temp_size_in_bytes < 1.2e9


def test_brumby_programs_name_their_calls(brumby_programs):
    calls = _mosaic_calls(brumby_programs["decode_step"].as_text())
    assert calls == ["retention_decode"] * BR_LAYERS
    fills = _mosaic_calls(brumby_programs["prefill_b6144"].as_text())
    assert fills == ["retention_prefill"] * BR_LAYERS


# ---------------------------------------------------------------------
# nemotron_h (Mamba-2 states beside grouped-query caches): both SSD
# kernels at the cell's widths, and the decode engine's programs compiled
# whole: each state held once, no layer of the SSD state moved
# ---------------------------------------------------------------------

NM_SLOTS, NM_MAMBA, NM_HEADS, NM_DIM, NM_GROUPS, NM_N = 512, 6, 64, 64, 8, 128
NM_SSD = (NM_MAMBA, NM_SLOTS, NM_HEADS // 2 * NM_N, 2 * NM_DIM)
NM_LAYER_ELEMS = math.prod(NM_SSD[1:])


def _nemotron_cases():
    from paddle_tpu.kernels.grouped_mm import moe_grouped_mm
    from paddle_tpu.kernels.ssd import ssd_decode, ssd_prefill

    s, h, p, g, n = NM_SLOTS, NM_HEADS, NM_DIM, NM_GROUPS, NM_N
    state = (NM_SSD, F32)
    out = {"ssd_decode": (
        lambda x, dt, a, b, c, st, act: ssd_decode(
            x, dt, a, b, c, st, 3, act)[0],
        [((s, h, p), BF16), ((s, h), F32), ((h,), F32), ((s, g, n), BF16),
         ((s, g, n), BF16), state, ((s,), bool)], ["ssd_decode"])}
    for bucket in (256, 2048):
        out[f"ssd_prefill_{bucket}"] = (
            lambda x, dt, a, b, c, t, st, slot: ssd_prefill(
                x, dt, a, b, c, t, st, 2, slot, chunk=128)[0],
            [((bucket, h, p), BF16), ((bucket, h), F32), ((h,), F32),
             ((bucket, g, n), BF16), ((bucket, g, n), BF16), ((), jnp.int32),
             state, ((), jnp.int32)], ["ssd_prefill"])
    # the held experts' two products at the width stored (1,856 padded
    # to 1,920), 768 kept rows of a decode step of 512 slots
    out["moe_grouped_mm_up_1920"] = (
        lambda r, w, c: moe_grouped_mm(r, w, c),
        [((768, 2688), BF16), ((16, 2688, 1920), BF16), ((16,), jnp.int32)],
        ["moe_grouped_mm"])
    out["moe_grouped_mm_down_1920"] = (
        lambda r, w, c: moe_grouped_mm(r, w, c),
        [((768, 1920), BF16), ((16, 1920, 2688), BF16), ((16,), jnp.int32)],
        ["moe_grouped_mm"])
    return out


@pytest.mark.parametrize("name", [
    "ssd_decode", "ssd_prefill_256", "ssd_prefill_2048",
    "moe_grouped_mm_up_1920", "moe_grouped_mm_down_1920"])
def test_nemotron_kernel_compiles_for_v5e(compiled_kernels, v5e, name):
    fn, args, calls = _nemotron_cases()[name]
    one = jax.sharding.SingleDeviceSharding(v5e[0])
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in args]
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert sorted(set(_mosaic_calls(text))) == calls


@pytest.fixture(scope="module")
def nemotron_programs(v5e):
    """(decode step, prefill at bucket 2048) of `DecodeEngine` over
    `models/nemotron_h.py` at the cell's widths, 13 layers and 512
    slots x 3,072, compiled for the described v5e with the state
    donated, as the engine jits them."""
    import functools
    import json
    import os

    from paddle_tpu.models import nemotron_h
    from paddle_tpu.serving import decode as D

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        cfg = json.load(f)
    ncfg = nemotron_h.NemotronHCfg.from_hf(cfg, max_seq_len=3072)
    one = jax.sharding.SingleDeviceSharding(v5e[0])

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    flat = {n: aval(s, F32 if kind in ("dt_bias", "A_log", "D", "bias")
                    else BF16)
            for n, (s, kind) in nemotron_h.param_shapes(ncfg).items()}
    trees = jax.tree.map(
        lambda a: aval(a.shape, a.dtype),
        jax.eval_shape(lambda f: nemotron_h.NemotronHParams.from_flat(
            ncfg, f).trees, flat))
    i32 = jnp.int32
    cache = {n: aval(a.shape, a.dtype) for n, a in jax.eval_shape(
        lambda: ncfg.cache_arrays(NM_SLOTS, 3072)).items()}
    assert {n: a.shape for n, a in cache.items()} == {
        "ssd": NM_SSD, "conv": (NM_MAMBA, 3, NM_SLOTS, 6144),
        "k": (2, NM_SLOTS, 2, 128, 3072), "v": (2, NM_SLOTS, 2, 128, 3072)}
    s = NM_SLOTS
    state = dict(cache, pos=aval((s,), i32), active=aval((s,), bool),
                 token=aval((s,), i32), stop=aval((s,), i32),
                 eos=aval((s,), i32), temp=aval((s,), F32),
                 key=aval((s, 2), jnp.uint32))

    def compiled(impl, *args):
        return jax.jit(functools.partial(impl, cfg=ncfg),
                       donate_argnums=(0,)).trace(
            state, trees, *args).lower(
            lowering_platforms=("tpu",)).compile()

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(backend, "is_tpu_backend", lambda: True)
        return {
            "cache_bytes": sum(math.prod(a.shape) * a.dtype.itemsize
                               for a in cache.values()),
            "decode_step": compiled(D._decode_step_impl, aval((s,), bool)),
            "prefill_b2048": compiled(
                D._prefill_impl, aval((1, 2048), i32), aval((), i32),
                aval((), i32), aval((), i32), aval((), i32),
                aval((), F32), aval((2,), jnp.uint32)),
        }


@pytest.mark.parametrize("program", ["decode_step", "prefill_b2048"])
def test_nemotron_program_moves_no_layer_of_the_ssd_state(nemotron_programs,
                                                          program):
    """No copy, slice, update or re-layout of the SSD state or of a layer
    of it: only the aliased Mosaic calls touch it."""
    text = nemotron_programs[program].as_text()
    dims = f"{NM_SLOTS},{NM_SSD[2]},{NM_SSD[3]}]"
    large = [(op, line) for op, line in _large_results(text, NM_LAYER_ELEMS)
             if dims in line.split(" = ", 1)[1].split("(")[0]]
    assert large, "the state is not in the program at all"
    odd = [(op, line) for op, line in large if op not in PASSES_ALONG]
    assert not odd, odd
    assert f"f32[{dims}" not in text.replace(f"f32[{NM_MAMBA},{dims}", "")
    # nor is the conv window copied whole (a layout other than the
    # parameter's would copy it in and out, 113 MB each way)
    conv = f"bf16[{NM_MAMBA},3,{NM_SLOTS},6144]"
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= \S*" + re.escape(conv) + r"\S* copy", line)]
    assert not copies, copies


@pytest.mark.parametrize("program", ["decode_step", "prefill_b2048"])
def test_nemotron_program_holds_one_copy_of_each_state(nemotron_programs,
                                                       program):
    """Both states and K and V (9.8 GB) are aliased, arguments to
    results; temporaries are a step's or a prompt's activations, never a
    second state."""
    mem = nemotron_programs[program].memory_analysis()
    cache = nemotron_programs["cache_bytes"]
    assert mem.alias_size_in_bytes >= cache
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 1 << 20
    assert mem.temp_size_in_bytes < 0.05 * cache


def test_nemotron_programs_name_their_calls(nemotron_programs):
    """6 Mamba layers of `ssd_decode` or `ssd_prefill`, 2 attention
    layers of `gqa_decode` (the prefill's attention is `flash_fwd`), and
    the 5 expert layers' two products in each branch of the kept rows'
    cond."""
    from collections import Counter

    calls = Counter(_mosaic_calls(
        nemotron_programs["decode_step"].as_text()))
    assert calls == {"ssd_decode": 6, "gqa_decode": 2, "moe_grouped_mm": 20}
    fills = Counter(_mosaic_calls(
        nemotron_programs["prefill_b2048"].as_text()))
    assert fills == {"ssd_prefill": 6, "flash_fwd": 2, "moe_grouped_mm": 20}
