"""chip_smoke.py — does the system still start on the chip?

One process, no children: it drives the main path once through the
entry points a user would call, at the full width of one model the repo
supports (the `transformer_flash` geometry: GPT, hidden 1024, 16 heads,
6 layers, vocab 32768, seq 2048, bf16, about 111M parameters; weights
random from a seed), and checks what comes out by the repo's own means.

Phases, in order; each prints its result and the compile seconds it paid:

  device           platform must be "tpu", or the run ends non-zero
  train_layer      models/train.py step with AdamW, flash kernels in its HLO
  train_executor   static GPT through Executor.train_from_dataset with
                   default flags (the AMP + fusion substitute)
  serve            DecodeEngine with default slots / max_len under a
                   burst of ragged greedy requests, nothing retried
  kernels          every Pallas kernel, compiled, against its XLA
                   composition at highest precision
  four_chips       sharded layer runtime on {dp=2, tp=2}, executor on
                   dp=4 and {dp=2, mp=2}; printed as skipped below 4 chips

Any exception in any phase is fatal.  It prints set-up facts (compile
seconds, step and token counts, errors against references) and no rate
or utilization.  Nothing it reads comes from outside the repository, and
it stays off the native reader (paddle_tpu/native builds a .so that git
does not carry).

    python chip_smoke.py          # exit 0 and a last line
                                  # {"ok": true, "device": {...}} on a TPU

The phase functions take their sizes as arguments so that
tests/test_chip_smoke.py can run them tiny on the CPU; main() has no
size switch and no CPU mode.
"""

import functools
import json
import os
import sys
import time

# the package import applies the compile-cache rule (compile_cache.py)
# before jax initialises a backend
import paddle_tpu as fluid
from paddle_tpu import compile_cache

import jax
import jax.numpy as jnp
import numpy as np

GPT_FULL = dict(vocab_size=32768, hidden_size=1024, num_layers=6,
                num_heads=16, max_seq_len=2048, dtype="bfloat16")
STATIC_GPT_FULL = dict(t=512, d=768, heads=12, vocab=32768)
MOSAIC_CALL = "tpu_custom_call"


class CompileLog:
    """What jax itself reports about compilation: every trip through
    the compile path, the seconds the backend compile took (a
    persistent-cache hit costs its retrieval), the seconds of tracing
    and lowering before it (no cache saves those), and the cache hits."""

    _TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration")
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles = 0
        self.backend_s = 0.0
        self.trace_lower_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == self._BACKEND:
            self.compiles += 1
            self.backend_s += secs
        elif event in self._TRACE_LOWER:
            self.trace_lower_s += secs

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.cache_hits += 1

    def mark(self):
        return (self.compiles, self.backend_s, self.trace_lower_s,
                self.cache_hits)

    def since(self, mark):
        return {"compiles": self.compiles - mark[0],
                "backend_compile_s": round(self.backend_s - mark[1], 2),
                "trace_and_lower_s": round(self.trace_lower_s - mark[2], 2),
                "persistent_cache_hits": self.cache_hits - mark[3]}


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


def _batch(vocab, batch, seq, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    y = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    return x, y


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device():
    import importlib.metadata

    import jaxlib

    devs = jax.devices()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu,
            "compile_cache_dir": compile_cache.cache_dir()}


# ---------------------------------------------------------------------------
# train: layer runtime
# ---------------------------------------------------------------------------

def phase_train_layer(gpt, batch, seq, steps, lr=1e-4):
    from paddle_tpu import nn
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.models.train import init_train_state, make_train_step
    from paddle_tpu.optimizer.functional import AdamW

    nn.seed(0)
    model = GPT(GPTConfig(**gpt))
    opt = AdamW(lr)
    state = init_train_state(model, opt)
    params = sum(int(np.prod(v.shape)) for v in state.params.values())
    step = make_train_step(model, opt)
    x, y = (jnp.asarray(a) for a in _batch(gpt["vocab_size"], batch, seq))
    compiled = step.lower(state, x, y).compile()
    hlo = compiled.as_text()
    losses = []
    for _ in range(steps):
        state, loss = compiled(state, x, y)
        losses.append(float(jax.block_until_ready(loss)))
    _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _check(losses[-1] < losses[0],
           f"loss did not fall on a fixed batch: {losses}")
    return {"params": params, "batch": [batch, seq], "steps": steps,
            "losses": [round(v, 4) for v in losses],
            "mosaic_calls_in_hlo": hlo.count(MOSAIC_CALL)}


# ---------------------------------------------------------------------------
# train: executor
# ---------------------------------------------------------------------------

def _static_gpt_feed(static, batch, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, static["vocab"], (batch, static["t"]))
    tgt = rng.integers(0, static["vocab"], (batch, static["t"], 1))
    return {"ids": ids.astype(np.int64), "targets": tgt.astype(np.int64)}


def _run_static_gpt(static, batch, steps, wrap=None):
    """Build the static GPT, run startup, train `steps` batches through
    train_from_dataset (default flags: AMP + fusion).  Returns (model,
    scope, first loss, last loss).  `wrap` turns the main program into
    the CompiledProgram to run."""
    from paddle_tpu.models import static_zoo

    m = static_zoo.build_gpt(**static)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(m.startup, scope=scope)
    prog = wrap(m) if wrap is not None else m.main
    feed = _static_gpt_feed(static, batch)

    def train(n):
        out = exe.train_from_dataset(prog, [feed] * n, scope=scope,
                                     fetch_list=[m.loss_name],
                                     print_period=10 ** 9)
        return float(np.mean(np.asarray(out[0])))

    first = train(1)
    last = train(steps - 1) if steps > 1 else first
    return m, scope, first, last


def phase_train_executor(static, batch, steps):
    m, _, first, last = _run_static_gpt(static, batch, steps)
    _check(np.isfinite(first) and np.isfinite(last),
           f"non-finite loss: {first}, {last}")
    _check(steps == 1 or last < first,
           f"loss did not fall on a fixed batch: {first} -> {last}")
    sub = next(iter(m.main._opt_cache.values()))
    fused = sorted({op.type for op in sub.global_block().ops
                    if op.type.startswith("fused_")})
    want = {"fused_attention", "fused_bias_act", "fused_layer_norm"}
    _check(want <= set(fused),
           f"default train path is not the fused substitute: {fused}")
    return {"model": "static_zoo.build_gpt, one layer deep (the widest "
                     "call the static zoo accepts)",
            "geometry": static, "batch": batch, "steps": steps,
            "loss_first": round(first, 4), "loss_last": round(last, 4),
            "fused_ops": fused}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(gpt, prompt_lens, new_tokens, buckets, log, slots=None,
                max_len=None, exact_vs_generate=False):
    """`prompt_lens[-1]` repeats the prompt of `prompt_lens[0]` (the
    twins).  slots / max_len None = the flag defaults a user gets."""
    from paddle_tpu import nn
    from paddle_tpu.models import generate as G
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    from paddle_tpu.serving import decode as decode_mod

    _check(prompt_lens[-1] == prompt_lens[0], "last prompt is the twin")
    nn.seed(0)
    model = GPT(GPTConfig(**gpt))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, gpt["vocab_size"], n).astype(np.int32)
               for n in prompt_lens[:-1]]
    prompts.append(prompts[0].copy())

    t0 = time.perf_counter()
    eng = DecodeEngine(model, config=DecodeConfig(
        slots=slots, max_len=max_len, buckets=buckets))
    start_s = time.perf_counter() - t0
    try:
        cfg = eng.config
        # which attention the decode step traced: lowering alone, from
        # shapes, no second compile and no second cache
        step_hlo = jax.jit(functools.partial(
            decode_mod._decode_step_impl, cfg=eng.params.cfg)).lower(
            jax.eval_shape(eng._fresh_state), eng._trees,
            np.zeros(cfg.slots, bool)).as_text()
        before = log.mark()
        futs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        outs = [np.asarray(f.result(timeout=600)) for f in futs]
        traffic = log.since(before)
        summary = eng.summary()
    finally:
        eng.close()

    for p, o in zip(prompts, outs):
        _check(o.shape == (new_tokens,) and o.dtype == np.int32,
               f"prompt {p.size}: tokens {o.shape} {o.dtype}")
        _check(((o >= 0) & (o < gpt["vocab_size"])).all(),
               f"prompt {p.size}: token out of vocabulary")
    _check((outs[0] == outs[-1]).all(),
           f"twin prompts disagree: {outs[0]} vs {outs[-1]}")
    n = len(prompts)
    _check(summary["requests"] == n
           and summary["outcomes"].get("completed") == n
           and summary["pending"] == 0, f"ledger: {summary}")
    # the resilience tier must have had nothing to do: a retried or
    # degraded dispatch would absorb a failure this run exists to see
    breaker = summary["breaker"]
    idle = {"dispatch_retries": summary["dispatch_retries"],
            "watchdog_stalls": summary["watchdog_stalls"],
            "degraded_batches": summary["degraded_batches"],
            "stalled_in_flight": summary.get("stalled_in_flight", 0),
            "breaker_transitions": len(breaker["transitions"]),
            "breaker_failures": breaker["consecutive_failures"]}
    _check(breaker["state"] == "closed" and not any(idle.values()),
           f"resilience tier was busy: {breaker['state']} {idle}")
    _check(traffic["compiles"] == 0,
           f"prewarm missed a program: {traffic} under traffic")

    # the cohort decoder on the shortest prompt: token-exact in float32
    # (the CPU test); in bf16 the deep-cache kernel and the XLA path
    # may break an argmax tie differently, so on the chip it is printed
    short = int(np.argmin([p.size for p in prompts]))
    ref = np.asarray(G.generate(model, prompts[short][None],
                                max_new_tokens=new_tokens))[0]
    same = int((ref == outs[short]).sum())
    if exact_vs_generate:
        _check(same == new_tokens,
               f"engine {outs[short]} vs generate() {ref}")
    dec = summary["decode"]
    return {"slots": cfg.slots, "max_len": cfg.max_len,
            "buckets": list(cfg.buckets), "programs_prewarmed":
            eng.prewarmed, "engine_start_s": round(start_s, 2),
            "requests": n, "prompt_lens": [int(p.size) for p in prompts],
            "new_tokens_each": new_tokens,
            "tokens_returned": int(sum(o.size for o in outs)),
            "prefill_steps": dec["prefill_steps"],
            "decode_steps": dec["decode_steps"],
            "compiles_under_traffic": traffic["compiles"],
            "resilience": idle, "twins_identical": True,
            "tokens_equal_to_generate": f"{same}/{new_tokens}",
            "mosaic_calls_in_decode_step": step_hlo.count(MOSAIC_CALL)}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _err(got, want):
    """(max abs error, the same over the reference's max magnitude)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    return err, err / (float(np.max(np.abs(want))) + 1e-30)


def _highest(fn):
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return jax.jit(run)


def phase_kernels(attn, decode, decode_lengths, ln, topk_n, dtype, tol):
    """attn = (b, h, s, d); decode = (b, h, t, d); ln = (rows, d).
    `tol` bounds each error relative to the reference's max magnitude;
    the top-k histogram must be exact."""
    from paddle_tpu.kernels import attention as A
    from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                    flash_decode,
                                                    flash_decode_resident,
                                                    kv_append)
    from paddle_tpu.kernels.layer_norm import layer_norm_pallas
    from paddle_tpu.kernels.topk_threshold import (NUM_EDGES,
                                                   count_ge_histogram,
                                                   topk_threshold)

    rng = np.random.default_rng(2)
    f32 = jnp.float32

    def rand(shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    out = {}

    def record(name, got, want):
        err, rel = _err(got, want)
        out[name] = {"max_err": float(f"{err:.3g}"),
                     "rel_to_max": float(f"{rel:.3g}")}
        _check(np.isfinite(err) and rel <= tol,
               f"{name}: error {err} ({rel} of max) over {tol}")

    # flash attention fwd + bwd, causal; the reference runs one batch
    # row at a time (its [h, s, s] scores are the memory flash avoids)
    q, k, v, w = (rand(attn) for _ in range(4))
    scale = 1.0 / np.sqrt(attn[-1])

    @jax.jit
    def flash(q, k, v, w):
        o, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        return (o,) + vjp(w)

    def ref_row(args):
        q, k, v, w = (a[None].astype(f32) for a in args)
        o, vjp = jax.vjp(lambda q, k, v: A._xla_attention(
            q, k, v, None, scale, True, 0.0, False, None), q, k, v)
        return tuple(a[0] for a in (o,) + vjp(w))

    got = flash(q, k, v, w)
    want = _highest(lambda *a: jax.lax.map(ref_row, a))(q, k, v, w)
    for name, g, r in zip(("flash_fwd", "flash_bwd_dq", "flash_bwd_dk",
                           "flash_bwd_dv"), got, want):
        record(name, g, r)

    # single-query decode over a ragged cache
    b, h, t, d = decode
    q1, kc, vc = rand((b, h, 1, d)), rand(decode), rand(decode)
    lengths = jnp.asarray(decode_lengths, jnp.int32)
    _check(lengths.shape == (b,), "one length per decode row")
    got = jax.jit(flash_decode)(q1, kc, vc, lengths)
    want = _highest(lambda q, k, v, n: A.decode_attention(
        q.astype(f32), k.astype(f32), v.astype(f32), pos=n - 1,
        use_flash=False))(q1, kc, vc, lengths)
    record("flash_decode", got, want)

    # the decode engine's pair on its resident layout [L, S, H, D, T]:
    # the new column of layer 1 appended in place (exact, and nothing
    # else touched), then that layer attended where it lies
    kT, vT = (jnp.stack([rand((b, h, d, t)), jnp.swapaxes(c, -1, -2)])
              for c in (kc, vc))
    k_new, v_new = rand((b, h, d)), rand((b, h, d))
    cols = lengths - 1
    got_k, got_v = jax.jit(kv_append, donate_argnums=(0, 1))(
        kT, vT, k_new, v_new, jnp.int32(1), cols)
    rows = np.arange(b)
    for name, got, c, new in (("k", got_k, kc, k_new),
                              ("v", got_v, vc, v_new)):
        want = np.asarray(jnp.swapaxes(c, -1, -2)).copy()
        want[rows, :, :, np.asarray(cols)] = np.asarray(new)
        _check(np.array_equal(np.asarray(got[1]), want),
               f"kv_append: {name} of layer 1 is not its input with the "
               f"new columns")
    out["kv_append"] = {"exact": True}
    got = jax.jit(flash_decode_resident)(q1, got_k, got_v, jnp.int32(1),
                                         lengths)
    want = _highest(lambda q, k, v, n: A.decode_attention(
        q.astype(f32), jnp.swapaxes(k[1], -1, -2).astype(f32),
        jnp.swapaxes(v[1], -1, -2).astype(f32), pos=n - 1,
        use_flash=False))(q1, got_k, got_v, lengths)
    record("flash_decode_resident", got, want)

    # layer norm fwd + bwd
    x, dy = rand(ln), rand(ln)
    gamma = jnp.asarray(1.0 + 0.1 * rng.standard_normal(ln[1]), dtype)
    beta = jnp.asarray(0.1 * rng.standard_normal(ln[1]), dtype)

    def ln_ref(x, g, b):
        x = x.astype(f32)
        xc = x - x.mean(-1, keepdims=True)
        return xc * jax.lax.rsqrt((xc * xc).mean(-1, keepdims=True)
                                  + 1e-5) * g.astype(f32) + b.astype(f32)

    def with_grads(fn):
        def run(x, g, b, dy):
            y, vjp = jax.vjp(fn, x, g, b)
            return (y,) + vjp(dy.astype(y.dtype))
        return run

    got = jax.jit(with_grads(layer_norm_pallas))(x, gamma, beta, dy)
    want = _highest(with_grads(ln_ref))(x, gamma, beta, dy)
    for name, g, r in zip(("layer_norm_fwd", "layer_norm_bwd_dx",
                           "layer_norm_bwd_dgamma", "layer_norm_bwd_dbeta"),
                          got, want):
        record(name, g, r)

    # DGC top-k threshold: the histogram against a broadcast compare,
    # a chunk at a time (whole, it is the 64 MB intermediate the kernel
    # was rewritten to avoid)
    grad = jnp.asarray(rng.standard_normal(topk_n), f32)
    k_keep = max(1, topk_n // 1000)
    flat = jnp.abs(grad)
    edges = jnp.linspace(0.0, 1.0, NUM_EDGES, dtype=f32) * jnp.max(flat)
    got = count_ge_histogram(flat, edges)
    pad = (-topk_n) % 4096
    chunks = jnp.pad(flat, (0, pad), constant_values=-1.0).reshape(-1, 4096)
    want = jax.jit(lambda c, e: jax.lax.map(
        lambda row: (row[:, None] >= e[None, :]).sum(0).astype(jnp.int32),
        c).sum(0))(chunks, edges)
    diff = int(np.max(np.abs(np.asarray(got, np.int64)
                             - np.asarray(want, np.int64))))
    thr = float(topk_threshold(grad, k_keep))
    kept = int((np.abs(np.asarray(grad)) >= thr).sum())
    out["topk_threshold"] = {"histogram_max_count_diff": diff, "k": k_keep,
                             "kept": kept}
    _check(diff == 0, f"topk histogram off by {diff}")
    _check(kept >= k_keep, f"threshold keeps {kept} < k = {k_keep}")
    return out


# ---------------------------------------------------------------------------
# Kimi-K2 behind the decode engine
# ---------------------------------------------------------------------------

def phase_k2(hf, slots, max_len, buckets, prompt_lens, new_tokens,
             decode_lengths, tol, gap_tol):
    """`hf`: the model's sizes under its config.json keys.  (1)
    `mla_decode` at the model's widths (the column it writes and what
    it attends to) against its XLA mathematics, at ragged lengths, and
    the routed experts' grouped product (`moe_grouped_mm`) against each
    group's rows times its expert, in numpy; (2) the engine's first `new_tokens` tokens of each
    prompt against the unbatched forward pass (`kimi_k2.full_logits`
    over prompt and served tokens, the published form of attention, no
    cache): the widest gap by which a served token's logit lies under
    that pass's best."""
    from paddle_tpu.distributed.moe import grouped_product
    from paddle_tpu.kernels.attention import resident_mla_attention
    from paddle_tpu.models import kimi_k2
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    cfg = kimi_k2.K2Cfg.from_hf(hf, max_seq_len=max_len)
    dtype = jnp.dtype(cfg.dtype)
    rng = np.random.default_rng(3)
    s, width = len(decode_lengths), cfg.latent_width

    def rand(shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    args = (rand((s, cfg.num_heads, cfg.kv_lora_rank)),
            rand((s, cfg.num_heads, cfg.qk_rope_head_dim)), rand((s, width)),
            rand((2, s, width, max_len)))
    pos = jnp.asarray(decode_lengths, jnp.int32) - 1
    outs = {}
    for use_kernel in (True, False):
        fn = functools.partial(resident_mla_attention, layer=1, pos=pos,
                               scale=cfg.softmax_scale,
                               use_kernel=use_kernel)
        outs[use_kernel] = (_highest(fn) if not use_kernel
                            else jax.jit(fn))(*args)
    _check(bool((outs[True][1] == outs[False][1]).all()),
           "mla_decode's written column differs from the XLA write")
    err, rel = _err(outs[True][0], outs[False][0])
    _check(rel <= tol, f"mla_decode: error {err:.3g} is {rel:.3g} of the "
                       f"reference's max, over {tol}")

    # a decode step's rows over the held experts: uneven groups, one
    # empty, one over a row-tile boundary
    counts = np.resize([5, 0, 130, 1, 9, 3], cfg.experts_held)
    rows = rand((256 * cfg.num_experts_per_tok, cfg.hidden_size))
    experts = rand((cfg.experts_held, cfg.hidden_size,
                    2 * cfg.moe_intermediate_size)) * 0.02
    got = jax.jit(grouped_product)(rows, experts, counts)
    ends = np.cumsum(counts)
    want = np.concatenate([
        np.asarray(rows[e - c:e], np.float32) @ np.asarray(w, np.float32)
        for w, c, e in zip(experts, counts, ends)])
    got = got[:ends[-1]]
    gerr, grel = _err(got, want)
    _check(grel <= tol, f"moe_grouped_mm: error {gerr:.3g} is {grel:.3g} "
                        f"of the reference's max, over {tol}")

    params = kimi_k2.K2Params.from_flat(
        cfg, kimi_k2.init_params(cfg, jax.random.PRNGKey(29)))
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    eng = DecodeEngine(params, config=DecodeConfig(
        slots=slots, max_len=max_len, buckets=buckets))
    try:
        futs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        served = [np.asarray(f.result(timeout=900)) for f in futs]
        summary = eng.summary()
    finally:
        eng.close()
    forward = jax.jit(functools.partial(kimi_k2.full_logits, cfg))
    widest, same = 0.0, 0
    for p, out in zip(prompts, served):
        _check(out.shape == (new_tokens,), f"prompt {p.size}: {out.shape}")
        logits = np.asarray(forward(
            params.trees, np.concatenate([p, out])), np.float32)
        rows = logits[p.size - 1:p.size - 1 + new_tokens]
        widest = max(widest, float((rows.max(axis=1) - rows[
            np.arange(new_tokens), out]).max()))
        same += int((rows.argmax(axis=1) == out).sum())
    _check(widest <= gap_tol, f"engine tokens lie up to {widest:.3g} under "
                              f"the forward pass's best, over {gap_tol}")
    dec = summary["decode"]
    _check(dec["experts"]["tokens_total"] > 0, f"no expert counts: {dec}")
    return {"mla_decode": {"max_abs_err": err, "rel_to_max": rel,
                           "written_column": "exact"},
            "moe_grouped_mm": {"max_abs_err": gerr, "rel_to_max": grel},
            "requests": len(prompts),
            "widest_logit_gap": widest,
            "tokens_equal_to_forward": f"{same}/{len(prompts) * new_tokens}",
            "cache": dec["cache"], "experts": dec["experts"]}


# ---------------------------------------------------------------------------
# AFMoE (Trinity) behind the decode engine
# ---------------------------------------------------------------------------

def phase_afmoe(hf, slots, max_len, buckets, prompt_lens, new_tokens,
                decode_lengths, prefill_seq, tol, gap_tol):
    """`hf`: the model's sizes under its config.json keys.  (1)
    `gqa_decode` at the model's widths (the column it writes and what it
    attends to) against the XLA mathematics at ragged lengths, over a
    full cache and over a ring that has wrapped, both caches donated:
    the caches equal everywhere, milliseconds a call and the tiles it
    walked of the rectangle's; and `flash_fwd` with the window and
    grouped heads against the XLA mask at `prefill_seq`; (2) the
    engine's first
    `new_tokens` tokens of each prompt (some shorter than the window,
    some longer, some that cross it while decoding) against the
    unbatched forward pass (`afmoe.full_logits` over prompt and served
    tokens: no cache, no ring): the widest gap by which a served
    token's logit lies under that pass's best."""
    from paddle_tpu.kernels import attention
    from paddle_tpu.kernels.flash_attention import gqa_tiling, tiles_walked
    from paddle_tpu.models import afmoe
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    cfg = afmoe.AfmoeCfg.from_hf(hf, max_seq_len=max_len)
    dtype = jnp.dtype(cfg.dtype)
    rng = np.random.default_rng(3)
    s, kvh, d = len(decode_lengths), cfg.num_kv_heads, cfg.head_dim

    def rand(shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    out = {}
    pos = jnp.asarray(decode_lengths, jnp.int32) - 1
    for name, depth, ring in (("full", max_len, False),
                              ("ring", cfg.sliding_window, True)):
        args = (rand((s, cfg.num_heads, 1, d)), rand((s, kvh, 1, d)),
                rand((s, kvh, 1, d)))
        caches = (rand((2, s, kvh, d, depth)), rand((2, s, kvh, d, depth)))
        fn = functools.partial(attention.resident_decode_attention,
                               layer=1, pos=pos, ring=ring)
        # the dispatch reads the switch while the call is traced
        os.environ["PADDLE_TPU_FORCE_FLASH_DECODE"] = "0"
        try:
            want = _highest(fn)(*args, *caches)
            os.environ["PADDLE_TPU_FORCE_FLASH_DECODE"] = "1"
            got = jax.jit(fn, donate_argnums=(3, 4))(
                *args, *(c + 0 for c in caches))
            ms = _ms_a_call(fn, args, *caches)
        finally:
            del os.environ["PADDLE_TPU_FORCE_FLASH_DECODE"]
        _check(all(bool((a == b).all()) for a, b in zip(got[1:], want[1:])),
               f"gqa_decode ({name}): a cache is not its input with the "
               f"new columns")
        err, rel = _err(got[0], want[0])
        _check(rel <= tol, f"gqa_decode ({name}): error {err:.3g} is "
                           f"{rel:.3g} of the reference's max, over {tol}")
        tile = gqa_tiling(kvh, d, depth).tile
        out[f"gqa_decode_{name}"] = {
            "max_abs_err": err, "rel_to_max": rel, "written_column": "exact",
            "ms_a_call": ms, "tiles_walked": tiles_walked(
                [min(n, depth) for n in decode_lengths], tile),
            "tiles_of_the_grid": s * (depth // tile)}

    q = rand((1, cfg.num_heads, prefill_seq, d))
    k, v = rand((1, kvh, prefill_seq, d)), rand((1, kvh, prefill_seq, d))
    for name, window in (("window", cfg.sliding_window), ("full", None)):
        fn = functools.partial(
            attention.dot_product_attention, is_causal=True, training=False,
            window=window)
        err, rel = _err(
            jax.jit(functools.partial(fn, use_flash=True))(q, k, v),
            _highest(functools.partial(fn, use_flash=False))(q, k, v))
        _check(rel <= tol, f"flash_fwd ({name}, grouped): error {err:.3g} "
                           f"is {rel:.3g} of the reference's max, over {tol}")
        out[f"flash_fwd_{name}"] = {"max_abs_err": err, "rel_to_max": rel}

    params = afmoe.AfmoeParams.from_flat(
        cfg, afmoe.init_params(cfg, jax.random.PRNGKey(34), bias_std=0.002))
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    eng = DecodeEngine(params, config=DecodeConfig(
        slots=slots, max_len=max_len, buckets=buckets))
    try:
        futs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        served = [np.asarray(f.result(timeout=900)) for f in futs]
        summary = eng.summary()
    finally:
        eng.close()
    forward = jax.jit(functools.partial(afmoe.full_logits, cfg))
    widest, same = 0.0, 0
    for p, toks in zip(prompts, served):
        _check(toks.shape == (new_tokens,), f"prompt {p.size}: {toks.shape}")
        logits = np.asarray(forward(
            params.trees, np.concatenate([p, toks])), np.float32)
        rows = logits[p.size - 1:p.size - 1 + new_tokens]
        widest = max(widest, float((rows.max(axis=1) - rows[
            np.arange(new_tokens), toks]).max()))
        same += int((rows.argmax(axis=1) == toks).sum())
    _check(widest <= gap_tol, f"engine tokens lie up to {widest:.3g} under "
                              f"the forward pass's best, over {gap_tol}")
    dec = summary["decode"]
    _check(dec["experts"]["tokens_total"] > 0, f"no expert counts: {dec}")
    _check([a["depth"] for a in dec["cache"]["arrays"]]
           == [max_len] * 2 + [min(cfg.sliding_window, max_len)] * 2,
           f"the caches' depths: {dec['cache']}")
    out.update({"requests": len(prompts), "widest_logit_gap": widest,
                "tokens_equal_to_forward":
                    f"{same}/{len(prompts) * new_tokens}",
                "cache": dec["cache"], "experts": dec["experts"]})
    return out


def _ms_a_call(fn, args, state, norm, calls=5):
    """Milliseconds a call of `fn(*args, state, norm)` -> (o, state,
    norm), the two being arrays the call writes in place (a recurrent
    state and its divisor's; a K and a V cache), jitted with both
    donated as the engine donates them (a call that keeps its arguments
    pays a copy of both first), after one call to compile."""
    fn = jax.jit(fn, donate_argnums=(len(args), len(args) + 1))
    _, state, norm = fn(*args, state + 0, norm + 0)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(calls):
        _, state, norm = fn(*args, state, norm)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / calls * 1e3


def phase_brumby(hf, slots, max_len, buckets, prompt_lens, new_tokens,
                 kernel_slots, kernel_bucket, tol, state_tol, gap_tol,
                 tile_rows_tried=(None,)):
    """`hf`: the model's sizes under its config.json keys.  (1) Both
    retention kernels alone at the model's widths against their XLA
    mathematics: a decode step over `kernel_slots` slots of which every
    third is not active (those slots' states bit for bit as they were),
    a prefill of `kernel_bucket` positions that ends inside the bucket's
    padding, as served (its products against the state on bfloat16
    operands), its written state and divisor's state within `state_tol`
    of the recurrent form's in float32 (what holds the kernel to the
    precision benchmarks/configs/brumby-14b.json states: with float32
    operands, read beside, the same gap is 60 times smaller, operands
    any coarser pass `state_tol`); milliseconds and bytes a call, the
    decode step for each of `tile_rows_tried`; (2) the engine's first
    `new_tokens` tokens of each prompt (several chunks long, ending inside a bucket's
    padding) against the unbatched forward pass in the attention form
    (`brumby.full_logits`: no state, no chunk): the widest gap by which
    a served token's logit lies under that pass's best."""
    from paddle_tpu.kernels import retention
    from paddle_tpu.models import brumby
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    cfg = brumby.BrumbyCfg.from_hf(hf, max_seq_len=max_len)
    dtype = jnp.dtype(cfg.dtype)
    rng = np.random.default_rng(36)
    h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rows = retention.phi_rows(d)

    def rand(shape, scale=1.0, dt=dtype):
        return jnp.asarray(rng.standard_normal(shape) * scale, dt)

    def gates(shape):
        # half-lives of 8 to 4,096 positions
        return jnp.asarray(-np.log(2) / 8 ** rng.uniform(1, 4, shape),
                           jnp.float32)

    out = {}
    s = kernel_slots
    active = jnp.asarray(np.arange(s) % 3 != 1)
    args = (rand((s, h, d)), rand((s, kvh, d)), rand((s, kvh, d)),
            gates((s, kvh)))
    state = rand((2, s, kvh, d, rows * d), 4.0, jnp.float32)
    # a divisor's state is a sum of k k^T under decay: positive
    # semi-definite
    half = rand((2, s, kvh, d, d), 1.0, jnp.float32)
    norm = jnp.einsum("lsjab,lsjcb->lsjac", half, half)
    want = _highest(functools.partial(
        retention.retention_decode, layer=1, active=active,
        use_kernel=False))(*args, state, norm)
    step_bytes = 2 * int(active.sum()) * kvh * (rows + 1) * d * d * 4
    for tile_rows in tile_rows_tried:
        fn = jax.jit(functools.partial(
            retention.retention_decode, layer=1, active=active,
            use_kernel=True, tile_rows=tile_rows))
        got = fn(*args, state, norm)
        idle = ~np.asarray(active)
        _check(all(bool((a[:, idle] == b[:, idle]).all())
                   for a, b in zip(got[1:], (state, norm))),
               "retention_decode touched a slot that is not active")
        _check(bool((got[1][0] == state[0]).all()),
               "retention_decode touched another layer")
        errs = [_err(a, b) for a, b in zip(got, want)]
        _check(all(rel <= tol for _, rel in errs),
               f"retention_decode ({tile_rows}): {errs}, over {tol}")
        ms = _ms_a_call(fn, args, state, norm)
        out[f"retention_decode_{tile_rows or 'tiled'}"] = {
            "rel_to_max": [rel for _, rel in errs], "ms": ms,
            "state_bytes": step_bytes, "gb_per_s": step_bytes / ms / 1e6}

    t, true_len = kernel_bucket, kernel_bucket - kernel_bucket // 13 - 1
    pargs = (rand((t, h, d)), rand((t, kvh, d)), rand((t, kvh, d)),
             gates((t, kvh)))
    _, st, z = _highest(retention.recurrent_form)(
        *(a[:true_len] for a in pargs))

    def prefill(**kw):
        return lambda q, k, v, log_g, st, nm: retention.retention_prefill(
            q, k, v, log_g, true_len, st, nm, 1, 2, **kw)

    want_o = _highest(prefill(use_kernel=False))(*pargs, state, norm)[0]
    fn = jax.jit(prefill(use_kernel=True))
    o, got_s, got_z = fn(*pargs, state, norm)
    errs = [_err(o[:true_len], want_o[:true_len]),
            _err(got_s[1, 2], st), _err(got_z[1, 2], z)]
    _check(errs[0][1] <= tol, f"retention_prefill: {errs}, over {tol}")
    _check(all(rel <= state_tol for _, rel in errs[1:]),
           f"retention_prefill's state: {errs[1:]}, over {state_tol}")
    _check(bool((got_s[1, 0] == state[1, 0]).all())
           and bool((got_s[0] == state[0]).all()),
           "retention_prefill touched another slot or layer")
    exact = jax.jit(prefill(use_kernel=True, operands="float32"))(
        *pargs, state, norm)
    out["retention_prefill"] = {
        "rel_to_max": [rel for _, rel in errs],
        "rel_to_max_float32_operands": [
            _err(exact[0][:true_len], want_o[:true_len])[1],
            _err(exact[1][1, 2], st)[1], _err(exact[2][1, 2], z)[1]],
        "ms": _ms_a_call(fn, pargs, state, norm, calls=3), "bucket": t,
        "chunks": t // retention.retention_tiling(d, t).chunk}
    del state, norm, want, got, got_s, got_z, exact

    params = brumby.BrumbyParams.from_flat(
        cfg, brumby.init_params(cfg, jax.random.PRNGKey(36)))
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    forward = jax.jit(functools.partial(brumby.full_logits, cfg))
    eng = DecodeEngine(params, config=DecodeConfig(
        slots=slots, max_len=max_len, buckets=buckets))
    try:
        futs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        served = [np.asarray(f.result(timeout=900)) for f in futs]
        summary = eng.summary()
    finally:
        eng.close()
    widest, same = 0.0, 0
    for p, toks in zip(prompts, served):
        _check(toks.shape == (new_tokens,), f"prompt {p.size}: {toks.shape}")
        ids = np.zeros(max_len, np.int32)          # one shape: one compile
        ids[:p.size] = p
        ids[p.size:p.size + new_tokens] = toks
        logits = np.asarray(forward(params.trees, ids), np.float32)
        picked = logits[p.size - 1:p.size - 1 + new_tokens]
        widest = max(widest, float((picked.max(axis=1) - picked[
            np.arange(new_tokens), toks]).max()))
        same += int((picked.argmax(axis=1) == toks).sum())
    _check(widest <= gap_tol, f"engine tokens lie up to {widest:.3g} under "
                              f"the forward pass's best, over {gap_tol}")
    cache = summary["decode"]["cache"]
    _check([a["kind"] for a in cache["arrays"]] == ["state", "state"]
           and not any("depth" in a for a in cache["arrays"]),
           f"the cache's arrays: {cache}")
    _check(cache["state_bytes"] > 0 and cache["chunks"] > 0,
           f"no state traffic counted: {cache}")
    out.update({"requests": len(prompts), "widest_logit_gap": widest,
                "tokens_equal_to_forward":
                    f"{same}/{len(prompts) * new_tokens}", "cache": cache})
    return out


def phase_nemotron(hf, slots, max_len, buckets, prompt_lens, new_tokens,
                   kernel_slots, kernel_bucket, tol, state_tol, gap_tol):
    """`hf`: the model's sizes under its config.json keys.  (1) Both SSD
    kernels alone at the model's widths: a decode step over
    `kernel_slots` slots of which every third is not active (those
    slots' states bit for bit as they were) against the XLA mathematics;
    a prefill of `kernel_bucket` positions that ends inside the bucket's
    padding, its written state within `state_tol` of the recurrence's a
    position at a time in float32 (`ssd.ssd_recurrent`); milliseconds
    and bytes a call, the state donated as the engine donates it; (2)
    the engine's first `new_tokens` tokens of each prompt (several chunks
    long, ending inside a bucket's padding, one of 2 positions) against
    the unbatched forward pass (`nemotron_h.full_logits`: the chunked
    form in XLA, no state, no cache): the widest gap by which a served
    token's logit lies under that pass's best."""
    from paddle_tpu.kernels import ssd
    from paddle_tpu.models import nemotron_h
    from paddle_tpu.serving import DecodeConfig, DecodeEngine

    cfg = nemotron_h.NemotronHCfg.from_hf(hf, max_seq_len=max_len)
    dtype = jnp.dtype(cfg.dtype)
    rng = np.random.default_rng(41)
    h, p, g, n = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.n_groups,
                  cfg.ssm_state_size)
    pack = cfg.tiling.pack
    a = -jnp.asarray(rng.uniform(1, 16, h), jnp.float32)

    def rand(shape, scale=1.0, dt=dtype):
        return jnp.asarray(rng.standard_normal(shape) * scale, dt)

    def steps(shape):
        # dt of 0.001 to 0.1: half-lives of 0.04 to 700 positions
        return jnp.asarray(10 ** rng.uniform(-3, -1, shape), jnp.float32)

    def ms_in_place(fn, args, state, calls=5):
        fn = jax.jit(fn, donate_argnums=(len(args),))
        _, state = fn(*args, state + 0)
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for _ in range(calls):
            _, state = fn(*args, state)
        jax.block_until_ready(state)
        return (time.perf_counter() - t0) / calls * 1e3

    out = {}
    s = kernel_slots
    active = jnp.asarray(np.arange(s) % 3 != 1)
    args = (rand((s, h, p)), steps((s, h)), a, rand((s, g, n)),
            rand((s, g, n)))
    state = rand((2, s, h // pack * n, pack * p), 4.0, jnp.float32)

    def decode(**kw):
        return lambda x, dt, a, b, c, st: ssd.ssd_decode(
            x, dt, a, b, c, st, 1, active, **kw)

    want = _highest(decode(use_kernel=False))(*args, state)
    fn = jax.jit(decode(use_kernel=True))
    got = fn(*args, state)
    idle = ~np.asarray(active)
    _check(bool((got[1][:, idle] == state[:, idle]).all())
           and bool((got[1][0] == state[0]).all()),
           "ssd_decode touched a slot that is not active, or another layer")
    errs = [_err(x, y) for x, y in zip(got, want)]
    _check(all(rel <= tol for _, rel in errs),
           f"ssd_decode: {errs}, over {tol}")
    ms = ms_in_place(decode(use_kernel=True), args, state)
    step_bytes = 2 * int(active.sum()) * h * p * n * 4
    out["ssd_decode"] = {"rel_to_max": [rel for _, rel in errs], "ms": ms,
                         "state_bytes": step_bytes,
                         "gb_per_s": step_bytes / ms / 1e6}

    t, true_len = kernel_bucket, kernel_bucket - kernel_bucket // 13 - 1
    pargs = (rand((t, h, p)), steps((t, h)), a, rand((t, g, n)),
             rand((t, g, n)))
    want_y, want_s = _highest(ssd.ssd_recurrent)(
        *(z[:true_len] if z.ndim > 1 else z for z in pargs))

    def prefill(**kw):
        return lambda x, dt, a, b, c, st: ssd.ssd_prefill(
            x, dt, a, b, c, true_len, st, 1, 2, chunk=cfg.chunk_size, **kw)

    fn = jax.jit(prefill(use_kernel=True))
    y, got_s = fn(*pargs, state)
    errs = [_err(y[:true_len], want_y),
            _err(ssd.unpack_state(got_s[1, 2], h, pack), want_s)]
    _check(errs[0][1] <= tol, f"ssd_prefill: {errs}, over {tol}")
    _check(errs[1][1] <= state_tol,
           f"ssd_prefill's state: {errs[1]}, over {state_tol}")
    _check(bool((got_s[1, 0] == state[1, 0]).all())
           and bool((got_s[0] == state[0]).all()),
           "ssd_prefill touched another slot or layer")
    out["ssd_prefill"] = {
        "rel_to_max": [rel for _, rel in errs],
        "ms": ms_in_place(prefill(use_kernel=True), pargs, state, calls=3),
        "bucket": t, "chunks": t // cfg.chunk_size}
    del state, want, got, got_s

    params = nemotron_h.NemotronHParams.from_flat(
        cfg, nemotron_h.init_params(cfg, jax.random.PRNGKey(41)))
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    forward = jax.jit(functools.partial(nemotron_h.full_logits, cfg))
    eng = DecodeEngine(params, config=DecodeConfig(
        slots=slots, max_len=max_len, buckets=buckets))
    try:
        futs = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
        served = [np.asarray(f.result(timeout=900)) for f in futs]
        summary = eng.summary()
    finally:
        eng.close()
    widest, same = 0.0, 0
    for p, toks in zip(prompts, served):
        _check(toks.shape == (new_tokens,), f"prompt {p.size}: {toks.shape}")
        ids = np.zeros(max_len, np.int32)          # one shape: one compile
        ids[:p.size] = p
        ids[p.size:p.size + new_tokens] = toks
        logits = np.asarray(forward(params.trees, ids), np.float32)
        picked = logits[p.size - 1:p.size - 1 + new_tokens]
        widest = max(widest, float((picked.max(axis=1) - picked[
            np.arange(new_tokens), toks]).max()))
        same += int((picked.argmax(axis=1) == toks).sum())
    _check(widest <= gap_tol, f"engine tokens lie up to {widest:.3g} under "
                              f"the forward pass's best, over {gap_tol}")
    dec = summary["decode"]
    cache = dec["cache"]
    _check([(a["name"], a["kind"]) for a in cache["arrays"]]
           == [("ssd", "state"), ("conv", "state"), ("k", "depth"),
               ("v", "depth")], f"the cache's arrays: {cache}")
    _check(cache["state_bytes"] > 0 and cache["chunks"] > 0,
           f"no state traffic counted: {cache}")
    _check(dec["experts"]["tokens_total"] > 0, f"no expert counts: {dec}")
    out.update({"requests": len(prompts), "widest_logit_gap": widest,
                "tokens_equal_to_forward":
                    f"{same}/{len(prompts) * new_tokens}", "cache": cache,
                "experts": dec["experts"]})
    return out


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _on_distinct_devices(arr, n):
    return len({s.device for s in arr.addressable_shards}) == n


def phase_four_chips(gpt, batch, seq, steps, one_chip_loss, static,
                     static_batch, one_chip_static_loss, rtol, lr=1e-4):
    """The two train runtimes again, sharded over four devices, on the
    batches of the one-chip phases: first-step losses must agree."""
    from paddle_tpu import nn
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.sharded import (gpt_rules,
                                                make_sharded_train_step,
                                                shard_batch)
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.optimizer.functional import AdamW

    out = {}
    # layer runtime, {dp=2, tp=2}
    nn.seed(0)
    model = GPT(GPTConfig(**gpt))
    mesh = build_mesh(dp=2, tp=2)
    step, state = make_sharded_train_step(model, AdamW(lr), mesh,
                                          rules=gpt_rules())
    x, y = shard_batch(mesh, *_batch(gpt["vocab_size"], batch, seq))
    losses = []
    for _ in range(steps):
        state, loss = step(state, x, y)
        losses.append(float(jax.block_until_ready(loss)))
    leaf = state.params["blocks.0.attn.q_proj.weight"]
    shard_bytes = leaf.addressable_shards[0].data.nbytes
    _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _check(_on_distinct_devices(leaf, 4)
           and _on_distinct_devices(state.params["norm_f.weight"], 4),
           "parameter shards do not sit on four distinct devices")
    _check(shard_bytes * 2 == leaf.nbytes,
           f"tensor-parallel leaf holds {shard_bytes} of {leaf.nbytes} "
           f"bytes a device, not half")
    _check(abs(losses[0] - one_chip_loss) <= rtol * abs(one_chip_loss),
           f"first-step loss {losses[0]} vs one chip {one_chip_loss}")
    out["layer_dp2_tp2"] = {
        "losses": [round(v, 4) for v in losses],
        "one_chip_first_loss": round(one_chip_loss, 4),
        "tp_leaf_bytes": [shard_bytes, leaf.nbytes],
        "devices": sorted(d.id for d in mesh.devices.flat)}

    # executor: dp=4, then the GSPMD tier on {dp=2, mp=2}
    def dp4(m):
        return fluid.CompiledProgram(m.main).with_data_parallel(
            loss_name=m.loss_name, places=4)

    def dp2_mp2(m):
        return fluid.CompiledProgram(m.main).with_sharding_rules(
            m.partition_rules(), execute=True)

    for name, wrap, halved in (("executor_dp4", dp4, False),
                               ("executor_dp2_mp2", dp2_mp2, True)):
        _, scope, first, _ = _run_static_gpt(static, static_batch, 1,
                                             wrap=wrap)
        w = scope.vars["fc_0.w_0"]
        _check(_on_distinct_devices(w, 4),
               f"{name}: fc_0.w_0 is not on four distinct devices")
        shard_bytes = w.addressable_shards[0].data.nbytes
        _check(shard_bytes * (2 if halved else 1) == w.nbytes,
               f"{name}: a device holds {shard_bytes} of fc_0.w_0's "
               f"{w.nbytes} bytes")
        _check(abs(first - one_chip_static_loss)
               <= rtol * abs(one_chip_static_loss),
               f"{name}: first-step loss {first} vs one chip "
               f"{one_chip_static_loss}")
        out[name] = {"loss_first": round(first, 4),
                     "one_chip_first_loss": round(one_chip_static_loss, 4),
                     "fc_0.w_0_bytes": [shard_bytes, w.nbytes]}
    return out


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main():
    device = phase_device()
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU: jax.devices() found platform "
              f"{device['platform']!r} ({device['kind']}, "
              f"{device['count']} device(s))", file=sys.stderr)
        return 1
    print(f"[device] {json.dumps(device)}", flush=True)
    log = CompileLog()

    def run(name, fn, *args, **kw):
        mark, t0 = log.mark(), time.perf_counter()
        result = fn(*args, **kw)
        print(f"[{name}] {json.dumps(result)}", flush=True)
        print(f"[{name}] {json.dumps(log.since(mark))} "
              f"phase_s={time.perf_counter() - t0:.1f}", flush=True)
        return result

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "benchmarks", "configs",
                           "brumby-14b.json")) as f:
        brumby = dict(json.load(f), num_hidden_layers=2)

    def run_brumby(**kw):
        # prompts of several chunks of 256 that end inside their bucket's
        # padding; the kernels alone at the cell's 16 slots
        return run("brumby", phase_brumby, brumby, slots=4, max_len=2048,
                   buckets=(1024, 1536),
                   prompt_lens=[600, 1000, 1300, 1025], new_tokens=48,
                   kernel_slots=16, kernel_bucket=2048,
                   # the state a prefill writes, against the recurrent
                   # form's: bfloat16 operands read 3.5e-3 of the
                   # state's largest entry, float32 ones 5.5e-5 (my chip
                   # runs, PR 36)
                   tol=2e-2, state_tol=8e-3, gap_tol=0.25, **kw)

    with open(os.path.join(here, "benchmarks", "configs",
                           "trinity-mini.json")) as f:
        trinity = json.load(f)

    def run_afmoe():
        return run(
            "afmoe", phase_afmoe, trinity, slots=8, max_len=4096,
            buckets=(512, 2048, 4096),
            prompt_lens=[40, 2040, 2049, 3000, 700], new_tokens=32,
            decode_lengths=[1, 40, 1024, 2048, 777, 128, 2049, 4096],
            prefill_seq=4096, tol=5e-2,
            gap_tol=0.45)  # read 0.3125 (145 of 160 tokens equal), PR 34

    with open(os.path.join(here, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        nemotron = json.load(f)

    def run_nemotron():
        # the cell's 13 layers on 4 slots: prompts of several chunks of
        # 128 that end inside their bucket's padding, and one of 2 (a conv
        # window mostly zeros); the kernels alone at 64 slots
        return run("nemotron", phase_nemotron, nemotron, slots=4,
                   max_len=2048, buckets=(256, 1024),
                   prompt_lens=[200, 700, 2, 1000, 129], new_tokens=32,
                   kernel_slots=64, kernel_bucket=2048,
                   # float32 on both sides of each comparison: a product
                   # on bfloat16 operands would read about 4e-3
                   tol=1e-3, state_tol=1e-3, gap_tol=0.5)

    # one phase alone: `chip_smoke.py brumby [rows a decode tile ...]`,
    # `chip_smoke.py afmoe`, `chip_smoke.py nemotron`
    alone = {"brumby": lambda: run_brumby(tile_rows_tried=(None,) + tuple(
                 int(a) for a in sys.argv[2:])),
             "afmoe": run_afmoe,
             "nemotron": run_nemotron}.get(sys.argv[1]) if sys.argv[1:] \
        else None
    if alone is not None:
        alone()
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0
    layer = run("train_layer", phase_train_layer, GPT_FULL, batch=8,
                seq=2048, steps=5)
    _check(layer["mosaic_calls_in_hlo"] > 0,
           "train step HLO has no Mosaic call: the flash kernels did not "
           "run")
    static_batch = 16
    executor = run("train_executor", phase_train_executor, STATIC_GPT_FULL,
                   batch=static_batch, steps=5)
    # twelve requests, 20..1500 tokens, the last the twin of the first
    lens = [int(n) for n in np.linspace(20, 1500, 11)] + [20]
    serve = run("serve", phase_serve, GPT_FULL, lens, 32, (512, 2048), log)
    _check(serve["mosaic_calls_in_decode_step"] > 0,
           "decode step has no Mosaic call: flash_decode did not run")
    run("kernels", phase_kernels, attn=(8, 16, 2048, 64),
        decode=(8, 16, 2048, 64),
        decode_lengths=[1, 40, 1024, 2048, 777, 128, 129, 2047],
        ln=(16384, 1024), topk_n=1024 * 4096, dtype=jnp.bfloat16,
        tol=5e-2)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmarks", "configs", "kimi-k2.6.json")) as f:
        k2 = json.load(f)
    run("k2", phase_k2, k2, slots=8, max_len=2048, buckets=(256, 1024),
        prompt_lens=[40, 200, 900, 513, 700, 40, 255, 1000, 333, 90],
        new_tokens=32, decode_lengths=[1, 40, 1024, 2048, 777, 128, 129,
                                       2047], tol=5e-2, gap_tol=0.25)
    run_afmoe()
    run_brumby()
    run_nemotron()
    if device["count"] >= 4:
        run("four_chips", phase_four_chips, GPT_FULL, 8, 2048, 3,
            layer["losses"][0], STATIC_GPT_FULL, static_batch,
            executor["loss_first"], rtol=2e-2)
    else:
        print(f"[four_chips] skipped, {device['count']} device",
              flush=True)
    _check("paddle_tpu.native" not in sys.modules,
           "the smoke touched the native reader")
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
