"""Brumby (gated power retention of degree 2) behind the decode engine's
seam (ISSUE 36), on the CPU at toy widths with seeded random weights,
each test against the plain reference of
`benchmarks/configs/brumby-14b.py` (the attention form; it imports
nothing of paddle_tpu), the recurrent form as written
(`kernels/retention.py` `recurrent_form`) or a few lines of numpy.

The toy model is float32, so what separates program and reference is
re-association only: a state carried and decayed against a mask over
all the keys.  Logits of size one agree to a few 1e-5; the tolerances
below leave ten times that."""

import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor, profiler
from paddle_tpu.kernels import retention as R
from paddle_tpu.models import brumby
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "brumby-14b")
TOL = 5e-4


def _module():
    spec = importlib.util.spec_from_file_location("brumby_14b_config",
                                                  CONFIG + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _toy_cfg(**over):
    with open(CONFIG + ".json") as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    cfg.update(dtype="float32", **over)
    return cfg


class Toy:
    """The rehearsal's widths: 4 query heads over 2 K/V heads of 16 (a
    state of 9 rows x 16 x 16), 3 layers, half-lives of 2 to 48
    positions.  Buckets of 48 and 96 are walked in chunks of 16 and 32,
    three chunks each."""

    def __init__(self, seed=36, max_len=160, **over):
        self.M = _module()
        self.cfg = _toy_cfg(**over)
        self.bcfg = brumby.BrumbyCfg.from_hf(self.cfg, max_seq_len=max_len)
        with jax.default_matmul_precision("highest"):
            self.flat = self.M.init_params(self.cfg, seed)
        self.params = brumby.BrumbyParams.from_flat(self.bcfg, self.flat)
        self.ref = self.M.ReferenceLM(self.cfg, seed, max_len,
                                      params=self.flat)

    def engine(self, auto_start=False, **kw):
        kw.setdefault("slots", 2)
        kw.setdefault("max_len", self.bcfg.max_seq_len)
        kw.setdefault("buckets", (48, 96))
        kw.setdefault("watchdog_stall_s", 60.0)
        kw.setdefault("label", f"brumby_{time.time_ns() % 1000000}")
        return DecodeEngine(self.params, config=DecodeConfig(**kw),
                            auto_start=auto_start)


@pytest.fixture(scope="module")
def toy():
    return Toy()


@pytest.fixture(autouse=True)
def _clean_state():
    monitor.disable()
    monitor.reset()
    profiler.reset_profiler()
    yield
    monitor.disable()
    monitor.reset()
    profiler.reset_profiler()


def _drain(eng, futs, max_steps=2000):
    for _ in range(max_steps):
        if all(f.done() for f in futs):
            return
        eng.step()
    raise AssertionError("engine did not drain")


def _inputs(seed, t, heads, kvh, d, shortest=2.0, longest=64.0):
    """Seeded q, k, v and gates of half-lives between the two."""
    rng = np.random.default_rng(seed)

    def rand(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    life = shortest * (longest / shortest) ** rng.uniform(size=(t, kvh))
    return (rand((t, heads, d)), rand((t, kvh, d)), rand((t, kvh, d)),
            jnp.asarray(-np.log(2.0) / life, jnp.float32))


def _attention_form(q, k, v, log_g):
    """The published form in float64 numpy: no phi, no state."""
    q, k, v, log_g = (np.asarray(x, np.float64) for x in (q, k, v, log_g))
    t, h, _ = q.shape
    group = h // k.shape[1]
    k, v, log_g = (np.repeat(x, group, axis=1) for x in (k, v, log_g))
    big_g = np.cumsum(log_g, axis=0).T                          # [h, T]
    a = np.einsum("thd,shd->hts", q, k) ** 2 \
        * np.exp(big_g[:, :, None] - big_g[:, None, :])
    a = np.where(np.tril(np.ones((t, t), bool)), a, 0.0)
    return np.einsum("hts,shd->thd", a, v) / a.sum(-1).T[..., None]


def _fresh(layers, slots, kvh, d, fill=0.0):
    return (jnp.full((layers, slots, kvh, d, R.phi_rows(d) * d), fill,
                     jnp.float32),
            jnp.full((layers, slots, kvh, d, d), fill, jnp.float32))


# ---------------------------------------------------------------------
# the three forms of one function
# ---------------------------------------------------------------------

@pytest.mark.parametrize("d", [2, 8, 16, 128])
def test_phi_of_query_and_key_multiply_to_the_squared_product(d):
    rng = np.random.default_rng(d)
    q, k = (jnp.asarray(rng.standard_normal((7, d)), jnp.float32)
            for _ in range(2))
    assert R.phi_q(q).shape == (7, (d // 2 + 1) * d)
    np.testing.assert_allclose((R.phi_q(q) * R.phi_k(k)).sum(-1),
                               (q * k).sum(-1) ** 2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("t,chunk,heads,kvh", [
    (48, 16, 4, 2), (48, 48, 4, 2), (40, 8, 6, 2), (32, 4, 5, 1)])
def test_recurrent_chunked_and_attention_forms_agree(t, chunk, heads, kvh):
    """Outputs of the three forms, and the state and the divisor's
    themselves of the two that have one."""
    d = 16
    q, k, v, log_g = _inputs(t, t, heads, kvh, d)
    want = _attention_form(q, k, v, log_g)
    o_rec, st_rec, m_rec = R.recurrent_form(q, k, v, log_g)
    state, norm = _fresh(1, 1, kvh, d)
    o_chunk, state, norm = R.retention_prefill(
        q, k, v, log_g, t, state, norm, 0, 0, use_kernel=False, chunk=chunk)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(o_rec) - want).max() <= TOL * scale
    assert np.abs(np.asarray(o_chunk) - want).max() <= TOL * scale
    for got, ref in ((state[0, 0], st_rec), (norm[0, 0], m_rec)):
        assert float(jnp.abs(got - ref).max()) \
            <= TOL * float(jnp.abs(ref).max())


def test_decode_steps_continue_what_a_prefill_left():
    """A prompt prefilled and then decoded a position at a time reads
    what the attention form reads over all of it."""
    t, cut, d = 40, 24, 16
    q, k, v, log_g = _inputs(1, t, 4, 2, d)
    want = _attention_form(q, k, v, log_g)
    state, norm = _fresh(2, 3, 2, d, fill=7.0)
    _, state, norm = R.retention_prefill(
        q[:32], k[:32], v[:32], log_g[:32], cut, state, norm, 1, 2,
        use_kernel=False, chunk=8)
    active = jnp.asarray([False, False, True])
    for i in range(cut, t):
        def rows(x):
            return jnp.broadcast_to(x[i][None], (3,) + x.shape[1:])
        o, state, norm = R.retention_decode(
            rows(q), rows(k), rows(v), rows(log_g), state, norm, 1, active)
        assert np.abs(np.asarray(o[2]) - want[i]).max() \
            <= TOL * np.abs(want).max()
        assert float(jnp.abs(o[:2]).max()) == 0.0
    # the other slots and the other layer were never written
    assert bool((state[0] == 7.0).all()) and bool((state[1, :2] == 7.0).all())
    assert bool((norm[0] == 7.0).all()) and bool((norm[1, :2] == 7.0).all())


@pytest.mark.parametrize("kernel", [False, True])
def test_a_buckets_padding_leaves_the_state_untouched(monkeypatch, kernel):
    """Whatever lies past `true_len` in the bucket, the slot holds the
    state of the true last position, bit for bit the same state, and it
    replaces whatever the slot held."""
    d, heads, kvh = (128, 5, 1) if kernel else (16, 4, 2)
    t, true_len, chunk = 32, 19, 8
    q, k, v, log_g = _inputs(2, t, heads, kvh, d)
    _, st, m = R.recurrent_form(q[:true_len], k[:true_len], v[:true_len],
                                log_g[:true_len])
    got = []
    for seed, fill in ((3, 0.0), (4, 5.0)):
        noise = _inputs(seed, t, heads, kvh, d)
        late = (jnp.arange(t) >= true_len)
        qq, kk, vv = (jnp.where(late[:, None, None], n, x)
                      for n, x in zip(noise, (q, k, v)))
        ll = jnp.where(late[:, None], noise[3], log_g)
        state, norm = _fresh(1, 2, kvh, d, fill=fill)
        _, state, norm = R.retention_prefill(
            qq, kk, vv, ll, true_len, state, norm, 0, 1, use_kernel=kernel,
            chunk=chunk, operands="float32")
        assert bool((state[0, 0] == fill).all())
        got.append((state[0, 1], norm[0, 1]))
    for a, b in zip(*got):
        assert bool((a == b).all())
    for x, ref in zip(got[0], (st, m)):
        assert float(jnp.abs(x - ref).max()) <= TOL * float(jnp.abs(ref).max())


# ---------------------------------------------------------------------
# the kernels, interpreted, against their XLA mathematics
# ---------------------------------------------------------------------

def _decode_case():
    d, heads, kvh, slots = 128, 5, 1, 3
    rng = np.random.default_rng(6)
    state = jnp.asarray(rng.standard_normal((2, slots, kvh, d, 65 * d)),
                        jnp.float32)
    half = jnp.asarray(rng.standard_normal((2, slots, kvh, d, d)),
                       jnp.float32)
    return _inputs(5, slots, heads, kvh, d) \
        + (state, jnp.einsum("lsjab,lsjcb->lsjac", half, half))


_decode_fns = {}


def _decode_fn(**kw):
    """One compile a path: `active` is traced."""
    key = tuple(sorted(kw.items()))
    if key not in _decode_fns:
        _decode_fns[key] = jax.jit(
            lambda q, k, v, log_g, state, norm, active: R.retention_decode(
                q, k, v, log_g, state, norm, 1, active, **kw))
    return _decode_fns[key]


@pytest.mark.parametrize("active", [
    [True, True, True], [False, True, False], [True, False, True],
    [False, False, True], [False, False, False]])
def test_decode_kernel_equals_xla_and_spares_idle_slots(active):
    case = _decode_case()
    act = jnp.asarray(active)
    want = _decode_fn(use_kernel=False)(*case, act)
    got = _decode_fn(use_kernel=True)(*case, act)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) \
            <= 1e-5 * max(float(jnp.abs(b).max()), 1.0)
    idle = ~np.asarray(active)
    for a, b in zip(got[1:], case[4:]):
        # bit for bit: the idle slots of this layer, all of the other
        assert bool((a[1][idle] == b[1][idle]).all())
        assert bool((a[0] == b[0]).all())


def test_decode_kernel_gives_the_same_in_any_tiling():
    case, act = _decode_case(), jnp.asarray([True, False, True])
    want = _decode_fn(use_kernel=True)(*case, act)
    got = _decode_fn(use_kernel=True, tile_rows=5)(*case, act)
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) \
            <= 1e-5 * max(float(jnp.abs(b).max()), 1.0)


@pytest.mark.parametrize("operands,tol", [("float32", 1e-5),
                                          ("bfloat16", 2e-2)])
def test_prefill_kernel_equals_xla(operands, tol):
    d, heads, kvh, t, true_len = 128, 4, 2, 32, 27
    q, k, v, log_g = _inputs(7, t, heads, kvh, d)
    state, norm = _fresh(2, 2, kvh, d, fill=3.0)
    want = R.retention_prefill(q, k, v, log_g, true_len, state, norm, 1, 0,
                               use_kernel=False, chunk=8)
    got = R.retention_prefill(q, k, v, log_g, true_len, state, norm, 1, 0,
                              use_kernel=True, chunk=8, operands=operands)
    for a, b in zip(got, want):
        a, b = a[:true_len] if a.ndim == 3 else a, \
            b[:true_len] if b.ndim == 3 else b
        assert float(jnp.abs(a - b).max()) <= tol * float(jnp.abs(b).max())
    assert bool((got[1][1, 1] == 3.0).all()) and bool((got[1][0] == 3.0).all())
    if operands == "bfloat16":
        # the divisor's state never leaves float32
        assert float(jnp.abs(got[2] - want[2]).max()) \
            <= 1e-5 * float(jnp.abs(want[2]).max())


def test_tiling_comes_from_the_shape_alone():
    for bucket in (6144, 8192):
        assert R.retention_tiling(128, bucket) == (13, 5, 256)
    assert R.retention_tiling(16, 48).chunk == 16
    assert R.retention_tiling(16, 96).chunk == 32
    # the chunk lies well under where the two forms cost the same
    assert 8 * R.retention_tiling(128, 8192).chunk < 65 * 128 // 2
    with pytest.raises(ValueError):
        R.retention_tiling(128, 100, chunk=64)
    with pytest.raises(ValueError):
        R.retention_tiling(128, tile_rows=7)


def test_one_predicate_picks_the_kernels(monkeypatch):
    assert not R._takes_kernel(128, 5)             # no TPU here
    assert R._takes_kernel(128, 5, use_kernel=True)
    assert not R._takes_kernel(64, 5, use_kernel=True)
    assert not R._takes_kernel(128, 6, use_kernel=True)
    monkeypatch.setenv("PADDLE_TPU_FORCE_RETENTION", "1")
    assert R._takes_kernel(128, 5)
    monkeypatch.setattr(R.backend, "is_tpu_backend", lambda: True)
    assert not R._takes_kernel(128, 5, chunk=64)   # no whole lanes
    assert R._takes_kernel(128, 5, chunk=256)


# ---------------------------------------------------------------------
# the model's side of the seam against the reference
# ---------------------------------------------------------------------

def test_param_shapes_are_the_benchmarks(toy):
    want = {n: (tuple(s), kind) for n, s, kind in toy.M.param_specs(toy.cfg)}
    have = {n: (tuple(s), kind)
            for n, (s, kind) in brumby.param_shapes(toy.bcfg).items()}
    assert want == have
    own = brumby.init_params(toy.bcfg, jax.random.PRNGKey(0),
                             half_life=(2.0, 48.0))
    assert {n: v.shape for n, v in own.items()} \
        == {n: v.shape for n, v in toy.flat.items()}
    life = np.log(2) / -np.asarray(jax.nn.log_sigmoid(
        own["layers.0.gate_bias"]))
    assert (life > 1.99).all() and (life < 48.1).all()


def test_the_published_sizes_are_the_issues_count():
    """The cut of configs/brumby-14b.json: 8 x 330,352,896 parameters of
    layers, 2 x 777,912,320 of vocabulary and a final norm (the gates'
    64 offsets beside them), 272.6 MB of published state a slot."""
    M = _module()
    with open(CONFIG + ".json") as f:
        cfg = json.load(f)
    assert M.param_count(cfg) == 8 * 330_352_896 + 2 * 777_912_320 \
        + 5120 + 8 * 8 == 4_198_652_928 + 64
    assert M.monomials(cfg) == 8256
    assert M.slot_state_bytes(cfg) == 8 * 8 * (8256 * 128 + 8256) * 4
    assert M.retention_decode_bytes(cfg, 16) == 2 * 16 * 272_646_144
    assert M.retention_decode_flops(cfg, 16) \
        == 16 * 8 * 2 * 8256 * 128 * 48
    assert M.serve_flops_per_token(cfg) == 2 * (
        8 * (330_352_896 - 10_496) + 777_912_320) \
        + 8 * 2 * 8256 * 128 * 48
    # the program's layout holds 1.5% more than the published state
    bcfg = brumby.BrumbyCfg.from_hf(cfg)
    assert 1.0 < bcfg.slot_state_bytes / M.slot_state_bytes(cfg) < 1.02
    # the prefill's count: the cheaper form a position, never more than
    # the recurrent form's
    for bucket in (6144, 8192):
        assert 0.7 < M.retention_prefill_flops(cfg, bucket) / (
            bucket * 8 * M.retention_flops_per_position(cfg)) < 1.0


@pytest.mark.parametrize("key,value", [
    ("sliding_window", 4096), ("use_sliding_window", True),
    ("rope_scaling", {"type": "yarn"}), ("attention_bias", True),
    ("tie_word_embeddings", True), ("hidden_act", "gelu")])
def test_from_hf_refuses_what_is_not_served(key, value):
    with pytest.raises(ValueError):
        brumby.BrumbyCfg.from_hf(_toy_cfg(**{key: value}))


def _reference_logits(toy, ids):
    """float32 logits [len(ids), vocab] of the reference's pass."""
    n = len(ids)
    padded = np.zeros(toy.ref.max_len, np.int32)
    padded[:n] = ids
    x = toy.ref.hidden(padded, np.arange(toy.ref.max_len))[:n]
    x = toy.M._rms_norm(x, toy.flat["final_norm"], toy.cfg["rms_norm_eps"])
    return np.asarray(jnp.matmul(x, toy.flat["lm_head"],
                                 precision="highest"))


def test_full_logits_equal_the_references(toy):
    ids = np.random.default_rng(1).integers(0, 211, 96).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(brumby.full_logits(toy.bcfg, toy.params.trees,
                                            jnp.asarray(ids)))
    want = _reference_logits(toy, ids)
    assert np.abs(got - want).max() <= TOL * np.abs(want).max()


@pytest.mark.parametrize("fault", _module().FAULTS)
def test_each_mechanism_moves_the_references_tokens(toy, fault):
    """Every planted fault changes what the reference puts first, by
    more than the rehearsal's limit."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 211, 70).astype(np.int32)
    served = rng.integers(0, 211, 40).astype(np.int32)
    # at toy widths the program's chunk of 256 never ends inside 110
    # positions: a chunk of 16 stands for it
    toy.M.FAULT_CHUNK, toy.M.FAULT_PADDING = 16, 8
    try:
        gaps = toy.ref.token_gaps(prompt, served, fault=fault)
    finally:
        toy.M.FAULT_CHUNK, toy.M.FAULT_PADDING = 256, 64
    assert gaps.max() > 0.05, (fault, gaps.max())


def test_prefill_then_decode_give_the_forward_passes_logits(toy):
    """The seam by hand: a prefill in the bucket of 48 (three chunks, the
    padding not empty), then 36 decode steps (more than the longest
    half-life but one), each step's logits against the reference's."""
    cfg, trees = toy.bcfg, toy.params.trees
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 211, 41 + 36).astype(np.int32)
    want = _reference_logits(toy, ids)
    cache = cfg.cache_arrays(2, 160)
    prompt = np.zeros((1, 48), np.int32)
    prompt[0, :41] = ids[:41]
    with jax.default_matmul_precision("highest"):
        cache, hidden, counters = cfg.prefill(trees, cache, prompt,
                                              jnp.int32(41), jnp.int32(1))
        assert int(counters["chunks"]) == 3
        got = [np.asarray(cfg.head(trees, hidden))[0]]
        active = jnp.asarray([False, True])
        for i in range(41, 41 + 35):
            cache, hidden, counters = cfg.decode(
                trees, cache, jnp.asarray([0, ids[i]]),
                jnp.asarray([0, i]), active)
            got.append(np.asarray(cfg.head(trees, hidden))[1])
        assert counters == {}
    got = np.stack(got)
    assert np.abs(got - want[40:40 + 36]).max() <= TOL * np.abs(want).max()
    assert float(jnp.abs(cache["state"][:, 0]).max()) == 0.0


PROMPTS = (5, 15, 16, 17, 33, 47, 48, 49, 70, 96)


def _requests(seed=4, new=34):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 211, n).astype(np.int32), new)
            for n in PROMPTS]


def _assert_reference_tokens(toy, prompts, served):
    for (prompt, new), tokens in zip(prompts, served):
        assert len(tokens) == new
        gaps = toy.ref.token_gaps(prompt, np.asarray(tokens))
        assert gaps.max() <= TOL, (prompt.size, gaps.max())


def test_engine_serves_what_the_reference_computes(toy):
    """Prompts either side of the chunks (16, 32) and of both buckets (48,
    96), up to three chunks long, answers of 34 tokens (the shortest
    half-lives are 2 positions): every token is the reference's first,
    through refilled slots."""
    eng = toy.engine()
    reqs = _requests()
    futs = [eng.submit(p, n) for p, n in reqs]
    _drain(eng, futs)
    cache = eng.summary()["decode"]["cache"]
    eng.close()
    _assert_reference_tokens(toy, reqs, [f.result() for f in futs])
    assert cache["chunks"] == sum(3 for _ in reqs)
    assert cache["state_bytes"] > 0


def test_loop_thread_one_step_ahead_serves_what_the_reference_computes(toy):
    eng = toy.engine(auto_start=True, slots=3)
    reqs = _requests(seed=5, new=20)[:6]
    try:
        futs = [eng.submit(p, n) for p, n in reqs]
        served = [f.result(timeout=120) for f in futs]
    finally:
        eng.close()
    _assert_reference_tokens(toy, reqs, served)


def test_a_refilled_slot_answers_as_a_fresh_engine(toy):
    """One slot: a long request, then a short one into the state the long
    one left.  The prefill replaces the whole state, so the short one's
    tokens are a fresh engine's."""
    rng = np.random.default_rng(6)
    long_one = rng.integers(0, 211, 90).astype(np.int32)
    short = rng.integers(0, 211, 7).astype(np.int32)
    eng = toy.engine(slots=1)
    futs = [eng.submit(long_one, 40), eng.submit(short, 30)]
    _drain(eng, futs)
    eng.close()
    fresh = toy.engine(slots=1)
    alone = fresh.submit(short, 30)
    _drain(fresh, [alone])
    fresh.close()
    assert futs[1].result().tolist() == alone.result().tolist()
    _assert_reference_tokens(toy, [(short, 30)], [alone.result()])


def test_a_step_leaves_idle_slots_states_bit_for_bit(toy):
    """Three slots, one request: the two idle slots' states (what their
    last tenants left) are the same bits after every step."""
    rng = np.random.default_rng(7)
    eng = toy.engine(slots=3)
    first = [eng.submit(rng.integers(0, 211, n).astype(np.int32), 3)
             for n in (20, 30, 40)]
    _drain(eng, first)
    before = {n: np.asarray(eng._state[n]) for n in ("state", "norm")}
    assert all(np.abs(v[:, 1:]).max() > 0 for v in before.values())
    fut = eng.submit(rng.integers(0, 211, 10).astype(np.int32), 25)
    _drain(eng, [fut])
    after = {n: np.asarray(eng._state[n]) for n in ("state", "norm")}
    eng.close()
    slot = next(i for i in range(3)
                if not (before["state"][:, i] == after["state"][:, i]).all())
    for n in before:
        for i in set(range(3)) - {slot}:
            assert (before[n][:, i] == after[n][:, i]).all()


def test_summary_lists_the_states_as_states(toy):
    eng = toy.engine(slots=3)
    cache = eng.summary()["decode"]["cache"]
    eng.close()
    assert cache["kind"] == toy.bcfg.cache_kind
    assert cache["arrays"] == [
        {"name": "state", "kind": "state", "layers": 3,
         "bytes": 3 * 3 * 2 * 16 * 9 * 16 * 4},
        {"name": "norm", "kind": "state", "layers": 3,
         "bytes": 3 * 3 * 2 * 16 * 16 * 4}]
    assert cache["bytes"] == 3 * toy.bcfg.slot_state_bytes


def test_engine_through_the_kernels_serves_the_references_tokens(
        monkeypatch):
    """Heads of 128 through both kernels in the interpreter: 5 query
    heads over one K/V head, a bucket of 32 in chunks of 32 and one of
    48 in chunks of 16."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_RETENTION", "1")
    wide = Toy(num_hidden_layers=1, head_dim=128, num_attention_heads=5,
               num_key_value_heads=1, max_len=64)
    eng = wide.engine(buckets=(32, 48))
    rng = np.random.default_rng(8)
    reqs = [(rng.integers(0, 211, n).astype(np.int32), 6)
            for n in (9, 40, 31)]
    futs = [eng.submit(p, n) for p, n in reqs]
    _drain(eng, futs)
    eng.close()
    _assert_reference_tokens(wide, reqs, [f.result() for f in futs])
