"""Nemotron-H (Mamba-2 mixers beside grouped-query attention and relu2
experts) behind the decode engine's seam, on the CPU at toy widths with
seeded random weights, each test against the plain reference of
`benchmarks/configs/nemotron-3-nano-30b-a3b.py` (the published minimal
chunked form of the recurrence; it imports nothing of paddle_tpu), the
recurrence as written a position at a time (`kernels/ssd.py`
`ssd_recurrent`) or a few lines of numpy.

The toy model is float32, so what separates program and reference is
re-association only.  Logits of size one agree to a few 1e-5; the
tolerances below leave ten times that."""

import importlib.util
import json
import logging
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor, profiler
from paddle_tpu.distributed.moe import (grouped_product, relu2_act,
                                        routed_experts)
from paddle_tpu.kernels import grouped_mm
from paddle_tpu.kernels import ssd as K
from paddle_tpu.models import nemotron_h as NH
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "nemotron-3-nano-30b-a3b")
TOL = 5e-4


def _module():
    spec = importlib.util.spec_from_file_location("nemotron_h_config",
                                                  CONFIG + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _published():
    with open(CONFIG + ".json") as f:
        return json.load(f)


def _toy_cfg(**over):
    cfg = _published()
    cfg.update(cfg["rehearse"])
    cfg.update(dtype="float32", **over)
    return cfg


class Toy:
    """The rehearsal's widths: the 13 layers MEMEM*EMEMEM*, 8 Mamba heads
    of 16 in 2 groups with a state of 16, chunks of 16, 4 query heads
    over 2 K/V heads of 16, 4 of 16 routed experts held, top 2."""

    def __init__(self, seed=41, max_len=128, **over):
        self.M = _module()
        self.cfg = _toy_cfg(**over)
        self.ncfg = NH.NemotronHCfg.from_hf(self.cfg, max_seq_len=max_len)
        with jax.default_matmul_precision("highest"):
            self.flat = self.M.init_params(self.cfg, seed)
        self.params = NH.NemotronHParams.from_flat(self.ncfg, self.flat)
        self.ref = self.M.ReferenceLM(self.cfg, seed, max_len,
                                      params=self.flat)

    def engine(self, auto_start=False, **kw):
        kw.setdefault("slots", 2)
        kw.setdefault("max_len", self.ncfg.max_seq_len)
        kw.setdefault("buckets", (32, 64))
        kw.setdefault("watchdog_stall_s", 60.0)
        kw.setdefault("label", f"nemotron_{time.time_ns() % 1000000}")
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


def _inputs(seed, t, heads, p, groups, n):
    """Seeded x, dt (0.001 to 0.3), A (-1 to -16), B, C."""
    rng = np.random.default_rng(seed)

    def rand(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (rand((t, heads, p)),
            jnp.asarray(10 ** rng.uniform(-3, -0.5, (t, heads)), jnp.float32),
            -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32),
            rand((t, groups, n)), rand((t, groups, n)))


def _loop_form(x, dt, a, b, c):
    """The recurrence as written, float64 numpy, a position at a time."""
    x, dt, a, b, c = (np.asarray(z, np.float64) for z in (x, dt, a, b, c))
    t, h, p = x.shape
    group = h // b.shape[1]
    s = np.zeros((h, p, b.shape[-1]))
    y = np.zeros_like(x)
    for i in range(t):
        for j in range(h):
            g = j // group
            s[j] = np.exp(dt[i, j] * a[j]) * s[j] \
                + dt[i, j] * np.outer(x[i, j], b[i, g])
            y[i, j] = s[j] @ c[i, g]
    return y, s


def _fresh(layers, slots, heads, p, n, groups, fill=0.0):
    pack = K.ssd_tiling(heads, p, n, groups).pack
    return jnp.full((layers, slots, heads // pack * n, pack * p), fill,
                    jnp.float32)


# ---------------------------------------------------------------------
# kernels/ssd.py: the forms of one function
# ---------------------------------------------------------------------

# (t, heads, head_dim, groups, state, chunk, kernel): the kernel's rows
# are 128 lanes of packed heads (2 of 64, 4 of 32, 1 of 128)
SHAPES = [(48, 8, 16, 2, 16, 16, False), (40, 4, 8, 1, 8, 8, False),
          (64, 8, 64, 2, 32, 32, True), (32, 8, 32, 2, 16, 16, True),
          (32, 2, 128, 2, 8, 16, True)]


@pytest.mark.parametrize("t,h,p,g,n,chunk,kernel", SHAPES)
def test_recurrent_and_chunked_forms_agree(t, h, p, g, n, chunk, kernel):
    """Outputs and the state itself, of the recurrence as written, of
    `ssd_recurrent` and of `ssd_prefill` (XLA, or the kernel in the
    interpreter)."""
    x, dt, a, b, c = _inputs(t, t, h, p, g, n)
    want_y, want_s = _loop_form(x, dt, a, b, c)
    y_rec, s_rec = K.ssd_recurrent(x, dt, a, b, c)
    state = _fresh(1, 1, h, p, n, g)
    y, state = K.ssd_prefill(x, dt, a, b, c, t, state, 0, 0, chunk=chunk,
                             use_kernel=kernel)
    scale, s_scale = np.abs(want_y).max(), np.abs(want_s).max()
    pack = K.ssd_tiling(h, p, n, g).pack
    for got, got_s in ((y_rec, s_rec),
                       (y, K.unpack_state(state[0, 0], h, pack))):
        assert np.abs(np.asarray(got) - want_y).max() <= TOL * scale
        assert np.abs(np.asarray(got_s) - want_s).max() <= TOL * s_scale


@pytest.mark.parametrize("kernel", [False, True])
def test_a_buckets_padding_leaves_the_state_untouched(kernel):
    """Whatever lies past `true_len` in the bucket, the slot holds the
    state of the true last position, the same bits for any padding, and
    it replaces whatever the slot held; no other slot or layer is
    written."""
    h, p, g, n = (8, 64, 2, 32) if kernel else (8, 16, 2, 16)
    t, true_len, chunk = 64, 37, 16
    x, dt, a, b, c = _inputs(2, t, h, p, g, n)
    _, want = _loop_form(x[:true_len], dt[:true_len], a, b[:true_len],
                         c[:true_len])
    pack = K.ssd_tiling(h, p, n, g).pack
    got = []
    for seed, fill in ((3, 0.0), (4, 5.0)):
        noise = _inputs(seed, t, h, p, g, n)
        pad = [jnp.concatenate([z[:true_len], w[true_len:]])
               for z, w in zip((x, dt, b, c), (noise[0], noise[1], noise[3],
                                               noise[4]))]
        state = _fresh(2, 3, h, p, n, g, fill=fill)
        _, state = K.ssd_prefill(pad[0], pad[1], a, pad[2], pad[3],
                                 true_len, state, 1, 2, chunk=chunk,
                                 use_kernel=kernel)
        assert bool((state[0] == fill).all())
        assert bool((state[1, :2] == fill).all())
        got.append(np.asarray(state[1, 2]))
    assert (got[0] == got[1]).all()
    s = np.asarray(K.unpack_state(jnp.asarray(got[0]), h, pack))
    assert np.abs(s - want).max() <= TOL * np.abs(want).max()


@pytest.mark.parametrize("kernel", [False, True])
def test_decode_steps_continue_what_a_prefill_left(kernel):
    """A prompt prefilled in a bucket and then decoded a position at a
    time reads what the recurrence reads over all of it; idle slots
    answer 0, and neither they nor the other layer are written."""
    h, p, g, n = (8, 64, 2, 32) if kernel else (8, 16, 2, 16)
    t, cut = 40, 21
    x, dt, a, b, c = _inputs(1, t, h, p, g, n)
    want, _ = _loop_form(x, dt, a, b, c)
    state = _fresh(2, 3, h, p, n, g, fill=7.0)
    _, state = K.ssd_prefill(x[:32], dt[:32], a, b[:32], c[:32], cut, state,
                             1, 2, chunk=16, use_kernel=kernel)
    active = jnp.asarray([False, False, True])
    for i in range(cut, t):
        def rows(z):
            return jnp.broadcast_to(z[i][None], (3,) + z.shape[1:])
        y, state = K.ssd_decode(rows(x), rows(dt), a, rows(b), rows(c),
                                state, 1, active, use_kernel=kernel)
        assert np.abs(np.asarray(y[2]) - want[i]).max() \
            <= TOL * np.abs(want).max()
        assert float(jnp.abs(y[:2]).max()) == 0.0
    assert bool((state[0] == 7.0).all()) and bool((state[1, :2] == 7.0).all())


@pytest.mark.parametrize("active", [[True, False, True, True],
                                    [False, False, True, False],
                                    [False] * 4])
def test_decode_kernel_equals_xla_and_spares_idle_slots(active):
    """At the published heads, groups and state: two blocks of 32 heads a
    slot, so a slot that is not active names the last block of the
    active slot before it."""
    h, p, g, n, s = 64, 64, 8, 128, 4
    assert K.ssd_tiling(h, p, n, g).block_heads == 32
    x, dt, a, b, c = _inputs(5, s, h, p, g, n)
    state = jnp.asarray(np.random.default_rng(6).standard_normal(
        (2, s, h // 2 * n, 2 * p)), jnp.float32)
    act = jnp.asarray(active)
    want = K.ssd_decode(x, dt, a, b, c, state, 1, act, use_kernel=False)
    got = K.ssd_decode(x, dt, a, b, c, state, 1, act, use_kernel=True)
    for u, v in zip(got, want):
        assert np.abs(np.asarray(u) - np.asarray(v)).max() \
            <= 1e-5 * np.abs(np.asarray(v)).max()
    idle = ~np.asarray(active)
    assert (np.asarray(got[1])[:, idle] == np.asarray(state)[:, idle]).all()
    assert (np.asarray(got[1])[0] == np.asarray(state)[0]).all()


def test_tiling_comes_from_the_shape_alone():
    published = K.ssd_tiling(64, 64, 128, 8, chunk=128)
    # 2 heads of 64 a row of lanes; 4 groups of 8 heads a decode step,
    # 1 MiB of state
    assert published == K.SsdTiling(pack=2, block_heads=32, chunk=128)
    assert 32 * 64 * 128 * 4 == 1 << 20
    assert K.ssd_tiling(8, 16, 16, 2).pack == 4
    assert K.ssd_tiling(4, 128, 16, 1).pack == 1
    assert K.ssd_tiling(6, 48, 16, 3).pack == 1
    # a group's state over 1 MiB is a block of its own
    assert K.ssd_tiling(16, 128, 256, 2).block_heads == 8
    s = jnp.arange(2 * 8 * 16 * 4, dtype=jnp.float32).reshape(2, 8, 16, 4)
    for pack in (1, 2, 4, 8):
        assert (K.unpack_state(K.pack_state(s, pack), 8, pack) == s).all()


def _kernels_in_the_interpreter(monkeypatch):
    """The kernels' module sees a TPU but runs its calls interpreted."""
    monkeypatch.setattr(K, "backend", types.SimpleNamespace(
        is_tpu_backend=lambda: True, interpret=lambda: True))


def test_one_predicate_picks_the_kernels(monkeypatch):
    assert not K._takes_kernel(64, 64, 128, 8)          # the CPU
    assert K._takes_kernel(64, 64, 128, 8, use_kernel=True)
    # a row of packed heads that is not 128 lanes never takes it
    assert not K._takes_kernel(8, 16, 16, 2, use_kernel=True)
    _kernels_in_the_interpreter(monkeypatch)
    assert K._takes_kernel(64, 64, 128, 8)
    assert not K._takes_kernel(64, 64, 128, 8, use_kernel=False)


# ---------------------------------------------------------------------
# the experts: relu2 through routed_experts, the width in whole lanes
# ---------------------------------------------------------------------

def _expert_layer(seed, tokens, d, f, held, routed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)

    def rand(shape, scale):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    return (rand((tokens, d), 1.0), rand((d, routed), 0.3),
            jnp.asarray(rng.standard_normal(routed) * 0.02, jnp.float32),
            rand((held, d, f), 0.1), rand((held, f, d), 0.1),
            rand((d, 2 * f), 0.1), rand((2 * f, d), 0.1))


def _relu2_layer(h, router, bias, up, down, first, top_k, scale):
    """The routed part in numpy: every held expert over every token,
    weighted by who chose it."""
    h, router, up, down = (np.asarray(z, np.float64)
                           for z in (h, router, up, down))
    scores = 1 / (1 + np.exp(-h @ router))
    chosen = np.argsort(-(scores + np.asarray(bias)), axis=1)[:, :top_k]
    picked = np.take_along_axis(scores, chosen, axis=1)
    weights = picked / picked.sum(1, keepdims=True) * scale
    y = np.zeros_like(h)
    for e in range(up.shape[0]):
        mine = np.where(chosen == first + e, weights, 0.0).sum(1)
        y += mine[:, None] * (np.maximum(h @ up[e], 0) ** 2 @ down[e])
    return y


@pytest.mark.parametrize("tokens", [16, 256])
def test_routed_experts_with_relu2_is_the_published_layer(tokens):
    """The model's expert function through `routed_experts`: at 16
    tokens every row runs, at 256 the kept rows (6 of 128 of 16 x 2)."""
    h, router, bias, up, down, _, _ = _expert_layer(1, tokens, 32, 24, 4, 16)
    y, counts, kept = routed_experts(h, router, bias, (up, down), 4, 16, 2,
                                     2.5, act=relu2_act)
    want = _relu2_layer(h, router, bias, up, down, 4, 2, 2.5)
    assert np.abs(np.asarray(y) - want).max() <= 1e-4 * np.abs(want).max()
    assert int(counts.sum()) > 0
    assert int(kept) == (1 if tokens == 256 else 0)


def test_the_experts_width_padded_to_whole_lanes_is_exact():
    """1,856 is 14.5 lanes: `moe_grouped_mm` refuses it (and says why);
    the width as the model stores it, zero-padded to 1,920, goes through
    the kernel (the interpreter) and gives what the unpadded product
    gives through `ragged_dot`."""
    rng = np.random.default_rng(2)
    rows = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 256, 1856)) * 0.1, jnp.float32)
    counts = jnp.asarray([100, 0, 120], jnp.int32)
    assert grouped_mm.refusal(256, 256, 1856) == \
        "N 1856 is not a multiple of 128"
    assert not grouped_mm.takes_kernel(256, 256, 1856)
    want = grouped_product(rows, w, counts, use_kernel=True)    # ragged_dot
    padded = jnp.pad(w, ((0, 0), (0, 0), (0, 64)))
    assert grouped_mm.takes_kernel(256, 256, 1920)
    got = grouped_product(rows, padded, counts, use_kernel=True)
    live = np.arange(256) < 220
    assert np.abs(np.asarray(got)[live, :1856]
                  - np.asarray(want)[live]).max() <= 1e-4
    assert float(jnp.abs(got[live, 1856:]).max()) == 0.0
    # the down product: 64 rows of zeros under zero activations
    down = jnp.asarray(rng.standard_normal((3, 1856, 256)), jnp.float32)
    act = relu2_act(want)
    got = grouped_product(jnp.pad(act, ((0, 0), (0, 64))),
                          jnp.pad(down, ((0, 0), (0, 64), (0, 0))), counts,
                          use_kernel=True)
    want = grouped_product(act, down, counts, use_kernel=True)
    assert np.abs(np.asarray(got)[live] - np.asarray(want)[live]).max() \
        <= 1e-3 * np.abs(np.asarray(want)[live]).max()


def test_a_refused_width_is_logged_once_a_shape(caplog):
    grouped_mm._REFUSED.discard((128, 128, 1856))
    with caplog.at_level(logging.INFO, logger=grouped_mm.__name__):
        for _ in range(3):
            assert not grouped_mm.takes_kernel(128, 128, 1856)
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 1 and "N 1856 is not a multiple of 128" in said[0]


def test_tiles_of_odd_lane_counts_are_not_slivers():
    """Power-of-two tiles where they divide (K2's and Trinity's widths
    keep theirs), else the largest whole-lane divisor."""
    assert grouped_mm._tile(7168, 1024) == 1024
    assert grouped_mm._tile(7168, 2048) == 1024
    assert grouped_mm._tile(4096, 2048) == 2048
    assert grouped_mm._tile(2048, 1024) == 1024
    assert grouped_mm._tile(2688, 1024) == 896
    assert grouped_mm._tile(1920, 2048) == 1920
    assert grouped_mm._tile(1920, 1024) == 640
    assert grouped_mm._tile(64, 1024) is None


def test_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The guide's share test: 8 chips of 16 experts each (of 128, top
    6), each computing its own experts' part of the routed sum, and the
    shared expert counted once, give what one chip holding all 128
    gives."""
    h, router, bias, up, down, s_up, s_down = _expert_layer(
        3, 64, 32, 24, 128, 128)
    shared = relu2_act(h @ s_up) @ s_down
    whole, _, _ = routed_experts(h, router, bias, (up, down), 0, 128, 6, 2.5,
                                 act=relu2_act)
    parts = [routed_experts(h, router, bias,
                            (up[i:i + 16], down[i:i + 16]), i, 128, 6, 2.5,
                            act=relu2_act)[0] for i in range(0, 128, 16)]
    want = _relu2_layer(h, router, bias, up, down, 0, 6, 2.5) \
        + np.asarray(shared)
    got = np.asarray(sum(parts) + shared)
    assert np.abs(got - np.asarray(whole + shared)).max() <= 1e-4
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


# ---------------------------------------------------------------------
# the configuration and the model
# ---------------------------------------------------------------------

def test_param_shapes_are_the_benchmarks(toy):
    want = {n: (tuple(s), kind) for n, s, kind in toy.M.param_specs(toy.cfg)}
    have = {n: (tuple(s), kind)
            for n, (s, kind) in NH.param_shapes(toy.ncfg).items()}
    assert want == have
    own = NH.init_params(toy.ncfg, jax.random.PRNGKey(0))
    assert {n: v.shape for n, v in own.items()} \
        == {n: v.shape for n, v in toy.flat.items()}
    # the recurrence decays as the init keys say: dt in [0.001, 0.1]
    for flat in (own, toy.flat):
        dt = np.asarray(jax.nn.softplus(flat["layers.0.dt_bias"]))
        assert (dt > 0.00099).all() and (dt < 0.1001).all()
        a = np.exp(np.asarray(flat["layers.0.A_log"]))
        assert (a >= 1).all() and (a <= 16).all()
    # the held experts' width is stored in whole lanes
    lp = toy.params.trees["layers"][1]
    assert lp["experts_up"].shape[-1] == 128
    assert float(jnp.abs(lp["experts_up"][..., 32:]).max()) == 0.0


def test_the_published_sizes_are_the_issues_count():
    """The cut: 6 Mamba layers of 38.74 M, 5 expert layers of 179.9 M, 2
    attention layers of 23.4 M, embedding and head 88.1 M: 1,267 M
    parameters; 12.8 MB of state a slot."""
    M = _module()
    cfg = _published()
    mamba = 2688 * 10304 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 4096 * 2688 \
        + 2688
    experts = 16 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688 * 128 + 128 \
        + 2688
    attention = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    assert M.param_count(cfg) == 6 * mamba + 5 * experts + 2 * attention \
        + 2 * 16384 * 2688 + 2688
    assert round(M.param_count(cfg) / 1e6) == 1267
    assert M.ssd_state_bytes(cfg) == 6 * 64 * 64 * 128 * 4
    assert M.slot_state_bytes(cfg) == 6 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert round(M.slot_state_bytes(cfg) / 1e4) == 1280
    ncfg = NH.NemotronHCfg.from_hf(cfg, max_seq_len=3072)
    cache = jax.eval_shape(lambda: ncfg.cache_arrays(512, 3072))
    assert sum(a.size * a.dtype.itemsize for n, a in cache.items()
               if n in ncfg.cache_states) == 512 * M.slot_state_bytes(cfg)
    assert (ncfg.d_inner, ncfg.conv_dim, ncfg.expert_width) \
        == (4096, 6144, 1920)
    assert ncfg.tiling == K.SsdTiling(2, 32, 128)
    assert M.ssd_decode_bytes(cfg, 512) == 2 * 512 * M.ssd_state_bytes(cfg)
    # the decode kernel is bound by bytes: 5 operations for 8 bytes
    assert M.ssd_decode_flops(cfg, 512) / 197e12 \
        < M.ssd_decode_bytes(cfg, 512) / 819e9
    # the kept rows: a decode step of 512 slots keeps 768 of 3,072, a
    # prefill a quarter of bucket x 6
    assert ncfg.expert_layers(512) == 5
    from paddle_tpu.distributed.moe import _rows_kept
    assert _rows_kept(512 * 6, 16 / 128) == 768
    assert _rows_kept(2048 * 6, 16 / 128) == 2048 * 6 // 4


@pytest.mark.parametrize("key,value", [
    ("mlp_hidden_act", "silu"), ("attention_bias", True),
    ("tie_word_embeddings", True), ("n_group", 8),
    ("hybrid_override_pattern", "MEMEM*EMEMEM"), ("use_conv_bias", False),
    ("sliding_window", 4096)])
def test_from_hf_refuses_what_is_not_served(key, value):
    with pytest.raises(ValueError):
        NH.NemotronHCfg.from_hf(_toy_cfg(**{key: value}))


def _reference_logits(toy, ids):
    """float32 logits [len(ids), vocab] of the reference's pass."""
    n = len(ids)
    padded = np.zeros(toy.ref.max_len, np.int32)
    padded[:n] = ids
    return np.asarray(toy.ref.logits(padded, n))[:n]


def test_full_logits_equal_the_references(toy):
    ids = np.random.default_rng(1).integers(0, 211, 128).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(NH.full_logits(toy.ncfg, toy.params.trees,
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
    gaps = toy.ref.gaps(prompt, served, fault=fault)
    assert gaps.max() > 1e-3, (fault, gaps.max())


def test_a_bfloat16_state_moves_the_served_positions_alone(toy):
    """The bfloat16 state's pass: the prompt's logits as float32's (its
    state is rounded only when the prompt ends), every served position's
    moved by more than float32's rounding."""
    rng = np.random.default_rng(2)
    ids = np.zeros(toy.ref.max_len, np.int32)
    ids[:110] = rng.integers(0, 211, 110)
    want = np.asarray(toy.ref.logits(ids, 70))
    got = np.asarray(toy.ref.logits(ids, 70, judge="bf16_state"))
    scale = np.abs(want).max()
    assert np.abs(got[:70] - want[:70]).max() <= TOL * scale
    moved = np.abs(got[70:110] - want[70:110]).max(axis=1)
    assert moved.min() > 1e-4 * scale


def test_prefill_then_decode_give_the_forward_passes_logits(toy):
    """The seam by hand: a prefill of 37 in the bucket of 64 (three
    chunks, the padding not empty), then 40 decode steps with the other
    slot idle, each step's logits against the reference's."""
    cfg, trees = toy.ncfg, toy.params.trees
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 211, 37 + 40).astype(np.int32)
    want = _reference_logits(toy, ids)
    cache = cfg.cache_arrays(2, 128)
    prompt = np.zeros((1, 64), np.int32)
    prompt[0, :37] = ids[:37]
    with jax.default_matmul_precision("highest"):
        cache, hidden, counters = cfg.prefill(trees, cache, prompt,
                                              jnp.int32(37), jnp.int32(1))
        assert int(counters["chunks"]) == 4
        got = [np.asarray(cfg.head(trees, hidden))[0]]
        active = jnp.asarray([False, True])
        for i in range(37, 37 + 39):
            cache, hidden, counters = cfg.decode(
                trees, cache, jnp.asarray([0, ids[i]]),
                jnp.asarray([0, i]), active)
            got.append(np.asarray(cfg.head(trees, hidden))[1])
        assert set(counters) == {"expert_counts", "expert_layers_kept"}
    got = np.stack(got)
    assert np.abs(got - want[36:36 + 40]).max() <= TOL * np.abs(want).max()
    assert float(jnp.abs(cache["ssd"][:, 0]).max()) == 0.0
    assert float(jnp.abs(cache["conv"][:, :, 0]).max()) == 0.0


def test_a_prefill_leaves_the_prompts_own_conv_window(toy):
    """Of a prompt of 2 the window holds zeros and the prompt's two
    inputs; of a longer one its last three, never the bucket's
    padding."""
    cfg, trees = toy.ncfg, toy.params.trees
    rng = np.random.default_rng(9)
    for true_len in (2, 21):
        ids = rng.integers(1, 211, 32).astype(np.int32)
        prompt = ids.copy()
        prompt[true_len:] = rng.integers(1, 211, 32 - true_len)
        cache = cfg.cache_arrays(1, 128)
        with jax.default_matmul_precision("highest"):
            cache, _, _ = cfg.prefill(trees, cache, prompt[None],
                                      jnp.int32(true_len), jnp.int32(0))
            x = jnp.take(trees["embed"], jnp.asarray(prompt), axis=0)
            lp = trees["layers"][0]
            a = NH.rms_norm(cfg, x, lp["norm"])
            _, xbc, _ = NH._split_in(cfg, a @ lp["in_proj"])
        want = np.zeros((3, cfg.conv_dim), np.float32)
        got_rows = np.asarray(xbc)[max(0, true_len - 3):true_len]
        want[3 - len(got_rows):] = got_rows
        np.testing.assert_allclose(np.asarray(cache["conv"][0, :, 0]), want,
                                   rtol=1e-6, atol=1e-6)


PROMPTS = (2, 15, 16, 17, 33, 47, 63, 64, 31)


def _requests(seed=4, new=30):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 211, n).astype(np.int32), new)
            for n in PROMPTS]


def _assert_reference_tokens(toy, prompts, served):
    for (prompt, new), tokens in zip(prompts, served):
        assert len(tokens) == new
        gaps = toy.ref.gaps(prompt, np.asarray(tokens))
        assert gaps.max() <= TOL, (prompt.size, gaps.max())


def test_engine_serves_what_the_reference_computes(toy):
    """Prompts either side of the chunks (16) and of both buckets (32,
    64), one of 2, answers of 30 tokens: every token is the reference's
    first, through refilled slots."""
    eng = toy.engine()
    reqs = _requests()
    futs = [eng.submit(p, n) for p, n in reqs]
    _drain(eng, futs)
    cache = eng.summary()["decode"]["cache"]
    eng.close()
    _assert_reference_tokens(toy, reqs, [f.result() for f in futs])
    assert cache["chunks"] == sum(2 if p.size <= 32 else 4 for p, _ in reqs)
    assert cache["state_bytes"] > 0
    assert toy.ncfg.cache_reads([3, 40]) == {"live_full": 43}


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
    """One slot: a long request, then a short one into the states the
    long one left.  The prefill replaces both states, so the short one's
    tokens are a fresh engine's."""
    rng = np.random.default_rng(6)
    long_one = rng.integers(0, 211, 60).astype(np.int32)
    short = rng.integers(0, 211, 2).astype(np.int32)
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
    # each state with its slots first: the conv window keeps its taps
    # before them
    def slots_first(state):
        return {"ssd": np.asarray(state["ssd"]).swapaxes(0, 1),
                "conv": np.asarray(state["conv"]).transpose(2, 0, 1, 3)}

    before = slots_first(eng._state)
    assert all(np.abs(v[1:]).max() > 0 for v in before.values())
    fut = eng.submit(rng.integers(0, 211, 10).astype(np.int32), 25)
    _drain(eng, [fut])
    after = slots_first(eng._state)
    eng.close()
    slot = next(i for i in range(3)
                if not (before["ssd"][i] == after["ssd"][i]).all())
    for n in before:
        for i in set(range(3)) - {slot}:
            assert (before[n][i] == after[n][i]).all()


def test_summary_lists_two_states_and_a_depth(toy):
    eng = toy.engine(slots=3)
    cache = eng.summary()["decode"]["cache"]
    eng.close()
    assert cache["kind"] == toy.ncfg.cache_kind
    assert cache["arrays"] == [
        {"name": "ssd", "kind": "state", "layers": 6,
         "bytes": 6 * 3 * 8 * 16 * 16 * 4},
        {"name": "conv", "kind": "state", "layers": 6,
         "bytes": 6 * 3 * 3 * (128 + 2 * 2 * 16) * 4},
        {"name": "k", "kind": "depth", "layers": 2, "depth": 128,
         "bytes": 2 * 3 * 2 * 16 * 128 * 4},
        {"name": "v", "kind": "depth", "layers": 2, "depth": 128,
         "bytes": 2 * 3 * 2 * 16 * 128 * 4}]


def test_the_idle_split_tool_averages_the_spans_counters(toy):
    """`tools/engine_idle_split.py` prints, for each kind of `*_wait`
    span, the mean of each counter the engine's spans carry: here both
    states' bytes and the prefills' chunks, the cached positions read
    and the experts' load, from a served engine's own spans."""
    spec = importlib.util.spec_from_file_location(
        "engine_idle_split", os.path.join(ROOT, "tools",
                                          "engine_idle_split.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    eng = toy.engine(slots=2)
    profiler.start_profiler(state="CPU")
    try:
        futs = [eng.submit(p, n) for p, n in _requests(seed=9, new=6)[:3]]
        _drain(eng, futs)
    finally:
        profiler.stop_profiler(profile_path=None)
        eng.close()
    waits = profiler.spans("engine.")
    waits = [w for w in waits if w[0].endswith("_wait")]
    rows = tool.span_counters(waits)
    fills = [w[3] for w in waits if w[0] == "engine.prefill_wait"]
    assert rows["engine.prefill_wait"]["spans"] == len(fills) == 3
    assert rows["engine.prefill_wait"]["chunks"] == pytest.approx(
        sum(a["chunks"] for a in fills) / 3)
    step = rows["engine.decode_wait"]
    assert {"state_bytes", "live_full", "expert_tokens",
            "expert_layers"} <= set(step)
    assert step["state_bytes"] > 0 and step["expert_tokens"] > 0


def test_each_kind_of_layer_is_traced_once_a_shape(toy):
    """Six Mamba layers, five expert layers and two attention layers of a
    decode step are three nested calls, each traced once."""
    cfg, trees = toy.ncfg, toy.params.trees
    cache = cfg.cache_arrays(2, 128)
    jaxpr = jax.make_jaxpr(lambda c, t, p, a: cfg.decode(trees, c, t, p, a))(
        cache, jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
        jnp.ones(2, bool))
    bodies = ("_mamba_decode", "_experts", "_attention_decode")
    calls = [e for e in jaxpr.eqns if e.params.get("name") in bodies]
    assert [e.params["name"] for e in calls] == [
        {"M": bodies[0], "E": bodies[1], "*": bodies[2]}[k]
        for k in cfg.pattern]
    assert len({id(e.params["jaxpr"]) for e in calls}) == 3


def test_engine_through_the_kernels_serves_the_references_tokens(
        monkeypatch):
    """Mamba heads of 64 through both SSD kernels in the interpreter (2
    heads a row of lanes), a bucket of 32 and one of 64, chunks of 16."""
    _kernels_in_the_interpreter(monkeypatch)
    wide = Toy(mamba_head_dim=64, max_len=96)
    eng = wide.engine()
    rng = np.random.default_rng(8)
    reqs = [(rng.integers(0, 211, n).astype(np.int32), 6)
            for n in (9, 40, 2)]
    futs = [eng.submit(p, n) for p, n in reqs]
    _drain(eng, futs)
    eng.close()
    _assert_reference_tokens(wide, reqs, [f.result() for f in futs])
