"""Spans inside the decode engine and the reader, on two clocks (ISSUE
27): `profiler.RecordEvent` follows the jax.profiler session with no
flag, the engine's iteration is cut into phases, a request's way to its
first token is split, the spans lie in the xplane trace with the
`perf_counter_ns` reading that ties the clocks, and the programs and
kernels carry names.

Structure only: no test asserts a duration on a shared CPU.  Engines are
driven by `step()` (`auto_start=False`), so what ran is exact."""

import glob
import importlib.util
import os
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from engine_fakes import Gate, hold_steps, until
from paddle_tpu import monitor, profiler
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.reader import device_prefetch
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ["engine.sweep", "engine.admit", "engine.prefill_host",
          "engine.prefill_wait", "engine.prefill_book",
          "engine.decode_host", "engine.decode_wait", "engine.emit",
          "engine.telemetry"]


@pytest.fixture(autouse=True)
def _clean_state():
    monitor.disable()
    monitor.reset()
    profiler.reset_profiler()
    yield
    monitor.disable()
    monitor.reset()
    profiler.reset_profiler()


@pytest.fixture(scope="module")
def model():
    np.random.seed(27)
    return GPT(GPTConfig(vocab_size=97, hidden_size=48, num_layers=2,
                         num_heads=4, max_seq_len=32, dropout=0.0))


def _engine(model, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 32)
    kw.setdefault("buckets", (8, 16))
    kw.setdefault("watchdog_stall_s", 30.0)
    kw.setdefault("label", f"span_test_{len(kw)}_{id(kw) % 10000}")
    return DecodeEngine(model, config=DecodeConfig(**kw), auto_start=False)


def _serve(eng, n=4, max_new=5):
    """Submit `n` requests (more than slots, so one waits) and step the
    engine until all have resolved."""
    rng = np.random.default_rng(3)
    futs = [eng.submit(rng.integers(0, 97, size=4 + i), max_new + i)
            for i in range(n)]
    for _ in range(200):
        if all(f.done() for f in futs):
            return futs
        eng.step()
    raise AssertionError("engine did not drain")


@pytest.fixture
def traced(tmp_path):
    """Run `body()` under a jax.profiler session; returns the events of
    the xplane file's host plane whose names carry the prefix."""
    def run(body):
        tdir = str(tmp_path / f"trace{len(os.listdir(tmp_path))}")
        jax.profiler.start_trace(tdir)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        assert files, "the profiler left no trace"
        data = jax.profiler.ProfileData.from_file(files[0])
        return [e for plane in data.planes if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events
                if e.name.startswith(profiler.TRACE_PREFIX)]
    return run


# ---------------------------------------------------------------------
# off: nothing recorded, nothing opened
# ---------------------------------------------------------------------

def test_off_the_engine_and_the_reader_record_nothing(model, monkeypatch):
    class Refused:
        def __init__(self, *a, **kw):
            raise AssertionError("an annotation was opened with no "
                                 "profiler session running")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    eng = _engine(model)
    futs = _serve(eng)
    list(device_prefetch(iter([np.zeros(3)] * 3)))
    eng.close()
    assert all(f.result(timeout=0).size for f in futs)
    assert profiler.spans() == []
    assert profiler._all_events() == []


# ---------------------------------------------------------------------
# on: the iteration's phases
# ---------------------------------------------------------------------

def test_every_iteration_holds_its_phases_in_order(model, traced):
    eng = _engine(model)
    steps_run = []

    def body():
        _serve(eng)
        steps_run.append(eng.stats.decode_steps)
        assert eng.step() == 0         # idle: has to leave no span

    traced(body)
    eng.close()
    spans = profiler.spans("engine.")
    steps = [(s, e) for n, s, e, _ in spans if n == "engine.step"]
    phases = [(n, s, e) for n, s, e, _ in spans if n != "engine.step"]
    assert set(n for n, _, _ in phases) <= set(PHASES)
    assert len(steps) >= steps_run[0] > 0
    seen = 0
    for lo, hi in steps:
        inside = [(n, s, e) for n, s, e in phases if lo <= s and e <= hi]
        seen += len(inside)
        names = [n for n, _, _ in inside]
        # in the order of the list above (a prefill's three once for
        # each admitted request), disjoint, nothing twice but prefills
        assert names[:2] == ["engine.sweep", "engine.admit"]
        assert [PHASES.index(n) for n in names if "prefill" not in n] == \
            sorted(PHASES.index(n) for n in names if "prefill" not in n)
        pre = [n for n in names if "prefill" in n]
        assert pre == PHASES[2:5] * (len(pre) // 3)
        last_prefill = max([i for i, n in enumerate(names)
                            if "prefill" in n], default=1)
        assert all("prefill" in n for n in names[2:last_prefill + 1])
        for (_, _, e0), (_, s1, _) in zip(inside, inside[1:]):
            assert e0 <= s1
        if "engine.decode_wait" in names:
            assert names[names.index("engine.decode_wait") - 1:][:3] == [
                "engine.decode_host", "engine.decode_wait", "engine.emit"]
    assert seen == len(phases)         # no phase outside an iteration
    waits = [a for n, _, _, a in spans if n == "engine.decode_wait"]
    assert len(waits) == steps_run[0]
    assert all(1 <= a["active"] <= 3 for a in waits)
    # a model with one cache and no experts: the spans carry what they
    # always carried, and nothing of a second cache's
    assert all(set(a) == {"active", "ahead", "device_s", "behind_s",
                          "behind"} for a in waits)
    fills = [a for n, _, _, a in spans if n == "engine.prefill_wait"]
    assert fills and all(set(a) == {
        "bucket", "true_len", "slot", "rid", "queue_wait_s", "turnaround_s",
        "late", "device_s"} for a in fills)


def test_the_summary_lists_the_gpt_cache_under_its_own_names(model):
    eng = _engine(model)
    cache = eng.summary()["decode"]["cache"]
    eng.close()
    one = 2 * 3 * 4 * 12 * 32 * 4       # layers, slots, heads, d, depth
    assert cache == {
        "kind": "kv [layers, slots, heads, head_dim, max_len]",
        "bytes": 2 * one,
        "arrays": [{"name": "k", "kind": "depth", "layers": 2, "bytes": one,
                    "depth": 32},
                   {"name": "v", "kind": "depth", "layers": 2, "bytes": one,
                    "depth": 32}]}


def test_a_models_state_traffic_rides_the_wait_spans(traced):
    """A model whose cache is a state (`models/brumby.py`): every
    `engine.decode_wait` carries `state_bytes`, the active slots' states
    once each way, every `engine.prefill_wait` the slot's own and the
    `chunks` its scan walked, and the summary totals both."""
    from paddle_tpu.models import brumby

    cfg = brumby.BrumbyCfg(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, intermediate_size=48, rms_norm_eps=1e-6,
        rope_theta=1e6, max_seq_len=64, dtype="float32")
    params = brumby.BrumbyParams.from_flat(cfg, brumby.init_params(
        cfg, jax.random.PRNGKey(0), std=0.3, half_life=(2.0, 32.0)))
    eng = DecodeEngine(params, config=DecodeConfig(
        slots=3, max_len=64, buckets=(8, 32), label="spans_state"),
        auto_start=False)
    rng = np.random.default_rng(0)

    def body():
        futs = [eng.submit(rng.integers(0, 97, n), max_new_tokens=5)
                for n in (5, 20)]
        while not all(f.done() for f in futs):
            eng.step()

    traced(body)
    cache = eng.summary()["decode"]["cache"]
    eng.close()
    spans = profiler.spans("engine.")
    steps = [a for n, _, _, a in spans if n == "engine.decode_wait"]
    fills = [a for n, _, _, a in spans if n == "engine.prefill_wait"]
    slot = cfg.slot_state_bytes
    assert slot == 2 * 2 * (5 + 1) * 8 * 8 * 4
    assert [a["state_bytes"] for a in steps] == [2 * 2 * slot] * 4
    assert all(a["state_bytes"] == 2 * a["active"] * slot for a in steps)
    assert [(a["state_bytes"], a["chunks"]) for a in fills] \
        == [(slot, 1), (slot, 1)]
    assert all(isinstance(a["state_bytes"], int) for a in steps + fills)
    assert cache["state_bytes"] == sum(a["state_bytes"]
                                       for a in steps + fills)
    assert cache["chunks"] == 2


def test_a_grouped_models_walk_rides_the_decode_wait_spans(traced):
    """A model with window and full layers whose caches the decode
    kernel tiles (`models/afmoe.py`; heads of 64, a full cache 384 deep
    in tiles of 128 beside a ring of one tile of 128, as `gqa_tiling`
    names them): every `engine.decode_wait` carries `full_tiles` of
    `full_grid` and `window_tiles` of `window_grid`, the visits of one
    layer of each kind by the lengths the host holds, and the summary
    their totals; the second request crosses into its second tile of the
    full cache on the way."""
    from paddle_tpu.kernels.flash_attention import gqa_tiling
    from paddle_tpu.models import afmoe

    cfg = afmoe.AfmoeCfg.from_hf(dict(
        vocab_size=97, hidden_size=32, num_hidden_layers=2,
        num_dense_layers=1,
        layer_types=["sliding_attention", "full_attention"],
        num_attention_heads=2, num_key_value_heads=1, head_dim=64,
        sliding_window=128, intermediate_size=48, moe_intermediate_size=16,
        num_experts=2, num_experts_per_tok=1, route_scale=1.0,
        rms_norm_eps=1e-6, rope_theta=1e4, max_position_embeddings=384,
        dtype="float32"))
    assert gqa_tiling(1, 64, 384).tile == gqa_tiling(1, 64, 128).tile == 128
    assert cfg.cache_walk([1, 128, 129, 384], 4, 384) == {
        "full_tiles": 1 + 1 + 2 + 3, "full_grid": 4 * 3,
        "window_tiles": 4, "window_grid": 4}
    # depths the kernel does not tile are not walked
    assert cfg.cache_walk([1, 50], 4, 96) == {}
    params = afmoe.AfmoeParams.from_flat(
        cfg, afmoe.init_params(cfg, jax.random.PRNGKey(0)))
    eng = DecodeEngine(params, config=DecodeConfig(
        slots=2, max_len=384, buckets=(16, 128), label="spans_walk"),
        auto_start=False)
    rng = np.random.default_rng(0)
    sizes = (12, 126)

    def body():
        futs = [eng.submit(rng.integers(0, 97, n), max_new_tokens=5)
                for n in sizes]
        while not all(f.done() for f in futs):
            eng.step()

    traced(body)
    summary = eng.summary()["decode"]
    eng.close()
    steps = [a for n, _, _, a in profiler.spans("engine.decode_wait")]
    # a request's first token is its prefill's; decode step j reads its
    # prompt and j tokens
    want = [sum(-(-(n + j) // 128) for n in sizes) for j in range(1, 5)]
    assert want == [2, 2, 3, 3]
    assert [a["full_tiles"] for a in steps] == want
    assert [a["window_tiles"] for a in steps] == [2] * 4
    assert {(a["full_grid"], a["window_grid"]) for a in steps} == {(6, 2)}
    assert all(type(a["full_tiles"]) is int for a in steps)
    cache = summary["cache"]
    assert (cache["full_tiles"], cache["full_grid"]) == (sum(want), 6 * 4)
    assert (cache["window_tiles"], cache["window_grid"]) == (2 * 4, 2 * 4)
    assert summary["decode_steps"] == len(want)


def test_state_traffic_is_counted_in_python_ints():
    """The published depth's step (16 slots x 40 layers of 34.6 MB each
    way) passes 2**31: the engine counts on the host, in Python ints."""
    from paddle_tpu.serving.decode import _state_traffic

    slot = 40 * 8 * 66 * 128 * 128 * 4
    step = _state_traffic(slot, {}, np.ones(16, bool))
    assert step == {"state_bytes": 2 * 16 * slot} and step["state_bytes"] \
        > 2 ** 31 and type(step["state_bytes"]) is int
    assert _state_traffic(slot, {"chunks": np.int32(32)}) \
        == {"state_bytes": slot, "chunks": 32}
    assert _state_traffic(0, {}, np.ones(4, bool)) == {}


def test_first_token_split_adds_up_to_ttft(model, traced):
    """Under a clock that ticks on every reading, `queue_wait_s` +
    `turnaround_s` is exactly the `ttft_s` that `note_prefill` got."""
    ticks = iter(np.arange(100.0, 1e6, 0.125))
    eng = _engine(model, clock=lambda: float(next(ticks)), slots=1,
                  buckets=(8,))
    got = []
    note = eng.stats.note_prefill

    def note_prefill(ttft_s=None, now=None):
        got.append(ttft_s)
        return note(ttft_s=ttft_s, now=now)

    eng.stats.note_prefill = note_prefill
    traced(lambda: _serve(eng, n=3, max_new=2))
    eng.close()
    attrs = [a for n, _, _, a in profiler.spans("engine.prefill_wait")]
    assert len(attrs) == len(got) == 3
    assert sorted(a["rid"] for a in attrs) == [1, 2, 3]
    for a, ttft_s in zip(attrs, got):
        assert a["bucket"] == 8 and a["slot"] == 0
        assert a["queue_wait_s"] > 0 and a["turnaround_s"] > 0
        assert a["queue_wait_s"] + a["turnaround_s"] == ttft_s
    # one slot: the later requests waited for the earlier ones
    assert attrs[2]["queue_wait_s"] > attrs[0]["queue_wait_s"]


# ---------------------------------------------------------------------
# the loop thread: one step ahead, listening while the device works
# ---------------------------------------------------------------------

def test_loop_listens_until_its_deadline_and_counts_who_came_in_time(
        model, traced):
    """The loop thread under an injected clock and decode steps whose
    answers the test releases.  Step 1 takes one second of that clock,
    so the loop expects step 2 to: it listens (`engine.listen_wait`)
    for a second after step 1's emit.  A request submitted then is
    prefilled behind step 2, which was running at its submission: in
    time.  One submitted after step 3 went out, while step 2 still
    runs, lands behind step 3: late.  The counter adds up, and the
    spans carry the same two facts."""
    class Clock:
        t = 100.0

        def __call__(self):
            return self.t

    clk, gate = Clock(), Gate()
    eng = _engine(model, clock=clk, slots=3, buckets=(8,))
    hold_steps(eng, gate)
    rng = np.random.default_rng(32)
    prompts = [rng.integers(0, 97, size=5) for _ in range(3)]
    futs = []

    def answered():
        return eng.stats.decode_steps

    def body():
        eng.start()
        futs.append(eng.submit(prompts[0], 12))
        until(lambda: gate.held == 2, what="steps 1 and 2 in flight")
        clk.t += 1.0
        gate.release()                     # step 1 answers, a second on
        until(lambda: answered() == 1, what="step 1 emitted")
        # the loop listens: step 2 has a second to run by its measure
        futs.append(eng.submit(prompts[1], 10))
        until(lambda: eng.summary()["queue_depth"] == 0,
              what="request 2 admitted")
        assert gate.held == 2              # step 3 waits for the deadline
        clk.t += 1.0                       # ... which passes
        with eng._cond:
            eng._cond.notify_all()
        until(lambda: gate.held == 3, what="step 3 enqueued")
        futs.append(eng.submit(prompts[2], 8))   # step 2 still runs
        clk.t += 1.0
        gate.open()
        for f in futs:
            f.result(timeout=60)
        eng.close()

    traced(body)
    look = eng.summary()["decode"]["lookahead"]
    dec = eng.summary()["decode"]
    assert look["steps"] == dec["decode_steps"] and look["ahead"] >= 2
    assert (look["in_time"], look["late"]) == (2, 1)
    assert look["in_time"] + look["late"] == dec["prefill_steps"] == 3
    spans = profiler.spans("engine.")
    pre = {a["rid"]: a for n, _, _, a in spans
           if n == "engine.prefill_wait"}
    assert [pre[r]["late"] for r in (1, 2, 3)] == [False, False, True]
    waits = [a for n, _, _, a in spans if n == "engine.decode_wait"]
    assert len(waits) == dec["decode_steps"]
    assert sum(a["ahead"] for a in waits) == look["ahead"]
    assert not waits[0]["ahead"] and waits[1]["ahead"] and waits[2]["ahead"]
    # the listening is a span of its own, in which the host only waits;
    # an iteration that emitted has its emit inside its engine.step
    names = {n for n, _, _, _ in spans}
    assert "engine.listen_wait" in names
    assert names <= set(PHASES) | {"engine.step", "engine.listen_wait"}
    steps = [(s, e) for n, s, e, _ in spans if n == "engine.step"]
    for n, s, e, _ in spans:
        if n != "engine.step":
            assert any(lo <= s and e <= hi for lo, hi in steps), n
    emits = [s for n, s, _, _ in spans if n == "engine.emit"]
    assert len(emits) == dec["decode_steps"]


# ---------------------------------------------------------------------
# the same spans in the xplane trace, and the two clocks tied
# ---------------------------------------------------------------------

def test_xplane_holds_the_spans_with_their_clock_reading(model, traced):
    eng = _engine(model)

    def body():
        _serve(eng)
        list(device_prefetch(iter([np.zeros(3)] * 4)))

    events = traced(body)
    eng.close()
    spans = profiler.spans()
    # and `host.gc` where a garbage collection ran in the session
    assert {n.split(".")[0] for n, _, _, _ in spans} - {"host"} == \
        {"engine", "reader"}
    in_trace = sorted((e.name[len(profiler.TRACE_PREFIX):],
                       int(dict(e.stats)["pc_ns"])) for e in events)
    assert in_trace == sorted((n, s) for n, s, _, _ in spans)
    stats = {e.name: dict(e.stats) for e in events}
    assert {"bucket", "slot", "rid", "queue_wait_s", "turnaround_s"} <= \
        set(stats[profiler.TRACE_PREFIX + "engine.prefill_wait"])
    assert "active" in stats[profiler.TRACE_PREFIX + "engine.decode_wait"]
    # one offset for the session, to well under a decode step
    offsets = [profiler.trace_clock_offset_ns([e]) for e in events]
    assert max(offsets) - min(offsets) < 2e6
    assert profiler.trace_clock_offset_ns(events) == \
        statistics.median(offsets)
    assert profiler.trace_clock_offset_ns([]) is None
    # the span store's perf_counter_ns times land on the trace's clock
    offset = profiler.trace_clock_offset_ns(events)
    by_start = {int(dict(e.stats)["pc_ns"]): e for e in events}
    for n, s, e, _ in spans:
        assert abs(by_start[s].start_ns - (s + offset)) < 2e6


def test_a_second_session_does_not_return_the_first_ones_spans(model,
                                                               traced):
    eng = _engine(model)
    traced(lambda: _serve(eng, n=2))
    first = profiler.spans()
    assert first and profiler.spans() == first   # they outlive stop_trace
    traced(lambda: list(device_prefetch(iter([np.zeros(3)] * 2))))
    eng.close()
    second = profiler.spans()
    assert second and {n.split(".")[0] for n, _, _, _ in second} - \
        {"host"} == {"reader"}
    assert profiler.spans("engine.") == []
    profiler.reset_profiler()
    assert profiler.spans() == []


def test_start_profiler_sessions_still_record(model):
    """The older switch: `start_profiler` without a jax trace records to
    the store, attrs and all, and opens no annotation."""
    profiler.start_profiler(state="CPU")
    try:
        with profiler.RecordEvent("outer", k=1):
            with profiler.RecordEvent("inner"):
                pass
    finally:
        table = profiler.stop_profiler(profile_path=None)
    assert set(table) - {profiler.GC_SPAN} == {"outer", "inner"}
    (n0, s0, e0, a0), (n1, s1, e1, a1) = [
        s for s in profiler.spans() if s[0] != profiler.GC_SPAN]
    assert (n0, a0, n1, a1) == ("outer", {"k": 1}, "inner", {})
    assert s0 <= s1 <= e1 <= e0
    from paddle_tpu.monitor.trace import host_span_events

    rows = host_span_events([e for e in profiler._all_events()
                             if e["name"] != profiler.GC_SPAN])
    assert rows[0]["args"] == {"depth": 0, "k": 1}


# ---------------------------------------------------------------------
# the reader
# ---------------------------------------------------------------------

def test_reader_spans_one_pair_a_batch(traced):
    batches = [{"x": np.full((2, 3), i, np.float32)} for i in range(5)]
    out = []
    traced(lambda: out.extend(device_prefetch(iter(batches), size=2)))
    assert [int(b["x"][0, 0]) for b in out] == list(range(5))
    names = [n for n, _, _, _ in profiler.spans("reader.")]
    assert names.count("reader.device_put") == 5
    # and the looks at the source that found it exhausted
    assert names.count("reader.source") == 5 + 2
    spans = profiler.spans("reader.")
    for (_, _, e0, _), (_, s1, _, _) in zip(spans, spans[1:]):
        assert e0 <= s1


# ---------------------------------------------------------------------
# what the benchmark leans on
# ---------------------------------------------------------------------

def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_benchmarks_recording_stats_still_graft(model):
    driver = _load(os.path.join(ROOT, "benchmarks", "drivers",
                                "serve_closed.py"), "serve_closed_driver")
    t = iter(np.arange(0.0, 1e6, 0.5))
    clock = lambda: float(next(t))  # noqa: E731
    eng = _engine(model, clock=clock)
    eng.stats.__class__ = driver.recording_stats(type(eng.stats))
    eng.stats.start_recording(eng, clock)
    futs = _serve(eng)
    eng.close()
    stats = eng.stats
    tokens = sum(f.result(timeout=0).size for f in futs)
    assert len(stats.first_tokens) == len(futs)
    assert len(stats.gaps) == tokens - len(futs)
    assert sum(s[2] for s in stats.decode_steps_at) == len(stats.gaps)
    assert len(stats.resolved_at) == len(futs)
    assert all(now is not None for now, _ in stats.first_tokens)


def test_no_telemetry_record_is_built_while_telemetry_is_off(model):
    eng = _engine(model)
    built = []
    to_record = eng.stats.to_record

    def counting():
        built.append(1)
        return to_record()

    eng.stats.to_record = counting
    futs = [eng.submit(np.arange(4), 24) for _ in range(9)]
    while not all(f.done() for f in futs):
        eng.step()
    assert eng.stats.decode_steps >= 64    # past the 64th step's record
    assert eng.emit_telemetry() is None
    assert built == []
    monitor.enable()
    try:
        rec = eng.emit_telemetry()
    finally:
        monitor.disable()
    assert built == [1] and rec["kind"] == "serving"
    eng.close()


# ---------------------------------------------------------------------
# names on the device
# ---------------------------------------------------------------------

def test_the_engines_programs_are_jitted_from_named_functions(
        model, monkeypatch):
    names = []
    jit = jax.jit

    def recording_jit(fn, *a, **kw):
        names.append(fn.__name__)
        return jit(fn, *a, **kw)

    monkeypatch.setattr(jax, "jit", recording_jit)
    eng = _engine(model, prewarm=False)
    monkeypatch.setattr(jax, "jit", jit)
    eng.close()
    assert names == ["decode_step", "prefill_b8", "prefill_b16"]
    # jax names a program after the function it was jitted from
    step = jit(_named("decode_step"))
    assert "module @jit_decode_step" in step.lower(1.0).as_text()


def _named(name):
    def fn(x):
        return x

    fn.__name__ = name
    return fn


def _kernel_jaxprs():
    from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                    flash_attention_fwd,
                                                    flash_decode,
                                                    flash_decode_resident,
                                                    gqa_decode, kv_append)
    from paddle_tpu.kernels.layer_norm import layer_norm_pallas
    from paddle_tpu.kernels.topk_threshold import dgc_topk_mask_pallas

    f32 = jnp.float32
    q = jax.ShapeDtypeStruct((1, 2, 128, 64), f32)

    def total(fn):
        return lambda *a: jnp.sum(fn(*a).astype(f32))

    def causal(q, k, v):
        return flash_attention(q, k, v, causal=True)

    ln = [jax.ShapeDtypeStruct(s, f32) for s in ((64, 128), (128,), (128,))]
    cache = jax.ShapeDtypeStruct((2, 2, 2, 64, 128), f32)
    new = jax.ShapeDtypeStruct((2, 2, 64), f32)
    layer = jax.ShapeDtypeStruct((), jnp.int32)
    per_slot = jax.ShapeDtypeStruct((2,), jnp.int32)

    # the decode engine's pair on its resident cache
    def engine_layer(q, k_cache, v_cache, k_new, v_new, layer, pos):
        k_cache, v_cache = kv_append(k_cache, v_cache, k_new, v_new,
                                     layer, pos)
        return flash_decode_resident(q, k_cache, v_cache, layer, pos + 1)

    return {
        "engine": str(jax.make_jaxpr(engine_layer)(
            jax.ShapeDtypeStruct((2, 2, 1, 64), f32), cache, cache, new,
            new, layer, per_slot)),
        # grouped queries over the same cache, and a windowed prefill
        "grouped": str(jax.make_jaxpr(gqa_decode)(
            jax.ShapeDtypeStruct((2, 8, 1, 64), f32), new, new, cache,
            cache, layer, per_slot, per_slot)),
        "windowed": str(jax.make_jaxpr(
            lambda q, k, v: flash_attention_fwd(q, k, v, window=128))(
            jax.ShapeDtypeStruct((1, 4, 256, 64), f32),
            jax.ShapeDtypeStruct((1, 2, 256, 64), f32),
            jax.ShapeDtypeStruct((1, 2, 256, 64), f32))),
        "flash": str(jax.make_jaxpr(jax.grad(total(causal), (0, 1, 2)))(
            q, q, q)),
        "decode": str(jax.make_jaxpr(flash_decode)(
            jax.ShapeDtypeStruct((2, 2, 1, 64), f32),
            jax.ShapeDtypeStruct((2, 2, 128, 64), f32),
            jax.ShapeDtypeStruct((2, 2, 128, 64), f32),
            jax.ShapeDtypeStruct((2,), jnp.int32))),
        "layer_norm": str(jax.make_jaxpr(jax.grad(
            total(layer_norm_pallas), (0, 1, 2)))(*ln)),
        "topk": str(jax.make_jaxpr(
            lambda g: dgc_topk_mask_pallas(g, 0.99))(
            jax.ShapeDtypeStruct((64, 128), f32))),
        "retention": _retention_jaxpr(),
    }


def _retention_jaxpr():
    """A prefill and a decode step on one resident recurrent state."""
    from paddle_tpu.kernels.retention import (retention_decode,
                                              retention_prefill)

    f32 = jnp.float32

    def both(q, k, v, log_g, state, norm):
        _, state, norm = retention_prefill(
            q, k, v, log_g, 5, state, norm, 0, 1, use_kernel=True, chunk=8)
        return retention_decode(q[:2], k[:2], v[:2], log_g[:2], state, norm,
                                0, jnp.ones(2, bool), use_kernel=True)

    return str(jax.make_jaxpr(both)(
        jax.ShapeDtypeStruct((8, 2, 128), f32),
        jax.ShapeDtypeStruct((8, 1, 128), f32),
        jax.ShapeDtypeStruct((8, 1, 128), f32),
        jax.ShapeDtypeStruct((8, 1), f32),
        jax.ShapeDtypeStruct((1, 2, 1, 128, 65 * 128), f32),
        jax.ShapeDtypeStruct((1, 2, 1, 128, 128), f32)))


@pytest.fixture(scope="module")
def kernel_jaxprs():
    return _kernel_jaxprs()


@pytest.mark.parametrize("where,kernel", [
    ("flash", "flash_fwd"), ("flash", "flash_dq"), ("flash", "flash_dkv"),
    ("decode", "flash_decode"), ("engine", "kv_append"),
    ("engine", "flash_decode"), ("grouped", "gqa_decode"),
    ("windowed", "flash_fwd"), ("layer_norm", "layer_norm_fwd"),
    ("layer_norm", "layer_norm_bwd"), ("topk", "topk_threshold"),
    ("retention", "retention_prefill"), ("retention", "retention_decode")])
def test_every_pallas_call_has_its_name(kernel_jaxprs, where, kernel):
    assert f"name={kernel}\n" in kernel_jaxprs[where] \
        or f"name={kernel} " in kernel_jaxprs[where]


# ---------------------------------------------------------------------
# the tool that lays the spans against the device's idle gaps
# ---------------------------------------------------------------------

def test_idle_split_attributes_gaps_to_phases():
    tool = _load(os.path.join(ROOT, "tools", "engine_idle_split.py"),
                 "engine_idle_split")
    # device busy 10-40 and 60-90 of a window 0-100 (ns): idle 0-10,
    # 40-60, 90-100
    ops = [(10, 40), (60, 90)]
    spans = [("engine.step", 35, 95), ("engine.decode_wait", 35, 45),
             ("engine.emit", 45, 55), ("engine.decode_host", 57, 62),
             ("engine.decode_wait", 62, 95)]
    table, idle_s, outside_s = tool.split(spans, ops, (0, 100))
    rows = {n: (c, round(h * 1e9), round(i * 1e9)) for n, c, h, i in table}
    assert round(idle_s * 1e9) == 40
    assert rows["engine.emit"] == (1, 10, 10)
    assert rows["engine.decode_wait"] == (2, 43, 10)
    assert rows["engine.decode_host"] == (1, 5, 3)
    assert rows["engine.step outside its phases"] == (1, 2, 2)
    assert round(outside_s * 1e9) == 15   # 0-10 and 95-100
    # the walk's counters of the summary's `cache`, a pair a name
    assert tool.cache_walk({
        "kind": "kv", "bytes": 1, "arrays": [], "full_tiles": 9,
        "full_grid": 19, "window_tiles": 4, "window_grid": 4,
        "state_bytes": 7}) == {"full": (9, 19), "window": (4, 4)}
    assert tool.cache_walk({"latent_tiles": 5, "latent_grid": 8}) \
        == {"latent": (5, 8)}


def test_idle_split_checks_the_engines_device_times_against_the_trace():
    """The clock check of ISSUE 38 on made-up readings (ns on the
    trace's clock; the store's spans 1,000 ns behind it): the decode
    steps' median `device_s` against the median `jit_decode_step` run,
    each `jit_prefill_b*` run in the window paired with the first prefill
    answered after it, and the idle time under `host.gc`."""
    tool = _load(os.path.join(ROOT, "tools", "engine_idle_split.py"),
                 "engine_idle_split")
    ms = 1_000_000
    programs = {
        "jit_decode_step": [(0, 10 * ms), (13 * ms, 23 * ms),
                            (23 * ms, 34 * ms), (95 * ms, 120 * ms)],
        "jit_prefill_b64": [(10 * ms, 13 * ms)],
        # cut by the window's end: left out, as its flight is
        "jit_prefill_b128": [(96 * ms, 104 * ms)]}

    def wait(name, end, **attrs):
        return (name, end - 1000 - ms, end - 1000, attrs)

    waits = [
        wait("engine.decode_wait", 10.2 * ms, device_s=0.0102,
             behind_s=0.0, behind=0),
        wait("engine.prefill_wait", 13.1 * ms, device_s=0.0029),
        wait("engine.decode_wait", 23.1 * ms, device_s=0.0100,
             behind_s=0.0029, behind=1),
        wait("engine.decode_wait", 34.1 * ms, device_s=0.0110,
             behind_s=0.0, behind=0),
        # answered after the window: not counted
        wait("engine.prefill_wait", 104.5 * ms, device_s=0.008),
        # the parent's span, without the attribute
        wait("engine.prefill_wait", 50 * ms)]
    check = tool.clock_check(waits, programs, 1000, (0, 100 * ms))
    assert check["decode"]["flights"] == 3 and check["decode"]["runs"] == 3
    assert check["decode"]["device_s_p50_ms"] == pytest.approx(10.2)
    assert check["decode"]["run_p50_ms"] == pytest.approx(10.0)
    assert check["prefill"]["paired"] == check["prefill"]["runs"] == 1
    assert check["prefill"]["ratio"] == pytest.approx(2.9 / 3.0)
    assert check["behind_ms"]["max"] == pytest.approx(2.9)
    assert check["behind_ms"]["p50"] == 0.0
    assert check["behind"] == {"steps": 1, "prefills": 1, "max": 1}
    assert tool.clock_check([], programs, 0, (0, 100 * ms)) == {}
    # idle 34-95 ms: two collections, 8 ms of it under them
    assert tool.idle_under_all(
        [(30 * ms, 40 * ms), (60 * ms, 62 * ms)],
        [(a, b) for runs in programs.values() for a, b in runs],
        (0, 100 * ms)) == (2, pytest.approx(0.012), pytest.approx(0.008))
