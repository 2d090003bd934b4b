"""Continuous-batching decode engine tests (ISSUE 17): token-exact
parity vs models.generate() (dense + MoE, including a request that
joins mid-decode into a previously-released slot), the two-compile
steady state through the compile ledger, per-token budget shedding /
expiry with an injectable clock, watchdog escalation of a wedged
decode step (engine broken, ledger balanced), the single-query flash
decode kernel, the fuse pass's decode-shape dispatch, and the
DecodeStats / exporter / report observability surface.

Determinism strategy: scheduling tests drive the engine synchronously
(auto_start=False + step()) so slot composition is exact; budget tests
use a fake clock; the hang test blocks on a threading.Event the test
releases (no wall-clock guesses)."""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from engine_fakes import Gate, hold_prefills, hold_steps, until
from paddle_tpu import monitor
from paddle_tpu.models import generate as G
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.resilience import RetryPolicy, faultinject
from paddle_tpu.serving import (DeadlineExceeded, QueueFullError,
                                ServingClosedError, WatchdogStall)
from paddle_tpu.serving.decode import (DecodeConfig, DecodeEngine,
                                       EngineBrokenError,
                                       default_prompt_buckets)
from paddle_tpu.serving.stats import DecodeStats, exact_percentile


# ---------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _clean_state():
    faultinject.disarm()
    monitor.disable()
    monitor.reset()
    yield
    faultinject.disarm()
    monitor.disable()
    monitor.reset()


@pytest.fixture(scope="module")
def dense_model():
    np.random.seed(11)
    cfg = GPTConfig(vocab_size=97, hidden_size=48, num_layers=3,
                    num_heads=4, max_seq_len=32, dropout=0.0)
    return GPT(cfg)


@pytest.fixture(scope="module")
def moe_model():
    np.random.seed(12)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=24, num_experts=4,
                    moe_top_k=2, moe_capacity_factor=8.0)
    m = GPT(cfg)
    # sharpen the router so expert choice is decisive (capacity 8.0
    # never binds -> generate()'s own prefill is drop-free and the
    # engine's drop-free decode routing matches it exactly)
    for blk in m.blocks:
        blk.moe.wg.set_value(np.asarray(blk.moe.wg.value) * 10.0)
    return m


def _engine(model, clock=time.monotonic, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("max_len", 32)
    kw.setdefault("buckets", (8, 16))
    kw.setdefault("watchdog_stall_s", 30.0)
    kw.setdefault("label", f"dec_test_{id(model) % 10000}_{time.time_ns() % 100000}")
    auto = kw.pop("auto_start", False)
    return DecodeEngine(model, config=DecodeConfig(clock=clock, **kw),
                        auto_start=auto)


def _drain(eng, futs, max_steps=200):
    for _ in range(max_steps):
        if all(f.done() for f in futs):
            return
        eng.step()
    raise AssertionError("engine did not drain")


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------
# token-exact parity
# ---------------------------------------------------------------------

def test_dense_parity_and_midstream_slot_refill(dense_model):
    """Slot-decoded tokens are TOKEN-EXACT vs generate() (greedy),
    with heterogeneous prompt lengths and max_new across slots; a
    request submitted after a short one finishes joins mid-decode into
    the RELEASED slot and is exact too (the prefill overwrote the
    previous tenant's cache region)."""
    eng = _engine(dense_model)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, size=n) for n in (5, 7, 3)]
    futs = [eng.submit(p, n) for p, n in zip(prompts, (9, 3, 6))]
    # run until the short request frees its slot but others are live
    for _ in range(200):
        eng.step()
        if futs[1].done():
            break
    assert futs[1].done() and not futs[0].done()
    # join mid-decode: must land in a previously-used slot (all three
    # slots have been written by earlier tenants)
    late = rng.integers(0, 97, size=12)
    f_late = eng.submit(late, 7)
    _drain(eng, futs + [f_late])
    for p, n, f in zip(prompts + [late], (9, 3, 6, 7),
                       futs + [f_late]):
        ref = np.asarray(G.generate(dense_model, p[None, :],
                                    max_new_tokens=n))[0]
        assert np.array_equal(f.result(timeout=0), ref)
    s = eng.summary()
    assert s["outcomes"]["completed"] == 4
    assert s["requests"] == sum(s["outcomes"].values())
    eng.close()


def test_moe_parity_threaded(moe_model):
    """MoE configs decode token-exact through the engine too (drop-free
    routing: per-token expert choice is independent of slot cohort),
    with the loop thread scheduling."""
    eng = _engine(moe_model, slots=2, max_len=24, buckets=(8,),
                  auto_start=True)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 64, size=n) for n in (4, 6, 5)]
    futs = [eng.submit(p, 5) for p in prompts]
    for p, f in zip(prompts, futs):
        ref = np.asarray(G.generate(moe_model, p[None, :],
                                    max_new_tokens=5))[0]
        assert np.array_equal(f.result(timeout=60), ref)
    eng.close()
    s = eng.summary()
    assert s["outcomes"]["completed"] == 3
    assert s["requests"] == sum(s["outcomes"].values())


def test_eos_early_stop(dense_model):
    """An eos_id request stops the slot at the eos token (inclusive)
    and matches generate()'s output up to that point."""
    rng = np.random.default_rng(5)
    p = rng.integers(0, 97, size=6)
    full = np.asarray(G.generate(dense_model, p[None, :],
                                 max_new_tokens=10))[0]
    eos = int(full[3])        # force a stop after 4 tokens
    eng = _engine(dense_model, buckets=(8,))
    f = eng.submit(p, 10, eos_id=eos)
    _drain(eng, [f])
    got = f.result(timeout=0)
    stop = int(np.argmax(full == eos)) + 1
    assert np.array_equal(got, full[:stop])
    eng.close()


# ---------------------------------------------------------------------
# the loop thread, one decode step ahead of its host (ISSUE 32)
# ---------------------------------------------------------------------

def _lookahead_adds_up(summary):
    dec = summary["decode"]
    look = dec["lookahead"]
    assert look["steps"] == dec["decode_steps"]
    assert 0 <= look["ahead"] <= look["steps"]
    assert look["in_time"] + look["late"] == dec["prefill_steps"]
    return look


@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_loop_thread_serves_generates_tokens_with_joins_and_leaves(
        kind, dense_model, moe_model):
    """With the loop thread running one step ahead (every step's answer
    comes 10 ms after its launch, so the next is always enqueued before
    it is read), requests of mixed lengths that queue for a slot, leave
    and are followed by later arrivals emit token for token what
    generate() emits."""
    model, vocab = {"dense": (dense_model, 97), "moe": (moe_model, 64)}[kind]
    eng = _engine(model, slots=2, max_len=24, buckets=(8,))
    hold_steps(eng, Gate(delay_s=0.01))
    eng.start()
    rng = np.random.default_rng(32)
    prompts = [rng.integers(0, vocab, size=n) for n in (4, 7, 3, 6, 5, 8)]
    news = (9, 2, 6, 1, 7, 4)
    futs = [eng.submit(p, n) for p, n in zip(prompts[:4], news[:4])]
    futs[1].result(timeout=60)             # a slot has been released
    futs += [eng.submit(p, n) for p, n in zip(prompts[4:], news[4:])]
    for p, n, f in zip(prompts, news, futs):
        ref = np.asarray(G.generate(model, p[None, :],
                                    max_new_tokens=n))[0]
        assert np.array_equal(f.result(timeout=60), ref)
    eng.close()
    s = eng.summary()
    assert s["outcomes"]["completed"] == 6
    assert s["requests"] == sum(s["outcomes"].values())
    look = _lookahead_adds_up(s)
    # the host is never 10 ms behind: but for the steps that found the
    # queue empty (the first, one after a pause), they went out ahead
    assert look["ahead"] >= look["steps"] // 2 > 0


def test_eos_gains_no_token_from_the_step_queued_behind_it(dense_model):
    """A request that reaches its eos in step n is still in the
    snapshot of step n+1, which was enqueued before step n was read:
    it gains nothing from it, and the next tenant of its slot (prefilled
    behind that step) is exact."""
    rng = np.random.default_rng(5)
    p1, p2 = rng.integers(0, 97, size=6), rng.integers(0, 97, size=9)
    full = np.asarray(G.generate(dense_model, p1[None, :],
                                 max_new_tokens=10))[0]
    eos = int(full[3])
    stop = int(np.argmax(full == eos)) + 1
    eng = _engine(dense_model, slots=1, buckets=(8, 16))
    hold_steps(eng, Gate(delay_s=0.01))
    eng.start()
    f1 = eng.submit(p1, 10, eos_id=eos)
    f2 = eng.submit(p2, 5)                 # waits for the one slot
    assert np.array_equal(f1.result(timeout=60), full[:stop])
    ref2 = np.asarray(G.generate(dense_model, p2[None, :],
                                 max_new_tokens=5))[0]
    assert np.array_equal(f2.result(timeout=60), ref2)
    eng.close()
    s = eng.summary()
    look = _lookahead_adds_up(s)
    assert look["ahead"] >= 1 and stop < 10
    # the step behind the eos ran for nothing and emitted nothing
    assert s["decode"]["tokens_total"] == (stop - 1) + 4


def test_step_by_hand_answers_everything_before_it_returns(dense_model):
    """`step()` runs the same primitives serially: nothing is in flight
    when it returns, no step ran ahead, and an admission is never late
    while a slot is free."""
    eng = _engine(dense_model, buckets=(8,))
    rng = np.random.default_rng(33)
    futs = [eng.submit(rng.integers(0, 97, size=5), 4) for _ in range(2)]
    assert eng.step() == 3                 # two prefills, one decode step
    assert not eng._flights and not eng._prefilling
    assert eng.summary()["in_flight"] == 0
    assert [len(r.tokens) for r in eng._slot_req if r is not None] == [2, 2]
    _drain(eng, futs)
    look = _lookahead_adds_up(eng.summary())
    assert look["ahead"] == 0 and look["late"] == 0
    eng.close()


def test_times_are_read_once_the_answer_is_on_the_host(dense_model):
    """A first token's time and every gap include the device's time
    for the program (an answer that comes 50 ms after the launch shows
    in the TTFT and in the inter-token gaps the stats report)."""
    eng = _engine(dense_model, slots=1, buckets=(8,))
    gate = Gate(delay_s=0.05)
    hold_prefills(eng, gate)
    hold_steps(eng, gate)
    rng = np.random.default_rng(34)
    f = eng.submit(rng.integers(0, 97, size=5), 3)
    _drain(eng, [f])
    eng.close()
    assert min(eng.stats.ttft_samples()) >= 0.05
    gaps = eng.stats.token_latency_samples()
    assert len(gaps) == 2 and min(gaps) >= 0.05
    assert eng.summary()["latency"]["max_ms"] >= 150.0


def test_budget_expiring_under_two_programs_in_flight_resolves_once(
        dense_model):
    """A request whose budget passes while two decode steps carry it
    (one running, one queued behind) is resolved 'expired' once; the
    tokens those steps bring are dropped, a later step kills its slot,
    and the next tenant decodes token-exact."""
    clk = FakeClock()
    eng = _engine(dense_model, clock=clk, slots=1, buckets=(8,))
    gate = Gate()
    hold_steps(eng, gate)
    eng.start()
    rng = np.random.default_rng(35)
    p1, p2 = rng.integers(0, 97, size=5), rng.integers(0, 97, size=6)
    f1 = eng.submit(p1, 8, token_budget_s=0.5)
    until(lambda: gate.held == 2, what="two decode steps in flight")
    assert eng.summary()["in_flight"] == 2 and not f1.done()
    clk.advance(1.0)
    assert isinstance(f1.exception(timeout=30), DeadlineExceeded)
    f2 = eng.submit(p2, 4)
    gate.open()
    ref = np.asarray(G.generate(dense_model, p2[None, :],
                                max_new_tokens=4))[0]
    assert np.array_equal(f2.result(timeout=60), ref)
    eng.close()
    s = eng.summary()
    assert s["outcomes"]["expired"] == 1 and s["outcomes"]["completed"] == 1
    assert s["requests"] == sum(s["outcomes"].values()) == 2
    assert s["pending"] == 0 and s["in_flight"] == 0
    _lookahead_adds_up(s)
    # the two steps that carried the expired request emitted nothing
    assert s["decode"]["tokens_total"] == 3


def test_a_wedged_queued_step_breaks_the_engine_once_for_everyone(
        dense_model, tmp_path):
    """Step n+1, queued behind step n, never answers: the watchdog has
    tracked it since its enqueue, the engine breaks, and the resident
    request, the one whose prefill is in flight and the queued one are
    each resolved exactly once."""
    old = fluid.get_flags("FLAGS_flight_recorder_dir")
    fluid.set_flags({"FLAGS_flight_recorder_dir": str(tmp_path)})
    steps, prefills = Gate(), Gate()
    try:
        eng = _engine(dense_model, slots=2, buckets=(8,),
                      watchdog_stall_s=1.0, watchdog_poll_s=0.02,
                      retry_policy=None)
        hold_steps(eng, steps)
        hold_prefills(eng, prefills)
        eng.start()
        rng = np.random.default_rng(36)
        f1 = eng.submit(rng.integers(0, 97, size=4), 8)
        prefills.release()                 # the first token of request 1
        until(lambda: steps.held == 2, what="two decode steps in flight")
        f2 = eng.submit(rng.integers(0, 97, size=5), 8)
        steps.release()                    # step 1 answers; step 2 never
        until(lambda: steps.held == 3, what="the third step enqueued")
        f3 = eng.submit(rng.integers(0, 97, size=6), 8)   # no slot: queued
        assert eng.summary()["in_flight"] == 3    # step, prefill, step
        errs = [f.exception(timeout=30) for f in (f1, f2, f3)]
        assert isinstance(errs[0], WatchdogStall)     # resident
        assert isinstance(errs[1], WatchdogStall)     # prefill in flight
        assert isinstance(errs[2], EngineBrokenError)  # queued
        with pytest.raises(EngineBrokenError):
            eng.submit(rng.integers(0, 97, size=4), 2)
        s = eng.summary()
        assert s["outcomes"]["stalled"] == 2
        assert s["outcomes"]["cancelled"] == 1
        assert s["requests"] == sum(s["outcomes"].values()) == 3
        assert s["pending"] == 0 and s["in_flight"] == 0
        assert s["watchdog_stalls"] >= 1
    finally:
        steps.open()
        prefills.open()
        fluid.set_flags(old)
    eng.close()


# ---------------------------------------------------------------------
# compile discipline
# ---------------------------------------------------------------------

def test_two_compile_steady_state(dense_model):
    """Steady state compiles exactly once per program: 1 decode step +
    1 prefill per bucket, all at prewarm; joins/leaves/refills after
    that add ZERO compile-ledger events."""
    monitor.reset()
    monitor.enable()
    eng = _engine(dense_model, label="dec_compile_t")
    assert eng.prewarmed == 3      # 2 buckets + 1 decode step
    warm = len(monitor.compile_events())
    keys = {e.get("key") for e in monitor.compile_events()}
    assert {"dec_compile_t.decode_step", "dec_compile_t.prefill_b8",
            "dec_compile_t.prefill_b16"} <= keys
    rng = np.random.default_rng(6)
    futs = [eng.submit(rng.integers(0, 97, size=int(n)), 4)
            for n in rng.integers(2, 15, size=7)]
    _drain(eng, futs)
    assert len(monitor.compile_events()) == warm
    eng.close()


def test_default_prompt_buckets():
    assert default_prompt_buckets(64) == (16, 32, 64)
    assert default_prompt_buckets(100) == (16, 32, 64)


# ---------------------------------------------------------------------
# per-token budgets
# ---------------------------------------------------------------------

def test_budget_shed_in_queue(dense_model):
    """A queued request whose first-token budget passes before a slot
    frees is SHED with DeadlineExceeded — the sweep runs host-side, no
    device step needed."""
    clk = FakeClock()
    eng = _engine(dense_model, clock=clk, slots=1, buckets=(8,))
    rng = np.random.default_rng(7)
    f_long = eng.submit(rng.integers(0, 97, size=4), 8)
    eng.step()                     # occupies the only slot
    f_tight = eng.submit(rng.integers(0, 97, size=4), 4,
                         token_budget_s=0.5)
    clk.advance(1.0)
    assert eng.sweep_expired() == 1
    assert isinstance(f_tight.exception(timeout=0), DeadlineExceeded)
    _drain(eng, [f_long])
    s = eng.summary()
    assert s["outcomes"]["shed"] == 1
    assert s["outcomes"]["completed"] == 1
    assert s["requests"] == sum(s["outcomes"].values())
    eng.close()


def test_budget_expired_midstream_releases_slot(dense_model):
    """A slot-resident request whose inter-token budget passes is
    resolved 'expired', its slot is killed on the next step, and the
    freed slot is REFILLED by the next queued request (which still
    decodes token-exact)."""
    clk = FakeClock()
    eng = _engine(dense_model, clock=clk, slots=1, buckets=(8,))
    rng = np.random.default_rng(8)
    p1, p2 = rng.integers(0, 97, size=5), rng.integers(0, 97, size=6)
    f1 = eng.submit(p1, 8, token_budget_s=0.5)
    eng.step()                     # prefill: first token lands
    eng.step()                     # one decode token
    assert not f1.done()
    clk.advance(1.0)               # inter-token gap > budget
    assert eng.sweep_expired() == 1
    assert isinstance(f1.exception(timeout=0), DeadlineExceeded)
    f2 = eng.submit(p2, 4)         # queued behind the dead tenant
    _drain(eng, [f2])
    ref = np.asarray(G.generate(dense_model, p2[None, :],
                                max_new_tokens=4))[0]
    assert np.array_equal(f2.result(timeout=0), ref)
    s = eng.summary()
    assert s["outcomes"]["expired"] == 1
    assert s["outcomes"]["completed"] == 1
    assert s["requests"] == sum(s["outcomes"].values())
    eng.close()


def test_a_slow_step_expires_only_the_request_with_a_budget(dense_model):
    """Two slot-resident requests share one decode step that takes
    longer than the inter-token budget of one of them: that one is
    resolved 'expired', its budget-less neighbour rides the same step
    to a token-exact completion, and nothing ends unclassified."""
    clk = FakeClock()
    eng = _engine(dense_model, clock=clk, slots=2, buckets=(8,))
    rng = np.random.default_rng(19)
    p_free, p_tight = (rng.integers(0, 97, size=5),
                       rng.integers(0, 97, size=6))
    f_free = eng.submit(p_free, 8)
    f_tight = eng.submit(p_tight, 8, token_budget_s=0.5)
    eng.step()                     # both prefilled
    eng.step()                     # one shared decode token
    assert not f_free.done() and not f_tight.done()
    clk.advance(1.0)               # the slow step
    assert eng.sweep_expired() == 1
    assert isinstance(f_tight.exception(timeout=0), DeadlineExceeded)
    _drain(eng, [f_free])
    ref = np.asarray(G.generate(dense_model, p_free[None, :],
                                max_new_tokens=8))[0]
    assert np.array_equal(f_free.result(timeout=0), ref)
    s = eng.summary()
    assert s["outcomes"]["expired"] == 1
    assert s["outcomes"]["completed"] == 1
    assert s["outcomes"]["failed"] == s["outcomes"]["stalled"] == 0
    assert s["requests"] == sum(s["outcomes"].values())
    assert s["pending"] == 0
    eng.close()


def test_queue_full_rejected(dense_model):
    eng = _engine(dense_model, slots=1, max_queue_depth=2,
                  buckets=(8,))
    rng = np.random.default_rng(9)
    subs = [eng.submit(rng.integers(0, 97, size=4), 4)
            for _ in range(2)]
    with pytest.raises(QueueFullError):
        eng.submit(rng.integers(0, 97, size=4), 4)
    assert eng.summary()["outcomes"]["rejected"] == 1
    _drain(eng, subs)
    eng.close()
    s = eng.summary()
    assert s["requests"] == sum(s["outcomes"].values())


def test_submit_validation(dense_model):
    # validation never reaches a program: skip the prewarm compiles
    eng = _engine(dense_model, prewarm=False)
    with pytest.raises(ValueError):
        eng.submit([], 4)                     # empty prompt
    with pytest.raises(ValueError):
        eng.submit([1, 2], 0)                 # no tokens requested
    with pytest.raises(ValueError):
        eng.submit(list(range(20)), 4)        # beyond largest bucket
    with pytest.raises(ValueError):
        eng.submit([1, 2, 3], 40)             # prompt+new > max_len
    eng.close()
    with pytest.raises(ServingClosedError):
        eng.submit([1, 2], 2)


# ---------------------------------------------------------------------
# watchdog + broken-engine semantics
# ---------------------------------------------------------------------

def test_watchdog_stall_breaks_engine(dense_model, tmp_path):
    """A wedged decode step escalates: the watchdog flags it, riding
    requests resolve 'stalled' (classified), queued requests cancel,
    and the engine refuses new work — the donated KV state is inside
    the wedged call, so pretending to continue would serve garbage."""
    old = fluid.get_flags("FLAGS_flight_recorder_dir")
    fluid.set_flags({"FLAGS_flight_recorder_dir": str(tmp_path)})
    hang = threading.Event()
    try:
        eng = _engine(dense_model, auto_start=True, buckets=(8,),
                      watchdog_stall_s=0.08, watchdog_poll_s=0.02,
                      retry_policy=None)
        rng = np.random.default_rng(10)
        f1 = eng.submit(rng.integers(0, 97, size=4), 8)
        # wedge the NEXT dispatch (prefill or decode — both run under
        # the same guard)
        faultinject.arm(stall_points={"decode.step": ("every", hang)})
        f2 = eng.submit(rng.integers(0, 97, size=4), 8)
        err = f1.exception(timeout=30) or f2.exception(timeout=30)
        assert isinstance(err, WatchdogStall)
        with pytest.raises(EngineBrokenError):
            eng.submit(rng.integers(0, 97, size=4), 2)
        s = eng.summary()
        assert s["outcomes"]["stalled"] >= 1
        assert s["watchdog_stalls"] >= 1
        assert s["requests"] == sum(s["outcomes"].values())
        assert s["pending"] == 0
    finally:
        hang.set()
        faultinject.disarm()
        fluid.set_flags(old)
    eng.close()


def test_close_cancels_queued(dense_model):
    # never steps: everything cancels in the queue, no compiles needed
    eng = _engine(dense_model, slots=1, prewarm=False)
    rng = np.random.default_rng(13)
    futs = [eng.submit(rng.integers(0, 97, size=4), 6)
            for _ in range(3)]
    eng.close()
    s = eng.summary()
    assert s["outcomes"]["cancelled"] >= 2    # the queued ones
    assert s["requests"] == sum(s["outcomes"].values())
    assert all(f.done() for f in futs)


# ---------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------

def test_decode_stats_percentiles_exact():
    """TTFT and inter-token percentiles ride the nearest-rank
    machinery: the published p99 is EXACTLY recomputable from the raw
    samples — no estimator drift."""
    st = DecodeStats("dec_pct_t", slots=4, register=False)
    rng = np.random.default_rng(14)
    for v in rng.uniform(0.001, 0.2, size=257):
        st.note_token_latency(float(v))
        st.note_prefill(ttft_s=float(v) * 2)
    d = st.decode_summary()
    toks = sorted(st.token_latency_samples())
    assert d["token_latency"]["p99_ms"] == round(
        exact_percentile(toks, 0.99) * 1e3, 3)
    ttfts = sorted(st.ttft_samples())
    assert d["ttft"]["p50_ms"] == round(
        exact_percentile(ttfts, 0.50) * 1e3, 3)


def test_metrics_and_record_surface(dense_model):
    """/metrics exposes decode_tokens_total + decode_slot_occupancy
    (parseable, family-contiguous) and the kind='serving' record
    carries the decode block the report tool renders."""
    from paddle_tpu.monitor import exporter

    monitor.reset()
    monitor.enable()
    eng = _engine(dense_model, label="dec_metrics_t", buckets=(8,))
    rng = np.random.default_rng(15)
    futs = [eng.submit(rng.integers(0, 97, size=5), 4)
            for _ in range(3)]
    _drain(eng, futs)
    eng.emit_telemetry()
    text = exporter.prometheus_text()
    parsed = exporter.parse_prometheus(text)
    lab = (("runtime", "dec_metrics_t"),)
    assert parsed[("paddle_tpu_decode_tokens_total", lab)] \
        == eng.stats.tokens_total
    occ = parsed[("paddle_tpu_decode_slot_occupancy", lab)]
    assert 0.0 < occ <= 1.0
    recs = [r for r in monitor.serving_records()
            if r.get("kind") == "serving" and r.get("decode")]
    assert recs
    dec = recs[-1]["decode"]
    assert dec["tokens_total"] == eng.stats.tokens_total
    assert dec["prefill_steps"] == 3

    from tools.telemetry_report import _serving_section

    sec = _serving_section(recs)
    block = sec["by_runtime"]["dec_metrics_t"]["decode"]
    assert block["tokens_total"] == eng.stats.tokens_total
    assert block["steps"]["prefill"] == 3
    assert 0.0 < block["prefill_step_frac"] < 1.0
    assert "p99_ms" in block.get("ttft_ms", {})
    eng.close()


# ---------------------------------------------------------------------
# kernels + fuse dispatch
# ---------------------------------------------------------------------

def test_flash_decode_matches_xla_path():
    """The Pallas single-query decode kernel (interpret mode on CPU)
    matches the exact XLA decode_attention math with ragged per-row
    lengths."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.attention import decode_attention
    from paddle_tpu.kernels.flash_attention import flash_decode

    rng = np.random.default_rng(16)
    b, h, t, d = 3, 4, 256, 64
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    pos = jnp.asarray([5, 200, 255], jnp.int32)
    ref = decode_attention(q, k, v, pos=pos, use_flash=False)
    out = flash_decode(q, k, v, pos + 1)
    assert np.allclose(np.asarray(out), np.asarray(ref),
                       rtol=1e-5, atol=1e-5)


def test_fuse_tags_decode_shape_and_matches():
    """A decode-shaped attention pattern (q_len==1 against a longer
    K/V prefix) fuses with attrs['decode']=True and the fused program
    still matches the unfused one numerically."""
    from paddle_tpu import layers as L
    from paddle_tpu import passes
    from paddle_tpu.framework.executor import Scope

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            q = fluid.data("q", [None, 4, 1, 8])
            k = fluid.data("k", [None, 4, 16, 8])
            v = fluid.data("v", [None, 4, 16, 8])
            mask = fluid.data("mask", [None, 4, 1, 16])
            scores = L.scale(L.matmul(q, k, transpose_y=True),
                             scale=8 ** -0.5)
            probs = L.softmax(L.elementwise_add(scores, mask))
            ctx = L.matmul(probs, v)
            loss = L.mean(ctx)
    fused, _ = passes.fuse_program(main, fetch_names=[loss.name],
                                   record=False)
    fa = next(op for op in fused.global_block().ops
              if op.type == "fused_attention")
    assert fa.attrs.get("decode") is True
    exe = fluid.Executor()
    rng = np.random.default_rng(17)
    feed = {"q": rng.standard_normal((2, 4, 1, 8)).astype(np.float32),
            "k": rng.standard_normal((2, 4, 16, 8)).astype(np.float32),
            "v": rng.standard_normal((2, 4, 16, 8)).astype(np.float32),
            "mask": np.where(
                np.arange(16)[None, None, None, :] <= 9, 0.0,
                -1e9).astype(np.float32)
            * np.ones((2, 4, 1, 16), np.float32)}
    ref = exe.run(main, feed=feed, fetch_list=[loss.name],
                  scope=Scope())
    out = exe.run(fused, feed=feed, fetch_list=[loss.name],
                  scope=Scope())
    assert np.allclose(np.asarray(ref[0]), np.asarray(out[0]),
                       rtol=1e-5, atol=1e-6)
