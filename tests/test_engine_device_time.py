"""The decode engine times each program from its own answers (ISSUE 38):
`device_s` on every flight, `behind_s` / `behind` on a decode step,
`true_len` on a prefill, and the summary's `device` block.

    JAX_PLATFORMS=cpu python -m pytest tests/test_engine_device_time.py -q

The first test plays a device on an injected clock (each program's
answer costs a fixed time of it, and nothing else moves it), so every
reading is exact; the second runs the loop thread one step ahead on the
real clock and holds the readings to what the spans say of each other.
No test compares two wall-clock times."""

import numpy as np
import pytest

from engine_fakes import Gate, hold_prefills, hold_steps
from paddle_tpu import monitor, profiler
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.nn import parameter
from paddle_tpu.serving.decode import DecodeConfig, DecodeEngine
from paddle_tpu.serving.stats import exact_percentile

PREFILL_S, DECODE_S = 0.25, 0.125       # binary fractions: sums are exact


@pytest.fixture(autouse=True)
def _clean_state():
    monitor.disable()
    profiler.reset_profiler()
    yield
    monitor.disable()
    profiler.reset_profiler()


@pytest.fixture(scope="module")
def model():
    # the process's generator from a seed of its own: whatever a test
    # before left in it (a traced key, even) is not drawn from
    parameter.seed(38)
    return GPT(GPTConfig(vocab_size=97, hidden_size=48, num_layers=2,
                         num_heads=4, max_seq_len=32, dropout=0.0))


class Clock:
    t = 100.0

    def __call__(self):
        return self.t


class Costs(Gate):
    """A device whose every program of one kind takes `cost` of the
    injected clock: its answer moves the clock on by that much."""

    def __init__(self, clock, cost):
        super().__init__()
        self.clock, self.cost = clock, cost

    def wait(self):
        self.clock.t += self.cost


def _flights(eng):
    """Record every flight `eng` answers, in order."""
    answered = []
    answer = eng._answer

    def recording(flight):
        now = answer(flight)
        if now is not None:
            answered.append(flight)
        return now

    eng._answer = recording
    return answered


def _prompts(n):
    rng = np.random.default_rng(38)
    return [rng.integers(0, 97, size=3 + 2 * i) for i in range(n)]


def test_each_program_reads_its_own_device_time_on_a_played_device(model):
    clk = Clock()
    eng = DecodeEngine(model, config=DecodeConfig(
        slots=3, max_len=32, buckets=(8, 16), clock=clk,
        watchdog_stall_s=1e9, label="device_time_played"), auto_start=False)
    hold_prefills(eng, Costs(clk, PREFILL_S))
    hold_steps(eng, Costs(clk, DECODE_S))
    flights = _flights(eng)
    prompts = _prompts(5)                  # 3, 5, 7, 9, 11 tokens
    profiler.start_profiler("CPU")
    try:
        futs = [eng.submit(p, 4) for p in prompts]
        for _ in range(100):
            if all(f.done() for f in futs):
                break
            eng.step()
    finally:
        profiler.stop_profiler(profile_path=None)
    summary = eng.summary()["decode"]
    eng.close()
    assert all(f.result(timeout=0).size == 4 for f in futs)

    # each reading is its program's cost; serially, nothing idles, so
    # they telescope to the first launch and the last answer
    kinds = [f.op for f in flights]
    assert kinds.count("prefill") == 5 and "decode" in kinds
    assert [f.device_s for f in flights] == [
        PREFILL_S if k == "prefill" else DECODE_S for k in kinds]
    assert sum(f.device_s for f in flights) == \
        flights[-1].ready_t - flights[0].launched_t == clk.t - 100.0

    # a decode step waited behind the prefills answered since the last
    spans = profiler.spans("engine.")
    waits = [(n, a) for n, _, _, a in spans if n.endswith("_wait")]
    behind = []
    fills = 0
    for name, a in waits:
        if name == "engine.prefill_wait":
            fills += 1
            assert a["device_s"] == PREFILL_S
        else:
            assert a["device_s"] == DECODE_S
            assert (a["behind"], a["behind_s"]) == (fills, fills * PREFILL_S)
            behind.append(a["behind_s"])
            fills = 0
    assert any(behind) and behind[-1] == 0.0

    # the prompts' lengths ride the prefill spans, the summary adds up
    fill = [a for n, a in waits if n == "engine.prefill_wait"]
    assert sorted(a["true_len"] for a in fill) == [3, 5, 7, 9, 11]
    assert sorted(a["bucket"] for a in fill) == [8, 8, 8, 16, 16]
    steps = kinds.count("decode")
    dev = summary["device"]
    assert dev["prefill_s"] == 5 * PREFILL_S
    assert dev["decode_s"] == steps * DECODE_S
    assert dev["prefill_share"] == round(
        5 * PREFILL_S / (5 * PREFILL_S + steps * DECODE_S), 4)
    assert dev["padding_share"] == round(1 - 35 / 56, 4)
    behind.sort()
    assert dev["behind_ms"] == {
        "p50": exact_percentile(behind, 0.5) * 1e3,
        "p90": exact_percentile(behind, 0.9) * 1e3,
        "p99": exact_percentile(behind, 0.99) * 1e3,
        "max": behind[-1] * 1e3}


def test_run_ahead_readings_telescope_and_name_what_each_step_waited_behind(
        model):
    """The loop thread, one step ahead, on the real clock: the readings
    of consecutive programs overlap in flight and still telescope (their
    sum is the first launch to the last answer, less the stretches in
    which nothing was on the device's queue), a decode step's `behind_s`
    is the prefills answered since the step before, and the always-on
    counters equal the spans' sums."""
    eng = DecodeEngine(model, config=DecodeConfig(
        slots=3, max_len=32, buckets=(8, 16), watchdog_stall_s=60.0,
        label="device_time_loop"), auto_start=False)
    hold_steps(eng, Gate(delay_s=0.003))   # the device is still busy
    flights = _flights(eng)
    profiler.start_profiler("CPU")
    try:
        eng.start()
        futs = [eng.submit(p, 6) for p in _prompts(7)]
        for f in futs:
            f.result(timeout=120)
        eng.close()
    finally:
        profiler.stop_profiler(profile_path=None)
    summary = eng.summary()["decode"]

    idle = sum(max(0.0, f.launched_t - e.ready_t)
               for e, f in zip(flights, flights[1:]))
    assert sum(f.device_s for f in flights) == pytest.approx(
        flights[-1].ready_t - flights[0].launched_t - idle, abs=1e-9)
    # the reading ends where the results were ready, before their fetch
    assert all(f.device_s <= f.ready_t - f.launched_t
               and f.ready_t <= f.done_t for f in flights)
    assert summary["lookahead"]["ahead"] > 0

    waits = [(n, a) for n, _, _, a in profiler.spans("engine.")
             if n in ("engine.prefill_wait", "engine.decode_wait")]
    assert len(waits) == len(flights)
    since = []
    for (name, a), flight in zip(waits, flights):
        assert a["device_s"] == flight.device_s
        if name == "engine.prefill_wait":
            since.append(a["device_s"])
        else:
            assert a["behind"] == len(since)
            assert a["behind_s"] == pytest.approx(sum(since), abs=1e-12)
            since = []
    dev = summary["device"]
    fills = [a for n, a in waits if n == "engine.prefill_wait"]
    steps = [a for n, a in waits if n == "engine.decode_wait"]
    assert dev["prefill_s"] == round(sum(a["device_s"] for a in fills), 6)
    assert dev["decode_s"] == round(sum(a["device_s"] for a in steps), 6)
    assert dev["padding_share"] == round(
        1 - sum(a["true_len"] for a in fills)
        / sum(a["bucket"] for a in fills), 4)
    assert dev["behind_ms"]["max"] == round(
        max(a["behind_s"] for a in steps) * 1e3, 3)


def test_the_counters_need_no_profiler(model):
    eng = DecodeEngine(model, config=DecodeConfig(
        slots=2, max_len=32, buckets=(8, 16), label="device_time_off"),
        auto_start=False)
    assert "device" not in eng.summary()["decode"]
    fut = eng.submit(_prompts(1)[0], 3)
    while not fut.done():
        eng.step()
    dev = eng.summary()["decode"]["device"]
    eng.close()
    assert profiler.spans() == []
    assert dev["prefill_s"] > 0 and dev["decode_s"] > 0
    assert dev["padding_share"] == round(1 - 3 / 8, 4)
    assert set(dev["behind_ms"]) == {"p50", "p90", "p99", "max"}
