"""Tests of `expert_rows_kept_pct.serve`: the share of the
expert layers that ran over `routed_experts`' kept rows, from the
engine's `*_wait` spans; on the CPU, a few seconds.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/test_expert_rows_kept.py -q

1. Its arithmetic on a handful of made-up spans, both kinds of program.
2. It reads nothing, and does not raise, where there is nothing to read:
   no spans, the parent's spans without the attributes, programs whose
   shape has no kept case (the rehearsal's toys), a profiler without
   `spans` at all.
3. It is declared for the two expert cells.
"""

import json
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks import run as R  # noqa: E402
from paddle_tpu import profiler  # noqa: E402

NAME = "expert_rows_kept_pct.serve"


class FakeRun:
    trace = None
    result = {}


def spans_of(monkeypatch, spans):
    def fake(prefix=None):
        return [s for s in spans
                if prefix is None or s[0].startswith(prefix)]

    monkeypatch.setattr(profiler, "spans", fake)


def _read():
    return R.load_reader(NAME).read(FakeRun(), NAME)


def test_reads_the_layers_kept_of_both_kinds_of_program(monkeypatch):
    kept = {"expert_tokens": 300, "expert_load_max": 40}
    spans_of(monkeypatch, [
        ("engine.decode_wait", 0, 1, dict(kept, expert_layers_kept=4,
                                          expert_layers=4)),
        ("engine.prefill_wait", 1, 2, dict(kept, expert_layers_kept=4,
                                           expert_layers=4)),
        # a prefill whose held assignments overflowed in three layers
        ("engine.prefill_wait", 2, 3, dict(kept, expert_layers_kept=1,
                                           expert_layers=4)),
        ("engine.decode_wait", 3, 4, dict(kept, expert_layers_kept=4,
                                          expert_layers=4)),
        # other phases and spans carry no such attributes
        ("engine.emit", 4, 5, {}),
        ("host.gc", 6, 7, {"generation": 0, "collected": 0}),
    ])
    assert _read() == pytest.approx(100.0 * 13 / 16)


def test_reads_nothing_where_the_program_records_nothing(monkeypatch):
    spans_of(monkeypatch, [])
    assert _read() is None
    # the parent's spans: the same names, without the new attributes
    spans_of(monkeypatch, [
        ("engine.prefill_wait", 0, 1, {"bucket": 256, "expert_tokens": 90,
                                       "expert_load_max": 12}),
        ("engine.decode_wait", 1, 2, {"active": 3, "expert_tokens": 60,
                                      "expert_load_max": 8})])
    assert _read() is None
    # programs whose shape has no kept case
    spans_of(monkeypatch, [
        ("engine.decode_wait", 1, 2, {"expert_layers_kept": 0,
                                      "expert_layers": 0})])
    assert _read() is None
    monkeypatch.delattr(profiler, "spans")     # a profiler without spans
    assert _read() is None


def test_declared_for_the_two_expert_cells():
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = next(m for m in bench["per_layer"] if m["name"] == NAME)
    assert bench["per_layer"][-1] is m
    assert m["workloads"] == ["kimi-k2.6.serve-closed-c256",
                              "trinity-mini.serve-closed-mixed-c64"]
    assert (m["source"], m["layer"], m["moves"], m["unit"], m["better"]) \
        == ("program_span", "sharding", "serve_output_tokens_per_s", "%",
            "higher")
    serve = next(e for e in bench["end_to_end"]
                 if e["name"] == "serve_output_tokens_per_s")["workloads"]
    assert set(m["workloads"]) <= set(serve)
