"""Debugger (graphviz/pprint) and profiler (chrome trace) aux tests —
parity: fluid/debugger.py, net_drawer.py, fluid/profiler.py +
tools/timeline.py."""

import json
import pathlib

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import debugger, profiler


def _toy_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [None, 4])
        h = fluid.layers.fc(x, 3, act="relu")
        loss = fluid.layers.mean(h)
    return main, startup, loss


def test_draw_block_graphviz(tmp_path):
    main, _, _ = _toy_program()
    path = str(tmp_path / "g.dot")
    dot = debugger.draw_block_graphviz(main.global_block(), path=path)
    assert dot.startswith("digraph G {") and dot.rstrip().endswith("}")
    assert "ellipse" in dot            # op nodes
    assert "mean" in dot               # op label present
    assert open(path).read() == dot
    # a persistable var renders highlighted grey
    assert "lightgrey" in dot


def test_pprint_program_lists_ops():
    main, _, _ = _toy_program()
    text = debugger.pprint_program(main)
    assert "block 0" in text
    assert "mean" in text


def test_profiler_chrome_trace(tmp_path):
    main, startup, loss = _toy_program()
    exe = fluid.Executor()
    exe.run(startup)
    with profiler.profiler(state="CPU",
                           profile_path=str(tmp_path / "prof")):
        with profiler.RecordEvent("train_step"):
            exe.run(main, feed={"x": np.zeros((2, 4), np.float32)},
                    fetch_list=[loss])
    trace_path = str(tmp_path / "trace.json")
    profiler.export_chrome_tracing(trace_path)
    data = json.load(open(trace_path))
    events = data["traceEvents"] if isinstance(data, dict) else data
    names = {e.get("name") for e in events}
    assert "train_step" in names


def test_profiler_aggregates_events_across_threads(tmp_path):
    """Spans recorded on a worker thread (train_from_dataset's producer)
    must not vanish into an unreachable threading.local: stop_profiler's
    table and export_chrome_tracing aggregate every thread's events,
    tagged with the recording thread's tid."""
    import threading

    profiler.reset_profiler()
    with profiler.profiler(state="CPU",
                           profile_path=str(tmp_path / "prof")):
        with profiler.RecordEvent("main_span"):
            pass

        def work():
            with profiler.RecordEvent("producer_span"):
                pass

        t = threading.Thread(target=work)
        t.start()
        t.join()
    trace_path = str(tmp_path / "trace.json")
    profiler.export_chrome_tracing(trace_path)
    events = json.load(open(trace_path))["traceEvents"]
    spans = {e["name"]: e for e in events}
    assert {"main_span", "producer_span"} <= set(spans)
    assert spans["main_span"]["tid"] != spans["producer_span"]["tid"]


def test_profiler_table_counts_worker_spans():
    """stop_profiler's aggregate table includes worker-thread spans."""
    import threading

    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    threads = [threading.Thread(
        target=lambda: profiler.RecordEvent("worker").__enter__().__exit__())
        for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    table = profiler.stop_profiler(profile_path=None)
    assert table["worker"]["calls"] == 3


def test_record_event_zero_cost_when_profiling_off():
    """ISSUE 3 satellite: the gate lives inside RecordEvent itself —
    spans opened while no session is active record NOTHING, anywhere
    (not only at the executor call sites)."""
    assert not profiler.is_profiling()
    profiler.reset_profiler()
    for _ in range(5):
        with profiler.RecordEvent("stopped_span"):
            pass
    assert profiler._all_events() == []
    # and a session started afterwards sees only ITS spans
    profiler.start_profiler(state="CPU")
    with profiler.RecordEvent("live_span"):
        pass
    table = profiler.stop_profiler(profile_path=None)
    assert "stopped_span" not in table
    assert table["live_span"]["calls"] == 1


def test_record_event_straddling_session_stop_is_dropped():
    """A span entered while profiling is OFF but exited while ON must
    not record (its start time is meaningless for the session)."""
    profiler.reset_profiler()
    ev = profiler.RecordEvent("straddler")
    ev.__enter__()
    profiler.start_profiler(state="CPU")
    ev.__exit__(None, None, None)
    table = profiler.stop_profiler(profile_path=None)
    assert "straddler" not in table


def test_reset_profiler_during_open_span_is_safe():
    """ISSUE 3 satellite: an in-flight RecordEvent exiting after
    reset_profiler neither crashes nor resurrects its stale event —
    and spans opened after the reset record normally."""
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    ev = profiler.RecordEvent("stale_span")
    ev.__enter__()
    profiler.reset_profiler()          # clears while the span is open
    ev.__exit__(None, None, None)      # must not re-populate the store
    with profiler.RecordEvent("fresh_span"):
        pass
    table = profiler.stop_profiler(profile_path=None)
    assert "stale_span" not in table
    assert table["fresh_span"]["calls"] == 1


def test_nested_spans_survive_reset_without_stack_corruption():
    """reset mid-nest: both spans exit cleanly (no pop-from-empty), the
    outer one is dropped, and the NEXT session still nests correctly."""
    profiler.reset_profiler()
    profiler.start_profiler(state="CPU")
    with profiler.RecordEvent("outer"):
        profiler.reset_profiler()
        with profiler.RecordEvent("inner"):
            pass
    profiler.stop_profiler(profile_path=None)
    # depth bookkeeping intact for a fresh session
    profiler.start_profiler(state="CPU")
    with profiler.RecordEvent("a"):
        with profiler.RecordEvent("b"):
            pass
    table = profiler.stop_profiler(profile_path=None)
    assert table["a"]["calls"] == 1 and table["b"]["calls"] == 1


# ---------------------------------------------------------------------
# a session has an owner (a jax.profiler session is process-wide)
# ---------------------------------------------------------------------
def test_a_second_start_raises_and_names_the_first():
    profiler.start_profiler(state="CPU")
    try:
        with pytest.raises(RuntimeError, match="already open") as err:
            profiler.start_profiler(state="CPU")
        assert "test_a_second_start_raises_and_names_the_first" in \
            str(err.value)
        assert profiler.is_profiling()     # the first one is untouched
        with profiler.RecordEvent("still_recording"):
            pass
    finally:
        table = profiler.stop_profiler(profile_path=None)
    assert table["still_recording"]["calls"] == 1
    assert profiler.stop_profiler(profile_path=None) is not None  # safe


def test_start_under_a_foreign_trace_raises_and_opens_nothing(tmp_path):
    """`start_profiler("All")` needs the process's jax.profiler session;
    where someone else holds it, jax's error passes through and no
    half-session (host spans without a device trace) is left."""
    from paddle_tpu import flags

    old = flags.flag("profiler_dir")
    flags.set_flags({"FLAGS_profiler_dir": str(tmp_path / "ours")})
    jax.profiler.start_trace(str(tmp_path / "theirs"))
    try:
        with pytest.raises(RuntimeError, match="already been started"):
            profiler.start_profiler("All")
        assert not profiler.is_profiling()
    finally:
        jax.profiler.stop_trace()
        flags.set_flags({"FLAGS_profiler_dir": old})


def test_stop_profiler_resets_the_nesting_depth():
    """A span that never exits (its generator was dropped) must not
    deepen the next session's spans."""
    profiler.start_profiler(state="CPU")
    profiler.RecordEvent("never_exits").__enter__()
    profiler.stop_profiler(profile_path=None)
    profiler.start_profiler(state="CPU")
    with profiler.RecordEvent("top"):
        pass
    profiler.stop_profiler(profile_path=None)
    # (a garbage collection in the session is a `host.gc` span of its own)
    assert [e["depth"] for e in profiler._all_events()
            if e["name"] != profiler.GC_SPAN] == [0]


def test_a_failed_annotation_leaves_no_depth_behind(tmp_path, monkeypatch):
    def refuse(*a, **k):
        raise OSError("no annotation today")

    jax.profiler.start_trace(str(tmp_path))
    try:
        with monkeypatch.context() as m:
            m.setattr(jax.profiler, "TraceAnnotation", refuse)
            with pytest.raises(OSError):
                profiler.RecordEvent("refused").__enter__()
        with profiler.RecordEvent("after"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [(e["name"], e["depth"]) for e in profiler._all_events()
            if e["name"] != profiler.GC_SPAN] == [("after", 0)]


# ---------------------------------------------------------------------------
# what pauses the process: a garbage collection is a span of its own
# ---------------------------------------------------------------------------
def _collections():
    return [a for n, _, _, a in profiler.spans(profiler.GC_SPAN)]


def test_a_collection_in_a_session_is_a_host_gc_span(tmp_path):
    import gc

    profiler.reset_profiler()
    gc.collect()                      # no session: nothing is recorded
    assert _collections() == []
    assert profiler._on_gc not in gc.callbacks    # nor hooked
    profiler.start_profiler(state="CPU")
    try:
        assert profiler._on_gc in gc.callbacks
        gc.collect()
    finally:
        table = profiler.stop_profiler(profile_path=None)
    assert profiler._on_gc not in gc.callbacks
    got = _collections()
    # at least the forced one: the count only rises with what else ran
    assert table[profiler.GC_SPAN]["calls"] == len(got) >= 1
    assert any(a["generation"] == 2 for a in got)
    assert all(set(a) == {"generation", "collected"}
               and isinstance(a["collected"], int) for a in got)

    # under a jax trace the span lies in the trace as well, with both
    # attributes; noted once a span site has seen the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.RecordEvent("noted"):
            pass
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    assert any(a["generation"] == 2 for a in _collections())
    # the reading saw the session end: the hook is out again
    assert profiler._on_gc not in gc.callbacks
    files = list(pathlib.Path(tmp_path).rglob("*.xplane.pb"))
    events = [e for p in jax.profiler.ProfileData.from_file(
                  str(files[0])).planes
              for line in p.lines for e in line.events
              if e.name == profiler.TRACE_PREFIX + profiler.GC_SPAN]
    assert events and {"pc_ns", "generation", "collected"} <= set(
        dict(events[-1].stats))


_LEAKY = """
import jax
from paddle_tpu import profiler

def test_leaves_a_session_open():
    profiler.start_profiler(state="CPU")

def test_leaves_a_jax_trace_open(tmp_path):
    jax.profiler.start_trace(str(tmp_path))

def test_neighbour_runs_clean(tmp_path):
    assert not profiler.is_profiling()
    with profiler.profiler("All", profile_path=None):
        pass
"""


def test_a_leaked_session_fails_its_own_test_not_a_neighbour(pytester):
    """tests/conftest.py's autouse fixture, in a pytest run of its own:
    each leak is an error of the test that left it, and the test after
    them opens a device-trace session without trouble."""
    pytester.makeconftest(
        pathlib.Path(__file__).with_name("conftest.py").read_text())
    pytester.makepyfile(test_leaky=_LEAKY)
    result = pytester.runpytest_inprocess(
        "-p", "no:xdist", "-p", "no:randomly", "-p", "no:cacheprovider",
        "-rE")
    result.assert_outcomes(passed=3, errors=2)
    result.stdout.fnmatch_lines([
        "*ERROR at teardown of test_leaves_a_session_open*",
        "*start_profiler session started at*test_leaky.py:*"
        " in test_leaves_a_session_open",
        "*ERROR at teardown of test_leaves_a_jax_trace_open*",
        "*jax.profiler trace",
    ])
    assert not profiler.is_profiling()
