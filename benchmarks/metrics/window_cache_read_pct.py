"""Share of a decode step's cache bytes that lie in window rings: over
the `engine.decode_wait` spans of the traced stretch, the positions read
in the rings (`live_window`, times the window layers) over those read
in rings and full caches together (`live_full`, times the full layers);
a cached position is as many bytes in either.  Reads nothing where the
program records no such attributes (a model with one cache).

It describes the traffic (how long the contexts are against the window)
and is no target: no optimisation should move it.  `BENCHMARK.json`
wants a `better` on every metric and has no key for a note; "higher"
there only says that a mix with more of its reads in rings works the
mechanism more."""

from paddle_tpu import profiler

SPAN = "engine.decode_wait"


def read(run, name):
    spans = getattr(profiler, "spans", lambda prefix: [])("engine.")
    reads = [a for n, _, _, a in spans
             if n == SPAN and "live_full" in a and "live_window" in a]
    if not reads or not hasattr(run.config, "layers_of"):
        return None
    window = run.config.layers_of(run.cfg, "sliding_attention") \
        * sum(a["live_window"] for a in reads)
    full = run.config.layers_of(run.cfg, "full_attention") \
        * sum(a["live_full"] for a in reads)
    return 100.0 * window / (window + full) if window + full else None
