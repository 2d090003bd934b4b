"""The grouped-query decode attention's share of its roofline over the
two caches of a model with window and full layers: the larger of the
live K and V bytes over the peak bytes/s and the attention's operations
over the peak bf16 FLOP/s, for the cached positions the traced decode
steps read (`live_full` in every full layer, `live_window` in every
ring: the attributes of the engine's `engine.decode_wait` spans), over
the device time of the calls named `gqa_decode` and `kv_append`, read by
kind from the trace's `device_ops`.

The traced steps are counted from the trace (runs of `jit_decode_step`,
as `mla_decode_roofline_pct.py` counts them) and each is given the mean
step's reads: the spans of the session and the programs of the trace
part by a step or two at the session's edges.  Reads nothing where the
program records no such attributes or the trace holds no such kernel."""

from benchmarks.metrics.mla_decode_roofline_pct import (decode_runs,
                                                        kind_seconds)
from paddle_tpu import profiler

KERNELS = ("gqa_decode", "kv_append")
SPAN = "engine.decode_wait"


def step_reads():
    """[(live_full, live_window)] of the session's decode steps."""
    spans = getattr(profiler, "spans", lambda prefix: [])("engine.")
    return [(a["live_full"], a["live_window"]) for n, _, _, a in spans
            if n == SPAN and "live_full" in a and "live_window" in a]


def read(run, name):
    trace, reads = run.trace, step_reads()
    if trace is None or not reads \
            or not hasattr(run.config, "swa_decode_bytes"):
        return None
    kernel_s = kind_seconds(trace, KERNELS)
    peaks = run.chip_peaks()
    n = decode_runs(trace)
    if not kernel_s or peaks is None or not n:
        return None
    full = n * sum(r[0] for r in reads) / len(reads)
    window = n * sum(r[1] for r in reads) / len(reads)
    floor = max(
        run.config.swa_decode_bytes(run.cfg, full, window)
        / peaks["hbm_bytes_per_s"],
        run.config.swa_decode_flops(run.cfg, full, window)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor / kernel_s
