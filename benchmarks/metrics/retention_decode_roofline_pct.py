"""The retention decode kernel's share of its roofline: the larger of
the bytes of the published state that the traced decode steps had to
read and write (the configuration's `retention_decode_bytes` of the
`active` slots of the engine's `engine.decode_wait` spans: their states
once each way, 8,256 monomials wide, whatever the program's layout
stores, so that a layout with more padding reads no higher a share)
over the peak bytes/s and the recurrent form's operations for those
slots (`retention_decode_flops`) over the peak bf16 FLOP/s, over the
device time of the calls named `retention_decode`, read by kind from
the trace's `device_ops`.

The traced steps are counted from the trace (runs of `jit_decode_step`,
as `mla_decode_roofline_pct.py` counts them) and each is given the mean
step's slots: the spans of the session and the programs of the trace
part by a step or two at the session's edges.  Reads nothing where the
program keeps no state (its spans carry no `state_bytes`) or the trace
holds no such kernel."""

from benchmarks.metrics.mla_decode_roofline_pct import (decode_runs,
                                                        kind_seconds)
from paddle_tpu import profiler

KERNEL = "retention_decode"
SPAN = "engine.decode_wait"


def active_slots():
    """The slots active in each of the session's decode steps over a
    state."""
    spans = getattr(profiler, "spans", lambda prefix: [])("engine.")
    return [a["active"] for n, _, _, a in spans
            if n == SPAN and "state_bytes" in a]


def read(run, name):
    trace, steps = run.trace, active_slots()
    if trace is None or not steps \
            or not hasattr(run.config, "retention_decode_bytes"):
        return None
    kernel_s = kind_seconds(trace, (KERNEL,))
    peaks = run.chip_peaks()
    n = decode_runs(trace)
    if not kernel_s or peaks is None or not n:
        return None
    slots = n * sum(steps) / len(steps)
    floor = max(
        run.config.retention_decode_bytes(run.cfg, slots)
        / peaks["hbm_bytes_per_s"],
        run.config.retention_decode_flops(run.cfg, slots)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor / kernel_s
