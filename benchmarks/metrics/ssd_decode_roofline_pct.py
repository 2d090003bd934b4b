"""The SSD decode kernel's share of its roofline: the larger of the
bytes of the published SSD state that the traced decode steps had to
read and write (the configuration's `ssd_decode_bytes` of the `active`
slots of the engine's `engine.decode_wait` spans that carry
`state_bytes`: each state once each way, float32 [heads, head_dim,
state] a Mamba layer, whatever the program's layout stores) over the
peak bytes/s and the recurrence's operations for those slots
(`ssd_decode_flops`) over the peak bf16 FLOP/s, over the device time of
the calls named `ssd_decode`, read by kind from the trace's
`device_ops`.

The traced steps are counted from the trace (runs of `jit_decode_step`)
and each is given the mean step's slots, as
`retention_decode_roofline_pct.py` does.  Reads nothing where the
program keeps no state (its spans carry no `state_bytes`), the
configuration has no such count, or the trace holds no such kernel."""

from benchmarks.metrics.mla_decode_roofline_pct import (decode_runs,
                                                        kind_seconds)
from benchmarks.metrics.retention_decode_roofline_pct import active_slots

KERNEL = "ssd_decode"


def read(run, name):
    trace, steps = run.trace, active_slots()
    if trace is None or not steps \
            or not hasattr(run.config, "ssd_decode_bytes"):
        return None
    kernel_s = kind_seconds(trace, (KERNEL,))
    peaks = run.chip_peaks()
    n = decode_runs(trace)
    if not kernel_s or peaks is None or not n:
        return None
    slots = n * sum(steps) / len(steps)
    floor = max(
        run.config.ssd_decode_bytes(run.cfg, slots)
        / peaks["hbm_bytes_per_s"],
        run.config.ssd_decode_flops(run.cfg, slots)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor / kernel_s
