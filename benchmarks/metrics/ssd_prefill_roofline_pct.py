"""The SSD prefill kernel's share of its roofline: the operations of the
published chunked form over a prefill's bucket (the configuration's
`ssd_prefill_flops`, all Mamba layers, in chunks of its `chunk_size`),
summed over the traced prefills (runs of `jit_prefill_b<bucket>` in the
trace's `module_runs`), over the peak bf16 FLOP/s, over the device time
of the calls named `ssd_prefill`, read by kind from `device_ops`.  A
bucket's padding counts: the kernel is given the bucket.  Reads nothing
where the configuration has no such count or the trace no such
kernel."""

from benchmarks.metrics.mla_decode_roofline_pct import kind_seconds
from benchmarks.metrics.retention_prefill_roofline_pct import PROGRAM
from benchmarks.trace_reduce import program_name

KERNEL = "ssd_prefill"


def read(run, name):
    trace = run.trace
    if trace is None or not hasattr(run.config, "ssd_prefill_flops"):
        return None
    kernel_s = kind_seconds(trace, (KERNEL,))
    peaks = run.chip_peaks()
    if not kernel_s or peaks is None:
        return None
    ops = 0
    for module, runs in trace["module_runs"].items():
        m = PROGRAM.match(program_name(module))
        if m:
            ops += len(runs) * run.config.ssd_prefill_flops(
                run.cfg, int(m.group(1)))
    if not ops:
        return None
    return 100.0 * ops / peaks["bf16_flops_per_s"] / kernel_s
