"""The latent-attention decode kernel's share of its roofline: the
larger of the live latent's bytes over the peak bytes/s and the
absorbed form's operations over the peak bf16 FLOP/s, for the cached
positions that the traced decode steps read (all layers), over the
device time of the calls named `mla_decode` and `latent_append`, read by
kind from the trace's `device_ops`.

The traced steps are counted from the trace (runs of `jit_decode_step`),
and that many leading entries of the driver's `traced_steps` are taken:
the driver's list runs on past the trace's end."""

from benchmarks.trace_reduce import program_name

PROGRAM = "jit_decode_step"
KERNELS = ("mla_decode", "latent_append")


def kind_seconds(trace, kinds):
    """Summed device seconds of the `device_ops` entries of these kinds
    (an entry's label is `<kind> x<count> largest <shape>`)."""
    return sum(secs for label, secs in trace["device_ops"]
               if label.split(" x", 1)[0] in kinds)


def decode_runs(trace):
    return sum(len(v) for k, v in trace["module_runs"].items()
               if program_name(k) == PROGRAM)


def read(run, name):
    trace, steps = run.trace, run.result.get("traced_steps")
    if trace is None or not steps:
        return None
    kernel_s = kind_seconds(trace, KERNELS)
    peaks = run.chip_peaks()
    n = decode_runs(trace)
    if not kernel_s or peaks is None or not n:
        return None
    live = sum(s[3] for s in steps[:n])
    floor = max(
        live * run.config.latent_bytes_per_token(run.cfg)
        / peaks["hbm_bytes_per_s"],
        live * run.config.mla_decode_flops_per_cached_token(run.cfg)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor / kernel_s
