"""The routed experts' grouped product's share of its roofline, over
the traced stretch: the larger of the held experts' bytes, read once by
every program run (decode steps and prefills, counted from the trace's
`module_runs`), over the peak bytes/s, and the operations of the
assignments on the held experts (the `expert_tokens` of the engine's
`*_wait` spans) over the peak bf16 FLOP/s; over the device time of the
grouped product, the calls named `moe_grouped_mm`, read by kind from
`device_ops`."""

from paddle_tpu import profiler

PROGRAMS = ("jit_decode_step", "jit_prefill_b")
KERNEL = "moe_grouped_mm"


def grouped_seconds(trace):
    """Device seconds of the `device_ops` entries of the kernel's kind
    (an entry's label is `<kind> x<count> largest <shape>`)."""
    return sum(secs for label, secs in trace["device_ops"]
               if label.split(" x", 1)[0] == KERNEL)


def read(run, name):
    trace = run.trace
    if trace is None or not hasattr(run.config, "expert_bytes"):
        return None
    kernel_s = grouped_seconds(trace)
    peaks = run.chip_peaks()
    if not kernel_s or peaks is None:
        return None
    runs = sum(len(v) for k, v in trace["module_runs"].items()
               if k.startswith(PROGRAMS))
    spans = getattr(profiler, "spans", lambda prefix: [])("engine.")
    assignments = sum(attrs.get("expert_tokens", 0)
                      for n, _, _, attrs in spans if n.endswith("_wait"))
    floor = max(
        runs * run.config.expert_bytes(run.cfg) / peaks["hbm_bytes_per_s"],
        assignments * run.config.expert_flops_per_assignment(run.cfg)
        / peaks["bf16_flops_per_s"])
    return 100.0 * floor / kernel_s
