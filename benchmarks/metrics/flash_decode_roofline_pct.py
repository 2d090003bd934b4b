"""The decode-attention kernel's share of its roofline: the bytes of
live K and V that the traced decode steps had to read (from the slots'
lengths at each step, all layers) over the peak bytes/s, over the summed
device time of the Mosaic custom-calls inside the decode program."""

from benchmarks.trace_reduce import busiest_program


def read(run, name):
    trace, steps = run.trace, run.result.get("traced_steps")
    if trace is None or not steps:
        return None
    # the decode program: the one with the most device time (see
    # prefill_ms_p50.py)
    decode = busiest_program(trace)
    kernel_s = sum(t for module, t in trace["mosaic_calls"]
                   if module == decode)
    if not kernel_s:
        return None
    peaks = run.chip_peaks()
    if peaks is None:
        return None
    live = sum(s[3] for s in steps)
    floor = live * run.config.kv_bytes_per_token(run.cfg) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * floor / kernel_s
