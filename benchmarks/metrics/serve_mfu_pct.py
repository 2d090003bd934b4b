"""The serving path's share of the chip's peak: 2 operations for each
weight a token meets, times prompt and output tokens per second of the
window, over the peak bf16 FLOP/s.  Small by nature while decoding is
bound by memory; it is what still bounds a claim once a kernel is gone."""


def read(run, name):
    r = run.result
    if "out_tokens" not in r:
        return None
    peaks = run.chip_peaks()
    if peaks is None:
        return None
    peak = peaks["bf16_flops_per_s"]
    rate = (r["out_tokens"] + r["prompt_tokens"]) / r["window_s"]
    return 100.0 * run.config.serve_flops_per_token(run.cfg) * rate \
        / (run.chips * peak)
