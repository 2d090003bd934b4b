"""The whole training step's share of the chips' peak: required forward
and backward operations per token (the configuration's count, from
shapes; recomputation not counted) times tokens per second, over chips
times the peak bf16 FLOP/s of peaks.json."""


def read(run, name):
    rate = run.result["values"].get("train_tokens_per_s")
    if rate is None:
        return None
    peaks = run.chip_peaks()
    if peaks is None:
        return None
    peak = peaks["bf16_flops_per_s"]
    flops = run.config.train_flops_per_token(run.cfg, run.job["seq"])
    return 100.0 * flops * rate / (run.chips * peak)
