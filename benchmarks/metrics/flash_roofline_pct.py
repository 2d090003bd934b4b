"""The flash-attention calls' share of their roofline in training: the
least time the chip needs for the flash forward and backward calls of
the traced steps (the configuration's count from shapes: the larger of
operations over peak FLOP/s and bytes over peak bytes/s) over the summed
device time of the Mosaic custom-calls in the trace."""


def read(run, name):
    trace = run.trace
    if trace is None or not trace["mosaic_calls"]:
        return None
    steps = len(run.result.get("traced_steps", ()))
    if not steps:
        return None
    peaks = run.chip_peaks()
    if peaks is None:
        return None
    floor = run.config.flash_train_floor_s(
        run.cfg, run.job["batch"], run.job["seq"], peaks)
    return 100.0 * steps * floor / trace["mosaic_s"]
