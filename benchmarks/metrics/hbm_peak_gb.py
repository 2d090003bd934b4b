"""Peak device memory on the fullest chip, read when the window closed:
`run.memory_peak()`'s figure (the larger of the runtime's peak in use
and, with the window's programs resident, in use plus reserved)."""


def read(run, name):
    peak = run.result["memory_peak_bytes"]
    return peak / 1e9 if peak else None
