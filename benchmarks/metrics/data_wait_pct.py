"""Share of the window the loop spent waiting for its next batch (the
`next_batch` span around the reader), in percent.  Layer: input."""


def read(run, name):
    steps = run.result.get("steps")
    if not steps:
        return None
    waited = sum(s[1] - s[0] for s in steps)
    return 100.0 * waited / run.result["elapsed_s"]
