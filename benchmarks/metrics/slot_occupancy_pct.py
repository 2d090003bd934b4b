"""Mean share of the engine's slots that were live going into a decode
step, over the decode steps of the window."""


def read(run, name):
    steps = run.result.get("steps")
    if not steps or "engine" not in run.job:
        return None
    slots = run.job["engine"]["slots"]
    return 100.0 * sum(s[1] for s in steps) / (len(steps) * slots)
