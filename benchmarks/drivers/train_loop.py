"""Training driver: fresh host batches from the seed through the
program's own double buffer, one optimizer step a batch, the loss of
every step fetched (`loss_lag` steps late, so that the device has work
queued while the host is busy or paused).

The configuration's module gives `build_trainer(cfg, job, seed)` (the
compiled step with its state; `.step(x, y)` returns the loss on the
device) and `reference_train(...)`.  Set-up drives that one trainer
through its first `check_steps` steps on the window's own feed and call,
reads what the comparison needs from its state (the first gradient from
Adam's first moment, the change of the parameters), warms up, and hands
the same trainer to the window.  The reference follows those steps once
the window has closed, the peak has been read and the trainer is freed.

`train_tokens_per_s` is the tokens of every step completed in the window
(its loss on the host before the window closes) over the time from the
window's start to the last of those losses.
"""

import collections
import statistics

import numpy as np


def batches(job, vocab, seed):
    """Endless (x, y) int32 [batch, seq]: uniform tokens, the target the
    next token; every row differs."""
    rng = np.random.default_rng(seed)
    while True:
        t = rng.integers(0, vocab, (job["batch"], job["seq"] + 1),
                         dtype=np.int32)
        yield t[:, :-1], t[:, 1:]


def worst_leaf_gap(got, want, skip=()):
    """Largest, over the leaves, of |norm got - norm want| measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger.  Returns (gap, leaf)."""
    floor = statistics.median(want.values())
    worst, at = 0.0, None
    for name, w in want.items():
        if name in skip:
            continue
        gap = abs(got[name] - w) / max(w, floor)
        if not gap <= worst:      # a NaN gap is the worst there is
            worst, at = gap, name
    return float(worst), at


def compare(readings, ref, limits):
    """The numbers compared, each with its limit: [(name, value, limit)]
    and notes on where the worst leaf was."""
    losses = max(abs(a - b) / abs(b)
                 for a, b in zip(readings["losses"], ref["losses"]))
    grad, grad_at = worst_leaf_gap(readings["grad_norms"], ref["grad_norms"])
    # leaves whose gradient is nought to rounding in the reference move
    # under Adam by round-off alone: left out of the change by a rule on
    # the reference's gradient, not by name
    med = statistics.median(ref["grad_norms"].values())
    dead = {n for n, g in ref["grad_norms"].items() if g < 1e-3 * med}
    change, change_at = worst_leaf_gap(readings["change_norms"],
                                       ref["change_norms"], skip=dead)
    checks = [("grad_norm_gap", grad, limits["grad_norm_gap"]),
              ("change_norm_gap", change, limits["change_norm_gap"])]
    # the losses' gap is read and not compared: neither the control nor a
    # fault reads ten times what sound runs do (PERF.md, section 2)
    notes = {"loss_rel_gap": losses, "grad_worst_leaf": grad_at,
             "change_worst_leaf": change_at,
             "leaves_left_out_of_change": len(dead)}
    return checks, notes


def first_steps(run, trainer, feed, keep):
    """Drive the trainer through its first steps on the window's feed and
    call; `keep` gets the host copy of every batch for the reference."""
    n = run.job["check_steps"]
    losses = []
    for i in range(n):
        x, y = next(feed)
        keep.append((np.asarray(x), np.asarray(y)))
        losses.append(float(trainer.step(x, y)))
        if i == 0:
            grad_norms = trainer.first_gradient_norms()
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": trainer.change_norms()}


def timed_steps(run, trainer, feed, until):
    """Steps until the clock passes `until`, then the losses still out.
    The loss of every step is fetched, `loss_lag` steps after it was
    dispatched: the device has that many steps queued, so a pause of the
    host shorter than they take does not leave it idle.  Returns, for
    every step, (t_start, t_got_batch, t_dispatched, t_done): when the
    loop turned to it, had its batch, had dispatched it, and had its loss
    on the host."""
    lag = run.job["loss_lag"]
    marks, done, pending = [], [], collections.deque()

    def fetch():
        with run.span("fetch_loss"):
            last = float(pending.popleft())
        done.append(run.clock())
        return last

    while run.clock() < until:
        t0 = run.clock()
        with run.span("next_batch"):
            x, y = next(feed)
        t1 = run.clock()
        with run.span("dispatch"):
            pending.append(trainer.step(x, y))
        marks.append((t0, t1, run.clock()))
        if len(pending) > lag:
            fetch()
    while pending:
        last = fetch()
    if not np.isfinite(last):
        raise RuntimeError(f"loss is {last} after {len(done)} steps")
    return [m + (d,) for m, d in zip(marks, done)]


def step_seconds(steps, t_open):
    """Time from one step's loss on the host to the next one's."""
    ends = [t_open] + [s[3] for s in steps]
    return [b - a for a, b in zip(ends, ends[1:])]


def long_steps(steps, step_s, lag, most=5):
    """The steps that took over a fifth longer than the median step:
    [index, ms, the span the loop spent longest in on its way to that
    loss].  One long step and every step slower are different faults."""
    median = statistics.median(step_s)
    out = []
    for i, dt in enumerate(step_s):
        if dt > 1.2 * median:
            # the loss of step i comes back in the iteration that
            # dispatches step i + lag (after the loop, for the last ones)
            spans = {}
            if i + lag < len(steps):
                t0, t1, t2, _ = steps[i + lag]
                spans = {"next_batch": t1 - t0, "dispatch": t2 - t1}
            spans["fetch_loss"] = dt - sum(spans.values())
            out.append([i, dt * 1e3, max(spans, key=spans.get)])
    return sorted(out, key=lambda s: -s[1])[:most]


def run(run):
    from paddle_tpu.reader import device_prefetch

    cfg, job = run.cfg, run.job
    trainer = run.config.build_trainer(cfg, job, run.seed)
    feed = iter(device_prefetch(batches(job, cfg["vocab_size"], run.seed),
                                size=job["prefetch"]))
    kept = []
    readings = first_steps(run, trainer, feed, kept)
    for _ in range(job["warm_steps"]):
        x, y = next(feed)
        float(trainer.step(x, y))

    t_open = run.open_window()
    traced_s = job["trace_seconds"] if run.trace_on else 0.0
    t_close = t_open + run.seconds - traced_s
    dispatched = timed_steps(run, trainer, feed, t_close)
    run.close_window()
    traced = []
    if run.trace_on:
        t_trace = run.clock()
        run.traced(lambda: traced.extend(
            timed_steps(run, trainer, feed, t_trace + traced_s)))
    peak = run.memory_peak()
    trainer.free()
    del feed

    # the window's work is the steps whose loss was on the host when it
    # closed; the queued ones that finish after it are not counted, so a
    # pause of the host at the very end cannot stretch the time
    steps = [s for s in dispatched if s[3] <= t_close]
    if not steps:
        raise RuntimeError("no step completed in the window")
    step_s = step_seconds(steps, t_open)
    elapsed = steps[-1][3] - t_open
    run.write_json("steps.json", {
        "columns": ["start", "got_batch", "dispatched", "loss_on_host"],
        "window_open": t_open, "window_close": t_close,
        "steps": dispatched})

    ref = run.config.reference_train(cfg, job, run.seed, kept)
    checks, notes = compare(readings, ref, run.limits)
    if run.control:
        # the reference in the program's place: in fp8, and with half of
        # each batch left out; each has to fail one of the limits
        for what, kw in (("fp8", {"precision": "fp8"}),
                         ("half_batch", {"fault": "half_batch"})):
            bad = run.config.reference_train(cfg, job, run.seed, kept, **kw)
            notes["control_" + what] = {
                n: v for n, v, _ in compare(bad, ref, run.limits)[0]}
    notes.update(steps=len(steps), window_s=elapsed,
                 step_ms_max=max(step_s) * 1e3,
                 long_steps=long_steps(dispatched, step_s, job["loss_lag"]),
                 losses=readings["losses"], ref_losses=ref["losses"])
    return {
        "correct": all(v <= lim for _, v, lim in checks),
        "attempted": len(steps), "failed": 0,
        "values": {"train_tokens_per_s":
                   len(steps) * trainer.tokens_per_step / elapsed},
        "memory_peak_bytes": peak,
        "checks": checks, "notes": notes,
        "steps": steps,
        "step_s": step_s, "elapsed_s": elapsed, "traced_steps": traced,
    }
