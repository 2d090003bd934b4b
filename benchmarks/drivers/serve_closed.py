"""Closed-loop serving driver: `clients` callers, each submitting its
next request the moment its last one resolves.

The configuration's module gives `build_engine(cfg, job, seed, clock)`
(a `serving.DecodeEngine` with its own loop thread) and `ReferenceLM`.
One client thread keeps `clients` requests outstanding by polling their
futures; the requests are a fixed multiset of (prompt length, output
length) pairs, the same for every seed, which the seed orders and fills
with tokens.  The clients start during set-up and the window opens after
`warm_completions`, so the back-to-back prefills of a cold start are not
in the tail.

Every first token and every gap between tokens is recorded, with the
engine-clock time it happened at, by a recording subclass of the
engine's `DecodeStats` (the program's own rings keep the last 8,192
samples only), and so is every resolution, against which the clients'
resubmissions are held.  A run fails, rather than reports, if a sample is
missing or the clients kept less than `min_outstanding_share` of their
`clients` x window request-seconds outstanding.

Once the window has closed: the peak is read, the engine is closed and
freed, and the reference runs once over a seed-drawn sample of the
requests finished in the window, the longest among them, prompt and
served tokens together; the number compared is the widest gap by which a
served token's reference logit lies under the reference's best.
"""

import threading
import time

import numpy as np


def size_pairs(job):
    """The mix's fixed multiset of (prompt_len, output_len): quantiles of
    a log-uniform and a uniform range, paired by a fixed shuffle."""
    n = job["request_pairs"]
    q = (np.arange(n) + 0.5) / n
    lo, hi = job["prompt_len"]
    prompts = np.round(np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo))))
    lo, hi = job["output_len"]
    outputs = np.round(lo + q * (hi - lo))
    np.random.default_rng(job["pairing_seed"]).shuffle(outputs)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


def requests(job, vocab, seed):
    """Endless (prompt tokens, output_len): the multiset in the seed's
    order, again and again."""
    rng = np.random.default_rng(seed)
    pairs = size_pairs(job)
    order = rng.permutation(len(pairs))
    while True:
        for i in order:
            p, o = pairs[i]
            yield rng.integers(0, vocab, p, dtype=np.int32), o


def recording_stats(base):
    """A subclass of the engine's stats class that keeps every sample."""

    class RecordingStats(base):
        def start_recording(self, engine, clock):
            self.engine, self.clock = engine, clock
            self.first_tokens = []   # (now, ttft_s)
            self.gaps = []           # (now, gap_s)
            self.decode_steps_at = []  # (now, active, emitted, live_tokens)
            self.resolved_at = []    # when each request's future resolved
            self._pending = []

        def note_outcome(self, outcome, latency_s=None):
            super().note_outcome(outcome, latency_s=latency_s)
            self.resolved_at.append(self.clock())

        def note_prefill(self, ttft_s=None, now=None):
            super().note_prefill(ttft_s=ttft_s, now=now)
            if ttft_s is not None:
                self.first_tokens.append((now, ttft_s))

        def note_token_latency(self, latency_s):
            super().note_token_latency(latency_s)
            self._pending.append(latency_s)

        def note_decode_step(self, active, emitted, now=None):
            super().note_decode_step(active, emitted, now=now)
            self.gaps.extend((now, g) for g in self._pending)
            self._pending = []
            # cached positions the step read: each resident request's
            # prompt and tokens but the one just emitted
            live = sum(r.prompt.size + len(r.tokens) - 1
                       for r in self.engine._slot_req if r is not None)
            self.decode_steps_at.append((now, active, emitted, live))

    return RecordingStats


class Clients(threading.Thread):
    """Keeps `n` requests outstanding until told to stop."""

    def __init__(self, engine, source, n, clock, poll_s):
        super().__init__(name="bench-clients", daemon=True)
        self.engine, self.source, self.n = engine, source, n
        self.clock, self.poll_s = clock, poll_s
        self.done = []        # (t_seen, prompt, n_out, tokens or exception)
        self.poll_gap_max = 0.0
        self.error = None
        self._halt = threading.Event()

    def _submit(self):
        prompt, n_out = next(self.source)
        return prompt, n_out, self.engine.submit(prompt,
                                                 max_new_tokens=n_out)

    def run(self):
        try:
            live = [self._submit() for _ in range(self.n)]
            last = self.clock()
            while not self._halt.is_set():
                time.sleep(self.poll_s)
                now = self.clock()
                self.poll_gap_max = max(self.poll_gap_max, now - last)
                last = now
                for i, (prompt, n_out, fut) in enumerate(live):
                    if fut.done():
                        err = fut.exception()
                        self.done.append(
                            (now, prompt, n_out,
                             err if err is not None else fut.result()))
                        live[i] = self._submit()
        except BaseException as e:  # noqa: BLE001  (reported by the driver)
            self.error = e

    def stop(self):
        self._halt.set()
        self.join(timeout=30)
        if self.is_alive():
            raise RuntimeError("client thread did not stop")


def percentile(values, q):
    """Nearest-rank percentile of all the values."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(np.ceil(q * len(s))) - 1))]


def check_sample(run, finished):
    """Reference over a seed-drawn sample of the finished requests, the
    longest among them.  Returns (widest gap, the control's widest gap
    or None, tokens compared)."""
    ok = [d for d in finished if not isinstance(d[3], BaseException)]
    longest = max(range(len(ok)), key=lambda i: ok[i][1].size + ok[i][2])
    rng = np.random.default_rng(run.seed)
    others = [i for i in rng.permutation(len(ok)) if i != longest]
    picks = [longest] + others[:run.job["check_requests"] - 1]
    ref = run.config.ReferenceLM(run.cfg, run.seed,
                                 run.job["engine"]["max_len"])
    widest, control, n = 0.0, 0.0, 0
    for i in picks:
        _, prompt, n_out, tokens = ok[i]
        if len(tokens) != n_out:
            return float("inf"), None, n   # a short answer is a wrong one
        gaps = ref.token_gaps(prompt, np.asarray(tokens))
        widest = max(widest, float(gaps.max())) if np.all(
            np.isfinite(gaps)) else float("inf")
        if run.control:
            # the token the fp8 forward pass puts first, at each position
            # of the same prompts and served tokens
            control = max(control, float(ref.token_gaps(
                prompt, np.asarray(tokens), control=True).max()))
        n += len(tokens)
    return widest, (control if run.control else None), n


def run(run):
    cfg, job = run.cfg, run.job
    engine = run.config.build_engine(cfg, job, run.seed, run.clock)
    try:
        engine.stats.__class__ = recording_stats(type(engine.stats))
        stats = engine.stats
        stats.start_recording(engine, run.clock)
        clients = Clients(engine, requests(job, cfg["vocab_size"], run.seed),
                          job["clients"], run.clock, job["poll_s"])
        clients.start()
        while len(clients.done) < job["warm_completions"]:
            if clients.error is not None or not clients.is_alive():
                raise RuntimeError(f"clients died in warm-up: "
                                   f"{clients.error!r}")
            time.sleep(0.05)

        t_open = run.open_window()
        clients.poll_gap_max = 0.0
        traced_s = job["trace_seconds"] if run.trace_on else 0.0
        time.sleep(max(0.0, t_open + run.seconds - traced_s - run.clock()))
        t_close = run.clock()
        poll_gap_max = clients.poll_gap_max
        run.close_window()
        if run.trace_on:
            run.traced(lambda: time.sleep(traced_s))
            t_trace_end = run.clock()
            if run.trace is not None:
                # only the engine's loop launches programs here: a gap
                # before one is its host work between two dispatches
                merged = {}
                for label, secs in run.trace["idle_gaps"]:
                    if label.startswith("before:"):
                        label = "engine_step"
                    merged[label] = merged.get(label, 0.0) + secs
                run.trace["idle_gaps"] = sorted(
                    ([k, v] for k, v in merged.items()), key=lambda g: -g[1])
        clients.stop()
        peak = run.memory_peak()
        summary = engine.summary()
    finally:
        engine.close()
    if clients.error is not None:
        raise RuntimeError(f"client thread failed: {clients.error!r}")

    # a run that lost a sample or starved its clients does not report
    emitted = sum(s[2] for s in stats.decode_steps_at)
    if len(stats.gaps) != emitted or stats._pending:
        raise RuntimeError(f"{emitted} tokens decoded but "
                           f"{len(stats.gaps)} gaps recorded")

    def inside(t):
        return t_open <= t < t_close

    # request-seconds in which a caller had no request outstanding: from
    # each future's resolution to the poll that saw it and sent the next
    # (k-th resolution against k-th sighting: every sighting follows its
    # own resolution, so the sorted lists pair off with no negative lag)
    window_s = t_close - t_open
    lags = [seen - res for res, seen in zip(sorted(stats.resolved_at),
                                            sorted(d[0] for d in clients.done))
            if inside(seen)]
    outstanding = 1.0 - sum(lags) / (job["clients"] * window_s)
    if outstanding < job["min_outstanding_share"]:
        raise RuntimeError(
            f"the callers kept {100 * outstanding:.2f}% of {job['clients']} "
            f"requests outstanding over the window (longest resubmission "
            f"lag {max(lags, default=0.0):.3f} s, longest poll gap "
            f"{poll_gap_max:.3f} s)")
    resilience = {k: summary.get(k, 0) for k in (
        "dispatch_retries", "watchdog_stalls", "degraded_batches",
        "stalled_in_flight")}
    resilience["breaker_transitions"] = len(
        summary.get("breaker", {}).get("transitions", []))
    print(f"resilience tier: {resilience}; client poll gap max "
          f"{poll_gap_max * 1e3:.1f} ms", flush=True)

    ttft = [x for t, x in stats.first_tokens if inside(t)]
    gaps = [g for t, g in stats.gaps if inside(t)]
    steps = [s for s in stats.decode_steps_at if inside(s[0])]
    out_tokens = len(ttft) + sum(s[2] for s in steps)
    finished = [d for d in clients.done if inside(d[0])]
    failed = sum(isinstance(d[3], BaseException) for d in finished)
    prompt_tokens = sum(d[1].size for d in finished)

    engine._state = None      # the cache and the weights go before the
    engine._trees = None      # reference takes the chip
    engine.params = None
    widest, control, compared = check_sample(run, finished)
    checks = [("token_logit_gap", widest, run.limits["token_logit_gap"]),
              ("failed_requests", float(failed), 0.0)]
    result = {
        "correct": all(v <= lim for _, v, lim in checks),
        "attempted": len(finished), "failed": failed,
        "values": {
            "serve_output_tokens_per_s": out_tokens / window_s,
            "ttft_p90_ms": percentile(ttft, 0.90) * 1e3,
            "itl_p99_ms": percentile(gaps, 0.99) * 1e3,
        },
        "memory_peak_bytes": peak,
        "checks": checks,
        "notes": {"window_s": window_s, "first_tokens": len(ttft),
                  "ttft_ms": {f"p{int(q * 100)}": percentile(ttft, q) * 1e3
                              for q in (0.5, 0.9, 0.95, 0.99)},
                  "itl_ms": {f"p{int(q * 100)}": percentile(gaps, q) * 1e3
                             for q in (0.5, 0.9, 0.95, 0.99)},
                  "step_gap_ms_top5": sorted(
                      (b[0] - a[0]) * 1e3
                      for a, b in zip(steps, steps[1:]))[-5:],
                  "gaps": len(gaps), "decode_steps": len(steps),
                  "tokens_compared": compared, "control_fp8": control,
                  "poll_gap_max_ms": poll_gap_max * 1e3,
                  "resubmit_lag_max_ms": max(lags, default=0.0) * 1e3,
                  "outstanding_share": outstanding,
                  "resilience": resilience},
        "window_s": window_s, "gaps": gaps, "steps": steps,
        "out_tokens": out_tokens, "prompt_tokens": prompt_tokens,
    }
    if run.trace_on:
        def traced(t):
            return t_close <= t < t_trace_end
        result["traced_steps"] = [s for s in stats.decode_steps_at
                                  if traced(s[0])]
    return result
