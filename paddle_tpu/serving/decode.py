"""Continuous-batching decode serving — slot-based cache engine
(ISSUE 17 tentpole; the model behind a seam since ISSUE 29).

The PR-8 runtime batches single-shot predictors; this engine serves a
causal decoder autoregressively, with the iteration-level scheduling of
Orca (OSDI '22) and the slot-resident cache of vLLM (SOSP '23).

**The seam.**  The engine does not know its model.  It is given
`params` with `.trees` (the arrays, a pytree) and `.cfg` (hashable,
static under jit), and `cfg` gives:

- `cache_arrays(slots, max_len)` -> {name: array}, the model's cache
  (`cache_kind` says what it is), which the engine keeps in its donated
  state beside its own per-slot vectors.  Every array is `[layers,
  slots, ...]`.  One that ends in a depth (`[..., depth]`: a position a
  column, `max_len` deep or a ring) grows with the context; one that
  `cfg.cache_states` names is a **state**: of a fixed size a slot,
  whatever the context, so that `max_len` bounds the positions and not
  the slot's bytes (`models/brumby.py`: a recurrent state and its
  divisor's);
- `prefill(trees, cache, prompt[1, bucket], true_len, slot)` ->
  (cache, hidden [1, H] at the true last position, counters).  Of a
  state it writes the slot's whole: a slot that is refilled keeps
  nothing of its last tenant (columns need no clearing, being read only
  up to the position; a state has no such bound);
- `decode(trees, cache, token[S], pos[S], active[S])` -> (cache, hidden
  [S, H], counters), one step of every slot at its own position;
  `active` says which slots hold a request (a model with columns may
  ignore it: what it writes for the others is never read; one with a
  state leaves theirs alone, and moves no byte of them);
- `head(trees, hidden)` -> logits; and `max_seq_len`.

Everything after the hidden state (head, greedy or sampled token,
`pos/active/stop/eos/temp/key`) and everything on the host (admission,
slots, budgets, spans, stats, hardening) is the engine's and shared.
`models/generate.py`'s `DecCfg` (the GPT family; a `models/gpt.py` GPT
is accepted as it is) and `models/kimi_k2.py`'s `K2Cfg` implement it.
`counters` is a dict of small arrays, the last result of each program
and read by key (a model that counts nothing gives an empty one, and its
program answers with exactly what the engine always fetched):
`expert_counts` (assignments on each expert held here, summed over the
expert layers) becomes the `expert_tokens` / `expert_load_max`
attributes of `engine.*_wait` and `DecodeStats`' running totals, and
`expert_layers_kept` beside it (the expert layers that ran over
`routed_experts`' kept rows) the attribute of that name, beside
`expert_layers`, which such a model's `expert_layers(tokens)` gives
from the program's shape.
What a program read of the cache the engine counts on the host, from
the lengths it holds (nothing is fetched): a model whose cache arrays
differ in depth (`models/afmoe.py`: full layers beside window rings)
gives `cache_reads(lengths)` -> {name: the cached positions read in one
layer of that kind, summed over `lengths`}, which the engine calls with
the lengths a decode step's active slots had, or a prefill's prompt
length, and puts on `engine.*_wait`.  A model whose decode kernel
walks its cache in tiles may give `cache_walk(lengths, slots, max_len)`
-> {name: count} of one layer of one decode step (`models/kimi_k2.py`:
`latent_tiles` walked of the `latent_grid` a rectangle over every slot
would hold; `models/afmoe.py`: `full_tiles` of `full_grid` in a full
layer, `window_tiles` of `window_grid` in a ring); the engine calls it
with the same lengths, puts the counts on `engine.decode_wait` and their
totals under `summary()["decode"]["cache"]`.  Where the model keeps a
state the engine counts its traffic itself, on the host, from what the
seam says of a state: `state_bytes`, the bytes of state a program read
and wrote, is a decode step's active slots' states once each way and a
prefill's own slot's once (a slot's bytes are those of the arrays
`cache_states` names, over the slots); a prefill's counters may give
`chunks`, the chunks its scan walked; both become attributes of those
names on `engine.*_wait` and totals under `summary()["decode"]
["cache"]`.  `DecodeStats.summary()["decode"]["cache"]["arrays"]` lists
each array by name, with its kind (`depth`, and how deep, or `state`)
and its bytes.  What follows describes the engine with the GPT
family's cache:

- ONE compiled decode step owns the whole serving state: a fixed
  ring-buffer KV cache plus per-slot `pos/active/token/stop/eos/temp/
  key` vectors, passed as **donated** executor state (the PR-16
  donation idiom).
- The cache has ONE resident layout, `[layers, slots, heads, head_dim,
  max_len]`: K and V are stored transposed, cache depth minor.  That is
  the order the TPU keeps such an array in whatever its logical shape (a
  minor dimension of 64 would be padded to 128 lanes), and every reader
  and writer takes it as it lies: the decode step carries both stacked
  caches through its layer loop (never as a scan's `xs`/`ys`, which
  slices a layer out and stacks it back), `kv_append` writes the new
  column into the donated buffer through a Pallas call aliased to its
  input, `flash_decode` reads layer `l` of the stack through its
  BlockSpec, and a prefill drops its transposed K/V into the slot's
  region with one `dynamic_update_slice`.  The compiled step holds one
  copy of the cache and no copy, slice or re-layout of a layer of it
  (asserted on the compiled program by
  tests/test_kernels_tpu_lowering.py).  An XLA write of one column
  into this layout is not an option: a scatter or a per-slot
  `dynamic_update_slice` re-lays the whole stacked cache around the
  write.
- Requests **join and leave mid-decode**: a finished slot is released
  and refilled by the next queued request's prefill WITHOUT retracing —
  prefill runs at the PR-8 bucket shapes (prompt padded to a
  power-of-two bucket, causally masked so padding is exactly inert) and
  writes K/V straight into the slot's cache region; slot index, true
  prompt length and stop position are traced scalars.  Steady state
  therefore compiles exactly (1 decode step + 1 prefill per bucket),
  asserted through the compile ledger by the decode_serving_smoke row.
- Every decode step runs the full slot width; inactive slots compute
  harmlessly masked garbage (their writes land clamped inside their own
  slot's region and are overwritten by the next tenant's prefill or by
  the step that first attends the position — see _decode_step_impl).

Token-exactness: off the kernel path decode attention is the SAME code
generate() uses (kernels/attention.py decode_attention, reached through
resident_decode_attention), prefill is the same layer math
at bucket shape with MoE routed drop-free (cap = cohort size), and
padded/causally-dead columns underflow to exact f32 zeros — so a
request decoded through slots, including one that joins mid-stream
into a previously-released slot, emits token-for-token what
generate() emits (greedy; asserted dense + MoE in
tests/test_decode_serving.py).

**The loop runs one decode step ahead of its host** (ISSUE 32).  A
step's inputs never leave the device (`token/pos/active/stop/eos` are
in the donated state, and `_decode_step_impl` itself drops a slot that
reached its stop or its eos), so step n+1 needs nothing the host learns
from step n.  The loop thread (`_run_ahead`) therefore keeps one step
queued on the device behind the one that is running, and reads an
answer only after the work that follows it has been enqueued.  Going
into an iteration the device's queue holds step n (running), the
prefills admitted last time and step n+1; the iteration collects n
(`engine.decode_wait`) and emits it (`engine.emit`), collects those
prefills and books their first tokens (`engine.prefill_wait`,
`engine.prefill_book`), sweeps budgets (`engine.sweep`), listens
(`engine.listen_wait`: it waits on the submit condition while the
device works on n+1, and each arrival is admitted, `engine.admit`, and
its prefill enqueued, `engine.prefill_host`, at once, behind n+1), and
enqueues step n+2 (`engine.decode_host`) with the kill mask and the
slot -> request snapshot of that moment, which stay with the step until
it is emitted: all inside one `engine.step` span.  `step()`, by hand,
runs the same primitives (`_enqueue_prefill`, `_enqueue_step`,
`_collect`) serially: every program is answered before the next goes
out, and nothing is in flight when it returns.

What holds in either order.  (1) Every time is read when the token is
on the host: `first_token_t`, `last_token_t` and what `note_prefill`,
`note_decode_step` and `note_token_latency` get take the clock after
the answer is fetched, so a first token pays its prefill's device time
and the step it queued behind.  (2) A request that answers a token of
step n is prefilled directly behind step n+1: after the emit the loop
listens, and sends step n+2 only when the device's queue is about to
run dry (`_deadline`: when n+1 started, plus what the fastest of the
last steps took, less twice what the dearest of the last enqueues cost
the host, all measured by the engine on its own clock; no flag or
field sets it), or at once when there is nothing to listen for (no
slot free, or nothing in flight to hide the wait behind).  A slot that
ended in step n is inactive in step n+1 by the device's own state: it
gains no token from the step queued behind it and sits out that one
step, no more.  (3) A program in flight (`_Flight`) belongs to the
requests of its snapshot from its enqueue until its answer is
collected: the watchdog tracks it all that time (the fetch included), a
budget that passes meanwhile resolves its request once (`kill` reaches
the device with the next step that goes out, an answer for a resolved
request is dropped), and a wedged or failed program breaks the engine
and resolves queued, resident and in-flight requests exactly once; on
`close()` the loop answers what it has in flight before it leaves.
`DecodeStats.summary()["decode"]["lookahead"]` says whether it engages:
decode steps, those enqueued while the step before them was still
unanswered (`ahead`, also on `engine.decode_wait`), and admissions that
landed behind the step running at their submission (`in_time`) or
behind a later one (`late`, also on `engine.prefill_wait`).  Every
program's device time is read from its own answers (`_answer`), with no
profiler: `summary()["decode"]["device"]` holds it by kind (`prefill_s`,
`decode_s`, `prefill_share`), the share of the positions the prefills
computed that were padding (`padding_share`), and what the decode steps
waited behind (`behind_ms`: the prefills between two steps, p50 / p90 /
p99 / max).

Hardening is the PR-8 stack rewired for token granularity: per-TOKEN
deadline budgets (TTFT included) feeding the outcome ledger
(requests == sum(outcomes) stays the invariant), the circuit breaker
around both dispatch kinds, the hang watchdog tracking each program in
flight (a wedged decode step gets a flight-recorder post-mortem and its
requests fail classified — the donated state is inside the wedged
call, so the engine marks itself broken rather than pretend the cache
survived), and DecodeStats publishing tokens/s, TTFT and inter-token
percentiles (exact nearest-rank), slot occupancy and the
prefill/decode split to /metrics and the telemetry stream.
"""

import threading
import time
from collections import deque

import numpy as np

from .. import flags
from ..profiler import RecordEvent
from ..resilience import faultinject
from ..resilience.breaker import CircuitBreaker
from ..resilience.retry import RetryPolicy, call_with_retry
from ..resilience.taxonomy import DeadlineExceeded
from .runtime import QueueFullError, ServingClosedError, ServingFuture
from .stats import DecodeStats
from .watchdog import HangWatchdog, WatchdogStall

__all__ = ["DecodeEngine", "DecodeConfig", "EngineBrokenError",
           "default_prompt_buckets", "QueueFullError",
           "ServingClosedError", "WatchdogStall", "DeadlineExceeded"]

_DEFAULT_RETRY = object()


def _fr():
    from ..monitor import flight_recorder

    return flight_recorder


def _mon():
    from .. import monitor

    return monitor


def _tracing():
    from ..monitor import tracing

    return tracing


def default_prompt_buckets(max_len):
    """Power-of-two prompt buckets 16..max_len (PR-8 bucketing shape):
    one prefill program per bucket, compiled once."""
    out = []
    b = 16
    while b <= max_len:
        out.append(b)
        b *= 2
    return tuple(out) or (int(max_len),)


class DecodeConfig:
    """Knobs for one decode engine; flag-backed like ServingConfig."""

    def __init__(self, slots=None, max_len=None, buckets=None,
                 max_queue_depth=None, default_token_budget_s=None,
                 retry_policy=_DEFAULT_RETRY, breaker_threshold=5,
                 breaker_cooldown_s=5.0, watchdog_stall_s=None,
                 watchdog_poll_s=None, prewarm=True,
                 label="decode", clock=time.monotonic):
        self.slots = int(slots if slots is not None
                         else flags.flag("decode_slots"))
        self.max_len = int(max_len if max_len is not None
                           else flags.flag("decode_max_len"))
        if self.slots < 1 or self.max_len < 2:
            raise ValueError("need slots >= 1 and max_len >= 2")
        self.buckets = tuple(sorted(set(
            int(b) for b in (buckets
                             or default_prompt_buckets(self.max_len)))))
        if any(b < 1 or b > self.max_len for b in self.buckets):
            raise ValueError(
                f"buckets {self.buckets} must lie in [1, max_len="
                f"{self.max_len}]")
        self.max_queue_depth = int(
            max_queue_depth if max_queue_depth is not None
            else flags.flag("serving_queue_depth"))
        if default_token_budget_s is None:
            default_token_budget_s = \
                flags.flag("decode_token_budget_s") or None
        self.default_token_budget_s = default_token_budget_s
        if retry_policy is _DEFAULT_RETRY:
            retry_policy = RetryPolicy(max_retries=2, base_delay=0.02,
                                       max_delay=0.5, seed=0)
        self.retry_policy = retry_policy          # None disables retry
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self.watchdog_stall_s = float(
            watchdog_stall_s if watchdog_stall_s is not None
            else flags.flag("serving_watchdog_stall_s"))
        self.watchdog_poll_s = watchdog_poll_s
        self.prewarm = bool(prewarm)
        self.label = label
        self.clock = clock


class _DecodeRequest:
    __slots__ = ("prompt", "max_new", "eos_id", "temperature",
                 "token_budget_s", "rid", "future", "tokens",
                 "enqueue_t", "last_token_t", "first_token_t", "slot",
                 "bucket", "kill", "key", "trace", "qspan", "dspan",
                 "seen_step")

    def __init__(self, prompt, max_new, eos_id, temperature,
                 token_budget_s, rid, bucket, key):
        self.prompt = prompt              # np.int32 [len]
        self.max_new = max_new
        self.eos_id = eos_id              # int or None
        self.temperature = temperature
        self.token_budget_s = token_budget_s
        self.rid = rid
        self.bucket = bucket
        self.key = key                    # np.uint32 [2]
        self.future = ServingFuture()
        self.tokens = []
        self.enqueue_t = None
        self.last_token_t = None          # engine clock of newest token
        self.first_token_t = None
        self.slot = None
        self.kill = False                 # expired while slot-resident
        self.seen_step = 0                # decode steps answered at submit
        # request-scoped trace context (monitor/tracing.py); None when
        # FLAGS_request_tracing is off
        self.trace = None
        self.qspan = None                 # queue-wait span
        self.dspan = None                 # slot-resident decode span

    def next_deadline(self):
        """Per-token budget: the NEXT token (the first included — TTFT
        counts queue wait) must land within budget of the previous."""
        if self.token_budget_s is None:
            return None
        anchor = self.last_token_t if self.last_token_t is not None \
            else self.enqueue_t
        return anchor + self.token_budget_s

    def expired(self, now):
        d = self.next_deadline()
        return d is not None and now >= d


class EngineBrokenError(RuntimeError):
    """The engine lost its donated device state (a wedged or failed
    decode step) and cannot continue; submit() fails fast."""


class _Flight:
    """One program on the device's queue, from its enqueue until its
    answer is collected.  It belongs to the requests it carries
    (`requests`: a prefill's one, a decode step's residents of the
    moment it went out): their budgets, the watchdog's entry and a
    failure are theirs for as long as it is in flight."""

    __slots__ = ("op", "meta", "requests", "wd", "stalled", "launched",
                 "done", "state", "results", "error", "launched_t",
                 "ready_t", "done_t", "device_s", "behind_s", "behind",
                 "slot", "req", "admit_t", "pspan", "late", "slot_reqs",
                 "kill", "ahead")

    def __init__(self, op, meta, requests, **own):
        self.op = op                      # "prefill" or "decode"
        self.meta = meta
        self.requests = requests
        self.launched = threading.Event()  # the launch returned
        self.done = threading.Event()      # the answer is on the host
        self.state = self.results = self.error = None
        # engine clock, the worker's: the launch returned; the program's
        # results were ready on the device; they were on the host
        self.launched_t = self.ready_t = self.done_t = None
        self.device_s = None              # `_answer`'s reading
        for k, v in own.items():
            setattr(self, k, v)


# ---------------------------------------------------------------------------
# device programs (module-level so each engine jits exactly two shapes)
# ---------------------------------------------------------------------------

# what the engine keeps for each slot beside the model's cache
_SLOT_KEYS = ("pos", "active", "token", "stop", "eos", "temp", "key")


def _cache_of(state):
    return {k: v for k, v in state.items() if k not in _SLOT_KEYS}


def _decode_step_impl(state, trees, kill, cfg):
    """One full-width decode step over every slot.  `cfg` is the model's
    side of the seam: `cfg.decode` runs the layers and writes the cache,
    `cfg.head` gives the logits; the rest is the engine's.

    Inactive (or host-killed) slots still flow through the math — their
    writes land at their stale position CLAMPED inside their own slot's
    cache region, which is safe: a position is only ever attended on or
    after the step that first writes it (the live mask is `col <= pos`
    and the write at `pos` happens before the attend), and a refilling
    prefill overwrites the prompt region wholesale.

    Returns (state, tokens [S], was active [S], still active [S], the
    model's counters)."""
    import jax
    import jax.numpy as jnp

    active = jnp.logical_and(state["active"], jnp.logical_not(kill))
    pos = state["pos"]
    tok = state["token"]
    cache, hidden, counters = cfg.decode(trees, _cache_of(state), tok, pos,
                                         active)
    logits = cfg.head(trees, hidden)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = state["temp"]
    scaled = logits.astype(jnp.float32) \
        / jnp.maximum(temp, 1e-6)[:, None]
    sampled = jax.vmap(
        lambda kk, p, lg: jax.random.categorical(
            jax.random.fold_in(kk, p), lg))(
        state["key"], pos, scaled).astype(jnp.int32)
    nxt = jnp.where(temp > 0.0, sampled, greedy)
    new_pos = pos + 1
    done = jnp.logical_or(
        jnp.logical_and(state["eos"] >= 0, nxt == state["eos"]),
        new_pos >= state["stop"])
    still = jnp.logical_and(active, jnp.logical_not(done))
    out = dict(state)
    out.update(cache)
    out.update(
        pos=jnp.where(active, new_pos, pos),
        token=jnp.where(active, nxt, tok),
        active=still)
    return out, nxt, active, still, counters


def _prefill_impl(state, trees, prompt, true_len, slot, stop, eos,
                  temp, key, cfg):
    """Prefill one request into one slot at a static bucket shape:
    `cfg.prefill` runs the prompt [1, bucket] (zero-padded; the model
    keeps the padding inert) and writes the slot's region of the cache.
    true_len/slot/stop are traced scalars: refilling any slot with any
    prompt length inside the bucket reuses this one program.

    Returns (state, first token, active, the model's counters)."""
    import jax
    import jax.numpy as jnp

    cache, hidden, counters = cfg.prefill(trees, _cache_of(state), prompt,
                                          true_len, slot)
    logits = cfg.head(trees, hidden)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
    sampled = jax.random.categorical(key, scaled,
                                     axis=-1).astype(jnp.int32)
    first = jnp.where(temp > 0.0, sampled, greedy)[0]
    active = jnp.logical_and(
        true_len < stop,
        jnp.logical_not(jnp.logical_and(eos >= 0, first == eos)))
    out = dict(state)
    out.update(cache)
    out.update(
        pos=state["pos"].at[slot].set(true_len),
        token=state["token"].at[slot].set(first),
        active=state["active"].at[slot].set(active),
        stop=state["stop"].at[slot].set(stop),
        eos=state["eos"].at[slot].set(eos),
        temp=state["temp"].at[slot].set(temp),
        key=state["key"].at[slot].set(key))
    return out, first, active, counters


def _on_host(results):
    """A program's results, counters and all, fetched: every transfer
    started before the first is waited for."""
    import jax

    for leaf in jax.tree.leaves(results):
        getattr(leaf, "copy_to_host_async", lambda: None)()
    return jax.tree.map(np.asarray, results)


def _expert_load(cfg, counters, tokens):
    """The span attributes of a program's expert counters: the
    assignments that fell on the experts held here (`expert_counts`),
    the fullest one's, and the expert layers that ran over
    `routed_experts`' kept rows (`expert_layers_kept`) beside those of a
    program over `tokens` tokens whose shape has that case
    (`cfg.expert_layers`).  A model without experts gives none."""
    counts = counters.get("expert_counts")
    if counts is None:
        return {}
    return {"expert_tokens": int(counts.sum()),
            "expert_load_max": int(counts.max()),
            "expert_layers_kept": int(counters["expert_layers_kept"]),
            "expert_layers": cfg.expert_layers(tokens)}


def _lengths(slot_reqs, active):
    """The cached positions each slot active in a decode step held: a
    request's prompt and the tokens it held when the step was answered,
    the step's own position among them.  From what the host holds:
    nothing is fetched."""
    return [r.prompt.size + len(r.tokens)
            for r, live in zip(slot_reqs, active) if live and r is not None]


def _cache_walk(cfg, config, lengths):
    """The span attributes of what a decode step's attention walked, for
    a model that says so (`cfg.cache_walk` of the step's `_lengths`)."""
    walk = getattr(cfg, "cache_walk", None)
    if walk is None:
        return {}
    return walk(lengths, config.slots, config.max_len)


def _cache_reads(cfg, lengths):
    """The span attributes of the cached positions a program read in one
    layer of each kind, for a model whose caches differ in depth
    (`cfg.cache_reads` of a decode step's `_lengths`, or of a prefill's
    prompt length alone), summed over the slots.  A model with one cache
    gives none."""
    reads = getattr(cfg, "cache_reads", None)
    return {} if reads is None else reads(lengths)


def _state_traffic(slot_state_bytes, counters, active=None):
    """The span attributes of a program's traffic with the states:
    `state_bytes`, the states of the slots `active` in a decode step read
    and written, or a prefill's own slot's written, as Python ints (a
    step of 16 slots x 40 layers of models/brumby.py passes 2**31); and
    a prefill's `chunks`.  A model without a state gives none."""
    out = {}
    if slot_state_bytes:
        out["state_bytes"] = slot_state_bytes if active is None \
            else 2 * int(active.sum()) * slot_state_bytes
    if "chunks" in counters:
        out["chunks"] = int(counters["chunks"])
    return out


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class DecodeEngine:
    """See module docstring.  `auto_start=False` keeps the loop thread
    off so tests drive scheduling deterministically via `step()`, which
    answers every program before it returns; the loop thread runs one
    decode step ahead."""

    def __init__(self, model_or_params, config=None, auto_start=True,
                 **kw):
        self.config = cfg = config or DecodeConfig(**kw)
        if config is not None and kw:
            raise TypeError("pass either config= or keyword knobs, "
                            "not both")
        params = model_or_params
        if not hasattr(params, "trees"):
            # a models/gpt.py GPT layer: the seam's first implementation
            from ..models import generate as G

            params = G.build_decode_params(params)
        self.params = params
        if cfg.max_len > params.cfg.max_seq_len:
            raise ValueError(
                f"max_len {cfg.max_len} exceeds the model's "
                f"max_seq_len {params.cfg.max_seq_len}")
        self._trees = params.trees
        self.stats = DecodeStats(cfg.label, slots=cfg.slots)
        self.breaker = CircuitBreaker(
            failure_threshold=cfg.breaker_threshold,
            cooldown_s=cfg.breaker_cooldown_s, clock=cfg.clock,
            name=cfg.label)
        self.stats.attach_breaker(self.breaker)
        self.watchdog = HangWatchdog(
            cfg.watchdog_stall_s, poll_s=cfg.watchdog_poll_s,
            clock=cfg.clock, stats=self.stats, label=cfg.label,
            pre_dump=self.emit_telemetry, on_poll=self.sweep_expired)
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._queue = deque()
        self._slot_req = [None] * cfg.slots
        # slot -> request whose prefill is on the device's queue and not
        # yet answered: the slot is taken, its first token not booked
        self._prefilling = {}
        # programs on the device's queue, oldest first (loop thread's)
        self._flights = deque()
        self._steps_enqueued = 0
        self._steps_answered = 0
        # what the loop measures about itself to time its listening:
        # the device's time for its last decode steps, what enqueueing
        # one cost the host, and when the device was last seen to end a
        # program (engine clock, the fetching thread's reading)
        self._pace = deque(maxlen=8)
        self._launch_cost = deque(maxlen=8)
        self._freed_t = self._ready_t = float("-inf")
        # the prefills answered since the last decode step: their summed
        # device time and their number (what the next step waited behind)
        self._behind = (0.0, 0)
        self._live = set()
        self._rid = 0
        self._closed = False
        self._broken = False
        self._loop_thread = None
        self._build_programs()
        self._state = self._fresh_state()
        self.prewarmed = self._prewarm() if cfg.prewarm else 0
        if auto_start:
            self.start()

    # -- compiled programs ---------------------------------------------
    def _build_programs(self):
        import jax

        mon = _mon()
        cfg = self.config
        dec_cfg = self.params.cfg

        # jitted from named functions, so that a device trace reads
        # `jit_decode_step` and `jit_prefill_b<bucket>` (a jitted
        # functools.partial reads `jit__unknown`)
        def named(name, impl):
            def fn(*args):
                return impl(*args, cfg=dec_cfg)

            fn.__name__ = fn.__qualname__ = name
            return jax.jit(fn, donate_argnums=(0,))

        self._step_fn = mon.instrument_jit(
            named("decode_step", _decode_step_impl),
            key=f"{cfg.label}.decode_step")
        self._prefill_fns = {}
        for b in cfg.buckets:
            # one program and one instrumented wrapper per bucket: the
            # ledger wrappers are signature-pinned, and per-bucket keys
            # make the "1 prefill compile per bucket" assertion a
            # ledger query
            self._prefill_fns[b] = mon.instrument_jit(
                named(f"prefill_b{b}", _prefill_impl),
                key=f"{cfg.label}.prefill_b{b}")

    def _fresh_state(self):
        import jax.numpy as jnp

        cfg = self.config
        cache = self.params.cfg.cache_arrays(cfg.slots, cfg.max_len)
        taken = sorted(set(cache) & set(_SLOT_KEYS))
        if taken:
            raise ValueError(
                f"the model's cache arrays {taken} carry names the engine "
                f"keeps for its own per-slot vectors {_SLOT_KEYS}")
        states = getattr(self.params.cfg, "cache_states", ())
        self.stats.note_cache(self.params.cfg.cache_kind, cache,
                              states=states)
        self._slot_state_bytes = sum(
            int(a.size * a.dtype.itemsize) for n, a in cache.items()
            if n in states) // cfg.slots
        return {
            **cache,
            "pos": jnp.zeros(cfg.slots, jnp.int32),
            "active": jnp.zeros(cfg.slots, bool),
            "token": jnp.zeros(cfg.slots, jnp.int32),
            "stop": jnp.zeros(cfg.slots, jnp.int32),
            "eos": jnp.full((cfg.slots,), -1, jnp.int32),
            "temp": jnp.zeros(cfg.slots, jnp.float32),
            "key": jnp.zeros((cfg.slots, 2), jnp.uint32),
        }

    def _prewarm(self):
        """Compile every program this engine will ever run (1 decode
        step + 1 prefill per bucket) against throwaway state, then
        rebuild the state zeros — serving must start from an empty
        cache.  The warm state is released first, so the device never
        holds two caches."""
        import jax

        cfg = self.config
        n = 0
        for b in cfg.buckets:
            self._state, *_ = self._prefill_fns[b](
                self._state, self._trees,
                np.zeros((1, b), np.int32), np.int32(1), np.int32(0),
                np.int32(1), np.int32(-1), np.float32(0.0),
                np.zeros(2, np.uint32))
            n += 1
        warm, *_ = self._step_fn(
            self._state, self._trees, np.zeros(cfg.slots, bool))
        # a buffer that a running program writes cannot be freed: wait
        # for the step, free the warm state, and only then build the
        # fresh one
        self._state = None
        for leaf in jax.tree.leaves(jax.block_until_ready(warm)):
            leaf.delete()
        self._state = self._fresh_state()
        return n + 1

    # -- lifecycle ------------------------------------------------------
    def start(self):
        with self._lock:
            if self._loop_thread is not None or self._closed:
                return
            self._loop_thread = threading.Thread(
                target=self._loop, name=f"{self.config.label}-engine",
                daemon=True)
            self._loop_thread.start()
        self.watchdog.start()

    def close(self):
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            t = self._loop_thread
        if t is not None:
            t.join(timeout=10.0)
        err = ServingClosedError("decode engine closed")
        with self._lock:
            leftovers = list(self._live)
            self._queue.clear()
            self._slot_req = [None] * self.config.slots
            self._prefilling.clear()
            # the loop answered what it had in flight before it left; a
            # loop that did not leave (wedged) keeps nothing tracked
            flights = list(self._flights)
            self._flights.clear()
        for flight in flights:
            self.watchdog.untrack(flight.wd)
        for req in leftovers:
            self._resolve_error(req, err, "cancelled")
        self.watchdog.stop()
        self.emit_telemetry()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- submission -----------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens, eos_id=None,
               temperature=0.0, token_budget_s=None, seed=None,
               traceparent=None):
        """Enqueue one generation request; returns a ServingFuture that
        resolves to the np.int32 token array (length max_new_tokens,
        or shorter if eos_id fires).  `traceparent` optionally joins an
        external W3C trace when FLAGS_request_tracing is on."""
        cfg = self.config
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must have at least one token")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new > cfg.max_len:
            raise ValueError(
                f"prompt+new = {prompt.size + max_new} exceeds the "
                f"engine's max_len {cfg.max_len}")
        bucket = next((b for b in cfg.buckets if b >= prompt.size),
                      None)
        if bucket is None:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the largest "
                f"prefill bucket {cfg.buckets[-1]}")
        if token_budget_s is None:
            token_budget_s = cfg.default_token_budget_s
        with self._lock:
            if self._closed:
                raise ServingClosedError("decode engine is closed")
            if self._broken:
                raise EngineBrokenError(
                    "decode engine lost its device state (stalled or "
                    "failed step); build a fresh engine")
            # started after the closed/broken gates (those raise
            # without a ledger outcome, so no tree must exist for
            # them) but before the queue-full gate (rejected IS a
            # ledger outcome and its tree must close as "rejected")
            trace = _tracing().get().start_request(
                f"decode.request/{cfg.label}", label=cfg.label,
                traceparent=traceparent,
                attrs={"prompt_len": int(prompt.size),
                       "max_new": max_new})
            if len(self._queue) >= cfg.max_queue_depth:
                self.stats.note_outcome("rejected")
                if trace is not None:
                    trace.annotate(trace.root, "rejected: queue full",
                                   depth=len(self._queue))
                    trace.finish("rejected")
                raise QueueFullError(
                    f"decode queue at depth {cfg.max_queue_depth}")
            self._rid += 1
            rid = self._rid
            key = np.asarray(
                np.random.RandomState(
                    seed if seed is not None else rid).randint(
                    0, 2 ** 31, size=2), np.uint32)
            req = _DecodeRequest(prompt, max_new, eos_id,
                                 float(temperature),
                                 token_budget_s, rid, bucket, key)
            req.enqueue_t = cfg.clock()
            req.seen_step = self._steps_answered
            if trace is not None:
                trace.rid = rid
                req.trace = trace
                req.qspan = trace.child("queue", "queue")
            self._queue.append(req)
            self._live.add(req)
            self.stats.note_admitted(len(self._queue))
            self._cond.notify_all()
        return req.future

    # -- budget sweep (watchdog poll + loop tick) ----------------------
    def sweep_expired(self):
        """Shed queued requests and expire slot-resident ones whose
        per-token budget has passed — runs on the watchdog thread too,
        so budget expiry keeps resolving even while the engine thread
        is wedged inside a stalled step."""
        now = self.config.clock()
        shed, expired = [], []
        with self._lock:
            keep = deque()
            for req in self._queue:
                (shed.append if req.expired(now)
                 else keep.append)(req)
            self._queue = keep
            # slot-resident, or its prefill on the device's queue: mark
            # for the next step's kill mask
            for req in self._slot_req + list(self._prefilling.values()):
                if req is not None and not req.kill \
                        and not req.future.done() and req.expired(now):
                    req.kill = True
                    expired.append(req)
            depth = len(self._queue)
        for req in shed:
            self._resolve_error(
                req, DeadlineExceeded(
                    f"first token budget "
                    f"({req.token_budget_s * 1e3:.1f}ms/token) expired "
                    f"in queue", budget_s=req.token_budget_s),
                "shed")
        for req in expired:
            self._resolve_error(
                req, DeadlineExceeded(
                    f"per-token budget "
                    f"({req.token_budget_s * 1e3:.1f}ms/token) expired "
                    f"after {len(req.tokens)} tokens",
                    budget_s=req.token_budget_s),
                "expired")
        if shed or expired:
            self.stats.note_queue_depth(depth)
        return len(shed) + len(expired)

    # -- resolution -----------------------------------------------------
    def _resolve_ok(self, req, now):
        if req.future._set_result(np.asarray(req.tokens, np.int32)):
            self.stats.note_outcome("completed",
                                    latency_s=now - req.enqueue_t)
            if req.trace is not None:
                req.trace.finish("completed")
        with self._lock:
            self._live.discard(req)

    def _resolve_error(self, req, exc, outcome):
        if req.future._set_exception(exc):
            self.stats.note_outcome(outcome)
            if req.trace is not None:
                req.trace.finish(outcome)
        with self._lock:
            self._live.discard(req)

    def _mark_broken(self, why):
        """The donated device state rode a doomed call: drain EVERY
        unresolved request — queued, slot-resident AND carried by a
        program still in flight — as cancelled, so no future (and no
        trace) stays open behind a dead engine, and no program stays
        tracked.  Requests the failure already resolved (stalled/
        failed) are skipped by the idempotent resolve."""
        with self._lock:
            self._broken = True
            doomed = list(self._queue)
            self._queue.clear()
            doomed += [r for r in self._slot_req if r is not None]
            self._slot_req = [None] * self.config.slots
            self._prefilling.clear()
            flights = list(self._flights)
            self._flights.clear()
        for flight in flights:
            self.watchdog.untrack(flight.wd)
            doomed += flight.requests
        err = EngineBrokenError(f"decode engine broken: {why}")
        for req in doomed:
            self._resolve_error(req, err, "cancelled")
        _fr().note_event("decode_engine_broken", severe=True,
                         label=self.config.label, reason=why)

    # -- the device's queue --------------------------------------------
    def _enqueue(self, flight, call):
        """Put one program on the device's queue, behind what is there.
        A worker thread launches it (`call(state)`, under retry and the
        fault points) and then fetches its answer; the watchdog tracks
        it from here until `_answer` has collected it.  Returns once the
        launch has, with the program's state as the engine's; False
        when the launch wedged or failed (`_await`)."""
        cfg = self.config
        flight.wd, flight.stalled = self.watchdog.track(flight.meta)
        state = self._state

        def runner():
            try:
                def _call():
                    if faultinject.is_armed():
                        faultinject.check_transient()
                        faultinject.stall_point("decode.step")
                    return call(state)

                if cfg.retry_policy is not None:
                    out = call_with_retry(
                        _call, cfg.retry_policy,
                        on_retry=lambda *a: self.stats.note_retry())
                else:
                    out = _call()
                flight.state, *results = out
                flight.launched_t = cfg.clock()
                flight.launched.set()
                # the program's end on the device: its first result is
                # ready (all of them are, together), before the fetch
                results[0].block_until_ready()
                flight.ready_t = cfg.clock()
                flight.results = _on_host(results)
                flight.done_t = cfg.clock()
            except BaseException as e:  # noqa: BLE001
                flight.error = e
            finally:
                flight.launched.set()
                flight.done.set()
                with self._cond:           # a listening loop looks again
                    self._cond.notify_all()

        self._flights.append(flight)
        threading.Thread(target=runner, daemon=True,
                         name=f"{cfg.label}-dispatch").start()
        if not self._await(flight, flight.launched):
            return False
        self._state = flight.state
        return True

    def _await(self, flight, event):
        """Block the loop on `event` of `flight` (its launch or its
        answer), enforcing budgets meanwhile.  False when a program in
        flight wedged or failed: the requests of every program in
        flight are resolved (stalled/failed; those queued behind the
        doomed one ride the same lost state), everything else is
        cancelled and the engine is broken."""
        cfg = self.config
        while not event.wait(timeout=0.002):
            self.sweep_expired()
            wedged = next((f for f in self._flights
                           if f.stalled.is_set()), None)
            if wedged is not None:
                self._abandon(WatchdogStall(
                    f"decode {wedged.meta.get('op')} step in flight > "
                    f"{cfg.watchdog_stall_s}s"), "stalled",
                    "watchdog_stall")
                return False
        if flight.error is not None:
            e = flight.error
            _fr().note_event(
                "decode_dispatch_failed", label=cfg.label,
                error=f"{type(e).__name__}: {e}"[:200],
                **{k: v for k, v in flight.meta.items()
                   if k not in ("request_ids", "trace_ids")})
            self._abandon(e, "failed", "dispatch_failed")
            self.emit_telemetry()
            return False
        return True

    def _abandon(self, exc, outcome, why):
        self.breaker.note_failure(exc)
        for flight in list(self._flights):
            for req in flight.requests:
                self._resolve_error(req, exc, outcome)
        self._mark_broken(why)

    def _answer(self, flight):
        """Wait for the answer of `flight`, the oldest program in
        flight, and take it off the queue.  Returns the engine clock
        read once the answer is on the host (the time of its tokens),
        or None when the engine broke instead.

        Reads the program's device time, `flight.device_s`: from when it
        could start (the program before it ended, or it was launched) to
        when its results were ready on the device (`ready_t`, before the
        fetch, whose length differs by program).  The device's queue is
        FIFO, so the readings of consecutive programs telescope: their
        sum is the time from the first one's start to the last answer,
        less only the device's idle time.  The look-ahead's pace keeps
        its own reading, to the answer on the host (`done_t`).  A decode
        step also gets `behind_s` and `behind`: the device time and
        number of the prefills answered since the step before it, which
        ran between the two."""
        if not self._await(flight, flight.done):
            return None
        self._flights.popleft()
        self.watchdog.untrack(flight.wd)
        self.breaker.note_success()
        flight.device_s = flight.ready_t - max(self._ready_t,
                                               flight.launched_t)
        if flight.op == "decode":
            with self._lock:
                self._steps_answered += 1
            # the device's time for this step as the host sees it: from
            # when it could start to its answer on the host
            self._pace.append(flight.done_t - max(self._freed_t,
                                                  flight.launched_t))
            flight.behind_s, flight.behind = self._behind
            self._behind = (0.0, 0)
        else:
            self._behind = (self._behind[0] + flight.device_s,
                            self._behind[1] + 1)
        self._freed_t, self._ready_t = flight.done_t, flight.ready_t
        return self.config.clock()

    # -- scheduling -----------------------------------------------------
    def _free_slots_locked(self):
        return [i for i, r in enumerate(self._slot_req)
                if r is None and i not in self._prefilling]

    def _admit_locked(self):
        """Pick (slot, request) pairs to prefill this iteration: any
        free slot is refilled the moment the queue has work."""
        free = self._free_slots_locked()
        if not free or not self._queue:
            return []
        picks = []
        while free and self._queue:
            req = self._queue.popleft()
            if req.future.done():          # shed while queued
                continue
            picks.append((free.pop(0), req))
        self.stats.note_queue_depth(len(self._queue))
        return picks

    def _has_work_locked(self):
        return bool(self._queue or self._flights or any(self._slot_req))

    def step(self):
        """One engine iteration by hand: sweep budgets, refill free
        slots via prefill, then run one full-width decode step, each
        program answered before the next goes out.  Returns the number
        of device dispatches made (0 = idle), with all of it answered
        and emitted.  The loop thread runs the same primitives
        (`_enqueue_prefill`, `_enqueue_step`, `_collect`) in another
        order, one decode step ahead of its host: see `_run_ahead`.

        While a profiler session runs, an iteration with work is one
        `engine.step` span cut into its phases, here in this order:
        `engine.sweep`, `engine.admit`; for each admitted request
        `engine.prefill_host` (its program built and launched),
        `engine.prefill_wait` (until its first token is on the host),
        `engine.prefill_book`; then `engine.decode_host`,
        `engine.decode_wait`, `engine.emit` and, every 64th step,
        `engine.telemetry`.  The loop thread's iteration holds the same
        phases from `engine.decode_wait` of the oldest step round to
        `engine.decode_host` of the newest, and `engine.listen_wait`
        where it waited for submissions.  In the `*_wait` spans the
        host is blocked (on the device's answer, or listening); all the
        others are the host's own work.  `engine.prefill_wait` carries
        `bucket`, `true_len` (the prompt's length), `slot`, `rid`,
        `queue_wait_s` (submit to admission), `turnaround_s` (admission
        to the first token on the host), `late` and `device_s` (the
        program's device time, `_answer`); `engine.decode_wait` carries
        `active`, `ahead`, `device_s`, and `behind_s` and `behind`, the
        device time and number of the prefills that ran since the step
        before (a resident request's gap between two tokens is
        `device_s + behind_s` and any idle time); where the model counts
        expert assignments, both gain `expert_tokens`,
        `expert_load_max`, `expert_layers_kept` and `expert_layers`
        (the layers over `routed_experts`' kept rows, of those whose
        shape has that case), and where its caches differ in depth, the
        cached positions read in one layer of each (`live_full` and
        `live_window` of `models/afmoe.py`); a decode step also what the
        model's `cache_walk` counts (`latent_tiles` and `latent_grid` of
        `models/kimi_k2.py`; `full_tiles`, `full_grid`, `window_tiles`,
        `window_grid` of `models/afmoe.py`); where the model keeps a
        state, both gain `state_bytes` and a prefill its `chunks`.  What
        is known only once the answer is in (`turnaround_s`, `device_s`,
        `behind_s`, the counts) is in `spans()`; the trace's copy of the
        span was opened before (its `turnaround_s` runs to the launch's
        return)."""
        with self._lock:
            if not self._has_work_locked():
                return 0                   # nothing to do: no span
        with RecordEvent("engine.step"):
            return self._step()

    def _step(self):
        with RecordEvent("engine.sweep"):
            self.sweep_expired()
        dispatched = 0
        for _ in self._prefills(self._admit()):
            dispatched += 1
            self._collect()
        if not self._broken and self._enqueue_step() is not None:
            dispatched += 1
            self._collect()
        return dispatched

    def _run_ahead(self):
        """One iteration of the loop thread.  Going in, the device's
        queue holds step n (running), the prefills admitted last time
        and step n+1: collect n and emit it, collect those prefills,
        listen for submissions while the device works on n+1 (each
        arrival's prefill goes out at once, behind n+1), and enqueue
        n+2 when the queue is about to run dry.  An answer is only ever
        read after the work that follows it has been enqueued, so the
        device waits for nothing the host does in between."""
        newest = max((i for i, f in enumerate(self._flights)
                      if f.op == "decode"), default=0)
        for _ in range(newest):            # all that precedes step n+1
            if not self._collect():
                return 1
        with RecordEvent("engine.sweep"):
            self.sweep_expired()
        enqueued = self._listen()
        if self._broken or self._closed:   # the loop answers what is out
            return 1
        if self._enqueue_step() is not None:
            enqueued += 1
        elif not self._broken:
            # nothing goes out behind what is in flight (no slot would
            # gain by a step, or the breaker is open): answer it now
            while self._flights and self._collect():
                newest += 1
        return newest + enqueued

    def _admit(self):
        with RecordEvent("engine.admit"):
            with self._lock:
                return [] if self._broken else self._admit_locked()

    def _prefills(self, picks):
        """Enqueue the prefills of `picks` one by one, yielding each as
        it goes out; the caller may collect it before the next."""
        pending = deque(picks)
        while pending:
            if self._broken:
                err = EngineBrokenError(
                    "decode engine broke mid-admission")
                for _, r in pending:
                    self._resolve_error(r, err, "cancelled")
                return
            if not self.breaker.allow():
                # breaker open: requeue the whole remainder and let
                # budgets shed; the cooldown probe reopens admission.
                # A requeued request keeps its SAME trace (its queue
                # span never ended — requeued wait keeps accruing);
                # the detour is a point annotation, not a new tree.
                with self._lock:
                    for _, r in reversed(pending):
                        if r.trace is not None:
                            r.trace.annotate(r.trace.root,
                                             "breaker_requeue")
                        self._queue.appendleft(r)
                return
            flight = self._enqueue_prefill(*pending.popleft())
            if flight is not None:
                yield flight

    def _listen(self):
        """Admit what is queued and, while the device works on the
        newest step, wait on the submit condition and admit each
        arrival at once, its prefill landing behind that step.  Ends
        when there is nothing to listen for (no slot free; nothing in
        flight that hides the wait) or when the next step has to go
        out (`_deadline`).  Returns the prefills enqueued."""
        cfg = self.config
        deadline = self._deadline()
        enqueued = 0
        while not self._broken:
            with self._cond:
                while True:
                    free = self._free_slots_locked()
                    if free and self._queue:
                        break
                    if self._closed or not free or not self._flights \
                            or self._flights[-1].done.is_set() \
                            or cfg.clock() >= deadline:
                        return enqueued
                    with RecordEvent("engine.listen_wait"):
                        self._cond.wait(deadline - cfg.clock())
            for _ in self._prefills(self._admit()):
                enqueued += 1
        return enqueued

    def _deadline(self):
        """When the next decode step has to be on its way so that the
        device's queue does not run dry, from what the engine measured
        of itself: the newest step in flight started when the program
        before it ended (or when it was launched), takes what the
        fastest of the last steps took, and enqueueing the next one
        costs the host what the dearest of the last enqueues cost,
        allowed for twice.  No reading yet, or no step in flight: at
        once."""
        steps = [f for f in self._flights if f.op == "decode"]
        if not steps or not self._pace:
            return float("-inf")
        return max(self._freed_t, steps[-1].launched_t) \
            + min(self._pace) - 2 * max(self._launch_cost)

    def _enqueue_prefill(self, slot, req):
        """Launch `req`'s prefill into `slot`, behind what is on the
        device's queue.  Returns its flight, or None when the launch
        broke the engine."""
        cfg = self.config
        # the engine turns to this request: its wait in the queue ends
        # here and its prefill's turnaround begins
        admit_t = cfg.clock()
        with RecordEvent("engine.prefill_host"):
            bucket = req.bucket
            prompt = np.zeros((1, bucket), np.int32)
            prompt[0, :req.prompt.size] = req.prompt
            true_len = req.prompt.size
            stop = true_len + req.max_new - 1   # position of the last token
            meta = {"op": "prefill", "bucket": bucket, "slot": slot,
                    "rid": req.rid}
            pspan = None
            if req.trace is not None:
                meta["trace_id"] = req.trace.trace_id
                req.trace.end(req.qspan)
                pspan = req.trace.child(f"prefill/b{bucket}", "prefill",
                                        attrs={"bucket": bucket,
                                               "slot": slot})
            with self._lock:
                self._prefilling[slot] = req
                # it lands behind a later step than the one that was
                # running when it was submitted
                late = self._steps_enqueued > req.seen_step + 1
            flight = _Flight("prefill", meta, [req], slot=slot, req=req,
                             admit_t=admit_t, pspan=pspan, late=late)
            fn = self._prefill_fns[bucket]

            def call(state):
                return fn(state, self._trees, prompt, np.int32(true_len),
                          np.int32(slot), np.int32(stop),
                          np.int32(-1 if req.eos_id is None
                                   else req.eos_id),
                          np.float32(req.temperature), req.key)

            return flight if self._enqueue(flight, call) else None

    def _enqueue_step(self):
        """Launch one full-width decode step behind what is on the
        device's queue, if a slot can gain by one and the breaker
        allows it, with the kill mask and the slot -> request snapshot
        of this moment kept with it.  Returns its flight, or None."""
        cfg = self.config
        t0 = cfg.clock()
        with RecordEvent("engine.decode_host"):
            with self._lock:
                slot_reqs = list(self._slot_req)
                for slot, req in self._prefilling.items():
                    slot_reqs[slot] = req
            carried = [f for f in self._flights if f.op == "decode"]

            def gains(i, r):
                # tokens that the programs in flight will bring it: its
                # prefill's, and one a step that carries it (the host
                # cannot know of an eos on the way)
                coming = (r.first_token_t is None) + sum(
                    f.slot_reqs[i] is r for f in carried)
                return not r.future.done() \
                    and len(r.tokens) + coming < r.max_new

            gaining = [r for i, r in enumerate(slot_reqs)
                       if r is not None and not r.kill and gains(i, r)]
            kill = np.array([r is not None and r.kill for r in slot_reqs],
                            bool)
            # a killed slot needs one step that carries its kill
            unkilled = any(
                k and not any(f.kill[i] and f.slot_reqs[i] is slot_reqs[i]
                              for f in carried)
                for i, k in enumerate(kill))
            if not ((gaining or unkilled) and self.breaker.allow()):
                return None
            meta = {"op": "decode", "active": len(gaining),
                    "request_ids": [r.rid for r in slot_reqs
                                    if r is not None]}
            tids = [r.trace.trace_id for r in slot_reqs
                    if r is not None and r.trace is not None]
            if tids:
                # a wedged decode step's stall dump names every resident
                # request's trace
                meta["trace_ids"] = tids
            flight = _Flight(
                "decode", meta,
                [r for r in slot_reqs
                 if r is not None and not r.future.done()],
                slot_reqs=slot_reqs, kill=kill,
                # the step before it is still unanswered: this one runs
                # ahead of the host
                ahead=bool(carried) and not carried[-1].done.is_set())
            with self._lock:
                self._steps_enqueued += 1

            def call(state):
                return self._step_fn(state, self._trees, kill)

            if not self._enqueue(flight, call):
                return None
            self._launch_cost.append(flight.launched_t - t0)
        return flight

    def _collect(self):
        """Answer of the oldest program in flight: a prefill's first
        token is booked, a decode step's tokens are emitted.  False when
        the engine broke instead."""
        flight = self._flights[0]
        if flight.op == "prefill":
            return self._collect_prefill(flight)
        return self._collect_step(flight)

    def _collect_prefill(self, flight):
        req, slot = flight.req, flight.slot
        true_len = req.prompt.size
        with RecordEvent(
                "engine.prefill_wait", bucket=req.bucket, true_len=true_len,
                slot=slot, rid=req.rid,
                queue_wait_s=flight.admit_t - req.enqueue_t,
                turnaround_s=flight.launched_t - flight.admit_t,
                late=flight.late) as span:
            # the first token's time, for the stats and the budgets: its
            # answer is on the host
            now = self._answer(flight)
            if now is None:
                return False
            first, active, counters = flight.results
            load = _expert_load(self.params.cfg, counters, req.bucket)
            traffic = _state_traffic(self._slot_state_bytes, counters)
            span.attrs.update(
                load, **_cache_reads(self.params.cfg, [true_len]),
                **traffic, turnaround_s=now - flight.admit_t,
                device_s=flight.device_s)
        self.stats.note_experts(**load)
        self.stats.note_cache_walk(traffic)
        self.stats.note_device("prefill", flight.device_s,
                               true_len=true_len, bucket=req.bucket)
        with RecordEvent("engine.prefill_book"):
            self._prefill_book(slot, req, int(first), bool(active),
                               flight.pspan, now)
            self.stats.note_admission(flight.late)
        return True

    def _prefill_book(self, slot, req, first, active, pspan, now):
        req.first_token_t = req.last_token_t = now
        if req.trace is not None:
            req.trace.annotate(pspan, "first_token")
            req.trace.end(pspan)
        resident = req if active else None
        if req.future.done():              # expired mid-prefill
            self.stats.note_prefill(ttft_s=None, now=now)
            req.kill = True
        else:
            self.stats.note_prefill(ttft_s=now - req.enqueue_t, now=now)
            req.tokens.append(first)
            req.slot = slot
            if not active:                 # max_new == 1 or instant eos
                self._resolve_ok(req, now)
            elif req.trace is not None:
                # slot-resident decode: one span from slot entry to
                # the last token, per-token progress as annotations
                req.dspan = req.trace.child("decode", "decode",
                                            attrs={"slot": slot})
        with self._lock:
            self._prefilling.pop(slot, None)
            self._slot_req[slot] = resident

    def _collect_step(self, flight):
        with RecordEvent("engine.decode_wait",
                         active=flight.meta["active"],
                         ahead=flight.ahead) as span:
            now = self._answer(flight)
            if now is None:
                return False
            tokens, was_active, still, counters = flight.results
            load = _expert_load(self.params.cfg, counters,
                                self.config.slots)
            lengths = _lengths(flight.slot_reqs, was_active)
            walk = _cache_walk(self.params.cfg, self.config, lengths)
            traffic = _state_traffic(self._slot_state_bytes, counters,
                                     was_active)
            span.attrs.update(
                load, **_cache_reads(self.params.cfg, lengths), **walk,
                **traffic, device_s=flight.device_s,
                behind_s=flight.behind_s, behind=flight.behind)
        self.stats.note_experts(**load)
        self.stats.note_cache_walk({**walk, **traffic})
        self.stats.note_device("decode", flight.device_s,
                               behind_s=flight.behind_s)
        with RecordEvent("engine.emit"):
            self._emit(flight.slot_reqs, tokens, was_active, still, now)
            self.stats.note_lookahead(flight.ahead)
        if self.stats.decode_steps % 64 == 0:
            with RecordEvent("engine.telemetry"):
                self.emit_telemetry()
        return True

    def _emit(self, slot_reqs, tokens, was_active, still, now):
        """Hand the step's tokens to their requests (`slot_reqs`: the
        snapshot the step went out with), resolve the finished ones and
        release their slots."""
        emitted = 0
        for i, req in enumerate(slot_reqs):
            if req is None:
                continue
            if not was_active[i]:
                # killed (budget-expired), raced to done, or ended in
                # the step before this one, which was already queued
                # behind it: release
                with self._lock:
                    if self._slot_req[i] is req:
                        self._slot_req[i] = None
                continue
            if not req.future.done():
                req.tokens.append(int(tokens[i]))
                if req.last_token_t is not None:
                    self.stats.note_token_latency(
                        now - req.last_token_t)
                req.last_token_t = now
                emitted += 1
                if req.trace is not None:
                    req.trace.annotate(req.dspan, "token",
                                       n=len(req.tokens))
                if not still[i]:
                    if req.trace is not None:
                        req.trace.end(req.dspan)
                    self._resolve_ok(req, now)
            if not still[i]:
                with self._lock:
                    if self._slot_req[i] is req:
                        self._slot_req[i] = None
        self.stats.note_decode_step(int(was_active.sum()), emitted,
                                    now=now)

    def _loop(self):
        while True:
            with self._cond:
                while not self._closed and not self._has_work_locked():
                    self._cond.wait(0.02)
                if self._broken or (self._closed and not self._flights):
                    return
                closed = self._closed
            try:
                with RecordEvent("engine.step"):
                    if closed:
                        # nothing stays uncollected behind a closed engine
                        while self._flights and self._collect():
                            pass
                        return
                    did = self._run_ahead()
            except Exception as e:  # noqa: BLE001
                _fr().note_event(
                    "decode_engine_error", severe=True,
                    label=self.config.label,
                    error=f"{type(e).__name__}: {e}"[:200])
                self._mark_broken("engine_loop_error")
                return
            if self._broken:
                return
            if not did:
                time.sleep(0.001)

    # -- observability --------------------------------------------------
    def emit_telemetry(self):
        """Push the freshest kind="serving" decode record onto the
        telemetry JSONL stream (no-op while telemetry is off).  With
        request tracing on, the record carries the label's
        attribution/SLO summary."""
        if not _mon().is_enabled():
            # to_record() sorts three rings of 8,192 samples: not on the
            # engine's thread for a record that would be thrown away
            return None
        rec = self.stats.to_record()
        store = _tracing().get()
        if store.enabled:
            s = store.summary(self.config.label)
            if s is not None:
                rec["tracing"] = s
        return _mon().record_serving(rec)

    def summary(self):
        return self.stats.summary()
