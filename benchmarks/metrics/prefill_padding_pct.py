"""Share of the positions the prefills computed that were padding, in
percent: over the program's `engine.prefill_wait` spans of the traced
stretch, (sum of `bucket` - sum of `true_len`) / sum of `bucket`.  A
prefill program runs its whole bucket whatever the prompt's length, so
this is the prefill layer's attempts that bought no outcome.  Both
attributes are fixed by the traffic and the engine's bucket ladder: the
reading moves with a new ladder or with chunked prefill, and NOT with a
prefill that skips the positions past `true_len` inside its bucket,
which leaves both as they are.  Reads nothing where the program records
no `true_len` (before ISSUE 38)."""

from paddle_tpu import profiler

SPAN = "engine.prefill_wait"


def read(run, name):
    spans = getattr(profiler, "spans", lambda prefix: [])(SPAN)
    fills = [a for _, _, _, a in spans if "true_len" in a and "bucket" in a]
    buckets = sum(a["bucket"] for a in fills)
    if not buckets:
        return None
    return 100.0 * (buckets - sum(a["true_len"] for a in fills)) / buckets
