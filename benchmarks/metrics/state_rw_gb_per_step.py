"""Gigabytes of recurrent state a decode step read and wrote: the mean
`state_bytes` of the `engine.decode_wait` spans of the traced stretch
(the active slots' states, once each way).  Reads nothing where the
program records no such attribute (a model whose cache is columns).

It follows the slots' occupancy and the state's size and nothing else:
a step costs the same bytes at every context."""

from paddle_tpu import profiler

SPAN = "engine.decode_wait"


def read(run, name):
    spans = getattr(profiler, "spans", lambda prefix: [])("engine.")
    reads = [a["state_bytes"] for n, _, _, a in spans
             if n == SPAN and "state_bytes" in a]
    return sum(reads) / len(reads) / 1e9 if reads else None
