"""Mean assignments on one held expert in one expert layer of one decode
step: the `expert_tokens` attribute of the engine's `engine.decode_wait`
spans of the traced stretch (assignments on the experts held here,
summed over the expert layers), over held experts and expert layers.
Reads nothing where the program records no such attribute."""

import statistics

from paddle_tpu import profiler

SPAN = "engine.decode_wait"


def decode_loads(attr):
    spans = getattr(profiler, "spans", lambda prefix: [])("engine.")
    return [attrs for n, _, _, attrs in spans
            if n == SPAN and attr in attrs]


def read(run, name):
    loads = decode_loads("expert_tokens")
    if not loads:
        return None
    per_step = run.cfg["n_routed_experts"] * run.config.expert_layers(run.cfg)
    return statistics.mean(a["expert_tokens"] for a in loads) / per_step
