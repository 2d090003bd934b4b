"""How uneven the held experts' load is: for each decode step of the
traced stretch the fullest held expert's assignments (summed over the
expert layers) over the mean held expert's, from the `expert_load_max`
and `expert_tokens` attributes of `engine.decode_wait`; the mean over
the steps that had any assignment."""

import statistics

from paddle_tpu import profiler

SPAN = "engine.decode_wait"


def read(run, name):
    spans = getattr(profiler, "spans", lambda prefix: [])("engine.")
    loads = [attrs for n, _, _, attrs in spans if n == SPAN
             and attrs.get("expert_load_max") and attrs.get("expert_tokens")]
    if not loads:
        return None
    held = run.cfg["n_routed_experts"]
    return statistics.mean(a["expert_load_max"] * held / a["expert_tokens"]
                           for a in loads)
