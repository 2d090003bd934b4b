"""Share of the expert layers that ran over `routed_experts`' kept rows,
in percent: over the engine's `engine.decode_wait` and
`engine.prefill_wait` spans of the traced stretch, 100 x the sum of
`expert_layers_kept` (the layers that ran over the kept rows, counted
by the program) over the sum of `expert_layers` (the layers whose shape
has that case, known from the shape).  Below 100 where the held
experts' assignments overflowed the kept rows and every row ran.
Reads nothing where the program records no such attribute (a program
that does not count its layers over the kept rows), or where no
program's shape has the case."""

from paddle_tpu import profiler

SPANS = ("engine.decode_wait", "engine.prefill_wait")


def read(run, name):
    spans = getattr(profiler, "spans", lambda prefix: [])("engine.")
    counted = [a for n, _, _, a in spans
               if n in SPANS and "expert_layers_kept" in a
               and "expert_layers" in a]
    layers = sum(a["expert_layers"] for a in counted)
    if not layers:
        return None
    return 100.0 * sum(a["expert_layers_kept"] for a in counted) / layers
