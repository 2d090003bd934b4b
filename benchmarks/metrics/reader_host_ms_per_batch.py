"""Host time `device_prefetch` spends on one batch, in milliseconds: the
program's `reader.source` spans (the source making the batch) and
`reader.device_put` spans (starting its transfer) of the traced stretch
(`paddle_tpu.profiler.spans`), summed, over the batches put.  It times
the reader from inside; `data_wait_pct.train` times the same call from
the loop.  Reads nothing where the program records no such spans."""

from paddle_tpu import profiler


def read(run, name):
    spans = getattr(profiler, "spans", lambda prefix: [])("reader.")
    batches = sum(n == "reader.device_put" for n, _, _, _ in spans)
    if not batches:
        return None
    return sum(e - s for _, s, e, _ in spans) / batches / 1e6
