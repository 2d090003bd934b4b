"""Data pipeline.

Parity targets:
- DataLoader.from_generator (/root/reference/python/paddle/fluid/reader.py:179)
- reader decorators (python/paddle/reader/decorator.py: batch/shuffle/map/...)
- the C++ double-buffered device feed (operators/reader/buffered_reader.cc)
  becomes a background-thread prefetcher handing ready host batches to the
  jitted step (device transfer overlaps with compute via jax async dispatch).
"""

import itertools
import queue
import random as _random
import threading

import numpy as np

__all__ = ["DataLoader", "PyReader", "batch", "shuffle", "buffered", "map_readers",
           "chain", "compose", "firstn", "cache", "device_prefetch"]


# ---------------------------------------------------------------------------
# reader decorators (python/paddle/reader/decorator.py parity)
# ---------------------------------------------------------------------------

def batch(reader, batch_size, drop_last=False):
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched


def shuffle(reader, buf_size, seed=None):
    def shuffled():
        rng = _random.Random(seed)
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) >= buf_size:
                rng.shuffle(buf)
                yield from buf
                buf = []
        rng.shuffle(buf)
        yield from buf

    return shuffled


def buffered(reader, size):
    """Background-thread prefetch (decorator.py buffered).  The
    consumer side is instrumented: buffer occupancy lands on the
    `reader.prefetch_depth` gauge at every get (starvation shows as a
    flatline at 0 on /metrics and the chrome counter track), and the
    blocking get itself is charged to the goodput ledger's data_wait
    bucket while one is active."""

    class _End:
        pass

    def buffered_reader():
        from .. import monitor
        from ..monitor import goodput

        depth = monitor.gauge("reader.prefetch_depth")
        q = queue.Queue(maxsize=size)

        def worker():
            try:
                for item in reader():
                    q.put(item)
            finally:
                q.put(_End)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        while True:
            depth.set(q.qsize())
            gled = goodput.active()
            if gled is None:
                item = q.get()
            else:
                with gled.span("data_wait"):
                    item = q.get()
            if item is _End:
                break
            yield item

    return buffered_reader


def map_readers(func, *readers):
    def reader():
        its = [r() for r in readers]
        for items in zip(*its):
            yield func(*items)

    return reader


def chain(*readers):
    def reader():
        for r in readers:
            yield from r()

    return reader


def compose(*readers):
    def reader():
        for items in zip(*[r() for r in readers]):
            out = []
            for it in items:
                if isinstance(it, tuple):
                    out.extend(it)
                else:
                    out.append(it)
            yield tuple(out)

    return reader


def firstn(reader, n):
    def reader_n():
        yield from itertools.islice(reader(), n)

    return reader_n


def cache(reader):
    all_items = []
    filled = [False]

    def cached():
        if not filled[0]:
            for item in reader():
                all_items.append(item)
                yield item
            filled[0] = True
        else:
            yield from all_items

    return cached


def _transferable(leaf):
    """Array-like leaves get device_put; names/metadata pass through."""
    if isinstance(leaf, (np.ndarray, np.generic)):
        return True
    # jax.Array without importing jax at module scope
    return type(leaf).__module__.startswith(("jaxlib", "jax"))


def device_prefetch(batches, size=2, device=None):
    """Double-buffered host->device prefetch (buffered_reader.cc role,
    done the TPU way).

    Keeps `size` batches' transfers IN FLIGHT ahead of the consumer:
    `jax.device_put` is async dispatch, so batch N+1's host->device copy
    is issued before the consumer has finished step N — the copy rides
    the DMA while the step occupies the compute units, which is the
    entire win (the benchmark's train cell feeds its steps through
    it).  size=2 is the classic double buffer; larger only
    helps if the producer is burstier than the consumer.

    Each array leaf of every yielded batch is a FRESH device buffer that
    the consumer exclusively owns, so donating it into a jitted step
    (donate_argnums) is safe — no buffer is ever yielded twice and the
    iterator keeps no reference once a batch is handed out.  Non-array
    leaves (names, metadata) pass through untouched.  Order is the
    source order: nothing is dropped, duplicated, or reordered.

    batches: iterable of pytrees (feed dicts, tuples of arrays, ...).
    device: target jax.Device (default: jax's default device).
    """
    import collections

    import jax

    if size < 1:
        raise ValueError(f"device_prefetch size must be >= 1, got {size}")

    def put_leaf(leaf):
        if not _transferable(leaf):
            return leaf
        if isinstance(leaf, jax.Array):
            # device_put on an already-on-device array ALIASES the same
            # buffer; copy so the fresh-buffer/donation guarantee holds
            # for every leaf, not just host ones
            import jax.numpy as jnp

            fresh = jnp.copy(leaf)
            return fresh if device is None \
                else jax.device_put(fresh, device)
        return jax.device_put(leaf, device)

    def put(item):
        return jax.tree_util.tree_map(put_leaf, item)

    from .. import monitor
    from ..profiler import RecordEvent

    depth = monitor.gauge("reader.prefetch_depth")
    it = iter(batches)
    queue = collections.deque()
    exhausted = object()

    def fill(n):
        # under a profiler session: the source's time to make a batch
        # and the host's time to start its transfer, apart
        for _ in range(n):
            with RecordEvent("reader.source"):
                item = next(it, exhausted)
            if item is exhausted:
                return
            with RecordEvent("reader.device_put"):
                queue.append(put(item))

    fill(size)
    while queue:
        # buffer occupancy AT each get: a healthy double buffer reads
        # `size`, a starved one flatlines at 1 (this batch only) — the
        # input-starvation signal on /metrics and the chrome track
        depth.set(len(queue))
        out = queue.popleft()
        # issue batch N+1's transfer BEFORE handing batch N to the
        # consumer: the copy overlaps the consumer's step
        fill(1)
        yield out


# ---------------------------------------------------------------------------
# DataLoader
# ---------------------------------------------------------------------------

def _stack_samples(samples, feed_names):
    """list of tuples -> dict of batched numpy arrays."""
    cols = list(zip(*samples))
    out = {}
    for name, col in zip(feed_names, cols):
        out[name] = np.stack([np.asarray(c) for c in col])
    return out


class DataLoader:
    """Feeds dict batches to Executor.run (reader.py:179 parity).

    Iterating yields dicts name->np.ndarray ready to pass as `feed`.
    """

    def __init__(self, feed_list=None, capacity=4, iterable=True,
                 use_multiprocess=False, num_workers=2):
        self._feed_names = [
            v.name if hasattr(v, "name") else v for v in (feed_list or [])
        ]
        self._capacity = capacity
        self._batch_reader = None
        self._use_multiprocess = use_multiprocess
        self._num_workers = num_workers

    @staticmethod
    def from_generator(feed_list=None, capacity=4, iterable=True,
                       return_list=False, use_double_buffer=True,
                       use_multiprocess=False, num_workers=2):
        """use_multiprocess=True engages worker processes + shared-memory
        transport (reader.py:469 DygraphGeneratorLoader parity) instead
        of the background thread — the GIL-free path for CPU-bound
        python readers."""
        return DataLoader(feed_list, capacity, iterable,
                          use_multiprocess=use_multiprocess,
                          num_workers=num_workers)

    def set_batch_generator(self, reader, places=None):
        self._batch_reader = reader
        return self

    def set_sample_list_generator(self, reader, places=None):
        def batched():
            for samples in reader():
                yield _stack_samples(samples, self._feed_names)

        self._batch_reader = batched
        return self

    def set_sample_generator(self, reader, batch_size, drop_last=True,
                             places=None):
        return self.set_sample_list_generator(
            batch(reader, batch_size, drop_last=drop_last), places)

    def __iter__(self):
        if self._batch_reader is None:
            raise RuntimeError("no generator set on DataLoader")
        if self._use_multiprocess:
            from .shm import ShmBatchLoader

            def sharded(worker_id, num_workers):
                return self._gen_feed_dicts(worker_id, num_workers)

            return iter(ShmBatchLoader(sharded,
                                       num_workers=self._num_workers,
                                       capacity=self._capacity))
        prefetched = buffered(self._gen_feed_dicts, self._capacity)
        return iter(prefetched())

    def _gen_feed_dicts(self, worker_id=None, num_workers=None):
        import itertools

        reader = self._batch_reader
        if worker_id is None:
            items = reader()
        else:
            # multiprocess path: pass the shard through when the user's
            # reader is shard-aware, else round-robin islice (order
            # preserved; see ShmBatchLoader doc for the cost model)
            from .shm import is_shard_aware

            items = (reader(worker_id, num_workers)
                     if is_shard_aware(reader)
                     else itertools.islice(reader(), worker_id, None,
                                           num_workers))
        for item in items:
            if isinstance(item, dict):
                yield item
            elif isinstance(item, (list, tuple)) and self._feed_names:
                yield {n: np.asarray(v)
                       for n, v in zip(self._feed_names, item)}
            else:
                yield item


class DataFeeder:
    """Parity: fluid.DataFeeder (data_feeder.py) — converts sample lists
    to feed dicts."""

    def __init__(self, feed_list, place=None):
        self._feed_names = [v.name if hasattr(v, "name") else v
                            for v in feed_list]

    def feed(self, samples):
        return _stack_samples(samples, self._feed_names)


def xmap_readers(mapper, reader, process_num, buffer_size, order=False):
    """Parallel-map a reader with a thread pool (parity:
    python/paddle/reader/decorator.py:364 xmap_readers — the reference
    uses threads too). order=True preserves sample order."""
    import queue as _q
    import threading as _t

    def xreader():
        in_q = _q.Queue(buffer_size)
        out_q = _q.Queue(buffer_size)
        END = object()

        errors = []

        def feeder():
            try:
                for i, sample in enumerate(reader()):
                    in_q.put((i, sample))
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)
            finally:
                # guarantee every worker sees an END even if the source
                # reader raised (missing sentinels deadlock the consumer)
                for _ in range(process_num):
                    in_q.put(END)

        def worker():
            try:
                while True:
                    item = in_q.get()
                    if item is END:
                        return
                    i, sample = item
                    out_q.put((i, mapper(sample)))
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
            finally:
                out_q.put(END)

        threads = [_t.Thread(target=feeder, daemon=True)]
        threads += [_t.Thread(target=worker, daemon=True)
                    for _ in range(process_num)]
        for t in threads:
            t.start()

        finished = 0
        if order:
            import heapq
            heap, want = [], 0
            while finished < process_num:
                item = out_q.get()
                if item is END:
                    finished += 1
                    continue
                heapq.heappush(heap, item)
                while heap and heap[0][0] == want:
                    yield heapq.heappop(heap)[1]
                    want += 1
            # on error some indices never arrive; drain what's complete
            while heap and not errors:
                yield heapq.heappop(heap)[1]
        else:
            while finished < process_num:
                item = out_q.get()
                if item is END:
                    finished += 1
                    continue
                yield item[1]
        if errors:
            raise errors[0]

    return xreader


def multiprocess_reader(readers, use_pipe=True, queue_size=1000):
    """Interleave multiple readers, each drained on its own thread
    (parity: decorator.py:457 — the reference forks processes; readers
    here are python generators feeding a jit pipeline, so threads give
    the same overlap without fork hazards under JAX)."""
    import queue as _q
    import threading as _t

    def mreader():
        out_q = _q.Queue(queue_size)
        END = object()

        errors = []

        def drain(r):
            try:
                for sample in r():
                    out_q.put(sample)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)
            finally:
                out_q.put(END)  # guaranteed sentinel, even on error

        threads = [_t.Thread(target=drain, args=(r,), daemon=True)
                   for r in readers]
        for t in threads:
            t.start()
        finished = 0
        while finished < len(readers):
            item = out_q.get()
            if item is END:
                finished += 1
                continue
            yield item
        if errors:
            raise errors[0]

    return mreader


class PyReader(DataLoader):
    """`fluid.io.PyReader` parity (reference reader.py:441): the 1.x
    name for the generator-fed loader.  decorate_* methods map onto the
    DataLoader setters; start()/reset() exist for the non-iterable
    protocol (iteration here is always the iterable protocol, so they
    are no-ops kept for script parity)."""

    def __init__(self, feed_list=None, capacity=4, use_double_buffer=True,
                 iterable=True, return_list=False):
        super().__init__(feed_list=feed_list, capacity=capacity,
                         iterable=iterable)

    def decorate_sample_generator(self, sample_generator, batch_size,
                                  drop_last=True, places=None):
        return self.set_sample_generator(sample_generator, batch_size,
                                         drop_last=drop_last, places=places)

    def decorate_sample_list_generator(self, reader, places=None):
        return self.set_sample_list_generator(reader, places=places)

    def decorate_batch_generator(self, reader, places=None):
        return self.set_batch_generator(reader, places=places)

    def start(self):
        return None

    def reset(self):
        return None
