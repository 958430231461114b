"""DevicePrefetcher — double-buffered host→device batch pipeline.

The fused TrainStep (step.py) is one XLA program; its only serial host work
per iteration is placing the batch on the mesh (``_put_batch`` — a
mesh-sharded ``device_put``).  This stage moves that put OFF the training
thread: a depth-bounded producer places batch N+1..N+depth while the
compiled step for batch N executes, and yields batches whose leaves already
carry the step's ``data_sharding``.  ``_put_batch`` detects the pre-placed
leaves and skips the inline put, so each leaf crosses PCIe/ICI exactly once
(assertable through ``step.add_transfer_hook``); with
``TrainStep(donate_batch=True)`` the placed buffers are donated to the XLA
program, so the steady-state feed holds only the in-flight ``depth``
batches in HBM.

ref: the structure TensorFlow input pipelines made standard (Abadi et al.)
and the reference exposes as ``mx.io.PrefetchingIter`` — here the second,
device-side half of that pipeline.

Usage::

    step = parallel.TrainStep(net, loss_fn, opt, mesh=mesh,
                              donate_batch=True)
    with parallel.DevicePrefetcher(loader, step=step, depth=2) as feed:
        for data, label in feed:          # leaves already on the mesh
            loss = step(data, label)      # no inline device_put

Any iterable works as the source: items may be ``(data, label)`` tuples,
``mx.io.DataBatch``-es, dicts, or bare arrays — the structure is walked and
every numpy / jax.Array / NDArray leaf is placed, everything else passes
through untouched.  Without ``step``/``sharding`` the leaves go to the
default device (the gluon DataLoader ``pin_memory`` path).

Observability: three ``profiler.scope`` spans, each also an annotation in
any jax profiler trace on the thread that ran it —
``DevicePrefetcher.device_put`` (producer thread: placing one batch; the
span ends when ``jax.device_put`` has DISPATCHED every leaf's transfer, not
when the bytes have arrived: the producer goes on to the source's next
batch while they travel, and a step on a batch still in flight waits on
the device, where only a device trace shows it),
``DevicePrefetcher.producer_wait`` (producer blocked on a full queue: the
step is the bottleneck) and ``DevicePrefetcher.consumer_wait`` (the
consumer blocked on an empty queue: the feed is the bottleneck).  ``stats``
carries ``produced``/``consumed``, live ``queue_depth`` (also the
``DevicePrefetcher::queue_depth`` counter) and the two waits summed, from
the same stamps as the spans.
"""
from __future__ import annotations

import queue as _queue
import threading

import numpy as np
import jax

from .. import profiler as _profiler
from ..fault import fire as _fire, with_context as _with_context
from ..ndarray import NDArray
from .step import _put_batch

__all__ = ["DevicePrefetcher"]


def _default_put(leaf):
    """Place one host leaf on the default device, uncommitted (like
    ``nd.array`` — eager ops and steps can both consume it, and mixing
    with arrays committed elsewhere stays legal)."""
    import jax.numpy as jnp
    arr = np.asarray(leaf)
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    return jnp.asarray(arr)  # already device-resident; no committing put


def _map_leaves(fn, item):
    """Apply ``fn`` to every array leaf of a batch structure, rebuilding
    containers (incl. namedtuples and io.DataBatch) around the results."""
    from ..io import DataBatch
    if isinstance(item, NDArray):
        return NDArray(fn(item._data))
    if isinstance(item, (np.ndarray, jax.Array)):
        return NDArray(fn(item))
    if isinstance(item, DataBatch):
        out = DataBatch(_map_leaves(fn, item.data),
                        _map_leaves(fn, item.label),
                        pad=item.pad, index=item.index,
                        provide_data=item.provide_data,
                        provide_label=item.provide_label,
                        bucket_key=item.bucket_key)
        return out
    if isinstance(item, tuple):
        return (type(item)(*(_map_leaves(fn, x) for x in item))
                if hasattr(item, "_fields")
                else tuple(_map_leaves(fn, x) for x in item))
    if isinstance(item, list):
        return [_map_leaves(fn, x) for x in item]
    if isinstance(item, dict):
        return {k: _map_leaves(fn, v) for k, v in item.items()}
    return item


class DevicePrefetcher:
    """Depth-bounded async device placement over any batch iterable."""

    _STOP = object()

    def __init__(self, source, step=None, sharding=None, depth=2, put=None):
        if put is None:
            if sharding is None and step is not None:
                sharding = step.data_sharding
            if sharding is not None:
                put = lambda leaf: _put_batch(leaf, sharding)  # noqa: E731
            else:
                put = _default_put
        self._source = source
        self._put = put
        self._depth = max(1, int(depth))
        self._closed = False
        self._thread = None
        self._lock = threading.Lock()
        self.stats = {"produced": 0, "consumed": 0, "queue_depth": 0,
                      "producer_wait_s": 0.0, "consumer_wait_s": 0.0}
        self._depth_counter = _profiler.Counter(
            None, "DevicePrefetcher::queue_depth")

    # ----------------------------------------------------------- produce --
    def _produce(self, it, q, stop):
        while not stop.is_set():
            try:
                _fire("prefetch.device_put")
                item = next(it)
                with _profiler.scope("DevicePrefetcher.device_put",
                                     cat="feed"):
                    item = _map_leaves(self._put, item)
            except StopIteration:
                item = self._STOP
            except Exception as exc:  # re-raised on the consumer side,
                # tagged as placement-thread provenance (the consumer's
                # traceback otherwise points at the blameless q.get)
                item = _with_context(exc, "DevicePrefetcher producer")
            enqueued = False
            with _profiler.scope("DevicePrefetcher.producer_wait",
                                 cat="wait") as wait:
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.05)
                        enqueued = True
                        break
                    except _queue.Full:
                        continue
            with self._lock:
                self.stats["producer_wait_s"] += wait.seconds
                if enqueued and item is not self._STOP \
                        and not isinstance(item, Exception):
                    # a batch dropped by a halt is NOT produced: keeps the
                    # produced == consumed + queue_depth invariant honest
                    self.stats["produced"] += 1
                self._set_depth_locked(q)
            if item is self._STOP or isinstance(item, Exception):
                return

    def _set_depth_locked(self, q):
        depth = q.qsize()
        self.stats["queue_depth"] = depth
        self._depth_counter.set_value(depth)

    # ------------------------------------------------------------ consume --
    def __iter__(self):
        if self._closed:
            raise RuntimeError("DevicePrefetcher is closed")
        self._join()  # at most one producer at a time
        q = _queue.Queue(self._depth)
        stop = threading.Event()
        thread = threading.Thread(
            target=self._produce, args=(iter(self._source), q, stop),
            name="DevicePrefetcher-producer", daemon=True)
        self._queue, self._stop_evt, self._thread = q, stop, thread
        thread.start()
        try:
            while True:
                with _profiler.scope("DevicePrefetcher.consumer_wait",
                                     cat="wait") as wait:
                    # poll so a stale generator resumed AFTER a newer
                    # __iter__ superseded it (its producer joined, queue
                    # drained) ends cleanly instead of blocking forever
                    while True:
                        try:
                            item = q.get(timeout=0.05)
                            break
                        except _queue.Empty:
                            if stop.is_set():
                                item = self._STOP
                                break
                with self._lock:
                    self.stats["consumer_wait_s"] += wait.seconds
                    self._set_depth_locked(q)
                if item is self._STOP:
                    return
                if isinstance(item, Exception):
                    raise item
                with self._lock:
                    self.stats["consumed"] += 1
                yield item
        finally:
            # halt/join THIS generator's own machinery (captured locals):
            # a stale abandoned generator closed late must not stop a newer
            # iteration's producer or drain its queue
            self._halt(q, stop)
            thread.join()
            if self._thread is thread:
                self._thread = None

    # ------------------------------------------------------------ cleanup --
    @staticmethod
    def _halt(q, stop):
        stop.set()
        while True:  # unblock a producer parked on a full queue
            try:
                q.get_nowait()
            except _queue.Empty:
                break

    def _join(self):
        if self._thread is not None:
            self._halt(self._queue, self._stop_evt)
            self._thread.join()
            self._thread = None

    def close(self):
        """Stop + join the producer thread; idempotent."""
        if self._closed:
            return
        self._join()
        self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
