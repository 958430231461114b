"""DataLoader.

ref: python/mxnet/gluon/data/dataloader.py — class DataLoader,
_MultiWorkerIter (multiprocessing workers + batchify + pin_memory).

TPU-native: workers produce numpy batches (host); `device_put` to HBM happens
once per batch on read.  ``pin_memory=True`` is the async-put path: a
``parallel.DevicePrefetcher`` issues the host→device transfer for batch N+1
on a background thread while the consumer computes on batch N (the moral
equivalent of the reference's pinned staging buffer — transfer overlaps
compute instead of serializing with it).  This class matches the reference's
flexible python path; the packed-record high-throughput path is
``mxnet_tpu.io``.
"""
from __future__ import annotations

import io
import multiprocessing as mp
import pickle
import sys

import numpy as np

from ...ndarray import NDArray
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_batchify_fn(data):
    """ref: default_batchify_fn — stack samples into a batch."""
    if isinstance(data[0], NDArray):
        from ... import ndarray as nd
        return nd.stack(*data, axis=0)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    arr = np.asarray(data)
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    return arr


default_mp_batchify_fn = default_batchify_fn  # no shared-mem rewrap needed


def _as_numpy_sample(s):
    if isinstance(s, NDArray):
        return s.asnumpy()
    if isinstance(s, tuple):
        return tuple(_as_numpy_sample(x) for x in s)
    return s


def _to_device_batch(batch):
    """numpy host batch -> NDArray on device (one device_put per leaf; the
    reference's pin_memory + copy-to-ctx happens here)."""
    if isinstance(batch, np.ndarray):
        from ... import ndarray as nd
        return nd.array(batch)
    if isinstance(batch, tuple):
        # namedtuples construct from positional args, plain tuples from one
        return (type(batch)(*map(_to_device_batch, batch))
                if hasattr(batch, "_fields")
                else tuple(_to_device_batch(b) for b in batch))
    if isinstance(batch, list):
        return [_to_device_batch(b) for b in batch]
    if isinstance(batch, dict):
        return {k: _to_device_batch(v) for k, v in batch.items()}
    return batch


def _worker_fn(dataset, key, samples, batchify_fn):
    batch = batchify_fn([_as_numpy_sample(dataset[i]) for i in samples])
    return key, batch


class DataLoader:
    """ref: class DataLoader."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=None, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=False, timeout=120):
        if num_workers is None:
            # MXNET_CPU_WORKER_NTHREADS sets the fleet-wide default
            from ... import config as _config
            num_workers = _config.get("MXNET_CPU_WORKER_NTHREADS")
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._timeout = timeout
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size required when batch_sampler is None")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle \
                    else SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle and sampler are mutually exclusive")
            batch_sampler = BatchSampler(sampler, batch_size, last_batch or "keep")
        elif (batch_size is not None or shuffle or sampler is not None
              or last_batch is not None):
            raise ValueError("batch_sampler is mutually exclusive with "
                             "batch_size/shuffle/sampler/last_batch")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        self._thread_pool = thread_pool
        self._pool = None
        self._closed = False
        if self._num_workers > 0:
            if thread_pool:
                from multiprocessing.dummy import Pool
                self._pool = Pool(self._num_workers)
            else:
                ctx = mp.get_context("fork") if sys.platform != "win32" else mp.get_context()
                self._pool = ctx.Pool(self._num_workers)

    def __iter__(self):
        if self._closed:
            raise RuntimeError("DataLoader is closed")
        if not self._pin_memory:
            for batch in self._host_batches():
                yield _to_device_batch(batch)
            return
        # pin_memory: async-put — device placement of batch N+1 runs on a
        # background thread while the consumer computes on batch N.  The
        # device-side queue holds WHOLE batches in HBM, so its depth is
        # capped independently of the (host-side) worker prefetch count:
        # beyond 2-3 only buys jitter absorption (docs/api.md)
        from ...parallel.prefetch import DevicePrefetcher
        with DevicePrefetcher(self._host_batches(),
                              depth=min(max(1, self._prefetch or 1),
                                        3)) as feed:
            yield from feed

    def _host_batches(self):
        """Yield batchified HOST (numpy) batches, multi-worker when a pool
        exists (ref: _MultiWorkerIter — async map with bounded prefetch).

        A worker exception (bad sample, decode failure) re-raises here
        tagged with the batch index it came from, AFTER ``close()`` has
        torn the pool down — a failed loader never leaks worker
        processes."""
        from ... import fault as _fault
        if self._pool is None:
            for samples in self._batch_sampler:
                _fault.fire("io.producer")
                yield self._batchify_fn(
                    [_as_numpy_sample(self._dataset[i]) for i in samples])
            return
        issued = {}
        batches = list(self._batch_sampler)
        next_issue = 0
        next_yield = 0

        def _issue():
            nonlocal next_issue
            if next_issue < len(batches):
                key = next_issue
                issued[key] = self._pool.apply_async(
                    _worker_fn, (self._dataset, key, batches[key], self._batchify_fn))
                next_issue += 1

        for _ in range(self._prefetch or 1):
            _issue()
        while next_yield < len(batches):
            try:
                _fault.fire("io.producer")
                key, batch = issued[next_yield].get(self._timeout)
            except mp.TimeoutError:
                # no close() here: joining a (thread-)pool that is still
                # stuck inside the slow task would turn a prompt timeout
                # into a hang — the caller owns teardown after a timeout
                raise TimeoutError(
                    f"DataLoader worker batch {next_yield} not ready within "
                    f"timeout={self._timeout}s (a forked worker that "
                    f"touches an NDArray blocks for good: the device "
                    f"belongs to the parent process — keep worker-side "
                    f"samples in numpy, or pass thread_pool=True)") from None
            except Exception as exc:
                self.close()
                raise _fault.with_context(
                    exc, f"DataLoader worker, batch {next_yield}") from exc
            del issued[next_yield]
            _issue()
            next_yield += 1
            yield batch

    def __len__(self):
        return len(self._batch_sampler)

    def close(self):
        """Shut the worker pool down deterministically (``__del__`` on
        interpreter teardown is racy — ref: satellite of the async-feed
        work).  Idempotent; the loader cannot be iterated afterwards."""
        self._closed = True
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.terminate()
            pool.join()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
