"""Input-pipeline throughput microbenchmark.

ref: the reference sizes its C++ decode pipeline (iter_image_recordio_2)
to keep GPUs fed; here the same question for the TPU step: how many
img/s can ImageRecordIter deliver on this host?  Compare against the
model step rate (the resnet50_v1.train_b256 cell: 2672 img/s on one v5e,
PERF_LEDGER.jsonl PR 28) to know when input
becomes the bottleneck.

Three decode paths (see mxnet_tpu/io.py):
  native — src/image_decode.cc: whole-batch JPEG decode in N native
           threads (no GIL/IPC), in-thread resize/crop/mirror;
  pil    — the process-pool PIL fallback;
  raw    — pre-decoded uint8 records (im2rec --raw): memcpy + crop only.

Throughput scales with host cores for the JPEG paths (~300 img/s/core of
photo-like 256px decode; random-noise JPEGs are ~1.5x slower).  The dev
container has ONE core; a real TPU-VM host (v5e: 100+ vCPUs) runs one
native thread per core.  The raw path is IO/memcpy-bound and sustains
thousands of img/s on a single core.

    python benchmark/dataloader_perf.py [--n 2048] [--hw 224]
        [--threads 0,4,8] [--batch-size 256] [--paths native,pil,raw]

``--overlap`` instead measures the async-feed pipeline itself: a producer
throttled to ``--overlap-ms`` per batch feeds a fake step throttled to the
same, serial vs through mx.io.PrefetchingIter.  A perfect pipeline takes
~max(producer, step) per batch instead of their sum; the printed
``overlap_efficiency`` is the fraction of that ideal saving achieved.

    python benchmark/dataloader_perf.py --overlap [--overlap-ms 10]
        [--overlap-batches 30]
"""
from __future__ import annotations

import argparse
import io as _pyio
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from mxnet_tpu import io as mio  # noqa: E402
from mxnet_tpu import recordio  # noqa: E402


def make_dataset(path, n, hw, quality=90, raw=False, noise=False):
    """Write a synthetic record file (+index).  Default images are
    photo-like (low-frequency structure, realistic JPEG cost); --noise
    packs incompressible noise (decode worst case)."""
    from PIL import Image
    rec, idx = path + ".rec", path + ".idx"
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    rng = np.random.RandomState(0)
    s = hw + 32
    yy, xx = np.mgrid[0:s, 0:s]
    for i in range(n):
        if noise:
            img = rng.randint(0, 255, (s, s, 3), np.uint8)
        else:
            base = (np.sin(xx / (18 + i % 9)) * 60
                    + np.cos(yy / (14 + i % 7)) * 60 + 128)
            img = np.clip(np.stack([base, np.roll(base, i % 32, 0),
                                    np.roll(base, i % 32, 1)], -1)
                          + rng.randn(s, s, 3) * 8, 0, 255).astype(np.uint8)
        hdr = recordio.IRHeader(0, float(i % 1000), i, 0)
        if raw:
            w.write_idx(i, recordio.pack_img(hdr, img, img_fmt=".raw"))
        else:
            buf = _pyio.BytesIO()
            Image.fromarray(img).save(buf, format="JPEG", quality=quality)
            w.write_idx(i, recordio.pack(hdr, buf.getvalue()))
    w.close()
    return rec, idx


def bench_record_iter(rec, idx, hw, batch_size, threads, native, epochs=1):
    it = mio.ImageRecordIter(
        rec, data_shape=(3, hw, hw), batch_size=batch_size,
        path_imgidx=idx, rand_crop=True, rand_mirror=True,
        preprocess_threads=threads, use_native_decode=native)
    n = 0
    batch = next(iter(it))  # warm (pool fork / lib load)
    batch.data[0].wait_to_read()
    it.reset()
    t0 = time.perf_counter()
    for _ in range(epochs):
        for batch in it:
            batch.data[0].wait_to_read()
            n += batch.data[0].shape[0]
        it.reset()
    dt = time.perf_counter() - t0
    it.close()
    return n / dt


class ThrottledIter(mio.DataIter):
    """Synthetic DataIter that takes ``delay_s`` of wall-clock per batch —
    stands in for decode/augment cost in the overlap benchmark."""

    def __init__(self, n_batches, delay_s, batch_size=2, feature_dim=4):
        super().__init__(batch_size)
        self._n = n_batches
        self._delay = delay_s
        self._shape = (batch_size, feature_dim)
        self._i = 0

    def reset(self):
        self._i = 0

    @property
    def provide_data(self):
        return [mio.DataDesc("data", self._shape)]

    @property
    def provide_label(self):
        return [mio.DataDesc("softmax_label", (self.batch_size,))]

    def next(self):
        if self._i >= self._n:
            raise StopIteration
        self._i += 1
        time.sleep(self._delay)
        data = np.full(self._shape, self._i, np.float32)
        label = np.full((self.batch_size,), self._i, np.float32)
        return mio.DataBatch([mio._to_nd(data)], [mio._to_nd(label)],
                             provide_data=self.provide_data,
                             provide_label=self.provide_label)


def overlap_bench(producer_s=0.010, step_s=0.010, n_batches=30, capacity=2):
    """Serial vs PrefetchingIter pipeline with a throttled producer and a
    throttled fake step.  Returns timings, speedup, overlap efficiency, and
    the prefetcher's wait-split stats."""
    def consume(it):
        count = 0
        t0 = time.perf_counter()
        for _ in it:
            time.sleep(step_s)  # the "training step"
            count += 1
        return time.perf_counter() - t0, count

    serial_s, n1 = consume(ThrottledIter(n_batches, producer_s))
    pf = mio.PrefetchingIter(ThrottledIter(n_batches, producer_s),
                             capacity=capacity)
    pipelined_s, n2 = consume(pf)
    stats = dict(pf.stats)
    pf.close()
    assert n1 == n2 == n_batches
    ideal_s = n_batches * max(producer_s, step_s)  # perfect overlap
    eff = (serial_s - pipelined_s) / max(serial_s - ideal_s, 1e-9)
    return {"serial_s": serial_s, "pipelined_s": pipelined_s,
            "ideal_s": ideal_s, "speedup": serial_s / pipelined_s,
            "overlap_efficiency": min(max(eff, 0.0), 1.0),
            "producer_wait_s": stats["producer_wait_s"],
            "consumer_wait_s": stats["consumer_wait_s"]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--hw", type=int, default=224)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--threads", "--workers", default="0,4,8")
    ap.add_argument("--paths", default="native,pil,raw")
    ap.add_argument("--noise", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--overlap", action="store_true",
                    help="measure producer/step overlap through "
                         "PrefetchingIter instead of decode throughput")
    ap.add_argument("--overlap-ms", type=float, default=10.0)
    ap.add_argument("--overlap-batches", type=int, default=30)
    args = ap.parse_args()

    if args.overlap:
        t = args.overlap_ms / 1e3
        r = overlap_bench(t, t, args.overlap_batches)
        row = {"metric": "input_pipeline_overlap",
               "producer_ms": args.overlap_ms, "step_ms": args.overlap_ms,
               "batches": args.overlap_batches,
               "serial_s": round(r["serial_s"], 4),
               "pipelined_s": round(r["pipelined_s"], 4),
               "speedup": round(r["speedup"], 3),
               "overlap_efficiency": round(r["overlap_efficiency"], 3),
               "producer_wait_s": round(r["producer_wait_s"], 4),
               "consumer_wait_s": round(r["consumer_wait_s"], 4)}
        print(json.dumps(row) if args.json else
              f"overlap: serial {r['serial_s']:.3f}s -> pipelined "
              f"{r['pipelined_s']:.3f}s  speedup {r['speedup']:.2f}x  "
              f"efficiency {r['overlap_efficiency']:.0%}  "
              f"(producer-wait {r['producer_wait_s']:.3f}s, "
              f"consumer-wait {r['consumer_wait_s']:.3f}s)")
        return

    paths = args.paths.split(",")
    with tempfile.TemporaryDirectory() as d:
        datasets = {}
        if "native" in paths or "pil" in paths:
            print(f"writing {args.n} JPEGs ({args.hw + 32}px)...",
                  file=sys.stderr)
            datasets["jpeg"] = make_dataset(os.path.join(d, "bj"), args.n,
                                            args.hw, noise=args.noise)
        if "raw" in paths:
            print(f"writing {args.n} raw records...", file=sys.stderr)
            datasets["raw"] = make_dataset(os.path.join(d, "br"), args.n,
                                           args.hw, raw=True,
                                           noise=args.noise)
        for path in paths:
            rec, idx = datasets["raw" if path == "raw" else "jpeg"]
            # native=True raises if the .so is unbuilt (never silently
            # measure pil under a 'native' label); raw auto-selects
            native = {"native": True, "pil": False}.get(path)
            # the raw path is a per-image numpy loop (memcpy-bound) — a
            # thread sweep would relabel the same single-thread config
            threads = [int(x) for x in args.threads.split(",")]
            if path == "raw":
                threads = threads[:1]
            for t in threads:
                rate = bench_record_iter(rec, idx, args.hw, args.batch_size,
                                         t, native=native)
                row = {"metric": "image_record_iter_throughput",
                       "path": path, "threads": t,
                       "value": round(rate, 1), "unit": "img/s"}
                print(json.dumps(row) if args.json
                      else f"{path:<7s} threads={t:<3d} {rate:>9.1f} img/s")


if __name__ == "__main__":
    main()
