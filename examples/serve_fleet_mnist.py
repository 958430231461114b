"""Serve MNIST from a replicated fleet while training streams new weights.

ref: no reference equivalent — the 1.x stack stops at Module.predict.
This is the ISSUE 7 fleet end to end: a ``TrainStep`` job checkpoints an
MLP through ``CheckpointManager`` while a 3-replica
``serving.ServingFleet`` serves the test set under concurrent client
load; a ``WeightUpdater`` watches the checkpoint directory and rolls
each new snapshot across the replicas live — quarantine → drain →
hot-swap → probe → readmit, one replica at a time, zero dropped
requests, zero recompiles (the bucket census covers the whole fleet
because every replica shares one jitted forward).

    python examples/serve_fleet_mnist.py [--requests 400] [--clients 4]
"""
import argparse
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import mxnet_tpu as mx
import jax
import jax.numpy as jnp
from mxnet_tpu import gluon, parallel, profiler, serving
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.checkpoint import (CheckpointManager,
                                           load_snapshot_params)


def load_mnist(n_train=2048, n_test=256):
    train = gluon.data.vision.MNIST(train=True)
    test = gluon.data.vision.MNIST(train=False)

    def to_arrays(ds, n):
        x = np.stack([np.asarray(ds[i][0], np.float32).reshape(-1) / 255.0
                      for i in range(n)])
        y = np.array([int(ds[i][1]) for i in range(n)])
        return x, y

    return to_arrays(train, n_train), to_arrays(test, n_test)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=400,
                    help="total client requests across all threads")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--warm-batches", type=int, default=8,
                    help="training batches before the FIRST snapshot")
    ap.add_argument("--more-batches", type=int, default=48,
                    help="training batches behind the streamed snapshot")
    ap.add_argument("--batch-size", type=int, default=256)
    args = ap.parse_args()
    mx.config.setup_compile_cache()

    (train_x, train_y), (test_x, test_y) = load_mnist()
    print(f"training an MLP: {args.warm_batches} warm batches, then "
          f"{args.more_batches} more under live serving ...")

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(128, activation="relu", in_units=784),
            nn.Dense(10, in_units=128))
    net.initialize(mx.init.Xavier())
    mesh = parallel.make_mesh(dp=len(jax.devices()))
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              mx.optimizer.create("adam"), mesh=mesh)

    rng = np.random.RandomState(0)

    def train_batches(k):
        for _ in range(k):
            idx = rng.randint(0, len(train_x), args.batch_size)
            step(train_x[idx], train_y[idx])

    ckpt_dir = tempfile.mkdtemp(prefix="fleet_mnist_ckpts_")
    mgr = CheckpointManager(step, ckpt_dir, keep_last=3)
    train_batches(args.warm_batches)
    mgr.save()
    first_n = mgr.checkpoints()[-1][0]
    params, _names = load_snapshot_params(mgr.checkpoints()[-1][1])

    # one jitted forward shared by every replica: the executable census
    # of the bucket grid covers the WHOLE fleet
    shapes = [tuple(p.shape) for p in params]
    iw1, ib1 = shapes.index((128, 784)), shapes.index((128,))
    iw2, ib2 = shapes.index((10, 128)), shapes.index((10,))

    @jax.jit
    def fwd(p, x):
        h = jnp.maximum(x @ p[iw1].T + p[ib1], 0.0)
        return h @ p[iw2].T + p[ib2]

    fleet = serving.ServingFleet.replicated(
        lambda p, x: np.asarray(fwd(p, x)), params, 3,
        buckets=(1, 4, 8), max_delay=0.003,
        sample=test_x[0], name="MnistFleet")
    t0 = time.time()
    fleet.start()
    print(f"fleet ready in {time.time() - t0:.2f}s "
          f"(3 replicas, healthz ready_replicas="
          f"{fleet.healthz()['ready_replicas']})")

    updater = serving.WeightUpdater(fleet, mgr, last_seen=first_n,
                                    poll=0.05)
    updater.start()

    results = []                  # (wall time, correct?) per served request
    shed = [0]
    count_lock = threading.Lock()

    def client(k):
        rng_c = np.random.RandomState(k)
        for _ in range(args.requests // args.clients):
            i = rng_c.randint(len(test_x))
            try:
                out = fleet(test_x[i], timeout=60)
                with count_lock:
                    results.append((time.time(),
                                    int(np.argmax(out) == test_y[i])))
            except serving.RejectedError:
                with count_lock:
                    shed[0] += 1
            time.sleep(0.004)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(args.clients)]
    swapped_at = [None]
    try:
        for t in threads:
            t.start()
        # the training job keeps going and commits a better snapshot;
        # the updater rolls it onto the fleet while clients hammer it
        train_batches(args.more_batches)
        mgr.save()
        t0 = time.time()
        while updater.applied < 1 and time.time() - t0 < 60:
            time.sleep(0.02)
        swapped_at[0] = time.time()
    finally:
        for t in threads:
            t.join()
        updater.stop(timeout=10)
    st = fleet.stats
    drained = fleet.drain(timeout=60)

    before = [ok for ts, ok in results
              if swapped_at[0] is None or ts < swapped_at[0]]
    after = [ok for ts, ok in results
             if swapped_at[0] is not None and ts >= swapped_at[0]]
    acc = (np.mean(before) if before else float("nan"),
           np.mean(after) if after else float("nan"))
    print(f"rolling update applied={updater.applied} "
          f"(snapshots skipped={updater.skipped}), swaps={st['swaps']} "
          f"redispatched={st['redispatched']}")
    print(f"served={len(results)} shed={shed[0]} "
          f"acc_before_swap={acc[0]:.3f} acc_after_swap={acc[1]:.3f}")
    print(f"counters={profiler.counters('MnistFleet::')}")
    resolved = st["completed"] + st["failed"] + st["expired"]
    print(f"drained={drained} dropped={st['admitted'] - resolved}")


if __name__ == "__main__":
    main()
