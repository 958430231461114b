"""Serve a trained MNIST MLP through mx.serving under concurrent load.

ref: no reference equivalent — the 1.x stack stops at Module.predict.
This is the ISSUE 4 serving runtime end to end: train a small Gluon MLP
for a few batches, wrap its forward in an ``InferenceServer`` (admission
control, shape-bucketed dynamic batching, deadlines, circuit breaker,
graceful drain), then hammer it from client threads and print the
health/occupancy counters.  The bucket grid keeps the jit cache bounded:
however ragged the traffic, at most ``len(buckets)`` executables exist.

    python examples/serve_mnist.py [--requests 256] [--clients 4]
"""
import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, profiler, serving


def train_quick(batches=30, batch_size=128, lr=0.1):
    """A few SGD batches on (possibly synthetic) MNIST — enough to make
    the served model non-trivial; accuracy is not the point here."""
    data = gluon.data.DataLoader(
        gluon.data.vision.MNIST(train=True).transform_first(
            gluon.data.vision.transforms.ToTensor()),
        batch_size=batch_size, shuffle=True)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(128, activation="relu"),
            gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for i, (x, y) in enumerate(data):
        if i >= batches:
            break
        x = x.reshape((x.shape[0], -1))
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(x.shape[0])
    return net


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=256,
                    help="total requests across all clients")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--train-batches", type=int, default=30)
    ap.add_argument("--deadline", type=float, default=0.5,
                    help="per-request deadline (seconds)")
    args = ap.parse_args()
    mx.config.setup_compile_cache()

    print("training a quick MLP ...")
    net = train_quick(batches=args.train_batches)

    def apply(x):
        return net(mx.nd.array(x)).asnumpy()

    srv = serving.InferenceServer(
        apply, buckets=(1, 4, 8, 16), max_queue=64, max_delay=0.003,
        sample=np.zeros((784,), np.float32),
        default_deadline=args.deadline, name="MnistServer")
    t0 = time.time()
    srv.start()           # warmup-compiles all four bucket executables
    print(f"server ready in {time.time() - t0:.2f}s "
          f"({len(srv.distinct_shapes)} bucket executables warm), "
          f"healthz={srv.healthz()}")

    test = gluon.data.vision.MNIST(train=False)
    images = np.stack([np.asarray(test[i][0], np.float32).reshape(-1) / 255.0
                       for i in range(64)])
    labels = np.array([int(test[i][1]) for i in range(64)])

    ok, shed, failed, hits = [0], [0], [0], [0]
    count_lock = threading.Lock()

    def client(k):
        rng = np.random.RandomState(k)
        for _ in range(args.requests // args.clients):
            i = rng.randint(len(images))
            try:
                out = srv(images[i])
                with count_lock:
                    ok[0] += 1
                    hits[0] += int(np.argmax(out) == labels[i])
            except serving.RejectedError:
                with count_lock:
                    shed[0] += 1
            except Exception:
                with count_lock:
                    failed[0] += 1

    t0 = time.time()
    threads = [threading.Thread(target=client, args=(k,))
               for k in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.time() - t0
    st = srv.stats
    print(f"served {ok[0]} requests in {dt:.2f}s "
          f"({ok[0] / dt:.0f} req/s), shed={shed[0]} failed={failed[0]} "
          f"acc={hits[0] / max(1, ok[0]):.3f}")
    print(f"batches={st['batches']} "
          f"mean occupancy={st['completed'] / max(1, st['batches']):.1f} "
          f"distinct_shapes={st['distinct_shapes']} "
          f"counters={profiler.counters('MnistServer::')}")
    drained = srv.drain()
    print(f"drained={drained} (accepted requests resolved: "
          f"{st['completed'] + st['failed'] + st['expired']}"
          f"/{st['admitted']})")


if __name__ == "__main__":
    main()
