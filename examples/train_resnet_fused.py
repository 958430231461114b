"""ResNet image classification on the fused SPMD training path.

ref: example/image-classification/train_imagenet.py, modernised to the
TPU-native fast path: parallel.TrainStep compiles forward+backward+
optimizer into ONE XLA program over a device mesh (this is the loop the
benchmark's resnet50_v1.train_b256 cell runs, chipbench/run.py).

    python examples/train_resnet_fused.py [--model resnet50_v1] [--iters 50]
    # Pallas fused norm-relu-conv blocks (bn+relu folded into the convs):
    python examples/train_resnet_fused.py --fused-conv
    # feed from a real RecordIO file instead of synthetic tensors:
    python examples/train_resnet_fused.py --rec data/train.rec
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon.model_zoo import vision


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50_v1")
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--fused-conv", action="store_true",
                    help="Pallas fused norm-relu-conv resnet blocks")
    ap.add_argument("--rec", default=None,
                    help="RecordIO path: feed via ImageRecordIter (native "
                         "decode) instead of synthetic tensors")
    args = ap.parse_args()

    import jax
    mx.config.setup_compile_cache()
    n_dev = len(jax.devices())

    kw = {"fused": True} if args.fused_conv else {}
    net = vision.get_model(args.model, classes=args.classes,
                           layout="NHWC", **kw)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9,
                              wd=1e-4, multi_precision=True)
    mesh = parallel.make_mesh(dp=n_dev)
    step = parallel.TrainStep(net, lambda o, l: loss_fn(o, l), opt,
                              mesh=mesh)

    if args.rec:
        # real input pipeline: packed records through the native decoder
        # (NCHW floats out; convert to the net's NHWC bf16)
        it = mx.io.ImageRecordIter(
            args.rec, data_shape=(3, 224, 224), batch_size=args.batch_size,
            shuffle=True, rand_crop=True, rand_mirror=True, resize=256,
            preprocess_threads=os.cpu_count() or 1,
            mean_r=123.7, mean_g=116.3, mean_b=103.5,
            std_r=58.4, std_g=57.1, std_b=57.4)

        def batches():
            while True:
                for b in it:
                    x = b.data[0].transpose((0, 2, 3, 1)).astype("bfloat16")
                    yield x, b.label[0]
                it.reset()
        feed = batches()
    else:
        rng = np.random.RandomState(0)
        x = mx.nd.array(rng.randn(args.batch_size, 224, 224, 3)
                        .astype(np.float32)).astype("bfloat16")
        y = mx.nd.array(rng.randint(0, args.classes, (args.batch_size,))
                        .astype(np.float32))
        feed = iter(lambda: (x, y), None)

    xb, yb = next(feed)
    step(xb, yb).asnumpy()  # compile
    t0 = time.perf_counter()
    for _ in range(args.iters):
        xb, yb = next(feed)
        loss = step(xb, yb)
    loss.asnumpy()
    dt = time.perf_counter() - t0
    print(f"{args.model}: {args.batch_size * args.iters / dt:.1f} img/s "
          f"({n_dev} device(s), loss={float(loss.asnumpy()):.3f})")


if __name__ == "__main__":
    main()
