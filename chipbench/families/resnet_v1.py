"""``family: resnet_v1``: a bottleneck ResNet v1 of the model zoo, built by
name, with softmax cross-entropy on random images and labels.  A family is
found by its file's name, so a new one is a new file; each carries ``build``,
``check_labels`` and ``train_flops``."""
import jax.numpy as jnp
from mxnet_tpu import gluon
from mxnet_tpu.gluon.model_zoo import vision

from chipbench import flops, traffic


def build(model):
    """(net, loss_fn, make_batch(rng, n) -> (data, labels))."""
    net = getattr(vision, model["name"])(layout=model["layout"],
                                         classes=model["classes"])

    def batch(rng, n):
        return traffic.image_batch(rng, n, model["image_size"],
                                   model["classes"])
    return net, gluon.loss.SoftmaxCrossEntropyLoss(), batch


def check_labels(out):
    """Labels that only this forward reproduces: the class each image's
    reference logits rank first.  The loss on them sits far below the
    ln(classes) that any near-uniform output reads, so a dropped layer or a
    wrong mask, which moves the logits, moves it by units (kinds/train.py)."""
    return (jnp.argmax(out._data, axis=-1).astype(jnp.int32),)


def train_flops(model):
    """Per image: bottleneck ResNet v1 (stride on the block's first 1x1, as
    He et al. and Gluon's BottleneckV1), convolutions and the classifier;
    norms, activations and pooling are not matrix work and are left out."""
    size, total = model["image_size"], 0
    f, h, w = flops.conv2d(size, size, 3, model["channels"][0], 7, 2)
    total += f
    h, w = -(-h // 2), -(-w // 2)                     # 3x3 max-pool, stride 2
    cin = model["channels"][0]
    for stage, (blocks, cout) in enumerate(zip(model["layers"],
                                               model["channels"][1:])):
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            mid = cout // 4
            if b == 0:
                total += flops.conv2d(h, w, cin, cout, 1, stride)[0]
            f1, h, w = flops.conv2d(h, w, cin, mid, 1, stride)
            total += f1 + flops.conv2d(h, w, mid, mid, 3, 1)[0] \
                + flops.conv2d(h, w, mid, cout, 1, 1)[0]
            cin = cout
    total += 2 * cin * model["classes"]
    return 3 * total
