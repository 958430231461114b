"""Pallas fused norm→relu→conv kernel (PERF.md round-4: the ResNet
HBM-floor breaker).  Parity vs the XLA composition at kernel, layer, and
model level — forward, every gradient (including the BN-statistics path
through x), running stats, eval mode, hybridize, and a training step.
On the CPU mesh the kernel runs in Pallas interpreter mode; the same code
compiles natively on TPU (tests_tpu re-run)."""
import numpy as np
import pytest

# Interpreter-mode Pallas sweeps dominate the suite's runtime (~3 min on
# one core); the on-chip re-run (tests_tpu/test_fused_conv_tpu.py) always
# includes them, the default CPU tier does not.
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.ops.pallas.fused_conv import (norm_relu_conv,
                                             norm_relu_conv_reference)


# co=256 is two 128-wide channel tiles (a tile is a multiple of 128 or all
# of co), so the tiled grid is compared too, here and compiled on the chip
@pytest.mark.parametrize("k,res_on,relu,co", [
    (3, False, True, 16), (1, False, True, 16), (3, True, True, 16),
    (3, True, False, 16), (3, True, True, 256)])
def test_kernel_parity(k, res_on, relu, co):
    rng = np.random.RandomState(0)
    n, h, w_, ci = 2, 8, 8, 8
    x = jnp.asarray(rng.randn(n, h, w_, ci).astype(np.float32))
    sc = jnp.asarray(rng.rand(ci).astype(np.float32) + 0.5)
    sh = jnp.asarray(rng.randn(ci).astype(np.float32) * 0.1)
    w = jnp.asarray(rng.randn(k, k, ci, co).astype(np.float32) * 0.2)
    res = jnp.asarray(rng.randn(n, h, w_, ci).astype(np.float32)) \
        if res_on else None

    of = norm_relu_conv(x, sc, sh, w, residual=res, relu=relu)
    orf = norm_relu_conv_reference(x, sc, sh, w, residual=res, relu=relu)
    np.testing.assert_allclose(np.asarray(of), np.asarray(orf),
                               rtol=2e-4, atol=2e-4)

    argnums = (0, 1, 2, 3) + ((4,) if res_on else ())

    def loss_f(x, sc, sh, w, res=None):
        o = norm_relu_conv(x, sc, sh, w, residual=res, relu=relu)
        return (o.astype(jnp.float32) ** 2).sum()

    def loss_r(x, sc, sh, w, res=None):
        o = norm_relu_conv_reference(x, sc, sh, w, residual=res, relu=relu)
        return (o.astype(jnp.float32) ** 2).sum()

    args = (x, sc, sh, w) + ((res,) if res_on else ())
    gf = jax.grad(loss_f, argnums=argnums)(*args)
    gr = jax.grad(loss_r, argnums=argnums)(*args)
    for i, (a, b) in enumerate(zip(gf, gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"grad argnum {i}")


def test_kernel_rejects_unsupported():
    x = jnp.zeros((1, 4, 4, 4), jnp.float32)
    w5 = jnp.zeros((5, 5, 4, 4), jnp.float32)
    with pytest.raises(ValueError, match="1x1/3x3"):
        norm_relu_conv(x, jnp.ones(4), jnp.zeros(4), w5)


def _ref_pair(fused):
    """BatchNorm+ReLU+Conv2D NHWC composition sharing fused's params."""
    ci = fused.gamma.shape[0]
    co = fused.weight.shape[-1]
    bn = nn.BatchNorm(axis=-1, in_channels=ci)
    conv = nn.Conv2D(co, fused._k, padding=fused._k // 2, use_bias=False,
                     in_channels=ci, layout="NHWC")
    bn.initialize()
    conv.initialize()
    bn.gamma.set_data(fused.gamma.data())
    bn.beta.set_data(fused.beta.data())
    # NHWC Conv2D weights are O·kh·kw·I; the fused layer stores HWIO
    conv.weight.set_data(mx.nd.array(
        fused.weight.data().asnumpy().transpose(3, 0, 1, 2)))
    return bn, conv


def test_layer_parity_train_eval_hybrid():
    rng = np.random.RandomState(0)
    n, h, w_, ci, co = 2, 8, 8, 8, 16
    x = mx.nd.array(rng.randn(n, h, w_, ci).astype(np.float32))
    x.attach_grad()
    x2 = mx.nd.array(x.asnumpy())
    x2.attach_grad()

    fused = nn.NormReluConv2D(co, 3, in_channels=ci)
    fused.initialize()
    bn, conv = _ref_pair(fused)

    with autograd.record():
        of = fused(x)
        (of * of).sum().backward()
    with autograd.record():
        orf = conv(mx.nd.relu(bn(x2)))
        (orf * orf).sum().backward()

    np.testing.assert_allclose(of.asnumpy(), orf.asnumpy(),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(x.grad.asnumpy(), x2.grad.asnumpy(),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(
        fused.weight.grad().asnumpy().transpose(3, 0, 1, 2),
        conv.weight.grad().asnumpy(), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(fused.gamma.grad().asnumpy(),
                               bn.gamma.grad().asnumpy(),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(fused.beta.grad().asnumpy(),
                               bn.beta.grad().asnumpy(),
                               rtol=2e-3, atol=2e-3)
    # running stats advanced identically
    np.testing.assert_allclose(fused.running_mean.data().asnumpy(),
                               bn.running_mean.data().asnumpy(),
                               rtol=1e-4, atol=1e-5)
    # eval mode uses the running stats
    np.testing.assert_allclose(fused(x).asnumpy(),
                               conv(mx.nd.relu(bn(x2))).asnumpy(),
                               rtol=2e-4, atol=2e-4)
    # hybridized path (jit capture incl. aux-state writeback)
    net = nn.HybridSequential()
    net.add(fused)
    net.hybridize()
    with autograd.record():
        oh = net(x)
        (oh * oh).sum().backward()
    np.testing.assert_allclose(oh.asnumpy(), of.asnumpy(),
                               rtol=1e-3, atol=1e-3)


def test_fused_resnet_matches_unfused():
    """resnet18_v1(fused=True) == resnet18_v1() with mapped params."""
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet
    mx.random.seed(0)
    plain = get_resnet(1, 18, layout="NHWC", classes=10, thumbnail=True)
    plain.initialize()
    mx.random.seed(0)
    fused = get_resnet(1, 18, layout="NHWC", classes=10, thumbnail=True,
                       fused=True)
    fused.initialize()
    x = mx.nd.array(np.random.RandomState(1).randn(2, 16, 16, 3)
                    .astype(np.float32))
    with autograd.pause():
        plain(x)
        fused(x)  # materialize deferred shapes

    # map plain block params -> fused block params
    # thumbnail features: [conv3x3, stage1..stage4, GlobalAvgPool]
    for st in range(1, 5):
        for pb, fb in zip(plain.features[st], fused.features[st]):
            body = pb.body
            fb.conv1.weight.set_data(body[0].weight.data())
            fb.f2.gamma.set_data(body[1].gamma.data())
            fb.f2.beta.set_data(body[1].beta.data())
            fb.f2.weight.set_data(mx.nd.array(
                body[3].weight.data().asnumpy().transpose(1, 2, 3, 0)))
            fb.bn2.gamma.set_data(body[4].gamma.data())
            fb.bn2.beta.set_data(body[4].beta.data())
            if pb.downsample is not None:
                fb.downsample[0].weight.set_data(pb.downsample[0].weight.data())
                fb.downsample[1].gamma.set_data(pb.downsample[1].gamma.data())
                fb.downsample[1].beta.set_data(pb.downsample[1].beta.data())
    # stem + head
    fused.features[0].weight.set_data(plain.features[0].weight.data())
    fused.output.weight.set_data(plain.output.weight.data())
    fused.output.bias.set_data(plain.output.bias.data())

    with autograd.pause():
        op = plain(x).asnumpy()
        of = fused(x).asnumpy()
    np.testing.assert_allclose(of, op, rtol=2e-3, atol=2e-3)


def test_fused_resnet_trains():
    """A fused resnet trains end to end (loss drops) through the Trainer
    path.  Bottleneck blocks (the resnet-50 shape) are covered by a single
    hybridized step; the loop uses resnet18 to keep interpreter-mode
    runtime down — the full-depth run happens on the TPU re-run suite."""
    from mxnet_tpu.gluon.model_zoo.vision import get_resnet
    mx.random.seed(0)
    net = get_resnet(1, 18, layout="NHWC", classes=4, thumbnail=True,
                     fused=True)
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9})
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(2)
    x = mx.nd.array(rng.randn(2, 16, 16, 3).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 4, (2,)).astype(np.int32))
    losses = []
    for _ in range(4):
        with autograd.record():
            loss = ce(net(x), y).mean()
        loss.backward()
        tr.step(2)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < losses[0], losses


def test_non_power_of_two_channels():
    """co=192 (not a multiple of the default 128 tile) must still be
    exact — the tile size adapts to divide co (regression)."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(1, 4, 4, 8).astype(np.float32))
    sc, sh = jnp.ones(8), jnp.zeros(8)
    w = jnp.asarray(rng.randn(3, 3, 8, 192).astype(np.float32) * 0.1)
    of = norm_relu_conv(x, sc, sh, w)
    orf = norm_relu_conv_reference(x, sc, sh, w)
    np.testing.assert_allclose(np.asarray(of), np.asarray(orf),
                               rtol=2e-4, atol=2e-4)
    g = jax.grad(lambda w: (norm_relu_conv(x, sc, sh, w) ** 2).sum())(w)
    gr = jax.grad(lambda w: (norm_relu_conv_reference(x, sc, sh, w) ** 2)
                  .sum())(w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("k,stride,h,co", [(3, 2, 8, 16), (3, 2, 9, 16),
                                           (1, 2, 8, 16), (3, 2, 8, 256)])
def test_kernel_stride2_parity(k, stride, h, co):
    """Stride-2 (the resnet downsample 3x3s): fwd + all grads match the
    XLA composition, incl. odd spatial extents and two channel tiles."""
    rng = np.random.RandomState(7)
    n, ci = 2, 8
    x = jnp.asarray(rng.randn(n, h, h, ci).astype(np.float32))
    sc = jnp.asarray(rng.rand(ci).astype(np.float32) + 0.5)
    sh = jnp.asarray(rng.randn(ci).astype(np.float32) * 0.1)
    w = jnp.asarray(rng.randn(k, k, ci, co).astype(np.float32) * 0.2)
    of = norm_relu_conv(x, sc, sh, w, stride=stride)
    orf = norm_relu_conv_reference(x, sc, sh, w, stride=stride)
    np.testing.assert_allclose(np.asarray(of), np.asarray(orf),
                               rtol=2e-4, atol=2e-4)
    gf = jax.grad(lambda *a: (norm_relu_conv(*a, stride=stride)
                              .astype(jnp.float32) ** 2).sum(),
                  argnums=(0, 1, 2, 3))(x, sc, sh, w)
    gr = jax.grad(lambda *a: (norm_relu_conv_reference(*a, stride=stride)
                              .astype(jnp.float32) ** 2).sum(),
                  argnums=(0, 1, 2, 3))(x, sc, sh, w)
    for i, (a, b) in enumerate(zip(gf, gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"grad {i}")


def test_layer_stride2():
    """NormReluConv2D(strides=2) halves spatial dims and trains."""
    layer = nn.NormReluConv2D(8, 3, strides=2, in_channels=4)
    layer.initialize()
    x = mx.nd.array(np.random.RandomState(0).randn(2, 8, 8, 4)
                    .astype(np.float32))
    x.attach_grad()
    with autograd.record():
        out = layer(x)
        (out * out).sum().backward()
    assert out.shape == (2, 4, 4, 8)
    assert np.isfinite(x.grad.asnumpy()).all()

