"""Aux subsystems: callbacks (SURVEY §5.5), custom-op escape hatch
(ref: src/operator/custom/custom.cc; tests/python/unittest/test_operator.py
test_custom_op), storage introspection, packed gradient compression."""
import logging
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, callback, gluon, operator


# ------------------------------------------------------------- callbacks ----
def test_speedometer_logs(caplog):
    sp = callback.Speedometer(batch_size=32, frequent=2, auto_reset=False)
    metric = mx.metric.Accuracy()
    metric.update([mx.nd.array([1, 1])], [mx.nd.array([[0.1, 0.9],
                                                       [0.2, 0.8]])])
    with caplog.at_level(logging.INFO):
        for nb in range(1, 5):
            sp(callback.BatchEndParam(epoch=0, nbatch=nb, eval_metric=metric))
    assert any("samples/sec" in r.message for r in caplog.records)
    assert any("accuracy" in r.message for r in caplog.records)


def test_do_checkpoint(tmp_path):
    net = gluon.nn.Dense(3, in_units=2)
    net.initialize()
    cb = callback.do_checkpoint(str(tmp_path / "model"), period=2)
    cb(0, net)   # epoch 1: no save
    cb(1, net)   # epoch 2: save
    assert not (tmp_path / "model-0001.params").exists()
    assert (tmp_path / "model-0002.params").exists()
    net2 = gluon.nn.Dense(3, in_units=2)
    net2.load_parameters(str(tmp_path / "model-0002.params"))
    np.testing.assert_allclose(net2.weight.data().asnumpy(),
                               net.weight.data().asnumpy())


# ------------------------------------------------------------- custom op ----
@operator.register("scaled_square")
class ScaledSquareProp(operator.CustomOpProp):
    def __init__(self, scale=2.0):
        super().__init__(need_top_grad=True)
        self._scale = float(scale)

    def create_operator(self, ctx, shapes, dtypes):
        outer = self

        class ScaledSquare(operator.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                x = in_data[0]
                self.assign(out_data[0], req[0], x * x * outer._scale)

            def backward(self, req, out_grad, in_data, out_data, in_grad,
                         aux):
                x = in_data[0]
                self.assign(in_grad[0], req[0],
                            out_grad[0] * 2.0 * outer._scale * x)

        return ScaledSquare()


def test_custom_op_forward_and_grad():
    x = mx.nd.array(np.array([1.0, 2.0, 3.0], np.float32))
    out = mx.nd.Custom(x, op_type="scaled_square", scale=3.0)
    np.testing.assert_allclose(out.asnumpy(), [3, 12, 27])
    x.attach_grad()
    with autograd.record():
        y = mx.nd.Custom(x, op_type="scaled_square")
        loss = y.sum()
    loss.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), [4, 8, 12])  # 2*2*x


def test_custom_op_unknown_name():
    with pytest.raises(ValueError, match="not registered"):
        mx.nd.Custom(mx.nd.ones((2,)), op_type="nope")


# -------------------------------------------------------------- storage -----
def test_memory_info_surface():
    info = mx.current_context().memory_info()
    assert isinstance(info, dict)   # CPU backends may report {}
    free, total = mx.gpu_memory_info()
    assert free <= total


# ------------------------------------------- gradient compression packing ---
def test_2bit_pack_roundtrip():
    from mxnet_tpu.kvstore.kvstore import (_pack_2bit, _quant_2bit,
                                           _unpack_sum_2bit)
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(7, 13).astype(np.float32))
    q, res = _quant_2bit(g, jnp.zeros_like(g), 0.5)
    packed = _pack_2bit(q)
    assert packed.dtype == jnp.uint8
    assert packed.size == int(np.ceil(g.size / 4))       # 16x smaller than f32
    back = _unpack_sum_2bit(packed[None], jnp.float32(0.5), tuple(g.shape),
                            str(g.dtype))
    np.testing.assert_allclose(np.asarray(back), np.asarray(q))
    # multi-peer decode+sum in one shot
    both = _unpack_sum_2bit(jnp.stack([packed, packed]), jnp.float32(0.5),
                            tuple(g.shape), str(g.dtype))
    np.testing.assert_allclose(np.asarray(both), 2 * np.asarray(q))
    # error feedback preserved: q + residual == original
    np.testing.assert_allclose(np.asarray(q + res), np.asarray(g), rtol=1e-6)


def test_compression_end_to_end_single_process():
    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init(0, mx.nd.zeros((8,)))
    kv.push(0, mx.nd.array(np.array([1.0, -1.0, 0.1, -0.1, 2.0, 0.0, 0.7,
                                     -0.7], np.float32)))
    out = mx.nd.zeros((8,))
    kv.pull(0, out=out)
    np.testing.assert_allclose(
        out.asnumpy(), [0.5, -0.5, 0.0, 0.0, 0.5, 0.0, 0.5, -0.5])


# ---------------------------------------------------- int8 quantization -----
def test_quantized_conv_matches_float():
    import jax.numpy as jnp
    from mxnet_tpu.ndarray import invoke
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 8, 8).astype(np.float32)
    w = rng.randn(4, 3, 3, 3).astype(np.float32) * 0.2
    # quantize inputs/weights with known ranges
    ax, aw = np.abs(x).max(), np.abs(w).max()
    xq = np.clip(np.round(x * 127 / ax), -127, 127).astype(np.int8)
    wq = np.clip(np.round(w * 127 / aw), -127, 127).astype(np.int8)
    out = invoke("quantized_conv", mx.nd.array(xq), mx.nd.array(wq), None,
                 -float(ax), float(ax), -float(aw), float(aw),
                 kernel=(3, 3), stride=(1, 1), pad=(1, 1), num_filter=4,
                 no_bias=True)
    ref = invoke("Convolution", mx.nd.array(x), mx.nd.array(w), None,
                 kernel=(3, 3), stride=(1, 1), pad=(1, 1), num_filter=4,
                 no_bias=True)
    err = np.abs(out.asnumpy() - ref.asnumpy()).max()
    scale = np.abs(ref.asnumpy()).max()
    assert err / scale < 0.05, (err, scale)   # int8 tolerance


def test_quantize_net_calibrated():
    """quantize_net: calibrate + swap; int8 net tracks the float net and
    keeps argmax predictions mostly identical (ref: quantize_net flow)."""
    from mxnet_tpu.contrib.quantization import (quantize_net, QuantizedConv2D,
                                                QuantizedDense)
    mx.random.seed(0)
    rng = np.random.RandomState(1)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=1, in_channels=3,
                            activation="relu"),
            gluon.nn.GlobalAvgPool2D(), gluon.nn.Flatten(),
            gluon.nn.Dense(5, in_units=8))
    net.initialize(mx.init.Xavier())
    calib = [rng.randn(4, 3, 12, 12).astype(np.float32) for _ in range(3)]
    test = mx.nd.array(rng.randn(16, 3, 12, 12).astype(np.float32))
    ref = net(test).asnumpy()

    quantize_net(net, calib_data=calib)
    kinds = [type(c).__name__ for c in net._children.values()]
    assert "QuantizedConv2D" in kinds and "QuantizedDense" in kinds
    got = net(test).asnumpy()
    agree = (got.argmax(1) == ref.argmax(1)).mean()
    assert agree >= 0.8, agree
    rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 0.2, rel


def test_quantize_net_dense_activation_and_dilated_conv():
    """Fused activations survive quantization, and dilated convs keep their
    dilation (regression: both were silently dropped)."""
    from mxnet_tpu.contrib.quantization import quantize_net
    mx.random.seed(0)
    rng = np.random.RandomState(2)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=2, dilation=2, in_channels=3),
            gluon.nn.GlobalAvgPool2D(), gluon.nn.Flatten(),
            gluon.nn.Dense(6, in_units=8, activation="relu"))
    net.initialize(mx.init.Xavier())
    calib = [rng.randn(4, 3, 12, 12).astype(np.float32) for _ in range(3)]
    test = mx.nd.array(rng.randn(8, 3, 12, 12).astype(np.float32))
    ref = net(test).asnumpy()
    assert (ref >= 0).all()  # relu through the Dense

    quantize_net(net, calib_data=calib)
    got = net(test).asnumpy()
    assert got.shape == ref.shape  # dilation preserved → same spatial math
    assert (got >= 0).all()  # activation still applied after quantization
    rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
    assert rel < 0.25, rel


def test_quantize_net_on_hybridized_net():
    """quantize_net after hybridize()+forward: stale jit caches must not
    serve the old float graph (regression)."""
    from mxnet_tpu.contrib.quantization import quantize_net, QuantizedDense
    mx.random.seed(0)
    rng = np.random.RandomState(3)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4, in_units=6))
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(rng.randn(5, 6).astype(np.float32))
    net.hybridize()
    net(x)  # builds the compiled float forward
    calib = [rng.randn(4, 6).astype(np.float32) for _ in range(2)]
    quantize_net(net, calib_data=calib)
    kinds = [type(c).__name__ for c in net._children.values()]
    assert kinds == ["QuantizedDense"]
    # the forward must now run the quantized graph, not the stale jit cache
    float_ref = x.asnumpy() @ np.zeros((6, 4), np.float32)  # shape check only
    got = net(x).asnumpy()
    assert got.shape == float_ref.shape
    q = next(iter(net._children.values()))
    manual = QuantizedDense.forward(q, mx.nd.array(x.asnumpy())).asnumpy()
    assert np.allclose(got, manual, atol=1e-6)


def test_quantize_net_survives_calibration_failure():
    """A bad calibration batch must not leave collector wrappers or lost
    hybridization behind (regression)."""
    from mxnet_tpu.contrib.quantization import quantize_net
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4, in_units=6))
    net.initialize()
    net.hybridize()
    x = mx.nd.array(np.ones((2, 6), np.float32))
    net(x)
    with pytest.raises(Exception):
        quantize_net(net, calib_data=[np.ones((2, 3), np.float32)])  # bad shape
    kinds = [type(c).__name__ for c in net._children.values()]
    assert kinds == ["Dense"]  # collectors unwrapped
    assert getattr(net, "_active", False)  # hybridization restored
    out = net(x)
    assert out.shape == (2, 4)


def test_util_module():
    """mx.util surface (ref: python/mxnet/util.py)."""
    import tempfile
    d = tempfile.mkdtemp()
    mx.util.makedirs(os.path.join(d, "a/b/c"))
    assert os.path.isdir(os.path.join(d, "a/b/c"))
    mx.util.makedirs(os.path.join(d, "a/b/c"))  # idempotent
    assert mx.util.getenv("MXNET_ENGINE_TYPE") == "ThreadedEnginePerDevice"
    mx.util.setenv("MXNET_TEST_DUMMY", "42")
    assert os.environ["MXNET_TEST_DUMMY"] == "42"
    assert mx.util.is_np_array() in (True, False)

    @mx.util.use_np
    def np_mode_fn():
        return mx.util.is_np_array()

    assert np_mode_fn() is True
    assert mx.util.is_np_array() is False  # reset after the call


def test_runtime_features():
    """mx.runtime.Features (ref: python/mxnet/runtime.py)."""
    feats = mx.runtime.Features()
    assert feats.is_enabled("CPU")
    import jax
    assert feats.is_enabled("CUDA") == (jax.default_backend()
                                        in ("gpu", "cuda"))
    assert feats.is_enabled("TPU") == mx.context.on_tpu()
    assert feats.is_enabled("INT8")
    assert "RECORDIO_NATIVE" in feats
    with pytest.raises(RuntimeError, match="unknown feature"):
        feats.is_enabled("WARP_DRIVE")
    assert mx.runtime.feature_list()


def test_visualization_print_summary(capsys):
    """mx.viz.print_summary (ref: visualization.py)."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, in_units=4, activation="relu"),
            gluon.nn.Dense(2, in_units=8))
    net.initialize()
    mx.viz.print_summary(net, shape=(1, 4))
    out = capsys.readouterr().out
    assert "Dense" in out
    assert "(1, 2)" in out  # hooked forward captured output shapes
    mx.viz.print_summary(net)  # shape-less form: param table only
    out2 = capsys.readouterr().out
    assert "Total params" in out2
    # plot_network works on SYMBOLS (emits DOT); a Block points at summary
    with pytest.raises(TypeError, match="Symbol"):
        mx.viz.plot_network(net)


def test_summary_on_warm_hybridized_net(capsys):
    """summary must capture child output shapes even when the children's
    jit caches are warm (regression: hooks skipped on cache hits)."""
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, in_units=4), gluon.nn.Dense(2, in_units=8))
    net.initialize()
    net.hybridize()
    x = mx.nd.array(np.ones((3, 4), np.float32))
    net(x)  # warm the compiled path
    net(x)
    net.summary(x)
    out = capsys.readouterr().out
    assert "(3, 8)" in out and "(3, 2)" in out  # child shapes present


@pytest.mark.slow
def test_int8_quantized_zoo_model_accuracy_gate():
    """THE int8 workflow gate: train a model-zoo
    network to real accuracy on separable data, quantize it with
    calibration, and assert the int8 model's accuracy is within epsilon
    of float (ref: quantize_net + imagenet_inference.py validation)."""
    from mxnet_tpu.contrib.quantization import quantize_net
    from mxnet_tpu import autograd

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    n_cls, n_train, n_val = 4, 256, 128

    def make_split(n):
        # strongly separable: class k shifts channel k%3 globally AND
        # lights quadrant k — converges in a handful of steps
        y = rng.randint(0, n_cls, n)
        x = rng.randn(n, 3, 16, 16).astype(np.float32) * 0.3
        for i, k in enumerate(y):
            x[i, int(k) % 3] += 1.0 + 0.5 * (int(k) // 3)
            r, c = divmod(int(k), 2)
            x[i, :, r * 8:(r + 1) * 8, c * 8:(c + 1) * 8] += 1.5
        return x, y.astype(np.int32)

    xtr, ytr = make_split(n_train)
    xva, yva = make_split(n_val)

    net = gluon.model_zoo.vision.get_model("resnet18_v1", classes=n_cls,
                                           thumbnail=True)
    net.initialize(mx.init.Xavier())
    net.hybridize()  # one compiled step: CPU-affordable training loop
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 3e-3})
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    bs = 64
    for epoch in range(4):
        for i in range(0, n_train, bs):
            xb = mx.nd.array(xtr[i:i + bs])
            yb = mx.nd.array(ytr[i:i + bs])
            with autograd.record():
                loss = ce(net(xb), yb).mean()
            loss.backward()
            tr.step(xb.shape[0])
    # settle BN running stats (momentum 0.9 needs ~30 updates; forwards in
    # record mode update the aux state without touching weights)
    for _ in range(16):
        with autograd.record():
            net(mx.nd.array(xtr[:bs]))

    def accuracy(model):
        pred = model(mx.nd.array(xva)).asnumpy().argmax(1)
        return float((pred == yva).mean())

    float_acc = accuracy(net)
    assert float_acc >= 0.9, f"float model underfit: {float_acc}"

    calib = [xtr[i:i + bs] for i in range(0, 192, bs)]
    quantize_net(net, calib_data=calib)
    # the zoo model's conv/dense layers actually swapped
    names = []

    def _walk(b):
        names.append(type(b).__name__)
        for c in b._children.values():
            _walk(c)

    _walk(net)
    assert "QuantizedConv2D" in names and "QuantizedDense" in names
    int8_acc = accuracy(net)
    assert int8_acc >= float_acc - 0.05, (float_acc, int8_acc)


def test_log_module(tmp_path):
    """ref: python/mxnet/log.py — get_logger is idempotent and writes
    through the chosen handler."""
    f = str(tmp_path / "t.log")
    lg = mx.log.get_logger("mxtpu_test_logger", filename=f,
                           level=mx.log.INFO)
    lg2 = mx.log.get_logger("mxtpu_test_logger")
    assert lg is lg2 and len(lg.handlers) == 1   # no duplicate handlers
    lg.info("hello-from-test")
    lg.handlers[0].flush()
    assert "hello-from-test" in open(f).read()


def test_mnist_iter(tmp_path):
    """ref: io.MNISTIter — classic iterator: IDX parsing, seed-stable
    shuffle, NCHW default + flat form."""
    # explicit IDX paths parse directly (gz and raw), never silently fall back
    import gzip
    import struct
    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 256, (10, 28, 28)).astype(np.uint8)
    labs = rng.randint(0, 10, (10,)).astype(np.uint8)
    img_p = str(tmp_path / "train-images-idx3-ubyte.gz")
    lab_p = str(tmp_path / "train-labels-idx1-ubyte")
    with gzip.open(img_p, "wb") as f:
        f.write(struct.pack(">HBB", 0, 8, 3) + struct.pack(">III", 10, 28, 28)
                + imgs.tobytes())
    with open(lab_p, "wb") as f:
        f.write(struct.pack(">HBB", 0, 8, 1) + struct.pack(">I", 10)
                + labs.tobytes())
    itx = mx.io.MNISTIter(image=img_p, label=lab_p, batch_size=5,
                          shuffle=False)
    b0 = next(iter(itx))
    np.testing.assert_allclose(b0.data[0].asnumpy()[0, 0],
                               imgs[0].astype(np.float32) / 255.0)
    np.testing.assert_allclose(b0.label[0].asnumpy(),
                               labs[:5].astype(np.float32))
    with pytest.raises(ValueError, match="not found"):
        mx.io.MNISTIter(image=str(tmp_path / "nope"), label=lab_p)
    # seed makes the shuffle order reproducible
    def order(seed):
        it = mx.io.MNISTIter(image=img_p, label=lab_p, batch_size=10,
                             shuffle=True, seed=seed)
        return next(iter(it)).label[0].asnumpy()
    np.testing.assert_array_equal(order(3), order(3))

    it = mx.io.MNISTIter(batch_size=64, shuffle=True)
    b = next(iter(it))
    assert b.data[0].shape == (64, 1, 28, 28)
    assert b.label[0].shape == (64,)
    x = b.data[0].asnumpy()
    assert 0.0 <= x.min() and x.max() <= 1.0
    flat = mx.io.MNISTIter(batch_size=32, flat=True, shuffle=False)
    assert next(iter(flat)).data[0].shape == (32, 784)
    # a classic Module script trains from it end to end
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), name="fc", num_hidden=10), name="softmax",
        normalization="batch")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(flat, optimizer="adam",
            optimizer_params=(("learning_rate", 0.05),), num_epoch=2)
    assert mod.score(flat, "acc")[0][1] > 0.5


def test_read_idx_validates_header(tmp_path):
    """ISSUE 3 satellite: _read_idx must reject non-IDX/corrupt/int32
    files with a ValueError naming the path instead of parsing them as
    uint8 garbage."""
    import gzip
    import struct

    from mxnet_tpu.io import _read_idx

    good = tmp_path / "ok-idx1-ubyte"
    good.write_bytes(struct.pack(">HBB", 0, 8, 1) + struct.pack(">I", 4)
                     + bytes([1, 2, 3, 4]))
    np.testing.assert_array_equal(_read_idx(str(good)), [1, 2, 3, 4])

    # bad magic (bytes 0-1 non-zero): e.g. a PNG or text file
    bad_magic = tmp_path / "not-idx"
    bad_magic.write_bytes(b"\x89PNG....")
    with pytest.raises(ValueError, match="not-idx.*magic"):
        _read_idx(str(bad_magic))

    # int32 dtype byte (0x0c) must not be read as uint8 garbage
    int32 = tmp_path / "int32-idx"
    int32.write_bytes(struct.pack(">HBB", 0, 0x0C, 1)
                      + struct.pack(">I", 2) + b"\x00" * 8)
    with pytest.raises(ValueError, match="int32-idx.*0x0c"):
        _read_idx(str(int32))

    # truncated payload: dims promise more bytes than the file holds
    trunc = tmp_path / "trunc-idx.gz"
    with gzip.open(trunc, "wb") as f:
        f.write(struct.pack(">HBB", 0, 8, 3)
                + struct.pack(">III", 10, 28, 28) + b"\x00" * 100)
    with pytest.raises(ValueError, match="trunc-idx.*truncated or corrupt"):
        _read_idx(str(trunc))

    # truncated header: rank promises dims the header doesn't contain
    short = tmp_path / "short-idx"
    short.write_bytes(struct.pack(">HBB", 0, 8, 3) + b"\x00\x00")
    with pytest.raises(ValueError, match="short-idx.*truncated IDX header"):
        _read_idx(str(short))

    # MNISTIter surfaces the same error (not garbage batches)
    lab = tmp_path / "labels-idx1-ubyte"
    lab.write_bytes(struct.pack(">HBB", 0, 8, 1) + struct.pack(">I", 4)
                    + bytes([0, 1, 2, 3]))
    with pytest.raises(ValueError, match="magic"):
        mx.io.MNISTIter(image=str(bad_magic), label=str(lab), batch_size=2)


def test_prune_fit_snapshots_wide_stamps(tmp_path):
    """The n%04d/b%06d stamp widths are minimums: epoch>=10000 or
    nbatch>=1e6 widen the field and must still be pruned (fixed-width
    \\d{4}/\\d{6} left them on disk forever)."""
    from mxnet_tpu.module import _prune_fit_snapshots

    prefix = str(tmp_path / "model")
    keep = "n0001b000005"
    names = [f"model-{keep}.params", f"model-{keep}-symbol.json",
             "model-n0002b000001.params",          # stale, classic width
             "model-n10000b1000000.params",        # stale, wide stamp
             "model-n10000b1000000.tmp-optstate",  # orphan tmp, wide
             "model-notes.txt",                    # unrelated user file
             "model-new-symbol.json"]              # unrelated prefix-ish
    for n in names:
        (tmp_path / n).write_text("x")
    _prune_fit_snapshots(prefix, keep_stamp=keep)
    left = sorted(p.name for p in tmp_path.iterdir())
    assert left == sorted([f"model-{keep}.params",
                           f"model-{keep}-symbol.json",
                           "model-notes.txt", "model-new-symbol.json"])
