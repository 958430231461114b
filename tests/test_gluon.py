"""Gluon block/layer tests (ref: tests/python/unittest/test_gluon.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


def test_dense_shapes():
    layer = nn.Dense(10, in_units=4)
    layer.initialize()
    x = nd.ones((2, 4))
    out = layer(x)
    assert out.shape == (2, 10)


def test_dense_deferred_init():
    layer = nn.Dense(7)
    layer.initialize()
    x = nd.ones((3, 5))
    out = layer(x)
    assert out.shape == (3, 7)
    assert layer.weight.shape == (7, 5)


def test_dense_flatten_false():
    layer = nn.Dense(6, flatten=False)
    layer.initialize()
    out = layer(nd.ones((2, 3, 4)))
    assert out.shape == (2, 3, 6)


def test_sequential_and_collect_params():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    out = net(nd.ones((2, 16)))
    assert out.shape == (2, 4)
    params = net.collect_params()
    assert len(params) == 4  # 2 weights + 2 biases


def test_hybridize_matches_eager():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    x = nd.random.normal(shape=(2, 16))
    eager = net(x).asnumpy()
    net.hybridize()
    compiled = net(x).asnumpy()
    np.testing.assert_allclose(eager, compiled, rtol=2e-5, atol=2e-5)


def test_hybridize_grad_matches_eager():
    def run(hybridize):
        mx.random.seed(7)
        np.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="relu"), nn.Dense(1))
        net.initialize()
        if hybridize:
            net.hybridize()
        x = nd.array(np.random.randn(4, 6).astype(np.float32))
        with autograd.record():
            y = net(x)
            loss = (y * y).sum()
        loss.backward()
        w = net[0].weight
        return w.grad().asnumpy()

    g_eager = run(False)
    g_hybrid = run(True)
    np.testing.assert_allclose(g_eager, g_hybrid, rtol=2e-4, atol=2e-5)


def test_conv2d():
    layer = nn.Conv2D(4, kernel_size=3, padding=1)
    layer.initialize()
    out = layer(nd.ones((1, 3, 8, 8)))
    assert out.shape == (1, 4, 8, 8)


def test_conv2d_stride_groups():
    layer = nn.Conv2D(8, kernel_size=3, strides=2, padding=1, groups=2,
                      in_channels=4)
    layer.initialize()
    out = layer(nd.ones((2, 4, 8, 8)))
    assert out.shape == (2, 8, 4, 4)


def test_pooling_layers():
    x = nd.random.uniform(shape=(1, 2, 8, 8))
    assert nn.MaxPool2D(2)(x).shape == (1, 2, 4, 4)
    assert nn.AvgPool2D(2, strides=1)(x).shape == (1, 2, 7, 7)
    assert nn.GlobalAvgPool2D()(x).shape == (1, 2, 1, 1)


def test_batchnorm_updates_running_stats():
    layer = nn.BatchNorm(in_channels=3)
    layer.initialize()
    x = nd.array(np.random.randn(4, 3, 5, 5).astype(np.float32) * 3 + 1)
    before = layer.running_mean.data().asnumpy().copy()
    with autograd.record():
        layer(x)
    after = layer.running_mean.data().asnumpy()
    assert not np.allclose(before, after)


def test_batchnorm_hybridized_aux_update():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(3, 3, padding=1), nn.BatchNorm())
    net.initialize()
    x = nd.random.normal(shape=(2, 3, 6, 6))
    net(x)  # resolve deferred
    net.hybridize()
    bn = net[1]
    before = bn.running_mean.data().asnumpy().copy()
    with autograd.record():
        net(x)
    after = bn.running_mean.data().asnumpy()
    assert not np.allclose(before, after)


def test_batchnorm_eval_uses_running_stats():
    layer = nn.BatchNorm(in_channels=2)
    layer.initialize()
    x = nd.array(np.random.randn(8, 2, 4, 4).astype(np.float32))
    out_eval = layer(x)  # not recording -> predict mode: global stats (0,1)
    expected = x.asnumpy() / np.sqrt(1 + 1e-5)
    np.testing.assert_allclose(out_eval.asnumpy(), expected, rtol=1e-4, atol=1e-4)


def test_embedding():
    layer = nn.Embedding(10, 4)
    layer.initialize()
    out = layer(nd.array([[1, 2], [3, 4]], dtype=np.int32))
    assert out.shape == (2, 2, 4)


def test_dropout_train_vs_eval():
    layer = nn.Dropout(0.5)
    layer.initialize()
    x = nd.ones((100, 100))
    out_eval = layer(x)
    np.testing.assert_allclose(out_eval.asnumpy(), x.asnumpy())
    with autograd.record():
        out_train = layer(x)
    arr = out_train.asnumpy()
    assert (arr == 0).mean() > 0.3  # roughly half dropped


def test_layernorm():
    layer = nn.LayerNorm(in_channels=6)
    layer.initialize()
    out = layer(nd.random.normal(shape=(3, 6)))
    m = out.asnumpy().mean(axis=-1)
    np.testing.assert_allclose(m, np.zeros_like(m), atol=1e-5)


def test_save_load_parameters(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(8), nn.Dense(4))
    net.initialize()
    net(nd.ones((1, 6)))
    f = str(tmp_path / "params.npz")
    net.save_parameters(f)

    net2 = nn.HybridSequential()
    net2.add(nn.Dense(8), nn.Dense(4))
    net2.initialize()
    net2(nd.ones((1, 6)))
    net2.load_parameters(f)
    np.testing.assert_allclose(net[0].weight.data().asnumpy(),
                               net2[0].weight.data().asnumpy())


def test_trainer_sgd_step():
    net = nn.Dense(1, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.ones((4, 2))
    w_before = net.weight.data().asnumpy().copy()
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    trainer.step(4)
    w_after = net.weight.data().asnumpy()
    assert not np.allclose(w_before, w_after)


def test_prelu_swish_gelu():
    x = nd.random.normal(shape=(2, 3))
    for layer in [nn.PReLU(), nn.SELU(), nn.GELU(), nn.Swish(), nn.ELU(),
                  nn.LeakyReLU(0.1)]:
        layer.initialize()
        out = layer(x)
        assert out.shape == x.shape


def test_rnn_cells_unroll():
    cell = gluon.rnn.LSTMCell(8, input_size=4)
    cell.initialize()
    x = nd.random.normal(shape=(2, 5, 4))  # NTC
    outputs, states = cell.unroll(5, x, layout="NTC", merge_outputs=True)
    assert outputs.shape == (2, 5, 8)
    assert len(states) == 2


def test_fused_lstm_layer():
    layer = gluon.rnn.LSTM(16, num_layers=2)
    layer.initialize()
    x = nd.random.normal(shape=(7, 3, 8))  # TNC
    out = layer(x)
    assert out.shape == (7, 3, 16)
    states = layer.begin_state(batch_size=3)
    out, new_states = layer(x, states)
    assert out.shape == (7, 3, 16)
    assert new_states[0].shape == (2, 3, 16)


def test_fused_gru_bidirectional():
    layer = gluon.rnn.GRU(8, num_layers=1, bidirectional=True)
    layer.initialize()
    x = nd.random.normal(shape=(5, 2, 4))
    out = layer(x)
    assert out.shape == (5, 2, 16)


def test_loss_functions():
    pred = nd.random.normal(shape=(4, 10))
    label = nd.array([1, 2, 3, 4], dtype=np.int32)
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label)
    assert l.shape == (4,)
    l2 = gluon.loss.L2Loss()(pred, nd.zeros((4, 10)))
    assert l2.shape == (4,)
    bce = gluon.loss.SigmoidBCELoss()(pred, nd.ones((4, 10)))
    assert bce.shape == (4,)


def test_model_zoo_smoke():
    net = gluon.model_zoo.vision.get_model("resnet18_v1", classes=10)
    net.initialize()
    out = net(nd.random.normal(shape=(1, 3, 32, 32)))
    assert out.shape == (1, 10)


def test_model_zoo_inception_v3():
    import numpy as np
    net = gluon.model_zoo.vision.get_model("inceptionv3", classes=7)
    net.initialize()
    out = net(nd.random.normal(shape=(1, 3, 299, 299)))
    assert out.shape == (1, 7)
    n = sum(int(np.prod(p.shape)) for p in net.collect_params().values())
    assert 20e6 < n < 30e6   # the reference's ~23.8M at 1000 classes


def test_block_repr_and_summary(capsys):
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3))
    net.initialize()
    net.summary(nd.ones((1, 3)))
    out = capsys.readouterr().out
    assert "Total params" in out


def test_gluon_utils_split_and_clip():
    """gluon.utils (ref: python/mxnet/gluon/utils.py)."""
    from mxnet_tpu.gluon import utils
    x = mx.nd.array(np.arange(24, dtype=np.float32).reshape(6, 4))
    parts = utils.split_data(x, 3)
    assert [p.shape for p in parts] == [(2, 4)] * 3
    np.testing.assert_array_equal(parts[1].asnumpy(), x.asnumpy()[2:4])
    with pytest.raises(ValueError):
        utils.split_data(x, 4)  # uneven
    loaded = utils.split_and_load(x, [mx.cpu(), mx.cpu()])
    assert len(loaded) == 2 and loaded[0].shape == (3, 4)

    grads = [mx.nd.array(np.full((4,), 3.0, np.float32)),
             mx.nd.array(np.full((2,), 4.0, np.float32))]
    total = utils.clip_global_norm(grads, 1.0)
    expect = np.sqrt(9 * 4 + 16 * 2)
    assert abs(total - expect) < 1e-4
    new_norm = np.sqrt(sum(float((g * g).sum().asnumpy()) for g in grads))
    assert abs(new_norm - 1.0) < 1e-3  # rescaled to max_norm


def test_fixed_bucket_sampler():
    """Bucketing for variable-length sequences (ref: SURVEY §5.7 — the
    reference's bucketing story; fixed shape set avoids XLA recompiles)."""
    from mxnet_tpu.gluon.data import FixedBucketSampler
    rng = np.random.RandomState(0)
    lengths = rng.randint(5, 120, size=200)
    s = FixedBucketSampler(lengths, batch_size=16, num_buckets=5,
                           shuffle=True)  # default "keep": exact cover
    seen = []
    for batch in s:
        assert len(batch) <= 16
        blens = lengths[batch]
        # every sample fits its bucket key, and the batch spans ONE bucket
        keys = [k for k in s.bucket_keys if blens.max() <= k]
        assert keys, (blens.max(), s.bucket_keys)
        tight = keys[0]
        assert all(l <= tight for l in blens)
        seen.extend(batch)
    assert sorted(seen) == list(range(200))  # exact cover, no dupes
    assert len(s) == sum(1 for _ in s)
    assert "samples" in s.stats()
    # "pad": every batch full (fixed compiled shape set), padding re-samples
    # strictly from within ONE bucket, and every sample still appears
    sp = FixedBucketSampler(lengths, batch_size=16, num_buckets=5,
                            last_batch="pad")
    covered = []
    for batch in sp:
        assert len(batch) == 16
        assert any(set(batch) <= set(b) for b in sp._buckets)
        covered.extend(batch)
    assert set(covered) == set(range(200))
    # "discard": full batches only, no dupes
    sd = FixedBucketSampler(lengths, batch_size=16, num_buckets=5,
                            last_batch="discard")
    dropped = [b for b in sd]
    assert all(len(b) == 16 for b in dropped)
    flat = [i for b in dropped for i in b]
    assert len(flat) == len(set(flat))


def test_estimator_fit_and_handlers(tmp_path, caplog):
    """gluon.contrib.estimator (ref: estimator.py + event_handler.py):
    fit converges on a separable toy, logs, checkpoints, early-stops."""
    import logging
    from mxnet_tpu.gluon.contrib.estimator import (
        CheckpointHandler, EarlyStoppingHandler, Estimator, LoggingHandler)

    rng = np.random.RandomState(0)
    w = rng.randn(8, 3).astype(np.float32)
    x = rng.randn(256, 8).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.float32)
    ds = gluon.data.ArrayDataset(mx.nd.array(x), mx.nd.array(y))
    loader = gluon.data.DataLoader(ds, batch_size=32)

    net = gluon.nn.Dense(3, in_units=8)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.05})
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    trainer=trainer)
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.estimator"):
        est.fit(loader, val_data=loader, epochs=4, event_handlers=[
            LoggingHandler(),
            CheckpointHandler(str(tmp_path), monitor="val_loss",
                              save_best=True),
            EarlyStoppingHandler("val_accuracy", mode="max", patience=10),
        ])
    vals = dict(est.metric_values())
    assert vals["accuracy"] > 0.8, vals
    assert (tmp_path / "model-0003.params").exists()
    assert (tmp_path / "model-best.params").exists()
    assert any("epoch 3" in r.message for r in caplog.records)


def test_estimator_early_stopping(caplog):
    import logging
    from mxnet_tpu.gluon.contrib.estimator import (EarlyStoppingHandler,
                                                   Estimator)
    rng = np.random.RandomState(0)
    x = rng.randn(64, 4).astype(np.float32)
    y = rng.randint(0, 2, 64).astype(np.float32)  # pure noise
    ds = gluon.data.ArrayDataset(mx.nd.array(x), mx.nd.array(y))
    loader = gluon.data.DataLoader(ds, batch_size=16)
    net = gluon.nn.Dense(2, in_units=4)
    net.initialize()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    trainer=gluon.Trainer(net.collect_params(), "sgd",
                                          {"learning_rate": 0.0}))
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.estimator"):
        est.fit(loader, val_data=loader, epochs=50, event_handlers=[
            EarlyStoppingHandler("val_loss", patience=1)])
    # lr=0 → no improvement → stops long before 50 epochs
    assert est.current_epoch < 10


def test_mnist_real_idx_files_load(tmp_path):
    """When real IDX files exist the dataset reads THEM, not the synthetic
    stand-in (Weak #5 contract: gates run on real data where available)."""
    import gzip
    import struct
    from mxnet_tpu.gluon.data.vision import datasets
    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 255, (50, 28, 28), np.uint8)
    labs = rng.randint(0, 10, 50).astype(np.uint8)
    with gzip.open(str(tmp_path / "train-images-idx3-ubyte.gz"), "wb") as f:
        f.write(struct.pack(">IIII", 2051, 50, 28, 28))
        f.write(imgs.tobytes())
    with gzip.open(str(tmp_path / "train-labels-idx1-ubyte.gz"), "wb") as f:
        f.write(struct.pack(">II", 2049, 50))
        f.write(labs.tobytes())
    ds = datasets.MNIST(root=str(tmp_path), train=True)
    assert len(ds) == 50  # not the synthetic 8192
    x, y = ds[3]
    np.testing.assert_array_equal(np.asarray(x).squeeze(), imgs[3])
    assert int(y) == int(labs[3])


def test_export_symbolblock_imports_roundtrip(tmp_path):
    """HybridBlock.export → SymbolBlock.imports → forward parity WITHOUT the
    defining class (ref: SymbolBlock.imports over model-symbol.json +
    model-0000.params — SURVEY §5.4 model interchange)."""
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    mx.random.seed(0)
    net = resnet50_v1()
    net.initialize()
    net.hybridize()
    x = mx.nd.array(np.random.RandomState(0).randn(2, 3, 32, 32)
                    .astype(np.float32))
    ref = net(x).asnumpy()
    sym, par = net.export(str(tmp_path / "model"))
    blk = gluon.SymbolBlock.imports(sym)
    got = blk(x).asnumpy()
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # params visible on the imported block (servable checkpoint surface)
    assert len(blk.collect_params()) > 100

    # the real interchange claim: a FRESH process that never constructs the
    # model class can serve the artifact.  A chip belongs to one process,
    # and this one may hold it: the fresh process serves on XLA:CPU (the
    # export carries both platforms), across platforms when this is the TPU
    import os, subprocess, sys, textwrap
    code = textwrap.dedent(f"""
        import numpy as np
        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        blk = gluon.SymbolBlock.imports({str(sym)!r})
        x = mx.nd.array(np.random.RandomState(0).randn(2, 3, 32, 32)
                        .astype(np.float32))
        out = blk(x).asnumpy()
        np.save({str(tmp_path / "out.npy")!r}, out)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    # the TPU at its default matmul precision against XLA:CPU: 4.6e-3 apart
    # on these logits, measured on the chip (PR 21)
    tol = 2e-2 if mx.context.on_tpu() else 1e-5
    np.testing.assert_allclose(np.load(str(tmp_path / "out.npy")), ref,
                               rtol=tol, atol=tol)


def test_symbolblock_imports_legacy_artifact_message(tmp_path):
    """Artifacts without a serialized graph get the actionable error."""
    import json
    p = tmp_path / "old-symbol.json"
    p.write_text(json.dumps({"framework": "mxnet_tpu", "block": "X",
                             "params": "old-0000.params"}))
    with pytest.raises(ValueError, match="re-export"):
        gluon.SymbolBlock.imports(str(p))
