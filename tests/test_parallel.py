"""Mesh parallelism + KVStore tests on the virtual 8-device CPU mesh.

Mirrors the reference's distributed-without-a-cluster strategy
(SURVEY.md §4: tests/nightly/dist_sync_kvstore.py runs multi-process on one
machine; here the mesh itself is multi-device).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon import nn


def test_make_mesh_axes():
    mesh = parallel.make_mesh(dp=2, tp=4)
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
    mesh = parallel.make_mesh(dp=-1, tp=2)
    assert mesh.shape["dp"] == 4
    with pytest.raises(ValueError):
        parallel.make_mesh(dp=3, tp=4)


def test_shard_map_validates_spec_axes():
    """mesh.shard_map's call-time axis validation (the runtime twin of
    mxlint's spmd-axis-unknown): a typo'd axis in in_specs/out_specs
    raises a ValueError NAMING the axis at the wrapping site, instead
    of a deep jax internal error at trace time."""
    mesh = parallel.make_mesh(dp=8)
    with pytest.raises(ValueError, match="'pd'"):
        parallel.shard_map(lambda x: x, mesh=mesh,
                           in_specs=(PartitionSpec("pd"),),
                           out_specs=PartitionSpec())
    with pytest.raises(ValueError, match="out_specs.*'tp'"):
        parallel.shard_map(lambda x: x, mesh=mesh,
                           in_specs=(PartitionSpec("dp"),),
                           out_specs=PartitionSpec("tp"))
    # tuple-of-names spec entries are validated too
    with pytest.raises(ValueError, match="'sp'"):
        parallel.validate_specs(
            mesh, in_specs=(PartitionSpec(("dp", "sp")),))
    # a valid wrapper still runs (curried decorator form included)
    run = parallel.shard_map(lambda x: x * 2, mesh=mesh,
                             in_specs=(PartitionSpec("dp"),),
                             out_specs=PartitionSpec("dp"),
                             check_vma=False)
    out = run(jnp.ones((8, 4)))
    assert out.shape == (8, 4) and float(out[0, 0]) == 2.0
    deco = parallel.shard_map(mesh=mesh,
                              in_specs=(PartitionSpec("dp"),),
                              out_specs=PartitionSpec("dp"))
    assert deco(lambda x: x + 1)(jnp.zeros((8, 2))).shape == (8, 2)


def test_sharding_rules_tp():
    mesh = parallel.make_mesh(dp=2, tp=4)
    rules = parallel.tp_dense_rules()
    spec = rules.spec_for("bert0_query_weight", (64, 32), mesh)
    assert spec == PartitionSpec("tp", None)
    spec = rules.spec_for("bert0_proj_weight", (32, 64), mesh)
    assert spec == PartitionSpec(None, "tp")
    # non-divisible shape falls back to replicated
    spec = rules.spec_for("bert0_query_weight", (63, 32), mesh)
    assert spec == PartitionSpec()


def _mlp():
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=16),
            nn.Dense(10, in_units=32))
    net.initialize()
    return net


def test_train_step_dp_matches_trainer():
    """Fused sharded step must match the eager Trainer update numerically."""
    np.random.seed(3)
    x = np.random.randn(16, 16).astype(np.float32)
    y = np.random.randint(0, 10, (16,))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # eager reference path
    mx.random.seed(7)
    net_e = _mlp()
    trainer = gluon.Trainer(net_e.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore=None)
    for _ in range(3):
        with mx.autograd.record():
            loss = loss_fn(net_e(mx.nd.array(x)), mx.nd.array(y))
        loss.backward()
        trainer.step(16)

    # fused mesh path
    mx.random.seed(7)
    net_f = _mlp()
    mesh = parallel.make_mesh(dp=8)
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
    step = parallel.TrainStep(net_f, loss_fn, opt, mesh=mesh)
    for _ in range(3):
        step(mx.nd.array(x), mx.nd.array(y))
    step.sync_params_to_net()

    for (n1, p1), (n2, p2) in zip(
            sorted(net_e.collect_params().items()),
            sorted(net_f.collect_params().items())):
        np.testing.assert_allclose(p1.data().asnumpy(), p2.data().asnumpy(),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"{n1} vs {n2}")


def test_train_step_loss_decreases_tp():
    np.random.seed(0)
    x = np.random.randn(32, 16).astype(np.float32)
    y = np.random.randint(0, 10, (32,))
    net = _mlp()
    mesh = parallel.make_mesh(dp=2, tp=4)
    rules = parallel.ShardingRules(
        rules=[(r"dense0_weight", ("tp", None)),
               (r"dense0_bias", ("tp",)),
               (r"dense1_weight", (None, "tp"))])
    opt = mx.optimizer.create("adam", learning_rate=1e-2)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), opt,
                              mesh=mesh, rules=rules)
    losses = [float(step(mx.nd.array(x), mx.nd.array(y)).asnumpy())
              for _ in range(10)]
    assert losses[-1] < losses[0] - 0.4, losses


def test_train_step_batchnorm_aux():
    """BatchNorm running stats must update through the fused step (the
    aux-state path, ref: cached_op.cc aux_states)."""
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4), nn.BatchNorm(in_channels=8),
            nn.Dense(2, in_units=8))
    net.initialize()
    mesh = parallel.make_mesh(dp=8)
    opt = mx.optimizer.create("sgd", learning_rate=0.01)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), opt,
                              mesh=mesh)
    x = np.random.randn(16, 4).astype(np.float32) * 3 + 1
    y = np.random.randint(0, 2, (16,))
    before = None
    for name, p in net.collect_params().items():
        if "running_mean" in name:
            before = p.data().asnumpy().copy()
    for _ in range(3):
        step(mx.nd.array(x), mx.nd.array(y))
    step.sync_params_to_net()
    after = None
    for name, p in net.collect_params().items():
        if "running_mean" in name:
            after = p.data().asnumpy()
    assert before is not None and not np.allclose(before, after)


def test_eval_step():
    net = _mlp()
    mesh = parallel.make_mesh(dp=8)
    ev = parallel.EvalStep(net, mesh=mesh)
    x = mx.nd.array(np.random.randn(16, 16).astype(np.float32))
    out = ev(x)
    ref = net(x)
    np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(), rtol=1e-5,
                               atol=1e-5)


# ------------------------------------------------------------------ kvstore --
def test_kvstore_push_pull_aggregate():
    kv = mx.kv.create("device")
    kv.init(3, mx.nd.ones((2, 3)))
    vals = [mx.nd.ones((2, 3)) * i for i in range(4)]
    kv.push(3, vals)
    out = mx.nd.zeros((2, 3))
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full((2, 3), 6.0))


def test_kvstore_update_on_kvstore():
    # server-side-optimizer semantics (ref: kvstore_dist_server.h) are
    # type-independent; dist_* types additionally require a multi-process run
    kv = mx.kv.create("device")
    opt = mx.optimizer.create("sgd", learning_rate=0.5)
    kv.set_optimizer(opt)
    w0 = np.ones((4,), np.float32)
    kv.init(0, mx.nd.array(w0))
    g = mx.nd.array(np.full((4,), 2.0, np.float32))
    kv.push(0, g)
    out = mx.nd.zeros((4,))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), w0 - 0.5 * 2.0)


def test_kvstore_gradient_compression():
    kv = mx.kv.create("device")
    kv.set_gradient_compression({"type": "2bit", "threshold": 1.0})
    kv.init(0, mx.nd.zeros((4,)))
    g = mx.nd.array(np.array([2.0, 0.3, -1.5, 0.0], np.float32))
    kv.push(0, g)
    out = mx.nd.zeros((4,))
    kv.pull(0, out=out)
    # quantized to {-1, 0, +1} * threshold
    np.testing.assert_allclose(out.asnumpy(), [1.0, 0.0, -1.0, 0.0])
    # residual carries the error: pushing zeros flushes accumulated residual
    kv.push(0, mx.nd.array(np.array([2.0, 0.3, -1.5, 0.0], np.float32)))
    kv.pull(0, out=out)
    np.testing.assert_allclose(out.asnumpy(), [1.0, 0.0, -1.0, 0.0])


def test_trainer_with_kvstore_allreduce():
    net = _mlp()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="device")
    x = mx.nd.array(np.random.randn(8, 16).astype(np.float32))
    y = mx.nd.array(np.random.randint(0, 10, (8,)))
    with mx.autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    before = net.collect_params()
    trainer.step(8)  # must not raise; weights move
    l2 = float(loss.asnumpy().mean())
    assert np.isfinite(l2)


def test_train_step_adagrad_and_lamb():
    """Regression: every fused optimizer path must at least run + descend."""
    for name in ("adagrad", "lamb", "adamw", "nag"):
        net = _mlp()
        opt = mx.optimizer.create(name, learning_rate=1e-2)
        step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                  opt, mesh=parallel.make_mesh(dp=8))
        x = mx.nd.array(np.random.randn(16, 16).astype(np.float32))
        y = mx.nd.array(np.random.randint(0, 10, (16,)))
        l0 = float(step(x, y).asnumpy())
        for _ in range(4):
            l1 = float(step(x, y).asnumpy())
        assert np.isfinite(l1) and l1 < l0, (name, l0, l1)


def test_train_step_bf16_multi_precision():
    """bf16 params train with fp32 master weights in state (ref: mp_sgd_update)
    and param/state dtypes stay fixed across steps (no silent fp32 promotion,
    which would retrace the compiled step with mismatched conv dtypes)."""
    net = _mlp()
    net.cast("bfloat16")
    mesh = parallel.make_mesh(dp=8)
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), opt,
                              mesh=mesh)
    x = mx.nd.array(np.random.randn(16, 16).astype(np.float32)).astype("bfloat16")
    y = mx.nd.array(np.random.randint(0, 10, (16,)))
    l0 = float(step(x, y).asnumpy())
    for _ in range(9):
        l1 = float(step(x, y).asnumpy())
    assert np.isfinite(l1) and l1 < l0
    for a in step._train_arrays:
        assert a.dtype == jnp.bfloat16, a.dtype
    for s in step._states:
        assert s[-1].dtype == jnp.float32  # fp32 master weight
    # exactly one trace: dtype drift in pure_update would retrace every step
    assert step._jit._cache_size() == 1, step._jit._cache_size()


def test_kvstore_string_keys_distinct_state():
    kv = mx.kv.create("local")
    opt = mx.optimizer.create("adam", learning_rate=0.1)
    kv.set_optimizer(opt)
    kv.init(["weight", "bias"], [mx.nd.ones((2,)), mx.nd.ones((2,))])
    for _ in range(2):
        kv.push(["weight", "bias"],
                [mx.nd.ones((2,)), mx.nd.ones((2,))])
    # each key must have advanced its own update count exactly twice
    idx_w = kv._key_index["weight"]
    idx_b = kv._key_index["bias"]
    assert idx_w != idx_b
    assert opt._index_update_count[idx_w] == 2
    assert opt._index_update_count[idx_b] == 2


def test_kvstore_pull_mismatch_raises():
    kv = mx.kv.create("local")
    kv.init([0, 1, 2], [mx.nd.ones((2,))] * 3)
    with pytest.raises(ValueError):
        kv.pull([0, 1, 2], out=[mx.nd.zeros((2,)), mx.nd.zeros((2,))])


def test_trainstep_cost_analysis():
    """TrainStep.cost_analysis(): XLA's cost model of the compiled step
    (a static count)."""
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize()
    step = parallel.TrainStep(net, gluon.loss.L2Loss(),
                              mx.optimizer.create("sgd", learning_rate=0.1),
                              mesh=parallel.make_mesh(dp=-1))
    with pytest.raises(RuntimeError):
        step.cost_analysis()
    x = mx.nd.array(np.random.randn(8, 8).astype(np.float32))
    y = mx.nd.array(np.random.randn(8, 4).astype(np.float32))
    step(x, y).asnumpy()
    costs = step.cost_analysis()
    assert costs.get("flops", 0) > 0
    assert costs.get("bytes accessed", 0) > 0


def test_trainstep_cost_analysis_lower_only():
    """The ISSUE 6 budget path: cost_analysis/memory_analysis from a
    sample batch, WITHOUT ever executing a step — and the audit must not
    perturb training state (params, update counter, RNG stream)."""
    mx.random.seed(11)
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize()
    step = parallel.TrainStep(net, gluon.loss.L2Loss(),
                              mx.optimizer.create("sgd", learning_rate=0.1),
                              mesh=parallel.make_mesh(dp=-1))
    x = np.zeros((8, 8), np.float32)
    y = np.zeros((8, 4), np.float32)
    key_before = mx.random.current_key_source().key
    costs = step.cost_analysis(x, y)        # no step has run
    assert costs.get("flops", 0) > 0
    assert costs.get("bytes accessed", 0) > 0
    memstats = step.memory_analysis(x, y)
    assert memstats.argument_size_in_bytes > 0
    # the audit consumed no RNG and advanced no update counter
    assert memstats is not None
    assert step._num_update == step.optimizer.begin_num_update
    assert mx.random.current_key_source().key is key_before
    # the program it costed is the one a real step then reuses: stepping
    # afterwards must not recompile (same signature -> same executable)
    step(mx.nd.array(np.random.randn(8, 8).astype(np.float32)),
         mx.nd.array(np.random.randn(8, 4).astype(np.float32))).asnumpy()
    assert step._jit._cache_size() == 1
    # and the cached AOT costing survives the step (no second compile)
    assert step.cost_analysis() is costs


def test_trainstep_cost_analysis_tracks_signature_changes():
    """A sample batch with a NEW signature must re-lower and re-cost —
    never serve the previous signature's cached numbers."""
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize()
    step = parallel.TrainStep(net, gluon.loss.L2Loss(),
                              mx.optimizer.create("sgd", learning_rate=0.1),
                              mesh=parallel.make_mesh(dp=-1))
    small = step.cost_analysis(np.zeros((8, 8), np.float32),
                               np.zeros((8, 4), np.float32))
    big = step.cost_analysis(np.zeros((16, 8), np.float32),
                             np.zeros((16, 4), np.float32))
    assert big["bytes accessed"] > small["bytes accessed"]
    mem = step.memory_analysis()       # follows the current signature
    assert mem.argument_size_in_bytes > 0
