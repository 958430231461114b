"""A model's parameters are materialised once, in bulk, by one compiled
program (ISSUE 26): ``Parameter.initialize()`` records and draws keys,
``gluon.block.infer_shapes`` fills the deferred shapes abstractly, and
``parameter.materialize`` runs every initializer in one executable whose keys
are arguments.  The one-at-a-time path (``p.data()``, an eager forward) stays
and gives the same bits."""
import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, gluon, nd, parallel, telemetry
from mxnet_tpu import random as mxrandom
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import parameter as parameter_mod
from mxnet_tpu.gluon.block import infer_shapes
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.gluon.parameter import (DeferredInitializationError,
                                       materialize)


class HostMade(mx.init.Initializer):
    """Reads its own draw back to the host: cannot be traced."""

    def init_array(self, shape, dtype="float32"):
        draw = np.asarray(jax.random.normal(mxrandom.next_key(), shape))
        return jax.numpy.asarray(np.round(draw, 2), dtype)


def _dense_net():
    """Deferred and fully-shaped parameters side by side, a norm's aux
    states, a host-made bias, an SVD and an untraceable initializer."""
    net = nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu",
                         weight_initializer=mx.init.Xavier()),
                nn.BatchNorm(),
                nn.Dense(8, in_units=16, bias_initializer=mx.init.LSTMBias()),
                nn.Dense(8, weight_initializer=mx.init.Orthogonal()),
                nn.Dense(4, in_units=8, weight_initializer=HostMade()))
    return net


def _dense_batch():
    rng = np.random.RandomState(0)
    return (rng.randn(8, 12).astype(np.float32),
            rng.randint(0, 4, (8,)).astype(np.int32))


def _resnet(name):
    return vision.get_model(name, classes=10, thumbnail=True, layout="NHWC")


def _resnet_batch():
    rng = np.random.RandomState(0)
    return (rng.randn(2, 32, 32, 3).astype(np.float32),
            rng.randint(0, 10, (2,)).astype(np.int32))


NETS = {"dense_bn": (_dense_net, _dense_batch),
        "resnet18": (lambda: _resnet("resnet18_v1"), _resnet_batch),
        "resnet34": (lambda: _resnet("resnet34_v1"), _resnet_batch)}


def _executables():
    config.watch_compiles()
    return telemetry.compile_stats()["executables_created"]


def _fresh(which, seed, dtype=None):
    mx.random.seed(seed)
    build, batch = NETS[which]
    net = build()
    net.initialize()
    if dtype:
        net.cast(dtype)
    x, y = batch()
    return net, (x.astype(dtype) if dtype else x), y


def _values(net):
    return [np.asarray(p.data()._data).astype(np.float32)
            for _, p in sorted(net.collect_params().items())]


def _next_draw():
    return np.asarray(jax.random.key_data(mxrandom.next_key()))


def _materialized(which, seed, how, dtype=None):
    """The net's parameter values and the framework's next key after
    materialising ``how``."""
    net, x, _ = _fresh(which, seed, dtype)
    if how == "bulk":
        assert materialize(infer_shapes(net, nd.array(x))) \
            == len(net.collect_params())
    elif how == "eager_forward":
        net(nd.array(x))
    else:
        assert how == "data_in_order"
        for p in infer_shapes(net, nd.array(x)):
            p.data()
    return _values(net), _next_draw()


# ------------------------------------------------- (a) the same bits ----
@pytest.mark.parametrize("how", ["eager_forward", "data_in_order"])
@pytest.mark.parametrize("which,dtype", [("dense_bn", None),
                                         ("dense_bn", "bfloat16"),
                                         ("resnet18", "bfloat16")])
def test_bulk_equals_one_at_a_time(which, dtype, how):
    bulk, bulk_next = _materialized(which, 5, "bulk", dtype)
    single, single_next = _materialized(which, 5, how, dtype)
    assert len(bulk) == len(single)
    for b, s in zip(bulk, single):
        assert b.dtype == s.dtype and np.array_equal(b, s)
    # both consumed the framework's stream alike
    assert np.array_equal(bulk_next, single_next)


@pytest.mark.parametrize("which", ["dense_bn", "resnet18"])
def test_another_seed_gives_other_parameters(which):
    one, _ = _materialized(which, 5, "bulk")
    other, _ = _materialized(which, 6, "bulk")
    differ = sum(not np.array_equal(a, b) for a, b in zip(one, other))
    assert differ >= len(one) // 5      # every weight; norms and biases not


# --------------------------- (b), (c), (e): through TrainStep's first step --
_SCENARIOS = {}


def _train_step(net):
    """The benchmark cell's step at thumbnail size: one device, bf16
    parameters with float32 masters in the optimizer state."""
    return parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.01, momentum=0.9),
        mesh=parallel.make_mesh(dp=1, devices=jax.devices()[:1]))


def _first_step(which):
    """``net.initialize()`` through two donated steps, once per net, from
    cold jit caches so the counts do not depend on what ran before in the
    process."""
    if which in _SCENARIOS:
        return _SCENARIOS[which]
    jax.clear_caches()
    parameter_mod._PROGRAMS.clear()
    telemetry.disable()
    telemetry.enable(collect=True)
    try:
        start = _executables()
        net, x, y = _fresh(which, 7, "bfloat16")
        step = _train_step(net)
        step._prepare(x, y)
        initial = _values(net)
        holds_tracer = [p.name for p in net.collect_params().values()
                        if isinstance(p._data._data, jax.core.Tracer)]
        loss = float(step(x, y).asnumpy())
        to_first_step = _executables() - start
        step(x, y).asnumpy()
        (span,) = telemetry.scope_spans("TrainStep.deferred_init")
    finally:
        telemetry.disable()
    _SCENARIOS[which] = dict(
        which=which, net=net, step=step, x=x, y=y, loss=loss, initial=initial,
        holds_tracer=holds_tracer, span=span, to_first_step=to_first_step)
    return _SCENARIOS[which]


@pytest.fixture(params=["resnet18", "resnet34"])
def first_step(request):
    return _first_step(request.param)


def test_first_step_creates_a_handful_of_executables(first_step):
    # the key's seed, split and unstack, the initializer, TrainStep's own
    # copies and optimizer state, the step: not one per parameter or op
    assert first_step["to_first_step"] < 12, first_step["to_first_step"]
    assert np.isfinite(first_step["loss"])


def test_executable_count_does_not_grow_with_depth():
    assert _first_step("resnet18")["to_first_step"] \
        == _first_step("resnet34")["to_first_step"]


def test_span_counts_params_and_executables(first_step):
    attrs = first_step["span"].attrs
    assert attrs["params"] == len(first_step["net"].collect_params())
    assert 1 <= attrs["executables"] <= 2


def test_no_parameter_holds_a_tracer(first_step):
    assert first_step["holds_tracer"] == []
    key = mxrandom.current_key_source().key
    assert key is None or not isinstance(key, jax.core.Tracer)


def test_parameters_read_back_after_donated_steps(first_step):
    # the step donated ITS arrays twice; the net's are the initial values
    # until sync_params_to_net
    after = _values(first_step["net"])
    for a, b in zip(first_step["initial"], after):
        assert np.array_equal(a, b)
    first_step["step"].sync_params_to_net()
    moved = sum(not np.array_equal(a, b) for a, b in
                zip(first_step["initial"], _values(first_step["net"])))
    assert moved > len(after) // 5


@pytest.mark.parametrize("which", ["dense_bn", "resnet18"])
def test_second_net_with_another_seed_creates_no_executable(which):
    values = []
    for seed in (7, 8):
        start = _executables()
        net, x, y = _fresh(which, seed, "bfloat16")
        # the abstract pass, the initializer, TrainStep's copies and state
        _train_step(net)._prepare(x, y)
        values.append(_values(net))
    assert _executables() == start      # keys are arguments
    differ = sum(not np.array_equal(a, b) for a, b in zip(*values))
    assert differ >= len(values[0]) // 5


def test_step_program_equals_the_eager_twins(first_step):
    net, x, y = _fresh(first_step["which"], 7, "bfloat16")
    net(nd.array(x))                       # a parameter at a time
    assert all(p._deferred_init is None
               for p in net.collect_params().values())
    twin = _train_step(net)
    assert twin.lower(x, y).as_text() == first_step["step"].lower().as_text()
    for a, b in zip(first_step["initial"], _values(net)):
        assert np.array_equal(a, b)


# ------------------------------------------------ (d) data() right away --
def test_data_after_initialize_shaped_and_unshaped():
    mx.random.seed(1)
    shaped = nn.Dense(4, in_units=3)
    shaped.initialize()
    assert shaped.weight._data is None         # recorded, not allocated
    w = shaped.weight.data()
    assert w.shape == (4, 3) and shaped.weight._deferred_init is None
    assert shaped.weight.data() is w
    assert shaped.weight.grad().shape == (4, 3)
    unshaped = nn.Dense(4)
    with pytest.raises(RuntimeError, match="has not been initialized"):
        unshaped.weight.data()
    unshaped.initialize()
    with pytest.raises(DeferredInitializationError):
        unshaped.weight.data()
    assert unshaped.bias.data().shape == (4,)  # its shape was never in doubt
    unshaped(nd.ones((2, 5)))
    assert unshaped.weight.data().shape == (4, 5)


def test_cast_and_set_data_on_a_pending_parameter():
    mx.random.seed(2)
    a, b = nn.Dense(4, in_units=3), nn.Dense(4, in_units=3)
    for d in (a, b):
        d.initialize(mx.init.Constant(0.1))
    a.weight.data()                            # made in float32, then cast
    a.cast("bfloat16")
    b.cast("bfloat16")                         # cast recorded, made later
    assert b.weight._data is None
    assert b.weight.data().dtype == a.weight.data().dtype
    assert np.array_equal(np.asarray(a.weight.data()._data, np.float32),
                          np.asarray(b.weight.data()._data, np.float32))
    c = nn.Dense(4, in_units=3)
    c.initialize()
    c.cast("bfloat16")
    c.weight.set_data(nd.ones((4, 3)))         # never materialised
    assert c.weight.data().dtype == a.weight.data().dtype
    d = nn.Dense(4, in_units=3)
    d.initialize()
    with pytest.raises(ValueError, match="shape mismatch"):
        d.weight.set_data(nd.ones((3, 4)))


def test_initialize_twice_draws_once_and_force_reinit_draws_again():
    mx.random.seed(3)
    d = nn.Dense(4, in_units=3)
    d.initialize()
    d.initialize()                             # a no-op while pending
    first = np.asarray(d.weight.data()._data)
    mx.random.seed(3)
    e = nn.Dense(4, in_units=3)
    e.initialize()
    assert np.array_equal(first, np.asarray(e.weight.data()._data))
    e.initialize(force_reinit=True)
    assert e.weight._data is None
    assert not np.array_equal(first, np.asarray(e.weight.data()._data))


# ---------------------------------------------- the other bulk consumers --
def test_hybridized_first_call_and_eval_step_use_one_program():
    x, _ = _dense_batch()
    for consume in ("hybridize", "eval_step"):
        jax.clear_caches()
        parameter_mod._PROGRAMS.clear()
        net, _, _ = _fresh("dense_bn", 4)
        if consume == "hybridize":
            net.hybridize()
            start = _executables()
            out = net(nd.array(x))
        else:
            start = _executables()
            out = parallel.EvalStep(net)(x)
        # the host-made initializers ran eagerly at initialize(); from here
        # on: the initializer program and the forward
        assert _executables() - start <= 4, consume
        net2, _, _ = _fresh("dense_bn", 4)
        assert np.allclose(out.asnumpy(), net2(nd.array(x)).asnumpy(),
                           atol=1e-5)


@pytest.fixture
def cache_dir(tmp_path):
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    yield tmp_path
    jax.config.update("jax_compilation_cache_dir", old)


def test_program_is_kept_beside_the_persistent_cache(cache_dir, monkeypatch):
    # a warm PROCESS must not lower the initializers again (on the TPU
    # that is where the time goes): the exported program is stored under
    # jax's cache directory and a later process compiles from it
    x, _ = _dense_batch()

    def bulk(seed):
        parameter_mod._PROGRAMS.clear()        # as a new process would be
        net, _, _ = _fresh("dense_bn", seed, "bfloat16")
        materialize(infer_shapes(net, nd.array(x)))
        return _values(net)

    first = bulk(5)
    (stored,) = cache_dir.glob("mxnet_tpu-program-*.stablehlo")
    written = stored.stat().st_mtime_ns
    with monkeypatch.context() as patched:
        def no_export(*a, **k):
            raise AssertionError("exported again")
        patched.setattr(jax.export, "export", no_export)
        again, other = bulk(5), bulk(6)
    assert stored.stat().st_mtime_ns == written
    assert all(np.array_equal(a, b) for a, b in zip(first, again))
    assert any(not np.array_equal(a, b) for a, b in zip(first, other))
    single, _ = _materialized("dense_bn", 5, "eager_forward", "bfloat16")
    assert all(np.array_equal(a, b) for a, b in zip(first, single))
    # a damaged file is written anew, not trusted and not fatal
    stored.write_bytes(b"not a program")
    assert all(np.array_equal(a, b) for a, b in zip(first, bulk(5)))
    assert stored.stat().st_size > 1000


def test_infer_shapes_leaves_nothing_behind():
    net, x, _ = _fresh("dense_bn", 9)
    before = _next_draw()
    mx.random.seed(9)
    net, x, _ = _fresh("dense_bn", 9)
    pending = infer_shapes(net, nd.array(x))
    assert [p.shape for p in pending if p.name.endswith("dense0_weight")] \
        == [(16, 12)]
    assert all(p._data is None for p in pending)
    assert np.array_equal(before, _next_draw())    # the stream did not move
