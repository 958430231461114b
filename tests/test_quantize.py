"""Quantized gradient collectives + int8 weight serving (ISSUE 8).

The numerics gate for ``parallel.quantize`` / ``amp.Int8Quantizer``:
primitive round-trip bounds, statistical unbiasedness of the stochastic
rounding, A/B loss-trajectory parity of ``TrainStep(grad_reduce=...)``
against the f32 path (deterministic under a fixed seed), int8
``module_apply`` output parity, the no-recompile census with
quantization enabled, and the fleet's re-quantize-on-swap ingest for
f32 training snapshots streaming into an int8 fleet.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import amp, gluon, parallel, serving
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import quantize as qz
from mxnet_tpu.parallel.mesh import shard_map
from jax.sharding import PartitionSpec as P


# ------------------------------------------------------------- primitives --
def test_quantize_roundtrip_nearest_within_half_scale():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(3, 1000).astype(np.float32))
    q, s = qz.quantize_chunked(x, chunk=128)
    assert q.dtype == jnp.int8 and q.shape == (3, 8, 128)
    assert s.dtype == jnp.float32 and s.shape == (3, 8)
    y = qz.dequantize_chunked(q, s, 1000)
    err = np.abs(np.asarray(y) - np.asarray(x))
    bound = np.repeat(np.asarray(s), 128, axis=-1)[:, :1000] / 2
    assert np.all(err <= bound + 1e-7)


def test_quantize_roundtrip_stochastic_within_one_scale():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(700).astype(np.float32))
    q, s = qz.quantize_chunked(x, chunk=256, key=jax.random.key(7))
    y = qz.dequantize_chunked(q, s, 700)
    err = np.abs(np.asarray(y) - np.asarray(x))
    bound = np.repeat(np.asarray(s), 256)[:700]
    assert np.all(err <= bound + 1e-7)


def test_chunking_isolates_outliers():
    """An outlier only poisons ITS chunk's scale — the point of
    per-chunk scales over per-tensor."""
    x = np.full(512, 0.01, np.float32)
    x[0] = 100.0                                  # chunk-0 outlier
    q, s = qz.quantize_chunked(jnp.asarray(x), chunk=256)
    y = np.asarray(qz.dequantize_chunked(q, s, 512))
    # chunk 1 keeps full small-value precision
    assert np.abs(y[256:] - 0.01).max() <= 0.01 / 127 / 2 + 1e-9
    # chunk 0's small values are crushed by the outlier's scale (the
    # lattice step there is 100/127 ≈ 0.79, so 0.01 rounds to 0)
    assert np.abs(y[1:256] - 0.01).max() > 0.005


def test_nonfinite_survives_the_round_trip():
    """A NaN/inf gradient element must come back NON-finite — a finite
    scale for a poisoned chunk would launder the NaN into zeros right
    under TrainStep's skip_nonfinite guard (review finding)."""
    for poison in (np.nan, np.inf, -np.inf):
        x = np.asarray([1.0, poison, 2.0, 3.0], np.float32)
        q, s = qz.quantize_chunked(jnp.asarray(x), chunk=4)
        y = np.asarray(qz.dequantize_chunked(q, s, 4))
        assert not np.isfinite(y).all(), (poison, y)
        # stochastic path too
        q, s = qz.quantize_chunked(jnp.asarray(x), chunk=4,
                                   key=jax.random.key(0))
        y = np.asarray(qz.dequantize_chunked(q, s, 4))
        assert not np.isfinite(y).all(), (poison, y)


def test_nan_snapshot_rejected_by_int8_fleet_validation():
    """A NaN-poisoned f32 snapshot must NOT pass the int8 fleet's
    all-finite gate after re-quantization (review finding): the NaN
    channel keeps a NaN scale, so validate_params still sees poison."""
    from mxnet_tpu.serving.fleet import (SnapshotRejectedError,
                                         validate_params)
    quant = amp.Int8Quantizer(axis=1)
    clean = [np.random.RandomState(0).randn(6, 16).astype(np.float32)]
    served = quant.quantize(clean)
    bad = [clean[0].copy()]
    bad[0][3, 4] = np.nan
    with pytest.raises(SnapshotRejectedError, match="non-finite"):
        validate_params(quant.quantize(bad), served)


def test_zero_chunk_dequantizes_exactly():
    x = jnp.zeros((300,), jnp.float32)
    q, s = qz.quantize_chunked(x, chunk=128)
    assert np.all(np.asarray(s) == 1.0)           # amax 0 -> scale 1
    assert np.all(np.asarray(qz.dequantize_chunked(q, s, 300)) == 0.0)


def test_stochastic_rounding_is_unbiased_nearest_is_not():
    """On a grid offset 1/4 below the quantizer's lattice, nearest
    rounding is biased by construction (-scale/4 per element) while the
    stochastic rounder's empirical mean converges to the true value.
    Deterministic: fixed keys."""
    scale = 1.0 / 127.0
    x = np.full(256, 10 * scale + 0.25 * scale, np.float32)
    x[0] = 1.0            # pins amax so the lattice is exactly scale
    xj = jnp.asarray(x)
    q, s = qz.quantize_chunked(xj, chunk=256)
    nearest_bias = float(np.mean(
        np.asarray(qz.dequantize_chunked(q, s, 256))[1:] - x[1:]))
    assert abs(nearest_bias + 0.25 * scale) < 0.02 * scale
    acc = np.zeros(256, np.float64)
    n_keys = 400
    for i in range(n_keys):
        q, s = qz.quantize_chunked(xj, chunk=256, key=jax.random.key(i))
        acc += np.asarray(qz.dequantize_chunked(q, s, 256),
                          np.float64)
    sr_bias = float(np.mean(acc[1:] / n_keys - x[1:]))
    # sigma of the mean-over-255-elements-over-400-keys is tiny; 0.05
    # scale is > 10 sigma of headroom while 0.25 scale would fail
    assert abs(sr_bias) < 0.05 * scale


def test_cast_bf16_stochastic_unbiased_and_exact_preserving():
    # exactly representable values never move
    exact = jnp.asarray([0.0, 1.0, -2.5, 0.15625], jnp.float32)
    out = qz.cast_bf16(exact, key=jax.random.key(0))
    assert np.all(np.asarray(out, np.float32) == np.asarray(exact))
    # a value centered between two bf16 neighbours rounds up ~half the
    # time; the empirical mean converges to the true value
    x = jnp.full((512,), 1.0 + 2 ** -9, jnp.float32)   # midpoint at 1.0+
    acc = np.zeros(512, np.float64)
    n_keys = 200
    for i in range(n_keys):
        acc += np.asarray(qz.cast_bf16(x, key=jax.random.key(i)),
                          np.float64)
    bias = float(np.mean(acc / n_keys) - (1.0 + 2 ** -9))
    assert abs(bias) < 2 ** -11        # nearest/truncate would be 2**-9


def test_reduce_gradients_matches_true_mean_under_shard_map():
    mesh = parallel.make_mesh(dp=8)
    rng = np.random.RandomState(3)
    x = rng.randn(8, 1003).astype(np.float32)   # non-divisible size: pads

    def run(mode):
        def inner(xl, key):
            (g,) = qz.reduce_gradients([xl[0]], "dp", 8, mode=mode,
                                       key=key, reduce="mean")
            return g

        f = jax.jit(shard_map(inner, mesh=mesh, in_specs=(P("dp"), P()),
                              out_specs=P(), check_vma=False))
        return np.asarray(f(x, jax.random.key(0)))

    true = x.mean(axis=0)
    np.testing.assert_allclose(run("f32"), true, rtol=1e-6, atol=1e-6)
    # quantized modes: within a few quantization steps of the truth
    tol = 2.5 * np.abs(x).max() / 127
    assert np.abs(run("int8") - true).max() <= tol
    assert np.abs(run("bf16") - true).max() <= np.abs(x).max() / 128


def test_all_reduce_activations_modes_and_bound():
    """The serving activation all-reduce (ISSUE 14, the tp_collectives
    wire): f32 is the partials added in device order, bit for bit — the
    same order for every element, which psum does not promise (on the
    v5e 2x2 it varies with the element's place in the buffer; PERF.md
    PR 21); int8 stays within the chunk
    quantization bound of the true sum (two quantization stages, each
    |err| <= scale/2 = amax/254 per stage per addend, summed over
    devices); both are bit-identical across devices (taken on faith by
    out_specs=P() — asserted here by comparing per-device outputs)."""
    mesh = parallel.make_mesh(tp=8)
    rng = np.random.RandomState(7)
    x = rng.randn(8, 6, 37).astype(np.float32)     # [dev, slots, d]

    def run(mode):
        def inner(xl):
            r = qz.all_reduce_activations(xl[0], "tp", 8, mode=mode)
            return r[None]               # [1, ...]: re-stack per device

        f = jax.jit(shard_map(inner, mesh=mesh, in_specs=P("tp"),
                              out_specs=P("tp"), check_vma=False))
        return np.asarray(f(x))          # per-device outputs, stacked

    true = x.sum(axis=0)
    in_order = x[0]
    for d in range(1, 8):
        in_order = in_order + x[d]
    got_f32 = run("f32")
    for d in range(8):                   # replicated: every device equal
        np.testing.assert_array_equal(got_f32[d], in_order)
    got_q8 = run("int8")
    for d in range(1, 8):
        np.testing.assert_array_equal(got_q8[0], got_q8[d])
    # bounded divergence: phase-1 per-addend error (8 devices) plus the
    # phase-2 re-quantization of the sum
    tol = (8 + 1) * 2.0 * np.abs(x).max() / 127
    assert np.abs(got_q8[0] - true).max() <= tol
    rel = np.abs(got_q8[0] - true).max() / np.abs(true).max()
    assert rel < 0.05                    # ~1% in practice
    with pytest.raises(ValueError):
        qz.all_reduce_activations(jnp.zeros((4,)), "tp", 8, mode="bf16")


# ---------------------------------------------------- TrainStep grad_reduce --
def _mlp_step(mode, seed=3, skip_nonfinite=False):
    mx.random.seed(seed)
    # names of its own: TrainStep orders the leaves by name, and the
    # process-wide block counters would order "dense9_" after "dense10_" in
    # whichever net crosses a digit (two nets built alike must reduce alike)
    net = nn.HybridSequential(prefix="mlp_")
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu", in_units=20),
                nn.Dense(5, in_units=32))
    net.initialize()
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
    return parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              opt, mesh=parallel.make_mesh(dp=-1),
                              grad_reduce=mode,
                              skip_nonfinite=skip_nonfinite)


def _trajectory(mode, n=12, seed=3, skip_nonfinite=False):
    step = _mlp_step(mode, seed=seed, skip_nonfinite=skip_nonfinite)
    rng = np.random.RandomState(11)
    losses = []
    for i in range(n):
        x = rng.randn(16, 20).astype(np.float32)
        y = rng.randint(0, 5, (16,)).astype(np.int32)
        losses.append(float(np.asarray(step(x, y)._data)))
    return step, losses


def test_grad_reduce_loss_trajectory_parity():
    """The A/B numerics gate: quantized grad_reduce tracks the f32 loss
    trajectory within tolerance over N steps — quantization noise must
    not change what the model learns, step by step."""
    _, f32 = _trajectory("f32")
    _, bf16 = _trajectory("bf16")
    _, int8 = _trajectory("int8")
    assert all(np.isfinite(f32))
    for a, b in zip(f32, bf16):
        assert abs(a - b) / abs(a) < 2e-3
    for a, b in zip(f32, int8):
        assert abs(a - b) / abs(a) < 1e-2


def test_grad_reduce_int8_deterministic_under_fixed_seed():
    _, one = _trajectory("int8")
    _, two = _trajectory("int8")
    assert one == two                   # bit-identical, not just close


def test_grad_reduce_no_retrace_and_census():
    """Census == runtime jit-cache count with quantization enabled: the
    explicit reduction stage lives INSIDE the one pinned executable."""
    from tools.costguard import executable_census
    step, _ = _trajectory("int8", n=6)
    assert executable_census(step) == 1
    assert step._jit._cache_size() == 1


def test_grad_reduce_skip_nonfinite_guard_still_works():
    """A NaN batch through the quantized reduction still leaves params,
    optimizer state, and the step counter untouched."""
    step, _ = _trajectory("int8", n=3, skip_nonfinite=True)
    before = [np.asarray(a) for a in step._train_arrays]
    t_before = int(np.asarray(step._t))
    x = np.full((16, 20), np.nan, np.float32)
    y = np.zeros((16,), np.int32)
    step(x, y)
    assert step.skipped_steps == 1
    assert int(np.asarray(step._t)) == t_before
    for b, a in zip(before, step._train_arrays):
        np.testing.assert_array_equal(b, np.asarray(a))
    # and a clean batch afterwards trains again
    rng = np.random.RandomState(0)
    loss = step(rng.randn(16, 20).astype(np.float32),
                rng.randint(0, 5, (16,)).astype(np.int32))
    assert np.isfinite(float(np.asarray(loss._data)))
    assert int(np.asarray(step._t)) == t_before + 1


def test_grad_reduce_aot_cost_audit_without_executing():
    """The costguard path: lower/cost_analysis from a sample batch, no
    step executed, and the audit does not cause a later retrace."""
    step = _mlp_step("int8")
    x = np.zeros((16, 20), np.float32)
    y = np.zeros((16,), np.int32)
    costs = step.cost_analysis(x, y)
    assert costs.get("flops", 0) > 0
    step(x, y)
    assert step._jit._cache_size() == 1


def test_grad_reduce_rejects_bad_mode_and_model_parallel_mesh():
    net = nn.Dense(4, in_units=8)
    net.initialize()
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    with pytest.raises(ValueError, match="grad_reduce"):
        parallel.TrainStep(net, gluon.loss.L2Loss(), opt,
                           mesh=parallel.make_mesh(dp=-1),
                           grad_reduce="int4")
    with pytest.raises(ValueError, match="pure data-parallel"):
        parallel.TrainStep(net, gluon.loss.L2Loss(), opt,
                           mesh=parallel.make_mesh(dp=-1, tp=2),
                           grad_reduce="int8")
    with pytest.raises(ValueError, match="'dp' mesh axis"):
        parallel.TrainStep(net, gluon.loss.L2Loss(), opt,
                           mesh=parallel.make_mesh(tp=8),
                           grad_reduce="bf16")


# ------------------------------------------------------- int8 weight PTQ --
def test_quantize_weight_per_channel_roundtrip():
    rng = np.random.RandomState(5)
    w = rng.randn(16, 8).astype(np.float32)
    w[3] *= 50                                   # one hot channel
    q, s = amp.quantize_weight(w, axis=0)
    assert q.dtype == jnp.int8 and s.shape == (16,)
    back = np.asarray(amp.dequantize_weight(q, s, axis=0))
    half = np.abs(w).max(axis=1, keepdims=True) / 127 / 2
    assert np.all(np.abs(back - w) <= half + 1e-7)


def test_int8_quantizer_list_and_dict_containers():
    rng = np.random.RandomState(6)
    plist = [jnp.asarray(rng.randn(6, 16), jnp.float32),
             jnp.asarray(np.zeros(16), jnp.float32)]
    quant = amp.Int8Quantizer(axis=1)
    qp = quant.quantize(plist)
    assert [str(p.dtype) for p in qp] == ["int8", "float32", "float32"]
    back = quant.dequantize(qp)
    assert len(back) == 2
    assert float(jnp.abs(back[0] - plist[0]).max()) < 0.05
    pdict = {"w": plist[0], "b": plist[1]}
    qd = quant.quantize(pdict)
    assert sorted(qd) == ["b", "w", "w::scale"]
    assert qd["w"].dtype == jnp.int8
    # re-quantizing the quantized container is a loud error, not drift
    with pytest.raises(ValueError, match="already"):
        quant.quantize(qd)
    with pytest.raises(ValueError, match="full-precision"):
        quant.quantize(qp)
    # deterministic: the ingest transform always lands on the same leaves
    qp2 = quant.quantize(plist)
    for a, b in zip(qp, qp2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _bound_module(batch=8):
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=32, name="fc")
    act = mx.sym.Activation(fc, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    out = mx.sym.SoftmaxOutput(fc2, name="softmax")
    mod = mx.mod.Module(out, context=mx.cpu())
    mod.bind([("data", (batch, 6))], [("softmax_label", (batch,))],
             for_training=False)
    mod.init_params(mx.init.Xavier(magnitude=2.0))
    return mod


def test_int8_module_apply_matches_f32_within_tolerance():
    mod = _bound_module()
    f32 = serving.module_apply(mod)
    q8 = serving.module_apply(mod, quantize="int8")
    x = np.random.RandomState(2).randn(8, 6).astype(np.float32)
    a, b = np.asarray(f32(x)), np.asarray(q8(x))
    assert a.shape == b.shape
    np.testing.assert_allclose(a, b, atol=5e-3)
    with pytest.raises(ValueError, match="quantize"):
        serving.module_apply(mod, quantize="int4")


def test_int8_serving_grid_census_equals_runtime_jit_count():
    """The acceptance invariant with quantization enabled: feeding the
    ENTIRE bucket grid (twice) through the int8 apply compiles exactly
    census executables — the int8 path leaks no recompiles."""
    from tools.costguard import executable_census, grid_signatures
    spec = serving.BucketSpec(batch=(1, 2, 4), length=(8, 16))
    quant = amp.Int8Quantizer(axis=1)
    rng = np.random.RandomState(7)
    params = [jnp.asarray(rng.randn(32, 64) / 8, jnp.float32),
              jnp.asarray(np.zeros(64), jnp.float32),
              jnp.asarray(rng.randn(64, 16) / 8, jnp.float32),
              jnp.asarray(np.zeros(16), jnp.float32)]
    qp = quant.quantize(params)

    def fwd(p, x):
        return jnp.tanh(x @ p[0] + p[1]) @ p[2] + p[3]

    qfn = jax.jit(quant.wrap(fwd))
    for _ in range(2):
        for b, L in grid_signatures(spec):
            out = qfn(qp, np.zeros((b, L, 32), np.float32))
            assert out.shape == (b, L, 16)
    assert qfn._cache_size() == executable_census(spec) == 6


# ------------------------------------------------ fleet re-quantize ingest --
def _int8_fleet(n=2):
    rng = np.random.RandomState(8)
    params = [rng.randn(6, 16).astype(np.float32) / 4,
              np.zeros(16, np.float32),
              rng.randn(16, 4).astype(np.float32) / 4,
              np.zeros(4, np.float32)]
    quant = amp.Int8Quantizer(axis=1)

    def fwd(p, x):
        return jnp.maximum(x @ p[0] + p[1], 0.0) @ p[2] + p[3]

    qfn = jax.jit(quant.wrap(fwd))
    fleet = serving.ServingFleet.replicated(
        qfn, quant.quantize(params), n, quantizer=quant.quantize,
        buckets=(1, 2, 4), sample=np.ones((6,), np.float32),
        max_delay=0.002, name="Int8Fleet")
    return fleet, params, quant


@pytest.mark.fleet
def test_f32_snapshot_streams_into_int8_fleet():
    """Satellite 1: a rolling update from an f32 training job into an
    int8 fleet re-quantizes through the fleet's quantizer instead of
    tripping the dtype-drift rejection."""
    fleet, params, quant = _int8_fleet()
    with fleet:
        x = np.ones((6,), np.float32)
        before = np.asarray(fleet(x, timeout=5))
        updater = serving.WeightUpdater(fleet)
        new = [p * 2.0 for p in params]          # f32 leaves, f32 count
        assert updater.update(new) == 2
        assert updater.applied == 1
        after = np.asarray(fleet(x, timeout=5))
        # the swap actually landed: outputs track the doubled weights
        assert np.abs(after - before).max() > 1e-3
        ref = [np.asarray(r) for r in quant.dequantize(quant.quantize(new))]
        want = np.maximum(x @ ref[0] + ref[1], 0.0) @ ref[2] + ref[3]
        np.testing.assert_allclose(after, want, atol=1e-5)
        # served representation is still the quantized one
        assert fleet.replicas[0].apply.params[0].dtype == jnp.int8


@pytest.mark.fleet
def test_int8_fleet_still_rejects_genuine_drift():
    fleet, params, _ = _int8_fleet()
    with fleet:
        updater = serving.WeightUpdater(fleet)
        bad_shape = [np.zeros((7, 16), np.float32)] + [
            np.asarray(p) for p in params[1:]]
        with pytest.raises(serving.SnapshotRejectedError):
            updater.update(bad_shape)
        bad_count = [np.asarray(p) for p in params[:-1]]
        with pytest.raises(serving.SnapshotRejectedError):
            updater.update(bad_count)
        assert updater.applied == 0 and updater.skipped == 2
        # fleet still serves the original weights at full capacity
        assert fleet.ready()
        assert np.isfinite(
            np.asarray(fleet(np.ones((6,), np.float32), timeout=5))).all()


@pytest.mark.fleet
def test_dtype_drift_without_quantizer_still_rejects():
    """The pre-ISSUE-8 contract survives: a fleet WITHOUT a quantizer
    treats dtype drift as a rejection, not something to coerce."""
    rng = np.random.RandomState(9)
    params = [rng.randn(6, 4).astype(np.float32)]
    fn = jax.jit(lambda p, x: x @ p[0])
    fleet = serving.ServingFleet.replicated(
        fn, params, 2, buckets=(1, 2), sample=np.ones((6,), np.float32),
        max_delay=0.002, name="F32Fleet")
    with fleet:
        updater = serving.WeightUpdater(fleet)
        with pytest.raises(serving.SnapshotRejectedError, match="dtype"):
            updater.update([params[0].astype(np.float64)])
