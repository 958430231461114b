"""SambaY (``gluon/model_zoo/sambay.py``), its ops and its benchmark family
against the plain reference kept with the benchmark
(``chipbench/reference/sambay.py``): float32, small widths, seeded weights,
more positions than two windows and three scan chunks."""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon.block import _flatten_nd
from mxnet_tpu.gluon.model_zoo import sambay
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops import state_space
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel.functional import (FunctionalState, functional_call,
                                           param_names_and_values)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest                              # noqa: E402
from chipbench.families import sambay as family             # noqa: E402
from chipbench.reference import sambay as reference         # noqa: E402

CONFIG = manifest.load_json(ROOT, "chipbench/configs/phi4_mini_flash.json")
CUT = ["mamba", "window", "mamba", "full", "gmu", "cross"]
# 256 positions: 8 scan chunks of 32, 5.3 windows of 48 (so the last block
# is padded), two blocks of the flash kernel
SMALL = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=64, sliding_window=48, state_size=4,
             dt_rank=4)
REF = dict(heads=4, kv_heads=2, window=48, eps=1e-5)
VOCAB, T = 50, 256


@pytest.fixture(autouse=True)
def _small_chunks(monkeypatch):
    monkeypatch.setattr(state_space, "SCAN_CHUNK", 32)


def _batch(seed=0, n=2, t=T):
    ids = np.random.RandomState(seed).randint(0, VOCAB, (n, t))
    return ids.astype(np.int32), np.roll(ids, -1, axis=1).astype(np.int32)


def _net(layers, seed=3):
    mx.random.seed(seed)
    net = sambay.SambaY(VOCAB, layers, **SMALL)
    net.initialize()
    return net


def _loss_and_grads(net, ids, labels):
    """The net's loss and gradients as ``TrainStep`` takes them: ``jax.grad``
    over ``functional_call``.  Gradients come back under the reference's
    structural names."""
    names, plist, arrays = param_names_and_values(net)
    structural = {p.name: n
                  for n, p in net._collect_params_with_prefix().items()}
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
    leaves, tree = _flatten_nd((NDArray(jnp.asarray(ids)),))

    def loss_of(arrays):
        outs = functional_call(net, plist, arrays, tree,
                               [l._data for l in leaves], jax.random.key(0),
                               True, FunctionalState())
        return jnp.mean(loss_fn(NDArray(outs[0]),
                                NDArray(jnp.asarray(labels)))._data)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(loss_of))(arrays)
    return float(loss), {structural[n]: g for n, g in zip(names, grads)}


def _one_device():
    return parallel.make_mesh(dp=1, devices=jax.devices()[:1])


def _worst(got, want):
    """Largest error of any leaf, relative to that leaf's largest entry."""
    assert set(got) == set(want)
    return max(float(jnp.abs(got[n] - want[n]).max()
                     / (jnp.abs(want[n]).max() + 1e-30)) for n in want)


# ------------------------------------------------------ net and reference --
@pytest.fixture(scope="module")
def cut():
    """The cut's net, one batch, and both sides' loss and gradients."""
    state_space.SCAN_CHUNK, held = 32, state_space.SCAN_CHUNK
    try:
        net = _net(CUT)
        ids, labels = _batch()
        params = reference.params_from_net(net)
        got = _loss_and_grads(net, ids, labels)
        want = reference.loss_and_grads(params, CUT, jnp.asarray(ids),
                                        jnp.asarray(labels), **REF)
    finally:
        state_space.SCAN_CHUNK = held
    return net, ids, params, got, want


def test_logits_match_the_reference(cut):
    net, ids, params, _, _ = cut
    got = net(mx.nd.array(ids, dtype="int32"))
    assert got.shape == (2, T, VOCAB) and got.dtype == np.float32
    with jax.default_matmul_precision("highest"):
        want = reference.forward(params, CUT, jnp.asarray(ids), **REF)
    assert float(jnp.abs(want).max()) > 0.3
    np.testing.assert_allclose(got.asnumpy(), np.asarray(want), atol=2e-6)


def test_loss_matches_the_reference(cut):
    _, _, _, (got, _), (want, _) = cut
    assert abs(got - float(want)) < 1e-5 and got > 3.0


def test_every_gradient_leaf_matches_the_reference(cut):
    _, _, params, (_, got), (_, want) = cut
    assert len(want) == len(params) == 6 * 6 + 2 * 9 + 4 * 2 + 3
    assert all(float(jnp.abs(g).max()) > 0 for g in want.values())
    assert _worst(got, want) < 2e-5


@pytest.mark.parametrize("layers", [
    ["mamba"], ["window"], ["full"], ["mamba", "gmu"], ["full", "cross"]],
    ids=lambda l: l[-1])
def test_each_mixer_alone(layers):
    net = _net(layers, seed=5)
    ids, labels = _batch(1)
    params = reference.params_from_net(net)
    loss, grads = _loss_and_grads(net, ids, labels)
    want, want_grads = reference.loss_and_grads(
        params, layers, jnp.asarray(ids), jnp.asarray(labels), **REF)
    assert abs(loss - float(want)) < 1e-5
    assert _worst(grads, want_grads) < 2e-5


def test_layers_that_read_need_a_layer_that_hands_on():
    with pytest.raises(ValueError, match="mamba"):
        sambay.SambaY(VOCAB, ["window", "gmu"], **SMALL)
    with pytest.raises(ValueError, match="full"):
        sambay.SambaY(VOCAB, ["mamba", "cross"], **SMALL)
    with pytest.raises(ValueError, match="kind"):
        sambay.SambaY(VOCAB, ["mamba", "attention"], **SMALL)


# ----------------------------------------------------------------- the ops --
def _scan_inputs(t, dim=24, n=4, b=2):
    rng = np.random.RandomState(7)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    return (f(b, t, dim), jax.nn.softplus(f(b, t, dim) - 2.0),
            -jnp.exp(f(dim, n)), f(b, t, n), f(b, t, n), f(dim))


def _scan_by_position(x, dt, a, b, c, d):
    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t) + d * x_t
    h0 = jnp.zeros((x.shape[0],) + a.shape)
    _, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(v, 1, 0)
                                        for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("chunk,t", [(256, 3 * 256 + 17), (32, 128), (32, 7)])
def test_chunked_scan_against_the_scan_by_position(monkeypatch, chunk, t):
    """Forward and backward, at the module's own chunk size over more than
    three chunks with a ragged tail, and at sizes that divide and fall
    short of a chunk."""
    monkeypatch.setattr(state_space, "SCAN_CHUNK", chunk)
    args = _scan_inputs(t)
    scan = get_op("selective_scan")
    np.testing.assert_allclose(scan(*args), _scan_by_position(*args),
                               atol=5e-6)

    def total(fn):
        return lambda *a: (fn(*a) ** 2).sum()
    got = jax.grad(total(scan), range(6))(*args)
    want = jax.grad(total(_scan_by_position), range(6))(*args)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max() / jnp.abs(w).max()) < 1e-5


@pytest.mark.parametrize("t,dim,n,low", [
    (3 * 32 + 17, 200, 16, False), (20, 24, 4, False),
    (3 * 32 + 17, 136, 8, True)],
    ids=["ragged_wide", "short", "bf16"])
def test_scan_kernels_against_the_scan_by_position(t, dim, n, low):
    """The Pallas kernels (the interpreter here) at a chunk of 32: T ragged
    against the chunk and short of one, D short of and beyond one tile of 128
    lanes, N that needs padding to the sublanes, two sequences; forward and
    all six gradients under a fixed cotangent.  ``low``: x, b, c, d in bf16
    beside a float32 dt, as the cell runs them; what comes back in bf16 is
    held to one bf16 step of its largest entry forward and two backward
    (x's gradient is the kernel's, rounded, plus the skip's)."""
    x, dt, a, b, c, d = _scan_inputs(t, dim, n)
    w = jnp.asarray(np.random.RandomState(5).randn(2, t, dim),
                    jnp.bfloat16).astype(jnp.float32)
    if low:
        x, b, c, d = (v.astype(jnp.bfloat16) for v in (x, b, c, d))
    args = (x, dt, a, b, c, d)
    wide = tuple(v.astype(jnp.float32) for v in args)
    scan = get_op("selective_scan")
    got, want = scan(*args), _scan_by_position(*wide)
    assert got.dtype == x.dtype
    step = 2.0 ** -8 if low else 0.0
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        <= 5e-6 + step * float(jnp.abs(want).max())

    def total(fn):
        return lambda *v: (fn(*v).astype(jnp.float32) * w).sum()
    got = jax.grad(total(scan), range(6))(*args)
    want = jax.grad(total(_scan_by_position), range(6))(*wide)
    for v, g, r in zip(args, got, want):
        assert g.dtype == v.dtype and g.shape == v.shape
        limit = 2.0 ** -7 if v.dtype == jnp.bfloat16 else 1e-5
        assert float(jnp.abs(g.astype(jnp.float32) - r).max()
                     / jnp.abs(r).max()) < limit


def test_scan_keeps_chunk_boundaries_only():
    """The backward pass holds no [T, N, D] tensor of states: the largest
    float32 array of the differentiated program is a chunk's."""
    b, t, dim, n = 1, 4 * 256, 128, 8
    args = _scan_inputs(t, dim, n, b)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(state_space, "SCAN_CHUNK", 256)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: get_op("selective_scan")(*a).sum(), range(6)))(*args)
    sizes = [int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
             for v in eqn.outvars if hasattr(v.aval, "shape")]
    assert max(sizes) < t * n * dim


def test_scan_state_stays_float32_under_bf16_inputs():
    x, dt, a, b, c, d = _scan_inputs(96)
    low = [v.astype(jnp.bfloat16) for v in (x, b, c, d)]
    y = get_op("selective_scan")(low[0], dt, a, low[1], low[2], low[3])
    assert y.dtype == jnp.bfloat16
    want = _scan_by_position(*(v.astype(jnp.float32) for v in (
        low[0], dt, a, low[1], low[2], low[3])))
    # only the result is rounded: half a bf16 step of its largest entry
    assert float(jnp.abs(y.astype(jnp.float32) - want).max()) \
        <= float(jnp.abs(want).max()) * 2.0 ** -8


def test_causal_conv_sees_no_later_position():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 20, 6), jnp.float32)
    w = jnp.asarray(rng.randn(4, 6), jnp.float32)
    bias = jnp.asarray(rng.randn(6), jnp.float32)
    conv = get_op("causal_conv1d")
    y = conv(x, w, bias)
    want = np.zeros((2, 20, 6), np.float32) + np.asarray(bias)
    for t in range(20):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += np.asarray(x[:, t - 3 + k] * w[k])
    np.testing.assert_allclose(y, want, atol=1e-5)
    later = x.at[:, 11:].set(9.0)
    np.testing.assert_array_equal(conv(later, w, bias)[:, :11], y[:, :11])


def _qkv(t, heads, kv_heads, d=16, b=2):
    rng = np.random.RandomState(4)
    return tuple(jnp.asarray(rng.randn(b, t, h * d), jnp.float32)
                 for h in (heads, kv_heads, kv_heads))


@pytest.mark.parametrize("t,window", [(200, 64), (128, 64), (40, 64), (64, 1)])
def test_window_attention_is_the_banded_softmax(t, window):
    """A ragged last block, whole blocks, a sequence inside one window, and
    the window that sees the query alone."""
    q, k, v = _qkv(t, 4, 2)
    op = lambda q, k, v: get_op("window_attention")(    # noqa: E731
        q, k, v, heads=4, kv_heads=2, window=window)
    want = lambda q, k, v: reference._attention(   # noqa: E731
        q, k, v, 4, 2, window)
    np.testing.assert_allclose(op(q, k, v), want(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: (op(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: (want(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    for g, w in zip(got, ref):
        np.testing.assert_allclose(g, w, atol=2e-5)


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (4, 1), (2, 2)])
def test_flash_attention_takes_grouped_query_heads(heads, kv_heads):
    q, k, v = _qkv(256, heads, kv_heads)
    op = lambda q, k, v: get_op("flash_attention")(     # noqa: E731
        q, k, v, heads=heads, kv_heads=kv_heads, causal=True)
    want = lambda q, k, v: reference._attention(   # noqa: E731
        q, k, v, heads, kv_heads, None)
    np.testing.assert_allclose(op(q, k, v), want(q, k, v), atol=2e-6)
    got = jax.grad(lambda *a: (op(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: (want(*a) ** 2).sum(), (0, 1, 2))(q, k, v)
    for g, w in zip(got, ref):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=3e-5)


def test_fully_connected_widens_its_result_on_request():
    x = jnp.ones((3, 8), jnp.bfloat16) * 1.001
    w = jnp.ones((5, 8), jnp.bfloat16) * 1.001
    fc = get_op("FullyConnected")
    assert fc(x, w, no_bias=True).dtype == jnp.bfloat16
    wide = fc(x, w, no_bias=True, out_dtype="float32")
    assert wide.dtype == jnp.float32
    grads = jax.grad(lambda x, w: fc(x, w, no_bias=True,
                                     out_dtype="float32").sum(), (0, 1))(x, w)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 2


# ----------------------------------------------------------- recomputation --
def _marked(layers=CUT):
    """The same net as ``_net(layers)`` (same seed), every layer marked."""
    net = _net(layers)
    for layer in net.layers:
        layer.recompute()
    return net


def test_recomputation_on_and_off_give_equal_gradients(cut):
    _, ids, _, (loss, grads), _ = cut
    _, labels = _batch()
    again, marked = _loss_and_grads(_marked(), ids, labels)
    assert again == loss
    assert _worst(marked, grads) < 1e-6


def test_recomputation_is_in_the_program_only_when_asked(cut):
    plain, ids, _, _, _ = cut
    _, labels = _batch()

    def remats(net):
        step = parallel.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(axis=-1),
            mx.optimizer.create("adamw", learning_rate=1e-3),
            mesh=_one_device())
        return step.lower(ids, labels).as_text().count("optimization_barrier")
    # the unmarked net has the scan's own
    assert remats(_marked()) > remats(plain)


@pytest.mark.parametrize("marked", [False, True], ids=["plain", "marked"])
def test_a_window_layer_is_the_windowed_kernels_and_recomputes_nothing(
        marked):
    """What took the banded loop's place (PR 32): the three windowed flash
    kernels by name, each lowered once for the two layers, and no
    recomputation of the layer's own inside the one ``recompute()`` asks
    for."""
    net = _net(["window", "window"])
    if marked:
        for layer in net.layers:
            layer.recompute()
    ids, labels = _batch()
    step = parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(axis=-1),
        mx.optimizer.create("adamw", learning_rate=1e-3), mesh=_one_device())
    text = step.lower(ids, labels).as_text(debug_info=True)
    for kernel in ("window_attention_fwd", "window_attention_bwd_dq",
                   "window_attention_bwd_dkv"):
        assert kernel in text
        assert kernel.replace("window", "flash") not in text
    # two layers call one lowering of each jitted kernel, marked or not: a
    # recomputed layer keeps the forward kernel's output and log-sum-exp,
    # so its recomputed forward does not run the kernel again
    lowered = re.findall(r"func.func private @(_flash_[a-z]+)", text)
    assert sorted(lowered) == ["_flash_bwd", "_flash_fwd"]
    assert len(re.findall(r"call @_flash_fwd\b", text)) == 2
    assert len(re.findall(r"call @_flash_bwd\b", text)) == 2
    assert ("optimization_barrier" in text) == marked


def test_recomputation_refuses_rewritten_aux_state():
    """A block whose forward rewrites aux state (BatchNorm's running
    statistics) cannot be recomputed: it says so and leaves the net's
    Parameters as they were."""
    mx.random.seed(11)
    net = gluon.nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(gluon.nn.Dense(8, in_units=4), gluon.nn.BatchNorm(),
                gluon.nn.Dense(3))
    net.initialize()
    net.recompute()
    step = parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.1), mesh=_one_device())
    rng = np.random.RandomState(0)
    x = rng.randn(16, 4).astype(np.float32)
    y = rng.randint(0, 3, (16,)).astype(np.int32)
    with pytest.raises(NotImplementedError, match="running_"):
        step(x, y)
    for p in net.collect_params().values():
        assert not isinstance(p.data()._data, jax.core.Tracer), p.name


def test_an_eager_call_is_not_recomputed():
    net = _net(["mamba", "window"])
    net.layers[0].recompute()
    ids, _ = _batch(n=1, t=64)
    x = mx.nd.array(ids, dtype="int32")
    with mx.autograd.record():
        out = net(x)
        out.sum().backward()
    grad = net.embed.weight.grad().asnumpy()
    assert np.isfinite(grad).all() and np.abs(grad).max() > 0


# --------------------------------------------- the configuration, the count --
def _parameters(vocab, layers):
    widths = {k: CONFIG["model"][k] for k in family.WIDTHS}
    net = sambay.SambaY(vocab, layers, **widths)
    return sum(int(np.prod(p.shape)) for p in net.collect_params().values())


def test_published_layer_map_counts_the_published_size():
    kinds = sambay.layer_kinds(CONFIG["num_hidden_layers"])
    assert [kinds.count(k) for k in sambay.KINDS] == [9, 8, 1, 7, 7]
    assert kinds[:4] == ["mamba", "window", "mamba", "window"]
    assert kinds[16:20] == ["mamba", "full", "gmu", "cross"]
    assert kinds[-2:] == ["gmu", "cross"]
    total = _parameters(CONFIG["vocab_size"], kinds)
    assert abs(total - 3.85e9) < 0.01 * 3.85e9


def test_the_cut_counts_697m():
    model = CONFIG["model"]
    assert model["layers"] == CUT
    assert model["vocab_size"] * 8 == CONFIG["vocab_size"]
    total = _parameters(model["vocab_size"], model["layers"])
    assert abs(total - 697.0e6) < 0.5e6


def test_configuration_keeps_every_published_width():
    """The catalog's ``config`` verbatim at the top level; ``model`` repeats
    the widths it needs and changes depth and vocabulary alone."""
    catalog = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    assert {k: CONFIG[k] for k in catalog} == catalog
    model = CONFIG["model"]
    for key in set(model) & set(catalog) - {"vocab_size"}:
        assert model[key] == catalog[key], key
    assert CONFIG["reduced"] == ["layers", "vocab_size"]
    assert model["expand"] == CONFIG["mb_per_layer"]
    assert model["dt_rank"] == -(-model["hidden_size"] // 16)
    assert set(CONFIG["assumed"]) >= {"state_size", "conv_kernel", "expand",
                                      "dt_rank", "positional_encoding"}
    assert any("differential" in d for d in CONFIG["departures"])
    entry = next(c for c in manifest.load(ROOT)["configs"]
                 if c["name"] == "phi4_mini_flash")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "chipbench/configs/phi4_mini_flash.json"


def test_train_flops_against_a_hand_count():
    """Per token, the ISSUE's arithmetic: the layers' matrices (633.0M
    parameters less norms, convolutions, A, D and biases), the head, and the
    attention products over the pairs causality and the window leave."""
    model = CONFIG["model"]
    t, h, v = model["sequence_length"], 2560, model["vocab_size"]
    mlp = 3 * 2560 * 10240
    mixers = (2 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
              + 2 * (2560 * 5120 + 2560 * 2560)
              + 2 * 2560 * 5120 + 2 * 2560 * 2560)
    matrices = 2 * t * (mixers + 6 * mlp + v * h)
    full = t * (t + 1) // 2
    band = sum(min(i + 1, 512) for i in range(t))
    attention = 4 * h * (band + 2 * full)
    want = 3 * (matrices + attention)
    got = family.train_flops(model)
    assert got == want
    assert 17.6e12 < got < 17.8e12 and 4.2e9 < got / t < 4.4e9
    # attention is a small part: the matrices set the count
    assert 0.02 < 3 * attention / got < 0.05


# -------------------------------------------------- through the benchmark --
TOY = dict(SMALL, layers=CUT, vocab_size=64, sequence_length=64,
           layer_norm_eps=1e-5, conv_kernel=4, expand=2)


@pytest.fixture
def toy_benchmark(tmp_path):
    """A throw-away benchmark holding the new configuration and cell at
    toy sizes, added as files and entries beside none (as
    ``chipbench/tests`` does for the accepted cell)."""
    root, src = str(tmp_path), os.path.join(ROOT, "chipbench")
    real = manifest.load(ROOT)
    cell = "phi4_mini_flash.train_s4096"
    for d in ("configs", "workloads", "layer_metrics"):
        os.makedirs(os.path.join(root, "chipbench", d))
    cfg = dict(CONFIG, model=TOY, compute_dtype="float32",
               check={"loss_atol": 0.02},
               optimizer={"name": "adamw", "args": {"learning_rate": 1e-3}})
    with open(os.path.join(root, "chipbench/configs/phi4_mini_flash.json"),
              "w") as f:
        json.dump(cfg, f)
    wl = manifest.load_json(src, f"workloads/{cell}.json")
    wl.update(trace_s=1.0)
    with open(os.path.join(root, manifest.workload_file(cell)), "w") as f:
        json.dump(wl, f)
    m = dict(real, run_seconds=2)
    m["configs"] = [c for c in real["configs"] if c["name"] == "phi4_mini_flash"]
    m["workloads"] = [w for w in real["workloads"] if w["name"] == cell]
    for section in ("end_to_end", "per_layer"):
        m[section] = [dict(r, workloads=[cell]) if "workloads" in r else r
                      for r in real[section]
                      if cell in r.get("workloads", [cell])]
    for r in m["per_layer"]:
        with open(os.path.join(src, "layer_metrics", r["name"] + ".json")) as f:
            spec = f.read()
        with open(os.path.join(root, manifest.metric_file(r["name"])),
                  "w") as f:
            f.write(spec)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    assert manifest.validate(m, root) == []
    return manifest.cell(m, root, cell)


@pytest.mark.parametrize("trace", [0, 1])
def test_family_rehearsed_through_the_benchmark(toy_benchmark, capsys, trace):
    from chipbench import run
    res = run.run_cell(toy_benchmark, jax.devices()[:1], 2**31 + 11, 2.0,
                       trace)
    fails = [l for l in capsys.readouterr().out.splitlines() if "[FAIL]" in l]
    # a CPU run can never pass for a result, and that is its only fault
    assert len(fails) == 1 and "runs on a TPU" in fails[0], fails
    assert res["correct"] is False and res["device"]["platform"] == "cpu"
    assert res["attempted"] > 0 and res["failed"] == 0
    # a traced line holds whatever the program's counters and spans let a
    # later metric read off the chip too; an untraced one the two it times
    if trace:
        assert set(res["metrics"]) >= {"step_ms_p50", "compile_ms_total"}
        assert not set(res["metrics"]) & {"setup_s", "train_samples_per_s"}
    else:
        assert set(res["metrics"]) == {"setup_s", "train_samples_per_s"}


def test_the_real_benchmark_holds_the_cell():
    real = manifest.load(ROOT)
    assert manifest.validate(real, ROOT) == []
    view = manifest.cell(real, ROOT, "phi4_mini_flash.train_s4096")
    assert view["chips"] == 1 and view["cfg"]["family"] == "sambay"
    assert view["wl"]["driver"] == "train_loop"
    assert view["wl"]["batch_per_chip"] == 1
    assert [m["name"] for m in view["end_to_end"]] == [
        "train_samples_per_s", "setup_s"]
    assert {m["name"] for m in view["per_layer"]} >= {
        "compile_ms_total", "step_ms_p50", "mfu_pct", "device_idle_pct.train"}


def test_family_draws_its_batch_from_the_seed():
    _, _, batch = family.build(TOY)
    (a,), (la,) = batch(np.random.default_rng(5), 3)
    (b,), _ = batch(np.random.default_rng(5), 3)
    (c,), _ = batch(np.random.default_rng(6), 3)
    assert a.shape == la.shape == (3, 64) and a.dtype == la.dtype == np.int32
    assert 0 <= a.min() and a.max() < 64
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.array_equal(la[:, :-1], a[:, 1:])
    made, = family.check_labels(NDArray(jnp.eye(4)[None]))
    assert made.tolist() == [[0, 1, 2, 3]]


# ------------------------------------------------------------- the scopes --
def test_train_step_program_names_every_layer_scope():
    net, loss_fn, batch = family.build(TOY)
    mx.random.seed(1)
    net.initialize()
    step = parallel.TrainStep(
        net, loss_fn, mx.optimizer.create("adamw", learning_rate=1e-3),
        mesh=_one_device())
    (ids,), (labels,) = batch(np.random.default_rng(0), 1)
    text = step.lower(ids, labels).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    scopes = ["embed", "head",
              "layer0/mamba", "layer0/mamba/conv", "layer0/mamba/scan",
              "layer1/window_attention",
              "layer2/mamba", "layer2/mamba/conv", "layer2/mamba/scan",
              "layer3/attention", "layer4/gmu", "layer5/cross_attention"] \
        + [f"layer{i}/mlp" for i in range(6)]
    for scope in scopes:
        forward = [p for p in paths
                   if f"/{scope}/" in p and "jvp(forward)" in p]
        assert forward, scope
        assert any("transpose(jvp(forward))" in p for p in forward), scope
    # the kernels carry their names into the program, the window layer's
    # under their own
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv", "window_attention_fwd",
                   "window_attention_bwd_dq", "window_attention_bwd_dkv"):
        assert kernel in text
