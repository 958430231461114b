"""The grouped products of the dropless expert layer
(``ops/pallas/grouped_matmul.py``) against ``jax.lax.ragged_dot``, the
product the layer ran before them, in the Pallas interpreter on XLA:CPU at
small widths; and the set-up mechanism they rest on: one trace and one
lowering per distinct kernel, one group map per layer."""
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel
from mxnet_tpu.ops.pallas import grouped_matmul as gm
from mxnet_tpu.ops.pallas import gated_rows, once, token_rows
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel import moe

from test_moe_decoder import (TOY, _one_device, family,
                              ragged_grouped_matmul)

# 100 rows in tiles of 16 (7 tiles, the last one part), 5 experts
ROWS, TILE, K_IN, N_OUT = 100, 16, 24, 40
SIZES = {
    "empty_experts": [10, 0, 33, 7, 5],        # total 55: inside a tile
    "no_row": [0, 0, 0, 0, 0],
    "every_row": [20, 20, 20, 20, 20],
    "one_expert_on_tile_edges": [0, 0, 64, 0, 0],
    "one_expert_every_row": [100, 0, 0, 0, 0],
    "the_last_expert_alone": [0, 0, 0, 0, 16],
}


def _walk(sizes, tile, m):
    """The map by hand: each non-empty expert's tiles in order, an empty
    expert's starting tile once."""
    visits, start = [], 0
    last_tile = -(-m // tile) - 1
    for g, size in enumerate(sizes):
        first = min(start // tile, last_tile)
        end = -(-(start + size) // tile) if size else first + 1
        visits += [(g, t) for t in range(first, end)]
        start += size
    return visits


@pytest.mark.parametrize("sizes", list(SIZES.values()), ids=list(SIZES))
def test_group_map_against_a_walk_of_the_tiles(sizes):
    gmap = gm.group_map(jnp.asarray(sizes, jnp.int32), ROWS, TILE)
    want = _walk(sizes, TILE, ROWS)
    steps = int(gmap.steps[0])
    assert steps == len(want)
    assert gmap.groups.shape == gmap.tiles.shape == (7 + 5 - 1,)
    got = list(zip(np.asarray(gmap.groups).tolist(),
                   np.asarray(gmap.tiles).tolist()))
    assert got[:steps] == want
    # past the last visit every step names it again
    assert set(got[steps:]) <= {want[-1]}
    assert np.asarray(gmap.offsets).tolist() == [0] + np.cumsum(
        sizes).tolist()
    # the gauge's count: the visits of experts that hold a row
    assert gm.tile_visits(sizes, TILE) == sum(sizes[g] > 0 for g, _ in want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("sizes", list(SIZES.values()), ids=list(SIZES))
def test_products_against_ragged_dot(sizes, dtype, monkeypatch):
    """Forward, input gradient and weight gradient on the rows below
    ``total``, with NaN in every row of ``x`` and ``dy`` past it: no NaN
    reaches a held row or a weight gradient, and an expert with no row gets
    a zero gradient."""
    monkeypatch.setattr(gm, "_ROW_TILE", TILE)
    rng = np.random.RandomState(0)
    sizes = jnp.asarray(sizes, jnp.int32)
    total = int(sizes.sum())
    x = jnp.asarray(rng.randn(ROWS, K_IN), dtype)
    w = jnp.asarray(rng.randn(5, K_IN, N_OUT), dtype)
    dy = jnp.asarray(rng.randn(ROWS, N_OUT), dtype)
    gmap = gm.group_map(sizes, ROWS, TILE)
    out, vjp = jax.vjp(lambda x, w: gm.grouped_matmul(x, w, gmap),
                       x.at[total:].set(jnp.nan), w)
    dx, dw = vjp(dy.at[total:].set(jnp.nan))
    want, vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, sizes), x, w)
    want_dx, want_dw = vjp(dy.at[total:].set(0))
    assert out.dtype == dx.dtype == dw.dtype == dtype
    assert (out.shape, dx.shape, dw.shape) == (want.shape, want_dx.shape,
                                               want_dw.shape)
    tol = 1e-4 if dtype == jnp.float32 else 0.1
    f32 = lambda a: np.asarray(a, np.float32)                  # noqa: E731
    np.testing.assert_allclose(f32(out[:total]), f32(want[:total]),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(f32(dx[:total]), f32(want_dx[:total]),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(f32(dw), f32(want_dw), atol=tol * 4,
                               rtol=tol)
    assert np.isfinite(f32(dw)).all()
    for g in np.flatnonzero(np.asarray(sizes) == 0):
        assert not f32(dw[g]).any()


@pytest.mark.parametrize("how", [{}, {"score": "sigmoid", "eps": 1e-6}],
                         ids=["softmax", "sigmoid_and_bias"])
@pytest.mark.parametrize("recomputed", [False, True],
                         ids=["kept", "recomputed"])
def test_grad_through_the_op_equals_the_parents_formulation(how, recomputed,
                                                            monkeypatch):
    """The layer, its load and all its gradients with the kernels (whose
    rows past the groups' sum are NaN in the interpreter) equal those of
    the same op over ``ragged_dot``, in tiles of 16 rows of 192."""
    monkeypatch.setattr(gm, "_ROW_TILE", 16)
    rng = np.random.RandomState(4)
    d, f, routed, held, k = 24, 12, 16, 6, 3
    tokens = jnp.asarray(rng.randn(64, d), jnp.float32)
    args = (tokens, jnp.asarray(rng.randn(d, routed), jnp.float32),
            jnp.asarray(rng.randn(held, d, 2 * f) * 0.3, jnp.float32),
            jnp.asarray(rng.randn(held, f, d) * 0.3, jnp.float32))
    bias = (jnp.asarray(rng.randn(routed) * 0.1, jnp.float32),) \
        if how else ()

    def run():
        def total(*a):
            out, load = get_op("moe_dropless_ffn")(
                *a, *bias, num_experts=routed, first_expert=5, k=k, **how)
            return (out ** 2).sum(), (out, load)
        if recomputed:
            total = jax.checkpoint(total)
        with jax.default_matmul_precision("highest"):
            grads, (out, load) = jax.grad(total, range(4), has_aux=True)(
                *args)
        return (out, load) + grads
    got = run()
    monkeypatch.setattr(moe.grouped_matmul, "grouped_matmul",
                        ragged_grouped_matmul)
    want = run()
    assert 0 < int(got[1][5:5 + held].sum()) < 64 * k
    assert np.array_equal(got[1], want[1])
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, atol=1e-5 * float(
            jnp.abs(w).max() + 1e-30))
    assert float(jnp.abs(got[4]).max()) > 0


# --------------------------------------------------- the set-up mechanism --
@pytest.fixture
def traces(monkeypatch):
    """Count each kernel's traces (its body reaching ``pallas_call``), the
    grouped products', the gated rows' and the token-side ones, with their
    jit caches and ``once``'s emptied first and after."""
    names = []
    real = gm.pl.pallas_call

    def counted(*a, name=None, **kw):
        names.append(name)
        return real(*a, name=name, **kw)
    for module in (gm, gated_rows, token_rows):
        monkeypatch.setattr(module, "pl", types.SimpleNamespace(
            **{**vars(gm.pl), "pallas_call": counted}))
    jits = (gm._product, gm._weights, gm.group_map, gated_rows._call,
            token_rows._reduce, token_rows.token_map)
    for fn in jits:
        fn.clear_cache()
    once.traced.cache_clear()
    yield names
    for fn in jits:
        fn.clear_cache()
    once.traced.cache_clear()


def _step(net, loss_fn):
    return parallel.TrainStep(
        net, loss_fn, mx.optimizer.create("adamw", learning_rate=1e-3),
        mesh=_one_device())


def test_one_trace_and_one_lowering_per_distinct_kernel(traces):
    """One ``TrainStep`` of the toy decoder (two recomputed expert layers,
    bf16): deferred initialisation and the step trace each of the six
    distinct kernels (two widths x forward, input gradient, weight gradient),
    the gated rows' two and the token-side two (the unit-weight sum is the
    weighted sum's kernel) once, whichever layer, pass or recomputation
    calls them (``ops/pallas/once.py``); a second step's trace adds none,
    and the step's program holds one function a kernel that every layer
    calls (the recomputed forward's copy of a forward kernel aside, which
    jax's partial evaluation makes)."""
    model = dict(TOY, layers=["window", "full"], sequence_length=32)
    net, loss_fn, batch = family.build(model)
    mx.random.seed(1)
    net.initialize()
    net.cast("bfloat16")
    (ids,), (labels,) = batch(np.random.default_rng(0), 2)
    assert np.isfinite(float(_step(net, loss_fn)(ids, labels).asnumpy()))
    assert sorted(traces) == sorted(
        2 * ["moe_grouped_fwd", "moe_grouped_dx", "moe_grouped_dw"]
        + ["moe_gated_fwd", "moe_gated_bwd", "moe_token_sum",
           "moe_token_dot"])
    again = _step(net, loss_fn)
    assert np.isfinite(float(again(ids, labels).asnumpy()))
    assert len(traces) == 10, traces[10:]
    text = again.lower(ids, labels).as_text()
    layers = len(model["layers"])
    funcs = re.findall(r"func\.func private @(_product|_weights)(_\d+)?\(",
                       text)
    calls = {"".join(f): len(re.findall(rf" call @{''.join(f)}\(", text))
             for f in funcs}
    assert all(n == layers for n in calls.values()), calls
    kinds = [f[0] for f in funcs]
    # fwd x2 widths in the forward and again in the recomputed forward, dx
    # x2; dw x2
    assert (kinds.count("_product"), kinds.count("_weights")) == (6, 2)


def test_one_group_map_a_layer_feeds_all_six_products():
    """In the jaxpr of the layer's gradient the map is built once, and all
    six kernel calls (the two products, their input gradients and their
    weight gradients) take it: the products directly, the gradients
    through the residuals."""
    rng = np.random.RandomState(0)
    d, f, routed, held, k = 24, 12, 8, 4, 2
    args = (jnp.asarray(rng.randn(32, d), jnp.float32),
            jnp.asarray(rng.randn(d, routed), jnp.float32),
            jnp.asarray(rng.randn(held, d, 2 * f), jnp.float32),
            jnp.asarray(rng.randn(held, f, d), jnp.float32))

    def total(*a):
        out, _ = get_op("moe_dropless_ffn")(*a, num_experts=routed, k=k)
        return (out ** 2).sum()
    jaxpr = jax.make_jaxpr(jax.grad(total, range(4)))(*args).jaxpr
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("pjit", "jit"):
                found.append((eqn.params["name"], eqn))
            for v in eqn.params.values():
                walk_any(v)

    def walk_any(v):
        if hasattr(v, "eqns"):
            walk(v)
        elif hasattr(getattr(v, "jaxpr", None), "eqns"):
            walk(v.jaxpr)
    walk(jaxpr)
    # the backward kernels sit in the layer's own jaxpr, the forward ones
    # behind the product's custom_vjp, whose third operand is the map
    backward = [e for name, e in found if name in ("_product", "_weights")
                and any(e is x for x in jaxpr.eqns)]
    forward = [e for e in jaxpr.eqns if e.primitive.name == "custom_vjp_call"
               and "_grouped_matmul_bwd" in str(e.params["bwd"])]
    assert len(backward) == 4 and len(forward) == 2
    built = [e for name, e in found if name == "group_map"]
    assert len(built) == 1
    maps = {tuple(e.invars[:4]) for e in backward} | {
        tuple(e.invars[2:6]) for e in forward}
    assert maps == {tuple(built[0].outvars)}
