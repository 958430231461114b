"""The token-side kernels of the dropless expert layer
(``ops/pallas/token_rows.py``) against jnp, in the Pallas interpreter on
XLA:CPU at small widths: each token's held rows of a sorted buffer, weighed
and summed (``moe_token_sum``) or dotted with the token's gradient
(``moe_token_dot``), with NaN in every row past ``total``, which no result
may show."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops.pallas import token_rows

HELD, D = 3, 128
# held shares: no row, part of the rows (every block holds some, and some
# runs start or end inside an 8-row chunk), every row
SHARES = {"no_row": 0.0, "inside_a_block": 0.45, "every_row": 1.0}


def _routing(n, k, share, seed=0):
    """``back`` (N, k) and the experts' offsets of a stable sort of random
    assignments, each to one of ``HELD`` experts with probability
    ``share`` and past them otherwise, as ``moe_dropless_ffn`` sorts."""
    rng = np.random.RandomState(seed)
    key = np.where(rng.rand(n * k) < share, rng.randint(0, HELD, n * k), HELD)
    order = np.argsort(key, kind="stable")
    back = np.empty(n * k, np.int32)
    back[order] = np.arange(n * k)
    sizes = np.bincount(key, minlength=HELD + 1)[:HELD]
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    return jnp.asarray(back.reshape(n, k)), jnp.asarray(offsets)


@pytest.fixture
def blocks_of_16(monkeypatch):
    # a jit of its own: the module's may hold maps of these shapes in
    # blocks of 64
    monkeypatch.setattr(token_rows, "_TOKENS", 16)
    monkeypatch.setattr(token_rows, "token_map",
                        jax.jit(token_rows.token_map.__wrapped__))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("share", list(SHARES.values()), ids=list(SHARES))
@pytest.mark.parametrize("n,k", [(33, 1), (40, 4), (40, 6), (24, 8)],
                         ids=["k1_rows_no_multiple_of_8", "k4", "k6", "k8"])
def test_token_kernels_read_the_held_rows_alone(n, k, share, dtype,
                                                blocks_of_16):
    """Blocks of 16 tokens, the last one part; NaN in every row of the
    buffer past ``total`` (the rows a fetched chunk may share with the held
    ones) and, in the interpreter, in every VMEM slot no chunk filled."""
    back, offsets = _routing(n, k, share)
    total = int(offsets[-1])
    m = n * k
    rng = np.random.RandomState(1)
    rows = jnp.asarray(rng.randn(m, D), dtype).at[total:].set(jnp.nan)
    weights = jnp.asarray(rng.rand(n, k), jnp.float32)
    dy = jnp.asarray(rng.randn(n, D), dtype)
    tmap = token_rows.token_map(back, offsets)
    got = token_rows.moe_token_sum(rows, tmap, weights)
    unit = token_rows.moe_token_sum(rows, tmap, jnp.ones((n, k)))
    dot = token_rows.moe_token_dot(rows, tmap, dy)
    held = (back < total)[..., None]
    gathered = jnp.where(held, rows[back].astype(jnp.float32), 0.0)
    want = jnp.sum(gathered * weights[..., None], axis=1)
    want_dot = jnp.sum(gathered * dy.astype(jnp.float32)[:, None], axis=-1)
    assert got.dtype == unit.dtype == dtype and dot.dtype == jnp.float32
    assert got.shape == unit.shape == (n, D) and dot.shape == (n, k)
    f32 = lambda a: np.asarray(a, np.float32)                  # noqa: E731
    assert np.isfinite(f32(got)).all() and np.isfinite(f32(unit)).all()
    assert np.isfinite(f32(dot)).all()
    # the weights keep 16 bits against bf16 rows; a bf16 result its own
    # rounding
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(f32(got), f32(want), atol=tol, rtol=tol)
    np.testing.assert_allclose(f32(unit), f32(jnp.sum(gathered, axis=1)),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(f32(dot), f32(want_dot), atol=1e-4,
                               rtol=1e-5)
    if not total:
        assert not f32(got).any() and not f32(dot).any()
    # the map fetches each run of held rows in whole chunks of 8: no more
    # than its rows and the rounding at both of its ends, nothing past them
    chunks = np.asarray(tmap.chunks)
    assert total <= 8 * chunks.sum() <= total + 14 * np.count_nonzero(chunks)


def test_the_unit_weight_sum_is_the_weighted_sums_lowering(blocks_of_16):
    """The sum with weights and with unit weights (the gather in's backward
    pass) call one function of the program, the dot another: two lowerings
    for three calls."""
    n, k = 40, 4
    back, offsets = _routing(n, k, 0.45)
    rows = jnp.ones((n * k, D), jnp.bfloat16)

    def passes(rows, dy, weights):
        tmap = token_rows.token_map(back, offsets)
        return (token_rows.moe_token_sum(rows, tmap, weights),
                token_rows.moe_token_sum(rows, tmap, jnp.ones((n, k))),
                token_rows.moe_token_dot(rows, tmap, dy))
    text = jax.jit(passes).lower(rows, jnp.ones((n, D), jnp.bfloat16),
                                 jnp.ones((n, k))).as_text(debug_info=True)
    paths = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    bodies = dict(re.findall(r"func\.func private @(_reduce\w*)\((.*?)\n  \}",
                             text, re.S))
    kernels = sorted({paths[loc].split("/")[0] for loc in re.findall(
        r"loc\((#loc\d+)\)", body) if paths.get(loc, "").startswith(
        "moe_token_")}.pop() for body in bodies.values())
    assert kernels == ["moe_token_dot", "moe_token_sum"]
    assert len(re.findall(r" call @_reduce\w*\(", text)) == 3
