"""Registry-level random sampling ops (ref: tests/python/unittest/
test_random.py — the reference checks its `_random_*`/`_sample_*` op family
through the op interface, moments against the parameterisation, and
reproducibility under mx.random.seed)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd


def _draw(op, **kw):
    return nd.invoke(op, **kw).asnumpy()


def test_registry_has_sampler_family():
    from mxnet_tpu.ops.registry import OPS
    for name in ["_random_uniform", "_random_normal", "_random_gamma",
                 "_random_exponential", "_random_poisson",
                 "_random_negative_binomial",
                 "_random_generalized_negative_binomial", "_random_randint",
                 "_sample_uniform", "_sample_normal", "_sample_gamma",
                 "_sample_exponential", "_sample_poisson", "_shuffle"]:
        assert name in OPS, name


def test_uniform_range_and_moments():
    mx.random.seed(0)
    x = _draw("_random_uniform", low=2.0, high=5.0, shape=(20000,))
    assert x.shape == (20000,)
    assert x.min() >= 2.0 and x.max() < 5.0
    assert abs(x.mean() - 3.5) < 0.05


def test_normal_moments():
    mx.random.seed(0)
    x = _draw("_random_normal", loc=1.5, scale=2.0, shape=(20000,))
    assert abs(x.mean() - 1.5) < 0.1
    assert abs(x.std() - 2.0) < 0.1


def test_gamma_exponential_poisson_moments():
    mx.random.seed(0)
    g = _draw("_random_gamma", alpha=3.0, beta=2.0, shape=(20000,))
    assert abs(g.mean() - 6.0) < 0.2          # mean = alpha * beta
    e = _draw("_random_exponential", lam=4.0, shape=(20000,))
    assert abs(e.mean() - 0.25) < 0.02        # mean = 1 / lam
    p = _draw("_random_poisson", lam=3.0, shape=(20000,))
    assert abs(p.mean() - 3.0) < 0.1
    assert np.allclose(p, np.round(p))        # integer counts


def test_negative_binomial_moments():
    mx.random.seed(0)
    x = _draw("_random_negative_binomial", k=4, p=0.4, shape=(20000,))
    assert abs(x.mean() - 4 * 0.6 / 0.4) < 0.3    # mean = k(1-p)/p
    g = _draw("_random_generalized_negative_binomial", mu=2.0, alpha=0.5,
              shape=(20000,))
    assert abs(g.mean() - 2.0) < 0.15
    # var = mu + alpha * mu^2 = 4
    assert abs(g.var() - 4.0) < 0.5


def test_randint_range_dtype():
    mx.random.seed(0)
    x = nd.invoke("_random_randint", low=-3, high=9, shape=(5000,))
    assert x.dtype == "int32"
    xv = x.asnumpy()
    assert xv.min() >= -3 and xv.max() < 9
    assert set(np.unique(xv)) == set(range(-3, 9))


def test_seed_reproducibility_through_registry():
    mx.random.seed(7)
    a = _draw("_random_uniform", shape=(16,))
    b = _draw("_random_uniform", shape=(16,))
    mx.random.seed(7)
    a2 = _draw("_random_uniform", shape=(16,))
    b2 = _draw("_random_uniform", shape=(16,))
    np.testing.assert_array_equal(a, a2)
    np.testing.assert_array_equal(b, b2)
    assert not np.array_equal(a, b)   # stream advances between calls


def test_alias_wrappers_exist():
    # the reference exposes mx.nd.uniform / normal / shuffle as op wrappers
    mx.random.seed(0)
    u = nd.uniform(low=0.0, high=1.0, shape=(8,))
    assert u.shape == (8,)
    n = nd.normal(loc=0.0, scale=1.0, shape=(8,))
    assert n.shape == (8,)
    r = nd.randint(low=0, high=5, shape=(8,))
    assert r.dtype == "int32"


def test_sample_variants_per_row():
    mx.random.seed(0)
    low = nd.array(np.array([0.0, 10.0], np.float32))
    high = nd.array(np.array([1.0, 20.0], np.float32))
    s = nd.invoke("_sample_uniform", low, high, shape=(5000,)).asnumpy()
    assert s.shape == (2, 5000)
    assert s[0].min() >= 0.0 and s[0].max() < 1.0
    assert s[1].min() >= 10.0 and s[1].max() < 20.0

    mu = nd.array(np.array([-5.0, 5.0], np.float32))
    sg = nd.array(np.array([1.0, 3.0], np.float32))
    z = nd.invoke("_sample_normal", mu, sg, shape=(5000,)).asnumpy()
    assert abs(z[0].mean() + 5.0) < 0.2 and abs(z[1].std() - 3.0) < 0.2

    al = nd.array(np.array([2.0, 8.0], np.float32))
    be = nd.array(np.array([1.0, 0.5], np.float32))
    g = nd.invoke("_sample_gamma", al, be, shape=(5000,)).asnumpy()
    assert abs(g[0].mean() - 2.0) < 0.2 and abs(g[1].mean() - 4.0) < 0.3

    lam = nd.array(np.array([0.5, 4.0], np.float32))
    e = nd.invoke("_sample_exponential", lam, shape=(5000,)).asnumpy()
    assert abs(e[0].mean() - 2.0) < 0.25 and abs(e[1].mean() - 0.25) < 0.05
    p = nd.invoke("_sample_poisson", lam, shape=(5000,)).asnumpy()
    assert abs(p[0].mean() - 0.5) < 0.1 and abs(p[1].mean() - 4.0) < 0.2


def test_sample_multinomial():
    mx.random.seed(0)
    probs = nd.array(np.array([[0.0, 0.1, 0.9], [1.0, 0.0, 0.0]], np.float32))
    draws = nd.invoke("_sample_multinomial", probs, shape=2000).asnumpy()
    assert draws.shape == (2, 2000)
    assert draws[0].min() >= 1                       # class 0 has prob 0
    assert abs((draws[0] == 2).mean() - 0.9) < 0.03  # matches pvals
    assert set(np.unique(draws[1])) == {0}           # degenerate row
    # unspecified shape squeezes (reference _Null); explicit 1 keeps axis
    one = nd.invoke("_sample_multinomial", probs).asnumpy()
    assert one.shape == (2,)
    kept = nd.invoke("_sample_multinomial", probs, shape=1).asnumpy()
    assert kept.shape == (2, 1)
    # tuple shape: output is batch + shape (all prod(shape) draws kept)
    t = nd.invoke("_sample_multinomial", probs, shape=(3, 5)).asnumpy()
    assert t.shape == (2, 3, 5)
    assert set(np.unique(t[1])) == {0}
    # get_prob returns the log-prob of each draw
    d, lp = nd.invoke("_sample_multinomial", probs, shape=4, get_prob=True)
    dv, lpv = d.asnumpy(), lp.asnumpy()
    assert dv.shape == (2, 4) and lpv.shape == (2, 4)
    np.testing.assert_allclose(
        lpv, np.log(np.maximum(probs.asnumpy(), 1e-30))[
            np.arange(2)[:, None], dv.astype(int)],
        # the TPU's log is within 8e-5 of libm's (measured on the chip)
        rtol=2e-4 if mx.context.on_tpu() else 1e-5)
    # the module-style wrapper is the same implementation
    mx.random.seed(11)
    m1 = nd.random.multinomial(probs, shape=6).asnumpy()
    mx.random.seed(11)
    m2 = nd.invoke("_sample_multinomial", probs, shape=6).asnumpy()
    np.testing.assert_array_equal(m1, m2)


def test_shuffle_permutes_rows():
    mx.random.seed(3)
    x = nd.array(np.arange(40, dtype=np.float32).reshape(10, 4))
    y = nd.invoke("_shuffle", x).asnumpy()
    xv = x.asnumpy()
    # same rows, different order (seed 3 chosen to actually permute)
    assert sorted(map(tuple, y)) == sorted(map(tuple, xv))
    assert not np.array_equal(y, xv)


def test_samplers_work_under_autograd_recording():
    # sampling inside a record() scope must not break the tape
    import mxnet_tpu.autograd as ag
    x = nd.array(np.ones((4,), np.float32))
    x.attach_grad()
    with ag.record():
        noise = nd.invoke("_random_normal", shape=(4,))
        y = (x * noise).sum()
    y.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), noise.asnumpy(), rtol=1e-6)
