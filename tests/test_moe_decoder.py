"""The mixture-of-experts decoder (``gluon/model_zoo/moe_decoder.py``), the
dropless expert layer (``parallel/moe.py``), the rotary op
(``ops/rotary.py``) and their benchmark family against the plain reference
kept with the benchmark (``chipbench/reference/moe_decoder.py``): float32,
small widths, seeded weights, more positions than five windows and two blocks
of the flash kernel."""
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, telemetry
from mxnet_tpu.gluon.block import _flatten_nd
from mxnet_tpu.gluon.model_zoo import moe_decoder
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.ops.rotary import rope_frequencies
from mxnet_tpu.parallel import moe
from mxnet_tpu.parallel.functional import (FunctionalState, functional_call,
                                           param_names_and_values)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest                                  # noqa: E402
from chipbench.families import deepseek_v3 as kanana2_family    # noqa: E402
from chipbench.families import lfm2_moe as lfm2_family          # noqa: E402
from chipbench.families import moe_decoder as family            # noqa: E402
from chipbench.reference import moe_decoder as reference        # noqa: E402

CONFIG = manifest.load_json(ROOT, "chipbench/configs/mellum2_12b_a2_5b.json")
CELL = "mellum2_12b_a2_5b.train_s8192"
CUT = ["window", "window", "window", "full"]
YARN = CONFIG["rope_parameters"]["full_attention"]
PLAIN = CONFIG["rope_parameters"]["sliding_attention"]
# the toy's YaRN table: an original context of 64 puts the ramp over the
# first rotary pairs of a head of 16 (low 0, high 2)
TOY_ROPE = {"sliding_attention": PLAIN,
            "full_attention": dict(YARN, original_max_position_embeddings=64)}
# 8 experts of which 4 are held (2 .. 5), top-3; heads of 16 on a hidden
# size of 32, so the query width (64) is not the hidden size
TOY = dict(layers=CUT, vocab_size=50, sequence_length=256, hidden_size=32,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           moe_intermediate_size=24, routed_experts=8, num_experts=4,
           first_expert=2, num_experts_per_tok=3, norm_topk_prob=True,
           sliding_window=48, rms_norm_eps=1e-6, embedding_std=0.5,
           rope_parameters=TOY_ROPE)
REF = dict(heads=4, kv_heads=2, head_dim=16, window=48, eps=1e-6, k=3,
           first_expert=2,
           rope={"window": TOY_ROPE["sliding_attention"],
                 "full": TOY_ROPE["full_attention"]})
VOCAB, T = TOY["vocab_size"], TOY["sequence_length"]
# the second configuration on the same decoder (``test_lfm2_moe.py`` has its
# own cases): one leading dense layer and a period of full, conv, conv, conv;
# 8 experts of which 4 are held (2 .. 5), top-3 of sigmoid score + bias
LFM2_CONFIG = manifest.load_json(ROOT, "chipbench/configs/lfm2_8b_a1b.json")
LFM2_CELL = "lfm2_8b_a1b.train_s8192"
LFM2_TOY = dict(layers=["conv", "full", "conv", "conv", "conv"],
                mlp_layers=["dense", "sparse", "sparse", "sparse", "sparse"],
                vocab_size=50, sequence_length=256, hidden_size=32,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                intermediate_size=48, moe_intermediate_size=24,
                routed_experts=8, num_experts=4, first_expert=2,
                num_experts_per_tok=3, norm_topk_prob=True,
                routed_scaling_factor=1, use_expert_bias=True, conv_L_cache=3,
                norm_eps=1e-5, rope_theta=1e6, embedding_std=0.5,
                expert_bias_std=0.1)
# the third (``test_kanana2.py`` has its own cases): latent attention in
# every layer, a leading dense layer, 8 experts of which 2 are held (2 and
# 3), top-3 of sigmoid score + bias, a shared expert of 2 x 16; heads of 16 +
# 8 for Q and K and of 16 for V, a latent of 16
KANANA2_CONFIG = manifest.load_json(ROOT,
                                    "chipbench/configs/kanana2_30b_a3b.json")
KANANA2_CELL = "kanana2_30b_a3b.train_s8192"
KANANA2_TOY = dict(
    layers=["latent"] * 3, mlp_layers=["dense", "sparse", "sparse"],
    num_experts=2, first_expert=2, routed_experts=8, vocab_size=128,
    sequence_length=64, hidden_size=64, num_attention_heads=4, head_dim=8,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=48, moe_intermediate_size=16, n_shared_experts=2,
    num_experts_per_tok=3, norm_topk_prob=True, routed_scaling_factor=2.448,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
    rms_norm_eps=1e-6, rope_theta=1e6, rope_interleave=True,
    embedding_std=0.5, expert_bias_std=0.1)
# (family, configuration, cell, toy model, how much more than its share the
# cell's attention sees, in words and in the entry's short form) of each
# configuration the benchmark runs on this decoder
ON_THIS_DECODER = {
    "mellum2_12b_a2_5b": (family, CONFIG, CELL, TOY, "four times", "4x"),
    "lfm2_8b_a1b": (lfm2_family, LFM2_CONFIG, LFM2_CELL, LFM2_TOY,
                    "four times", "4x"),
    "kanana2_30b_a3b": (kanana2_family, KANANA2_CONFIG, KANANA2_CELL,
                        KANANA2_TOY, "eight times", "8x")}


def _batch(seed=0, n=2, t=T):
    ids = np.random.RandomState(seed).randint(0, VOCAB, (n, t))
    return ids.astype(np.int32), np.roll(ids, -1, axis=1).astype(np.int32)


def _net(model=TOY, seed=3, recompute=False):
    mx.random.seed(seed)
    net = family.make_net(model)
    if recompute:
        for layer in net.layers:
            layer.recompute()
    net.initialize()
    return net


def _loss_and_grads(net, ids, labels, program=False):
    """The net's loss and gradients as ``TrainStep`` takes them: ``jax.grad``
    over ``functional_call``.  Gradients come back under the reference's
    structural names, the trained leaves alone; with ``program`` the
    gradient's lowered text comes back third."""
    names, plist, arrays = param_names_and_values(net)
    structural = {p.name: n
                  for n, p in net._collect_params_with_prefix().items()}
    trained = [i for i, p in enumerate(plist) if p.grad_req != "null"]
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss(axis=-1)
    leaves, tree = _flatten_nd((NDArray(jnp.asarray(ids)),))

    def loss_of(some):
        full = list(arrays)
        for i, a in zip(trained, some):
            full[i] = a
        outs = functional_call(net, plist, full, tree,
                               [l._data for l in leaves], jax.random.key(0),
                               True, FunctionalState())
        return jnp.mean(loss_fn(NDArray(outs[0]),
                                NDArray(jnp.asarray(labels)))._data)

    fn, x = jax.jit(jax.value_and_grad(loss_of)), [arrays[i] for i in trained]
    with jax.default_matmul_precision("highest"):
        loss, grads = fn(x)
        text = fn.lower(x).as_text() if program else None
    got = float(loss), {structural[names[i]]: g
                        for i, g in zip(trained, grads)}
    return got + (text,) if program else got


def _forward_kernel_calls(text):
    """Calls of the attention kernels' jitted forward in a program's text."""
    return len(re.findall(r" call @_flash_fwd(_\d+)?\(", text))


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
    net = _net()
    ids, labels = _batch()
    params = reference.params_from_net(net)
    got = _loss_and_grads(net, ids, labels)
    want = reference.loss_and_grads(params, CUT, jnp.asarray(ids),
                                    jnp.asarray(labels), **REF)
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
    # a layer: qkv, out_proj, two norms, router, gate_up, down; the
    # embedding, the last norm and the untied head
    assert len(want) == len(params) == 4 * 7 + 3
    assert all(float(jnp.abs(g).max()) > 0 for g in want.values())
    assert _worst(got, want) < 2e-5


@pytest.mark.parametrize("layers", [["window"], ["full"]], ids=lambda l: l[0])
def test_each_kind_of_layer_alone(layers):
    net = _net(dict(TOY, layers=layers), seed=5)
    ids, labels = _batch(1)
    params = reference.params_from_net(net)
    loss, grads = _loss_and_grads(net, ids, labels)
    want, want_grads = reference.loss_and_grads(
        params, layers, jnp.asarray(ids), jnp.asarray(labels), **REF)
    assert abs(loss - float(want)) < 1e-5
    assert _worst(grads, want_grads) < 2e-5


def test_a_kind_without_a_rotary_entry_gets_no_rotary_step():
    model = dict(TOY, layers=["full"], rope_parameters={})
    net = _net(model, seed=6)
    ids, _ = _batch(2)
    got = net(mx.nd.array(ids, dtype="int32"))
    want = reference.forward(reference.params_from_net(net), ["full"],
                             jnp.asarray(ids), **dict(REF, rope={}))
    np.testing.assert_allclose(got.asnumpy(), np.asarray(want), atol=2e-6)


def test_unknown_kinds_and_tables_raise():
    with pytest.raises(ValueError, match="kind"):
        _net(dict(TOY, layers=["window", "mamba"]))
    with pytest.raises(ValueError, match="rope_type"):
        rope_frequencies({"rope_type": "linear", "rope_theta": 1e4}, 16)
    with pytest.raises(ValueError, match="whole periods"):
        moe_decoder.published_layers(6)
    with pytest.raises(ValueError, match="not among"):
        parallel.DroplessMoEFFN(8, 8, num_experts=8, k=2, held=4,
                                first_expert=6)


def test_recomputed_layers_give_equal_gradients(cut):
    _, ids, _, (loss, grads), _ = cut
    _, labels = _batch()
    again, marked, text = _loss_and_grads(_net(recompute=True), ids, labels,
                                          program=True)
    assert again == loss
    assert _worst(marked, grads) < 1e-6
    # the marked layers keep their attention kernels' output and
    # log-sum-exp: the forward kernel runs once a layer, not again in the
    # recomputed forward
    assert _forward_kernel_calls(text) == len(CUT)


# -------------------------------------------------------- the expert layer --
E, HELD, K, D, F = 64, 16, 8, 24, 12


def _expert_weights(seed=0):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)     # noqa: E731
    return f(D, E), f(E, D, 2 * F) * 0.3, f(E, F, D) * 0.3


def _share(tokens, router, gate_up, down, first, held=HELD, k=K):
    return get_op("moe_dropless_ffn")(
        tokens, router, gate_up[first:first + held],
        down[first:first + held], num_experts=E, first_expert=first, k=k)


def _reference_share(tokens, router, gate_up, down, first, held=HELD, k=K):
    with jax.default_matmul_precision("highest"):
        return reference.moe(tokens, router, gate_up[first:first + held],
                             down[first:first + held], k, first)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """64 experts, top-8, four chips of 16: the parts the shares compute sum
    to what the uncut reference gives for the whole layer, and every share
    counts the same assignments."""
    router, gate_up, down = _expert_weights()
    tokens = jnp.asarray(np.random.RandomState(1).randn(96, D), jnp.float32)
    whole = _reference_share(tokens, router, gate_up, down, 0, held=E)
    parts, loads = zip(*[_share(tokens, router, gate_up, down, first)
                         for first in (0, 16, 32, 48)])
    for first, part in zip((0, 16, 32, 48), parts):
        np.testing.assert_allclose(
            part, _reference_share(tokens, router, gate_up, down, first),
            rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(whole).max()) > 0.1
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=5e-6)
    assert all(np.array_equal(l, loads[0]) for l in loads)
    assert int(loads[0].sum()) == 96 * K and loads[0].dtype == jnp.int32


@pytest.fixture(params=[None, 100, 8], ids=["one_chunk", "chunks_of_100",
                                            "chunks_of_8"])
def chunk(request, monkeypatch):
    """The rows a trip of the op's bounded passes visits: the rule's own
    (every buffer here is then one chunk), or a chunk that divides neither
    a buffer nor its held rows."""
    if request.param:
        monkeypatch.setattr(moe, "_row_chunk",
                            lambda m: min(m, request.param))
    return request.param


@pytest.mark.parametrize("favoured,held_rows", [
    (tuple(range(3, 11)), 96 * 8), (tuple(range(40, 48)), 0),
    (tuple(range(12, 20)), 96 * 4)],
    ids=["all_held", "none_held", "half_held"])
def test_dropless_under_forced_imbalance(favoured, held_rows, chunk):
    """A router forced to send every token to the same eight experts: all
    held here (every one of the N x k rows is a held row: no buffer sized
    for an even split would hold them, and the bounded passes visit every
    chunk), none held (no trip at all: the layer returns zero and its
    experts get zero gradients), half held (384 rows: in chunks of 100 the
    last trip is part held).  Values and every gradient match the reference;
    no token is dropped."""
    router, gate_up, down = _expert_weights(2)
    router = router * 0.01 + 20.0 * jnp.zeros((D, E)).at[
        0, jnp.asarray(favoured)].set(jnp.linspace(1.0, 1.7, 8))
    tokens = jnp.asarray(np.random.RandomState(3).randn(96, D), jnp.float32)
    tokens = tokens.at[:, 0].set(1.0)

    def args(gu, dn):
        return tokens, router, gu, dn
    out, load = _share(*args(gate_up, down), 0)
    assert int(load[jnp.asarray(favoured)].sum()) == 96 * K
    assert int(load[:HELD].sum()) == held_rows
    want = _reference_share(*args(gate_up, down), 0)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    if not held_rows:
        assert float(jnp.abs(out).max()) == 0.0

    def total(fn):
        return lambda t, r, gu, dn: (fn(t, r, gu, dn, 0) ** 2).sum()
    got = jax.grad(total(lambda *a: _share(*a)[0]), range(4))(
        *args(gate_up, down))
    ref = jax.grad(total(_reference_share), range(4))(*args(gate_up, down))
    for g, w in zip(got, ref):
        assert g.shape == w.shape
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, atol=2e-5 * float(
            jnp.abs(w).max() + 1e-30) + 1e-7)
    if not held_rows:
        assert all(float(jnp.abs(g).max()) == 0.0 for g in got)


@pytest.mark.parametrize("first,rows", [(5, 33), (6, 0)],
                         ids=["every_token", "no_token"])
def test_one_expert_takes_every_token(first, rows, chunk):
    """top-1 with one held expert that every token chooses (33 rows, all
    held: in chunks of 8 the last trip starts at row 25), and then one that
    none chooses."""
    router, gate_up, down = _expert_weights(4)
    router = router.at[:, 5].add(100.0 * jnp.sign(router[:, 5]))
    tokens = jnp.abs(jnp.asarray(
        np.random.RandomState(5).randn(33, D), jnp.float32)) \
        * jnp.sign(router[:, 5])
    out, load = _share(tokens, router, gate_up, down, first, held=1, k=1)
    assert int(load[5]) == 33 and int(load[first]) == rows
    np.testing.assert_allclose(out, _reference_share(
        tokens, router, gate_up, down, first, held=1, k=1), atol=2e-5)


@pytest.mark.parametrize("rows,total,block", [
    (72, 30, 16), (72, 0, 16), (72, 72, 16), (300, 257, 256), (50, 49, 256)],
    ids=["part_of_a_block", "no_row", "every_row", "a_block_that_does_not"
         "_divide", "one_block"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
def test_gated_rows_stop_at_the_held_rows(rows, total, block, dtype,
                                          monkeypatch):
    """``silu(gate) * up`` by the two kernels of ``ops/pallas/gated_rows``:
    below ``total`` the value and the cotangent are jnp's, from ``total`` to
    the end of its block they are zero, and NaN in the input or in ``dy``
    past ``total`` reaches neither.  (Past that block nothing is written:
    the interpreter leaves NaN there, the chip whatever the buffer held.)"""
    from mxnet_tpu.ops.pallas import gated_rows
    monkeypatch.setattr(gated_rows, "_BLOCK", block)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(rows, 2 * F), dtype)
    dy = jnp.asarray(rng.randn(rows, F), dtype)

    def plain(x):
        gate, up = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return (jax.nn.silu(gate) * up).astype(dtype)
    want, vjp = jax.vjp(plain, x)
    want_dx, = vjp(dy)
    out, vjp = jax.vjp(
        lambda x: gated_rows.gated_rows(x, jnp.int32(total)),
        x.at[total:].set(jnp.nan))
    dx, = vjp(dy.at[total:].set(jnp.nan))
    assert out.dtype == dx.dtype == dtype
    assert out.shape == (rows, F) and dx.shape == (rows, 2 * F)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out[:total].astype(np.float32),
                               want[:total].astype(np.float32), atol=tol)
    np.testing.assert_allclose(dx[:total].astype(np.float32),
                               want_dx[:total].astype(np.float32), atol=tol)
    end = -(-total // min(block, rows)) * min(block, rows)
    assert not np.asarray(out[total:end], np.float32).any()
    assert not np.asarray(dx[total:end], np.float32).any()


def ragged_grouped_matmul(x, w, groups):
    """The grouped product the op ran before its kernels (PR 38): XLA's
    ``ragged_dot`` over the experts of ``groups`` (a ``GroupMap``); on
    XLA:CPU its rows past the groups' sum come back zero."""
    return jax.lax.ragged_dot(x, w, jnp.diff(groups.offsets))


@pytest.mark.parametrize("recomputed", [False, True],
                         ids=["kept", "recomputed"])
def test_rows_past_the_groups_sum_may_hold_anything(recomputed, chunk,
                                                    monkeypatch):
    """The op, its load and all four gradients with the grouped-product
    kernels, which leave NaN in every row past their groups' sum in the
    interpreter (as stale memory on the chip), forward and in the rows'
    gradient: finite, and within rounding of the same op over
    ``ragged_dot``.  No pass reads such a row unmasked."""
    router, gate_up, down = _expert_weights(6)
    tokens = jnp.asarray(np.random.RandomState(7).randn(96, D), jnp.float32)

    def run():
        def total(*a):
            out, load = _share(*a, 16)
            return (out ** 2).sum(), (out, load)
        if recomputed:
            total = jax.checkpoint(total)
        with jax.default_matmul_precision("highest"):
            grads, (out, load) = jax.grad(total, range(4), has_aux=True)(
                tokens, router, gate_up, down)
        return (out, load) + grads
    poisoned = run()
    sizes = poisoned[1][16:32]
    assert 0 < int(sizes.sum()) < 96 * K
    # the poison is there: the kernel's own rows past the sum are NaN
    rows = 96 * K
    groups = moe.grouped_matmul.group_map(
        sizes, rows, moe.grouped_matmul.row_tile(rows))
    raw = moe.grouped_matmul.grouped_matmul(jnp.ones((rows, D)),
                                            gate_up[16:32], groups)
    assert bool(jnp.isnan(raw[int(sizes.sum()):]).all())
    monkeypatch.setattr(moe.grouped_matmul, "grouped_matmul",
                        ragged_grouped_matmul)
    clean = run()
    assert np.array_equal(poisoned[1], clean[1])
    for got, want in zip(poisoned, clean):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got, want, atol=2e-5 * float(
            jnp.abs(want).max()))
    assert float(jnp.abs(clean[0]).max()) > 0.1


def test_route_topk_renormalises_over_the_chosen():
    logits = jnp.asarray(np.random.RandomState(6).randn(10, E), jnp.float32)
    gates, experts = parallel.route_topk(logits, K)
    probs = jax.nn.softmax(logits, axis=-1)
    assert gates.shape == experts.shape == (10, K)
    assert experts.dtype == jnp.int32 and gates.dtype == jnp.float32
    np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)
    order = np.argsort(-np.asarray(probs), axis=-1)[:, :K]
    assert np.array_equal(np.sort(order, -1), np.sort(experts, -1))
    chosen = np.take_along_axis(np.asarray(probs), np.asarray(experts), -1)
    np.testing.assert_allclose(gates, chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-6)
    raw, _ = parallel.route_topk(logits, K, renormalise=False)
    np.testing.assert_allclose(raw, chosen, rtol=1e-6)
    want, _ = reference.route(logits, jnp.eye(E), K)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(want), np.asarray(experts), -1), gates,
        rtol=1e-5)


def test_block_holds_its_share_and_shards_it_over_ep():
    mx.random.seed(2)
    block = parallel.DroplessMoEFFN(D, F, num_experts=E, k=K, held=HELD,
                                    first_expert=32)
    block.initialize()
    assert block.router.shape == (D, E)
    assert block.gate_up.shape == (HELD, D, 2 * F)
    assert block.down.shape == (HELD, F, D)
    assert block.load.grad_req == "null" and block.held_range() == (32, 48)
    x = mx.nd.array(np.random.RandomState(0).randn(2, 20, D))
    out, load = block(x)
    assert out.shape == (2, 20, D) and load.shape == (E,)
    want = reference.moe(x._data, block.router.data()._data,
                         block.gate_up.data()._data, block.down.data()._data,
                         K, 32)
    np.testing.assert_allclose(out.asnumpy(), want, atol=2e-6)
    rules = block.sharding_rules()
    mesh = parallel.make_mesh(ep=4, devices=jax.devices()[:4])
    specs = {p.name: rules.spec_for(p.name, p.shape, mesh)
             for p in block.collect_params().values()}
    assert tuple(specs[block.gate_up.name])[0] == "ep"
    assert tuple(specs[block.down.name])[0] == "ep"
    assert not any(tuple(specs[n]) for n in (block.router.name,
                                             block.load.name))
    block.cast("bfloat16")
    assert block.load.data().dtype == np.int32
    assert block.router.data().dtype == jnp.bfloat16


# -------------------------------------------------------------- the rotary --
def test_yarn_table_against_hand_values():
    f, m = rope_frequencies(YARN, 128)
    assert len(f) == 64 and m == pytest.approx(1.2772588722239782, abs=1e-12)
    assert m == pytest.approx(0.1 * math.log(16) + 1, abs=1e-12)
    c = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) \
        / (2 * math.log(500000))                             # noqa: E731
    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    assert c(32) == pytest.approx(18.081, abs=1e-3)
    assert c(1) == pytest.approx(34.984, abs=1e-3)
    e = [500000 ** (-2 * i / 128) for i in range(64)]
    # up to pair 18 the plain table, from pair 35 on a sixteenth of it
    assert f[0] == 1.0
    np.testing.assert_allclose(f[:19], e[:19], rtol=1e-6)
    np.testing.assert_allclose(f[35:], np.asarray(e[35:]) / 16, rtol=1e-6)
    assert f[63] == pytest.approx(500000 ** (-126 / 128) / 16, rel=1e-6)
    ramp = (27 - 18) / (35 - 18)
    assert f[27] == pytest.approx(e[27] / 16 * ramp + e[27] * (1 - ramp),
                                  rel=1e-6)
    no_factor = dict(YARN)
    del no_factor["attention_factor"]
    assert rope_frequencies(no_factor, 128)[1] == pytest.approx(m, abs=1e-12)
    ref_f, ref_m = reference.rope_table(YARN, 128)
    assert np.array_equal(np.asarray(ref_f), np.asarray(f, np.float32))
    assert ref_m == m


def test_plain_table():
    f, m = rope_frequencies(PLAIN, 128)
    assert m == 1.0 and len(f) == 64
    np.testing.assert_allclose(
        f, [500000 ** (-2 * i / 128) for i in range(64)], rtol=1e-6)
    assert f[63] == pytest.approx(500000 ** (-126 / 128), rel=1e-6)
    ref_f, ref_m = reference.rope_table(PLAIN, 128)
    assert np.array_equal(np.asarray(ref_f), np.asarray(f, np.float32))
    assert ref_m == 1.0
    assert rope_frequencies({"rope_theta": 500000}, 128) == (f, m)


@pytest.mark.parametrize("table", [PLAIN, YARN], ids=["default", "yarn"])
def test_rotary_keeps_position_0_and_scores_depend_on_distance(table):
    f, m = rope_frequencies(table, 16)
    rope = lambda x, heads: get_op("rotary_embedding")(      # noqa: E731
        x, inv_freq=f, heads=heads, factor=m)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 40, 3 * 16), jnp.float32)
    y = rope(x, 3)
    assert y.shape == x.shape and y.dtype == x.dtype
    np.testing.assert_allclose(y[:, 0], m * x[:, 0], rtol=1e-6)
    want = reference._rotate(x.reshape(2, 40, 3, 16),
                             reference.rope_table(table, 16))
    np.testing.assert_allclose(y, want.reshape(2, 40, 48), atol=1e-6)
    # the same q and k at every position: the score of (t, s) is a function
    # of t - s alone
    q = jnp.broadcast_to(jnp.asarray(rng.randn(16), jnp.float32), (1, 40, 16))
    k = jnp.broadcast_to(jnp.asarray(rng.randn(16), jnp.float32), (1, 40, 16))
    scores = np.asarray(rope(q, 1)[0] @ rope(k, 1)[0].T)
    for lag in (0, 1, 7, 25):
        diagonal = np.diagonal(scores, -lag)
        np.testing.assert_allclose(diagonal, diagonal[0], atol=2e-5)
    assert abs(scores[7, 0] - scores[25, 0]) > 1e-3
    low = rope(x.astype(jnp.bfloat16), 3)
    assert low.dtype == jnp.bfloat16
    assert float(jnp.abs(low.astype(jnp.float32) - y).max()) < 0.05
    with pytest.raises(ValueError, match="inverse frequencies"):
        get_op("rotary_embedding")(x, inv_freq=f[:4], heads=3)


@pytest.mark.parametrize("heads,d", [(3, 16), (1, 64)])
def test_rotary_interleaved_turns_neighbouring_pairs(heads, d):
    """``interleaved=True``: dims (2i, 2i+1) of each head turned by ``t *
    f[i]`` in their place, against the explicit rotation of every pair in
    numpy; position 0 as it came; the default is the rotate-half program,
    unchanged by the new argument."""
    f, m = rope_frequencies(PLAIN, d)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 20, heads * d), jnp.float32)
    got = np.asarray(get_op("rotary_embedding")(x, inv_freq=f, heads=heads,
                                                interleaved=True))
    want = np.asarray(x).reshape(2, 20, heads, d).astype(np.float64)
    out = np.empty_like(want)
    for t in range(20):
        for i in range(d // 2):
            c, s = np.cos(t * np.float32(f[i])), np.sin(t * np.float32(f[i]))
            a, b = want[:, t, :, 2 * i], want[:, t, :, 2 * i + 1]
            out[:, t, :, 2 * i] = a * c - b * s
            out[:, t, :, 2 * i + 1] = a * s + b * c
    np.testing.assert_allclose(got, out.reshape(got.shape), atol=2e-5)
    np.testing.assert_array_equal(got[:, 0], np.asarray(x)[:, 0])
    # the pairs keep their lengths
    pairs = got.reshape(2, 20, heads, d // 2, 2)
    np.testing.assert_allclose((pairs ** 2).sum(-1), (np.asarray(x).reshape(
        pairs.shape) ** 2).sum(-1), rtol=1e-5)
    # and the rotate-half program stays as it was
    plain = [str(jax.make_jaxpr(lambda a: get_op("rotary_embedding")(
        a, inv_freq=f, heads=heads, **kw))(x)) for kw in ({}, {
            "interleaved": False})]
    assert plain[0] == plain[1]
    np.testing.assert_allclose(
        get_op("rotary_embedding")(x, inv_freq=f, heads=heads),
        reference._rotate(x.reshape(2, 20, heads, d),
                          reference.rope_table(PLAIN, d)).reshape(x.shape),
        atol=1e-6)
    from chipbench.reference import deepseek_v3
    np.testing.assert_allclose(
        got, deepseek_v3.rotate_pairs(x.reshape(2, 20, heads, d),
                                      PLAIN["rope_theta"]).reshape(x.shape),
        atol=2e-5)


def test_the_window_counts_1024_keys_with_the_query():
    """Uniform scores, a value that is 1 at key 0 alone: query t reads 1 /
    (keys it sees) while key 0 is among them: 1,024 at t = 1,023, and
    nothing from t = 1,024 on."""
    window = CONFIG["model"]["sliding_window"]
    assert window == CONFIG["sliding_window"] == 1024
    t = 2 * window + 50
    zeros = jnp.zeros((1, t, 8), jnp.float32)
    v = zeros.at[0, 0, :].set(1.0)
    out = np.asarray(get_op("window_attention")(
        zeros, zeros, v, heads=1, kv_heads=1, window=window))[0, :, 0]
    np.testing.assert_allclose(out[:window],
                               1.0 / np.arange(1, window + 1), rtol=1e-5)
    assert out[window - 1] == pytest.approx(1.0 / 1024, rel=1e-5)
    assert np.all(out[window:] == 0.0)


# --------------------------------------------- the configuration, the count --
def _trained_parameters(**changes):
    net = family.make_net(dict(CONFIG["model"], **changes))
    return sum(int(np.prod(p.shape)) for p in net.collect_params().values()
               if p.grad_req != "null")


def test_published_layers_is_the_configs_list():
    kinds = moe_decoder.published_layers(CONFIG["num_hidden_layers"])
    assert [{"window": "sliding_attention", "full": "full_attention"}[k]
            for k in kinds] == CONFIG["layer_types"]
    assert set(CONFIG["mlp_layer_types"]) == {"sparse"}
    assert (kinds.count("window"), kinds.count("full")) == (21, 7)
    assert CONFIG["model"]["layers"] == moe_decoder.published_layers(4) == CUT


def test_the_published_config_counts_12b_and_the_cut_595m():
    layer = 2304 * (4096 + 2 * 512) + 4096 * 2304 + 2 * 2304 + 2304 * 64
    expert = 3 * 2304 * 896
    assert (layer, expert) == (21_385_728, 6_193_152)
    published = _trained_parameters(
        layers=moe_decoder.published_layers(28), num_experts=64,
        vocab_size=CONFIG["vocab_size"])
    assert published == 28 * (layer + 64 * expert) \
        + 2 * 98304 * 2304 + 2304 == 12_149_915_904
    active = published - 28 * 56 * expert
    # ISSUE 31 wrote ...051,264: the last norm's 2,304 left out
    assert active == 2_439_053_568
    assert _trained_parameters() == 4 * (layer + 16 * expert) \
        + 2 * 24576 * 2304 + 2304 == 595_153_152


def test_configuration_keeps_every_published_width():
    """The catalog's ``config`` verbatim at the top level; ``model`` repeats
    the widths it needs and changes depth, the experts held and the
    vocabulary alone."""
    catalog = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}}}
    assert {k: CONFIG[k] for k in catalog} == catalog
    assert len(CONFIG["layer_types"]) == len(CONFIG["mlp_layer_types"]) == 28
    model = CONFIG["model"]
    for key in set(model) & set(catalog) - {"vocab_size", "num_experts"}:
        assert model[key] == catalog[key], key
    assert CONFIG["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert model["routed_experts"] == catalog["num_experts"]
    assert model["num_experts"] * 4 == catalog["num_experts"]
    assert model["vocab_size"] * 4 == catalog["vocab_size"]
    assert model["first_expert"] == 0 and model["sequence_length"] == 8192
    assert set(CONFIG["assumed"]) >= {"qk_norm", "intermediate_size",
                                      "initialisation", "load_balancing_loss",
                                      "learning_rate"}
    assert any("multi-token" in d for d in CONFIG["departures"])
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert CONFIG["check"]["loss_atol"] > 0 and CONFIG["check"]["why"]
    entry = next(c for c in manifest.load(ROOT)["configs"]
                 if c["name"] == "mellum2_12b_a2_5b")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "chipbench/configs/mellum2_12b_a2_5b.json"
    assert not any(manifest.WIDTH.search(k) for k in entry["reduced"])


def test_train_flops_against_a_hand_count():
    """The ISSUE's arithmetic, a sequence of 8,192: the layers' attention and
    router matrices and the 2 of 16 held experts a token meets under even
    routing, the attention products over the pairs causality and the window
    leave, the untied head over the slice."""
    model = CONFIG["model"]
    t = model["sequence_length"]
    products = 2 * t * 4 * (21_233_664 + 147_456 + 2 * 6_193_152)
    band = sum(min(i + 1, 1024) for i in range(t))
    full = t * (t + 1) // 2
    assert (band, full) == (7_864_832, 33_558_528)
    attention = 2 * 2 * 4096 * (3 * band + full)
    head = 2 * t * 24576 * 2304
    assert family.train_flops(model) == 3 * (products + attention + head)
    assert round(products / 1e12, 3) == 2.213
    assert round(attention / 1e12, 3) == 0.936
    assert round(head / 1e12, 3) == 0.928
    assert round(family.train_flops(model) / 1e12, 2) == 12.23
    # the held experts' share of the required operations, here and as a
    # deployed chip fed by four sees them (the cell's why)
    experts = 2 * t * 4 * 2 * 6_193_152
    here = experts / (products + attention + head)
    fed_by_four = 4 * experts / (products + attention + head + 3 * experts)
    assert 0.19 < here < 0.21 and 0.49 < fed_by_four < 0.51


def test_grouped_product_counts():
    model = CONFIG["model"]
    rows = 8192 * 8 // 4              # even routing: a quarter of the rows
    assert family.grouped_product_flops(model, rows) \
        == 2 * rows * 2304 * 1792 + 2 * rows * 896 * 2304
    assert family.grouped_product_bytes(model, rows, 16) == 2 * (
        rows * 2304 + 16 * 2304 * 1792 + rows * 1792
        + rows * 896 + 16 * 896 * 2304 + rows * 2304)
    assert family.grouped_product_bytes(model, 0, 16) == 2 * 16 * 6_193_152


# -------------------------------------------------- through the benchmark --
@pytest.fixture(params=sorted(ON_THIS_DECODER))
def toy_benchmark(tmp_path, request):
    """A throw-away benchmark holding one configuration and its cell at toy
    sizes, added as files and entries beside none."""
    name = request.param
    _, config, cell, toy, _, _ = ON_THIS_DECODER[name]
    root, src = str(tmp_path), os.path.join(ROOT, "chipbench")
    real = manifest.load(ROOT)
    for d in ("configs", "workloads", "layer_metrics"):
        os.makedirs(os.path.join(root, "chipbench", d))
    cfg = dict(config, model=dict(toy, sequence_length=64),
               compute_dtype="float32", check={"loss_atol": 0.02},
               optimizer={"name": "adamw", "args": {"learning_rate": 1e-3}})
    with open(os.path.join(root, f"chipbench/configs/{name}.json"),
              "w") as f:
        json.dump(cfg, f)
    wl = manifest.load_json(src, f"workloads/{cell}.json")
    wl.update(trace_s=1.0, batch_per_chip=1)
    with open(os.path.join(root, manifest.workload_file(cell)), "w") as f:
        json.dump(wl, f)
    m = dict(real, run_seconds=2)
    m["configs"] = [c for c in real["configs"] if c["name"] == name]
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


@pytest.mark.parametrize("name", sorted(ON_THIS_DECODER))
def test_the_real_benchmark_holds_the_cell(name):
    family_, config, cell, _, words, short = ON_THIS_DECODER[name]
    real = manifest.load(ROOT)
    assert manifest.validate(real, ROOT) == []
    cells = [w["name"] for w in real["workloads"]]
    assert cells.index(CELL) == 2 and cells.index(LFM2_CELL) == 3
    view = manifest.cell(real, ROOT, cell)
    assert view["chips"] == 1 and view["cfg"]["kind"] == "train"
    assert view["cfg"]["family"] == family_.__name__.rsplit(".", 1)[1]
    assert view["wl"]["driver"] == "train_loop"
    assert view["wl"]["batch_per_chip"] in (1, 2, 4)
    assert [m["name"] for m in view["end_to_end"]] == [
        "train_samples_per_s", "setup_s"]
    assert {m["name"] for m in view["per_layer"]} >= {
        "compile_ms_total", "step_ms_p50", "mfu_pct", "device_idle_pct.train"}
    entry = real["workloads"][cells.index(cell)]
    assert len(entry["why"]) <= 200 and words in view["wl"]["why"]
    assert short in entry["why"]


@pytest.mark.parametrize("name", sorted(ON_THIS_DECODER))
def test_family_draws_its_batch_from_the_seed(name):
    family_, _, _, toy, _, _ = ON_THIS_DECODER[name]
    _, _, batch = family_.build(dict(toy, sequence_length=64))
    (a,), (la,) = batch(np.random.default_rng(5), 3)
    (b,), _ = batch(np.random.default_rng(5), 3)
    (c,), _ = batch(np.random.default_rng(6), 3)
    assert a.shape == la.shape == (3, 64) and a.dtype == la.dtype == np.int32
    assert 0 <= a.min() and a.max() < toy["vocab_size"]
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.array_equal(la[:, :-1], a[:, 1:])
    made, = family_.check_labels(NDArray(jnp.eye(4)[None]))
    assert made.tolist() == [[0, 1, 2, 3]]


# ------------------------------------------------- the scopes and the load --
@pytest.fixture(scope="module")
def stepped():
    """The family's toy net (every layer recomputed, bf16) under TrainStep
    with AdamW on a one-device mesh, and its batch."""
    net, loss_fn, batch = family.build(dict(TOY, sequence_length=64))
    mx.random.seed(1)
    net.initialize()
    net.cast("bfloat16")
    step = parallel.TrainStep(
        net, loss_fn, mx.optimizer.create("adamw", learning_rate=1e-3),
        mesh=_one_device())
    (ids,), (labels,) = batch(np.random.default_rng(0), 2)
    return net, step, ids, labels


def test_train_step_program_names_every_new_scope(stepped):
    _, step, ids, labels = stepped
    text = step.lower(ids, labels).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*)"', text))
    scopes = ["embed", "head"]
    for i, kind in enumerate(CUT):
        attention = "window_attention" if kind == "window" else "attention"
        scopes += [f"layer{i}/{attention}", f"layer{i}/{attention}/rope",
                   f"layer{i}/moe"]
        scopes += [f"layer{i}/moe/{part}"
                   for part in ("router", "dispatch", "experts", "combine")]
    for scope in scopes:
        forward = [p for p in paths
                   if f"/{scope}/" in p and "jvp(forward)" in p]
        assert forward, scope
        if not scope.endswith(("/router", "/dispatch")) or "router" in scope:
            assert any("transpose(jvp(forward))" in p for p in forward), scope
    assert "rematted_computation" in text
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv", "window_attention_fwd",
                   "window_attention_bwd_dq", "window_attention_bwd_dkv",
                   "moe_grouped_fwd", "moe_grouped_dx", "moe_grouped_dw"):
        assert kernel in text
    ops = "\n".join(l for l in text.splitlines() if not l.startswith("#loc"))
    assert "ragged_dot" not in ops


def test_train_step_program_bounds_the_row_passes(monkeypatch):
    """The step's program for the toy decoder (384 sorted rows a layer, in
    chunks of 128): the gather of every expert layer's output gradient is a
    loop whose trip count is a value of the program, the stage between the
    products is the two kernels that take that count, no select runs over a
    whole ``[N x k, d]`` buffer, and the token-side passes are the kernels
    of ``ops/pallas/token_rows``: ``moe_token_sum`` under ``moe/combine`` in
    the forward pass and under ``moe/dispatch`` in the backward pass,
    ``moe_token_dot`` under ``moe/combine`` in the backward pass (the
    recomputed forward has no sum: the backward pass keeps the rows, not the
    sum), and no ``[N, k, d]`` tensor of gathered rows is left."""
    monkeypatch.setattr(moe, "_row_chunk", lambda m: min(m, 128))
    # the grouped products' tiles too: their interpreted bodies' masks are
    # selects over a tile, which must not be the whole buffer here
    monkeypatch.setattr(moe.grouped_matmul, "_ROW_TILE", 128)
    net, loss_fn, batch = family.build(dict(TOY, sequence_length=64))
    net.initialize()
    net.cast("bfloat16")
    step = parallel.TrainStep(
        net, loss_fn, mx.optimizer.create("adamw", learning_rate=1e-3),
        mesh=_one_device())
    (ids,), (labels,) = batch(np.random.default_rng(0), 2)
    text = step.lower(ids, labels).as_text(debug_info=True)
    paths = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    # a while whose condition compares two carried values, not one against
    # a constant: the trip count is read on the device
    # (the interpreted token kernels' own loops, outside the step's scopes,
    # aside)
    dynamic = [paths[loc] for loc in re.findall(
        r'stablehlo\.while\([^\n]*\n\s*cond \{\n\s*%\d+ = stablehlo\.compare'
        r'\s+LT, %iterArg\w*, %iterArg\w*,[^\n]*loc\((#loc\d+)\)', text)
        if paths[loc].startswith("jit(")]
    rows, d = ids.size * TOY["num_experts_per_tok"], TOY["hidden_size"]
    assert rows == 384 and f"tensor<{rows}x{d}xbf16>" in text
    for i in range(len(CUT)):
        found = [p for p in dynamic if "transpose(jvp(forward))" in p
                 and f"/layer{i}/moe/combine/while/cond" in p]
        assert len(found) == 1, (i, found)
    assert len(dynamic) == len(CUT)
    # every layer calls the same lowerings of the kernels: the forward one
    # as traced in the forward pass and as traced again for the recomputed
    # one, and the backward one
    for kernel in ("moe_gated_fwd", "moe_gated_bwd"):
        assert any(kernel in p for p in paths.values()), kernel
    assert len(re.findall(r"func\.func private @_call\w*\(", text)) == 3
    assert len(re.findall(r" call @_call\w*\(", text)) == 3 * len(CUT)
    assert not re.search(rf"stablehlo\.select [^\n]*tensor<{rows}x{d}xbf16>",
                         text)
    # each call of the token kernels' jit, by the kernel its body runs and
    # the scope it is called from
    bodies = dict(re.findall(r"func\.func private @(_reduce\w*)\((.*?)\n  \}",
                             text, re.S))
    kernels = {f: {paths[loc].split("/")[0] for loc in re.findall(
        r"loc\((#loc\d+)\)", body) if paths.get(loc, "").startswith(
        "moe_token_")} for f, body in bodies.items()}
    assert all(len(k) == 1 for k in kernels.values()), kernels
    sites = sorted(
        (min(kernels[f]), "transpose(" in paths[loc],
         paths[loc].rsplit("/moe/", 1)[1])
        for f, loc in re.findall(r" call @(_reduce\w*)\(.*loc\((#loc\d+)\)",
                                 text))
    assert sites == sorted(len(CUT) * [
        ("moe_token_sum", False, "combine/jit(_reduce)"),
        ("moe_token_sum", True, "dispatch/jit(_reduce)"),
        ("moe_token_dot", True, "combine/jit(_reduce)")]), sites
    n, k = ids.size, TOY["num_experts_per_tok"]
    assert not re.search(rf"tensor<{n}x{k}x{d}x(bf16|f32)>", text)


def test_the_load_reads_back_after_a_step(stepped):
    net, step, ids, labels = stepped
    before = parallel.publish_load(net)
    assert before == {"moe.load_max_over_mean": 0.0, "moe.held_share": 0.0,
                      "moe.row_pass_share": 0.0,
                      "moe.product_tile_share": 0.0}
    loss = float(step(ids, labels).asnumpy())
    assert np.isfinite(loss)
    step.sync_params_to_net()
    loads = [np.asarray(l.moe.load.data()._data) for l in net.layers]
    for load in loads:
        assert load.dtype == np.int32 and load.shape == (8,)
        assert load.sum() == ids.size * 3       # every assignment, none lost
    got = parallel.publish_load(net)
    held = sum(l[2:6].sum() for l in loads) / sum(l.sum() for l in loads)
    assert got["moe.held_share"] == pytest.approx(held)
    assert got["moe.load_max_over_mean"] == pytest.approx(
        max(l.max() / l.mean() for l in loads))
    assert 0.2 < got["moe.held_share"] < 0.8
    assert got["moe.load_max_over_mean"] >= 1.0
    gauges = telemetry.registry().snapshot()["gauges"]
    assert gauges["moe.held_share"] == got["moe.held_share"]
    assert gauges["moe.load_max_over_mean"] == got["moe.load_max_over_mean"]
    # 384 sorted rows a layer are one chunk of the bounded passes: every
    # layer holds a row, so every row was visited
    assert got["moe.row_pass_share"] == gauges["moe.row_pass_share"] == 1.0
    # ... and one row tile of the products, which every held expert that
    # holds a row visits: a share of one visit for each
    visits = sum(int((l[2:6] > 0).sum()) for l in loads) / len(loads)
    assert got["moe.product_tile_share"] == pytest.approx(visits)
    assert gauges["moe.product_tile_share"] == got["moe.product_tile_share"]
    # every trained leaf moved, the router among them
    names = [n for n, p in zip(step._names, step._plist)
             if p.grad_req != "null"]
    assert any(n.endswith("router") for n in names)
    assert sum("load" in n for n in step._names) == 4


@pytest.mark.parametrize("rows,loads,want", [
    (32, [[40, 24, 0, 64], [0, 0, 0, 128]], (64 + 0) / 256),
    (32, [[33, 0, 1, 94], [128, 0, 0, 0]], (64 + 128) / 256),
    (48, [[33, 0, 1, 94], [50, 50, 0, 28]], (48 + 128) / 256),
    (128, [[1, 0, 0, 127], [0, 0, 0, 128]], (128 + 0) / 256)],
    ids=["whole_chunks", "one_row_over", "a_chunk_that_does_not_divide",
         "the_rule_at_this_size"])
def test_row_pass_share_counts_whole_chunks(rows, loads, want, monkeypatch):
    """``moe.row_pass_share`` from the loads alone: each layer's held rows
    (experts 0 and 1 of 4) rounded up to whole chunks, never past the
    buffer; a layer with no held row is not visited at all."""
    monkeypatch.setattr(moe, "_row_chunk", lambda m: min(m, rows))
    net = gluon.nn.HybridSequential()
    for load in loads:
        block = parallel.DroplessMoEFFN(8, 4, 4, 2, held=2)
        block.initialize()
        block.load.set_data(NDArray(jnp.asarray(load, jnp.int32)))
        net.add(block)
    got = parallel.publish_load(net)
    assert got["moe.row_pass_share"] == pytest.approx(want)
    held = sum(sum(l[:2]) for l in loads) / 256
    assert got["moe.held_share"] == pytest.approx(held)
    assert held <= want


@pytest.mark.parametrize("rows,loads,want", [
    (32, [[40, 24, 0, 64], [0, 0, 0, 128]], (2 + 1 + 0) / 8),
    (32, [[33, 0, 1, 94], [128, 0, 0, 0]], (2 + 4) / 8),
    (48, [[33, 0, 1, 94], [50, 50, 0, 28]], (1 + 2 + 2) / 6)],
    ids=["a_shared_tile", "an_empty_expert", "a_tile_that_does_not_divide"])
def test_product_tile_share_counts_tile_visits(rows, loads, want,
                                               monkeypatch):
    """``moe.product_tile_share`` from the loads alone: each layer's held
    experts (0 and 1 of 4) visit the row tiles their rows touch, a tile two
    of them share once for each, an empty one none; over every layer's
    tiles of its N x k buffer."""
    from mxnet_tpu.ops.pallas import grouped_matmul
    monkeypatch.setattr(grouped_matmul, "_ROW_TILE", rows)
    net = gluon.nn.HybridSequential()
    for load in loads:
        block = parallel.DroplessMoEFFN(8, 4, 4, 2, held=2)
        block.initialize()
        block.load.set_data(NDArray(jnp.asarray(load, jnp.int32)))
        net.add(block)
    got = parallel.publish_load(net)
    assert got["moe.product_tile_share"] == pytest.approx(want)
