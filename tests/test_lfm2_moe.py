"""LFM2-8B-A1B on the one expert decoder (``gluon/model_zoo/moe_decoder.py``:
``conv`` layers, dense and sparse feed-forwards, QK-norm, a tied head), the
sigmoid router that selects by a bias (``parallel/moe.py``) and their
benchmark family against the plain reference kept with the benchmark
(``chipbench/reference/lfm2_moe.py``): float32, small widths, seeded
weights.  The cases that are mellum's over a second configuration (the
family through the benchmark, the real ``BENCHMARK.json``, the batch) are
parametrised in ``test_moe_decoder.py``, which also holds this toy."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import parallel, telemetry
from mxnet_tpu.gluon.model_zoo import moe_decoder
from mxnet_tpu.gluon.model_zoo._attention import _Attention
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.ops.rotary import rope_frequencies

from mxnet_tpu.parallel import moe

from test_moe_decoder import (LFM2_CONFIG as CONFIG, LFM2_TOY as TOY, ROOT,
                              _batch, _forward_kernel_calls, _loss_and_grads,
                              _one_device, _worst, ragged_grouped_matmul)

from chipbench import manifest                                  # noqa: E402
from chipbench.families import lfm2_moe as family               # noqa: E402
from chipbench.reference import lfm2_moe as reference           # noqa: E402

CUT, CUT_FF = TOY["layers"], TOY["mlp_layers"]
# the toy's widths as the reference takes them
REF = dict(heads=4, kv_heads=2, head_dim=16, eps=1e-5, k=3, first_expert=2,
           rope_theta=1e6)
VOCAB, T = TOY["vocab_size"], TOY["sequence_length"]


def _net(model=TOY, seed=3, recompute=False):
    mx.random.seed(seed)
    net = family.make_net(model)
    if recompute:
        for layer in net.layers:
            layer.recompute()
    net.initialize()
    return net


def _reference_of(net, layers, mlp_layers, ids, labels):
    """The reference's loss and gradients at ``net``'s weights, the
    gradients under the net's names."""
    params, buffers = reference.params_from_net(net, 4, 2, 16)
    loss, grads = reference.loss_and_grads(
        params, buffers, layers, mlp_layers, jnp.asarray(ids),
        jnp.asarray(labels), **REF)
    return float(loss), reference.grads_to_net(grads)


# ------------------------------------------------------ net and reference --
@pytest.fixture(scope="module")
def cut():
    """The cut's net, one batch, and both sides' loss and gradients."""
    net = _net()
    ids, labels = _batch()
    got = _loss_and_grads(net, ids, labels)
    want = _reference_of(net, CUT, CUT_FF, ids, labels)
    return net, ids, got, want


def test_logits_match_the_reference(cut):
    net, ids, _, _ = cut
    got = net(mx.nd.array(ids, dtype="int32"))
    assert got.shape == (2, T, VOCAB) and got.dtype == np.float32
    params, buffers = reference.params_from_net(net, 4, 2, 16)
    want = reference.forward(params, buffers, CUT, CUT_FF, jnp.asarray(ids),
                             **REF)
    # both sides float32; they differ in the order of sums and in the flash
    # kernel's online softmax: logits of up to ~20 (the head is the
    # embedding, drawn at 0.5 here) agree to six digits
    assert float(jnp.abs(want).max()) > 5.0
    np.testing.assert_allclose(got.asnumpy(), np.asarray(want), atol=2e-5)


def test_loss_matches_the_reference(cut):
    _, _, (got, _), (want, _) = cut
    assert abs(got - want) < 1e-5 and got > 3.0


def test_every_gradient_leaf_matches_the_reference(cut):
    net, _, (_, got), (_, want) = cut
    # conv + dense: in_proj, taps, out_proj, two norms, three matrices (8);
    # full + sparse: qkv, two head norms, out_proj, two norms, router,
    # gate_up, down (9); conv + sparse (8) x 3; the embedding (once: it is
    # the head too) and the last norm.  No expert_bias among them
    assert len(want) == len(got) == 8 + 9 + 3 * 8 + 2
    assert not any("expert_bias" in n or "head_weight" in n for n in got)
    assert all(float(jnp.abs(g).max()) > 0 for g in want.values())
    # a leaf's largest error over its largest entry; float32 sums in another
    # order on the two sides
    assert _worst(got, want) < 2e-5


@pytest.mark.parametrize("kind,ff", [("conv", "dense"), ("conv", "sparse"),
                                     ("full", "sparse")])
def test_each_kind_of_layer_alone(kind, ff):
    net = _net(dict(TOY, layers=[kind], mlp_layers=[ff]), seed=5)
    ids, labels = _batch(1)
    loss, grads = _loss_and_grads(net, ids, labels)
    want, want_grads = _reference_of(net, [kind], [ff], ids, labels)
    assert abs(loss - want) < 1e-5
    assert _worst(grads, want_grads) < 2e-5


def test_recomputed_layers_give_equal_gradients(cut):
    _, ids, (loss, grads), _ = cut
    _, labels = _batch()
    again, marked, text = _loss_and_grads(_net(recompute=True), ids, labels,
                                          program=True)
    assert again == loss
    assert _worst(marked, grads) < 1e-6
    # the one attention layer keeps its kernel's results: one forward call
    assert _forward_kernel_calls(text) == CUT.count("full") == 1


@pytest.mark.parametrize("rows", [None, 100], ids=["one_chunk",
                                                   "chunks_of_100"])
def test_a_recomputed_layer_with_garbage_past_the_groups_sum(rows,
                                                             monkeypatch):
    """A recomputed conv + sparse layer (sigmoid scores chosen by score +
    bias, 1,536 sorted rows) through the grouped-product kernels, which
    leave NaN in every row past their groups' sum in the interpreter (stale
    memory on the chip): the loss and every gradient are finite and within
    rounding of the same layer over ``ragged_dot``."""
    if rows:
        monkeypatch.setattr(moe, "_row_chunk", lambda m: min(m, rows))
    model = dict(TOY, layers=["conv"], mlp_layers=["sparse"])
    ids, labels = _batch(2)
    again, poisoned = _loss_and_grads(_net(model, seed=7, recompute=True),
                                      ids, labels)
    monkeypatch.setattr(moe.grouped_matmul, "grouped_matmul",
                        ragged_grouped_matmul)
    loss, grads = _loss_and_grads(_net(model, seed=7, recompute=True), ids,
                                  labels)
    assert np.isfinite(again) and again == pytest.approx(loss, rel=1e-6)
    assert set(poisoned) == set(grads)
    for name, grad in grads.items():
        assert np.isfinite(np.asarray(poisoned[name])).all(), name
    assert _worst(poisoned, grads) < 1e-5
    experts = [g for name, g in grads.items() if name.endswith("gate_up")]
    assert len(experts) == 1 and float(jnp.abs(experts[0]).max()) > 0


def test_unknown_kinds_lists_and_tables_raise():
    with pytest.raises(ValueError, match="kind"):
        _net(dict(TOY, layers=["conv", "mamba"], mlp_layers=["dense"] * 2))
    with pytest.raises(ValueError, match="mlp_layers"):
        _net(dict(TOY, mlp_layers=["dense"]))
    with pytest.raises(ValueError, match="mlp_layers"):
        _net(dict(TOY, mlp_layers=["dense"] * 4 + ["shared"]))
    widths = dict(hidden_size=32, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  moe_intermediate_size=24, num_experts=8,
                  num_experts_per_tok=3)
    with pytest.raises(ValueError, match="intermediate_size"):
        moe_decoder.MoEDecoder(50, ["conv"], mlp_layers=["dense"], **widths)
    with pytest.raises(ValueError, match="rotate"):
        moe_decoder.MoEDecoder(
            50, ["conv"], rope_parameters={"conv": {"rope_theta": 1e6}},
            **widths)
    with pytest.raises(ValueError, match="sigmoid"):
        parallel.DroplessMoEFFN(8, 8, num_experts=8, k=2, score="tanh")
    with pytest.raises(ValueError, match="sigmoid"):
        parallel.route_topk(jnp.zeros((2, 8)), 2, score="tanh")


# --------------------------------------------------- the convolution operator --
def test_short_conv_against_a_hand_written_loop():
    """``W_out (C * conv(B * v))``, position by position in numpy: positions
    0 and 1 read zeros where the sequence has not started."""
    mx.random.seed(7)
    block = moe_decoder._ShortConv(8, 3)
    block.initialize()
    rng = np.random.RandomState(0)
    for p in block.collect_params().values():       # weights of order 1
        p.set_data(mx.nd.array(rng.randn(*p.shape) * 0.5))
    u = rng.randn(2, 6, 8).astype(np.float32)
    w_in = block.in_proj.weight.data().asnumpy()
    w = block.conv_weight.data().asnumpy()
    w_out = block.out_proj.weight.data().asnumpy()
    assert w_in.shape == (24, 8) and w.shape == (3, 8)
    assert sorted(block._collect_params_with_prefix()) == [
        "conv_weight", "in_proj.weight", "out_proj.weight"]      # no bias
    proj = u @ w_in.T
    gate_in, gate_out, value = proj[..., :8], proj[..., 8:16], proj[..., 16:]
    want = np.zeros_like(u)
    for b in range(2):
        for t in range(6):
            z = np.zeros(8, np.float32)
            for j in range(3):
                at = t - 2 + j
                if at >= 0:
                    z += w[j] * (gate_in[b, at] * value[b, at])
            want[b, t] = (gate_out[b, t] * z) @ w_out.T
    got = block(mx.nd.array(u)).asnumpy()
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # position 0 sees its own tap alone, position 1 two of the three
    np.testing.assert_allclose(
        got[:, 0], (gate_out[:, 0] * w[2] * gate_in[:, 0] * value[:, 0])
        @ w_out.T, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(reference.short_conv(
        jnp.asarray(gate_in * value), jnp.asarray(w))[:, 1],
        w[2] * (gate_in * value)[:, 1] + w[1] * (gate_in * value)[:, 0],
        rtol=1e-5, atol=1e-7)
    # causal: a change at position 4 moves nothing before it
    moved = u.copy()
    moved[:, 4] += 1.0
    again = block(mx.nd.array(moved)).asnumpy()
    assert np.array_equal(again[:, :4], got[:, :4])
    assert np.abs(again[:, 4:7] - got[:, 4:7]).min(axis=-1).max() > 1e-3


# ------------------------------------------------------------- the router --
E, K = 32, 4


def _route_topk_before_this_pr(logits, k, renormalise=True):
    """``route_topk`` as PR 31 wrote it."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    if renormalise:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32)


@pytest.mark.parametrize("renormalise", [True, False])
def test_softmax_defaults_reproduce_route_topk_bit_for_bit(renormalise):
    logits = jnp.asarray(np.random.RandomState(6).randn(40, E), jnp.bfloat16)
    got = parallel.route_topk(logits, K, renormalise)
    want = _route_topk_before_this_pr(logits, K, renormalise)
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])
    text = [str(jax.make_jaxpr(lambda l: f(l, K, renormalise))(logits))
            for f in (parallel.route_topk, _route_topk_before_this_pr)]
    assert text[0] == text[1]


def test_the_bias_selects_and_does_not_weigh():
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(64, E), jnp.float32)
    bias = jnp.asarray(rng.randn(E) * 0.05, jnp.float32)
    s = np.asarray(jax.nn.sigmoid(logits))
    plain_gates, plain = parallel.route_topk(logits, K, score="sigmoid",
                                             eps=1e-6)
    gates, chosen = parallel.route_topk(logits, K, score="sigmoid", bias=bias,
                                        eps=1e-6)
    # WHICH experts: the four largest of score + bias, by hand
    by_hand = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :K]
    assert np.array_equal(np.sort(by_hand, -1), np.sort(chosen, -1))
    differ = (np.sort(plain, -1) != np.sort(chosen, -1)).any(-1)
    assert 0.2 < differ.mean() < 0.8         # the bias moved some choices
    # their gates: the UNBIASED scores over (their sum + 1e-6)
    picked = np.take_along_axis(s, np.asarray(chosen), -1)
    np.testing.assert_allclose(
        gates, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # a token whose choice the bias left alone keeps its gates (the chosen
    # may come in another order, so their sum is rounded another way)
    same = ~differ
    assert same.any()
    np.testing.assert_allclose(np.sort(gates, -1)[same],
                               np.sort(plain_gates, -1)[same], rtol=1e-6)
    # zeros select like no bias at all
    zero = parallel.route_topk(logits, K, score="sigmoid",
                               bias=jnp.zeros(E), eps=1e-6)
    assert np.array_equal(zero[1], plain)
    assert np.array_equal(zero[0], plain_gates)
    # the reference says the same
    want, want_chosen = reference.route(logits, jnp.eye(E), bias, K)
    assert np.array_equal(np.sort(want_chosen, -1), np.sort(chosen, -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(want), np.asarray(chosen), -1), gates,
        rtol=1e-6)


def test_the_gates_divide_by_their_sum_plus_eps_and_scale():
    logits = jnp.asarray(np.random.RandomState(2).randn(16, E), jnp.float32)
    s = np.sort(np.asarray(jax.nn.sigmoid(logits)), -1)[:, ::-1][:, :K]
    gates, _ = parallel.route_topk(logits, K, score="sigmoid", eps=0.5)
    np.testing.assert_allclose(gates, s / (s.sum(-1, keepdims=True) + 0.5),
                               rtol=1e-6)
    assert float(jnp.abs(gates.sum(-1) - 1.0).min()) > 0.05
    scaled, _ = parallel.route_topk(logits, K, score="sigmoid", eps=0.5,
                                    scale=2.5)
    np.testing.assert_allclose(scaled, 2.5 * np.asarray(gates), rtol=1e-6)
    raw, _ = parallel.route_topk(logits, K, renormalise=False,
                                 score="sigmoid", eps=0.5)
    np.testing.assert_allclose(raw, s, rtol=1e-6)


def _expert_weights(seed=0, d=24, f=12):
    rng = np.random.RandomState(seed)
    g = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)     # noqa: E731
    return g(d, E), g(E) * 0.3, g(E, d, 2 * f) * 0.3, g(E, f, d) * 0.3


def _share(tokens, router, bias, gate_up, down, first, held=8):
    return get_op("moe_dropless_ffn")(
        tokens, router, gate_up[first:first + held],
        down[first:first + held], bias, num_experts=E, first_expert=first,
        k=K, score="sigmoid", eps=1e-6)


def _reference_share(tokens, router, bias, gate_up, down, first, held=8):
    f = down.shape[1]
    with jax.default_matmul_precision("highest"):
        return reference.moe(
            tokens, router, bias, gate_up[first:first + held, :, :f],
            gate_up[first:first + held, :, f:], down[first:first + held], K,
            first)


def test_no_gradient_reaches_the_bias():
    router, bias, gate_up, down = _expert_weights(3)
    tokens = jnp.asarray(np.random.RandomState(4).randn(48, 24), jnp.float32)

    def total(router, bias):
        return (_share(tokens, router, bias, gate_up, down, 0)[0] ** 2).sum()
    d_router, d_bias = jax.grad(total, (0, 1))(router, bias)
    assert float(jnp.abs(d_router).max()) > 0
    assert np.array_equal(d_bias, np.zeros(E, np.float32))
    want = jax.grad(lambda r: (_reference_share(
        tokens, r, bias, gate_up, down, 0) ** 2).sum())(router)
    np.testing.assert_allclose(d_router, want, atol=2e-5 * float(
        jnp.abs(want).max()))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """32 experts, top-4 of sigmoid score + bias, four chips of 8: the parts
    the shares compute sum to what the uncut reference gives for the whole
    layer, and every share counts the same assignments."""
    router, bias, gate_up, down = _expert_weights()
    tokens = jnp.asarray(np.random.RandomState(1).randn(96, 24), jnp.float32)
    whole = _reference_share(tokens, router, bias, gate_up, down, 0, held=E)
    parts, loads = zip(*[_share(tokens, router, bias, gate_up, down, first)
                         for first in (0, 8, 16, 24)])
    for first, part in zip((0, 8, 16, 24), parts):
        np.testing.assert_allclose(
            part, _reference_share(tokens, router, bias, gate_up, down,
                                   first), rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(whole).max()) > 0.1
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=5e-6)
    assert all(np.array_equal(l, loads[0]) for l in loads)
    assert int(loads[0].sum()) == 96 * K and loads[0].dtype == jnp.int32
    # the bias is in the choice: without it other experts are loaded
    unbiased = _share(tokens, router, jnp.zeros(E), gate_up, down, 0)[1]
    assert not np.array_equal(unbiased, loads[0])


def test_block_keeps_its_bias_out_of_training():
    mx.random.seed(2)
    block = parallel.DroplessMoEFFN(24, 12, num_experts=E, k=K, held=8,
                                    first_expert=8, score="sigmoid",
                                    selection_bias=True, norm_eps=1e-6)
    block.expert_bias.init = family._Drawn(0.3)
    block.initialize()
    assert block.expert_bias.shape == (E,)
    assert block.expert_bias.grad_req == "null"
    bias = block.expert_bias.data()._data
    assert float(jnp.abs(bias).max()) > 0.1 and bias.dtype == jnp.float32
    x = mx.nd.array(np.random.RandomState(0).randn(2, 20, 24))
    out, load = block(x)
    gate_up, down = block.gate_up.data()._data, block.down.data()._data
    want = reference.moe(x._data, block.router.data()._data, bias,
                         gate_up[..., :12], gate_up[..., 12:], down, K, 8)
    np.testing.assert_allclose(out.asnumpy(), want, atol=2e-6)
    assert int(load.asnumpy().sum()) == 40 * K
    block.cast("bfloat16")
    assert block.expert_bias.data().dtype == np.float32
    assert block.load.data().dtype == np.int32
    assert block.router.data().dtype == jnp.bfloat16
    # the block of today, by default: no bias parameter at all
    plain = parallel.DroplessMoEFFN(24, 12, num_experts=E, k=K)
    assert sorted(plain._reg_params) == ["down", "gate_up", "load", "router"]


# --------------------------------------------------------------- QK-norm --
def _attention_by_hand(block, u, qk_norm):
    """The attention block's result from the reference's pieces."""
    w = block.qkv.weight.data()._data
    q, k, v = (u @ w[:64].T).reshape(2, -1, 4, 16), \
        (u @ w[64:96].T).reshape(2, -1, 2, 16), \
        (u @ w[96:].T).reshape(2, -1, 2, 16)
    if qk_norm:
        q = reference._rms_norm(q, block.q_norm.gamma.data()._data, 1e-5)
        k = reference._rms_norm(k, block.k_norm.gamma.data()._data, 1e-5)
    out = reference._attention(reference._rotate(q, 1e6),
                               reference._rotate(k, 1e6), v)
    return out @ block.out_proj.weight.data()._data.T


@pytest.mark.parametrize("qk_norm", [None, 1e-5], ids=["off", "on"])
def test_qk_norm_on_against_the_reference_and_off_as_today(qk_norm):
    mx.random.seed(4)
    block = _Attention(32, 4, 2, None, head_dim=16,
                       rope=rope_frequencies({"rope_theta": 1e6}, 16),
                       qk_norm=qk_norm)
    block.initialize()
    names = sorted(block._collect_params_with_prefix())
    if qk_norm is None:
        assert names == ["out_proj.weight", "qkv.weight"]       # today's
    else:
        assert names == ["k_norm.gamma", "out_proj.weight", "q_norm.gamma",
                         "qkv.weight"]
        # a gain that is not 1, so that the test sees it
        block.q_norm.gamma.set_data(mx.nd.array(np.linspace(0.5, 1.5, 16)))
        block.k_norm.gamma.set_data(mx.nd.array(np.linspace(1.4, 0.6, 16)))
    u = jnp.asarray(np.random.RandomState(0).randn(2, 128, 32), jnp.float32)
    got, k, v = block(mx.nd.array(u))
    with jax.default_matmul_precision("highest"):
        want = _attention_by_hand(block, u, qk_norm is not None)
    np.testing.assert_allclose(got.asnumpy(), want, atol=2e-6)
    if qk_norm is not None:
        # the norm is over a head's 16 and not over the row's 32: the
        # handed-on K has unit mean square a head before the gain
        per_head = k.asnumpy().reshape(2, 128, 2, 16) \
            / np.linspace(1.4, 0.6, 16)
        # (rotation keeps a pair's square sum only where both gains agree,
        # so test position 0, which is not rotated)
        np.testing.assert_allclose((per_head[:, 0] ** 2).mean(-1), 1.0,
                                   rtol=1e-3)


# --------------------------------------------------------- the tied head --
def test_tied_head_has_no_matrix_and_its_gradient_sums_both_uses():
    tied = _net(dict(TOY, layers=["conv"], mlp_layers=["dense"]), seed=8)
    assert "head_weight" not in tied._collect_params_with_prefix()
    mx.random.seed(8)
    untied = moe_decoder.MoEDecoder(
        VOCAB, ["conv"], mlp_layers=["dense"], hidden_size=32,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=48, moe_intermediate_size=24, num_experts=8,
        num_experts_per_tok=3, rms_norm_eps=1e-5)
    untied.initialize()
    ours = tied._collect_params_with_prefix()
    for name, p in untied._collect_params_with_prefix().items():
        p.set_data(ours["embed.weight" if name == "head_weight"
                        else name].data())
    # ids from the lower half of the vocabulary: the upper rows are read by
    # the head alone
    ids, labels = _batch(3)
    ids, labels = ids % (VOCAB // 2), labels % (VOCAB // 2)
    loss, grads = _loss_and_grads(tied, ids, labels)
    loss2, parts = _loss_and_grads(untied, ids, labels)
    assert abs(loss - loss2) < 1e-6
    looked_up, read_off = parts["embed.weight"], parts["head_weight"]
    assert float(jnp.abs(looked_up[VOCAB // 2:]).max()) == 0.0
    assert float(jnp.abs(read_off[VOCAB // 2:]).max()) > 0
    np.testing.assert_allclose(grads["embed.weight"], looked_up + read_off,
                               atol=1e-7)


# --------------------------------------------- the configuration, the count --
def _trained_parameters(**changes):
    net = family.make_net(dict(CONFIG["model"], **changes))
    return sum(int(np.prod(p.shape)) for p in net.collect_params().values()
               if p.grad_req != "null")


def test_the_published_config_counts_8_3b_and_the_cut_508m():
    operator = 2048 * 3 * 2048 + 3 * 2048 + 2048 * 2048
    attention = 2048 * (2048 + 2 * 512) + 2 * 64 + 2048 * 2048
    expert, dense, router = 3 * 2048 * 1792, 3 * 2048 * 7168, 2048 * 32
    assert (operator, attention, expert, dense) == (
        16_783_360, 10_485_888, 11_010_048, 44_040_192)
    layers, mlp_layers = family.layer_lists(CONFIG)
    assert (layers.count("conv"), layers.count("full")) == (18, 6)
    assert (mlp_layers.count("dense"), mlp_layers.count("sparse")) == (2, 22)
    published = _trained_parameters(
        layers=layers, mlp_layers=mlp_layers, num_experts=32,
        vocab_size=CONFIG["vocab_size"])
    assert published == 18 * operator + 6 * attention + 2 * dense \
        + 22 * (32 * expert + router) + 24 * 2 * 2048 + 65536 * 2048 + 2048 \
        == 8_339_929_856
    assert published - 22 * 28 * expert == 1_557_740_288      # active a token
    # an untied head would read 8.47B, not the published "8.3B"
    assert published + 65536 * 2048 == 8_474_147_584
    dense_conv = operator + dense + 4096
    sparse_full = attention + 8 * expert + router + 4096
    sparse_conv = operator + 8 * expert + router + 4096
    assert (dense_conv, sparse_full, sparse_conv) == (
        60_827_648, 98_635_904, 104_933_376)
    assert _trained_parameters() == dense_conv + sparse_full \
        + 3 * sparse_conv + 16384 * 2048 + 2048 == 507_820_160


def test_configuration_keeps_every_published_width():
    """The catalog's ``config`` verbatim at the top level; ``model`` repeats
    the widths it needs and changes depth, the experts held and the
    vocabulary alone."""
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536,
        "layer_types": (["conv", "conv", "full_attention", "conv"] * 5
                        + ["conv", "full_attention", "conv", "conv"])}
    assert {k: CONFIG[k] for k in catalog} == catalog
    assert len(CONFIG["layer_types"]) == 24
    model = CONFIG["model"]
    for key in set(model) & set(catalog) - {"vocab_size", "num_experts"}:
        assert model[key] == catalog[key], key
    assert model["head_dim"] * catalog["num_attention_heads"] \
        == catalog["hidden_size"]
    # published layers 1 .. 5: one leading dense layer and one whole period
    assert (model["layers"], model["mlp_layers"]) \
        == family.layer_lists(CONFIG, first=1, count=5) \
        == (["conv", "full", "conv", "conv", "conv"],
            ["dense", "sparse", "sparse", "sparse", "sparse"])
    assert CONFIG["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert model["routed_experts"] == catalog["num_experts"]
    assert model["num_experts"] * 4 == catalog["num_experts"]
    assert model["vocab_size"] * 4 == catalog["vocab_size"]
    assert model["first_expert"] == 0 and model["sequence_length"] == 8192
    assert set(CONFIG["assumed"]) >= {
        "tie_embedding", "expert_bias", "chunk_order", "norm_topk_eps",
        "initialisation", "load_balancing_loss", "learning_rate"}
    assert CONFIG["published"]["num_experts"] == 32
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert any("recomputed" in d for d in CONFIG["departures"])
    assert CONFIG["check"]["loss_atol"] > 0 and CONFIG["check"]["why"]
    entry = next(c for c in manifest.load(ROOT)["configs"]
                 if c["name"] == "lfm2_8b_a1b")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "chipbench/configs/lfm2_8b_a1b.json"
    assert "model_type lfm2_moe" in entry["source"]
    assert not any(manifest.WIDTH.search(k) for k in entry["reduced"])


def test_train_flops_against_a_hand_count():
    """The ISSUE's arithmetic, a sequence of 8,192: four operators'
    projections, one attention layer, the dense feed-forward, the 1 of 8 held
    experts a token meets under even routing in four layers, the routers, the
    tied head once over the slice."""
    model = CONFIG["model"]
    t = model["sequence_length"]
    token = {"operators": 4 * 2 * 16_777_216,
             "dense": 2 * 44_040_192,
             "experts": 4 * 2 * 11_010_048,
             "routers": 4 * 2 * 65_536,
             "head": 2 * 16384 * 2048,
             "attention": 2 * 10_485_760}
    pairs = t * (t + 1) // 2
    assert pairs == 33_558_528
    products = 2 * 2 * 2048 * pairs
    forward = t * sum(token.values()) + products
    assert family.train_flops(model) == 3 * forward
    # MFLOP a token (ISSUE 33 wrote 432.6)
    assert round(forward / t / 1e6, 1) == 432.5
    assert round(family.train_flops(model) / 1e12, 2) == 10.63
    share = {k: t * v / forward for k, v in token.items()}
    share["attention"] += products / forward
    assert [round(100 * share[k], 1) for k in (
        "operators", "dense", "experts", "head", "attention")] \
        == [31.0, 20.4, 20.4, 15.5, 12.6]
    # the held experts as a deployed chip fed by four sees them
    experts = t * token["experts"]
    assert 0.50 < 4 * experts / (forward + 3 * experts) < 0.52
    # the grouped products are mellum's count at this family's widths
    rows = 4 * t * 4 // 4               # batch 4, even routing: a quarter
    assert family.grouped_product_flops(model, rows) == 2 * rows * 11_010_048
    assert family.grouped_product_bytes(model, 0, 8) == 2 * 8 * 11_010_048


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
    for i, (kind, ff) in enumerate(zip(CUT, CUT_FF)):
        if kind == "conv":
            scopes += [f"layer{i}/short_conv", f"layer{i}/short_conv/conv"]
        else:
            scopes += [f"layer{i}/attention", f"layer{i}/attention/qk_norm",
                       f"layer{i}/attention/rope"]
        if ff == "dense":
            scopes += [f"layer{i}/mlp"]
        else:
            scopes += [f"layer{i}/moe"] + [
                f"layer{i}/moe/{part}"
                for part in ("router", "dispatch", "experts", "combine")]
    assert len(scopes) == 2 + 3 + 3 + 3 * 2 + 4 * 5
    for scope in scopes:
        forward = [p for p in paths
                   if f"/{scope}/" in p and "jvp(forward)" in p]
        assert forward, scope
        if not scope.endswith("/dispatch"):
            assert any("transpose(jvp(forward))" in p for p in forward), scope
    assert "layer0/moe" not in text and "layer1/mlp" not in text
    assert "rematted_computation" in text
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dqkv"):
        assert kernel in text
    assert not re.search(r"flash_attention_bwd_(dq|dkv)\b", text)
    ops = "\n".join(l for l in text.splitlines() if not l.startswith("#loc"))
    assert "window_attention" not in text and "ragged_dot" not in ops
    for kernel in ("moe_grouped_fwd", "moe_grouped_dx", "moe_grouped_dw"):
        assert kernel in text


def test_the_load_reads_back_after_a_step_with_a_dense_first_layer(stepped):
    net, step, ids, labels = stepped
    assert [layer.sparse for layer in net.layers] == [False] + [True] * 4
    before = parallel.publish_load(net)
    assert before == {"moe.load_max_over_mean": 0.0, "moe.held_share": 0.0,
                      "moe.row_pass_share": 0.0,
                      "moe.product_tile_share": 0.0}
    biases = [np.asarray(l.moe.expert_bias.data()._data)
              for l in net.layers[1:]]
    loss = float(step(ids, labels).asnumpy())
    assert np.isfinite(loss)
    step.sync_params_to_net()
    loads = [np.asarray(l.moe.load.data()._data) for l in net.layers[1:]]
    for load in loads:
        assert load.dtype == np.int32 and load.shape == (8,)
        assert load.sum() == ids.size * 3       # every assignment, none lost
    got = parallel.publish_load(net)
    held = sum(l[2:6].sum() for l in loads) / sum(l.sum() for l in loads)
    assert got["moe.held_share"] == pytest.approx(held)
    assert got["moe.load_max_over_mean"] == pytest.approx(
        max(l.max() / l.mean() for l in loads))
    assert 0.2 < got["moe.held_share"] < 0.8
    gauges = telemetry.registry().snapshot()["gauges"]
    assert gauges["moe.held_share"] == got["moe.held_share"]
    assert got["moe.row_pass_share"] == gauges["moe.row_pass_share"] == 1.0
    # ... and one row tile of the products, which every held expert that
    # holds a row visits: a share of one visit for each
    visits = sum(int((l[2:6] > 0).sum()) for l in loads) / len(loads)
    assert got["moe.product_tile_share"] == pytest.approx(visits)
    assert gauges["moe.product_tile_share"] == got["moe.product_tile_share"]
    # the step trains every leaf but the counts and the biases, which it
    # leaves as they were drawn, in float32 beside bf16 weights
    trained = [n for n, p in zip(step._names, step._plist)
               if p.grad_req != "null"]
    assert any(n.endswith("router") for n in trained)
    assert not any(n.endswith(("load", "expert_bias")) for n in trained)
    assert sum(n.endswith("load") for n in step._names) == 4
    assert sum(n.endswith("expert_bias") for n in step._names) == 4
    for layer, bias in zip(net.layers[1:], biases):
        after = layer.moe.expert_bias.data()._data
        assert after.dtype == jnp.float32 and np.array_equal(after, bias)
        assert float(np.abs(bias).max()) > 0
