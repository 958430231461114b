"""Kanana-2-30B-A3B on the one expert decoder (``gluon/model_zoo/
moe_decoder.py``: ``latent`` layers of multi-head latent attention, a leading
dense feed-forward, routed experts behind a sigmoid router that selects by a
bias and a shared expert beside them, an untied head) and its benchmark
family against the plain reference kept with the benchmark
(``chipbench/reference/deepseek_v3.py``): float32, small widths, seeded
weights.  The cases that are mellum's over a further configuration (the
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

from test_moe_decoder import (KANANA2_CONFIG as CONFIG, KANANA2_TOY as TOY,
                              ROOT, _forward_kernel_calls, _loss_and_grads,
                              _one_device, _worst)

from chipbench import manifest                                  # noqa: E402
from chipbench.families import deepseek_v3 as family            # noqa: E402
from chipbench.layer_readers import kernel_roofline_pct         # noqa: E402
from chipbench.reference import deepseek_v3 as reference        # noqa: E402

REF = dict(heads=4, nope=16, rope=8, eps=1e-6, k=3, first_expert=2,
           rope_theta=1e6, scale=2.448)
WIDTHS = (4, 16, 16, 16)             # heads, kv_rank, nope, v as the net fuses
VOCAB, T = TOY["vocab_size"], TOY["sequence_length"]


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


def _reference_of(net, mlp_layers, ids, labels):
    params, buffers = reference.params_from_net(net, WIDTHS)
    loss, grads = reference.loss_and_grads(
        params, buffers, mlp_layers, jnp.asarray(ids), jnp.asarray(labels),
        **REF)
    return float(loss), reference.grads_to_net(grads)


# ------------------------------------------------------ net and reference --
@pytest.fixture(scope="module")
def cut():
    """The toy cut's net, one batch, and both sides' loss and gradients."""
    net = _net()
    ids, labels = _batch()
    got = _loss_and_grads(net, ids, labels)
    want = _reference_of(net, TOY["mlp_layers"], ids, labels)
    return net, ids, got, want


def test_logits_match_the_reference(cut):
    net, ids, _, _ = cut
    got = net(mx.nd.array(ids, dtype="int32"))
    assert got.shape == (2, T, VOCAB) and got.dtype == np.float32
    params, buffers = reference.params_from_net(net, WIDTHS)
    want = reference.forward(params, buffers, TOY["mlp_layers"],
                             jnp.asarray(ids), **REF)
    # both sides float32; they differ in the order of sums, in the flash
    # kernel's online softmax and in the rotation's arithmetic: logits of
    # standard deviation ~0.2 agree to 1e-5 of their largest
    scale = float(jnp.abs(want).max())
    assert scale > 0.5
    np.testing.assert_allclose(got.asnumpy(), np.asarray(want),
                               atol=1e-5 * scale)


def test_loss_matches_the_reference(cut):
    _, _, (got, _), (want, _) = cut
    assert abs(got - want) < 1e-5 and got > 3.0


def test_every_gradient_leaf_matches_the_reference(cut):
    net, _, (_, got), (_, want) = cut
    # latent + dense: q, kv_a, kv_norm, kv_b, out_proj, two norms, three
    # matrices (10); latent + sparse: the five of attention, two norms,
    # router, gate_up, down and the shared expert's three (13) x 2; the
    # embedding, the head and the last norm.  No expert_bias among them
    assert len(want) == len(got) == 10 + 2 * 13 + 3
    assert not any("expert_bias" in n or n.endswith("load") for n in got)
    assert all(float(jnp.abs(g).max()) > 0 for g in want.values())
    # a leaf's largest error over its largest entry; float32 sums in another
    # order on the two sides
    assert _worst(got, want) < 2e-5


def test_recomputed_layers_give_equal_gradients(cut):
    _, ids, (loss, grads), _ = cut
    _, labels = _batch()
    again, marked, text = _loss_and_grads(_net(recompute=True), ids, labels,
                                          program=True)
    assert again == pytest.approx(loss, rel=1e-6)
    assert _worst(marked, grads) < 1e-6
    # the marked layers keep their attention kernels' output and
    # log-sum-exp: the forward kernel runs once a layer
    assert _forward_kernel_calls(text) == len(TOY["layers"])


def test_latent_layers_need_their_widths_and_the_router_its_own():
    with pytest.raises(ValueError, match="kv_lora_rank"):
        moe_decoder.MoEDecoder(
            50, ["latent"], hidden_size=32, num_attention_heads=4,
            num_key_value_heads=4, head_dim=8, moe_intermediate_size=16,
            num_experts=8, num_experts_per_tok=2, kv_lora_rank=8)
    for key, value in (("n_group", 8), ("topk_group", 4),
                       ("scoring_func", "softmax"), ("topk_method", "greedy")):
        with pytest.raises(ValueError, match="router"):
            family.make_net(dict(TOY, **{key: value}))


@pytest.mark.parametrize("value", [False, None])
def test_the_family_refuses_a_latent_layer_that_rotates_halves(value):
    """A latent layer turns pairs of neighbours alone: a configuration that
    says otherwise, or says nothing, is refused rather than run as this
    one."""
    model = dict(TOY, rope_interleave=value)
    if value is None:
        del model["rope_interleave"]
    with pytest.raises(ValueError, match="rope_interleave"):
        family.make_net(model)


# ------------------------------------------- the share of the expert layer --
def _layer_params(net):
    """The reference's names for the one-layer net's arrays, and its bias."""
    return reference.params_from_net(net, WIDTHS)


def _common(params, x):
    """What every chip of the deployment computes alike in the layer: the
    residual after attention plus the shared expert, by the reference."""
    at = "layer0."
    u = reference._rms_norm(x, params[at + "norm1.gamma"], 1e-6)
    a = x + reference.latent_attention(
        params, at, u, 4, 16, 8, 1e-6, 1e6) \
        @ params[at + "mixer.out_proj.weight"].T
    u = reference._rms_norm(a, params[at + "norm2.gamma"], 1e-6)
    shared = reference.gated(
        u, params[at + "shared_experts.gate.weight"].T,
        params[at + "shared_experts.up.weight"].T,
        params[at + "shared_experts.down.weight"].T)
    return a + shared, u


def test_the_four_shares_add_up_to_the_uncut_layer():
    """8 experts, top-3 of sigmoid score + bias, four chips of 2: the four
    shares' routed parts, with the attention, the residual and the shared
    expert counted once, add up to the uncut reference layer; each share's
    own part is the reference's for its experts."""
    model = dict(TOY, layers=["latent"], mlp_layers=["sparse"], num_experts=8,
                 first_expert=0)
    whole = _net(model, seed=9)
    rng = np.random.RandomState(4)
    for n, p in whole._collect_params_with_prefix().items():
        if not n.endswith(("load", "expert_bias")):     # weights of order 1
            p.set_data(mx.nd.array(rng.randn(*p.shape) * 0.3))
    arrays = {n: p.data() for n, p in
              whole._collect_params_with_prefix().items()}
    x = jnp.asarray(np.random.RandomState(2).randn(2, T, 64), jnp.float32)
    params, buffers = _layer_params(whole)
    with jax.default_matmul_precision("highest"):
        common, u = _common(params, x)
        routed = reference.moe(
            u, params["layer0.moe.router"], buffers["layer0.moe.expert_bias"],
            params["layer0.moe.w1"], params["layer0.moe.w3"],
            params["layer0.moe.w2"], 3, 0, 2.448)
        parts, loads = [], []
        for first in (0, 2, 4, 6):
            share = _net(dict(model, num_experts=2, first_expert=first))
            for n, p in share._collect_params_with_prefix().items():
                held = arrays[n]
                if n.endswith(("gate_up", "down")):
                    held = held[first:first + 2]
                p.set_data(held)
            out, load = share.layers[0](mx.nd.array(x))
            parts.append(out.asnumpy() - np.asarray(common))
            loads.append(load.asnumpy())
            want = reference.moe(
                u, params["layer0.moe.router"],
                buffers["layer0.moe.expert_bias"],
                params["layer0.moe.w1"][first:first + 2],
                params["layer0.moe.w3"][first:first + 2],
                params["layer0.moe.w2"][first:first + 2], 3, first, 2.448)
            np.testing.assert_allclose(parts[-1], want, atol=2e-5)
    assert float(jnp.abs(routed).max()) > 0.1
    np.testing.assert_allclose(sum(parts) + np.asarray(common),
                               np.asarray(common + routed), atol=5e-5)
    # every share routes every token alike
    assert all(np.array_equal(l, loads[0]) for l in loads)
    assert int(loads[0].sum()) == 2 * T * 3


# ----------------------------------------------- the program's own scopes --
@pytest.fixture(scope="module")
def stepped():
    net, loss_fn, batch = family.build(TOY)
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
    scopes = []
    for i, ff in enumerate(TOY["mlp_layers"]):
        scopes += [f"layer{i}/attention/latent", f"layer{i}/attention/rope"]
        scopes += [f"layer{i}/mlp"] if ff == "dense" else [
            f"layer{i}/moe", f"layer{i}/shared_experts"]
    for scope in scopes:
        forward = [p for p in paths
                   if f"/{scope}/" in p and "jvp(forward)" in p]
        assert forward, scope
        assert any("transpose(jvp(forward))" in p for p in forward), scope
    # the shared expert sits beside the routed experts, not inside them
    assert not any("/moe/shared_experts" in p for p in paths)
    assert "layer0/shared_experts" not in text
    assert "rematted_computation" in text
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dqkv"):
        assert kernel in text
    # one backward kernel, not the split pair
    assert not re.search(r"flash_attention_bwd_(dq|dkv)\b", text)
    # the operations, not the source locations (which name whatever traced
    # a shared jitted kernel first in this process)
    ops = "\n".join(l for l in text.splitlines() if not l.startswith("#loc"))
    assert "window_attention" not in ops


def test_a_recomputed_step_runs_the_attention_forward_once_a_layer():
    """One ``TrainStep`` of the toy, every layer recomputed: each latent
    layer calls the flash forward once (the recomputation keeps its output
    and log-sum-exp) and the backward kernels once, a recomputed expert
    layer still runs its grouped-product forward twice, and the gauge holds
    the bytes kept: ``o`` (bf16, B*H x T x D_v) and ``lse`` (float32,
    B*H x T) a layer."""
    telemetry.registry().remove("recompute.kept_attention_bytes")
    telemetry.registry().remove("flash.bwd_fused_share")
    net, loss_fn, batch = family.build(TOY)
    mx.random.seed(1)
    net.initialize()
    net.cast("bfloat16")
    step = parallel.TrainStep(
        net, loss_fn, mx.optimizer.create("adamw", learning_rate=1e-3),
        mesh=_one_device())
    (ids,), (labels,) = batch(np.random.default_rng(0), 2)
    text = step.lower(ids, labels).as_text()
    layers = len(TOY["layers"])

    def lowered(f):
        return re.findall(rf"func\.func private @{f}(?:_\d+)?\(", text)

    def calls(f):
        return len(re.findall(rf" call @{f}(?:_\d+)?\(", text))
    # one lowering of each flash jit, each called once a layer: _flash_bwd
    # holds the backward kernel (_bwd_dqkv), which every layer took
    assert (len(lowered("_flash_fwd")), len(lowered("_flash_bwd"))) == (1, 1)
    assert calls("_flash_fwd") == _forward_kernel_calls(text) == layers
    assert calls("_flash_bwd") == layers
    # the expert layers recompute as before: the two products' forward in
    # the forward pass and again in the recomputed one, their input
    # gradients; the weight gradients
    sparse = TOY["mlp_layers"].count("sparse")
    assert (len(lowered("_product")), len(lowered("_weights"))) == (6, 2)
    assert (calls("_product"), calls("_weights")) == (6 * sparse, 2 * sparse)
    heads, d_v = TOY["num_attention_heads"], TOY["v_head_dim"]
    rows = ids.shape[0] * heads * T
    assert telemetry.registry().get("recompute.kept_attention_bytes").value \
        == layers * (rows * d_v * 2 + rows * 4) == 55296
    assert telemetry.registry().get("flash.bwd_fused_share").value == 1.0


def test_a_step_trains_and_leaves_the_bias(stepped):
    net, step, ids, labels = stepped
    biases = [np.asarray(l.moe.expert_bias.data()._data)
              for l in net.layers[1:]]
    losses = [float(step(ids, labels).asnumpy()) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    step.sync_params_to_net()
    got = parallel.publish_load(net)
    assert 0.0 < got["moe.held_share"] < 1.0
    for layer, bias in zip(net.layers[1:], biases):
        assert np.array_equal(layer.moe.expert_bias.data()._data, bias)


# --------------------------------------------- the configuration, the count --
def _trained_parameters(**changes):
    net = family.make_net(dict(CONFIG["model"], **changes))
    return sum(int(np.prod(p.shape)) for p in net.collect_params().values()
               if p.grad_req != "null")


def test_the_published_config_counts_30_7b_and_the_cut_576m():
    attention = 2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256 \
        + 32 * 128 * 2048
    expert, dense = 3 * 2048 * 768, 3 * 2048 * 6144
    router, shared, norms = 2048 * 128, 3 * 2048 * 1536, 2 * 2048
    assert (attention, expert, dense, shared) == (
        26_345_984, 4_718_592, 37_748_736, 9_437_184)
    layers = ["latent"] * 48
    mlp_layers = ["dense"] + ["sparse"] * 47
    published = _trained_parameters(
        layers=layers, mlp_layers=mlp_layers, num_experts=128,
        vocab_size=CONFIG["vocab_size"])
    assert published == 48 * (attention + norms) + dense \
        + 47 * (128 * expert + router + shared) + 2 * 128256 * 2048 + 2048 \
        == 30_670_809_088
    assert published - 47 * 122 * expert == 3_614_402_560     # active
    dense_layer = attention + norms + dense
    sparse_layer = attention + norms + router + shared + 16 * expert
    assert (dense_layer, sparse_layer) == (64_098_816, 111_546_880)
    assert _trained_parameters() == dense_layer + 4 * sparse_layer \
        + 2 * 16032 * 2048 + 2048 == 575_955_456
    # 18 bytes a parameter: 10.37 GB resident before any batch
    assert round(18 * 575_955_456 / 1e9, 2) == 10.37


def test_configuration_keeps_every_published_width():
    catalog = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    assert {k: CONFIG[k] for k in catalog} == catalog
    model = CONFIG["model"]
    for key in set(model) & set(catalog) - {"vocab_size"}:
        assert model[key] == catalog[key], key
    assert model["qk_nope_head_dim"] + model["qk_rope_head_dim"] \
        == catalog["qk_head_dim"]
    # published layers 0 .. 4: the leading dense layer and four expert layers
    assert model["layers"] == ["latent"] * 5
    assert model["mlp_layers"] == ["dense"] + ["sparse"] * 4
    assert CONFIG["reduced"] == ["layers", "n_routed_experts", "vocab_size"]
    assert model["routed_experts"] == catalog["n_routed_experts"]
    assert model["num_experts"] * 8 == catalog["n_routed_experts"]
    assert model["vocab_size"] * 8 == catalog["vocab_size"]
    assert model["first_expert"] == 0 and model["sequence_length"] == 8192
    assert set(CONFIG["assumed"]) >= {
        "norm_topk_eps", "rope_interleave", "shared_experts", "expert_bias",
        "initialisation", "load_balancing_loss", "learning_rate"}
    assert CONFIG["published"]["n_routed_experts"] == 128
    assert "8 chips share each layer" in CONFIG["deployment"]
    assert any("recomputed" in d for d in CONFIG["departures"])
    assert CONFIG["check"]["loss_atol"] > 0 and CONFIG["check"]["why"]
    entry = next(c for c in manifest.load(ROOT)["configs"]
                 if c["name"] == "kanana2_30b_a3b")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["file"] == "chipbench/configs/kanana2_30b_a3b.json"
    assert "model_type deepseek_v3" in entry["source"]
    assert not any(manifest.WIDTH.search(k) for k in entry["reduced"])


def test_train_flops_against_a_hand_count():
    """The count by hand, a sequence of 8,192: five latent attention
    layers' projections and products, the dense feed-forward, four shared
    experts, the 3 of 4 held experts a token meets in four layers under even
    routing, the routers, the untied head over the slice."""
    model = CONFIG["model"]
    t = model["sequence_length"]
    pairs = t * (t + 1) // 2
    token = {"projections": 5 * 2 * 26_345_472,
             "dense": 2 * 37_748_736,
             "shared": 4 * 2 * 9_437_184,
             "experts": 4 * 2 * 0.75 * 4_718_592,
             "routers": 4 * 2 * 262_144,
             "head": 2 * 16032 * 2048}
    products = 5 * 2 * pairs * 32 * (192 + 128)
    forward = t * sum(token.values()) + products
    assert family.train_flops(model) == pytest.approx(3 * forward, rel=1e-12)
    # MFLOP a token and TFLOP a step of two sequences: 930 and 45.7
    assert round(forward / t / 1e6) == 930
    assert round(2 * family.train_flops(model) / 1e12, 1) == 45.7
    share = {k: t * v / forward for k, v in token.items()}
    mla = share.pop("projections") + products / forward
    assert round(100 * mla) == 73 and round(100 * products / forward) == 45
    assert [round(100 * share[k]) for k in ("shared", "dense", "head",
                                            "experts")] == [8, 8, 7, 3]
    # the kernels: one execution each, one sequence, the pairs required
    per = 2 * pairs * 32
    assert family.attention_kernel_flops(model) == {
        "flash_attention_fwd": per * 320,
        "flash_attention_bwd_dq": per * (2 * 192 + 128),
        "flash_attention_bwd_dkv": per * (2 * 192 + 2 * 128)}
    # the backward kernels do the backward's twice the forward, and QK^T
    # again in each and dO V^T once more
    kernels = family.attention_kernel_flops(model)
    assert kernels["flash_attention_fwd"] == products // 5
    assert kernels["flash_attention_bwd_dq"] \
        + kernels["flash_attention_bwd_dkv"] \
        == 2 * products // 5 + per * (2 * 192 + 128)


# ------------------------------------------------------ the roofline reader --
class _Trace:
    def __init__(self, ops):
        self.ops = ops

    def whole_steps(self):
        return [(0, 1), (1, 2)]

    def step_ops(self):
        return self.ops


class _Ctx:
    def __init__(self, ops, peaks=True, family_name="deepseek_v3"):
        self.reduced = _Trace(ops)
        self.peaks = {"bf16_flops_per_s": 197e12} if peaks else None
        self.cfg = {"family": family_name, "model": CONFIG["model"]}
        self.wl = {"batch_per_chip": 2}


def test_kernel_roofline_reads_executions_by_name():
    per = family.attention_kernel_flops(CONFIG["model"])
    ops = [(0, 4e6, "flash_attention_fwd.3 tpu_custom_call bf16[64,8192,128]",
            "jvp(forward)/layer0/attention", "forward"),
           (0, 6e6, "flash_attention_fwd.7 tpu_custom_call bf16[64,8192,128]",
            "transpose/rematted_computation", "recompute"),
           (0, 9e6, "flash_attention_bwd_dq tpu_custom_call bf16[64,8192,192]",
            "", "other"),
           (0, 12e6, "flash_attention_bwd_dkv.1 tpu_custom_call",
            "", "other"),
           (0, 50e6, "fusion.2 kOutput bf16[2,8192,2048]", "", "other"),
           (0, 50e6, "flash_attention_fwd_extra.1 custom", "", "other")]
    flops = 2 * (2 * per["flash_attention_fwd"]
                 + per["flash_attention_bwd_dq"]
                 + per["flash_attention_bwd_dkv"])
    want = 100 * flops / 31e-3 / 197e12
    assert kernel_roofline_pct.read(_Ctx(ops), flops="attention_kernel_flops"
                                    ) == pytest.approx(want, rel=1e-12)
    # nothing to read: no peaks (a CPU run), no such kernel, no function
    assert kernel_roofline_pct.read(_Ctx(ops, peaks=False),
                                    flops="attention_kernel_flops") is None
    assert kernel_roofline_pct.read(_Ctx(ops[4:]),
                                    flops="attention_kernel_flops") is None
    assert kernel_roofline_pct.read(_Ctx(ops, family_name="lfm2_moe"),
                                    flops="attention_kernel_flops") is None


def test_the_new_metrics_read_this_cell_alone():
    """The three metrics this configuration brings are appended together,
    behind those it found, and list its cell alone; the cell reports every
    accepted metric whose selection it matches and not the two that read
    nothing in it."""
    real = manifest.load(ROOT)
    cell = "kanana2_30b_a3b.train_s8192"
    names = [m["name"] for m in real["per_layer"]]
    at = names.index("latent_kv_ms")
    brought = real["per_layer"][at:at + 3]
    assert [m["name"] for m in brought] == [
        "latent_kv_ms", "shared_experts_ms", "attention_kernels_roofline_pct"]
    assert names.index("moe_product_tile_pct") < at
    assert all(m["workloads"] == [cell] and m["layer"] == "model_ops"
               and m["moves"] == "train_samples_per_s" for m in brought)
    mine = {m["name"] for m in manifest.cell(real, ROOT, cell)["per_layer"]}
    assert mine >= {"mfu_pct", "attention_ms", "attention_kernels_ms",
                    "ffn_ms", "moe_rows_ms", "moe_product_kernels_ms",
                    "moe_held_rows_pct", "cache_hit_pct", "setup_rest_ms"}
    assert not mine & {"moe_products_ms", "short_conv_ms", "mamba_ms"}
    assert real["workloads"][-1]["name"] == cell
    assert "6 x T" in real["workloads"][-1]["why"]
