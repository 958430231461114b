"""``family: deepseek_v3``: Kanana-2-30B-A3B on the model zoo's one expert
decoder (``gluon/model_zoo/moe_decoder.py``: multi-head latent attention in
every layer, a leading dense feed-forward, then routed experts behind a
sigmoid router that selects by a bias it does not weigh by, and a shared
expert beside them; an untied head) as a language model on random token
sequences, each layer recomputed in the backward pass."""
import numpy as np
from mxnet_tpu import gluon, initializer
from mxnet_tpu.gluon.model_zoo.moe_decoder import MoEDecoder

from chipbench.families.lfm2_moe import _Drawn
from chipbench.families.moe_decoder import (  # noqa: F401  (the same products)
    grouped_product_bytes, grouped_product_flops)
from chipbench.families.sambay import check_labels   # noqa: F401  (tokens too)

# the model's keys that are the net's own arguments under the same name
SAME_NAME = ("vocab_size", "layers", "mlp_layers", "hidden_size",
             "num_attention_heads", "head_dim", "intermediate_size",
             "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor", "first_expert", "rms_norm_eps",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim")
# transformers' ``deepseek_v3`` divides the gates by (their sum + 1e-20)
NORM_TOPK_EPS = 1e-20
# the router this family runs: the configuration's keys, as published
ROUTER = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
          "topk_group": 1}
KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
           "flash_attention_bwd_dkv")


def make_net(model):
    """The decoder of the configuration's ``model``, not yet initialised:
    ``num_experts`` there counts the experts HELD here, ``routed_experts``
    the router's outputs, and the shared expert is ``n_shared_experts x
    moe_intermediate_size`` wide.  With ``n_group`` = ``topk_group`` = 1 the
    group step of ``noaux_tc`` keeps every expert; another router is refused
    rather than run as this one.  The benchmark's own choices, not the
    model's: the embedding is drawn normal(``embedding_std``) and every
    layer's ``expert_bias`` normal(``expert_bias_std``), so that the
    selection path runs."""
    other = {k: model.get(k) for k in ROUTER if model.get(k) != ROUTER[k]}
    if other:
        raise ValueError(f"router {other}: this family runs {ROUTER} alone")
    if model.get("rope_interleave") is not True:
        raise ValueError(f"rope_interleave {model.get('rope_interleave')!r}: "
                         f"a latent layer turns pairs of neighbours alone")
    net = MoEDecoder(
        **{k: model[k] for k in SAME_NAME},
        num_key_value_heads=model["num_attention_heads"],
        num_experts=model["routed_experts"],
        held_experts=model["num_experts"], score_function="sigmoid",
        use_expert_bias=True, norm_topk_eps=NORM_TOPK_EPS,
        shared_experts=model["n_shared_experts"]
        * model["moe_intermediate_size"],
        rope_parameters={"latent": {"rope_type": "default",
                                    "rope_theta": model["rope_theta"]}})
    net.embed.weight.init = initializer.Normal(model["embedding_std"])
    for layer in net.layers:
        if layer.sparse:
            layer.moe.expert_bias.init = _Drawn(model["expert_bias_std"])
    return net


def build(model):
    """(net, loss_fn, make_batch(rng, n) -> (data, labels))."""
    net = make_net(model)
    for layer in net.layers:        # one layer's intermediates at a time
        layer.recompute()

    def batch(rng, n):
        """n sequences of ids uniform over the vocabulary slice; a position's
        label is the next position's id (the last wraps to the first)."""
        ids = rng.integers(0, model["vocab_size"],
                           (n, model["sequence_length"]), dtype=np.int32)
        return (ids,), (np.roll(ids, -1, axis=1),)
    return net, gluon.loss.SoftmaxCrossEntropyLoss(axis=-1), batch


def _causal_pairs(model):
    t = model["sequence_length"]
    return t * (t + 1) // 2


def _latent_parameters(model):
    """The four projections of one latent attention block."""
    h, heads = model["hidden_size"], model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, v = model["kv_lora_rank"], model["v_head_dim"]
    return (h * heads * (nope + rope) + h * (rank + rope)
            + rank * heads * (nope + v) + heads * v * h)


def train_flops(model):
    """Per sequence: 3 x (2 x positions x (each layer's latent attention
    projections and its feed-forward's matrices + the untied head) + the
    attention products).  The products count the (query, key) pairs
    causality leaves: ``2 x pairs x heads x (d_qk + d_v)`` a layer.  A
    ``dense`` feed-forward is three matrices of ``intermediate_size``; a
    ``sparse`` one the router, the shared expert and, an EXPECTATION, the
    ``k x held / routed`` held experts a token meets under even routing (3
    of 4 at top-6 of 128 with 16 held; the gauge ``moe.held_share`` against
    ``held / routed`` says how far a run is from it).  Norms, rotary,
    softmax, routing, sort, gathers and all recomputation are left out."""
    t, h = model["sequence_length"], model["hidden_size"]
    f = model["moe_intermediate_size"]
    met = model["num_experts_per_tok"] * model["num_experts"] \
        / model["routed_experts"]
    feed = {"dense": 3 * h * model["intermediate_size"],
            "sparse": h * model["routed_experts"] + met * 3 * h * f
            + 3 * h * model["n_shared_experts"] * f}
    d_qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    products = 2 * _causal_pairs(model) * model["num_attention_heads"] \
        * (d_qk + model["v_head_dim"])
    forward = 2 * t * model["vocab_size"] * h
    for ff in model["mlp_layers"]:
        forward += 2 * t * (_latent_parameters(model) + feed[ff]) + products
    return 3 * forward


def attention_kernel_flops(model):
    """{kernel name: FLOPs of ONE execution for one sequence} of the causal
    flash kernels at the model's heads, over the (query, key) pairs the
    model REQUIRES, not the masked blocks the grid computes: the forward
    multiplies QK^T and PV, the dq kernel QK^T again, dO V^T and dS K, the
    dk/dv kernel QK^T, dO V^T, P^T dO and dS^T Q.  A recomputed forward is
    one more execution of ``flash_attention_fwd``."""
    d_qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    d_v = model["v_head_dim"]
    per = 2 * _causal_pairs(model) * model["num_attention_heads"]
    return dict(zip(KERNELS, (per * (d_qk + d_v), per * (2 * d_qk + d_v),
                              per * (2 * d_qk + 2 * d_v))))
