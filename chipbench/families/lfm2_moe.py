"""``family: lfm2_moe``: LFM2-8B-A1B on the model zoo's one expert decoder
(``gluon/model_zoo/moe_decoder.py``: gated short-convolution and full
attention layers, a leading dense feed-forward, then a dropless top-k expert
layer behind a sigmoid router that selects by a bias it does not weigh by, a
head tied to the embedding) as a language model on random token sequences,
each layer recomputed in the backward pass."""
import numpy as np
from mxnet_tpu import gluon, initializer
from mxnet_tpu.gluon.model_zoo.moe_decoder import MoEDecoder

from chipbench.families.moe_decoder import (  # noqa: F401  (the same products)
    grouped_product_bytes, grouped_product_flops)
from chipbench.families.sambay import check_labels   # noqa: F401  (tokens too)

# the model's keys that are the net's own arguments under the same name
SAME_NAME = ("vocab_size", "layers", "mlp_layers", "hidden_size",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "intermediate_size", "moe_intermediate_size",
             "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
             "use_expert_bias", "first_expert")
# the reference's 1e-6 in the gates' divisor (the configuration's assumed)
NORM_TOPK_EPS = 1e-6
# the configuration's layer_types, by the net's kinds
KIND = {"conv": "conv", "full_attention": "full"}


def layer_lists(config, first=0, count=None):
    """``(layers, mlp_layers)`` as the net takes them for the published
    layers ``first .. first + count`` of the configuration's top-level keys:
    the operators by ``layer_types``, a dense feed-forward under
    ``num_dense_layers`` and a sparse one from there on."""
    kinds = [KIND[k] for k in config["layer_types"]]
    ffs = ["dense" if i < config["num_dense_layers"] else "sparse"
           for i in range(len(kinds))]
    end = len(kinds) if count is None else first + count
    return kinds[first:end], ffs[first:end]


class _Drawn(initializer.Normal):
    """normal(sigma) whatever the parameter's name ends in (the base class
    zeroes every ``*bias``)."""

    def __call__(self, name, shape, dtype="float32"):
        return self.init_array(shape, dtype)


def make_net(model):
    """The decoder of the configuration's ``model``, not yet initialised:
    ``num_experts`` there counts the experts HELD here, ``routed_experts``
    the router's outputs.  The benchmark's own choices, not the model's: the
    embedding (which is the head too) is drawn normal(``embedding_std``) and
    every layer's ``expert_bias`` normal(``expert_bias_std``), so that the
    selection path runs (zeros, the published initial value, would leave
    ``top_k(s + b)`` equal to ``top_k(s)``)."""
    net = MoEDecoder(
        **{k: model[k] for k in SAME_NAME}, num_experts=model["routed_experts"],
        held_experts=model["num_experts"], rms_norm_eps=model["norm_eps"],
        conv_taps=model["conv_L_cache"], qk_norm=True, tie_head=True,
        score_function="sigmoid", norm_topk_eps=NORM_TOPK_EPS,
        rope_parameters={"full": {"rope_type": "default",
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


def train_flops(model):
    """Per sequence: 3 x (2 x positions x (each layer's operator matrices
    and its feed-forward's + the tied head, once) + the attention products).
    A ``conv`` operator is its two projections (hidden -> 3 x hidden ->
    hidden); a ``full`` one Q, K, V and O and the products over the (query,
    key) pairs causality leaves; a ``dense`` feed-forward three matrices of
    ``intermediate_size``; a ``sparse`` one the router and, an EXPECTATION,
    the ``k x held / routed`` held experts a token meets under even routing
    (1 of 8 at top-4 of 32; the gauge ``moe.held_share`` against ``held /
    routed`` says how far a run is from it).  The taps, the gates, norms,
    rotary, softmax, routing, sort, gathers and all recomputation are left
    out."""
    t, h = model["sequence_length"], model["hidden_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    mixer = {"conv": h * 3 * h + h * h, "full": h * (q + 2 * kv) + q * h}
    met = model["num_experts_per_tok"] * model["num_experts"] \
        / model["routed_experts"]
    feed = {"dense": 3 * h * model["intermediate_size"],
            "sparse": h * model["routed_experts"]
            + met * 3 * h * model["moe_intermediate_size"]}
    causal = t * (t + 1) // 2                       # (query, key) pairs
    forward = 2 * t * model["vocab_size"] * h
    for kind, ff in zip(model["layers"], model["mlp_layers"]):
        forward += 2 * t * (mixer[kind] + feed[ff])
        if kind == "full":
            forward += 2 * 2 * causal * q           # QK^T and PV, all heads
    return 3 * forward
