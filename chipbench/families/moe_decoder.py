"""``family: moe_decoder``: the sparse mixture-of-experts decoder of the
model zoo (``gluon/model_zoo/moe_decoder.py``: window and full attention
under two rotary tables, a dropless top-k expert layer told which experts it
holds) as a language model on random token sequences, each layer recomputed
in the backward pass."""
import numpy as np
from mxnet_tpu import gluon, initializer
from mxnet_tpu.gluon.model_zoo.moe_decoder import MoEDecoder

from chipbench.families.sambay import check_labels   # noqa: F401  (tokens too)

# the model's keys that are the net's own arguments under the same name
SAME_NAME = ("hidden_size", "num_attention_heads", "num_key_value_heads",
          "head_dim", "moe_intermediate_size", "num_experts_per_tok",
          "sliding_window", "rms_norm_eps", "norm_topk_prob", "first_expert")
# the configuration's names for the two rotary tables, by the net's kinds
ROPE_KIND = {"sliding_attention": "window", "full_attention": "full"}


def make_net(model):
    """The decoder of the configuration's ``model``, not yet initialised:
    ``num_experts`` there counts the experts HELD here, ``routed_experts``
    the router's outputs.  The benchmark's own choice, not the model's: the
    EMBEDDING is drawn normal(``embedding_std``) where the net draws every
    matrix normal(0.02).  At random weights the residual stream is the
    embedding plus the layers' outputs, which are prefix averages much alike
    for every token, so a small embedding leaves the routers nothing
    token-specific and every token chooses the same experts; at unit scale
    the tokens stay distinct and the routing starts near even, BY
    CONSTRUCTION (no trained router's load is behind it)."""
    net = MoEDecoder(
        **{k: model[k] for k in SAME_NAME}, vocab_size=model["vocab_size"],
        layers=model["layers"], num_experts=model["routed_experts"],
        held_experts=model["num_experts"],
        rope_parameters={ROPE_KIND[k]: v
                         for k, v in model["rope_parameters"].items()})
    net.embed.weight.init = initializer.Normal(model["embedding_std"])
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


def _expert_parameters(model):
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def train_flops(model):
    """Per sequence: 3 x (2 x positions x (the layers' attention and router
    matrices + the experts a token meets here + the untied head) + the
    attention products).  The expert term is an EXPECTATION: under even
    routing a token meets ``k x held / routed`` of the held experts (2 of 16
    at top-8 of 64); the true count depends on the data, and the measured
    gauge ``moe.held_share`` against ``held / routed`` says how far a run is
    from it.  Attention counts what causality and the window leave: a query
    at t multiplies t+1 keys, or min(t+1, window).  Norms, rotary, softmax,
    routing, sort, gathers and all recomputation are left out."""
    t, h = model["sequence_length"], model["hidden_size"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    attention = h * (q + 2 * kv) + q * h
    router = h * model["routed_experts"]
    met = model["num_experts_per_tok"] * model["num_experts"] \
        / model["routed_experts"]
    causal = t * (t + 1) // 2                       # (query, key) pairs
    w = min(model["sliding_window"], t)
    pairs = {"window": w * (w + 1) // 2 + (t - w) * w, "full": causal}
    forward = 2 * t * model["vocab_size"] * h
    for kind in model["layers"]:
        forward += 2 * t * (attention + router
                            + met * _expert_parameters(model))
        forward += 2 * 2 * pairs[kind] * q          # QK^T and PV, all heads
    return 3 * forward


def grouped_product_flops(model, rows):
    """Operations of ONE layer's two grouped products, forward, over
    ``rows`` assignments that landed on held experts (the sum of the
    groups): ``rows x d x 2F`` and ``rows x F x d`` multiply-adds.  The
    backward pass is twice this (each product's two gradients)."""
    return 2 * rows * _expert_parameters(model)


def grouped_product_bytes(model, rows, held, itemsize=2):
    """Bytes ONE layer's two grouped products must move, forward: each reads
    its rows and the ``held`` experts' matrices once and writes its result
    (``itemsize`` 2: bf16)."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    first = rows * d + held * d * 2 * f + rows * 2 * f
    second = rows * f + held * f * d + rows * d
    return itemsize * (first + second)
