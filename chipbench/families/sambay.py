"""``family: sambay``: the hybrid decoder of the model zoo
(``gluon/model_zoo/sambay.py``: Mamba, window and full attention, gated
memory units, cross-attention over shared K/V) as a language model on
random token sequences, each layer recomputed in the backward pass."""
import jax.numpy as jnp
import numpy as np
from mxnet_tpu import gluon
from mxnet_tpu.gluon.model_zoo.sambay import SambaY

# the model's keys that are the net's own arguments
WIDTHS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
          "intermediate_size", "sliding_window", "layer_norm_eps",
          "state_size", "conv_kernel", "expand", "dt_rank")


def build(model):
    """(net, loss_fn, make_batch(rng, n) -> (data, labels))."""
    net = SambaY(model["vocab_size"], model["layers"],
                 **{k: model[k] for k in WIDTHS})
    for layer in net.layers:        # one layer's intermediates at a time:
        layer.recompute()           # what lets 4,096 tokens fit beside AdamW

    def batch(rng, n):
        """n sequences of ids uniform over the vocabulary slice; a position's
        label is the next position's id (the last wraps to the first)."""
        ids = rng.integers(0, model["vocab_size"],
                           (n, model["sequence_length"]), dtype=np.int32)
        return (ids,), (np.roll(ids, -1, axis=1),)
    return net, gluon.loss.SoftmaxCrossEntropyLoss(axis=-1), batch


def check_labels(out):
    """The token the reference logits rank first, at every position: labels
    that only this forward reproduces (see ``resnet_v1.check_labels``)."""
    return (jnp.argmax(out._data, axis=-1).astype(jnp.int32),)


def train_flops(model):
    """Per sequence: 3 x (2 x the layers' matrix parameters + the tied head
    + the attention products) x positions.  Attention counts what causality
    and the window leave: a query at t multiplies t+1 keys, or
    min(t+1, window).  The scan's element-wise work, the convolution, the
    norms and activations are not matrix work and are left out, as is all
    recomputation."""
    t, h = model["sequence_length"], model["hidden_size"]
    ff, inner = model["intermediate_size"], model["expand"] * model["hidden_size"]
    kv = h // model["num_attention_heads"] * model["num_key_value_heads"]
    mlp = 3 * h * ff
    attention = h * (h + 2 * kv) + h * h
    mixer = {
        "mamba": h * 2 * inner + inner * (model["dt_rank"]
                                          + 2 * model["state_size"])
        + model["dt_rank"] * inner + inner * h,
        "window": attention, "full": attention,
        "gmu": 2 * h * inner,
        "cross": 2 * h * h,
    }
    causal = t * (t + 1) // 2                       # (query, key) pairs
    w = min(model["sliding_window"], t)
    banded = w * (w + 1) // 2 + (t - w) * w
    pairs = {"window": banded, "full": causal, "cross": causal}
    forward = 2 * t * model["vocab_size"] * h
    for kind in model["layers"]:
        forward += 2 * t * (mixer[kind] + mlp)
        forward += 2 * 2 * pairs.get(kind, 0) * h   # QK^T and PV, all heads
    return 3 * forward
