"""Mixture-of-Experts layers, two paths.

**The one-hot path** (``moe_dispatch``, ``MoEFFN``; GShard / Mesh-TensorFlow):
top-k gating into capacity-bounded one-hot dispatch and combine tensors ``(N,
E, C)``, dense einsums over fixed shapes, stacked GELU experts with biases
whose leading dim E is annotated onto the ``ep`` axis: GSPMD partitions the
einsums and inserts the all-to-alls.  Tokens beyond an expert's capacity are
DROPPED, and the dispatch einsums cost more than the experts once groups are
thousands of tokens and experts dozens.  It is the path of the small ``ep``
demos (``tests/test_moe.py``, ``__graft_entry__.py``), kept for them.

**The dropless path** (``route_topk``, the op ``moe_dropless_ffn``,
``DroplessMoEFFN``): a router over all ``num_experts`` (softmax scores, or
sigmoid scores chosen by score + a per-expert bias that selects and does not
weigh), top-k, re-normalised; the block is told which experts it HOLDS
(``first_expert``, ``held``: one chip's share under expert parallelism),
sorts the token-expert assignments that land on its own experts to the front,
runs two grouped matrix products over them with ``silu(g) * u`` between (gated
experts, no bias), and sums its experts' weighted results back per token.
The products and their gradients are the Pallas kernels of
``ops/pallas/grouped_matmul.py`` (``moe_grouped_fwd`` / ``_dx`` / ``_dw``,
PR 38; XLA's ``jax.lax.ragged_dot`` is no longer on the path), which walk the
held rows' tiles by a map built once a layer from the experts' loads; the
token-side passes over the sorted rows are those of
``ops/pallas/token_rows.py`` (``moe_token_sum`` / ``_dot``).  No
capacity, no dropped token whatever the imbalance: the row buffer holds all
``N * k`` assignments.  What the absent experts would have added is left out;
on one chip the layer runs without an exchange.  This is the path for a model
whose experts are routed per token at real widths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.pallas import grouped_matmul, token_rows
from ..ops.pallas.gated_rows import gated_rows
from ..ops.registry import register_op

__all__ = ["moe_dispatch", "MoEFFN", "route_topk", "DroplessMoEFFN",
           "publish_load"]


def moe_dispatch(gate_logits, num_experts, capacity, k=2, valid=None):
    """GShard-style top-k routing with fixed capacity.

    gate_logits: (N, E).  ``valid``: optional (N,) bool — padded tokens are
    excluded from dispatch, capacity accounting, and the aux-loss statistics.
    Returns (dispatch (N, E, C) float, combine (N, E, C) float, aux_loss
    scalar).  Top-k gates are normalised over the selected k experts BEFORE
    capacity dropping (GShard semantics: mass routed to an overflowed expert
    is lost, not re-assigned), so tokens beyond an expert's capacity C simply
    combine with weight 0 — fixed shapes, jit-stable.
    """
    n, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)  # (N, E)
    valid_f = (jnp.ones((n,), jnp.float32) if valid is None
               else valid.astype(jnp.float32))
    n_valid = jnp.maximum(jnp.sum(valid_f), 1.0)

    # aux load-balancing loss (Switch/GShard): E * sum_e f_e * p_e over VALID tokens
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.sum(jax.nn.one_hot(top1, e, dtype=jnp.float32) * valid_f[:, None],
                axis=0) / n_valid
    p_mean = jnp.sum(probs * valid_f[:, None], axis=0) / n_valid
    aux_loss = e * jnp.sum(f * p_mean)

    # pass 1: select top-k experts per token; gather pre-drop gates
    remaining = probs
    selections = []
    gate_sum = jnp.zeros((n,), jnp.float32)
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                     # (N,)
        gate = jnp.take_along_axis(remaining, idx[:, None], 1)[:, 0]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)
        mask = onehot * valid_f[:, None].astype(jnp.int32)       # (N, E)
        selections.append((gate, mask))
        gate_sum = gate_sum + gate
        remaining = remaining * (1.0 - onehot)
    # pass 2: capacity-bounded slot assignment with pre-normalised gates
    dispatch = jnp.zeros((n, e, capacity), jnp.float32)
    combine = jnp.zeros((n, e, capacity), jnp.float32)
    occupancy = jnp.zeros((e,), jnp.int32)   # cumulative across the k rounds
    for gate, mask in selections:
        if k > 1:
            # normalise over the selected top-k BEFORE the keep-mask: a token
            # whose other choice overflows does NOT get its mass re-assigned
            gate = gate / jnp.maximum(gate_sum, 1e-9)
        # k == 1 keeps the raw gate multiplier (Switch Transformer):
        # normalising would make combine ≡ 1 and zero the router's gradient
        pos = jnp.cumsum(mask, axis=0) - mask + occupancy[None, :]
        pos_tok = jnp.sum(pos * mask, axis=-1)                   # (N,)
        keep = pos_tok < capacity
        onehot_pos = jax.nn.one_hot(pos_tok, capacity, dtype=jnp.float32)
        d = (mask.astype(jnp.float32)[:, :, None] * onehot_pos[:, None, :]
             * keep[:, None, None])
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        occupancy = occupancy + jnp.sum(mask * keep[:, None], axis=0)
    return dispatch, combine, aux_loss


def _moe_ffn_op(tokens, gate_w, w1, b1, w2, b2, num_experts=1, capacity=1,
                k=2, act="gelu", group_size=0):
    """Registered op: full MoE FFN on (N, C) tokens -> ((N, C), aux_loss).

    Tokens are routed in GROUPS of ``group_size`` with per-group capacity
    (the GShard formulation): dispatch/combine are (G, n_g, E, C_g), keeping
    routing-tensor memory linear in N instead of O(N^2)."""
    n, d = tokens.shape
    gs = group_size if group_size and group_size < n else n
    g = -(-n // gs)                       # ceil
    pad = g * gs - n
    if pad:
        tokens = jnp.concatenate(
            [tokens, jnp.zeros((pad, d), tokens.dtype)], axis=0)
    tg = tokens.reshape(g, gs, d)
    valid = (jnp.arange(g * gs) < n).reshape(g, gs)
    logits = tg.astype(jnp.float32) @ gate_w.astype(jnp.float32)  # (G,gs,E)
    dispatch, combine, aux = jax.vmap(
        lambda lg, v: moe_dispatch(lg, num_experts, capacity, k=k,
                                   valid=v))(logits, valid)
    # weight per-group aux by valid-token count so a padded tail group
    # doesn't dilute the load-balance statistics
    nv = jnp.maximum(jnp.sum(valid.astype(jnp.float32), axis=1), 1.0)
    aux = jnp.sum(aux * nv) / jnp.sum(nv)
    exp_in = jnp.einsum("gnec,gnd->gecd", dispatch.astype(tokens.dtype), tg)
    h = jnp.einsum("gecd,edh->gech", exp_in, w1) + b1[None, :, None, :]
    h = jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)
    out_e = jnp.einsum("gech,ehd->gecd", h, w2) + b2[None, :, None, :]
    out = jnp.einsum("gnec,gecd->gnd", combine.astype(tokens.dtype), out_e)
    out = out.reshape(g * gs, d)
    return out[:n], aux


register_op("moe_ffn", _moe_ffn_op)


def _make_moe_ffn():
    from ..gluon.block import HybridBlock
    from ..ndarray import NDArray
    from .sharding import ShardingRules
    import re

    class MoEFFN(HybridBlock):
        """Top-k gated expert FFN (GShard/Switch style).

        forward(x: (B, S, C) | (N, C)) -> (out, aux_loss).  Add
        ``aux_loss_weight * aux_loss`` to the training loss for load balance.
        Stacked expert weights (leading dim E) shard over ``ep`` via
        ``sharding_rules()``.
        """

        def __init__(self, units, hidden_size, num_experts, k=2,
                     capacity_factor=1.25, activation="gelu", ep_axis="ep",
                     group_size=4096, prefix=None, params=None):
            super().__init__(prefix=prefix, params=params)
            self._units = units
            self._hidden = hidden_size
            self._e = num_experts
            self._k = k
            self._cf = capacity_factor
            self._act = activation
            self._gs = group_size
            self.ep_axis = ep_axis
            self.gate_weight = self.params.get(
                "gate_weight", shape=(units, num_experts), init="xavier")
            self.w1 = self.params.get(
                "expert_w1", shape=(num_experts, units, hidden_size),
                init="xavier")
            self.b1 = self.params.get(
                "expert_b1", shape=(num_experts, hidden_size), init="zeros")
            self.w2 = self.params.get(
                "expert_w2", shape=(num_experts, hidden_size, units),
                init="xavier")
            self.b2 = self.params.get(
                "expert_b2", shape=(num_experts, units), init="zeros")

        def sharding_rules(self):
            pats = [(re.escape(self.w1.name), (self.ep_axis,)),
                    (re.escape(self.b1.name), (self.ep_axis,)),
                    (re.escape(self.w2.name), (self.ep_axis,)),
                    (re.escape(self.b2.name), (self.ep_axis,))]
            return ShardingRules(rules=pats)

        def infer_shape(self, *args):
            pass

        def hybrid_forward(self, F, x, gate_weight, w1, b1, w2, b2):
            shape = x.shape
            tokens = x.reshape((-1, shape[-1]))                # (N, C)
            n = tokens.shape[0]
            gs = self._gs if self._gs and self._gs < n else n
            capacity = max(1, int(self._cf * gs * self._k / self._e))
            out, aux = F.moe_ffn(tokens, gate_weight, w1, b1, w2, b2,
                                 num_experts=self._e, capacity=capacity,
                                 k=self._k, act=self._act, group_size=gs)
            return out.reshape(shape), aux

    return MoEFFN


MoEFFN = _make_moe_ffn()

# ---------------------------------------------------------------- dropless --
# the router's logits, softmax and gates, whatever the tokens' type: top-k
# over 64 near-equal probabilities does not survive bf16
_ROUTER_DTYPE = jnp.float32


def route_topk(logits, k, renormalise=True, score="softmax", bias=None,
               eps=0.0, scale=1.0):
    """Each token's ``k`` experts and their gates: ``(gates (N, k) float32,
    experts (N, k) int32)``.  The scores are ``softmax(logits)`` over the
    experts or, ``score="sigmoid"``, each expert's own ``sigmoid(logit)``, in
    float32.  The chosen are the ``k`` largest scores, or with ``bias`` (E,)
    the ``k`` largest of ``score + bias``: the bias SELECTS and does not
    weigh (the gates are the unbiased scores of the chosen) and no gradient
    reaches it.  ``renormalise`` divides the gates by ``(their sum over the
    chosen k) + eps`` (``norm_topk_prob``); ``scale`` multiplies them
    (``routed_scaling_factor``)."""
    logits = logits.astype(_ROUTER_DTYPE)
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"score {score!r} is neither 'softmax' nor "
                         f"'sigmoid'")
    if bias is None:
        gates, experts = jax.lax.top_k(scores, k)
    else:
        bias = jax.lax.stop_gradient(bias.astype(_ROUTER_DTYPE))
        experts = jax.lax.top_k(scores + bias, k)[1]
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalise:
        total = jnp.sum(gates, axis=-1, keepdims=True)
        gates = gates / (total + eps if eps else total)
    if scale != 1.0:
        gates = gates * scale
    return gates, experts.astype(jnp.int32)


def _row_chunk(m):
    """Rows a trip of the bounded gather visits (``_combine_rows``'s backward
    pass), from the buffer's rows alone: 4,096, or a smaller buffer whole.
    On the chip, 131,072 rows of 2,048 with 34,000 / 43,000 / all of them
    held: 2.78 / 3.23 / 8.00 ms a call in chunks of 2,048, 2.87 / 3.32 /
    7.92 at 4,096, 3.08 / 3.51 / 7.88 at 8,192, 3.99 / 3.99 / 9.12 at 16,384
    (a chunk that no longer stays in VMEM), against 12.19 for the whole
    pass; with all three sorted-row passes as such loops the op's forward
    and backward took 50.9 / 49.4 / 49.6 / 53.1 ms at a held share of 0.25
    and 80.2 / 76.7 / 75.4 / 80.4 at 0.55 (PERF.md 6, PR 34)."""
    return min(m, 4096)


@jax.custom_vjp
def _dispatch_rows(tokens, order, tmap):
    """Each assignment's token, in sorted order: ``tokens[order // k]``, all
    N * k rows (a row past ``total`` holds the token of an assignment that no
    held expert reads).  ``tmap`` (a ``token_rows.TokenMap``) lists, token
    by token, the sorted rows that read it: the backward pass is then the
    kernel ``moe_token_sum`` with unit weights (each token's held rows of
    ``dy`` summed) where autodiff would scatter-add N * k rows; ``dy``'s rows
    past ``total`` may hold anything, and the kernel never reads them."""
    return tokens[order // tmap.slots.shape[1]]


def _dispatch_rows_fwd(tokens, order, tmap):
    return _dispatch_rows(tokens, order, tmap), tmap


def _dispatch_rows_bwd(tmap, dy):
    k = tmap.slots.shape[1]
    ones = jnp.ones((dy.shape[0] // k, k), jnp.float32)
    return token_rows.moe_token_sum(dy, tmap, ones), None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(out, gates, order, tmap):
    """Each token's ``k`` sorted rows of ``out`` (N * k, d), those below
    ``total`` alone (the others may hold anything, and are never read),
    weighed by ``gates`` (N, k) float32 and summed in float32: the kernel
    ``moe_token_sum`` over ``tmap`` (a ``token_rows.TokenMap``).  The
    backward pass gives the gates' gradient by the kernel ``moe_token_dot``
    and writes the sorted rows below ``total``, each its token's ``dy``
    times its gate, as a loop over the ``ceil(total / chunk)`` chunks that
    hold one (``total`` is a device value and the trip count, so the work
    follows it while every shape stays static): zeros to the end of the last
    such chunk and nothing past it, where the rows hold anything, as the
    product's do."""
    return token_rows.moe_token_sum(out, tmap, gates)


def _combine_rows_fwd(out, gates, order, tmap):
    return _combine_rows(out, gates, order, tmap), (out, gates, order, tmap)


def _combine_rows_bwd(kept, dy):
    out, gates, order, tmap = kept
    m, k = order.shape[0], gates.shape[1]
    chunk = _row_chunk(m)
    total = tmap.total[0]
    d_gates = token_rows.moe_token_dot(out, tmap, dy).astype(gates.dtype)

    def trip(i, d_out):
        start = jnp.minimum(i * chunk, m - chunk)
        source = jax.lax.dynamic_slice_in_dim(order, start, chunk)
        mine = start + jnp.arange(chunk, dtype=jnp.int32) < total
        gate = jnp.where(mine, gates.reshape(-1)[source], 0)
        rows = dy[source // k].astype(jnp.float32) * gate[:, None]
        return jax.lax.dynamic_update_slice(
            d_out, rows.astype(d_out.dtype), (start, 0))
    # the loop writes into the kept rows' own buffer, which nothing reads
    # once ``d_gates`` is made (the barrier says so to the scheduler: with
    # it lfm2_8b_a1b.train_s8192 peaks 0.42 GB lower): a buffer of zeros of
    # its own cost that step 3.9 GB of temporaries (9.28 against 5.41;
    # PERF.md 6, PR 34), and the rows past ``total`` need not be zero
    out = jax.lax.optimization_barrier((out, d_gates))[0]
    d_out = jax.lax.fori_loop(0, (total + chunk - 1) // chunk, trip, out)
    return d_out, d_gates, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


@register_op("moe_dropless_ffn")
def _moe_dropless_ffn(tokens, router, gate_up, down, bias=None, num_experts=1,
                      first_expert=0, k=1, renormalise=True, score="softmax",
                      eps=0.0, scale=1.0):
    """Dropless top-k expert layer on ``tokens`` (N, d) for the experts
    ``first_expert .. first_expert + held`` of ``num_experts``: ``router``
    (d, E), ``gate_up`` (held, d, 2F), ``down`` (held, F, d).  Returns the
    held experts' part of ``sum_{e in top-k} w_e W_down,e (silu(W_gate,e u)
    * W_up,e u)`` (N, d), ``w_e`` normalised over all k chosen whether held
    or not, and the assignments per expert (E,) int32 of this call.  ``bias``
    (E,), ``score``, ``eps`` and ``scale`` are ``route_topk``'s.

    The N * k assignments are sorted by held expert, the unheld behind the
    held; the held rows run as two grouped products whose groups are the
    experts' loads: the kernels of ``ops/pallas/grouped_matmul.py``, all six
    calls of a layer (two products, their input and weight gradients) over
    ONE tile map built here from the loads.  Rows past the groups' sum,
    ``total``, belong to no held expert and a grouped product does not
    visit them.  The passes that WRITE sorted rows between and after the
    products stop there too:
    ``silu(g) * u`` and its backward pass are the kernels of
    ``ops/pallas/gated_rows.py``, whose grid steps past ``total`` fetch and
    write nothing, the gather of the output's gradient is a loop of
    ``ceil(total / chunk)`` trips.  (The gather in visits every row: as a
    loop it made XLA schedule the step of ``mellum2_12b_a2_5b.train_s8192``
    with 2 GB more of temporaries, which does not load.)  What lies past
    ``total`` in a buffer of sorted rows may be anything: other tokens,
    what the buffer held before; the grouped products leave such rows
    unwritten (NaN in the interpreter), as XLA:TPU's ``ragged_dot`` left
    garbage there before them.
    The token-side passes that READ sorted rows (the weighted sum out, the
    tokens' gradient, the gates' gradient) are the kernels of
    ``ops/pallas/token_rows.py`` over ONE token map a layer: each block of
    tokens fetches the 8-row chunks of its held rows and no row past
    ``total``, and zeroes what a fetched chunk holds past it."""
    n = tokens.shape[0]
    held = gate_up.shape[0]
    with jax.named_scope("router"):
        logits = jnp.dot(tokens, router.astype(tokens.dtype),
                         preferred_element_type=_ROUTER_DTYPE)
        gates, experts = route_topk(logits, k, renormalise, score, bias,
                                    eps, scale)
        flat = experts.reshape(n * k)
        load = jnp.sum(flat[:, None] == jnp.arange(num_experts)[None, :],
                       axis=0, dtype=jnp.int32)
    with jax.named_scope("dispatch"):
        local = flat - first_expert
        key = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        at = jnp.arange(n * k, dtype=jnp.int32)
        back = jnp.zeros_like(order).at[order].set(
            at, unique_indices=True).reshape(n, k)
        sizes = load[first_expert:first_expert + held]
        total = jnp.sum(sizes)
        groups = grouped_matmul.group_map(
            sizes, n * k, grouped_matmul.row_tile(n * k))
        tmap = token_rows.token_map(back, groups.offsets)
        rows = _dispatch_rows(tokens, order, tmap)
    with jax.named_scope("experts"):
        hidden = gated_rows(grouped_matmul.grouped_matmul(
            rows, gate_up, groups), total)
        out = grouped_matmul.grouped_matmul(hidden, down, groups)
    with jax.named_scope("combine"):
        out = _combine_rows(out, gates, order, tmap)
    return out, load


def _make_dropless_moe_ffn():
    from .. import initializer as init_mod
    from ..gluon.block import HybridBlock
    from ..ndarray import NDArray
    from .sharding import ShardingRules
    import re

    class DroplessMoEFFN(HybridBlock):
        """Dropless top-k gated experts, the share of them one chip holds.

        ``forward(x: (B, T, d) | (N, d)) -> (out, load)``: the held experts'
        part of the layer's result and this call's assignments per expert
        (``num_experts``,) int32.  Parameters: ``router`` (d, E),
        ``gate_up`` (held, d, 2F), ``down`` (held, F, d), no bias; ``load``
        (E,) int32 is aux state (``grad_req`` null) that ``record_load``
        writes, the way BatchNorm writes its running statistics.  The block
        does NOT write it itself: a block that rewrites aux state cannot sit
        inside ``Block.recompute()``, so whoever calls from outside any
        recomputed block hands the load to ``record_load`` (the decoder of
        ``gluon/model_zoo/moe_decoder.py`` does, in its own forward).
        ``held`` defaults to all ``num_experts``; the stacked experts shard
        over ``ep`` via ``sharding_rules()``.

        The router is ``route_topk``'s: ``score`` (``softmax`` | ``sigmoid``),
        ``norm_eps`` in the re-normalisation's divisor, ``scale`` on the
        gates.  ``selection_bias=True`` adds ``expert_bias`` (E,), aux state
        like ``load`` (no gradient, no optimizer state, float32 whatever the
        compute type): it is added to the scores to CHOOSE the k experts and
        never to their gates; zeros until loaded or drawn."""

        def __init__(self, units, hidden_size, num_experts, k, held=None,
                     first_expert=0, renormalise=True, score="softmax",
                     selection_bias=False, norm_eps=0.0, scale=1.0,
                     prefix=None, params=None):
            super().__init__(prefix=prefix, params=params)
            held = num_experts if held is None else held
            if not 0 <= first_expert <= first_expert + held <= num_experts:
                raise ValueError(
                    f"experts {first_expert} .. {first_expert + held} are "
                    f"not among {num_experts}")
            if not 1 <= k <= num_experts:
                raise ValueError(f"top-{k} of {num_experts} experts")
            self._e, self._k, self._first = num_experts, k, first_expert
            self._held, self._renormalise = held, renormalise
            if score not in ("softmax", "sigmoid"):
                raise ValueError(f"score {score!r} is neither 'softmax' nor "
                                 f"'sigmoid'")
            self._router = dict(score=score, eps=norm_eps, scale=scale)
            normal = init_mod.Normal(0.02)
            self.router = self.params.get(
                "router", shape=(units, num_experts), init=normal)
            self.gate_up = self.params.get(
                "gate_up", shape=(held, units, 2 * hidden_size),
                init=normal)
            self.down = self.params.get(
                "down", shape=(held, hidden_size, units), init=normal)
            self.load = self.params.get(
                "load", shape=(num_experts,), dtype="int32", init="zeros",
                differentiable=False)
            if selection_bias:
                self.expert_bias = self.params.get(
                    "expert_bias", shape=(num_experts,), dtype="float32",
                    init="zeros", differentiable=False)

        def sharding_rules(self):
            return ShardingRules(rules=[
                (re.escape(self.gate_up.name), ("ep",)),
                (re.escape(self.down.name), ("ep",))])

        def cast(self, dtype):
            super().cast(dtype)
            self.load.cast("int32")         # a count, whatever the compute type
            if "expert_bias" in self._reg_params:
                self.expert_bias.cast("float32")    # the router's own type
            return self

        def record_load(self, load):
            """Keep ``load`` (what ``forward`` returned) as this block's
            aux state, to be read back after the step."""
            self.load._data = NDArray(jax.lax.stop_gradient(load._data))

        def held_range(self):
            return self._first, self._first + self._held

        def hybrid_forward(self, F, x, router, gate_up, down, load,
                           expert_bias=None):
            shape = x.shape
            bias = () if expert_bias is None else (expert_bias,)
            out, load = F.moe_dropless_ffn(
                x.reshape((-1, shape[-1])), router, gate_up, down, *bias,
                num_experts=self._e, first_expert=self._first, k=self._k,
                renormalise=self._renormalise, **self._router)
            return out.reshape(shape), load

    return DroplessMoEFFN


DroplessMoEFFN = _make_dropless_moe_ffn()


def publish_load(net):
    """Read the ``load`` of every ``DroplessMoEFFN`` under ``net`` (after a
    step: ``TrainStep.sync_params_to_net()`` first) and publish, over all of
    them, the gauges ``moe.load_max_over_mean`` (the fullest expert's
    assignments over the mean: 1 is even routing), ``moe.held_share`` (the
    share of assignments that landed on held experts: ``held / num_experts``
    under even routing) and ``moe.row_pass_share`` (the share of the N * k
    sorted rows that the op's bounded gather visited: the held rows rounded
    up to whole chunks of ``_row_chunk``, layer by layer; 1 is a pass over
    every row) and ``moe.product_tile_share`` (the share of the N * k
    buffer's row tiles that the grouped-product kernels compute: each
    layer's held experts' tiles, a tile two experts share once for each;
    ``grouped_matmul.tile_visits``).
    Returns ``{gauge: value}``; all are 0 before any step."""
    import numpy as np
    from .. import telemetry
    blocks = []
    net.apply(lambda b: blocks.append(b)
              if isinstance(b, DroplessMoEFFN) else None)
    loads = [np.asarray(b.load.data()._data, np.float64) for b in blocks]
    total = sum(l.sum() for l in loads)
    values = {"moe.load_max_over_mean": 0.0, "moe.held_share": 0.0,
              "moe.row_pass_share": 0.0, "moe.product_tile_share": 0.0}
    if total:
        held = [l[slice(*b.held_range())].sum()
                for b, l in zip(blocks, loads)]
        values["moe.load_max_over_mean"] = float(
            max(l.max() / l.mean() for l in loads if l.sum()))
        values["moe.held_share"] = float(sum(held) / total)
        chunks = [_row_chunk(int(l.sum())) for l in loads]
        values["moe.row_pass_share"] = float(sum(
            min(-(-h // c) * c, l.sum())
            for h, l, c in zip(held, loads, chunks) if c) / total)
        tiles = [grouped_matmul.row_tile(int(l.sum())) for l in loads]
        values["moe.product_tile_share"] = float(sum(
            grouped_matmul.tile_visits(l[slice(*b.held_range())], t)
            for b, l, t in zip(blocks, loads, tiles) if t) / sum(
            -(-int(l.sum()) // t) for l, t in zip(loads, tiles) if t))
    for name, v in values.items():
        telemetry.registry().gauge(name).set(v)
    return values
