"""Kanana-2-30B-A3B on the chip at the widths and the share of
``chipbench/configs/kanana2_30b_a3b.json`` (published layers 0..4, 16 of 128
experts, 16,032 rows, 576M parameters) against the plain reference kept with
the benchmark (``chipbench/reference/deepseek_v3.py``), over three seeds of
weights and ids (six for the forward):

- ONE REAL STEP of ``parallel.TrainStep`` (the cell's net, optimizer and
  program, bf16) against AdamW's update written out in numpy on the
  reference's gradients: the change of every float32 master weight;
- the float32 net at "highest" precision: logits and loss at 8,192
  positions, every gradient leaf at 1,024;
- the bf16 forward against the limits the configuration states, and the
  precisions below (``kanana2_controls.CONTROLS``): the rotary angles in
  bf16, the router's logits, sigmoid, bias and gates in bf16, the latent
  RMSNorm's moments in bf16, and all of the forward's float32 parts at
  once, the last two of which must break a limit.

Gradients are compared at 1,024 positions because the plain reference
recomputes nothing.  What each comparison read is written to
``chiprun_out/kanana2_tpu.json``; the limits are from those readings
(``check.why``)."""
import gc
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.context import on_tpu

if not on_tpu():
    pytest.skip("TPU re-run suite needs the TPU backend",
                allow_module_level=True)

import mxnet_tpu as mx                                      # noqa: E402
from mxnet_tpu import parallel                              # noqa: E402
from mxnet_tpu.gluon.block import _flatten_nd               # noqa: E402
from mxnet_tpu.gluon.parameter import materialize           # noqa: E402
from mxnet_tpu.ndarray import NDArray                       # noqa: E402
from mxnet_tpu.parallel.functional import (                 # noqa: E402
    FunctionalState, functional_call)

from test_moe_decoder_tpu import (                          # noqa: E402
    _adamw_first_step, _kind, _mean_loss, _norm)
from kanana2_controls import CONTROLS, precision            # noqa: E402

from chipbench import manifest                              # noqa: E402
from chipbench.families import deepseek_v3 as family        # noqa: E402
from chipbench.reference import deepseek_v3 as reference    # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = manifest.load_json(ROOT, "chipbench/configs/kanana2_30b_a3b.json")
MODEL, CHECK = CONFIG["model"], CONFIG["check"]
WIDTHS = (MODEL["num_attention_heads"], MODEL["kv_lora_rank"],
          MODEL["qk_nope_head_dim"], MODEL["v_head_dim"])
REF = dict(heads=MODEL["num_attention_heads"], nope=MODEL["qk_nope_head_dim"],
           rope=MODEL["qk_rope_head_dim"], eps=MODEL["rms_norm_eps"],
           k=MODEL["num_experts_per_tok"], first_expert=MODEL["first_expert"],
           rope_theta=MODEL["rope_theta"],
           scale=MODEL["routed_scaling_factor"])
MLP_LAYERS = MODEL["mlp_layers"]
T, T_GRAD = MODEL["sequence_length"], 1024
SEEDS = (20261101, 20261102, 20261103)      # weights; ids from seed + 1
READ_SEEDS = SEEDS + (20261104, 20261105, 20261106)     # the bf16 forward
READINGS = {}
# the forward as the configuration states it, and the precisions below; the
# limits tell the router and every float32 part at once apart, the angles
# and the latent norm's moments in bf16 not (``check.why``)
PRECISIONS = ("sound", "angles", "router", "latent_norm", "combined")
TOLD_APART = ("router", "combined")


@pytest.fixture(scope="module", autouse=True)
def _write_readings():
    yield
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "kanana2_tpu.json"),
              "w") as f:
        json.dump(READINGS, f, indent=1)
    print("kanana2_tpu readings:", json.dumps(READINGS))


def _ids(t, seed):
    ids = np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], (1, t), dtype=np.int32)
    return ids, np.roll(ids, -1, axis=1)


@jax.jit
def _reference_forward(params, buffers, ids):
    chosen = []
    logits = reference.forward(params, buffers, MLP_LAYERS, ids,
                               chosen=chosen, **REF)
    return logits, chosen


@jax.jit
def _reference_grads(params, buffers, ids, labels):
    loss, grads = reference.loss_and_grads(params, buffers, MLP_LAYERS, ids,
                                           labels, **REF)
    return loss, reference.grads_to_net(grads)


# ------------------------------------------------ one real step of TrainStep --
def _one_step(seed):
    """Readings of one seed: the cell's net (bf16, every layer recomputed)
    under ``TrainStep`` with the configuration's optimizer, ONE step on one
    sequence of 1,024 tokens; the change of each float32 master weight
    against ``_adamw_first_step`` of the plain reference's gradient at the
    same rounded weights, as |got - want| / |want| per leaf (the worst of a
    kind) and over all leaves.  A leaf the step left alone reads 1.

    Adam's first update of an element is about ``-lr * sign(g)``, so an
    element whose bf16 gradient has the other sign than the reference's is
    off by ``2 lr``: of the worst leaf of each kind, ``sign_flips_by_kind``
    holds the share of the elements the reference moves (by over ``lr /
    2``) whose change has the other sign, ``2 sqrt`` of it (what the
    reading would be if those flips were all of the error) and the share of
    the squared error that lies on them."""
    mx.random.seed(seed)
    net, loss_fn, _ = family.build(MODEL)
    net.initialize()
    net.cast(CONFIG["compute_dtype"])
    materialize(net.collect_params().values())
    structural = {id(p): n
                  for n, p in net._collect_params_with_prefix().items()}
    ids, labels = _ids(T_GRAD, seed + 1)
    want_loss, grads = _reference_grads(
        *reference.params_from_net(net, WIDTHS), ids, labels)
    want_loss = float(want_loss)
    grads = {n: np.asarray(g) for n, g in grads.items()}
    before = {n: np.asarray(p.data()._data.astype(jnp.float32))
              for n, p in net._collect_params_with_prefix().items()
              if p.grad_req != "null"}
    gc.collect()

    args = CONFIG["optimizer"]["args"]
    opt = mx.optimizer.create(CONFIG["optimizer"]["name"], **args)
    step = parallel.TrainStep(
        net, loss_fn, opt,
        mesh=parallel.make_mesh(dp=1, devices=jax.devices()[:1]))
    got_loss = float(step(ids, labels).asnumpy())
    by_kind, flips, sq_err, sq_want = {}, {}, 0.0, 0.0
    lr = args["learning_rate"]
    for i, state in zip(step._train_idx, step._states):
        name = structural[id(step._plist[i])]
        assert state[-1].dtype == jnp.float32, name     # the master weight
        got = np.asarray(state[-1]) - before[name]
        want = _adamw_first_step(
            before[name], grads.pop(name), lr, args["wd"],
            beta2=args["beta2"])
        err, size = _norm(got - want), _norm(want)
        kind = _kind(name)
        if err / size >= by_kind.get(kind, 0.0):
            by_kind[kind] = err / size
            moved = np.abs(want) > lr / 2
            flipped = moved & (np.sign(got) != np.sign(want))
            share = float(flipped.sum() / max(moved.sum(), 1))
            flips[kind] = {
                "share": share, "two_root_share": 2 * share ** 0.5,
                "sq_err_share": float(((got - want)[flipped] ** 2).sum()
                                      / max(err ** 2, 1e-30))}
        sq_err, sq_want = sq_err + err ** 2, sq_want + size ** 2
    assert not grads, sorted(grads)          # every leaf was a trained one
    step.sync_params_to_net()
    gauges = parallel.publish_load(net)
    del step, net, before
    gc.collect()
    return {"loss": got_loss, "reference_loss": want_loss,
            "param_change_rel_err": float(np.sqrt(sq_err / sq_want)),
            "param_change_rel_err_by_kind": by_kind,
            "sign_flips_by_kind": flips, "gauges": gauges}


def test_one_trainstep_step_against_adamw_on_the_reference_gradients():
    """Runs first: TrainStep's 18 bytes a parameter need the chip to
    itself."""
    for seed in SEEDS:
        READINGS[f"step_{T_GRAD}.seed{seed}"] = _one_step(seed)
    for seed in SEEDS:
        r = READINGS[f"step_{T_GRAD}.seed{seed}"]
        # the step's own forward, bf16, on the batch's labels (a loss of
        # ~10.1 on one sequence of 1,024, not the check labels' ~6.5)
        assert abs(r["loss"] - r["reference_loss"]) < CHECK["step_loss_atol"], r
        worst = max(r["param_change_rel_err_by_kind"].values())
        assert worst < CHECK["param_change_rtol"], r
        even = MODEL["num_experts"] / MODEL["routed_experts"]
        assert abs(r["gauges"]["moe.held_share"] - even) < 0.05, r
        # the reading is the gradient's signs, not rounding of the update:
        # where a kind reads a tenth or more, the flipped elements carry
        # most of its squared error
        for kind, rel in r["param_change_rel_err_by_kind"].items():
            if rel >= 0.1:
                assert r["sign_flips_by_kind"][kind]["sq_err_share"] >= 0.75, \
                    (kind, r)


# ------------------------------------------------- the forward and jax.grad --
@pytest.fixture(scope="module")
def net():
    """The cell's net as the benchmark builds it (every layer recomputed),
    float32, without gradient buffers; ``_draw`` makes its parameters."""
    net, _, _ = family.build(MODEL)
    net.collect_params().setattr("grad_req", "null")
    return net


def _draw(net, seed, cast=None):
    """Every parameter anew from ``seed``, in one program; the arrays in
    the order of ``_functional``'s names, converted as ``net.cast`` would
    (the expert layers' counts stay int32)."""
    mx.random.seed(seed)
    net.initialize(force_reinit=True)
    materialize(net.collect_params().values())
    params = net._collect_params_with_prefix()
    arrays = [params[n].data()._data for n in sorted(params)]
    if cast is None:
        return arrays
    return [a.astype(cast) if jnp.issubdtype(a.dtype, jnp.floating)
            and not n.endswith(".expert_bias") else a
            for n, a in zip(sorted(params), arrays)]


def _aux(name):
    return name.endswith((".load", ".expert_bias"))


def _trained(names):
    return [n for n in names if not _aux(n)]


def _functional(net):
    """(names, logits(arrays, ids), grads) of the net as TrainStep runs it."""
    params = net._collect_params_with_prefix()
    names = sorted(params)
    plist = [params[n] for n in names]
    leaves, tree = _flatten_nd((NDArray(jnp.zeros((1, 1), jnp.int32)),))

    def logits(arrays, ids):
        outs = functional_call(net, plist, arrays, tree, [ids],
                               jax.random.key(0), True, FunctionalState())
        return outs[0]

    floating = [i for i, n in enumerate(names) if not _aux(n)]

    def grads(arrays, ids, labels):
        def loss_of(some):
            full = list(arrays)
            for i, a in zip(floating, some):
                full[i] = a
            return _mean_loss(logits(full, ids), labels)
        return jax.grad(loss_of)([arrays[i] for i in floating])
    return names, logits, grads


def _reference_params(names, arrays):
    """``(params, buffers)`` of the reference from the net's arrays."""
    params, buffers = {}, {}
    for n, a in zip(names, arrays):
        if n.endswith(".expert_bias"):
            buffers[n] = a.astype(jnp.float32)
        elif not _aux(n):
            params.update(reference.of_net(n, a.astype(jnp.float32), WIDTHS))
    return params, buffers


def test_float32_logits_and_loss_at_8192(net):
    names, logits, _ = _functional(net)
    arrays = _draw(net, SEEDS[0])
    ids, labels = _ids(T, 0)
    want, _ = _reference_forward(*_reference_params(names, arrays), ids)
    want_loss = float(_mean_loss(want, labels))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(logits)(arrays, ids)
    got_loss = float(_mean_loss(got, labels))
    err = jnp.abs(got - want).max(axis=-1)[0]            # a position's worst
    READINGS["float32_8192"] = {
        "logits_max_abs_err": float(err.max()),
        "logits_median_abs_err": float(jnp.median(err)),
        "positions_over_1e-4": int((err > 1e-4).sum()),
        "logits_std": float(want.std()),
        "loss": got_loss, "reference_loss": want_loss}
    # both sides float32 with six-pass matmuls; they differ in the order of
    # sums and in the flash kernel's online softmax.  A near-tie between a
    # token's 6th and 7th expert may still fall the other way on the two
    # sides and move that token's logits by far more than rounding: so the
    # limit is on all positions but a handful
    assert int((err > 1e-4).sum()) <= 8, READINGS
    assert float(jnp.median(err)) < 2e-5, READINGS
    assert abs(got_loss - want_loss) < 1e-5, READINGS
    assert abs(want_loss - np.log(MODEL["vocab_size"])) < 1.0


def test_float32_gradients_at_1024(net):
    names, _, grads = _functional(net)
    arrays = _draw(net, SEEDS[0])
    ids, labels = _ids(T_GRAD, 1)
    _, want = _reference_grads(*_reference_params(names, arrays), ids, labels)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(grads)(arrays, ids, labels)
    by_kind = {}
    for n, g in zip(_trained(names), got):
        w = want[n]
        rel = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        by_kind[_kind(n)] = max(by_kind.get(_kind(n), 0.0), rel)
        assert float(jnp.abs(w).max()) > 0, n
    READINGS["float32_grads_1024"] = by_kind
    # |g - ref| / |ref| in the 2-norm over a leaf, float32 sums in another
    # order: the limit is well above rounding and far below a wrong term
    assert max(by_kind.values()) < 3e-4, by_kind
    assert len(got) == 10 + 4 * 13 + 3


def _differing(got, want):
    """The share of a layer's (token, expert) assignments that are in one
    side's top-k and not in the other's."""
    e = MODEL["routed_experts"]
    a = (np.asarray(got)[..., None] == np.arange(e)).any(-2)
    b = (np.asarray(want).reshape(np.asarray(got).shape)[..., None]
         == np.arange(e)).any(-2)
    return float((a & ~b).sum() / a.sum())


def _bf16_readings(net, seed, jitted):
    """The bf16 net (weights rounded to bf16, one bf16 pass a matmul, as the
    cell runs it) against the reference on the SAME rounded weights, as the
    configuration states it ("sound") and with a part in bf16: the loss on
    the reference's check labels at 8,192 positions (what ``kinds/train.py``
    compares) and the logits' RMS error."""
    names = _functional(net)[0]
    arrays = _draw(net, seed, jnp.bfloat16)
    ids, _ = _ids(T, seed + 1)
    want, _ = _reference_forward(*_reference_params(names, arrays), ids)
    made = jnp.argmax(want, axis=-1).astype(jnp.int32)
    want_loss = float(_mean_loss(want, made))
    out = {"reference_loss": want_loss}
    for what in PRECISIONS:
        with precision(CONTROLS[what], net), \
                jax.default_matmul_precision("bfloat16"):
            got = jitted[what](arrays, ids)
        out[what] = {
            "loss_err": abs(float(_mean_loss(got, made)) - want_loss),
            "logits_rms_err": float(jnp.sqrt(jnp.mean((got - want) ** 2))),
            "argmax_agree": float((jnp.argmax(got, -1) == made).mean())}
        del got
    return out


def _outside(r):
    """The limits of ``check`` that the readings ``r`` break."""
    broken = []
    if r["loss_err"] > CHECK["loss_atol"]:
        broken.append("loss_atol")
    if r["logits_rms_err"] > CHECK["logits_rms_atol"]:
        broken.append("logits_rms_atol")
    return broken


def test_bf16_net_within_the_stated_limits_and_the_precisions_below_not(net):
    """Every seed's sound reading inside every limit, every seed's reading
    of the router in bf16 and of every float32 part in bf16 at once
    (``combined``) outside at least one; which limit a control
    breaks is recorded (``check.why``).  The rotary angles and the latent
    norm's moments in bf16 move the logits by less than the seeds do, and
    no limit tells them apart: the test holds only that each control ran."""
    _, logits, _ = _functional(net)
    # one program a precision, traced under its own precision: a function
    # object each, because jit's cache is keyed by the function
    jitted = {what: jax.jit(lambda a, i: logits(a, i)) for what in PRECISIONS}
    for seed in READ_SEEDS:
        READINGS[f"bf16_{T}.seed{seed}"] = _bf16_readings(net, seed, jitted)
    READINGS["controls_outside"] = {
        what: [_outside(READINGS[f"bf16_{T}.seed{seed}"][what])
               for seed in READ_SEEDS] for what in PRECISIONS[1:]}
    for seed in READ_SEEDS:
        r = READINGS[f"bf16_{T}.seed{seed}"]
        assert _outside(r["sound"]) == [], (seed, r)
        for what in PRECISIONS[1:]:
            if what in TOLD_APART:
                assert _outside(r[what]) != [], (seed, what, r)
            else:
                assert r[what]["logits_rms_err"] \
                    != r["sound"]["logits_rms_err"], (seed, what, r)
