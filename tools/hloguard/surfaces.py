"""The audited surface: which lowered modules hloguard lints.

Two kinds of surface, one text format:

* **Entrypoint surfaces** — every registered costguard entry point,
  lowered under the same ``JAX_PLATFORMS=cpu`` bring-up costguard uses
  (zero device steps, zero XLA compiles: hloguard reads the *lowered*
  StableHLO, which is cheaper than costguard's compiled reports and
  preserves user dtypes — the CPU backend's bf16-emulation converts
  only appear post-compile and would otherwise make every bf16 entry
  look like an f32 leak).
* **Pallas export surfaces** — the kernels lowered for the REAL TPU
  platform via ``jax.export`` (client-side Mosaic, runs on a CPU host —
  the test_fused_conv_lowering.py pattern): flash attention and the
  selective scan, which the benchmark's ``phi4_mini_flash`` cell runs,
  and the fused norm+relu+conv and ragged paged-attention kernels, which
  no cell runs.  These carry the ``tpu_custom_call`` payloads the
  custom-call census counts.  Unique-versus-total is what a kernel costs
  before the first step: every ``pallas_call`` a program reaches is
  traced and lowered to Mosaic again, however many of them are the same
  kernel (PERF.md 6, PR 28: 5 s of ``setup_s`` for six scans).

Builds are memoized per process: the hloguard gate, the costguard gate,
and chaos both walk the full surface in one tier-1 run, and lowering is
deterministic, so paying the ~20 s more than once buys nothing.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Callable, Dict, List, Tuple

#: hloguard-only surfaces (beyond the costguard registry), in gate
#: order: name → builder, filled by ``@_export_surface`` below
_EXPORT_BUILDERS: Dict[str, Callable[[], "Surface"]] = {}

_MEMO: Dict[str, "Surface"] = {}


@dataclasses.dataclass
class Surface:
    """One audited name: its program texts and golden metadata."""
    name: str
    meta: dict
    programs: List[Tuple[str, str]]    # [(program name, lowered text)]


def export_names() -> List[str]:
    return list(_EXPORT_BUILDERS)


def names() -> List[str]:
    from tools.costguard import entrypoints
    return sorted(entrypoints.names()) + export_names()


def source_of(name: str) -> Path:
    """File a surface's findings anchor to (SARIF locations)."""
    if name in _EXPORT_BUILDERS:
        return Path(__file__).resolve()
    from tools.costguard import entrypoints
    return entrypoints.source_of(name)


def build(name: str) -> Surface:
    if name not in _MEMO:
        builder = _EXPORT_BUILDERS.get(name)
        _MEMO[name] = (builder() if builder is not None
                       else _build_entrypoint(name))
    return _MEMO[name]


def _export_surface(name: str, **meta):
    """Register ``fn() -> [(tag, lowered text)]`` as export surface
    ``name``."""
    def register(fn):
        def builder() -> Surface:
            return Surface(
                name=name,
                meta=dict(meta, kind="export", platforms=["tpu"]),
                programs=[(f"{name}/{tag}", text) for tag, text in fn()])
        _EXPORT_BUILDERS[name] = builder
        return fn
    return register


def _build_entrypoint(name: str) -> Surface:
    from tools.costguard import entrypoints
    eb = entrypoints.build(name)
    programs = [(p.name, p.lowered if isinstance(p.lowered, str)
                 else p.lowered.as_text()) for p in eb.programs]
    return Surface(name=name, meta=dict(eb.meta, kind="entrypoint"),
                   programs=programs)


def _export_tpu(fn, *avals) -> str:
    import jax
    return jax.export.export(jax.jit(fn),
                             platforms=["tpu"])(*avals).mlir_module()


def _two_layer_step(layer):
    """``(loss, grads)`` of ``layer`` applied twice to its first argument,
    through ONE Python call site, as a model's loop over its layers does
    (Mosaic payloads embed the call site's location)."""
    import jax
    import jax.numpy as jnp

    def step(x, *rest):
        def loss(x, *rest):
            y = x
            for _ in range(2):
                y = layer(y, *rest).astype(x.dtype)
            return y.astype(jnp.float32).sum()
        return jax.value_and_grad(loss, argnums=tuple(
            range(1 + len(rest))))(x, *rest)
    return step


@_export_surface(
    "pallas_flash_attention_tpu", precision="bf16",
    model="causal grouped-query flash attention, forward and fused "
          "backward kernel, two layers (ops/pallas/flash_attention.py)",
    geometry="q bf16[40,256,64], k/v bf16[20,256,64], blocks of 128: "
             "phi4_mini_flash's 40/20 heads of 64 at a short sequence")
def _flash_attention_programs():
    """``_flash_fwd`` and ``_flash_bwd`` are ``jax.jit``s, so two layers
    share one lowering of each of the two kernels, the forward and the
    fused backward: total 2, unique 2.  A third payload is a kernel traced
    again."""
    import functools

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((40, 256, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((20, 256, 64), jnp.bfloat16)
    layer = functools.partial(flash_attention, causal=True, block_q=128,
                              block_k=128, interpret=False)
    return [("two_layers", _export_tpu(_two_layer_step(layer), q, kv, kv))]


@_export_surface(
    "pallas_selective_scan_tpu", precision="f32",
    model="selective scan, forward and backward kernel, two layers "
          "(ops/pallas/selective_scan.py)",
    geometry="x bf16[1,256,512], dt f32[1,256,512], a f32[512,16], "
             "b/c bf16[1,256,16], chunk 128")
def _selective_scan_programs():
    """``_scan_fwd`` and ``_scan_bwd`` are ``jax.jit``s, so two layers
    share one lowering of each kernel (PR 28): total 2, unique 2.  A
    third payload is a kernel traced again."""
    import functools

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas.selective_scan import selective_scan

    sds = jax.ShapeDtypeStruct
    x = sds((1, 256, 512), jnp.bfloat16)
    dt = sds((1, 256, 512), jnp.float32)
    a = sds((512, 16), jnp.float32)
    bc = sds((1, 256, 16), jnp.bfloat16)
    layer = functools.partial(selective_scan, chunk=128, interpret=False)
    return [("two_layers", _export_tpu(_two_layer_step(layer),
                                       x, dt, a, bc, bc))]


@_export_surface(
    "pallas_fused_conv_tpu", precision="bf16",
    model="fused norm+relu+conv tower 3x3/3x3/1x1",
    geometry="x bf16[2,16,16,64], 64ch")
def _fused_conv_programs():
    """A three-layer fused-conv tower in ONE program: two 3x3 layers at
    the identical geometry plus a 1x1 head.  The census must see
    pallas_unique < pallas_total — the repeated 3x3 instantiation is
    the dedup headroom the ~150-kernel A/B blowup is made of."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu.ops.pallas.fused_conv as fc

    sds = jax.ShapeDtypeStruct
    x = sds((2, 16, 16, 64), jnp.bfloat16)
    scale = sds((64,), jnp.float32)
    shift = sds((64,), jnp.float32)
    w3 = sds((3, 3, 64, 64), jnp.bfloat16)
    w1 = sds((1, 1, 64, 64), jnp.bfloat16)

    def tower(x, scale, shift, wa, wb, wh):
        # the repeated layers run through ONE call site, the model-zoo
        # shape (a Python loop over per-layer params): Mosaic payloads
        # embed call-site locations, so same-geometry instantiations
        # dedupe byte-exactly only when the site is shared — exactly
        # how the real ~150-kernel tower would (or would fail to)
        for w in (wa, wb):
            x = fc.norm_relu_conv(x, scale, shift, w, interpret=False)
        return fc.norm_relu_conv(x, scale, shift, wh, interpret=False)

    return [("tower", _export_tpu(tower, x, scale, shift, w3, w3, w1))]


@_export_surface(
    "pallas_paged_attention_tpu", precision="f32",
    model="ragged paged decode attention (ops/pallas/paged_attention.py)")
def _paged_attention_programs():
    """The ragged paged-attention decode kernel at the llm decode-grid
    geometry (8 slots, 8h x 4d — the ``_llm_parts`` head layout) and at
    a second, larger-page geometry: two distinct Mosaic instantiations
    of ONE kernel, so the census pins total 2 / unique 2 and any
    accidental re-instantiation at an existing geometry shows up as
    total moving without unique."""
    import functools

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_pallas)

    sds = jax.ShapeDtypeStruct
    programs = []
    for tag, (slots, pages_per_seq, page_size, heads, head_dim) in (
            ("decode_8h4", (8, 16, 16, 8, 4)),
            ("decode_8h32", (8, 4, 32, 8, 32))):
        n_pages = slots * pages_per_seq
        q = sds((slots, heads, head_dim), jnp.float32)
        pages = sds((n_pages, page_size, heads, head_dim), jnp.float32)
        tables = sds((slots, pages_per_seq), jnp.int32)
        lengths = sds((slots,), jnp.int32)
        fn = functools.partial(paged_decode_attention_pallas,
                               interpret=False)
        programs.append((tag, _export_tpu(fn, q, pages, pages, tables,
                                          lengths)))
    return programs
