"""The audited surface: which lowered modules hloguard lints.

Two kinds of surface, one text format:

* **Entrypoint surfaces** — every registered costguard entry point,
  lowered under the same ``JAX_PLATFORMS=cpu`` bring-up costguard uses
  (zero device steps, zero XLA compiles: hloguard reads the *lowered*
  StableHLO, which is cheaper than costguard's compiled reports and
  preserves user dtypes — the CPU backend's bf16-emulation converts
  only appear post-compile and would otherwise make every bf16 entry
  look like an f32 leak).
* **Pallas export surfaces** — the fused norm+relu+conv and ragged
  paged-attention kernels lowered for the REAL TPU platform via
  ``jax.export`` (client-side Mosaic, runs on a CPU host — the
  test_fused_conv_lowering.py pattern).  These carry the
  ``tpu_custom_call`` payloads the custom-call census counts: the
  unique-vs-total instantiation metric ROADMAP item 4's ~150-kernel
  compile blowup needs.

Builds are memoized per process: the hloguard gate, the costguard gate,
and chaos both walk the full surface in one tier-1 run, and lowering is
deterministic, so paying the ~20 s more than once buys nothing.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict, List, Tuple

#: hloguard-only surfaces (beyond the costguard registry), in gate order
EXPORT_SURFACES = ("pallas_fused_conv_tpu", "pallas_paged_attention_tpu")

_MEMO: Dict[str, "Surface"] = {}


@dataclasses.dataclass
class Surface:
    """One audited name: its program texts and golden metadata."""
    name: str
    meta: dict
    programs: List[Tuple[str, str]]    # [(program name, lowered text)]


def names() -> List[str]:
    from tools.costguard import entrypoints
    return sorted(entrypoints.names()) + list(EXPORT_SURFACES)


def source_of(name: str) -> Path:
    """File a surface's findings anchor to (SARIF locations)."""
    if name in EXPORT_SURFACES:
        return Path(__file__).resolve()
    from tools.costguard import entrypoints
    return entrypoints.source_of(name)


def build(name: str) -> Surface:
    if name not in _MEMO:
        if name == "pallas_fused_conv_tpu":
            _MEMO[name] = _build_fused_conv()
        elif name == "pallas_paged_attention_tpu":
            _MEMO[name] = _build_paged_attention()
        else:
            _MEMO[name] = _build_entrypoint(name)
    return _MEMO[name]


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


def _build_fused_conv() -> Surface:
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

    text = _export_tpu(tower, x, scale, shift, w3, w3, w1)
    meta = {"kind": "export", "platforms": ["tpu"], "precision": "bf16",
            "model": "fused norm+relu+conv tower 3x3/3x3/1x1",
            "geometry": "x bf16[2,16,16,64], 64ch"}
    return Surface(name="pallas_fused_conv_tpu", meta=meta,
                   programs=[("pallas_fused_conv_tpu/tower", text)])


def _build_paged_attention() -> Surface:
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
        text = _export_tpu(fn, q, pages, pages, tables, lengths)
        programs.append((f"pallas_paged_attention_tpu/{tag}", text))
    meta = {"kind": "export", "platforms": ["tpu"], "precision": "f32",
            "model": "ragged paged decode attention "
                     "(ops/pallas/paged_attention.py)"}
    return Surface(name="pallas_paged_attention_tpu", meta=meta,
                   programs=programs)
