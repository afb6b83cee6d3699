"""Lint rules over analyzed kernel traces (qlint pass 2).

Each rule is a function ``(entry, analysis) -> list[Finding]`` run over
the :class:`~repro.analysis.interp.Analysis` of one registered kernel
(:mod:`.registry`). The shipped rules:

``int-dot-preferred-type``
    Every integer-input ``dot_general`` must carry
    ``preferred_element_type=jnp.int32`` — without it XLA accumulates the
    MXU partials in the operand dtype (int8!) and saturates silently.
``narrowing-convert``
    An integer->integer ``convert_element_type`` whose statically derived
    value interval does not fit the target dtype (interval-aware: the int4
    nibble unpack's int32->int8 with derived range [-8, 7] is clean).
``int-overflow``
    Integer add/mul/dot/reduce whose interval escapes its result dtype —
    the direct "accumulation can overflow before it completes" signal.
``float-accum-on-is-path``
    On kernels registered as integer-scale (Eq. 2): any float-input
    ``dot_general`` in the kernel body, or more than ONE distinct
    int->float convert (the single-final-convert property IS the paper's
    speedup; per-group converts mean the Eq. 1 bottleneck crept back in).
``blockspec-divisibility``
    Block shapes must divide the (padded) operand arrays — a mismatch
    means silent partial tiles diverging from the TPU path.
``index-map-bounds``
    Interval-evaluates every BlockSpec index map over the whole grid
    (ragged scalar-prefetch row-count refs seeded from the wrapper's
    documented [0, C] clamp contract); block indices must stay within the
    operand's tile range.
``uninit-read``
    A kernel body read of an output/scratch ref no grid step has written.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from jax.experimental import pallas as pl

from .interp import Analysis, analyze_index_map
from .intervals import Interval


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    kernel: str
    message: str
    where: str = ""

    def __str__(self) -> str:
        loc = f" [{self.where}]" if self.where else ""
        return f"{self.kernel}: {self.rule}: {self.message}{loc}"


def _is_int(dtype_str: str) -> bool:
    try:
        return np.dtype(dtype_str).kind in "iu"
    except TypeError:
        return False


def rule_int_dot_preferred(entry, an: Analysis) -> list:
    out, seen = [], set()
    for r in an.records:
        if r.prim != "dot_general" or r.eqn_id in seen:
            continue
        seen.add(r.eqn_id)
        if not all(_is_int(d) for d in r.in_dtypes):
            continue
        pet = r.params.get("preferred_element_type")
        if pet is None or np.dtype(pet) != np.dtype(np.int32):
            out.append(Finding(
                "int-dot-preferred-type", entry.name,
                f"integer dot_general accumulates in "
                f"{pet or r.out_dtype}, not int32", r.where))
    return out


def rule_events(entry, an: Analysis) -> list:
    """narrowing-convert / int-overflow / uninit-read events -> findings."""
    out, seen = [], set()
    for e in an.events:
        if e.kind not in ("narrowing-convert", "int-overflow", "uninit-read"):
            continue
        key = (e.kind, e.prim, e.where, e.detail)
        if key in seen:
            continue
        seen.add(key)
        out.append(Finding(e.kind, entry.name, e.detail, e.where))
    return out


def rule_float_accum_on_is_path(entry, an: Analysis) -> list:
    if not getattr(entry, "integer_scale", False):
        return []
    out, seen = [], set()
    n_converts = set()
    for r in an.records:
        if not r.scope.startswith("pallas"):
            continue
        if r.prim == "dot_general" and r.eqn_id not in seen:
            seen.add(r.eqn_id)
            if not all(_is_int(d) for d in r.in_dtypes):
                out.append(Finding(
                    "float-accum-on-is-path", entry.name,
                    "float dot_general inside an integer-scale kernel "
                    "body (Eq. 2 requires the int8 MXU path)", r.where))
        if (r.prim == "convert_element_type" and r.in_dtypes
                and _is_int(r.in_dtypes[0])
                and np.dtype(r.in_dtypes[0]).itemsize >= 4
                and not _is_int(r.out_dtype)):
            n_converts.add(r.eqn_id)
    if len(n_converts) > 1:
        out.append(Finding(
            "float-accum-on-is-path", entry.name,
            f"{len(n_converts)} distinct int->float converts in the kernel "
            "body; Eq. 2 allows ONE (the epilogue) — per-group converts "
            "are the Eq. 1 bottleneck"))
    return out


def _block_dims(bm) -> list:
    """Block extent per operand dim (a squeezed dim counts as 1)."""
    return [1 if isinstance(b, pl.Squeezed) else int(b.block_size)
            for b in bm.block_shape]


def rule_blockspec_divisibility(entry, an: Analysis) -> list:
    out = []
    for p in an.pallas:
        for i, bm in enumerate(p.grid_mapping.block_mappings):
            shape = bm.array_aval.shape
            for d, (s, b) in enumerate(zip(shape, _block_dims(bm))):
                if b and s % b:
                    out.append(Finding(
                        "blockspec-divisibility", entry.name,
                        f"{p.name} operand {i} dim {d}: array extent {s} "
                        f"not divisible by block {b}"))
    return out


def rule_index_map_bounds(entry, an: Analysis) -> list:
    out = []
    prefetch = list(getattr(entry, "prefetch_ranges", ()) or ())
    for p in an.pallas:
        for i, bm in enumerate(p.grid_mapping.block_mappings):
            imj = bm.index_map_jaxpr
            shape = bm.array_aval.shape
            blocks = _block_dims(bm)
            try:
                idx = analyze_index_map(imj, p.grid, prefetch, len(p.grid))
            except Exception as e:  # analysis gap, surface rather than hide
                out.append(Finding(
                    "index-map-bounds", entry.name,
                    f"{p.name} operand {i}: index map not analyzable "
                    f"({type(e).__name__}: {e})"))
                continue
            for d, iv in enumerate(idx):
                if d >= len(shape) or not isinstance(iv, Interval):
                    continue
                b = blocks[d] if d < len(blocks) and blocks[d] else 1
                hi = -(-shape[d] // b) - 1  # cdiv - 1
                if not iv.within(0, hi):
                    out.append(Finding(
                        "index-map-bounds", entry.name,
                        f"{p.name} operand {i} dim {d}: block index "
                        f"{iv} escapes [0, {hi}]"))
    return out


RULES = (
    rule_int_dot_preferred,
    rule_events,
    rule_float_accum_on_is_path,
    rule_blockspec_divisibility,
    rule_index_map_bounds,
)


def run_rules(entry, analysis: Analysis) -> list:
    out = []
    for rule in RULES:
        out.extend(rule(entry, analysis))
    return out
