"""Interval dataflow interpreter over traced jaxprs (qlint pass 1).

``analyze_fn(fn, args, input_ranges=...)`` traces ``fn`` with
``jax.make_jaxpr`` and abstractly interprets the jaxpr, propagating one
:class:`~repro.analysis.intervals.Interval` per value. The interpreter
understands ``pallas_call`` natively:

  * the kernel body jaxpr is entered with per-operand intervals seeded
    from the wrapper-level dataflow (so e.g. ``sa / alpha`` folding is
    seen by the analysis);
  * kernel refs (inputs, outputs, scratch) are modeled as mutable cells
    holding intervals; ``get``/``swap`` read/replace them, ``cond``
    (``pl.when``) forks the cell store per branch and joins afterwards;
  * the **minor (innermost) grid axis is interpreted exactly**: the body
    runs once per index with ``program_id`` pinned to that index and the
    cell store carried across steps — this models accumulator revisits
    (the K-group loop of the quantized GEMMs) without widening, so
    ``pl.when(k == 0)`` resets resolve precisely. All other grid axes
    are abstracted to their full ``[0, extent-1]`` index range.

Soundness notes
---------------
* Unknown primitives fall back to the output dtype's full range and are
  recorded as ``unknown-prim`` events (never silently precise).
* Integer ``add/sub/mul/dot_general/reduce_sum/cumsum`` whose result
  interval escapes the result dtype emit an ``int-overflow`` event; the
  *unclamped* interval keeps propagating so downstream magnitudes stay
  worst-case. ``shift_left`` wrap is the one sanctioned wrap idiom (the
  int4 nibble unpack shifts through the sign bit on purpose): it clamps
  to the dtype range without an event.
* Integer-narrowing ``convert_element_type`` whose input interval does
  not fit the target dtype emits ``narrowing-convert`` (lint rule R2);
  in-range narrowing (e.g. unpacked nibbles int32->int8) is clean.
* A ``swap`` that covers the whole ref replaces the cell interval; a
  partial one (a slice store, e.g. the chunked activation quantization)
  joins it with the old interval. Reads of never-written cells fall back
  to the dtype range and emit ``uninit-read``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import numpy as np
from jax._src import source_info_util
from jax.extend.core import Literal

from .intervals import Interval

ARITH_PRIMS = frozenset(
    {"add", "sub", "mul", "dot_general", "reduce_sum", "cumsum"})
PASSTHRU_PRIMS = frozenset({
    "reshape", "transpose", "squeeze", "slice", "dynamic_slice",
    "broadcast_in_dim", "rev", "gather", "copy", "copy_p", "real",
    "expand_dims", "stop_gradient", "device_put", "sharding_constraint",
})
COMPARE_PRIMS = frozenset({"eq", "ne", "lt", "le", "gt", "ge"})
MAX_GRID_ITERS = 1024

DATA = "data"  # input_ranges sentinel: seed from dtype, not array values


def _where(eqn) -> str:
    try:
        return source_info_util.summarize(eqn.source_info)
    except Exception:  # pragma: no cover - best effort only
        return "<unknown>"


@dataclasses.dataclass(frozen=True)
class Event:
    """Analyzer-emitted fact consumed by lint rules / certificates."""

    kind: str  # int-overflow | narrowing-convert | uninit-read | unknown-prim
    prim: str
    detail: str
    interval: Interval | None
    where: str


@dataclasses.dataclass(frozen=True)
class EqnRecord:
    """One interpreted equation with its value intervals (lint input)."""

    prim: str
    scope: str  # "" = wrapper level, "pallas:<name>" = kernel body
    out_dtype: str
    out_interval: Interval
    in_dtypes: tuple
    in_intervals: tuple
    params: dict
    where: str
    eqn_id: int  # identity token: same eqn re-interpreted -> same id


@dataclasses.dataclass
class PallasRecord:
    """Structural info for one pallas_call (lint rules R4/R5)."""

    name: str
    grid: tuple
    grid_mapping: Any
    operand_intervals: list  # seeds, aligned with eqn invars


@dataclasses.dataclass
class Analysis:
    records: list
    events: list
    pallas: list
    out_intervals: list

    @property
    def int_accum_bound(self) -> float:
        """Max |value| over integer arithmetic results — the worst-case
        magnitude any integer accumulator chain can reach."""
        b = 0.0
        for r in self.records:
            if r.prim in ARITH_PRIMS and np.dtype(r.out_dtype).kind in "iu":
                b = max(b, r.out_interval.max_abs())
        return b

    def events_of(self, *kinds) -> list:
        return [e for e in self.events if e.kind in kinds]


class _Ref:
    """Identity handle for a pallas ref; the cell store maps it to an
    Interval (or None = never written)."""

    __slots__ = ("dtype",)

    def __init__(self, dtype):
        self.dtype = dtype


def _is_ref_aval(aval) -> bool:
    return hasattr(aval, "inner_aval")


def _aval_dtype(aval):
    return getattr(aval, "inner_aval", aval).dtype


class _Interp:
    def __init__(self):
        self.records: list[EqnRecord] = []
        self.events: list[Event] = []
        self.pallas: list[PallasRecord] = []
        self._scope: list[str] = [""]
        self._grid: list[tuple] = []  # (grid, minor_index | None)

    # -- plumbing -----------------------------------------------------------

    def note(self, kind, eqn, detail, interval=None):
        self.events.append(
            Event(kind, eqn.primitive.name, detail, interval, _where(eqn)))

    def read(self, env, atom):
        if isinstance(atom, Literal):
            v = atom.val
            if hasattr(v, "shape"):
                return Interval.of_array(v)
            return Interval.point(v)
        return env[atom]

    def run(self, jaxpr, consts, invals, store) -> list:
        env: dict = {}
        for v, c in zip(jaxpr.constvars, consts):
            env[v] = (Interval.of_array(c) if hasattr(c, "shape")
                      else Interval.point(c))
        for v, val in zip(jaxpr.invars, invals):
            env[v] = val
        for eqn in jaxpr.eqns:
            ins = [self.read(env, a) for a in eqn.invars]
            outs = self.eqn(eqn, ins, store)
            for ov, o in zip(eqn.outvars, outs):
                env[ov] = o
        return [self.read(env, a) for a in jaxpr.outvars]

    # -- equation dispatch --------------------------------------------------

    def eqn(self, eqn, ins, store) -> list:
        name = eqn.primitive.name
        handler = getattr(type(self), f"_p_{name}", None)
        if handler is None:
            handler = _GENERIC.get(name)
        if handler is None:
            outs = [Interval.from_dtype(_aval_dtype(v.aval))
                    for v in eqn.outvars]
            self.note("unknown-prim", eqn, f"no transfer fn for '{name}'")
        else:
            outs = handler(self, eqn, ins, store)
        outs = list(outs)
        # overflow surveillance on the integer arithmetic chain
        if name in ARITH_PRIMS and eqn.outvars:
            dt = _aval_dtype(eqn.outvars[0].aval)
            if np.dtype(dt).kind in "iu" and outs and \
                    not outs[0].fits_dtype(dt):
                self.note("int-overflow", eqn,
                          f"{name} result {outs[0]} exceeds {np.dtype(dt)}",
                          outs[0])
        for ov, o in zip(eqn.outvars, outs):
            if isinstance(o, Interval):
                self.records.append(EqnRecord(
                    name, self._scope[-1], str(_aval_dtype(ov.aval)), o,
                    tuple(str(_aval_dtype(a.aval)) for a in eqn.invars),
                    tuple(i for i in ins if isinstance(i, Interval)),
                    eqn.params, _where(eqn), id(eqn)))
        return outs

    # -- structured control flow -------------------------------------------

    def _call(self, eqn, ins, store):
        sub = None
        for k in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            sub = eqn.params.get(k, sub)
            if sub is not None:
                break
        if sub is None:  # pragma: no cover
            return [Interval.from_dtype(_aval_dtype(v.aval))
                    for v in eqn.outvars]
        if hasattr(sub, "jaxpr"):  # ClosedJaxpr
            return self.run(sub.jaxpr, sub.consts, ins, store)
        return self.run(sub, (), ins, store)

    def _p_jit(self, eqn, ins, store):
        # jnp's floor_divide wrapper lowers to div + a sign/rem-coupled
        # correction that a non-relational domain can't prune (it would
        # widen r // G to [r//G - 1, r//G], flagging every grouped-head
        # index map). Floor division IS interval-exact — compute it.
        if (eqn.params.get("name") == "floor_divide" and len(ins) == 2
                and all(isinstance(i, Interval) for i in ins)
                and np.dtype(_out_dtype(eqn)).kind in "iu"):
            return [ins[0].floordiv(ins[1])]
        return self._call(eqn, ins, store)

    _p_closed_call = _call
    _p_remat2 = _call

    def _p_custom_jvp_call(self, eqn, ins, store):
        sub = eqn.params.get("call_jaxpr")
        if sub is None:
            return [Interval.from_dtype(_aval_dtype(v.aval))
                    for v in eqn.outvars]
        return self.run(sub.jaxpr, sub.consts, ins, store)

    _p_custom_vjp_call = _p_custom_jvp_call

    def _p_cond(self, eqn, ins, store):
        branches = eqn.params["branches"]
        idx, ops = ins[0], ins[1:]
        if idx.is_point() and 0 <= int(idx.lo) < len(branches):
            take = [branches[int(idx.lo)]]
        else:
            lo = max(int(idx.lo), 0)
            hi = min(int(idx.hi), len(branches) - 1)
            take = [branches[i] for i in range(lo, hi + 1)] or list(branches)
        out_join: list | None = None
        stores = []
        for br in take:
            st = dict(store)
            outs = self.run(br.jaxpr, br.consts, ops, st)
            stores.append(st)
            if out_join is None:
                out_join = outs
            else:
                out_join = [a.union(b) if isinstance(a, Interval)
                            and isinstance(b, Interval) else a
                            for a, b in zip(out_join, outs)]
        # join cell stores (None = bottom, absorbed by union)
        keys = set().union(*[set(s) for s in stores]) if stores else set()
        for k in keys:
            vals = [s.get(k) for s in stores]
            have = [v for v in vals if v is not None]
            if len(have) < len(vals):  # some branch left it unwritten:
                have.append(store.get(k))  # pre-state survives
            have = [v for v in have if v is not None]
            store[k] = _union_all(have) if have else None
        return out_join or []

    # -- pallas -------------------------------------------------------------

    def _p_program_id(self, eqn, ins, store):
        axis = eqn.params["axis"]
        if not self._grid:
            return [Interval.point(0)]
        grid, minor_val = self._grid[-1]
        if axis == len(grid) - 1 and minor_val is not None:
            return [Interval.point(minor_val)]
        return [Interval(0.0, float(max(grid[axis] - 1, 0)))]

    def _p_num_programs(self, eqn, ins, store):
        grid = self._grid[-1][0] if self._grid else (1,)
        return [Interval.point(grid[eqn.params["axis"]])]

    def _p_get(self, eqn, ins, store):
        ref = ins[0]
        assert isinstance(ref, _Ref), "get on non-ref"
        val = store.get(ref)
        if val is None:
            self.note("uninit-read", eqn,
                      "read of never-written output/scratch ref")
            val = Interval.from_dtype(ref.dtype)
        return [val]

    def _p_swap(self, eqn, ins, store):
        ref, val = ins[0], ins[1]
        assert isinstance(ref, _Ref), "swap on non-ref"
        old = store.get(ref)
        full = (math.prod(eqn.invars[1].aval.shape)
                == math.prod(eqn.invars[0].aval.shape))
        store[ref] = val if full or old is None else old.union(val)
        return [old if old is not None else Interval.from_dtype(ref.dtype)]

    def _p_pallas_call(self, eqn, ins, store):
        gm = eqn.params["grid_mapping"]
        body = eqn.params["jaxpr"]
        name = str(eqn.params.get("name_and_src_info", "kernel")).split(" ")[0]
        grid = tuple(int(g) for g in gm.grid) or (1,)
        n_idx, n_in = gm.num_index_operands, gm.num_inputs
        n_out = gm.num_outputs
        self.pallas.append(PallasRecord(name, grid, gm, list(ins)))

        handles, st = [], {}
        for i, v in enumerate(body.invars):
            h = _Ref(_aval_dtype(v.aval))
            handles.append(h)
            st[h] = ins[i] if i < n_idx + n_in else None
        minor = grid[-1]
        if minor > MAX_GRID_ITERS:
            self.note("unknown-prim", eqn,
                      f"minor grid axis {minor} > {MAX_GRID_ITERS}: "
                      "iterating abstractly (bounds may be loose)")
        self._scope.append(f"pallas:{name}")
        consts = getattr(body, "constvars", ())
        cvals = [Interval.from_dtype(_aval_dtype(c.aval)) for c in consts]
        try:
            for k in range(min(minor, MAX_GRID_ITERS)):
                self._grid.append(
                    (grid, k if minor <= MAX_GRID_ITERS else None))
                try:
                    self.run(body, cvals, handles, st)
                finally:
                    self._grid.pop()
        finally:
            self._scope.pop()

        outs = []
        for j in range(n_out):
            h = handles[n_idx + n_in + j]
            outs.append(st[h] if st[h] is not None
                        else Interval.from_dtype(h.dtype))
        return outs


def _union_all(vals):
    out = vals[0]
    for v in vals[1:]:
        out = out.union(v)
    return out


# ---------------------------------------------------------------------------
# Generic (store-free) transfer functions
# ---------------------------------------------------------------------------


def _out_dtype(eqn):
    return _aval_dtype(eqn.outvars[0].aval)


def _h_dot_general(self, eqn, ins, store):
    (lc, _), _ = eqn.params["dimension_numbers"]
    lhs_shape = eqn.invars[0].aval.shape
    n = 1
    for d in lc:
        n *= lhs_shape[d]
    return [(ins[0] * ins[1]).sum_n(n)]


def _h_reduce_sum(self, eqn, ins, store):
    shape = eqn.invars[0].aval.shape
    n = 1
    for d in eqn.params["axes"]:
        n *= shape[d]
    return [ins[0].sum_n(n)]


def _h_cumsum(self, eqn, ins, store):
    n = eqn.invars[0].aval.shape[eqn.params["axis"]]
    return [ins[0].sum_n(n)]


def _h_convert(self, eqn, ins, store):
    src = _aval_dtype(eqn.invars[0].aval)
    dst = _out_dtype(eqn)
    iv = ins[0]
    if np.dtype(dst).kind in "iu" and not iv.fits_dtype(dst):
        if np.dtype(src).kind in "iu":
            self.note("narrowing-convert", eqn,
                      f"{np.dtype(src)}->{np.dtype(dst)} may truncate "
                      f"{iv}", iv)
        iv = Interval.from_dtype(dst)
    return [iv]


def _h_shift_left(self, eqn, ins, store):
    dt = _out_dtype(eqn)
    if ins[1].is_point():
        s = float(2 ** int(ins[1].lo))
        iv = Interval(ins[0].lo * s, ins[0].hi * s)
        if iv.fits_dtype(dt):
            return [iv]
    # wrapping shift (sanctioned idiom: int4 nibble unpack) -> dtype range
    return [Interval.from_dtype(dt)]


def _h_shift_right_logical(self, eqn, ins, store):
    iv = ins[0]
    if iv.lo >= 0:
        return [iv.shift_right(ins[1])]
    return [Interval.from_dtype(_out_dtype(eqn))]  # sign bits shift in


def _h_div(self, eqn, ins, store):
    if np.dtype(_out_dtype(eqn)).kind in "iu":
        return [ins[0].intdiv(ins[1])]
    return [ins[0].truediv(ins[1])]


def _h_rem(self, eqn, ins, store):
    """XLA rem truncates: the result's sign follows the dividend and
    |result| < |divisor| (and <= |dividend|)."""
    a, b = ins[0], ins[1]
    m = b.max_abs()
    lo = 0.0 if a.lo >= 0 else max(-m, a.lo)
    hi = 0.0 if a.hi <= 0 else min(m, a.hi)
    return [Interval(lo, hi)]


def _h_compare(self, eqn, ins, store):
    a, b = ins[0], ins[1]
    name = eqn.primitive.name
    if name in ("lt", "gt", "le", "ge"):
        x, y = (a, b) if name in ("lt", "le") else (b, a)
        strict = name in ("lt", "gt")
        if (x.hi < y.lo) or (not strict and x.hi <= y.lo):
            return [Interval.point(1)]
        if (x.lo > y.hi) or (strict and x.lo >= y.hi):
            return [Interval.point(0)]
    elif name == "eq":
        if a.is_point() and b.is_point():
            return [Interval.point(1 if a.lo == b.lo else 0)]
        if a.hi < b.lo or a.lo > b.hi:
            return [Interval.point(0)]
    elif name == "ne":
        if a.is_point() and b.is_point():
            return [Interval.point(0 if a.lo == b.lo else 1)]
        if a.hi < b.lo or a.lo > b.hi:
            return [Interval.point(1)]
    return [Interval(0.0, 1.0)]


def _h_bool_and(self, eqn, ins, store):
    if str(_out_dtype(eqn)) != "bool":
        return [Interval.from_dtype(_out_dtype(eqn))]
    a, b = ins[0], ins[1]
    if a.hi == 0 or b.hi == 0:
        return [Interval.point(0)]
    if a.lo == 1 and b.lo == 1:
        return [Interval.point(1)]
    return [Interval(0.0, 1.0)]


def _h_bool_or(self, eqn, ins, store):
    if str(_out_dtype(eqn)) != "bool":
        return [Interval.from_dtype(_out_dtype(eqn))]
    a, b = ins[0], ins[1]
    if a.lo == 1 or b.lo == 1:
        return [Interval.point(1)]
    if a.hi == 0 and b.hi == 0:
        return [Interval.point(0)]
    return [Interval(0.0, 1.0)]


def _h_bool_not(self, eqn, ins, store):
    if str(_out_dtype(eqn)) != "bool":
        return [Interval.from_dtype(_out_dtype(eqn))]
    a = ins[0]
    if a.is_point():
        return [Interval.point(0 if a.lo else 1)]
    return [Interval(0.0, 1.0)]


def _h_integer_pow(self, eqn, ins, store):
    y = eqn.params["y"]
    a = ins[0]
    if y == 2 or (y % 2 == 0 and y >= 0):
        m = a.max_abs() ** y
        lo = 0.0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi)) ** y
        return [Interval(lo, m)]
    if y >= 0:
        return [a.monotone(lambda v: v ** y)]
    return [Interval.top()]


def _safe_mono(f, lo_dom=-math.inf):
    def h(self, eqn, ins, store):
        a = ins[0]
        lo = max(a.lo, lo_dom)
        hi = max(a.hi, lo_dom)
        try:
            return [Interval(f(lo), f(hi))]
        except (ValueError, OverflowError):
            return [Interval.top()]
    return h


def _h_exp(self, eqn, ins, store):
    def e(v):
        if v == math.inf:
            return math.inf
        try:
            return math.exp(v)
        except OverflowError:
            return math.inf
    return [ins[0].monotone(e)]


def _h_iota(self, eqn, ins, store):
    shape = eqn.outvars[0].aval.shape
    d = eqn.params["dimension"]
    return [Interval(0.0, float(max(shape[d] - 1, 0)))]


_GENERIC: dict[str, Callable] = {
    "add": lambda s, e, i, st: [i[0] + i[1]],
    "sub": lambda s, e, i, st: [i[0] - i[1]],
    "mul": lambda s, e, i, st: [i[0] * i[1]],
    "neg": lambda s, e, i, st: [-i[0]],
    "abs": lambda s, e, i, st: [i[0].abs()],
    "sign": lambda s, e, i, st: [Interval(-1.0, 1.0)],
    "max": lambda s, e, i, st: [i[0].maximum(i[1])],
    "min": lambda s, e, i, st: [i[0].minimum(i[1])],
    "clamp": lambda s, e, i, st: [i[1].clamp(i[0], i[2])],
    "round": lambda s, e, i, st: [i[0].monotone(
        lambda v: v if not math.isfinite(v) else float(round(v)))],
    "floor": lambda s, e, i, st: [i[0].monotone(
        lambda v: v if not math.isfinite(v) else math.floor(v))],
    "ceil": lambda s, e, i, st: [i[0].monotone(
        lambda v: v if not math.isfinite(v) else math.ceil(v))],
    "nextafter": lambda s, e, i, st: [i[0]],
    "reduce_max": lambda s, e, i, st: [i[0]],
    "reduce_min": lambda s, e, i, st: [i[0]],
    "reduce_and": lambda s, e, i, st: [Interval(0.0, 1.0)],
    "reduce_or": lambda s, e, i, st: [Interval(0.0, 1.0)],
    "reduce_prod": lambda s, e, i, st: [Interval.top()],
    "argmax": lambda s, e, i, st: [Interval(
        0.0, float(max(np.prod([e.invars[0].aval.shape[d]
                                for d in e.params["axes"]]) - 1, 0)))],
    "select_n": lambda s, e, i, st: [_union_all(i[1:])],
    "concatenate": lambda s, e, i, st: [_union_all(i)],
    "pad": lambda s, e, i, st: [i[0].union(i[1])],
    "dynamic_update_slice": lambda s, e, i, st: [i[0].union(i[1])],
    "rem": _h_rem,
    "dot_general": _h_dot_general,
    "reduce_sum": _h_reduce_sum,
    "cumsum": _h_cumsum,
    "convert_element_type": _h_convert,
    "shift_left": _h_shift_left,
    "shift_right_arithmetic":
        lambda s, e, i, st: [i[0].shift_right(i[1])],
    "shift_right_logical": _h_shift_right_logical,
    "div": _h_div,
    "eq": _h_compare, "ne": _h_compare, "lt": _h_compare,
    "le": _h_compare, "gt": _h_compare, "ge": _h_compare,
    "and": _h_bool_and, "or": _h_bool_or, "not": _h_bool_not,
    "xor": lambda s, e, i, st: [Interval(0.0, 1.0)]
        if str(_out_dtype(e)) == "bool"
        else [Interval.from_dtype(_out_dtype(e))],
    "integer_pow": _h_integer_pow,
    "exp": _h_exp,
    "exp2": _h_exp,
    "log": _safe_mono(lambda v: math.log(v) if v > 0 else -math.inf),
    "sqrt": _safe_mono(lambda v: math.sqrt(max(v, 0.0))),
    "rsqrt": lambda s, e, i, st: [Interval(0.0, math.inf)],
    "tanh": lambda s, e, i, st: [Interval(-1.0, 1.0)],
    "logistic": lambda s, e, i, st: [Interval(0.0, 1.0)],
    "erf": lambda s, e, i, st: [Interval(-1.0, 1.0)],
    "is_finite": lambda s, e, i, st: [Interval(0.0, 1.0)],
    "iota": _h_iota,
    "square": lambda s, e, i, st: [Interval(
        0.0 if i[0].lo <= 0 <= i[0].hi
        else min(abs(i[0].lo), abs(i[0].hi)) ** 2,
        i[0].max_abs() ** 2)],
}
for _p in PASSTHRU_PRIMS:
    _GENERIC[_p] = lambda s, e, i, st: [i[0]]


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def analyze_jaxpr(closed_jaxpr, in_intervals) -> Analysis:
    """Interpret a ClosedJaxpr with the given input intervals."""
    it = _Interp()
    outs = it.run(closed_jaxpr.jaxpr, closed_jaxpr.consts,
                  list(in_intervals), {})
    return Analysis(it.records, it.events, it.pallas, outs)


def analyze_fn(fn, *args, input_ranges: dict | None = None) -> Analysis:
    """Trace ``fn(*args)`` and run the interval pass.

    ``args`` must be a flat sequence of arrays/scalars. Each input is
    seeded with the tight interval of its concrete values (appropriate
    for static operands: weights, scales, row counts); pass
    ``input_ranges={i: Interval(..) | interp.DATA}`` to widen input
    ``i`` to a contract range (``DATA`` = full dtype range) for
    data-dependent operands like activations.
    """
    closed = jax.make_jaxpr(fn)(*args)
    ranges = input_ranges or {}
    seeds = []
    for i, a in enumerate(args):
        r = ranges.get(i)
        if isinstance(r, Interval):
            seeds.append(r)
        elif r == DATA:
            seeds.append(Interval.from_dtype(np.asarray(a).dtype))
        else:
            seeds.append(Interval.of_array(a))
    return analyze_jaxpr(closed, seeds)


def analyze_index_map(index_map_closed_jaxpr, grid, prefetch_ranges,
                      n_scalar_args: int) -> list:
    """Interval-evaluate a BlockSpec index map over the whole grid.

    ``prefetch_ranges`` seed the trailing scalar-prefetch ref operands
    (e.g. ragged row counts, seeded from the wrapper's documented
    ``[0, C]`` clamp contract). Returns output block-index intervals.
    """
    it = _Interp()
    jaxpr = index_map_closed_jaxpr.jaxpr
    seeds: list = [Interval(0.0, float(max(g - 1, 0))) for g in grid]
    store: dict = {}
    invals: list = []
    for i, v in enumerate(jaxpr.invars):
        if _is_ref_aval(v.aval):
            h = _Ref(_aval_dtype(v.aval))
            pi = i - n_scalar_args
            store[h] = (prefetch_ranges[pi]
                        if 0 <= pi < len(prefetch_ranges)
                        else Interval.from_dtype(_aval_dtype(v.aval)))
            invals.append(h)
        else:
            invals.append(seeds[i] if i < len(seeds) else
                          Interval.from_dtype(_aval_dtype(v.aval)))
    return it.run(jaxpr, index_map_closed_jaxpr.consts, invals, store)
