"""Layer 2a: an int32 value-range abstract interpreter over jaxprs.

Traces the REAL compiled ingest entry points (the adapter ``update``
of every registered variant) and propagates the ``validate_block``
preconditions through the jaxpr as intervals, flagging any signed
add/sub/mul whose result interval can leave int32 (SK201).  The goal
is a machine-checked version of the PR 7 invariant: *counters never
wrap* — every count/error accumulation either stays bounded by plain
interval arithmetic or goes through the saturating ``sat_add``.

Abstract domain (DESIGN.md §16): each jaxpr var maps to an
:class:`Ival` — an integer interval ``[lo, hi]`` plus one relational
refinement, the **wtag**: "every element of this array is a signed sum
of a *disjoint* subset of the block's weights".  ``validate_block``
bounds the block's summed |weight| by int32 max, so any wtag value
lives in ``[-WSUM, WSUM]`` no matter how it was segment-summed,
prefix-summed, masked or permuted.  The tag is preserved by the
subset/rearrangement operations (where-with-zero, cumsum, segment
scatter-add onto zeros, sort, gather, neg, ...) and dropped by
anything that could double-count (adding two wtag values).

Two relational patterns are recognized on top of plain intervals:

* **sat_add** — ``a + clip(b, -IMAX - min(a,0), IMAX - max(a,0))``
  (the exact jaxpr ``repro.sketch.state.sat_add`` emits).  Interval
  arithmetic alone cannot see that the clip bounds depend on ``a``;
  the matcher proves the result lies in ``[-IMAX, IMAX]``.
* **loop-guard refinement** — a while cond of the shape
  ``i < n [& ...]`` bounds the carried ``i`` inside the body, so
  ``i + 1`` style counters don't widen to infinity.

Everything else is sound-but-conservative: unknown primitives return
the full range of their dtype and are never flagged themselves (only
add/sub/mul and the add-performing reductions are overflow sites).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.extend.core import Literal

from .findings import Finding, call_body, relpath

INT32_MIN = -(2**31)
INT32_MAX = 2**31 - 1
IMAX = 2**31 - 1
# "infinite" sentinel bounds for unknown values (kept finite so interval
# arithmetic stays in python ints without overflow concerns)
BIG = 2**127


@dataclasses.dataclass(frozen=True)
class Ival:
    """Interval plus three relational refinements.

    * ``wtag`` — elements are signed sums of MUTUALLY DISJOINT subsets
      of the validated block's weights (|block weight sum| <= W), so
      any further disjoint aggregation (reduce_sum, scatter-add onto
      zeros) stays in [-W, W].  The block weights themselves are the
      base case (singleton subsets).  Dropped by gather/broadcast
      (duplication could double-count) and by adding two wtag values.
    * ``psrc`` — the id of the cumsum equation this value's elements
      are prefix sums of (or 0); ``sub`` of two same-psrc values is a
      contiguous-range weight sum, bounded [-W, W] regardless of the
      positions subtracted.
    * ``rsum`` — elements are each a signed contiguous-range sum of
      one ordering of the block weights (so individually in [-W, W]).
      Per-element property: survives gather/broadcast/select.  Summing
      rsum values back up uses the documented D1 assumption (DESIGN.md
      §16): the repo only ever sums range sums taken at segment-head
      positions, which are disjoint.
    """
    lo: int
    hi: int
    wtag: bool = False
    psrc: int = 0          # 0 = no prefix source
    rsum: bool = False

    def join(self, other: "Ival") -> "Ival":
        return Ival(min(self.lo, other.lo), max(self.hi, other.hi),
                    self.wtag and other.wtag,
                    self.psrc if self.psrc == other.psrc else 0,
                    self.rsum and other.rsum)

    def contains(self, other: "Ival") -> bool:
        return self.lo <= other.lo and other.hi <= self.hi

    @property
    def is_zero(self) -> bool:
        return self.lo == 0 and self.hi == 0

    def same_tags(self, other: "Ival") -> bool:
        return (self.wtag == other.wtag and self.psrc == other.psrc
                and self.rsum == other.rsum)


def const_ival(x) -> Ival:
    arr = np.asarray(x)
    if arr.size == 0:
        return Ival(0, 0)
    if arr.dtype.kind in "iub":
        return Ival(int(arr.min()), int(arr.max()))
    return Ival(-BIG, BIG)


def dtype_ival(aval) -> Ival:
    try:
        dt = np.dtype(aval.dtype) if hasattr(aval, "dtype") else None
    except TypeError:
        dt = None  # extended dtypes (PRNG keys) have no numpy range
    if dt is None:
        return Ival(-BIG, BIG)
    if dt.kind == "b":
        return Ival(0, 1)
    if dt.kind in "iu":
        info = np.iinfo(dt)
        return Ival(int(info.min), int(info.max))
    return Ival(-BIG, BIG)


def _tdiv(x: int, y: int) -> int:
    """Truncate-toward-zero integer division (XLA int div semantics)."""
    q = abs(x) // abs(y)
    return q if (x >= 0) == (y >= 0) else -q


def _is_signed_int(aval) -> bool:
    try:
        return np.dtype(aval.dtype).kind == "i"
    except Exception:
        return False


def _int_bounds(aval) -> Tuple[int, int]:
    info = np.iinfo(np.dtype(aval.dtype))
    return int(info.min), int(info.max)


class _Analyzer:
    """One abstract interpretation of a closed jaxpr."""

    def __init__(self, entry: str, wsum: int = IMAX):
        self.entry = entry
        self.wsum = min(int(wsum), IMAX)
        self.findings: List[Finding] = []
        self._seen_sites = set()
        self.unknown_prims = set()

    # -- findings ---------------------------------------------------------

    def _site(self, eqn) -> Tuple[str, int]:
        """file:line of the first user frame under src/repro (falls back
        to the entry-point id)."""
        try:
            from jax._src import source_info_util as siu
            for fr in siu.user_frames(eqn.source_info):
                fn = fr.file_name
                if "/repro/" in fn and "/analysis/" not in fn \
                        and "site-packages" not in fn:
                    return relpath(fn), int(fr.start_line)
        except Exception:
            pass
        return self.entry, 0

    def flag(self, eqn, res: Ival, lo: int, hi: int):
        path, line = self._site(eqn)
        key = (path, line, eqn.primitive.name)
        if key in self._seen_sites:
            return
        self._seen_sites.add(key)
        self.findings.append(Finding(
            rule="SK201", path=path, line=line,
            symbol=eqn.primitive.name,
            message=f"`{eqn.primitive.name}` on signed int can reach "
                    f"[{res.lo}, {res.hi}] outside "
                    f"[{lo}, {hi}] under the validate_block "
                    f"preconditions; route it through sat_add or bound "
                    f"the operands"))

    def _check(self, eqn, res: Ival, report: bool) -> Ival:
        """Flag a result leaving its signed-int dtype range; clamp so the
        analysis continues from the concrete (wrapped-or-saturated)
        envelope instead of cascading."""
        aval = eqn.outvars[0].aval
        if not _is_signed_int(aval):
            return res
        lo, hi = _int_bounds(aval)
        if res.lo < lo or res.hi > hi:
            if report:
                self.flag(eqn, res, lo, hi)
            return Ival(lo, hi, False)
        return res

    # -- pattern: sat_add -------------------------------------------------

    def _matches_sat_add(self, eqn, defs) -> Optional[Ival]:
        """add(a, g) where g = clip(b, -IMAX - min(a,0), IMAX - max(a,0))."""
        a, g = eqn.invars
        for a, g in ((eqn.invars[0], eqn.invars[1]),
                     (eqn.invars[1], eqn.invars[0])):
            d = defs.get(id(g))
            if d is None:
                continue
            lo_v = hi_v = None
            if d.params.get("name") == "clip" and call_body(d) is not None \
                    and len(d.invars) == 3:
                _, lo_v, hi_v = d.invars
            elif d.primitive.name == "min" and len(d.invars) == 2:
                # inlined clip: min(hi, max(b, lo)) in either operand order
                for hi_c, inner in ((d.invars[0], d.invars[1]),
                                    (d.invars[1], d.invars[0])):
                    di = defs.get(id(inner))
                    if di is not None and di.primitive.name == "max":
                        hi_v = hi_c
                        lo_v = (di.invars[1]
                                if not self._is_lit(di.invars[1])
                                else di.invars[0])
                        break
            if lo_v is None or hi_v is None:
                continue
            if self._is_headroom(hi_v, a, "max", IMAX, defs) and \
                    self._is_headroom(lo_v, a, "min", -IMAX, defs):
                return Ival(-IMAX, IMAX)
        return None

    @staticmethod
    def _is_lit(v) -> bool:
        return isinstance(v, Literal)

    def _is_headroom(self, v, a, minmax: str, const: int, defs) -> bool:
        """Is ``v`` = const - min/max(a, 0) (possibly via broadcast)?"""
        v = self._skip_shape_ops(v, defs)
        d = defs.get(id(v))
        if d is None or d.primitive.name != "sub":
            return False
        c, m = d.invars
        if not (self._is_lit(c) and int(np.asarray(c.val)) == const):
            return False
        m = self._skip_shape_ops(m, defs)
        dm = defs.get(id(m))
        if dm is None or dm.primitive.name != minmax:
            return False
        x, zero = dm.invars
        if self._is_lit(x):
            x, zero = zero, x
        if not (self._is_lit(zero) and int(np.asarray(zero.val)) == 0):
            return False
        return self._same_var(x, a, defs)

    @staticmethod
    def _join_inert(cases: Sequence[Ival]) -> Ival:
        """Join of select/concat/pad/scatter cases where a literally-zero
        case is inert for every tag (empty subset / empty range / the
        prefix before position 0)."""
        res = cases[0]
        for c in cases[1:]:
            res = Ival(min(res.lo, c.lo), max(res.hi, c.hi))
        live = [c for c in cases if not c.is_zero]
        if not live:
            return res
        wtag = all(c.wtag for c in live)
        rsum = all(c.rsum for c in live)
        psrcs = {c.psrc for c in live}
        psrc = psrcs.pop() if len(psrcs) == 1 else 0
        return dataclasses.replace(res, wtag=wtag, psrc=psrc, rsum=rsum)

    def _matches_guarded_inc(self, eqn, ins, defs, env) -> Optional[Ival]:
        """add(i, cast(i < n)): a counter that freezes at its bound —
        if i < n the sum is <= n, otherwise i is unchanged, so the
        result stays in [i.lo, max(i.hi, n.hi)] (the batched while_loop
        ``i + active`` idiom in bank.residual_phase)."""
        for a_v, g_v in ((eqn.invars[0], eqn.invars[1]),
                         (eqn.invars[1], eqn.invars[0])):
            g = self._skip_shape_ops(g_v, defs)
            d = defs.get(id(g))
            if d is None or d.primitive.name not in ("lt", "and"):
                continue
            if d.primitive.name == "and":
                # active = (i < n) & other: the conjunction only shrinks
                # the set of incremented lanes
                lts = [defs.get(id(self._skip_shape_ops(x, defs)))
                       for x in d.invars]
                d = next((x for x in lts
                          if x is not None and x.primitive.name == "lt"),
                         None)
                if d is None:
                    continue
            lhs, rhs = d.invars
            if not self._same_var(lhs, a_v, defs):
                continue
            if isinstance(rhs, Literal):
                n_iv = const_ival(rhs.val)
            else:
                n_iv = env.get(id(self._skip_shape_ops(rhs, defs)))
                if n_iv is None:
                    n_iv = env.get(id(rhs))
            if n_iv is None:
                continue
            a_iv = ins[0] if a_v is eqn.invars[0] else ins[1]
            return Ival(a_iv.lo, max(a_iv.hi, n_iv.hi))
        return None

    def _skip_shape_ops(self, v, defs):
        while True:
            d = defs.get(id(v))
            if d is not None and d.primitive.name in (
                    "broadcast_in_dim", "reshape", "convert_element_type",
                    "squeeze"):
                v = d.invars[0]
            else:
                return v

    def _same_var(self, x, a, defs) -> bool:
        x = self._skip_shape_ops(x, defs)
        a = self._skip_shape_ops(a, defs)
        if self._is_lit(x) or self._is_lit(a):
            return False
        return x is a or (getattr(x, "count", None) is not None
                          and x.count == getattr(a, "count", -2)
                          and x.aval == a.aval)

    # -- the transfer function --------------------------------------------

    def run(self, jaxpr, in_ivals: Sequence[Ival],
            report: bool = True) -> List[Ival]:
        env: Dict[int, Ival] = {}
        defs: Dict[int, Any] = {}

        def read(v) -> Ival:
            if isinstance(v, Literal):
                return const_ival(v.val)
            return env.get(id(v), dtype_ival(v.aval))

        def write(v, ival: Ival):
            env[id(v)] = ival

        if len(jaxpr.invars) != len(in_ivals):
            raise ValueError(
                f"{self.entry}: {len(jaxpr.invars)} invars, "
                f"{len(in_ivals)} ivals")
        for v, iv in zip(jaxpr.invars, in_ivals):
            write(v, iv)
        for v in jaxpr.constvars:
            write(v, dtype_ival(v.aval))

        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                defs[id(ov)] = eqn
            outs = self._eqn(eqn, [read(v) for v in eqn.invars], defs,
                             env, report)
            for ov, oi in zip(eqn.outvars, outs):
                write(ov, oi)
        return [read(v) for v in jaxpr.outvars]

    def _eqn(self, eqn, ins: List[Ival], defs, env,
             report: bool) -> List[Ival]:
        p = eqn.primitive.name
        W = self.wsum

        def out_n() -> int:
            return len(eqn.outvars)

        if p == "add":
            sat = self._matches_sat_add(eqn, defs)
            if sat is not None:
                a, b = ins
                res = Ival(max(sat.lo, a.lo + b.lo), min(sat.hi, a.hi + b.hi))
                return [res]
            inc = self._matches_guarded_inc(eqn, ins, defs, env)
            if inc is not None:
                return [inc]
            a, b = ins
            res = Ival(a.lo + b.lo, a.hi + b.hi)
            return [self._check(eqn, res, report)]
        if p == "sub":
            a, b = ins
            if a.psrc and a.psrc == b.psrc:
                # difference of two prefix sums of the SAME cumsum over
                # block weights = a contiguous-range weight sum, bounded
                # by the block's total |weight| regardless of position
                return [Ival(-W, W, rsum=True)]
            res = Ival(a.lo - b.hi, a.hi - b.lo)
            return [self._check(eqn, res, report)]
        if p == "mul":
            a, b = ins
            cands = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
            res = Ival(min(cands), max(cands))
            # masking by a {0,1} operand zeroes elements: every tag
            # survives (0 is an empty subset / empty range / a valid
            # "prefix before the start")
            if a.wtag or a.rsum or a.psrc:
                a, b = b, a
            mask01 = 0 <= a.lo and a.hi <= 1
            tagged = self._check(eqn, res, report)
            if mask01:
                return [dataclasses.replace(
                    tagged, wtag=b.wtag, psrc=b.psrc, rsum=b.rsum)]
            return [tagged]
        if p == "neg":
            a = ins[0]
            return [Ival(-a.hi, -a.lo, a.wtag, 0, a.rsum)]
        if p in ("max", "min"):
            a, b = ins
            f = max if p == "max" else min
            # min/max against a constant 0 selects each element or zero:
            # all tags survive (zero is inert for every tag)
            res = Ival(f(a.lo, b.lo), f(a.hi, b.hi))
            if b.is_zero or (a.is_zero and not b.is_zero):
                keep = a if b.is_zero else b
                return [dataclasses.replace(
                    res, wtag=keep.wtag, psrc=keep.psrc, rsum=keep.rsum)]
            return [dataclasses.replace(
                res, wtag=a.wtag and b.wtag,
                psrc=a.psrc if a.psrc == b.psrc else 0,
                rsum=a.rsum and b.rsum)]
        if p in ("sign",):
            return [Ival(-1, 1)]
        if p == "abs":
            a = ins[0]
            lo = 0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi))
            return [Ival(lo, max(abs(a.lo), abs(a.hi)), a.wtag, 0, a.rsum)]
        if p == "div":
            a, b = ins
            if b.lo > 0 or b.hi < 0:
                cands = [_tdiv(x, y) for x in (a.lo, a.hi)
                         for y in (b.lo, b.hi)]
                return [Ival(min(cands), max(cands))]
            m = max(abs(a.lo), abs(a.hi))
            return [Ival(-m, m)]
        if p == "rem":
            a, b = ins
            m = max(abs(b.lo), abs(b.hi), 1) - 1
            m = min(m, max(abs(a.lo), abs(a.hi)))
            return [Ival(-m, m)]
        if p in ("eq", "ne", "lt", "le", "gt", "ge", "not", "is_finite",
                 "le_to", "lt_to"):
            return [Ival(0, 1)]
        if p in ("and", "or", "xor"):
            aval = eqn.outvars[0].aval
            if np.dtype(aval.dtype).kind == "b":
                return [Ival(0, 1)]
            return [dtype_ival(aval)]  # bitwise: defined, never flagged
        if p in ("reduce_and", "reduce_or"):
            return [Ival(0, 1)]
        if p in ("reduce_min", "reduce_max", "cummax", "cummin"):
            a = ins[0]
            return [Ival(a.lo, a.hi, a.wtag, a.psrc, a.rsum)]
        if p == "cumsum":
            a = ins[0]
            if a.wtag:
                # prefix sums of disjoint subsets: each element a growing
                # union, bounded by the block total; tag the cumsum site
                # so same-source differences become range sums
                return [Ival(-W, W, False, id(eqn), True)]
            if a.rsum:
                # D1: range sums are only ever accumulated at disjoint
                # segment positions in this repo (DESIGN.md §16)
                return [Ival(-W, W, False, id(eqn), False)]
            n = self._reduction_size(eqn)
            res = Ival(min(a.lo * n, 0) if a.lo < 0 else a.lo,
                       max(a.hi * n, 0) if a.hi > 0 else a.hi)
            return [self._check(eqn, res, report)]
        if p == "reduce_sum":
            a = ins[0]
            if a.wtag or a.rsum:
                # disjoint-subset sums collapse to one subset sum (wtag);
                # range sums via assumption D1
                return [Ival(-W, W, True)]
            n = self._reduction_size(eqn)
            res = Ival(min(a.lo * n, 0) if a.lo < 0 else a.lo,
                       max(a.hi * n, 0) if a.hi > 0 else a.hi)
            return [self._check(eqn, res, report)]
        if p in ("argmax", "argmin"):
            n = self._axis_size(eqn)
            return [Ival(0, max(n - 1, 0))]
        if p == "iota":
            dim = eqn.params.get("dimension", 0)
            shape = eqn.params.get("shape", (1,))
            n = shape[dim] if dim < len(shape) else 1
            return [Ival(0, max(n - 1, 0))]
        if p in ("reshape", "squeeze", "expand_dims", "transpose", "rev",
                 "copy", "stop_gradient", "slice", "dynamic_slice"):
            # pure permutations/subsets: every tag survives
            a = ins[0]
            return [a] * out_n()
        if p in ("broadcast_in_dim", "gather"):
            # may DUPLICATE elements: per-element tags (psrc, rsum)
            # survive, the array-level disjointness tag (wtag) does not
            a = ins[0]
            return [dataclasses.replace(a, wtag=False)] * out_n()
        if p == "convert_element_type":
            a = ins[0]
            tgt = dtype_ival(eqn.outvars[0].aval)
            if tgt.contains(a):
                return [a]
            return [tgt]
        if p == "bitcast_convert_type":
            return [dtype_ival(eqn.outvars[0].aval)]
        if p == "select_n":
            return [self._join_inert(ins[1:])]
        if p == "concatenate":
            return [self._join_inert(ins)]
        if p == "pad":
            return [self._join_inert(ins[:2])]
        if p in ("dynamic_update_slice",):
            a, upd = ins[0], ins[1]
            res = a.join(upd)
            return [dataclasses.replace(res, wtag=a.wtag and upd.wtag)]
        if p == "sort":
            # multi-operand sort permutes every operand identically
            return list(ins)
        if p == "top_k":
            a = ins[0]
            n = self._axis_size(eqn)
            return [a, Ival(0, max(n - 1, 0))]
        if p == "scatter":
            op, upd = ins[0], ins[2]
            return [self._join_inert([op, upd])]
        if p in ("scatter-add", "scatter_add"):
            op, upd = ins[0], ins[2]
            if (upd.wtag or upd.rsum) and op.is_zero:
                # segment sums onto a zero base: colliding indices merge
                # disjoint subsets (wtag, sound) or disjoint head ranges
                # (rsum, assumption D1) — either way bounded by the block
                return [Ival(-W, W, True)]
            n = self._update_count(eqn)
            res = Ival(op.lo + min(n * upd.lo, 0),
                       op.hi + max(n * upd.hi, 0))
            return [self._check(eqn, res, report)]
        if p in ("shift_left",):
            a, b = ins
            sh = min(max(b.hi, 0), 63)
            cands = [a.lo << min(max(b.lo, 0), 63), a.lo << sh,
                     a.hi << min(max(b.lo, 0), 63), a.hi << sh]
            res = Ival(min(cands), max(cands))
            return [self._check(eqn, res, report)]
        if p in ("shift_right_arithmetic", "shift_right_logical"):
            a, b = ins
            if p == "shift_right_logical" and a.lo < 0:
                return [dtype_ival(eqn.outvars[0].aval)]
            cands = []
            for x in (a.lo, a.hi):
                for s in (max(b.lo, 0), min(max(b.hi, 0), 63)):
                    cands.append(x >> s)
            return [Ival(min(cands), max(cands))]
        if p == "integer_pow":
            a = ins[0]
            y = eqn.params.get("y", 1)
            cands = [a.lo**y, a.hi**y] + ([0] if a.lo <= 0 <= a.hi else [])
            res = Ival(min(cands), max(cands))
            return [self._check(eqn, res, report)]
        if p == "clamp":
            lo_i, x, hi_i = ins
            lo = min(max(x.lo, lo_i.lo), hi_i.lo)
            hi = min(max(x.hi, lo_i.hi), hi_i.hi)
            return [Ival(lo, hi, x.wtag, x.psrc, x.rsum)]
        if p == "while":
            return self._while(eqn, ins, report)
        if p == "scan":
            return self._scan(eqn, ins, report)
        if p == "cond":
            branches = eqn.params["branches"]
            outs = None
            for br in branches:
                bo = self.run_sub(br.jaxpr, ins[1:], report)
                outs = bo if outs is None else [
                    a.join(b) for a, b in zip(outs, bo)]
            return outs
        inner = call_body(eqn)
        if inner is not None:
            return self.run_sub(inner, ins, report)
        if p in ("random_bits", "random_split", "random_wrap",
                 "random_unwrap", "random_seed"):
            return [dtype_ival(v.aval) for v in eqn.outvars]
        # unknown: conservative, never flagged
        self.unknown_prims.add(p)
        return [dtype_ival(v.aval) for v in eqn.outvars]

    def run_sub(self, jaxpr, ins, report) -> List[Ival]:
        sub = _Analyzer(self.entry, self.wsum)
        sub._seen_sites = self._seen_sites  # shared site de-dup
        sub.findings = self.findings        # accumulate in place
        sub.unknown_prims = self.unknown_prims
        outs = sub.run(jaxpr, list(ins), report)
        return outs

    # -- loops ------------------------------------------------------------

    def _cond_refinements(self, cond_jaxpr, carry_vars) -> Dict[int, Tuple]:
        """Bounds implied by the cond being True: follow `and` back from
        the output, collect lt/le/gt/ge comparisons carry-var vs value."""
        jaxpr = cond_jaxpr.jaxpr if hasattr(cond_jaxpr, "jaxpr") else \
            cond_jaxpr
        defs = {}
        for eqn in jaxpr.eqns:
            for ov in eqn.outvars:
                defs[id(ov)] = eqn
        carry_ids = {id(v): i for i, v in enumerate(jaxpr.invars)}
        out = {}
        stack = [jaxpr.outvars[0]]
        seen = set()
        while stack:
            v = stack.pop()
            if id(v) in seen or isinstance(v, Literal):
                continue
            seen.add(id(v))
            d = defs.get(id(v))
            if d is None:
                continue
            pn = d.primitive.name
            if pn == "and":
                stack.extend(d.invars)
            elif pn in ("lt", "le", "gt", "ge") and len(d.invars) == 2:
                a, b = d.invars
                ia = carry_ids.get(id(a))
                ib = carry_ids.get(id(b))
                out.setdefault(pn, []).append((ia, a, ib, b))
        return out, jaxpr

    def _while(self, eqn, ins: List[Ival], report: bool) -> List[Ival]:
        cond_j = eqn.params["cond_jaxpr"]
        body_j = eqn.params["body_jaxpr"]
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        cond_consts = ins[:cn]
        body_consts = ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        refinements, cond_jaxpr = self._cond_refinements(
            cond_j, None)

        body = body_j.jaxpr if hasattr(body_j, "jaxpr") else body_j

        def refine(carry_iv: List[Ival]) -> List[Ival]:
            # cond invars = cond_consts + carry; map refinement indices
            civ = list(cond_consts) + list(carry_iv)
            out = list(carry_iv)

            def val_of(idx, var):
                if isinstance(var, Literal):
                    return const_ival(var.val)
                return civ[idx] if idx is not None else None

            for pn, recs in refinements.items():
                for ia, a, ib, b in recs:
                    a_iv = val_of(ia, a) if ia is not None else (
                        const_ival(a.val) if isinstance(
                            a, Literal) else None)
                    b_iv = val_of(ib, b) if ib is not None else (
                        const_ival(b.val) if isinstance(
                            b, Literal) else None)
                    # refine only scalar carries (vector compares reduce
                    # through reduce_and/or and aren't followed here)
                    k = cn  # carry region starts at index cn in cond invars
                    if ia is not None and ia >= k and b_iv is not None:
                        j = ia - k
                        cur = out[j]
                        if pn == "lt":
                            out[j] = Ival(cur.lo,
                                          min(cur.hi, b_iv.hi - 1), cur.wtag)
                        elif pn == "le":
                            out[j] = Ival(cur.lo,
                                          min(cur.hi, b_iv.hi), cur.wtag)
                        elif pn == "gt":
                            out[j] = Ival(max(cur.lo, b_iv.lo + 1),
                                          cur.hi, cur.wtag)
                        elif pn == "ge":
                            out[j] = Ival(max(cur.lo, b_iv.lo),
                                          cur.hi, cur.wtag)
                    if ib is not None and ib >= k and a_iv is not None:
                        j = ib - k
                        cur = out[j]
                        if pn == "lt":    # a < carry  =>  carry > a
                            out[j] = Ival(max(cur.lo, a_iv.lo + 1),
                                          cur.hi, cur.wtag)
                        elif pn == "le":
                            out[j] = Ival(max(cur.lo, a_iv.lo),
                                          cur.hi, cur.wtag)
                        elif pn == "gt":  # a > carry  =>  carry < a
                            out[j] = Ival(cur.lo,
                                          min(cur.hi, a_iv.hi - 1), cur.wtag)
                        elif pn == "ge":
                            out[j] = Ival(cur.lo,
                                          min(cur.hi, a_iv.hi), cur.wtag)
                    # make sure intervals stay well formed
            for j, iv in enumerate(out):
                if iv.lo > iv.hi:
                    out[j] = carry_iv[j]
            return out

        # fixpoint with widening: silent passes first, one reporting pass
        # at the stable carry
        for it in range(24):
            body_in = list(body_consts) + refine(carry)
            outs = self.run_sub(body, body_in, report=False)
            joined = [c.join(o) for c, o in zip(carry, outs)]
            if all(c.contains(j) and c.same_tags(j)
                   for c, j in zip(carry, joined)):
                carry = joined
                break
            if it >= 11:
                # widen unstable slots to their dtype range
                widened = []
                for c, j, v in zip(carry, joined,
                                   body.invars[len(body_consts):]):
                    if c.contains(j) and c.same_tags(j):
                        widened.append(j)
                    else:
                        widened.append(dtype_ival(v.aval))
                carry = widened
            else:
                carry = joined
        if report:
            self.run_sub(body, list(body_consts) + refine(carry), True)
        return carry

    def _scan(self, eqn, ins: List[Ival], report: bool) -> List[Ival]:
        body_j = eqn.params["jaxpr"]
        body = body_j.jaxpr if hasattr(body_j, "jaxpr") else body_j
        nc = eqn.params.get("num_consts", 0)
        ncar = eqn.params.get("num_carry", 0)
        consts = ins[:nc]
        carry = list(ins[nc:nc + ncar])
        xs = ins[nc + ncar:]
        ys = None
        length = eqn.params.get("length")
        if length is not None and length <= 32:
            # short fixed-trip loop (fori binary searches lower here):
            # iterate exactly instead of widening — index-style carries
            # stay at their true tiny ranges
            joined_in = list(carry)
            cur = list(carry)
            for _ in range(length):
                outs = self.run_sub(body, list(consts) + cur + list(xs),
                                    report=False)
                cur = outs[:ncar]
                ys_now = outs[ncar:]
                ys = ys_now if ys is None else [
                    a.join(b) for a, b in zip(ys, ys_now)]
                joined_in = [a.join(b) for a, b in zip(joined_in, cur)]
            if report:
                outs = self.run_sub(
                    body, list(consts) + joined_in + list(xs), True)
                ys = [a.join(b) for a, b in zip(ys, outs[ncar:])] if ys \
                    else outs[ncar:]
            return cur + (ys or [])
        for it in range(24):
            outs = self.run_sub(body, list(consts) + carry + list(xs),
                                report=False)
            new_carry = outs[:ncar]
            ys_now = outs[ncar:]
            ys = ys_now if ys is None else [
                a.join(b) for a, b in zip(ys, ys_now)]
            joined = [c.join(o) for c, o in zip(carry, new_carry)]
            if all(c.contains(j) and c.same_tags(j)
                   for c, j in zip(carry, joined)):
                carry = joined
                break
            if it >= 11:
                widened = []
                for c, j, v in zip(carry, joined, body.invars[nc:nc + ncar]):
                    if c.contains(j) and c.same_tags(j):
                        widened.append(j)
                    else:
                        widened.append(dtype_ival(v.aval))
                carry = widened
            else:
                carry = joined
        if report:
            outs = self.run_sub(body, list(consts) + carry + list(xs), True)
            ys = [a.join(b) for a, b in zip(ys, outs[ncar:])] if ys else \
                outs[ncar:]
        return carry + (ys or [])

    # -- shape helpers ----------------------------------------------------

    def _reduction_size(self, eqn) -> int:
        try:
            in_sz = int(np.prod(eqn.invars[0].aval.shape))
            out_sz = max(int(np.prod(eqn.outvars[0].aval.shape)), 1)
            return max(in_sz // out_sz, 1)
        except Exception:
            return 1 << 20

    def _axis_size(self, eqn) -> int:
        try:
            axes = eqn.params.get("axes")
            shape = eqn.invars[0].aval.shape
            if axes:
                return int(np.prod([shape[a] for a in axes]))
            return int(shape[-1])
        except Exception:
            return 1 << 20

    def _update_count(self, eqn) -> int:
        try:
            return max(int(np.prod(eqn.invars[2].aval.shape)), 1)
        except Exception:
            return 1 << 20


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def precondition_ivals(state, items, weights,
                       hints: Optional[Dict[str, Ival]] = None) -> List[Ival]:
    """The validate_block preconditions as input intervals, matched to
    the flattened (state, items, weights) argument order.

    State leaves are named by their pytree path: ids hold non-negative
    real ids or the sentinels (>= -3); counts/errors are int32-safe by
    the sat_add induction; anything else gets its dtype range.  Items
    may be any int32 (padding ids are unchecked); weights carry the
    wtag — ``validate_block`` bounds their block |sum| by int32 max.
    ``hints`` maps a leaf-name substring to an interval for state-struct
    invariants the names alone can't carry (e.g. CR-precis ``primes``
    are bounded by the counter budget per ``init_crprecis``).
    """
    from jax.tree_util import tree_flatten_with_path

    leaves, _ = tree_flatten_with_path(state)
    out: List[Ival] = []
    for path, leaf in leaves:
        name = "/".join(str(getattr(p, "name", getattr(p, "idx", p)))
                        for p in path).lower()
        hinted = next((iv for sub, iv in (hints or {}).items()
                       if sub in name), None)
        if hinted is not None:
            out.append(hinted)
        elif "ids" in name:
            out.append(Ival(-3, INT32_MAX))
        elif "count" in name:
            out.append(Ival(-IMAX, IMAX))
        elif "error" in name:
            out.append(Ival(0, IMAX))
        elif "mass" in name or "total" in name:
            out.append(Ival(-IMAX, IMAX))
        else:
            out.append(dtype_ival(
                type("A", (), {"dtype": np.asarray(leaf).dtype})))
    out.append(Ival(INT32_MIN, INT32_MAX))        # items: any int32
    # weights: |block sum| <= IMAX; each element is both a singleton
    # disjoint subset (wtag) and a trivial one-element range (rsum)
    out.append(Ival(-IMAX, IMAX, wtag=True, rsum=True))
    return out


def analyze_update(spec, block: int = 64,
                   wsum: int = IMAX) -> Tuple[List[Finding], "_Analyzer"]:
    """Range-analyze one spec's compiled ingest entry point."""
    import jax.numpy as jnp

    from repro.sketch import api

    ad = api.adapter_for(spec)
    state = ad.make(spec)
    items = jnp.zeros((block,), jnp.int32)
    weights = jnp.zeros((block,), jnp.int32)
    closed = jax.make_jaxpr(
        lambda s, i, w: ad.update(spec, s, i, w))(state, items, weights)
    entry = (f"ingest[{spec.kind}/{spec.variant}/{spec.backend}"
             f"{'/s' + str(spec.shards) if spec.shards else ''}"
             f"{'/t' + str(spec.tenants) if spec.tenants else ''}]")
    an = _Analyzer(entry, wsum=wsum)
    # CR-precis moduli are primes <= total_budget // t (init_crprecis),
    # which the leaf name alone can't say
    hints = {"prime": Ival(1, max(2, int(spec.k)))}
    in_ivals = precondition_ivals(state, items, weights, hints=hints)
    an.run(closed.jaxpr, in_ivals)
    return an.findings, an


def analyze_merge(k: int = 64, wsum: int = IMAX) -> List[Finding]:
    """Range-analyze the cross-host summary merge (``state.merge``).

    Two independently-ingested summaries can EACH hold counts up to the
    saturation rail, so merge arithmetic gets the widest preconditions
    the sat_add induction allows: counts in [-IMAX, IMAX], errors in
    [0, IMAX], ids sentinel-or-data.  Every fold in merge must stay
    int32 under those — the PR 7 merge rewrite is the code under proof.
    """
    from repro.sketch import state as st

    a = st.init(k)
    closed = jax.make_jaxpr(st.merge)(a, a)
    an = _Analyzer(f"merge[k={k}]", wsum=wsum)
    in_ivals = [Ival(-3, INT32_MAX), Ival(-IMAX, IMAX), Ival(0, IMAX)] * 2
    an.run(closed.jaxpr, in_ivals)
    return an.findings


def analyze_jaxable(fn, args, entry: str, in_ivals=None,
                    wsum: int = IMAX) -> List[Finding]:
    """Range-analyze an arbitrary jax-traceable callable (test hook)."""
    closed = jax.make_jaxpr(fn)(*args)
    an = _Analyzer(entry, wsum=wsum)
    if in_ivals is None:
        in_ivals = [dtype_ival(v.aval) for v in closed.jaxpr.invars]
    an.run(closed.jaxpr, in_ivals)
    return an.findings


DEFAULT_GRID = (
    dict(variant="sspm", backend="bank"),
    dict(variant="lazy", backend="bank"),
    dict(variant="double", backend="bank"),
    dict(variant="unbiased", backend="bank"),
    dict(variant="sspm", backend="crprecis"),
)


def analyze_ingest_grid(k: int = 64, block: int = 64,
                        grid=DEFAULT_GRID) -> List[Finding]:
    """The acceptance surface: every registered variant's fused ingest
    must be provably wrap-free under the validate_block preconditions."""
    from repro.sketch import api

    out: List[Finding] = []
    for cell in grid:
        spec = api.SketchSpec(kind="frequency", k=k, **cell)
        fs, _ = analyze_update(spec, block=block)
        out.extend(fs)
    out.extend(analyze_merge(k=k))
    # de-dup across cells: the same source site proves once
    seen, uniq = set(), []
    for f in out:
        key = (f.rule, f.path, f.line, f.message)
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    return uniq
