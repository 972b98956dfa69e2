"""Layer 2b: sentinel-flow taint analysis over the query paths (SK202).

Proves, on the traced jaxpr of every registered variant's query entry
point, that values derived from stored slot ids — which may hold the
EMPTY(-1) / BLOCKED(-2) / POISON(-3) sentinels — never decide an
equality whose result escapes unguarded.  An ``eq`` between an
id-tainted value and a probe item matches a sentinel slot whenever a
deleted/padded probe id (-1) meets an EMPTY slot, silently resurrecting
that slot's garbage count into the estimate; the repo-wide idiom is
``(ids == item) & (ids >= 0)``.

The pass is a forward taint + local consumer check:

* taint: state ``ids`` leaves (and anything reached through shape ops,
  gathers, sorts, selects and integer arithmetic) are *sentinel-
  possible*.  Values proven non-negative by construction (iota, counts
  of things, clip at 0) drop the taint.
* guards: outputs of ``ge(t, 0)``/``gt(t, -1)``/``le(0, t)`` where
  ``t`` is id-tainted are *guard* booleans; guard-ness is closed under
  ``and``, broadcast, reshape, convert and reduce_and.
* check: every ``eq`` with an id-tainted operand must have ALL its
  boolean consumers be ``and`` chains that also contain a guard (or
  feed a select whose taken branch is itself guarded).  An ``eq``
  against a *negative literal* (e.g. ``ids == EMPTY`` masking) is
  deliberate sentinel arithmetic and exempt.

Anything else — unknown primitives, reductions — propagates taint
conservatively; the pass errs toward flagging.
"""
from __future__ import annotations

from typing import Dict, List, Set, Tuple

import numpy as np

import jax
from jax.extend.core import Literal

from .findings import Finding, call_body, relpath

_SHAPE_OPS = frozenset({
    "broadcast_in_dim", "reshape", "squeeze", "expand_dims", "transpose",
    "rev", "copy", "convert_element_type", "slice", "dynamic_slice",
    "gather", "concatenate", "pad", "sort", "select_n",
    "dynamic_update_slice", "scatter",
})

# primitives whose output is provably sentinel-free regardless of inputs
_NONNEG_OUT = frozenset({
    "iota", "argmax", "argmin", "cumsum",  # counts/positions
})


def _site(eqn, entry: str) -> Tuple[str, int]:
    try:
        from jax._src import source_info_util as siu
        for fr in siu.user_frames(eqn.source_info):
            fn = fr.file_name
            if "/repro/" in fn and "/analysis/" not in fn \
                    and "site-packages" not in fn:
                return relpath(fn), int(fr.start_line)
    except Exception:
        pass
    return entry, 0


def _is_lit(v) -> bool:
    return isinstance(v, Literal)


def _lit_value(v):
    return np.asarray(v.val) if _is_lit(v) else None


class _Taint:
    """Per-jaxpr sentinel taint state."""

    def __init__(self, entry: str):
        self.entry = entry
        self.findings: List[Finding] = []
        self._seen = set()

    def flag(self, eqn, why: str):
        path, line = _site(eqn, self.entry)
        key = (path, line)
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(Finding(
            rule="SK202", path=path, line=line, symbol="eq",
            message=f"sentinel-possible equality escapes unguarded: {why}; "
                    f"conjoin an `(ids >= 0)` guard on the id operand"))

    # -- one jaxpr --------------------------------------------------------

    def run(self, jaxpr, in_tainted: List[bool]) -> List[bool]:
        """Returns per-outvar taint; records findings along the way."""
        tainted: Set[int] = set()
        guards: Set[int] = set()
        defs: Dict[int, object] = {}
        uses: Dict[int, List[object]] = {}

        def is_t(v) -> bool:
            return not _is_lit(v) and id(v) in tainted

        def is_g(v) -> bool:
            return not _is_lit(v) and id(v) in guards

        for v, t in zip(jaxpr.invars, in_tainted):
            if t:
                tainted.add(id(v))
        for eqn in jaxpr.eqns:
            for v in eqn.invars:
                if not _is_lit(v):
                    uses.setdefault(id(v), []).append(eqn)
            for ov in eqn.outvars:
                defs[id(ov)] = eqn

        def guarded_use(v, depth: int = 0) -> bool:
            """True if EVERY boolean consumer path of v conjoins a guard."""
            if depth > 12:
                return False
            consumers = uses.get(id(v), [])
            if not consumers:
                return False  # escapes as an output unguarded
            for c in consumers:
                pn = c.primitive.name
                if pn == "and":
                    other = [x for x in c.invars if x is not v]
                    if any(is_g(o) for o in other):
                        continue
                    if guarded_use(c.outvars[0], depth + 1):
                        continue
                    return False
                if pn in ("broadcast_in_dim", "reshape", "convert_element_type",
                          "squeeze", "expand_dims", "transpose", "not"):
                    if guarded_use(c.outvars[0], depth + 1):
                        continue
                    return False
                if pn == "select_n":
                    # eq used as a select predicate: picking between
                    # values is not an identity decision leak only if the
                    # predicate itself is guarded upstream — it is not
                    return False
                return False
            return True

        # pass 1: propagate taint and collect guards (guards may be
        # emitted AFTER the equality they protect in topological order,
        # so equality checking is deferred to pass 2)
        for eqn in jaxpr.eqns:
            p = eqn.primitive.name
            ins_t = [is_t(v) for v in eqn.invars]

            if p == "eq":
                # comparison output itself is not id-tainted
                continue

            if p in ("ge", "gt", "le", "lt"):
                a, b = eqn.invars
                out = eqn.outvars[0]
                lv_a, lv_b = _lit_value(a), _lit_value(b)
                if is_t(a) and lv_b is not None and lv_b.size \
                        and (lv_b >= -1).all() and p in ("ge", "gt"):
                    # ids >= 0 / ids > -1
                    guards.add(id(out))
                if is_t(b) and lv_a is not None and lv_a.size \
                        and (lv_a <= 0).all() and p in ("le", "lt"):
                    # 0 <= ids / -1 < ids
                    guards.add(id(out))
                continue

            if p == "and":
                if any(is_g(v) for v in eqn.invars):
                    guards.add(id(eqn.outvars[0]))
                continue

            if p in ("reduce_and",):
                if any(is_g(v) for v in eqn.invars):
                    guards.add(id(eqn.outvars[0]))
                continue

            if p in ("broadcast_in_dim", "reshape", "convert_element_type",
                     "squeeze", "expand_dims", "transpose"):
                # guard-ness is closed under pure shape ops
                if is_g(eqn.invars[0]):
                    guards.add(id(eqn.outvars[0]))
                if ins_t[0]:
                    tainted.add(id(eqn.outvars[0]))
                continue

            if p == "while":
                cn = eqn.params.get("cond_nconsts", 0)
                bn = eqn.params.get("body_nconsts", 0)
                body = eqn.params["body_jaxpr"]
                body = body.jaxpr if hasattr(body, "jaxpr") else body
                carry_t = ins_t[cn + bn:]
                for _ in range(8):
                    out_t = _Taint.run_child(
                        self, body, ins_t[cn:cn + bn] + carry_t)
                    new = [a or b for a, b in zip(carry_t, out_t)]
                    if new == carry_t:
                        break
                    carry_t = new
                for ov, t in zip(eqn.outvars, carry_t):
                    if t:
                        tainted.add(id(ov))
                continue
            if p == "scan":
                nc = eqn.params.get("num_consts", 0)
                ncar = eqn.params.get("num_carry", 0)
                body = eqn.params["jaxpr"]
                body = body.jaxpr if hasattr(body, "jaxpr") else body
                carry_t = ins_t[nc:nc + ncar]
                xs_t = ins_t[nc + ncar:]
                ys_t = [False] * (len(eqn.outvars) - ncar)
                for _ in range(8):
                    out_t = _Taint.run_child(
                        self, body, ins_t[:nc] + carry_t + xs_t)
                    new = [a or b for a, b in zip(carry_t, out_t[:ncar])]
                    ys_t = [a or b for a, b in zip(ys_t, out_t[ncar:])]
                    if new == carry_t:
                        break
                    carry_t = new
                for ov, t in zip(eqn.outvars, carry_t + ys_t):
                    if t:
                        tainted.add(id(ov))
                continue
            if p == "cond":
                out_t = [False] * len(eqn.outvars)
                for br in eqn.params["branches"]:
                    bt = _Taint.run_child(self, br.jaxpr, ins_t[1:])
                    out_t = [a or b for a, b in zip(out_t, bt)]
                for ov, t in zip(eqn.outvars, out_t):
                    if t:
                        tainted.add(id(ov))
                continue
            inner = call_body(eqn)
            if inner is not None:
                out_t = _Taint.run_child(self, inner, ins_t)
                for ov, t in zip(eqn.outvars, out_t):
                    if t:
                        tainted.add(id(ov))
                continue

            # default propagation: taint flows through unless the
            # primitive's output is structurally non-negative
            if p in _NONNEG_OUT:
                continue
            if any(ins_t):
                for ov in eqn.outvars:
                    tainted.add(id(ov))

        # pass 2: with taint and guards complete, audit every equality
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "eq":
                continue
            a, b = eqn.invars
            for tside, other in ((a, b), (b, a)):
                if not is_t(tside):
                    continue
                lv = _lit_value(other)
                if lv is not None and lv.size and (lv < 0).all():
                    # deliberate sentinel test (ids == EMPTY, ...)
                    break
                if not guarded_use(eqn.outvars[0]):
                    self.flag(
                        eqn,
                        "`eq` over an id-derived operand reaches a "
                        "consumer with no `and`-conjoined non-negative "
                        "guard")
                break

        return [not _is_lit(v) and id(v) in tainted
                for v in jaxpr.outvars]

    @staticmethod
    def run_child(parent: "_Taint", jaxpr, in_t: List[bool]) -> List[bool]:
        child = _Taint(parent.entry)
        child.findings = parent.findings
        child._seen = parent._seen
        return child.run(jaxpr, list(in_t))


def analyze_query(spec, n_items: int = 8) -> List[Finding]:
    """Taint-check one spec's query_many entry point."""
    import jax.numpy as jnp

    from repro.sketch import api
    from jax.tree_util import tree_flatten_with_path

    ad = api.adapter_for(spec)
    state = ad.make(spec)
    items = jnp.zeros((n_items,), jnp.int32)
    closed = jax.make_jaxpr(
        lambda s, i: ad.query_many(spec, s, i))(state, items)
    leaves, _ = tree_flatten_with_path(state)
    in_t = []
    for path, _leaf in leaves:
        name = "/".join(str(getattr(p, "name", getattr(p, "idx", p)))
                        for p in path).lower()
        in_t.append("ids" in name)
    in_t.append(True)  # probe items may be negative (deleted / padding)
    entry = f"query[{spec.kind}/{spec.variant}/{spec.backend}]"
    t = _Taint(entry)
    t.run(closed.jaxpr, in_t)
    return t.findings


def analyze_query_rows(k: int = 64, rows: int = 4,
                       n_items: int = 8) -> List[Finding]:
    """Taint-check the bank row-query surface directly."""
    import jax.numpy as jnp

    from repro.sketch import bank as bank_mod

    ids = jnp.zeros((rows, k), jnp.int32)
    counts = jnp.zeros((rows, k), jnp.int32)
    errors = jnp.zeros((rows, k), jnp.int32)
    row_ix = jnp.zeros((n_items,), jnp.int32)
    items = jnp.zeros((n_items,), jnp.int32)
    state = bank_mod.SketchState(ids, counts, errors)
    closed = jax.make_jaxpr(
        lambda s, r, i: bank_mod.query_rows(s, r, i))(state, row_ix, items)
    # state leaves order: ids, counts, errors
    in_t = [True, False, False, False, True]
    t = _Taint("query_rows[bank]")
    t.run(closed.jaxpr, in_t)
    return t.findings


DEFAULT_GRID = (
    dict(variant="sspm", backend="bank"),
    dict(variant="lazy", backend="bank"),
    dict(variant="double", backend="bank"),
    dict(variant="unbiased", backend="bank"),
    dict(variant="sspm", backend="crprecis"),
)


def analyze_query_grid(k: int = 64, grid=DEFAULT_GRID) -> List[Finding]:
    from repro.sketch import api

    out: List[Finding] = []
    for cell in grid:
        spec = api.SketchSpec(kind="frequency", k=k, **cell)
        out.extend(analyze_query(spec))
    out.extend(analyze_query_rows(k=k))
    seen, uniq = set(), []
    for f in out:
        key = (f.rule, f.path, f.line)
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    return uniq
