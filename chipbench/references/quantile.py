"""Plain reference of the per-tenant Dyadic SS± semantics, in numpy, for
the check.

Nothing here comes from the program. A tenant's summary is the paper's
Dyadic SpaceSaving± (Algs 5 and 6) over its own values in
``[0, 2^bits)``:

* one SpaceSaving± row per dyadic layer l (a
  ``chipbench.references.frequency.Row`` of the layer's capacity, under
  the block rules that module states), fed node ``x >> l`` of each of
  the tenant's values with the value's weight, block by block;
* the tenant's mass |F_t|_1, counted exactly;
* rank(x): with y = x + 1, the sum over the layers l whose bit l of y is
  set of layer l's count of node ``2 * (y >> (l + 1))`` (0 where the
  layer does not monitor it), each clamped at 0; for y >= 2^bits, the
  mass;
* quantile(q): the smallest x in ``[0, 2^bits)`` with rank(x) >= q *
  mass, by binary search, with the target q * mass formed in float32
  and each rank compared as a float32.
"""
from __future__ import annotations

from typing import List

import numpy as np

from chipbench.references.frequency import Row


class Levels:
    """One tenant's dyadic layers (row l holds ``caps[l]`` counters)."""

    def __init__(self, caps: List[int]):
        self.rows = [Row(c) for c in caps]
        self.bits = len(caps)
        self.mass = 0

    def copy(self) -> "Levels":
        out = Levels.__new__(Levels)
        out.rows = [r.copy() for r in self.rows]
        out.bits, out.mass = self.bits, self.mass
        return out

    def update(self, items: np.ndarray, weights: np.ndarray) -> None:
        """One block's updates of this tenant (raw values)."""
        items = np.asarray(items, np.int64)
        for l, row in enumerate(self.rows):
            row.update(items >> l, weights)
        self.mass += int(np.asarray(weights, np.int64).sum())

    def ranks(self, xs: np.ndarray) -> np.ndarray:
        return ranks(self.bits, [(r.ids, r.counts) for r in self.rows],
                     self.mass, xs)

    def quantiles(self, qs: np.ndarray) -> np.ndarray:
        return quantiles(self.bits, [(r.ids, r.counts) for r in self.rows],
                         self.mass, qs)


def dense(bits: int, layers) -> List[np.ndarray]:
    """Each layer's count of every node, clamped at 0, from one (node
    ids, counts) pair a layer (negative ids are unused slots)."""
    out = []
    for l, (ids, counts) in enumerate(layers):
        ids = np.asarray(ids, np.int64)
        live = ids >= 0
        d = np.zeros(1 << (bits - l), np.int64)
        d[ids[live]] = np.maximum(np.asarray(counts, np.int64)[live], 0)
        out.append(d)
    return out


def ranks(bits: int, layers, mass: int, xs: np.ndarray) -> np.ndarray:
    """rank(x) of each x, from ``layers`` as ``dense`` reads them."""
    y = np.asarray(xs, np.int64) + 1
    r = np.zeros(len(y), np.int64)
    for l, d in enumerate(dense(bits, layers)):
        take = ((y >> l) & 1) == 1
        r += np.where(take, d[(2 * (y >> (l + 1))) % len(d)], 0)
    return np.where(y >= (1 << bits), mass, r)


def quantiles(bits: int, layers, mass: int, qs: np.ndarray) -> np.ndarray:
    """The smallest x with rank(x) >= q * mass, for each q."""
    ds = dense(bits, layers)

    def rank(x: int) -> int:
        y = x + 1
        if y >= 1 << bits:
            return mass
        return sum(int(d[2 * (y >> (l + 1))])
                   for l, d in enumerate(ds) if (y >> l) & 1)

    out = []
    for q in np.asarray(qs, np.float32):
        target = np.float32(q) * np.float32(mass)
        lo, hi = 0, (1 << bits) - 1
        while lo < hi:
            mid = (lo + hi) // 2
            if np.float32(rank(mid)) >= target:
                hi = mid
            else:
                lo = mid + 1
        out.append(lo)
    return np.asarray(out, np.int64)
