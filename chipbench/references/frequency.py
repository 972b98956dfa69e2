"""Plain reference of the served semantics, in numpy, for the check.

Nothing here comes from the program: it is the SpaceSaving± update of
the paper (Algs 1 and 4, weighted) written as a straight loop over one
tenant's row, under the block and re-admission rules the service states:

* A tick's updates reach a row as blocks. Within a block each item's
  weights net out first (an item whose net is 0 leaves the row alone).
* Items the row already monitors take their net at once (count += net).
* The row's unmonitored items with a positive net are then inserted one
  at a time: first, in ascending id order, as many as there are empty
  slots, each into the lowest empty slot (count = net, error = 0); then
  those left with net 1, in ascending id order; then those left with a
  larger net, in ascending id order. Each of these evicts the
  lowest-index slot of least count mc, and takes id, count mc + net and
  error mc (Alg 1).
* The summed weight of the unmonitored deletions is then spread, one
  slot at a time, over the lowest-index slot of largest error, each
  absorbing up to its error from count and error alike (Alg 4).
* Arithmetic saturates at +-(2^31 - 1), as int32 counters do.
* A spilled tenant re-admitted before a tick comes back holding the same
  items, counts and errors, packed to the front of its row in order of
  count (descending), then id (ascending); the rest of the row is empty.
* A point query of an item answers its count in the row, or 0 when the
  row does not monitor it. A top-m subscription answers the m largest
  counts in order, ties to the lower slot, with unused answers as the
  empty id -1 and count -2^31.
"""
from __future__ import annotations

import numpy as np

EMPTY = -1
I32 = 2**31 - 1


def _sat(x):
    return max(-I32, min(I32, int(x)))


class Row:
    """One tenant's row of ``k`` counters."""

    def __init__(self, k: int):
        self.ids = np.full(k, EMPTY, np.int64)
        self.counts = np.zeros(k, np.int64)
        self.errors = np.zeros(k, np.int64)

    def copy(self) -> "Row":
        r = Row.__new__(Row)
        r.ids, r.counts, r.errors = (self.ids.copy(), self.counts.copy(),
                                     self.errors.copy())
        return r

    def update(self, items: np.ndarray, weights: np.ndarray) -> None:
        """Apply one block's updates of this row (``items`` are the keys
        as the row stores them; weight-0 entries are padding)."""
        live = weights != 0
        uids, inv = np.unique(items[live].astype(np.int64),
                              return_inverse=True)
        net = np.zeros(len(uids), np.int64)
        np.add.at(net, inv, weights[live].astype(np.int64))
        keep = net != 0
        uids, net = uids[keep], net[keep]
        slot = {x: j for j, x in enumerate(self.ids.tolist()) if x >= 0}
        inserts, w_del = [], 0
        for x, w in zip(uids.tolist(), net.tolist()):
            j = slot.get(x)
            if j is not None:
                self.counts[j] = _sat(self.counts[j] + w)
            elif w > 0:
                inserts.append((x, w))
            else:
                w_del += -w
        empty = np.flatnonzero(self.ids == EMPTY)
        n_fill = min(len(inserts), len(empty))
        for (x, w), j in zip(inserts[:n_fill], empty):
            self.ids[j], self.counts[j], self.errors[j] = x, w, 0
        rest = inserts[n_fill:]
        for x, w in ([e for e in rest if e[1] == 1]
                     + [e for e in rest if e[1] != 1]):
            j = int(np.argmin(self.counts))   # no empty slot is left
            mc = self.counts[j]
            self.ids[j], self.counts[j], self.errors[j] = (
                x, _sat(mc + w), mc)
        rem = w_del
        while rem > 0:
            j = int(np.argmax(self.errors))
            e = self.errors[j]
            if e <= 0:
                break
            d = min(rem, e)
            self.counts[j] = _sat(self.counts[j] - d)
            self.errors[j] = _sat(self.errors[j] - d)
            rem -= d

    def readmit(self) -> None:
        """The row as a re-admission after a spill leaves it."""
        live = np.flatnonzero(self.ids >= 0)
        order = live[np.lexsort((self.ids[live], -self.counts[live]))]
        k = len(self.ids)
        ids = np.full(k, EMPTY, np.int64)
        counts = np.zeros(k, np.int64)
        errors = np.zeros(k, np.int64)
        n = len(order)
        ids[:n], counts[:n], errors[:n] = (self.ids[order],
                                           self.counts[order],
                                           self.errors[order])
        self.ids, self.counts, self.errors = ids, counts, errors

    def query(self, items: np.ndarray) -> np.ndarray:
        # a row monitors an item at most once
        hit = (self.ids >= 0) & (self.ids == np.asarray(
            items, np.int64)[:, None])
        return (hit * self.counts).sum(axis=1)

    def topk(self, m: int):
        score = np.where(self.ids >= 0, self.counts, -2**31)
        order = np.argsort(-score, kind="stable")[:m]
        return self.ids[order], score[order]


def exact_counts(items: np.ndarray, weights: np.ndarray):
    """Exact frequency of each item, inserted mass I and deleted mass D."""
    w = weights.astype(np.int64)
    uids, inv = np.unique(items.astype(np.int64), return_inverse=True)
    f = np.zeros(len(uids), np.int64)
    np.add.at(f, inv, w)
    return uids, f, int(w[w > 0].sum()), int(-w[w < 0].sum())
