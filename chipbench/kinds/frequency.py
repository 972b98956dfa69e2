"""The frequency kind: per-tenant SpaceSaving± summaries in one
``(tenants, k)`` tenant bank, with point queries and top-k
subscriptions, and idle tenants spilled to the host.

The configuration's own keys: ``k_per_tenant`` counters per tenant and
``bits`` item bits (composite keys ``tenant << bits | item``). The mix's
``topk_subscriptions`` largest tenants each subscribe to their top
``topk_m``, refreshed every tick (so they never spill).

``correct`` compares what the timed path produced, at the timed sizes,
against the plain reference (``chipbench.references.frequency``)
replayed over the blocks and re-admissions the driver's own record
calls for (``chipbench.check``). A sample of tenants is drawn from the
seed (the largest, which the top-k subscriptions follow, and a seeded
draw of the rest, re-admitted ones among them):

* ``feed_differing``: blocks the program fed its ingest in the window,
  and re-admissions it made, that differ from those expected — exact;
* ``rows_differing``: each sampled tenant's row after the window
  (resident or spilled), against the reference replayed over the
  expected blocks and re-admissions — exact;
* ``query_answers_differing``: every point-query answer of a sampled
  tenant, against the reference row at the tick that answered it —
  exact;
* ``topk_answers_differing``: every top-k answer of a sampled subscribed
  tenant, tick by tick — exact;
* ``error_over_bound``: the largest |estimate - exact count| over every
  item a sampled tenant touched (exact counts of what the driver
  submitted), as a share of the SS± bound
  2·alpha·|F|_1/k = 2·I/k (paper Thm 4) — at most 1;
* ``heavy_unmonitored``: items above that bound that the row does not
  monitor — none (no false negatives);
* ``ops_unacknowledged``: operations due in the window that no tick
  acknowledged within a minute of its close — none.

``control=True`` puts the reference in the program's place with one
guarantee broken: answers are read from the state one tick stale (before
the tick's own updates), which a later change that overlaps queries
with the ingest would do. It has to come out as not correct.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from chipbench.check import Fed, expected_feed, feed_differing
from chipbench.cost import LANES
from chipbench.references.frequency import Row, exact_counts

LIMITS = {
    "feed_differing": 0,
    "rows_differing": 0,
    "query_answers_differing": 0,
    "topk_answers_differing": 0,
    "error_over_bound": 1.0,
    "heavy_unmonitored": 0,
    "ops_unacknowledged": 0,
}

# the CPU rehearsal's size: bits kept (the key shape), the rest cut
TINY_TENANTS, TINY_K, TINY_BLOCK = 256, 64, 512


def spec(config):
    from repro.sketch import api

    p = config.params
    return api.SketchSpec(kind="frequency",
                          k=config.tenants * p["k_per_tenant"],
                          bits=p["bits"], tenants=config.tenants)


def tiny(config):
    return dataclasses.replace(
        config, tenants=TINY_TENANTS, block=TINY_BLOCK,
        params={**config.params, "k_per_tenant": TINY_K})


# -- the roofline's least bytes -----------------------------------------------


def rows_reached(keys: np.ndarray, weights: np.ndarray,
                 item_bits: int) -> int:
    """Distinct tenant rows holding a nonzero weight of the block."""
    live = weights != 0
    return len(np.unique(keys[live].astype(np.int64) >> item_bits))


def block_least_bytes(keys: np.ndarray, weights: np.ndarray,
                      item_bits: int, k: int) -> int:
    """The ingest of a block changes only the rows its items reach: each
    such row's ids, counts and errors (three int32 arrays of ``k``
    counters, padded to the 128-lane tile) are read once and written
    once, and the block itself (an int32 key and an int32 weight per
    slot) is read once. That is the same work whichever engine or kernel
    runs the ingest."""
    k_pad = -(-k // LANES) * LANES
    rows = rows_reached(keys, weights, item_bits)
    return rows * k_pad * 3 * 4 * 2 + len(keys) * 8


def least_bytes(config, blocks) -> List[int]:
    p = config.params
    return [block_least_bytes(ci, cw, p["bits"], p["k_per_tenant"])
            for ci, cw in blocks]


# -- the service under the window ---------------------------------------------


class Served:
    """The tenant bank's service, with the top-k subscriptions made."""

    def __init__(self, cell, traffic):
        from repro.serve import SketchService

        c = cell.config
        self.cell = cell
        self.svc = SketchService(spec(c), block=c.block,
                                 spill_after=c.spill_after)
        self.subscribed = [int(t) for t in np.argsort(
            -traffic.sizes, kind="stable")[:cell.mix.topk_subscriptions]]
        for t in self.subscribed:
            self.svc.subscribe_topk(t, cell.mix.topk_m)
        self.topk: Dict[int, list] = {}

    def warm_up(self, tick, query, quiet: int) -> None:
        """Every tick refreshes the top-k subscriptions; an idle tenant
        spills and a query re-admits it."""
        if self.cell.config.spill_after is None:
            return
        for _ in range(self.cell.config.spill_after + 1):
            tick()
        query(quiet, np.zeros(1, np.int32))
        tick()

    def after_tick(self, i: int) -> None:
        for t in self.subscribed:
            v = self.svc.topk_result(t)
            if v is not None:
                self.topk.setdefault(t, []).append((i, v[0], v[1]))

    def record(self, drv, sample, win) -> "Record":
        tr, c = drv.traffic, self.cell.config

        def update(u):
            if isinstance(u, tuple):
                return u
            s0, ln = tr.start[u], tr.length[u]
            return (int(tr.tenant[u]), tr.keys[s0:s0 + ln],
                    tr.weights[s0:s0 + ln])

        queries = {}
        for op, (tick_i, ans) in drv.tick_of_query.items():
            t = int(tr.tenant[op])
            if t in sample:
                s0, ln = tr.start[op], tr.length[op]
                queries.setdefault(t, []).append(
                    (tick_i, tr.keys[s0:s0 + ln], np.asarray(ans)))
        rows = {t: self.svc.tenant_snapshot(t) for t in sample}
        return Record(k=c.params["k_per_tenant"],
                      item_bits=c.params["bits"], block=c.block,
                      tenants=c.tenants, spill_after=c.spill_after,
                      kept=list(self.subscribed),
                      fed=[[update(u) for u in ups] for ups, _ in drv.log],
                      asked=[qs for _, qs in drv.log],
                      first_tick=drv.first_tick,
                      program_blocks=self.svc.trace_blocks,
                      program_admits=self.svc.trace_admits,
                      queries=queries,
                      topk={t: v for t, v in self.topk.items()
                            if t in sample},
                      rows=rows, unacknowledged=win.unacknowledged)


# -- the check ----------------------------------------------------------------


@dataclasses.dataclass
class Record(Fed):
    k: int
    queries: Dict[int, List[Tuple[int, np.ndarray, np.ndarray]]]
    topk: Dict[int, List[Tuple[int, np.ndarray, np.ndarray]]]
    rows: Dict[int, Dict[str, np.ndarray]]   # final program rows


def _tenant_blocks(blocks, item_bits: int,
                   sample) -> Dict[int, Dict[int, tuple]]:
    """For each sampled tenant, its entries of each block that has any."""
    want = np.asarray(sorted(sample), np.int64)
    out = {t: {} for t in sample}
    for b, (ci, cw) in enumerate(blocks):
        live = cw != 0
        keys = ci[live].astype(np.int64)
        ten = keys >> item_bits
        hit = np.isin(ten, want)
        if not hit.any():
            continue
        keys, w, ten = keys[hit], cw[live][hit], ten[hit]
        for t in np.unique(ten).tolist():
            m = ten == t
            out[t][b] = (keys[m], w[m])
    return out


def _topk_items(ids: np.ndarray, bits: int) -> np.ndarray:
    return np.where(ids >= 0, ids & ((1 << bits) - 1), ids)


def compare(rec: Record, sample, control: bool = False) -> dict:
    """The compared numbers (see the module docstring) and what they
    covered."""
    feed = expected_feed(rec)
    per = _tenant_blocks(feed.blocks, rec.item_bits, sample)
    admits = {}
    for i, (_, t) in enumerate(feed.admits):
        admits.setdefault(t, []).append(i)
    nums = dict.fromkeys(LIMITS, 0)
    nums["error_over_bound"] = 0.0
    nums["feed_differing"] = feed_differing(rec, feed)
    cover = {"blocks": len(rec.program_blocks), "rows": 0,
             "readmissions": 0, "queries": 0, "topk": 0, "items": 0}
    fed_by = {t: [] for t in sample}
    for ups in rec.fed:
        for u, items, w in ups:
            if u in fed_by:
                fed_by[u].append((items, w))
    for t in sample:
        row, prev = Row(rec.k), Row(rec.k)
        mine = per[t]
        adm = set(admits.get(t, []))
        qs = {}
        for tick, items, ans in rec.queries.get(t, []):
            qs.setdefault(rec.first_tick + tick, []).append((items, ans))
        ks = {rec.first_tick + tick: (items, vals)
              for tick, items, vals in rec.topk.get(t, [])}
        for i, (b0, b1, a0, a1) in enumerate(feed.ticks):
            if control:
                prev = row.copy()
            for a in range(a0, a1):
                if a in adm:
                    row.readmit()
                    cover["readmissions"] += 1
            for b in range(b0, b1):
                if b in mine:
                    row.update(*mine[b])
            seen = prev if control else row
            for items, ans in qs.get(i, []):
                keys = (np.int64(t) << rec.item_bits) | items.astype(np.int64)
                served = seen.query(keys) if control else ans
                nums["query_answers_differing"] += int(
                    not np.array_equal(np.asarray(served, np.int64),
                                       row.query(keys)))
                cover["queries"] += 1
            if i in ks:
                m = len(ks[i][0])
                ids, vals = row.topk(m)
                items, served = ks[i]
                if control:
                    sids, svals = seen.topk(m)
                    items, served = _topk_items(sids, rec.item_bits), svals
                nums["topk_answers_differing"] += int(not (
                    np.array_equal(np.asarray(items, np.int64),
                                   _topk_items(ids, rec.item_bits))
                    and np.array_equal(np.asarray(served, np.int64), vals)))
                cover["topk"] += 1
        got = rec.rows[t]
        if control:
            got = {"ids": row.ids, "counts": row.counts, "errors": row.errors}
        same = all(np.array_equal(np.asarray(got[f]).reshape(-1).astype(
            np.int64), getattr(row, f)) for f in ("ids", "counts", "errors"))
        nums["rows_differing"] += int(not same)
        cover["rows"] += 1

        # the SS± guarantees, on the served row, against exact counts of
        # what the driver submitted
        fed = fed_by[t]
        if fed:
            keys = np.concatenate([i for i, _ in fed]).astype(np.int64) \
                | (np.int64(t) << rec.item_bits)
            w = np.concatenate([w for _, w in fed])
            uids, f, ins, _ = exact_counts(keys, w)
            ids = np.asarray(got["ids"]).reshape(-1).astype(np.int64)
            cnt = np.asarray(got["counts"]).reshape(-1).astype(np.int64)
            slot = dict(zip(ids[ids >= 0].tolist(), cnt[ids >= 0].tolist()))
            est = np.array([slot.get(x, 0) for x in uids.tolist()], np.int64)
            bound = 2.0 * ins / rec.k
            if bound > 0:
                nums["error_over_bound"] = max(
                    nums["error_over_bound"],
                    float(np.abs(est - f).max() / bound))
            heavy = uids[f > bound]
            nums["heavy_unmonitored"] += int(
                (~np.isin(heavy, ids[ids >= 0])).sum())
            cover["items"] += len(uids)
    nums["ops_unacknowledged"] = int(rec.unacknowledged)
    return {"numbers": nums, "covered": cover}


def verdict(nums: dict) -> bool:
    return all(nums[k] <= lim for k, lim in LIMITS.items())


def draw_sample(sizes: np.ndarray, admitted, seed: int, n_largest: int = 8,
                n_random: int = 8):
    """The tenants the check replays: the ``n_largest`` by traffic (the
    top-k subscriptions follow them), and ``n_random`` drawn from the
    seed among the rest that sent updates, half of them from those the
    service re-admitted."""
    top = [int(t) for t in np.argsort(-sizes, kind="stable")[:n_largest]]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC4EC]))
    rest = np.setdiff1d(np.flatnonzero(sizes), top)
    back = np.intersect1d(np.asarray(sorted(admitted), np.int64), rest)
    pick = list(rng.choice(back, min(len(back), n_random // 2),
                           replace=False)) if len(back) else []
    rest = np.setdiff1d(rest, pick)
    pick += list(rng.choice(rest, min(len(rest), n_random - len(pick)),
                            replace=False))
    return top + sorted(int(t) for t in pick)
