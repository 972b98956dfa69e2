"""The comparison that decides ``correct``.

What the timed path produced, at the timed sizes, against the plain
reference (``chipbench.reference``). The reference's input is the
driver's own record of what it submitted, tick by tick (``fed``,
``asked``), never what the program recorded of it; from it
``expected_feed`` builds the blocks and re-admissions the service's
stated rules call for. A sample of tenants is drawn from the seed (the
largest, which the top-k subscriptions follow, and a seeded draw of the
rest, re-admitted ones among them):

* ``feed_differing``: blocks the program fed its ingest in the window,
  and re-admissions it made, that differ from those expected — exact;
  an update dropped, credited to another tenant or put off to a later
  tick shows here;
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
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench.reference import Row, exact_counts

LIMITS = {
    "feed_differing": 0,
    "rows_differing": 0,
    "query_answers_differing": 0,
    "topk_answers_differing": 0,
    "error_over_bound": 1.0,
    "heavy_unmonitored": 0,
    "ops_unacknowledged": 0,
}

Update = Tuple[int, np.ndarray, np.ndarray]    # (tenant, items, weights)


@dataclasses.dataclass
class Record:
    """What the driver handed the service, and what the window produced,
    as the check needs them."""

    k: int
    item_bits: int
    block: int
    tenants: int
    spill_after: Optional[int]
    kept: List[int]                          # subscribed: never spill
    # per tick since the service was built: the driver's updates in
    # submission order, and the tenants it queried
    fed: List[List[Update]]
    asked: List[List[int]]
    first_tick: int                          # the window's first tick
    # what the program fed its ingest, and re-admitted, in the window
    program_blocks: List[Tuple[np.ndarray, np.ndarray]]
    program_admits: List[Tuple[int, int]]    # (block index, tenant)
    queries: Dict[int, List[Tuple[int, np.ndarray, np.ndarray]]]
    topk: Dict[int, List[Tuple[int, np.ndarray, np.ndarray]]]
    rows: Dict[int, Dict[str, np.ndarray]]   # final program rows
    unacknowledged: int


@dataclasses.dataclass
class Feed:
    """The blocks and re-admissions the driver's submissions call for."""

    blocks: List[Tuple[np.ndarray, np.ndarray]]
    admits: List[Tuple[int, int]]            # (block index, tenant)
    # per tick: (blocks before, blocks after, admits before, admits after)
    ticks: List[Tuple[int, int, int, int]]


def expected_feed(rec: Record) -> Feed:
    """Each tick's blocks and re-admissions under the service's stated
    rules, from the driver's own record of what it submitted:

    * a tick first re-admits, in ascending tenant order, every spilled
      tenant it has an update or a query for;
    * it then feeds every update submitted since the last tick: tenants
      ascending, each tenant's in submission order, keyed
      ``tenant << item_bits | item``, cut into blocks of ``block`` keys,
      the last padded with weight-0 key-0 entries;
    * last, a tenant whose last update or re-admission is ``spill_after``
      ticks old or more, and that no top-k subscription keeps, spills.
    """
    T, B, bits = rec.tenants, rec.block, rec.item_bits
    last = np.zeros(T, np.int64)
    seen = np.zeros(T, bool)
    spilled = np.zeros(T, bool)
    kept = np.zeros(T, bool)
    kept[list(rec.kept)] = True
    out = Feed([], [], [])
    for i, (ups, asked) in enumerate(zip(rec.fed, rec.asked)):
        b0, a0 = len(out.blocks), len(out.admits)
        touched = np.unique(np.asarray(
            [t for t, _, _ in ups] + list(asked), np.int64))
        for t in touched[spilled[touched]].tolist():
            out.admits.append((len(out.blocks), t))
            spilled[t], last[t] = False, i
        if ups:
            ten = np.asarray([t for t, _, _ in ups], np.int64)
            order = np.argsort(ten, kind="stable")
            lens = np.asarray([len(ups[j][1]) for j in order])
            keys = ((np.repeat(ten[order], lens) << bits)
                    | np.concatenate([ups[j][1] for j in order]
                                     ).astype(np.int64)).astype(np.int32)
            w = np.concatenate([ups[j][2] for j in order]).astype(np.int32)
            for s in range(0, len(keys), B):
                ci, cw = keys[s:s + B], w[s:s + B]
                out.blocks.append((np.pad(ci, (0, B - len(ci))),
                                   np.pad(cw, (0, B - len(cw)))))
            last[ten], seen[ten] = i, True
        if rec.spill_after is not None:
            spilled |= seen & ~kept & (i - last >= rec.spill_after)
        out.ticks.append((b0, len(out.blocks), a0, len(out.admits)))
    return out


def feed_differing(rec: Record, feed: Feed) -> int:
    """Blocks and re-admissions of the window in which the program's
    differ from those the driver's submissions call for (each missing or
    extra one counts)."""
    b0, _, a0, _ = feed.ticks[rec.first_tick] if rec.first_tick < len(
        feed.ticks) else (len(feed.blocks), 0, len(feed.admits), 0)
    want_b, got_b = feed.blocks[b0:], rec.program_blocks
    n = abs(len(want_b) - len(got_b))
    for (wi, ww), (gi, gw) in zip(want_b, got_b):
        n += int(not (np.array_equal(wi, np.asarray(gi, np.int32))
                      and np.array_equal(ww, np.asarray(gw, np.int32))))
    want_a = [(b - b0, t) for b, t in feed.admits[a0:]]
    got_a = [(int(b), int(t)) for b, t in rec.program_admits]
    n += abs(len(want_a) - len(got_a))
    n += sum(int(w != g) for w, g in zip(want_a, got_a))
    return n


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
