"""The quantile kind: per-tenant Dyadic SS± summaries, each tenant's
``bits`` dyadic layers as rows of one bank, with the largest tenants
subscribed to their quantiles every tick.

The configuration's own keys: ``bits`` value bits (composite keys
``tenant << bits | value``), ``eps`` and ``alpha`` (each layer's
capacity, ``core.quantiles.dyadic_layer_capacities``, which
``layer_capacities`` restates), and the ``quantile_subscriptions``
largest tenants subscribed to ``quantiles``, refreshed every tick.

``correct`` compares what the timed path produced, at the timed sizes,
against the plain reference (``chipbench.references.quantile``) replayed
over the blocks the driver's own record calls for (``chipbench.check``).
A sample of tenants is drawn from the seed (the subscribed ones, which
are the largest, and a seeded draw of the rest):

* ``feed_differing``: blocks the program fed its ingest in the window
  that differ from those expected — exact;
* ``rows_differing``: each sampled tenant's ``bits`` layer rows after
  the window (ids tagged with their row, counts, errors, the BLOCKED
  tail) and its mass, against the reference replayed over the expected
  blocks — exact;
* ``quantile_answers_differing``: every subscription answer, tick by
  tick, against the reference's search at that tick (the same float32
  target) — exact;
* ``rank_error_over_bound``: the largest |rank estimate - exact rank|
  over every value of ``[0, 2^bits)`` of a sampled tenant, on the
  program's final rows, against exact ranks of what the driver
  submitted, as a share of eps·|F_t|_1 (paper §4.2) — at most 1;
* ``quantile_error_over_bound``: each answered x of a subscribed tenant,
  at the tick that answered it: how far its exact rank range
  [rank(x - 1), rank(x)] lies from q·|F_t|_1, as a share of
  eps·|F_t|_1 — at most 1 (the answer within q·|F_t|_1 ± eps·|F_t|_1);
* ``ops_unacknowledged``: operations due in the window that no tick
  acknowledged within a minute of its close — none.

``control=True`` puts the reference in the program's place with one
guarantee broken: answers are read from the state one tick stale (before
the tick's own updates). It has to come out as not correct.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from chipbench.check import Fed, expected_feed, feed_differing
from chipbench.cost import LANES
from chipbench.references import quantile as ref

LIMITS = {
    "feed_differing": 0,
    "rows_differing": 0,
    "quantile_answers_differing": 0,
    "rank_error_over_bound": 1.0,
    "quantile_error_over_bound": 1.0,
    "ops_unacknowledged": 0,
}

# the CPU rehearsal's size: bits kept (the key shape), the rest cut
TINY_TENANTS, TINY_EPS, TINY_BLOCK = 64, 0.25, 512

BLOCKED, INT_MAX = -2, 2**31 - 1


def spec(config):
    from repro.sketch import api

    p = config.params
    s = api.SketchSpec(kind="quantile", tenants=config.tenants,
                       bits=p["bits"], eps=p["eps"], alpha=p["alpha"])
    if list(p["layer_capacities"]) != s.layer_capacities():
        raise ValueError(f"{config.name}: layer_capacities "
                         f"{p['layer_capacities']} differ from the spec's "
                         f"{s.layer_capacities()}")
    return s


def tiny(config):
    from repro.core.quantiles import dyadic_layer_capacities

    p = config.params
    caps = dyadic_layer_capacities(p["bits"], eps=TINY_EPS,
                                   alpha=p["alpha"])
    return dataclasses.replace(
        config, tenants=TINY_TENANTS, block=TINY_BLOCK,
        params={**p, "eps": TINY_EPS, "layer_capacities": caps,
                "row_width": max(caps), "rows": TINY_TENANTS * p["bits"]})


# -- the roofline's least bytes -----------------------------------------------


def block_least_bytes(keys: np.ndarray, weights: np.ndarray, bits: int,
                      caps: List[int]) -> int:
    """The ingest of a block changes only the layer rows of the tenants
    its values reach: each such row's live counters (its layer's
    capacity, padded to the 128-lane tile) in three int32 arrays, read
    once and written once; and the block itself (an int32 key and an
    int32 weight a slot) is read once. That is the same work whichever
    layout or kernel runs the ingest: it does not count the padding of
    narrower layers up to the widest."""
    live = weights != 0
    tenants = len(np.unique(keys[live].astype(np.int64) >> bits))
    per_tenant = sum(-(-c // LANES) * LANES for c in caps)
    return tenants * per_tenant * 3 * 4 * 2 + len(keys) * 8


def least_bytes(config, blocks) -> List[int]:
    caps = spec(config).layer_capacities()
    bits = config.params["bits"]
    return [block_least_bytes(ci, cw, bits, caps) for ci, cw in blocks]


# -- the service under the window ---------------------------------------------


class Served:
    """The per-tenant dyadic service, with the quantile subscriptions
    made on the largest tenants."""

    def __init__(self, cell, traffic):
        from repro.serve import SketchService

        c = cell.config
        self.cell = cell
        self.svc = SketchService(spec(c), block=c.block,
                                 spill_after=c.spill_after)
        self.qs = np.asarray(c.params["quantiles"], np.float32)
        n = c.params["quantile_subscriptions"]
        self.subscribed = [int(t) for t in np.argsort(
            -traffic.sizes, kind="stable")[:n]]
        for t in self.subscribed:
            self.svc.subscribe_quantile(t, self.qs)
        self.answers: Dict[int, list] = {t: [] for t in self.subscribed}

    def warm_up(self, tick, query, quiet: int) -> None:
        """A tick with nothing pending answers every subscription: the
        batched search is compiled before the window."""
        tick()

    def after_tick(self, i: int) -> None:
        for t in self.subscribed:
            v = self.svc.quantile_result(t)
            if v is not None:
                self.answers[t].append((i, np.asarray(v)))

    def record(self, drv, sample, win) -> "Record":
        tr, c = drv.traffic, self.cell.config

        def update(u):
            if isinstance(u, tuple):
                return u
            s0, ln = tr.start[u], tr.length[u]
            return (int(tr.tenant[u]), tr.keys[s0:s0 + ln],
                    tr.weights[s0:s0 + ln])

        return Record(item_bits=c.params["bits"], block=c.block,
                      tenants=c.tenants, spill_after=c.spill_after,
                      kept=list(self.subscribed),
                      fed=[[update(u) for u in ups] for ups, _ in drv.log],
                      asked=[qs for _, qs in drv.log],
                      first_tick=drv.first_tick,
                      program_blocks=self.svc.trace_blocks,
                      program_admits=self.svc.trace_admits,
                      unacknowledged=win.unacknowledged,
                      caps=spec(c).layer_capacities(),
                      eps=c.params["eps"], qs=self.qs,
                      answers={t: v for t, v in self.answers.items()
                               if t in sample},
                      rows={t: self.svc.tenant_snapshot(t) for t in sample})


# -- the check ----------------------------------------------------------------


@dataclasses.dataclass
class Record(Fed):
    caps: List[int]
    eps: float
    qs: np.ndarray
    answers: Dict[int, List[Tuple[int, np.ndarray]]]
    rows: Dict[int, Dict[str, np.ndarray]]   # final program rows, mass


def _tenant_values(blocks, bits: int, sample) -> Dict[int, Dict[int, tuple]]:
    """For each sampled tenant, its (values, weights) of each block that
    has any."""
    want = np.asarray(sorted(sample), np.int64)
    out = {t: {} for t in sample}
    for b, (ci, cw) in enumerate(blocks):
        live = cw != 0
        keys = ci[live].astype(np.int64)
        ten = keys >> bits
        hit = np.isin(ten, want)
        if not hit.any():
            continue
        keys, w, ten = keys[hit], cw[live][hit], ten[hit]
        for t in np.unique(ten).tolist():
            m = ten == t
            out[t][b] = (keys[m] & ((1 << bits) - 1), w[m])
    return out


def _expected_rows(lv: ref.Levels, t: int, bits: int, k: int) -> dict:
    """The reference's layers as the bank holds them: ids tagged with
    their row ``t*bits + l``, each row's slots past its layer's capacity
    BLOCKED."""
    out = {f: np.empty((bits, k), np.int64) for f in ("ids", "counts",
                                                       "errors")}
    for l, row in enumerate(lv.rows):
        c = len(row.ids)
        tag = np.int64(t * bits + l) << bits
        out["ids"][l] = np.r_[np.where(row.ids >= 0, tag | row.ids, row.ids),
                              np.full(k - c, BLOCKED)]
        out["counts"][l] = np.r_[row.counts, np.full(k - c, INT_MAX)]
        out["errors"][l] = np.r_[row.errors, np.zeros(k - c, np.int64)]
    return out


def _served_layers(got: dict, t: int, bits: int):
    """(node ids, counts) of each layer of a program row snapshot: the
    ids a query of the layer can match, those tagged with the layer's own
    row and inside its ``2^(bits - l)`` nodes (-1 elsewhere)."""
    ids = np.asarray(got["ids"]).astype(np.int64)
    node = ids & ((1 << bits) - 1)
    out = []
    for l in range(bits):
        own = ((ids[l] >> bits) == t * bits + l) & (node[l] < 1 << (bits - l))
        out.append((np.where((ids[l] >= 0) & own, node[l], -1),
                    np.asarray(got["counts"][l], np.int64)))
    return out


def _over(q: float, x: int, cum: np.ndarray, mass: int, eps: float) -> float:
    """How far x's exact rank range [rank(x - 1), rank(x)] lies from
    q·mass, in units of eps·mass (0 where it holds q·mass)."""
    if mass <= 0:
        return 0.0
    hi = int(cum[x])
    lo = int(cum[x - 1]) if x > 0 else 0
    return max(0.0, q * mass - hi, lo - q * mass) / (eps * mass)


def compare(rec: Record, sample, control: bool = False) -> dict:
    """The compared numbers (see the module docstring) and what they
    covered."""
    bits, eps = rec.item_bits, rec.eps
    feed = expected_feed(rec)
    per = _tenant_values(feed.blocks, bits, sample)
    nums = dict.fromkeys(LIMITS, 0)
    nums["rank_error_over_bound"] = 0.0
    nums["quantile_error_over_bound"] = 0.0
    nums["feed_differing"] = feed_differing(rec, feed)
    cover = {"blocks": len(rec.program_blocks), "rows": 0, "answers": 0,
             "values": 0}
    fed_by = {t: [] for t in sample}
    for ups in rec.fed:
        for u, items, w in ups:
            if u in fed_by:
                fed_by[u].append((items, w))
    for t in sample:
        lv = ref.Levels(rec.caps)
        prev = lv
        exact = np.zeros(1 << bits, np.int64)
        mine = per[t]
        ans = {rec.first_tick + i: v for i, v in rec.answers.get(t, [])}
        for i, (b0, b1, _, _) in enumerate(feed.ticks):
            if control and i in ans:
                prev = lv.copy()
            for b in range(b0, b1):
                if b in mine:
                    lv.update(*mine[b])
                    np.add.at(exact, mine[b][0], mine[b][1])
            if i not in ans:
                continue
            want = lv.quantiles(rec.qs)
            served = prev.quantiles(rec.qs) if control else ans[i]
            nums["quantile_answers_differing"] += int(
                not np.array_equal(np.asarray(served, np.int64), want))
            cum = np.cumsum(exact)
            for q, x in zip(rec.qs.tolist(), np.asarray(served).tolist()):
                nums["quantile_error_over_bound"] = max(
                    nums["quantile_error_over_bound"],
                    _over(q, int(x), cum, lv.mass, eps))
            cover["answers"] += 1
        k = len(np.asarray(rec.rows[t]["ids"])[0])
        want_rows = _expected_rows(lv, t, bits, k)
        got = rec.rows[t]
        if control:
            got = dict(want_rows, mass=lv.mass)
        same = int(got["mass"]) == lv.mass and all(
            np.array_equal(np.asarray(got[f]).astype(np.int64), want_rows[f])
            for f in ("ids", "counts", "errors"))
        nums["rows_differing"] += int(not same)
        cover["rows"] += 1

        # the rank guarantee, on the served rows, against exact ranks of
        # what the driver submitted
        fed = fed_by[t]
        if fed:
            truth = np.zeros(1 << bits, np.int64)
            np.add.at(truth, np.concatenate([i for i, _ in fed]).astype(
                np.int64), np.concatenate([w for _, w in fed]))
            mass = int(got["mass"])
            xs = np.arange(1 << bits)
            est = ref.ranks(bits, _served_layers(got, t, bits), mass, xs)
            if mass > 0:
                nums["rank_error_over_bound"] = max(
                    nums["rank_error_over_bound"],
                    float(np.abs(est - np.cumsum(truth)).max()
                          / (eps * mass)))
            cover["values"] += int((truth != 0).sum())
    nums["ops_unacknowledged"] = int(rec.unacknowledged)
    return {"numbers": nums, "covered": cover}


def verdict(nums: dict) -> bool:
    return all(nums[k] <= lim for k, lim in LIMITS.items())


def draw_sample(sizes: np.ndarray, admitted, seed: int, n_largest: int = 8,
                n_random: int = 8):
    """The tenants the check replays: the ``n_largest`` by traffic (the
    quantile subscriptions follow them), and ``n_random`` drawn from the
    seed among the rest that sent updates. Nothing spills, so
    ``admitted`` is empty."""
    top = [int(t) for t in np.argsort(-sizes, kind="stable")[:n_largest]]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x0A17]))
    rest = np.setdiff1d(np.flatnonzero(sizes), top)
    pick = rng.choice(rest, min(len(rest), n_random), replace=False)
    return top + sorted(int(t) for t in pick)
