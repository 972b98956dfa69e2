"""The one traffic generator of the benchmark: a mix file's parameters in,
a seeded list of service operations out.

A mix (``chipbench/mixes/<name>.json``) sets every parameter below; a
new mix is a new data file, never new code. The shape is that of
``benchmarks.common.mixed_traffic`` (the multi-tenant day the service
was built against), vectorised so that set-up makes millions of
operations without a loop over tenants:

* tenant sizes: inserts shared over tenants by weight
  ``rank^-tenant_skew`` (``mixed_traffic`` draws them multinomially;
  here they are the expected counts, the same set for every seed, and
  the seed deals them out to tenant ids). With ``hot_tenants`` N > 0,
  all of them go to N tenants drawn by the seed, shared among those N
  by the same rule, and every other tenant sends nothing;
* each tenant's own stream: its inserts (items from ``item_dist`` over
  ``2^universe_bits``), then ``floor(delete_ratio * inserts)`` deletions
  of those inserts — the paper's bounded-deletion stream with
  ``order='inserts_first'`` (alpha = 1 / (1 - delete_ratio)), so no
  item's count ever goes negative. ``delete_pattern`` picks which:
  ``random``, a uniform subset of the inserts in random order, or
  ``targeted``, the tenant's least frequent items first, every copy of
  one before the next (ties to the item inserted first) — the paper's
  adversarial case (``core.streams.deletions_targeted`` with inserts
  first, as ``benchmarks.common.adversarial_stream`` states it);
* each tenant stream is cut into update operations of ``burst`` keys;
  after a share ``query_frac`` of them (drawn at random), a point query
  of ``query_keys`` items drawn (with replacement) from that burst;
* the operations of all tenants interleave at random, each tenant's own
  order kept (``mixed_traffic``'s label shuffle).

Item distributions: ``zipf`` (truncated Zipf of ``item_skew`` over the
universe, as ``core.streams.zipf_insertions``) and ``caida`` (the
CAIDA-2015 surrogate of ``core.streams.caida_like_insertions``: 90%
Zipf(1.2), 10% uniform background) are built in; any other name is the
file ``chipbench/dists/<name>.py`` (its docstring says what it
supplies).

A traffic "epoch" is one such day of ``epoch_updates`` updates. An
open-loop mix (``arrival: open``) offers updates at ``rate`` per second
for the window, so its one epoch holds ``rate * seconds`` updates (less
the deletions each tenant rounds down) and each operation is due when
the updates before it would have arrived at an even pace. With
``arrival_cv`` > 0 the gaps after each update operation are instead
its even-pace gap times a Gamma draw of mean 1 and coefficient of
variation ``arrival_cv``, scaled so that the window still offers
``rate`` per second (bursty arrivals, as BurstGPT, arXiv:2401.17644,
fits them). A saturated mix (``arrival: saturated``) has no due times:
its epochs (``epochs`` of them, each from its own seed) are played back
to back, and from the start again if a run ever reaches their end,
which stays a valid bounded-deletion stream since every epoch is one.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Optional

import numpy as np

UPDATE, QUERY = 0, 1
BUILT_IN_DISTS = ("zipf", "caida")


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    arrival: str                 # "open" | "saturated"
    tenant_skew: float
    item_dist: str               # "zipf" | "caida"
    universe_bits: int
    delete_ratio: float
    burst: int
    query_frac: float = 0.0
    query_keys: int = 8
    item_skew: float = 1.0
    rate: float = 0.0            # open loop: offered updates per second
    epoch_updates: int = 0       # saturated: updates per epoch
    epochs: int = 1              # saturated: epochs generated in set-up
    topk_subscriptions: int = 0  # top-k subscribed on the largest tenants
    topk_m: int = 16
    hot_tenants: int = 0         # > 0: all traffic on that many tenants
    delete_pattern: str = "random"   # "random" | "targeted"
    arrival_cv: float = 0.0      # open loop: CV of the gaps (0: even pace)

    @staticmethod
    def load(path: str) -> "Mix":
        with open(path) as f:
            d = json.load(f)
        d = {k: v for k, v in d.items() if not k.startswith("_")}
        d.setdefault("name", os.path.splitext(os.path.basename(path))[0])
        unknown = set(d) - {f.name for f in dataclasses.fields(Mix)}
        if unknown:
            raise ValueError(f"{path}: unknown keys {sorted(unknown)}")
        mix = Mix(**d)
        if mix.arrival not in ("open", "saturated"):
            raise ValueError(f"{path}: arrival must be open or saturated")
        if mix.delete_pattern not in ("random", "targeted"):
            raise ValueError(f"{path}: delete_pattern must be random or "
                             f"targeted")
        if mix.hot_tenants < 0 or mix.arrival_cv < 0:
            raise ValueError(f"{path}: hot_tenants and arrival_cv must "
                             f"not be negative")
        if mix.item_dist not in BUILT_IN_DISTS:
            try:
                dist_module(mix.item_dist)
            except ModuleNotFoundError as e:
                raise ValueError(f"{path}: item_dist {mix.item_dist!r} is "
                                 f"neither built in {BUILT_IN_DISTS} nor "
                                 f"chipbench/dists/{mix.item_dist}.py") from e
        return mix


def dist_module(name: str):
    """The item distribution ``chipbench/dists/<name>.py``."""
    return importlib.import_module(f"chipbench.dists.{name}")


@dataclasses.dataclass
class Traffic:
    """Operations in global order, as flat arrays.

    Operation ``i`` is ``kind[i]`` for ``tenant[i]`` over
    ``keys[start[i]:start[i] + length[i]]`` (``weights`` alongside for
    updates; a query's ``keys`` are its probes, weights 0). ``due`` is
    the operation's due time in seconds from the window's start (open
    loop) or None (saturated). ``sizes`` are the updates each tenant
    sends over all operations.
    """

    kind: np.ndarray
    tenant: np.ndarray
    start: np.ndarray
    length: np.ndarray
    keys: np.ndarray
    weights: np.ndarray
    due: Optional[np.ndarray]
    sizes: np.ndarray

    @property
    def n_ops(self) -> int:
        return len(self.kind)

    @property
    def n_updates(self) -> int:
        return int(self.length[self.kind == UPDATE].sum())


def _zipf_cdf(universe: int, skew: float) -> np.ndarray:
    p = np.arange(1, universe + 1, dtype=np.float64) ** -float(skew)
    return np.cumsum(p / p.sum())


def sample_items(rng: np.random.Generator, dist: str, n: int, universe: int,
                 skew: float = 1.0) -> np.ndarray:
    """``n`` item ids from ``dist`` over ``[0, universe)`` (rank = id)."""
    def zipf(m, s):
        cdf = _zipf_cdf(universe, s)
        return np.minimum(np.searchsorted(cdf, rng.random(m), side="right"),
                          universe - 1)

    if dist == "zipf":
        return zipf(n, skew).astype(np.int32)
    if dist not in BUILT_IN_DISTS:
        return np.asarray(dist_module(dist).sample(rng, n, universe, skew),
                          np.int32)
    # caida: 90% Zipf(1.2) body, 10% uniform background, mixed per draw
    body = rng.random(n) < 0.9
    out = rng.integers(0, universe, size=n)
    out[body] = zipf(int(body.sum()), 1.2)
    return out.astype(np.int32)


def tenant_sizes(n: int, num_tenants: int, skew: float) -> np.ndarray:
    """``n`` inserts shared out by rank weight ``rank^-skew``: the
    expected multinomial counts, rounded by largest remainder, so that
    every seed sends the same set of tenant sizes (in another order)."""
    p = np.arange(1, num_tenants + 1, dtype=np.float64) ** -float(skew)
    share = n * p / p.sum()
    sizes = np.floor(share).astype(np.int64)
    rest = np.argsort(-(share - sizes), kind="stable")[:n - sizes.sum()]
    sizes[rest] += 1
    return sizes


def tenant_day(rng: np.random.Generator, mix: Mix, num_tenants: int,
               n_updates: int):
    """One epoch: ``(kind, tenant, start, length, keys, weights, sizes)``
    with operations in global order and ``sizes`` per tenant."""
    universe = 1 << mix.universe_bits
    n_ins = int(round(n_updates / (1.0 + mix.delete_ratio)))
    if mix.hot_tenants:
        hot = rng.choice(num_tenants, mix.hot_tenants, replace=False)
        ins_t = np.zeros(num_tenants, np.int64)
        ins_t[hot] = rng.permutation(tenant_sizes(n_ins, mix.hot_tenants,
                                                  mix.tenant_skew))
    else:
        ins_t = rng.permutation(tenant_sizes(n_ins, num_tenants,
                                             mix.tenant_skew))
    del_t = np.floor(mix.delete_ratio * ins_t).astype(np.int64)
    ins_tenant = np.repeat(np.arange(num_tenants), ins_t)
    ins_items = sample_items(rng, mix.item_dist, n_ins, universe,
                             mix.item_skew)

    first_ins = np.concatenate([[0], np.cumsum(ins_t)[:-1]])
    if mix.delete_pattern == "targeted":
        del_items = targeted_deletions(ins_tenant, ins_items, first_ins,
                                       del_t, mix.universe_bits)
    else:
        # a uniform subset of each tenant's inserts, random order
        order = np.lexsort((rng.random(n_ins), ins_tenant))
        rank = np.arange(n_ins) - first_ins[ins_tenant[order]]
        dels = order[rank < del_t[ins_tenant[order]]]  # tenant-major
        del_items = ins_items[dels]
    # each tenant's stream: its inserts, then its deletions
    len_t = ins_t + del_t
    first = np.concatenate([[0], np.cumsum(len_t)[:-1]])
    pos_ins = first[ins_tenant] + (np.arange(n_ins) - first_ins[ins_tenant])
    del_tenant = np.repeat(np.arange(num_tenants), del_t)
    first_del = np.concatenate([[0], np.cumsum(del_t)[:-1]])
    pos_del = first[del_tenant] + ins_t[del_tenant] + (
        np.arange(len(del_items)) - first_del[del_tenant])
    total = int(len_t.sum())
    s_items = np.empty(total, np.int32)
    s_w = np.empty(total, np.int32)
    s_items[pos_ins] = ins_items
    s_w[pos_ins] = 1
    s_items[pos_del] = del_items
    s_w[pos_del] = -1

    # update operations: bursts of each tenant's stream, tenant-major
    nb_t = -(-len_t // mix.burst)
    b_tenant = np.repeat(np.arange(num_tenants), nb_t)
    first_b = np.concatenate([[0], np.cumsum(nb_t)[:-1]])
    b_idx = np.arange(len(b_tenant)) - first_b[b_tenant]
    b_start = first[b_tenant] + b_idx * mix.burst
    b_len = np.minimum(mix.burst, first[b_tenant] + len_t[b_tenant] - b_start)
    # a query after a share ``query_frac`` of the bursts, drawn at
    # random, probing items of that burst
    n_q = int(round(mix.query_frac * len(b_tenant)))
    has_q = np.zeros(len(b_tenant), bool)
    has_q[rng.choice(len(b_tenant), n_q, replace=False)] = True
    qb = np.flatnonzero(has_q)
    probe = (b_start[qb, None] + np.floor(
        rng.random((len(qb), mix.query_keys)) * b_len[qb, None])
        .astype(np.int64))
    q_keys = s_items[probe].reshape(-1)

    # tenant-major operation list: each burst, then its query if any
    n_ops = len(b_tenant) + len(qb)
    slot_u = np.arange(len(b_tenant)) + np.concatenate(
        [[0], np.cumsum(has_q)[:-1]])
    slot_q = slot_u[qb] + 1
    kind = np.empty(n_ops, np.int8)
    tenant = np.empty(n_ops, np.int32)
    start = np.empty(n_ops, np.int64)
    length = np.empty(n_ops, np.int32)
    kind[slot_u], kind[slot_q] = UPDATE, QUERY
    tenant[slot_u], tenant[slot_q] = b_tenant, b_tenant[qb]
    start[slot_u], length[slot_u] = b_start, b_len
    start[slot_q] = total + np.arange(len(qb)) * mix.query_keys
    length[slot_q] = mix.query_keys
    keys = np.concatenate([s_items, q_keys.astype(np.int32)])
    weights = np.concatenate([s_w, np.zeros(len(q_keys), np.int32)])

    # interleave tenants at random, each tenant's own order kept
    labels = tenant.copy()
    rng.shuffle(labels)
    g = np.empty(n_ops, np.int64)     # g[position] = tenant-major op
    g[np.argsort(labels, kind="stable")] = np.arange(n_ops)
    return (kind[g], tenant[g], start[g], length[g], keys, weights,
            len_t)


def targeted_deletions(ins_tenant: np.ndarray, ins_items: np.ndarray,
                       first_ins: np.ndarray, del_t: np.ndarray,
                       item_bits: int) -> np.ndarray:
    """Each tenant's ``del_t`` deletions, tenant-major: its least
    frequent items first, every copy of one before the next, ties to the
    item inserted first (``core.streams.deletions_targeted`` per tenant,
    over inserts sorted by tenant)."""
    key = (ins_tenant.astype(np.int64) << item_bits) | ins_items
    uk, first, cnt = np.unique(key, return_index=True, return_counts=True)
    ut = uk >> item_bits
    order = np.lexsort((first, cnt, ut))
    uk, ut, cnt = uk[order], ut[order], cnt[order]
    before = np.cumsum(cnt) - cnt - first_ins[ut]   # copies ahead, per tenant
    take = np.clip(del_t[ut] - before, 0, cnt)
    return np.repeat(uk & ((1 << item_bits) - 1), take).astype(np.int32)


def generate(mix: Mix, num_tenants: int, seed: int,
             seconds: float) -> Traffic:
    """The operations of one run of ``mix`` over ``num_tenants`` tenants."""
    if mix.arrival == "open":
        days = [(seed, int(round(mix.rate * seconds)))]
    else:
        days = [((seed, e), mix.epoch_updates) for e in range(mix.epochs)]
    parts = []
    base = 0
    for s, n in days:
        rng = np.random.default_rng(np.random.SeedSequence(
            [int(x) for x in np.ravel(s)] + [0x5EED]))
        kind, tenant, start, length, keys, weights, sizes = tenant_day(
            rng, mix, num_tenants, n)
        parts.append((kind, tenant, start + base, length, keys, weights,
                      sizes))
        base += len(keys)
    cat = lambda i: np.concatenate([p[i] for p in parts])
    kind, length = cat(0), cat(3)
    due = None
    if mix.arrival == "open":
        # spread evenly over the window: each tenant's deletions round
        # down, so a day holds slightly fewer than rate * seconds updates
        upd = np.where(kind == UPDATE, length, 0).astype(np.float64)
        if mix.arrival_cv > 0:
            shape = mix.arrival_cv ** -2.0
            rng = np.random.default_rng(np.random.SeedSequence(
                [int(seed), 0xB0257]))
            upd = upd * rng.gamma(shape, 1.0 / shape, len(upd))
        due = np.concatenate([[0.0], np.cumsum(upd)[:-1]]) * (
            seconds / upd.sum())
    return Traffic(kind=kind, tenant=cat(1), start=cat(2), length=length,
                   keys=cat(4), weights=cat(5), due=due,
                   sizes=np.sum([p[6] for p in parts], axis=0))
