"""Unified bank engine: one fused multi-row SpaceSaving± ingest core.

The paper's SpaceSaving± update (Algs 1-4) and its Dyadic extension
(Algs 5-6) are the same counter-summary algorithm instantiated at
different row granularities — the "SpaceSaving± Family" follow-up
(PAPERS.md) treats the variants as one family over a shared summary.
This module is that observation as code: ONE stacked ``(R, k)``
:class:`SketchState` bank, per-row capacity masks (BLOCKED sentinel
padding), and a pluggable **router** deciding what a row means —

  * :class:`HashShardRouter`   rows are hash shards; every item id is
    owned by exactly one row (``repro.sketch.sharded``);
  * :class:`DyadicLevelRouter` rows are dyadic layers; every item feeds
    every row as ``x >> level`` (``repro.sketch.dyadic``);
  * :class:`ShardLevelRouter`  the composition: rows are
    (shard, level) pairs, item x feeds row (shard_of(x >> l), l) — the
    mesh-distributed Dyadic bank (``repro.sketch.dyadic_sharded``);
  * :class:`TenantRouter`      rows are tenants (× per-tenant hash
    shards); composite keys (tenant << item_bits) | item route to the
    owning tenant's rows only — the multi-tenant service bank
    (``repro.sketch.tenant``).

Routers are frozen dataclasses (hashable → jit-static) with two duties:
``route_dense(items, weights) -> (R, B) row-sorted views`` and, for
partition routers, ``owner_of(items) -> owner row per id``. Both router
kinds share ONE ``B log B`` sort of the raw block: hash routing
broadcasts the sorted block with foreign weights masked to 0, level
routing right-shifts it (monotone, so every row view stays ascending).

Two fused ingest cores sit under ``update_block_fused``:

  * ``_fused_partition`` — the hash-sharded fast path (PR 3): phase 1
    runs ONCE on global (B,) arrays (shared sort, in-place segment
    aggregation, one searchsorted monitored match for all rows, ONE
    packed-key grouping sort building every row's
    [units | non-units | consumed] layout), and only the O(k)-per-row
    phases run batched over the bank.
  * ``_fused_dense`` — the broadcast path: batched phase 1 directly on
    the (R, B) matrices (per-row prefix-sum aggregation, vmapped
    first-occurrence match, ONE batched within-row grouping sort) with
    no per-row vmap of scatter ops.

Both feed the same banked phase 2, ``residual_phase_banked``: all rows'
eviction loops in lockstep on the FLAT (R, k) store with one-hot
where-mask updates — semantically ``vmap(phases.residual_phase)`` but
without the batched scatter/gather ops vmap generates (CPU XLA lowers
those to per-element loops costing ~4x a plain trip). Results are
bit-identical to running ``blocks.block_update`` per row on that row's
own substream/view — the invariant every client's differential test
pins (tests/test_sharded.py, test_dyadic_jax.py, test_bank.py).

Row layout contract (DESIGN.md §10): row r's live capacity is
``cap_r <= k``; slots beyond it carry BLOCKED ids, INT_MAX counts and
zero errors — inert under every phase. Weight > 0 insert, < 0 delete,
0 padding; item ids non-negative (negative = sentinel).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from . import state as st
from .phases import (
    _stable_partition_perm,
    fill_empty_slots,
    segment_nets,
    waterfill_unit_inserts,
)
from .state import (BLOCKED, EMPTY, VARIANT_LAZY, SketchState, _INT_MAX,
                    sat_add)


def init(capacities: Union[int, Sequence[int]],
         num_rows: Optional[int] = None) -> SketchState:
    """Empty (R, k) bank with per-row live capacities.

    ``capacities``: either a per-row capacity list (rows with smaller
    caps pad their tail with BLOCKED sentinel slots — ids = -2,
    counts = INT_MAX, errors = 0, inert under every phase) or a single
    int applied to ``num_rows`` equal rows.
    """
    if isinstance(capacities, (int, np.integer)):
        assert num_rows is not None and num_rows >= 1
        caps = [int(capacities)] * num_rows
    else:
        caps = [int(c) for c in capacities]
        assert num_rows is None or num_rows == len(caps)
    k = max(caps)
    lane = np.arange(k)[None, :]
    real = lane < np.asarray(caps)[:, None]  # (R, k) live-slot mask
    return SketchState(
        ids=jnp.asarray(np.where(real, int(EMPTY), int(BLOCKED)), jnp.int32),
        counts=jnp.asarray(np.where(real, 0, int(_INT_MAX)), jnp.int32),
        errors=jnp.zeros((len(caps), k), jnp.int32),
    )


def row_capacities(bank: SketchState) -> list:
    """Live (non-BLOCKED) counters per row — the inverse of ``init``."""
    ids = jax.device_get(bank.ids)
    return [int(c) for c in np.asarray(ids != int(BLOCKED)).sum(1)]


def shard_of(items: jax.Array, num_shards: int) -> jax.Array:
    """Owner shard of each item id: lowbias32 avalanche hash mod S.

    A multiplicative-xorshift finalizer (not ``id % S``) so that
    structured id spaces — strided token ids, dyadic prefixes, expert
    indices — still spread uniformly. Pure function of (id, S): any
    host, device or restart routes a uid identically (the routing
    invariant tests/test_sharded.py pins).
    """
    x = items.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return (x % jnp.uint32(num_shards)).astype(jnp.int32)


def sort_block(items: jax.Array, universe_bits: Optional[int]) -> jax.Array:
    """Shared ascending-id sort permutation for the whole bank.

    Packed-key single sort when the static universe bound proves
    ``item * B`` fits int32 (argsort lowers ~4x slower on CPU XLA), else
    one argsort — either way the ONLY B log B sort paid per block.
    """
    B = items.shape[0]
    if universe_bits is not None and universe_bits + (B - 1).bit_length() <= 31:
        return _stable_partition_perm(items)
    return jnp.argsort(items)


# ---------------------------------------------------------------------------
# Routers: what a bank row means
# ---------------------------------------------------------------------------

def _partition_route_dense(router, items: jax.Array,
                           weights: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Shared partition routing: (B,) block -> (R, B) row views.

    ONE shared sort, the sorted block broadcast to every row with
    foreign weights masked to 0. Every row stays ascending, so
    downstream aggregation runs sorted-free, and each row aggregates to
    exactly its own (uid, net) multiset: zero-net foreign uniques are
    dropped by the validity mask, preserving bit-identity with
    independently built rows.
    """
    items = items.astype(jnp.int32)
    weights = weights.astype(jnp.int32)
    order = sort_block(items, router.universe_bits)
    s_items = items[order]
    s_w = weights[order]
    owner = router.owner_of(s_items)
    rows = jnp.arange(router.num_rows, dtype=jnp.int32)[:, None]
    w_routed = jnp.where(owner[None, :] == rows, s_w[None, :], 0)
    items_b = jnp.broadcast_to(
        s_items[None, :], (router.num_rows, items.shape[0]))
    return items_b, w_routed


@dataclasses.dataclass(frozen=True)
class HashShardRouter:
    """Partition router: row = lowbias32 hash shard; one owner row per id.

    ``universe_bits``: static log2(universe) bound enabling the packed
    single-sort router (see ``sort_block``).
    """

    num_shards: int
    universe_bits: Optional[int] = None
    kind = "partition"

    @property
    def num_rows(self) -> int:
        return self.num_shards

    def owner_of(self, items: jax.Array) -> jax.Array:
        return shard_of(items, self.num_shards)

    def route_dense(self, items: jax.Array,
                    weights: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """(B,) block -> (S, B): sorted block broadcast, foreign weights 0."""
        return _partition_route_dense(self, items, weights)


@dataclasses.dataclass(frozen=True)
class TenantRouter:
    """Partition router for multi-tenant banks: row = tenant (× shard).

    Items arrive as composite routing keys ``(tenant << item_bits) |
    item`` (``repro.sketch.tenant.pack_keys``). The router peels the
    tenant off the high bits, and — when ``num_shards > 1`` — hashes the
    *item part* with the same lowbias32 ``shard_of`` a per-tenant
    ``HashShardRouter(num_shards)`` applies to raw items, so each
    tenant's rows partition its stream exactly like an independently
    built sharded sketch (the bit-identity tests/test_tenant.py pins).
    Rows are tenant-major: tenant t owns rows ``[t*S, (t+1)*S)``.

    Composite keys from different tenants never collide, so ownership —
    and therefore monitoring, queries and top-k — never crosses a tenant
    boundary: isolation is routing, not bookkeeping. Composes with the
    dyadic layout the way ``ShardLevelRouter`` composes shard × level: a
    dyadic bank over composite keys answers per-tenant ranks/quantiles
    as range differences inside the tenant's key range
    (``repro.sketch.tenant.tenant_rank_many``).
    """

    num_tenants: int
    item_bits: int
    num_shards: int = 1
    kind = "partition"

    @property
    def tenant_bits(self) -> int:
        return (self.num_tenants - 1).bit_length()

    @property
    def universe_bits(self) -> int:
        # static composite-key bound -> packed single-sort eligibility
        return self.item_bits + self.tenant_bits

    @property
    def num_rows(self) -> int:
        return self.num_tenants * self.num_shards

    @property
    def monotone_owner(self) -> bool:
        """Owner row is non-decreasing in composite-key order.

        With one row per tenant the owner is the key's high bits, so the
        fused ingest's shared sort leaves every row's entries in one
        contiguous run — ``_fused_partition`` swaps its (R, B) one-hot
        ranks/tallies for O(B + R) prefix-sum differences, the step that
        otherwise dominates once rows reach the thousands (multi-tenant
        banks). Per-tenant hash shards break monotonicity.
        """
        return self.num_shards == 1

    def owner_of(self, keys: jax.Array) -> jax.Array:
        keys = keys.astype(jnp.int32)
        tenant = jnp.right_shift(keys, self.item_bits)
        if self.num_shards == 1:
            return tenant
        item = jnp.bitwise_and(keys, (1 << self.item_bits) - 1)
        return tenant * self.num_shards + shard_of(item, self.num_shards)

    def route_dense(self, items: jax.Array,
                    weights: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """(B,) block -> (T*S, B): sorted block broadcast, foreign 0."""
        return _partition_route_dense(self, items, weights)


@dataclasses.dataclass(frozen=True)
class DyadicLevelRouter:
    """Broadcast router: row l monitors ``x >> l`` (the dyadic layers)."""

    bits: int
    kind = "dense"

    @property
    def num_rows(self) -> int:
        return self.bits

    def route_dense(self, items: jax.Array,
                    weights: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """(B,) block -> (bits, B) per-layer node views, ONE shared sort.

        Right-shift is monotonic, so the sorted block stays sorted in
        every layer view — each row's aggregation skips its own
        O(B log B) sort.
        """
        items = items.astype(jnp.int32)
        weights = weights.astype(jnp.int32)
        order = sort_block(items, self.bits)
        shifts = jnp.arange(self.bits, dtype=jnp.int32)[:, None]
        items_l = jnp.right_shift(items[order][None, :], shifts)
        # every row shares ONE weight vector: return it (1, B) so the
        # engine's aggregation prefix-sums it once, not ``bits`` times
        return items_l, weights[order][None, :]


@dataclasses.dataclass(frozen=True)
class ShardLevelRouter:
    """Composed shard × level router: row (s, l) monitors the level-l
    nodes owned by hash shard s — rows ordered shard-major
    (``row = s * bits + l``) so a mesh shards the leading axis by
    slicing whole shards.

    Equals sequential application of the two routings (property-tested):
    dyadic-shift first, then hash-partition each layer's node stream.
    """

    bits: int
    num_shards: int
    kind = "dense"

    @property
    def num_rows(self) -> int:
        return self.bits * self.num_shards

    def route_dense(self, items: jax.Array,
                    weights: jax.Array) -> Tuple[jax.Array, jax.Array]:
        nodes, w_l = DyadicLevelRouter(self.bits).route_dense(items, weights)
        B = nodes.shape[1]
        shape = (self.num_rows, B)
        items_b = jnp.broadcast_to(
            nodes[None], (self.num_shards, self.bits, B))
        return items_b.reshape(shape), self.mask_shards(nodes, w_l).reshape(
            shape)

    def mask_shards(self, nodes: jax.Array, w_l: jax.Array) -> jax.Array:
        """(bits, B) level weights -> (S, bits, B) with foreign weights 0.

        The one home of the shard-masking rule: ``route_dense`` reshapes
        its output to engine rows, the dyadic_sharded shard_map path
        partitions it over the mesh as-is — either way the same mask.
        """
        owner = shard_of(nodes, self.num_shards)          # (bits, B)
        rows = jnp.arange(self.num_shards, dtype=jnp.int32)[:, None, None]
        return jnp.where(owner[None] == rows, w_l[None], 0)


Router = Union[HashShardRouter, TenantRouter, DyadicLevelRouter,
               ShardLevelRouter]


# ---------------------------------------------------------------------------
# Banked phase 2: all rows' eviction loops in lockstep on the flat store
# ---------------------------------------------------------------------------

def residual_phase_banked(ids2, cnt2, err2, h_uids, h_net, uoff, start,
                          n_ins, w_del, variant: int):
    """Bank-wide phase 2: every row's eviction loop in lockstep.

    Semantically ``vmap(phases.residual_phase)`` — the while loops run
    until every row lane finishes, ≈ max_r(U_r) trips — but the body
    avoids the batched scatter/gather ops vmap generates (CPU XLA lowers
    those to per-element loops that cost ~4x a plain trip, cancelling
    the 1/S trip reduction of the sharded client). The store stays FLAT
    (R, k): a flat argmin over a row's k slots traverses the same
    elements as the (rows, LANES) tournament's reductions, so with every
    row reduced at once there is nothing for the two-level view to save.
    The body also drops the empty-slot branch of ``phases._pick_slot``
    outright: a row lane is only active while it still has non-unit
    residual inserts, which (phase 1.5) implies the bulk fill consumed
    every empty slot — pure min-count evictions, the same case analysis
    the single-sketch loop resolves dynamically. Inserts are read
    straight from the one global grouped layout at per-row offsets
    (``uoff``); the touched slot updates through a one-hot where-mask
    and finished lanes freeze via an ``active`` mask (the select
    semantics jax gives a batched while_loop). Tie-breaking matches flat
    argmin/argmax (lowest slot index), so results are bit-identical to
    the per-row loop. BLOCKED padding slots (INT_MAX counts, zero
    errors) are never the min count nor a positive-error spread target.
    """
    R, k = ids2.shape
    G = h_uids.shape[0]
    lane = jnp.arange(k, dtype=jnp.int32)[None, :]

    def ins_cond(carry):
        return (carry[0] < n_ins).any()

    def ins_step(carry):
        i, ids2, cnt2, err2 = carry
        active = i < n_ins
        g = jnp.clip(uoff + i, 0, G - 1)
        uid = h_uids[g]
        w = h_net[g]
        sel = jnp.argmin(cnt2, axis=1)
        mc = jnp.take_along_axis(cnt2, sel[:, None], axis=1)[:, 0]
        hot = (lane == sel[:, None]) & active[:, None]
        return (
            i + active.astype(jnp.int32),
            jnp.where(hot, uid[:, None], ids2),
            jnp.where(hot, sat_add(mc, w)[:, None], cnt2),
            jnp.where(hot, mc[:, None], err2),
        )

    _, ids2, cnt2, err2 = jax.lax.while_loop(
        ins_cond, ins_step, (start.astype(jnp.int32), ids2, cnt2, err2))

    if variant != VARIANT_LAZY:
        # the spread's (row, slot) argmax is carried incrementally so the
        # loop condition reads (R,) scalars, not an (R, k) reduction
        def sp_cond(carry):
            rem, _, _, sel, maxe = carry
            return ((rem > 0) & (maxe > 0)).any()

        def sp_step(carry):
            rem, cnt2, err2, sel, maxe = carry
            active = (rem > 0) & (maxe > 0)
            d = jnp.where(active, jnp.minimum(rem, maxe), 0)
            hot = (lane == sel[:, None]) & active[:, None]
            # saturating decrements: d <= maxe = err2[sel] and d <= rem,
            # so all three are exact for in-range states; a count already
            # at the negative rail absorbs the spread instead of wrapping
            nd2 = jnp.negative(d)[:, None]
            cnt2 = jnp.where(hot, sat_add(cnt2, nd2), cnt2)
            err2 = jnp.where(hot, sat_add(err2, nd2), err2)
            sel = jnp.argmax(err2, axis=1)
            maxe = jnp.take_along_axis(err2, sel[:, None], axis=1)[:, 0]
            return sat_add(rem, jnp.negative(d)), cnt2, err2, sel, maxe

        sel0 = jnp.argmax(err2, axis=1)
        maxe0 = jnp.take_along_axis(err2, sel0[:, None], axis=1)[:, 0]
        _, cnt2, err2, _, _ = jax.lax.while_loop(
            sp_cond, sp_step,
            (w_del.astype(jnp.int32), cnt2, err2, sel0, maxe0))
    return ids2, cnt2, err2


# ---------------------------------------------------------------------------
# Dense fused core: batched phase 1 on (R, B) row views
# ---------------------------------------------------------------------------

def phase1_dense_prep(bank: SketchState, row_items: jax.Array,
                      row_weights: jax.Array, variant: int):
    """The XLA half of the dense phase 1: everything that needs sorts,
    searchsorted or scatters, none of which lower inside a Mosaic
    kernel. Returns the per-cell state *delta* instead of mutating the
    bank, so the fused Pallas kernel can apply phases 1-2 on VMEM-
    resident tiles (kernels/sketch_update) while this path's own
    ``phase1_dense`` applies the identical arithmetic in XLA:

      1. per-row prefix-sum aggregation to (head, net) — every row is
         already ascending (router contract), so no sort at all;
      2. monitored matching for ALL rows with one vmapped searchsorted
         of the (R, k) bank ids into their own row's sorted view
         (first occurrence = segment head, where net is valid) ->
         ``delta``, the (R, k) monitored scatter addend;
      3. residual classification + ONE batched within-row grouping sort
         building every row's [units | non-units | consumed-by-fill]
         layout at once (the layout blocks._phase1 builds with two
         partition sorts, collapsed to one since the consumed prefix is
         known up front from in-row insert ranks).

    Only ``bank.ids`` is read (matching and the empty census); counts
    and errors are untouched, so the delta is valid however the
    consumer stages the apply. Returns ``(delta, h_uids, h_net, i0,
    mu, nnu, w_del)`` with ``h_uids``/``h_net`` the flattened (R*B,)
    grouped residual layout.
    """
    R, k = bank.ids.shape
    B = row_items.shape[1]
    row_items = row_items.astype(jnp.int32)
    row_weights = row_weights.astype(jnp.int32)
    idx = jnp.arange(B, dtype=jnp.int32)

    # -- 1. per-row aggregation (rows pre-sorted by the router) -----------
    head, net = segment_nets(row_items, row_weights)
    valid = head & (row_items >= 0) & (net != 0)

    # -- 2. monitored matching, all rows at once --------------------------
    # searchsorted returns the FIRST occurrence = the segment head; the
    # (ids >= 0) guard keeps EMPTY/BLOCKED slots from matching sentinel
    # padding items.
    pos = jnp.clip(jax.vmap(jnp.searchsorted)(row_items, bank.ids), 0, B - 1)
    match = (jnp.take_along_axis(row_items, pos, axis=1) == bank.ids) \
        & (bank.ids >= 0)
    delta = jnp.where(match, jnp.take_along_axis(net, pos, axis=1), 0)
    rows = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32)[:, None], (R, k))
    monitored = (
        jnp.zeros((R, B), bool)
        .at[rows, jnp.where(match, pos, B)]
        .set(True, mode="drop")
    )

    # -- 3. residual classification + ONE batched grouping sort -----------
    res_ins = valid & ~monitored & (net > 0)
    rank = jnp.cumsum(res_ins, axis=1) - 1      # in-row insert rank
    n_ins = res_ins.sum(axis=1)
    empties = (bank.ids == EMPTY).sum(axis=1)
    i0 = jnp.minimum(n_ins, empties)            # consumed by the bulk fill
    consumed = res_ins & (rank < i0[:, None])
    unit = res_ins & ~consumed & (net == 1)
    nonunit = res_ins & ~consumed & (net != 1)
    if variant == VARIANT_LAZY:
        w_del = jnp.zeros((R,), jnp.int32)
    else:
        res_del = valid & ~monitored & (net < 0)
        w_del = jnp.where(res_del, -net, 0).sum(axis=1)
    klass = jnp.where(
        res_ins, jnp.where(unit, 0, jnp.where(nonunit, 1, 2)), 3)
    # packed-key stable partition per row, ONE batched sort lowering
    perm = jnp.sort(klass * B + idx[None, :], axis=1) % B
    h_uids = jnp.take_along_axis(row_items, perm, axis=1).reshape(-1)
    h_net = jnp.take_along_axis(net, perm, axis=1).reshape(-1)
    mu = unit.sum(axis=1)
    nnu = nonunit.sum(axis=1)
    return delta, h_uids, h_net, i0, mu, nnu, w_del


def phase1_dense(bank: SketchState, row_items: jax.Array,
                 row_weights: jax.Array, variant: int):
    """Batched phases 1-1.75 on row-sorted (R, B) views — no per-row vmap
    of block orchestration, no compaction sorts.

    ``phase1_dense_prep`` (sorts/matching/grouping) followed by the
    in-place apply: saturating phase-1 scatter, then per-row slices of
    the one flattened grouped layout feed batched fill_empty_slots /
    waterfill_unit_inserts. The apply bodies are shared verbatim with
    the fused Pallas tile kernel, so the two stay bit-identical.

    Returns ``(ids1, cnt1, err1, h_uids, h_net, uoff, mu, nnu, w_del)``:
    the bank after the vectorized phases, the flattened (R*B,) grouped
    residual layout, per-row offsets of the unit run (``uoff``), unit /
    non-unit insert counts and summed unmonitored deletion weight — the
    banked residual loop's inputs.
    """
    R, k = bank.ids.shape
    B = row_items.shape[1]
    delta, h_uids, h_net, i0, mu, nnu, w_del = phase1_dense_prep(
        bank, row_items, row_weights, variant)
    counts1 = sat_add(bank.counts, delta)
    uoff = jnp.arange(R, dtype=jnp.int32) * B   # row r's run starts at r*B

    # -- 4. batched O(k) phases on the one global grouped layout ----------
    ids1, cnt1, err1, _ = jax.vmap(
        fill_empty_slots, in_axes=(0, 0, 0, None, None, 0, 0))(
        bank.ids, counts1, bank.errors, h_uids, h_net, i0, uoff + mu + nnu)
    ids1, cnt1, err1 = jax.vmap(
        waterfill_unit_inserts, in_axes=(0, 0, 0, None, 0, 0))(
        ids1, cnt1, err1, h_uids, mu, uoff)
    return ids1, cnt1, err1, h_uids, h_net, uoff, mu, nnu, w_del


def _fused_dense(bank: SketchState, row_items: jax.Array,
                 row_weights: jax.Array, variant: int) -> SketchState:
    """Dense fused ingest: batched phase 1 + the banked residual loop."""
    ids1, cnt1, err1, h_uids, h_net, uoff, mu, nnu, w_del = phase1_dense(
        bank, row_items, row_weights, variant)
    ids1, cnt1, err1 = residual_phase_banked(
        ids1, cnt1, err1, h_uids, h_net, uoff, mu, mu + nnu, w_del, variant)
    return SketchState(ids1, cnt1, err1)


@functools.partial(jax.jit, static_argnames=("variant",))
def update_rows(bank: SketchState, row_items: jax.Array,
                row_weights: jax.Array, variant: int = 2) -> SketchState:
    """Public dense entry: ingest pre-routed row-sorted (R, B) views.

    For callers that route themselves (the shard_map local program, the
    dyadic bank after its shared sort). Every row of ``row_items`` must
    be ascending; bit-identical to ``blocks.block_update(row, ...,
    assume_sorted=True)`` per row.
    """
    return _fused_dense(bank, row_items, row_weights, variant)


# ---------------------------------------------------------------------------
# Partition fused core: global phase 1, one grouping sort for all rows
# ---------------------------------------------------------------------------

def _fused_partition(bank: SketchState, items: jax.Array, weights: jax.Array,
                     router: HashShardRouter, variant: int) -> SketchState:
    """Fused single-launch partition ingest: global phase 1, banked phase 2.

    The single-sketch two-phase pipeline (blocks._phase1) run once on
    global arrays with row-aware grouping, so the B-wide sorts and the
    monitored matching are paid once — not once per row:

      1. one shared sort; one global aggregation to (uids, net)
         (``_partition_aggregate``);
      2. monitored matching for ALL rows with one searchsorted of the
         stacked (S, k) ids into the global uniques (same total work as
         the single sketch: an id matches only in its owner row)
         (``_match_search``);
      3. ONE packed-key partition groups residual inserts into every
         row's [units | non-units | consumed-by-fill] layout at once
         (the consumed prefix is known up front from in-row ranks);
      4. per-row slices of that one global array feed batched
         fill_empty_slots / waterfill_unit_inserts and the flat banked
         residual loop, whose trip count is max_s(non-unit_s) ≈ U/S
         instead of U (steps 3-4: ``_partition_apply``).

    Per-row results are bit-identical to blocks.block_update on the
    row's own substream (each step sees exactly the row's aggregated
    multiset in the same order) — pinned against
    ``sharded.update_block_serial_reference`` by tests and
    BENCH_sharded.json.
    """
    uids, net, valid = _partition_aggregate(items, weights,
                                            router.universe_bits)
    counts1, monitored = _match_search(bank, uids, net)
    return _partition_apply(
        SketchState(bank.ids, counts1, bank.errors), uids, net, valid,
        router.owner_of(uids), monitored,
        getattr(router, "monotone_owner", False), variant)


def _partition_aggregate(items: jax.Array, weights: jax.Array,
                         universe_bits: Optional[int]):
    """Step 1 of the partition ingest: shared sort + in-place aggregation.

    Same prefix-sum aggregation as blocks._aggregate_block but WITHOUT
    its head-compaction sort: the fused path matches and groups directly
    against the raw sorted block (a segment's head position stands in
    for the compacted unique), so the one grouping sort of
    ``_partition_apply`` does all the compaction this path ever needs.
    Returns ``(uids, net, valid)``: the sorted block, each segment's net
    weight (read at head positions only) and the mask of heads holding a
    real key with a nonzero net.
    """
    items = items.astype(jnp.int32)
    weights = weights.astype(jnp.int32)
    order = sort_block(items, universe_bits)
    uids = items[order]      # sorted; segment heads carry the uniques
    head, net = segment_nets(uids[None, :], weights[order][None, :])
    head, net = head[0], net[0]
    return uids, net, head & (uids >= 0) & (net != 0)


def _match_search(bank: SketchState, uids: jax.Array, net: jax.Array):
    """Step 2 of the partition ingest: the monitored match by search.

    One searchsorted of every slot id of the bank into the sorted block:
    it returns the FIRST occurrence = the segment head; the
    (flat_ids >= 0) guard keeps EMPTY slots from matching -1 padding
    items (the compacted path got this from its sentinel remap). Returns
    the counts after the monitored add and the (B,) ``monitored`` mask
    of matched heads.

    The whole-bank path keeps this search rather than
    ``_match_owner_rows``: most of its banks have few rows (at most one
    chunk, or a hash-sharded single sketch), where the owner-row
    compare, sliced to 3S keys at a time, would take B/3S dependent
    trips against ceil(log2(B+1)) search levels over S·k slots. Sharded
    tenant banks, its one wide case, take no benchmark cell yet.
    """
    S, k = bank.ids.shape
    B = uids.shape[0]
    flat_ids = bank.ids.reshape(-1)
    pos = jnp.clip(jnp.searchsorted(uids, flat_ids), 0, B - 1)
    match = (uids[pos] == flat_ids) & (flat_ids >= 0)
    counts1 = sat_add(bank.counts, jnp.where(match, net[pos], 0).reshape(S, k))
    monitored = (
        jnp.zeros((B,), bool)
        .at[jnp.where(match, pos, B)]
        .set(True, mode="drop")
    )
    return counts1, monitored


def _match_owner_rows(ids: jax.Array, uids: jax.Array, valid: jax.Array,
                      owner: jax.Array):
    """The monitored match by owner row: step 2 of the touched-rows
    ingest, once per block.

    An id is monitored, if at all, in its owner row only, so each head
    ``uids[i]`` is compared with the ``k`` ids of row ``owner[i]`` — a
    row gather of contiguous rows, as ``query_rows`` reads — instead of
    binary-searching every slot of the bank into the block. Heads that
    are not ``valid`` (padding, weight 0, a zero net) never match; the
    (ids >= 0) guard keeps EMPTY and BLOCKED slots out, and a key past
    the bank's last row, clipped onto it, finds no id of its own there.
    The compare reads a (B, k) block of ids; where that would outgrow
    the bank itself (B > 3R), the keys are compared 3R at a time.

    Returns ``(slot, monitored)``: each head's matched slot in its owner
    row (clipped into the row where there is none) and the (B,) mask of
    matched heads. On a bank whose ids sit in their owner rows these are
    the slots ``_match_search`` adds to, and its ``monitored`` mask
    wherever ``valid`` holds (the only heads it is read at).
    """
    R, k = ids.shape
    B = uids.shape[0]
    lane = jnp.arange(k, dtype=jnp.int32)

    def first_slot(x):
        row, key = x
        ids_r = ids[row]
        return jnp.where((ids_r == key) & (ids_r >= 0), lane, k).min()

    slot = jax.lax.map(first_slot, (jnp.clip(owner, 0, R - 1), uids),
                       batch_size=0 if B <= 3 * R else 3 * R)
    return jnp.minimum(slot, k - 1), valid & (slot < k)


def _partition_apply(bank: SketchState, uids: jax.Array, net: jax.Array,
                     valid: jax.Array, owner: jax.Array, monitored: jax.Array,
                     monotone: bool, variant: int) -> SketchState:
    """Steps 3-4 of the partition ingest, after the monitored add.

    ``bank``'s counts already hold the monitored add; ``uids``, ``net``,
    ``valid`` and ``monitored`` are the aggregated block and its match,
    and ``owner`` maps each sorted key onto a row of ``bank``.
    ``monotone``: the owner is non-decreasing in key order.
    """
    S = bank.ids.shape[0]
    B = uids.shape[0]
    if (3 * S + 1) * B >= 2**31:
        # the row-grouping packed key is klass * B + idx with 3S + 1
        # classes — the one partition call whose key range grows with S
        raise ValueError(
            f"fused partition update needs (3*rows+1)*block < 2^31 for the "
            f"packed grouping sort; got rows={S}, block={B}. Use "
            f"path='vmap' (or fewer rows per launch).")

    # -- 3. residual classification + ONE row-major grouping sort ----------
    # blocks._phase1 builds the [units | non-units | consumed] layout per
    # sketch with a second partition AFTER the empty fill; here the
    # consumed prefix ("the leading i0_s inserts the bulk empty fill
    # places") is known up front from each entry's rank within its row
    # — an (S, B) one-hot cumsum — so one packed sort builds all S
    # layouts back to back. Per-row tallies come from the same (S, B)
    # masks (no segment_sum: CPU XLA serializes B-wide scatter-adds).
    owner_c = jnp.clip(owner, 0, S - 1)
    res_ins = valid & ~monitored & (net > 0)
    empties_s = (bank.ids == EMPTY).sum(axis=1)
    if monotone:
        # owner is non-decreasing in sorted-key order (tenant-major
        # composite keys): each row's entries form one contiguous run,
        # so in-row ranks and per-row tallies are prefix-sum
        # differences at the run boundaries — O(B + S) where the dense
        # branch below pays (S, B). At S ~ 1000 rows this is the
        # difference between the fused launch beating per-row sessions
        # and losing to them (BENCH_service.json, fused_vs_sessions).
        rows_s = jnp.arange(S, dtype=jnp.int32)
        start_s = jnp.searchsorted(owner, rows_s, side="left")
        end_s = jnp.searchsorted(owner, rows_s, side="right")

        def seg_sum(vals):
            p = jnp.cumsum(vals.astype(jnp.int32))
            p = jnp.concatenate([jnp.zeros(1, jnp.int32), p])
            return p[end_s] - p[start_s]

        cum_ins = jnp.cumsum(res_ins.astype(jnp.int32))
        ex_ins = cum_ins - res_ins                 # exclusive prefix
        n_ins_s = seg_sum(res_ins)
        rank = ex_ins - ex_ins[start_s[owner_c]]   # valid at res_ins
        i0_s = jnp.minimum(n_ins_s, empties_s)
        consumed = res_ins & (rank < i0_s[owner_c])
        unit = res_ins & ~consumed & (net == 1)
        nonunit = res_ins & ~consumed & (net != 1)
        if variant == VARIANT_LAZY:
            w_del_s = jnp.zeros((S,), jnp.int32)
        else:
            res_del = valid & ~monitored & (net < 0)
            w_del_s = seg_sum(jnp.where(res_del, -net, 0))
        mu_s = seg_sum(unit)
        nnu_s = seg_sum(nonunit)
    else:
        shard_rows = jnp.arange(S, dtype=jnp.int32)[:, None]
        owner_mat = owner[None, :] == shard_rows                  # (S, B)
        ins_mat = owner_mat & res_ins[None, :]
        rank_mat = jnp.cumsum(ins_mat, axis=1)                    # inclusive
        n_ins_s = rank_mat[:, -1]
        rank = jnp.take_along_axis(rank_mat, owner_c[None, :], axis=0)[0] - 1
        i0_s = jnp.minimum(n_ins_s, empties_s)
        consumed = res_ins & (rank < i0_s[owner_c])
        unit = res_ins & ~consumed & (net == 1)
        nonunit = res_ins & ~consumed & (net != 1)
        if variant == VARIANT_LAZY:
            w_del_s = jnp.zeros((S,), jnp.int32)
        else:
            res_del = valid & ~monitored & (net < 0)
            w_del_s = jnp.where(owner_mat & res_del[None, :],
                                -net[None, :], 0).sum(axis=1)
        mu_s = (owner_mat & unit[None, :]).sum(axis=1)
        nnu_s = (owner_mat & nonunit[None, :]).sum(axis=1)
    klass = jnp.where(
        res_ins,
        owner_c * 3 + jnp.where(unit, 0, jnp.where(nonunit, 1, 2)),
        3 * S,
    )
    perm = _stable_partition_perm(klass)
    h_uids = uids[perm]
    h_net = net[perm]
    cc = jnp.stack([mu_s, nnu_s, i0_s], axis=1).reshape(-1)       # (3S,)
    class_off = jnp.cumsum(cc) - cc
    uoff_s = class_off[0::3]   # start of row s's [units | non-units] run
    coff_s = class_off[2::3]   # start of row s's consumed (fill) run

    # -- 4. batched O(k) phases + flat banked residual loop ----------------
    # All three consumers read the ONE global grouped layout at
    # per-row offsets — no per-row (S, B) slices materialize.
    ids1, cnt1, err1, _ = jax.vmap(
        fill_empty_slots, in_axes=(0, 0, 0, None, None, 0, 0))(
        bank.ids, bank.counts, bank.errors, h_uids, h_net, i0_s, coff_s)
    ids1, cnt1, err1 = jax.vmap(
        waterfill_unit_inserts, in_axes=(0, 0, 0, None, 0, 0))(
        ids1, cnt1, err1, h_uids, mu_s, uoff_s)
    ids1, cnt1, err1 = residual_phase_banked(
        ids1, cnt1, err1, h_uids, h_net, uoff_s, mu_s, mu_s + nnu_s,
        w_del_s, variant)
    return SketchState(ids1, cnt1, err1)


def touched_chunk_rows(block: int, k: int) -> int:
    """Rows per chunk of the touched-rows ingest (``_fused_touched``).

    The smallest power of two, and at least one 8-row sublane tile, whose
    rows of ``k`` counters hold ``block * log2(block)`` slots: one pass
    over a chunk's counters (the fill, water-fill or a residual-loop
    trip) then costs about what the chunk's grouping sort of the block
    does, so neither the per-chunk sort nor the rows' lockstep work
    dominates. The monitored match does not enter it: it runs once per
    block, before the chunks.
    """
    target = block * max(1, block.bit_length() - 1)
    rows = -(-target // k)
    return max(8, 1 << (rows - 1).bit_length())


def takes_touched(router: Router, block: int, k: int) -> bool:
    """Whether ``update_block_fused`` ingests a ``block``-item block into a
    bank of rows ``k`` counters wide through ``_fused_touched``."""
    return (router.kind == "partition"
            and getattr(router, "monotone_owner", False)
            and router.num_rows > touched_chunk_rows(block, k))


def ingest_chunks(router: Router, block: int, k: int, rows_reached):
    """Trips of the ingest's chunk loop, per block, on the host.

    ``rows_reached`` (an int or an array, one per block) counts the rows
    a block gives a nonzero weight. On the touched-rows path the loop
    takes ``ceil(rows_reached / touched_chunk_rows(block, k))`` trips;
    every other path is one pass over the whole bank.
    """
    rows_reached = np.asarray(rows_reached)
    if not takes_touched(router, block, k):
        return np.ones_like(rows_reached)
    return -(-rows_reached // touched_chunk_rows(block, k))


def _fused_touched(bank: SketchState, items: jax.Array, weights: jax.Array,
                   router: TenantRouter, variant: int) -> SketchState:
    """The partition ingest over the rows the block touches, a chunk at a
    time.

    Every phase of the whole-bank ingest costs O(R·k) per block — the
    monitored match, the fill and water-fill and each residual-loop trip
    pass over the whole bank — though a block of B items reaches at most
    B rows. Here phase 1 runs ONCE per block: the shared sort and
    aggregation (``_partition_aggregate``) and the monitored match by
    owner row (``_match_owner_rows``). The rows holding a nonzero weight
    are then listed in ascending order and ingested
    ``touched_chunk_rows(B, k)`` at a time: each chunk is gathered into a
    bank of its own, takes its matched heads' monitored add, runs the
    rest of the ingest (``_partition_apply``) on the aggregated block
    with every other row's keys masked out, and is scattered back. Rows
    are independent, each row's monitored add precedes its fill,
    water-fill and residual loop, and a row with no nonzero weight is
    left unchanged by an ingest, so the result is bit-identical to the
    whole-bank ingest, whatever number of rows the block reaches.

    Needs a monotone owner: the owner's rank among a chunk's rows is then
    non-decreasing in key order too, as ``_partition_apply``'s run
    boundaries require. Keys of other rows are masked out and never
    count, whatever rank they get; padding slots of the last chunk
    (index R) read a clipped row and are dropped on the way back.
    """
    R, k = bank.ids.shape
    B = items.shape[0]
    cap = touched_chunk_rows(B, k)
    items = items.astype(jnp.int32)
    weights = weights.astype(jnp.int32)
    owner = router.owner_of(items)
    hit = jnp.zeros((R,), bool).at[
        jnp.where((weights != 0) & (owner >= 0), owner, R)].set(
            True, mode="drop")
    size = -(-min(R, B) // cap) * cap
    rows = jnp.nonzero(hit, size=size, fill_value=R)[0].astype(jnp.int32)
    n_chunks = (hit.sum(dtype=jnp.int32) + cap - 1) // cap

    uids, net, valid = _partition_aggregate(items, weights,
                                            router.universe_bits)
    owner = router.owner_of(uids)
    slot, monitored = _match_owner_rows(bank.ids, uids, valid, owner)

    def chunk(c, bank):
        sel = jax.lax.dynamic_slice(rows, (c * cap,), (cap,))
        rank = jnp.searchsorted(sel, owner).astype(jnp.int32)
        rank_c = jnp.clip(rank, 0, cap - 1)
        mine = sel[rank_c] == owner
        sub = jax.tree.map(lambda x: jnp.take(x, sel, axis=0, mode="clip"),
                           bank)
        # the monitored add of the chunk's matched heads: one (B,) scatter
        # into the chunk's rows (a scatter into the whole (R, k) bank
        # makes the TPU relayout every count); a row's ids are unique, so
        # no slot is written twice
        counts1 = sub.counts.at[
            jnp.where(monitored & mine, rank_c, cap), slot].set(
                sat_add(sub.counts[rank_c, slot], net), mode="drop")
        out = _partition_apply(
            SketchState(sub.ids, counts1, sub.errors), uids,
            jnp.where(mine, net, 0), valid & mine, rank, monitored, True,
            variant)
        return jax.tree.map(
            lambda x, y: x.at[sel].set(y, mode="drop"), bank, out)

    return jax.lax.fori_loop(0, n_chunks, chunk, bank)


@functools.partial(jax.jit, static_argnames=("router", "variant"))
def update_block_fused(bank: SketchState, items: jax.Array,
                       weights: jax.Array, router: Router,
                       variant: int = 2) -> SketchState:
    """Ingest one (B,) block into the whole bank with a single launch.

    Dispatches on the router kind at trace time (routers are static):
    partition routers take the global-phase-1 fast path, broadcast
    routers the dense batched path. Either way the result is
    bit-identical to updating each row with ``blocks.block_update`` on
    the row's own routed view.
    """
    if takes_touched(router, items.shape[0], bank.ids.shape[1]):
        return _fused_touched(bank, items, weights, router, variant)
    if router.kind == "partition":
        return _fused_partition(bank, items, weights, router, variant)
    row_items, row_weights = router.route_dense(items, weights)
    return _fused_dense(bank, row_items, row_weights, variant)


@functools.partial(jax.jit, static_argnames=("variant", "universe_bits"))
def update_single(state: SketchState, items: jax.Array, weights: jax.Array,
                  variant: int = 2,
                  universe_bits: Optional[int] = None) -> SketchState:
    """Fused ingest of a flat (k,) sketch as a one-row bank.

    The engine backend for single-sketch clients (the stats facade):
    identical semantics to ``blocks.block_update`` — a one-shard
    partition is the whole block — through the same fused core every
    multi-row client runs, so there is ONE hot path to optimize.
    Bit-identity to ``block_update`` is pinned in tests/test_bank.py.
    """
    bank = jax.tree.map(lambda x: x[None], state)
    out = _fused_partition(bank, items, weights,
                           HashShardRouter(1, universe_bits), variant)
    return jax.tree.map(lambda x: x[0], out)


# ---------------------------------------------------------------------------
# Banked queries / merge / consolidate
# ---------------------------------------------------------------------------

@jax.jit
def query_rows(bank: SketchState, rows: jax.Array,
               items: jax.Array) -> jax.Array:
    """Estimated count of ``items[i]`` read from its owner row ``rows[i]``.

    The owner-row read every client's query path reduces to: an id is
    monitored (if at all) in exactly one row of a partition, so the
    global answer is the owner row's answer — no cross-row combination
    and therefore no merge cross-term error.
    """
    ids_r = bank.ids[rows]                       # (n, k) row gather
    cnt_r = bank.counts[rows]
    # sentinel slots (EMPTY/BLOCKED/POISON) are masked out so querying a
    # negative id returns 0 instead of the padding slots' garbage counts
    eq = (ids_r == items.astype(jnp.int32)[:, None]) & (ids_r >= 0)
    return jnp.where(eq, cnt_r, 0).sum(axis=1) * eq.any(axis=1)


def topk_bank(bank: SketchState, m: int) -> Tuple[jax.Array, jax.Array]:
    """Global top-m (ids, counts): flat top-k over all R·k slots.

    Exact given the per-row states under a partition router (every
    candidate heavy hitter is monitored by its owner row with its full
    estimated count). Sentinel slots (EMPTY/BLOCKED) never surface.
    """
    ids = bank.ids.reshape(-1)
    counts = jnp.where(ids < 0, jnp.int32(-2**31), bank.counts.reshape(-1))
    vals, idx = jax.lax.top_k(counts, m)
    return ids[idx], vals


@functools.partial(jax.jit, static_argnames=("m",))
def topk_rows(bank: SketchState, rows: jax.Array,
              m: int) -> Tuple[jax.Array, jax.Array]:
    """Top-m (ids, counts) over a row subset; ``m <= len(rows) * k``.

    ``topk_bank`` restricted to ``rows`` (a traced index array, so one
    compiled gather serves every tenant). When the subset is
    ownership-closed under a partition router — a tenant's rows — the
    answer is exact for that subset and blind to every other row: the
    never-cross-tenants top-k read.
    """
    ids = bank.ids[rows].reshape(-1)
    counts = jnp.where(ids < 0, jnp.int32(-2**31),
                       bank.counts[rows].reshape(-1))
    vals, idx = jax.lax.top_k(counts, m)
    return ids[idx], vals


@jax.jit
def merge_banks(a: SketchState, b: SketchState) -> SketchState:
    """Row-wise mergeable-summaries merge of two same-shape banks.

    Valid because both banks route with the same router: row r of either
    bank only ever monitored ids routed to r, so the pairing is exact
    and the merged bank keeps the row-ownership invariant.
    """
    return jax.vmap(st.merge)(a, b)


def consolidate(bank: SketchState, merge_fn=st.merge) -> SketchState:
    """Fold the leading row axis into ONE summary (checkpoint compaction).

    A tree of ``merge_fn`` (default ``state.merge``, which is
    BLOCKED-aware) reduces (R, k) -> (k,): the compact global view for
    checkpoints/telemetry, carrying the standard merged-summary error
    bounds (unlike owner-row queries on the live bank, which are
    merge-error-free). Not an inverse of routing — R·k counters collapse
    to k. Callers with extra trailing axes pass a lifted merge
    (dyadic_sharded folds (S, bits, k) -> (bits, k) with
    ``jax.vmap(state.merge)``).
    """
    rows = [jax.tree.map(lambda x: x[r], bank)
            for r in range(bank.ids.shape[0])]
    while len(rows) > 1:
        nxt = [merge_fn(rows[i], rows[i + 1])
               for i in range(0, len(rows) - 1, 2)]
        if len(rows) % 2:
            nxt.append(rows[-1])
        rows = nxt
    return rows[0]


# ---------------------------------------------------------------------------
# Second-bank coupling: the Double SpaceSaving± hooks
# ---------------------------------------------------------------------------

def split_signed(weights: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Split one signed block into the family's two insert-only streams.

    Double SpaceSaving± (family paper, PAPERS.md) feeds insertions into
    one summary and deletions into a second one *as insertions*; the
    estimator subtracts. Zero weights stay zero on both sides, so block
    padding remains padding for both banks.
    """
    w = weights.astype(jnp.int32)
    return jnp.maximum(w, 0), jnp.maximum(-w, 0)


@functools.partial(jax.jit, static_argnames=("router", "variant"))
def update_pair(ins_bank: SketchState, del_bank: SketchState,
                items: jax.Array, weights: jax.Array, router: Router,
                variant: int = 2) -> Tuple[SketchState, SketchState]:
    """Coupled two-bank ingest: ONE launch updating both family banks.

    The engine hook the Double SpaceSaving± backend builds on
    (``repro.sketch.family``): both banks share the router (and hence the
    row-ownership invariant), each sees an insert-only stream, so the
    fused cores run in their monitored-heavy sweet spot and the lazy/SS±
    distinction vanishes (no unmonitored deletions ever reach either
    bank). Banks may have different per-row capacities (the family's
    k_I/k_D split).
    """
    w_ins, w_del = split_signed(weights)
    return (
        update_block_fused(ins_bank, items, w_ins, router, variant),
        update_block_fused(del_bank, items, w_del, router, variant),
    )


__all__ = [
    "init",
    "row_capacities",
    "shard_of",
    "sort_block",
    "HashShardRouter",
    "TenantRouter",
    "DyadicLevelRouter",
    "ShardLevelRouter",
    "Router",
    "residual_phase_banked",
    "phase1_dense",
    "phase1_dense_prep",
    "update_rows",
    "update_block_fused",
    "update_single",
    "ingest_chunks",
    "query_rows",
    "topk_bank",
    "topk_rows",
    "merge_banks",
    "consolidate",
    "split_signed",
    "update_pair",
]
