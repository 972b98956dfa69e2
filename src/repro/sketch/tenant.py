"""Multi-tenant sketch layout: thousands of streams in ONE fused bank.

The north-star service ingests per-user streams — the naive spelling is
one ``StreamSession`` per tenant, which pays one dispatch (and one
compiled-cache entry) per tenant per block.  The bank engine makes
tenancy a *routing* problem instead: one ``(T*S, k)`` bank, rows
tenant-major, a :class:`repro.sketch.bank.TenantRouter` mapping
composite keys ``(tenant << item_bits) | item`` onto the owning
tenant's rows, and the whole fleet ingests with a single
``update_block_fused`` launch per coalesced block.  Because composite
keys never collide across tenants and the fused partition path is
bit-identical to per-row ``block_update`` on each row's routed view
(tests/test_bank.py), every tenant's rows evolve exactly as an
independently built per-tenant sketch fed the same fragments — the
isolation bill tests/test_tenant.py pins across variants and delete
ratios.

Layout contract:

  * tenant t owns rows ``[t*S, (t+1)*S)`` (S = per-tenant hash shards,
    usually 1); its capacity budget ``cap_t`` splits ``ceil(cap_t/S)``
    per row via the engine's BLOCKED capacity masks — per-tenant
    capacity is a mask pattern, not a new state type;
  * queries gather the owner row only (``bank.query_rows``), per-tenant
    top-k reads the tenant's row slice only (``bank.topk_rows``) —
    neither can cross a tenant boundary by construction;
  * global ``topk`` speaks COMPOSITE keys (unpack with
    :func:`unpack_keys`): items of different tenants are different keys;
  * cold tenants spill to a tagged flat dict (:func:`spill_rows`) and
    re-admit exactly via :func:`admit_rows` — ``state.merge`` against
    the cleared (empty) rows reproduces the spilled content, and the
    row's BLOCKED capacity mask is re-imposed afterwards (merge relaxes
    rows to full width);
  * per-tenant quantiles have a layout of their own
    (``SketchSpec(kind='quantile', tenants=T)``, :class:`TenantLevels`):
    tenant t owns one row per dyadic layer, rows ``t*bits + l``, fed
    through ``bank.TenantLevelRouter``, so each tenant's rank error is
    bounded by its own mass |F_t|₁ (:func:`tenant_ranks`,
    :func:`tenant_quantiles`, every tenant's queries in one search);
  * quantile tenancy also composes through ONE dyadic bank over
    composite keys: per-tenant rank is a range difference inside the
    tenant's key range (:func:`tenant_rank_many`), per-tenant quantiles
    a lockstep search over the item part only
    (:func:`tenant_quantile_many`), with an error bound of the whole
    bank's mass.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from . import bank as bk
from . import dyadic as dy
from . import state as st
from .blocks import block_update
from .state import BLOCKED, EMPTY, SketchState, _INT_MAX

# mirror api.LAYOUT_FREQUENCY / LAYOUT_QUANTILE (api imports this module
# post-registry; importing api here would be cyclic)
_LAYOUT_FREQUENCY = 1
_LAYOUT_QUANTILE = 2


# ---------------------------------------------------------------------------
# Composite routing keys
# ---------------------------------------------------------------------------

def tenant_bits_for(num_tenants: int) -> int:
    """High bits a composite key spends on the tenant id."""
    return (int(num_tenants) - 1).bit_length()


def pack_keys(tenants, items, item_bits: int):
    """Composite routing keys ``(tenant << item_bits) | item``.

    numpy inputs return int64 (so a malformed tenant/item pair overflows
    visibly and ``api.validate_block``'s int32 range check catches it);
    jax inputs stay int32 for in-trace use — the spec validation already
    guarantees ``tenant_bits + item_bits <= 31``.
    """
    if isinstance(tenants, jax.Array) or isinstance(items, jax.Array):
        t = jnp.asarray(tenants, jnp.int32)
        x = jnp.asarray(items, jnp.int32)
        return (t << item_bits) | x
    t = np.asarray(tenants, np.int64)
    x = np.asarray(items, np.int64)
    return (t << item_bits) | x


def unpack_keys(keys, item_bits: int):
    """Inverse of :func:`pack_keys`: ``(tenants, items)``."""
    mask = (1 << item_bits) - 1
    return keys >> item_bits, keys & mask


# ---------------------------------------------------------------------------
# The multi-tenant bank
# ---------------------------------------------------------------------------

class TenantBank(NamedTuple):
    """One ``(T*S, k)`` engine bank holding every tenant's counters.

    A thin wrapper (not a new state type): all engine invariants — the
    BLOCKED capacity masks, row-ownership, fused-update bit-identity —
    are the bank's own. ``num_shards``/``item_bits`` live in the spec /
    router, not here, so the pytree stays a pure array container.
    """

    bank: SketchState

    @property
    def num_rows(self) -> int:
        return self.bank.ids.shape[0]


def init_tenants(caps: Union[int, Sequence[int]],
                 num_tenants: Optional[int] = None,
                 num_shards: int = 1) -> TenantBank:
    """Empty multi-tenant bank; tenant t owns rows ``[t*S, (t+1)*S)``.

    ``caps``: per-tenant capacity (one int applied to ``num_tenants``
    tenants, or a per-tenant list). Each tenant's budget splits
    ``ceil(cap_t / S)`` per shard row — the same even split an
    independently built ``SketchSpec(shards=S)`` sketch of ``cap_t``
    counters applies, preserving per-tenant bit-identity.
    """
    if isinstance(caps, (int, np.integer)):
        assert num_tenants is not None and num_tenants >= 1
        caps = [int(caps)] * num_tenants
    else:
        caps = [int(c) for c in caps]
        assert num_tenants is None or num_tenants == len(caps)
    row_caps = [-(-c // num_shards) for c in caps for _ in range(num_shards)]
    return TenantBank(bank=bk.init(row_caps))


def router_for(num_tenants: int, item_bits: int,
               num_shards: int = 1) -> bk.TenantRouter:
    """The routing companion of :func:`init_tenants`."""
    return bk.TenantRouter(num_tenants, item_bits, num_shards)


def update_block(tb: TenantBank, keys, weights,
                 router: bk.TenantRouter, variant: int = 2) -> TenantBank:
    """One fused launch ingesting a composite-key block for ALL tenants."""
    return TenantBank(
        bank=bk.update_block_fused(tb.bank, keys, weights, router, variant))


@functools.partial(jax.jit, static_argnames=("router",))
def query_many_tenant(tb: TenantBank, keys: jax.Array,
                      router: bk.TenantRouter) -> jax.Array:
    """Estimated count per composite key, read from its owner row only."""
    keys = keys.astype(jnp.int32)
    return bk.query_rows(tb.bank, router.owner_of(keys), keys)


@functools.partial(jax.jit, static_argnames=("m", "num_shards", "item_bits"))
def topk_tenant(tb: TenantBank, tenant, m: int, *, num_shards: int,
                item_bits: int):
    """One tenant's top-m (raw items, counts); never crosses tenants.

    ``tenant`` may be a traced scalar — the row slice is a dynamic
    slice, so one compiled function serves every tenant.
    """
    start = jnp.asarray(tenant, jnp.int32) * num_shards
    sl = lambda x: jax.lax.dynamic_slice_in_dim(x, start, num_shards, 0)
    sub = SketchState(sl(tb.bank.ids), sl(tb.bank.counts), sl(tb.bank.errors))
    keys, vals = bk.topk_bank(sub, m)
    items = jnp.where(keys >= 0, keys & ((1 << item_bits) - 1), keys)
    return items, vals


@functools.partial(jax.jit, static_argnames=("m", "num_shards", "item_bits"))
def topk_tenants(tb: TenantBank, tenants: jax.Array, m: int, *,
                 num_shards: int, item_bits: int):
    """Batched per-tenant top-m: ONE row gather answers every
    subscription of a service tick.

    Returns ``(items, counts)`` of shape (n, m), row i = tenant
    ``tenants[i]``'s top-m raw items by estimated count.
    """
    tenants = tenants.astype(jnp.int32)
    rows = tenants[:, None] * num_shards + jnp.arange(
        num_shards, dtype=jnp.int32)[None, :]
    n = tenants.shape[0]
    ids = tb.bank.ids[rows].reshape(n, -1)        # (n, S*k)
    cnt = tb.bank.counts[rows].reshape(n, -1)
    score = jnp.where(ids < 0, jnp.int32(-2**31), cnt)
    vals, idx = jax.lax.top_k(score, m)
    keys = jnp.take_along_axis(ids, idx, axis=1)
    items = jnp.where(keys >= 0, keys & ((1 << item_bits) - 1), keys)
    return items, vals


# ---------------------------------------------------------------------------
# Cold-row spill / exact re-admission (the service's eviction path)
# ---------------------------------------------------------------------------

def tenant_rows(tenant: int, num_shards: int) -> np.ndarray:
    """The row indices tenant ``tenant`` owns (host-side helper)."""
    t = int(tenant)
    return np.arange(t * num_shards, (t + 1) * num_shards)


@jax.jit
def extract_rows(bank: SketchState, rows) -> SketchState:
    """Row slice (n, k): the live content of those rows (spill payload)."""
    rows = jnp.asarray(rows, jnp.int32)
    return SketchState(bank.ids[rows], bank.counts[rows], bank.errors[rows])


@jax.jit
def clear_rows(bank: SketchState, rows) -> SketchState:
    """Reset rows to empty, preserving their BLOCKED capacity masks."""
    rows = jnp.asarray(rows, jnp.int32)
    blocked = bank.ids[rows] == BLOCKED
    return SketchState(
        ids=bank.ids.at[rows].set(
            jnp.where(blocked, BLOCKED, EMPTY).astype(jnp.int32)),
        counts=bank.counts.at[rows].set(
            jnp.where(blocked, _INT_MAX, 0).astype(jnp.int32)),
        errors=bank.errors.at[rows].set(jnp.zeros_like(bank.errors[rows])),
    )


@jax.jit
def admit_rows(bank: SketchState, rows, spilled: SketchState) -> SketchState:
    """Merge a spilled row bundle back into its rows; re-impose the rows'
    capacity masks.

    ``state.merge`` per row pairs exactly (both sides only ever held
    keys routed to that row).  Against *cleared* rows — the service
    re-admits BEFORE any new traffic reaches the tenant — the merge is
    content-exact: an empty side contributes no cross-term, and the
    merged row packs the spilled items (<= cap of them) at the front, so
    re-imposing the BLOCKED tail drops nothing and every query/top-k
    answer is preserved bit-for-bit (tests/test_tenant.py).  Against
    non-empty rows it is a standard capacity-``cap`` mergeable-summaries
    merge (top-cap survivors).
    """
    rows = jnp.asarray(rows, jnp.int32)
    live = SketchState(bank.ids[rows], bank.counts[rows], bank.errors[rows])
    over = live.ids == BLOCKED
    merged = jax.vmap(st.merge)(live, spilled)
    return SketchState(
        ids=bank.ids.at[rows].set(
            jnp.where(over, BLOCKED, merged.ids).astype(jnp.int32)),
        counts=bank.counts.at[rows].set(
            jnp.where(over, _INT_MAX, merged.counts).astype(jnp.int32)),
        errors=bank.errors.at[rows].set(
            jnp.where(over, 0, merged.errors).astype(jnp.int32)),
    )


def _spill_dict(tenant: int, num_shards: int, item_bits: int,
                ids, counts, errors) -> Dict[str, Any]:
    return {
        "layout": np.int32(_LAYOUT_FREQUENCY),
        "tenant": np.int32(tenant),
        "shards": np.int32(num_shards),
        "item_bits": np.int32(item_bits),
        "ids": ids, "counts": counts, "errors": errors,
    }


def spill_rows(bank: SketchState, tenant: int, num_shards: int,
               item_bits: int,
               wait=contextlib.nullcontext) -> Dict[str, Any]:
    """Tagged flat numpy dict (npz-safe) of one tenant's rows.

    The cold-row spill format (DESIGN.md §15): the standard frequency
    triple restricted to the tenant's (S, k) row slice, plus enough
    metadata (``tenant``, ``shards``, ``item_bits``) to re-admit it into
    the right rows of a compatible bank. ``wait`` (a context-manager
    factory) is entered around each of the three device reads.
    """
    sp = extract_rows(bank, tenant_rows(tenant, num_shards))
    out = {}
    for name in ("ids", "counts", "errors"):
        with wait():
            out[name] = np.asarray(getattr(sp, name))
    return _spill_dict(tenant, num_shards, item_bits, **out)


def _spilled_rows(d: Dict[str, Any]):
    """The rows and (S, k) triple of a :func:`spill_rows` dict."""
    for key in ("tenant", "shards", "ids", "counts", "errors"):
        if key not in d:
            raise ValueError(
                f"spill dict is missing key {key!r} (truncated write?); a "
                f"tenant spill carries tenant/shards/item_bits + the "
                f"ids/counts/errors triple")
    num_shards = int(np.asarray(d["shards"]))
    rows = tenant_rows(int(np.asarray(d["tenant"])), num_shards)
    return rows, [np.asarray(d[name], np.int32)
                  for name in ("ids", "counts", "errors")]


def admit_spill(bank: SketchState, d: Dict[str, Any]) -> SketchState:
    """Re-admit a :func:`spill_rows` dict into its tenant's rows."""
    rows, triple = _spilled_rows(d)
    return admit_rows(bank, rows,
                      SketchState(*(jnp.asarray(x) for x in triple)))


# Tenants a launch of the batched spill and re-admission: one compiled
# shape whatever a tick's count, the last launch padded with row index
# R, which the scatters of clear_rows/admit_rows drop.
SPILL_BATCH = 32


@functools.lru_cache(maxsize=None)
def _batch_moves(donate: bool):
    """(evict, admit) over a batch of rows; ``donate``: the bank is
    donated, so its rows clear or re-admit in place."""
    def evict(bank, rows):
        return clear_rows(bank, rows), extract_rows(bank, rows)

    argnums = (0,) if donate else ()
    return (jax.jit(evict, donate_argnums=argnums),
            jax.jit(admit_rows, donate_argnums=argnums))


def _padded_rows(rows: Sequence[np.ndarray], num_shards: int, R: int):
    rows = np.concatenate(rows)
    per = SPILL_BATCH * num_shards
    return np.pad(rows, (0, -len(rows) % per), constant_values=R
                  ).astype(np.int32), per


def spill_tenants(bank: SketchState, tenants: Sequence[int],
                  num_shards: int, item_bits: int,
                  wait=contextlib.nullcontext, donate: bool = False):
    """Spill several tenants at once: ``(bank, dicts)``.

    ``dicts[i]`` is :func:`spill_rows` of ``tenants[i]`` and ``bank`` is
    :func:`clear_rows` of all their rows, in one launch per
    ``SPILL_BATCH`` tenants and ONE device read of every launch's rows
    (``wait`` is entered around it). ``donate``: donate ``bank`` where
    the platform reuses donated buffers
    (``repro.platform.donate_state_buffers``), so the rows clear in
    place instead of the bank being copied.
    """
    if not len(tenants):
        return bank, []
    from repro.platform import donate_state_buffers

    evict, _ = _batch_moves(bool(donate) and donate_state_buffers())
    rows, per = _padded_rows([tenant_rows(t, num_shards) for t in tenants],
                             num_shards, bank.ids.shape[0])
    payload = []
    for s in range(0, len(rows), per):
        bank, sp = evict(bank, rows[s:s + per])
        payload.append(sp)
    with wait():
        payload = jax.device_get(payload)
    ids, counts, errors = (np.concatenate([p[f] for p in payload])
                           for f in range(3))
    return bank, [
        _spill_dict(t, num_shards, item_bits,
                    *(x[i * num_shards:(i + 1) * num_shards].copy()
                      for x in (ids, counts, errors)))
        for i, t in enumerate(tenants)]


def admit_tenants(bank: SketchState, dicts: Sequence[Dict[str, Any]],
                  donate: bool = False) -> SketchState:
    """:func:`admit_spill` of several spill dicts of distinct tenants,
    in one launch per ``SPILL_BATCH`` tenants; ``donate`` as in
    :func:`spill_tenants`."""
    if not len(dicts):
        return bank
    from repro.platform import donate_state_buffers

    _, admit = _batch_moves(bool(donate) and donate_state_buffers())
    rows, triples = zip(*(_spilled_rows(d) for d in dicts))
    num_shards = len(rows[0])
    rows, per = _padded_rows(rows, num_shards, bank.ids.shape[0])
    pad = len(rows) - num_shards * len(dicts)
    ids, counts, errors = (
        np.pad(np.concatenate([t[f] for t in triples]), ((0, pad), (0, 0)),
               constant_values=c)
        for f, c in ((0, int(EMPTY)), (1, 0), (2, 0)))
    for s in range(0, len(rows), per):
        bank = admit(bank, rows[s:s + per],
                     SketchState(ids[s:s + per], counts[s:s + per],
                                  errors[s:s + per]))
    return bank


# ---------------------------------------------------------------------------
# Per-tenant quantiles over a composite-key dyadic bank
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("item_bits",))
def tenant_rank_many(state: dy.DyadicState, tenant, xs: jax.Array,
                     item_bits: int) -> jax.Array:
    """Per-tenant rank(x) = |{v <= x, v in tenant}| as a range difference.

    The dyadic bank is built over composite keys, so the tenant's values
    occupy the contiguous key range [base, base + 2^item_bits); rank
    within the tenant is rank(base + x) - rank(base - 1). For tenant 0
    the left edge is rank(-1) = 0 exactly. Error adds the two range
    endpoints' dyadic estimates: <= 2x the single-rank bound.
    """
    base = jnp.asarray(tenant, jnp.int32) << item_bits
    lo = dy.rank_many(state, (base - 1)[None])[0]
    return dy.rank_many(state, base + xs.astype(jnp.int32)) - lo


@functools.partial(jax.jit, static_argnames=("item_bits",))
def tenant_mass(state: dy.DyadicState, tenant, item_bits: int) -> jax.Array:
    """One tenant's live mass |F_t|₁ (range mass of its key range)."""
    base = jnp.asarray(tenant, jnp.int32) << item_bits
    edges = jnp.stack([base - 1, base + (1 << item_bits) - 1])
    r = dy.rank_many(state, edges)
    return r[1] - r[0]


@functools.partial(jax.jit, static_argnames=("item_bits",))
def tenant_quantile_many(state: dy.DyadicState, tenant, qs: jax.Array,
                         item_bits: int) -> jax.Array:
    """Per-tenant quantiles: lockstep search over the ITEM part only.

    Reuses ``dy.lockstep_quantile_search`` with the tenant's offset rank
    function and range mass — the universe searched is [0, 2^item_bits),
    item_bits + 1 rounds, regardless of how many tenants share the bank.
    """
    base = jnp.asarray(tenant, jnp.int32) << item_bits
    edges = jnp.stack([base - 1, base + (1 << item_bits) - 1])
    r = dy.rank_many(state, edges)
    lo, mass = r[0], r[1] - r[0]
    rank_fn = lambda xs: dy.rank_many(state, base + xs) - lo
    return dy.lockstep_quantile_search(
        rank_fn, mass, item_bits, qs.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Per-tenant Dyadic SS±: each tenant's own layers, one bank row each
# ---------------------------------------------------------------------------

class TenantLevels(NamedTuple):
    """Every tenant's dyadic layers in one ``(T*bits, k)`` bank.

    Tenant t's layer l is row ``t*bits + l`` (``bank.TenantLevelRouter``)
    and monitors ``x >> l`` of t's own values, tagged with its row;
    ``mass[t]`` is t's exactly tracked |F_t|₁. Each row evolves as layer
    l of a single-tenant ``dyadic.DyadicState`` fed only t's values.
    """

    bank: SketchState
    mass: jax.Array    # (T,) int32

    @property
    def num_tenants(self) -> int:
        return self.mass.shape[0]

    @property
    def bits(self) -> int:
        return self.bank.ids.shape[0] // self.mass.shape[0]


def init_levels(layer_caps: Sequence[int], num_tenants: int) -> TenantLevels:
    """Empty per-tenant dyadic bank: each tenant's layer l holds
    ``layer_caps[l]`` counters (BLOCKED padding up to the widest)."""
    return TenantLevels(bank=bk.init(list(layer_caps) * num_tenants),
                        mass=jnp.zeros((num_tenants,), jnp.int32))


def update_levels(tl: TenantLevels, keys, weights,
                  variant: int = 2) -> TenantLevels:
    """One fused launch ingesting a composite-key block into every
    tenant's layers: the block expands on the device into one row-tagged
    key per layer (``TenantLevelRouter.expand``) and takes the tenant
    bank's ingest over the ``T*bits`` rows."""
    router = bk.TenantLevelRouter(tl.num_tenants, tl.bits)
    keys = keys.astype(jnp.int32)
    weights = weights.astype(jnp.int32)
    tagged, w = router.expand(keys, weights)
    mass = tl.mass.at[jnp.right_shift(keys, tl.bits)].add(weights,
                                                         mode="drop")
    return TenantLevels(
        bank=bk.update_block_fused(tl.bank, tagged, w, router.rows, variant),
        mass=mass)


def _level_ranks(tl: TenantLevels, tenants: jax.Array,
                 xs: jax.Array) -> jax.Array:
    """rank(xs[i]) in tenant ``tenants[i]``'s own stream, both (n,).

    ``dyadic.rank_many`` on the tenant's rows: layer l adds node
    ``2*(y >> (l+1))`` iff bit l of y = x+1 is set, each layer's
    estimate clamped at 0; y >= 2^bits is the tenant's whole mass.
    """
    bits = tl.bits
    router = bk.TenantLevelRouter(tl.num_tenants, bits)
    y = xs.astype(jnp.int32) + 1
    lvl = jnp.arange(bits, dtype=jnp.int32)[None, :]
    node = (2 * jnp.right_shift(y[:, None], lvl + 1)) & ((1 << bits) - 1)
    take = (jnp.right_shift(y[:, None], lvl) & 1) > 0
    rows = tenants.astype(jnp.int32)[:, None] * bits + lvl     # (n, bits)
    est = bk.query_rows(tl.bank, rows.reshape(-1),
                        router.key(rows, node).reshape(-1))
    r = jnp.where(take, jnp.maximum(est.reshape(rows.shape), 0), 0).sum(1)
    return jnp.where(y >= (1 << bits), tl.mass[tenants], r).astype(jnp.int32)


@jax.jit
def tenant_ranks(tl: TenantLevels, tenants: jax.Array,
                 xs: jax.Array) -> jax.Array:
    """Estimated rank(x) = |{v <= x}| of each tenant's own values:
    ``tenants`` (n,), ``xs`` (n, m) -> (n, m). The error is at most
    eps·|F_t|₁ of the tenant's own mass (paper §4.2)."""
    n, m = xs.shape
    lane_t = jnp.broadcast_to(tenants[:, None], (n, m)).reshape(-1)
    return _level_ranks(tl, lane_t, xs.reshape(-1)).reshape(n, m)


@jax.jit
def tenant_quantiles(tl: TenantLevels, tenants: jax.Array,
                     qs: jax.Array) -> jax.Array:
    """Each tenant's quantiles: ``tenants`` (n,), ``qs`` (n, m) -> (n, m),
    the smallest x with rank_t(x) >= q·|F_t|₁.

    One ``dyadic.lockstep_quantile_search`` over all n·m lanes (bits+1
    rounds; its float32 rank target), so a tenant's answer is the same
    whether it is searched alone or beside others.
    """
    n, m = qs.shape
    lane_t = jnp.broadcast_to(tenants[:, None], (n, m)).reshape(-1)
    out = dy.lockstep_quantile_search(
        lambda xs: _level_ranks(tl, lane_t, xs), tl.mass[lane_t], tl.bits,
        qs.reshape(-1).astype(jnp.float32))
    return out.reshape(n, m)


class TenantLevelsAdapter:
    """``SketchSpec(kind='quantile', tenants=T)``: per-tenant dyadic
    layers (:class:`TenantLevels`). ``update`` reads the tenant count
    from the state, as :class:`TenantAdapter` does. Ranks and quantiles
    are per tenant (:func:`tenant_ranks`, :func:`tenant_quantiles`);
    point queries read the key's tenant's leaf layer."""

    def make(self, spec) -> TenantLevels:
        return init_levels(spec.layer_capacities(), spec.tenants)

    def update(self, spec, state, items, weights):
        return update_levels(state, items, weights, spec.variant_id)

    def query_many(self, spec, state, items):
        keys = items.astype(jnp.int32)
        router = bk.TenantLevelRouter(state.num_tenants, state.bits)
        rows = jnp.right_shift(keys, router.bits) * router.bits
        return bk.query_rows(state.bank, rows, router.key(
            rows, keys & ((1 << router.bits) - 1)))

    def topk(self, spec, state, m):
        raise ValueError(
            "the per-tenant quantile layout answers ranks and quantiles "
            "per tenant (tenant_ranks / tenant_quantiles), not top-k")

    def rank_many(self, spec, state, xs):
        raise ValueError(
            "ranks of the per-tenant quantile layout are per tenant: "
            "repro.sketch.tenant.tenant_ranks / tenant_quantiles")

    quantile_many = rank_many

    def merge(self, spec, a, b):
        return TenantLevels(bank=bk.merge_banks(a.bank, b.bank),
                            mass=a.mass + b.mass)

    def consolidate(self, spec, state):
        return state

    def save(self, spec, state) -> Dict[str, Any]:
        return {
            "layout": np.int32(_LAYOUT_QUANTILE),
            "ids": np.asarray(state.bank.ids),
            "counts": np.asarray(state.bank.counts),
            "errors": np.asarray(state.bank.errors),
            "mass": np.asarray(state.mass),
            "tenants": np.int32(state.num_tenants),
            "item_bits": np.int32(spec.bits),
        }

    def restore(self, spec, d) -> TenantLevels:
        fields = SketchState(*(jnp.asarray(np.asarray(d[f]), jnp.int32)
                               for f in ("ids", "counts", "errors")))
        if fields.ids.shape[0] != spec.tenants * spec.bits:
            raise ValueError(
                f"checkpoint has {fields.ids.shape[0]} rows but the spec's "
                f"layout (tenants={spec.tenants} x bits={spec.bits}) needs "
                f"{spec.tenants * spec.bits}")
        return TenantLevels(bank=fields, mass=jnp.asarray(
            np.asarray(d["mass"]).reshape(-1), jnp.int32))


# ---------------------------------------------------------------------------
# Serial oracle: each row updated independently on its routed view
# ---------------------------------------------------------------------------

def reference_row_update(row_state: SketchState, keys, weights,
                         router: bk.TenantRouter, row: int,
                         variant: int = 2) -> SketchState:
    """One row's independent oracle step: ``blocks.block_update`` on the
    row's own routed view of a raw composite-key block.

    The per-row ground truth the fused launch must match bit-for-bit
    (the ``sharded.update_block_serial_reference`` idiom, usable on a
    row subset so the service bench can sample its parity bill instead
    of replaying all T*S rows).
    """
    keys = jnp.asarray(keys, jnp.int32)
    weights = jnp.asarray(weights, jnp.int32)
    order = bk.sort_block(keys, router.universe_bits)
    s_keys = keys[order]
    w_row = jnp.where(router.owner_of(s_keys) == row, weights[order], 0)
    return block_update(row_state, s_keys, w_row, variant,
                        assume_sorted=True)


def update_serial_reference(tb: TenantBank, keys, weights,
                            router: bk.TenantRouter,
                            variant: int = 2) -> TenantBank:
    """Reference: route, then update every row SERIALLY (python loop)."""
    outs = [
        reference_row_update(
            jax.tree.map(lambda x: x[r], tb.bank), keys, weights, router, r,
            variant)
        for r in range(router.num_rows)
    ]
    return TenantBank(bank=jax.tree.map(lambda *xs: jnp.stack(xs), *outs))


# ---------------------------------------------------------------------------
# The SketchSpec(tenants=...) adapter
# ---------------------------------------------------------------------------

class TenantAdapter:
    """``SketchSpec(tenants=T)`` frequency layout: one (T*S, k) bank.

    Registered under the registry's ``tenants`` axis for both sharded
    and unsharded specs (``shards`` means per-tenant hash shards here).
    ``update`` derives the tenant count from the STATE shape, never from
    ``spec.tenants`` — the session's compiled-ingest cache normalizes
    tenant specs sharing a layout onto one entry
    (``session.ingest_cache_spec``), so one trace must serve any fleet
    size (jit retraces per state shape, which is exactly the layout).
    """

    def _shards(self, spec) -> int:
        return spec.shards or 1

    def _tenants_of(self, spec, state) -> int:
        return state.bank.ids.shape[0] // self._shards(spec)

    def _router(self, spec, state) -> bk.TenantRouter:
        return bk.TenantRouter(self._tenants_of(spec, state), spec.bits,
                               self._shards(spec))

    def make(self, spec) -> TenantBank:
        caps = spec.tenant_caps
        if caps is None:
            # even split of the total budget, ceil so every tenant gets
            # at least one counter
            caps = [-(-spec.capacity // spec.tenants)] * spec.tenants
        return init_tenants(list(caps), num_shards=self._shards(spec))

    def update(self, spec, state, items, weights):
        return update_block(state, items, weights,
                            self._router(spec, state), spec.variant_id)

    def query_many(self, spec, state, items):
        return query_many_tenant(state, items, self._router(spec, state))

    def topk(self, spec, state, m):
        """Global top-m across ALL tenants — returns COMPOSITE keys
        (items of different tenants are different keys; unpack with
        :func:`unpack_keys`). Per-tenant top-k is ``topk_tenant``."""
        return bk.topk_bank(state.bank, m)

    def topk_tenant(self, spec, state, tenant, m):
        return topk_tenant(state, tenant, m, num_shards=self._shards(spec),
                           item_bits=spec.bits)

    def rank_many(self, spec, state, xs):
        raise ValueError(
            f"rank/quantile queries need kind='quantile'; this spec is "
            f"kind={spec.kind!r}. Tenant quantiles run on a quantile spec "
            f"over composite keys (tenant_rank_many / "
            f"tenant_quantile_many).")

    quantile_many = rank_many

    def merge(self, spec, a, b):
        # rows pair exactly (same router); merged rows relax to full
        # width k — same capacity behavior as the dyadic layer merge
        return TenantBank(bank=bk.merge_banks(a.bank, b.bank))

    def consolidate(self, spec, state):
        # folding rows would collapse the tenancy the layout exists for;
        # the compact per-tenant view is spill_rows / topk_tenant
        return state

    def save(self, spec, state) -> Dict[str, Any]:
        return {
            "layout": np.int32(_LAYOUT_FREQUENCY),
            "ids": np.asarray(state.bank.ids),
            "counts": np.asarray(state.bank.counts),
            "errors": np.asarray(state.bank.errors),
            "tenants": np.int32(self._tenants_of(spec, state)),
            "shards": np.int32(spec.shards or 0),
            "item_bits": np.int32(spec.bits),
        }

    def restore(self, spec, d) -> TenantBank:
        fields = SketchState(
            ids=jnp.asarray(np.asarray(d["ids"]), jnp.int32),
            counts=jnp.asarray(np.asarray(d["counts"]), jnp.int32),
            errors=jnp.asarray(np.asarray(d["errors"]), jnp.int32),
        )
        want = spec.tenants * self._shards(spec)
        got = fields.ids.shape[0]
        if got != want:
            raise ValueError(
                f"checkpoint has {got} rows but the spec's layout "
                f"(tenants={spec.tenants} x shards={self._shards(spec)}) "
                f"needs {want}; restore through infer_spec(spec, d)")
        return TenantBank(bank=fields)


__all__ = [
    "TenantBank",
    "TenantAdapter",
    "tenant_bits_for",
    "pack_keys",
    "unpack_keys",
    "init_tenants",
    "router_for",
    "update_block",
    "query_many_tenant",
    "topk_tenant",
    "topk_tenants",
    "tenant_rows",
    "extract_rows",
    "clear_rows",
    "admit_rows",
    "spill_rows",
    "admit_spill",
    "SPILL_BATCH",
    "spill_tenants",
    "admit_tenants",
    "tenant_rank_many",
    "tenant_mass",
    "tenant_quantile_many",
    "TenantLevels",
    "TenantLevelsAdapter",
    "init_levels",
    "update_levels",
    "tenant_ranks",
    "tenant_quantiles",
    "reference_row_update",
    "update_serial_reference",
]
