"""Mesh-distributed Dyadic SpaceSaving±: the shard × level bank.

The first distributed deterministic quantile sketch in the repo: the
dyadic bank's (level, node) summaries are hash-partitioned over S shards
through the engine's composed :class:`repro.sketch.bank.ShardLevelRouter`
— shard s owns every level-l node with ``shard_of(node) == s``, so row
(s, l) of the stacked bank is a SpaceSaving± summary of exactly that
node substream. On a mesh the shard axis rides the "shards" logical rule
(→ the data axis, ``repro.parallel.sharding``): each device routes the
replicated block locally and updates only its own shards' rows under
``shard_map`` — zero cross-device traffic per block, S-way parallel
ingest.

**Sizing.** Each shard carries the FULL single-host per-level capacities
(``dyadic_layer_capacities``): a node's whole mass lands on one shard
(hashing partitions nodes, it cannot split a heavy node's counter), so a
shard must meet the paper's per-level bound on its own substream alone
to keep the unconditional ε·|F|₁ rank guarantee. The bank therefore
trades S× total memory for S× parallel ingest at the SAME ε — and since
each shard monitors only ~1/S of the distinct nodes with full-size
layers, its per-level error ε_l·|F_{s,l}|res is in practice *below* the
single-host bank's (property-tested against the Python oracle in
tests/test_dyadic_sharded.py).

**Queries** are owner-shard reads, exactly like the hash-sharded
frequency bank: rank(x) sums ≤ bits node frequencies, each answered by
the node's owner row via one gather — no merge step, no merge
cross-term. ``quantile_many`` wraps rank_many in the same lockstep
binary search as the single-host bank. **Merge** is row-wise (same S,
same hash); ``consolidate`` folds the S shards of every level into ONE
single-host :class:`repro.sketch.dyadic.DyadicState` for checkpoint
compaction.

Items must lie in [0, 2^bits); weight > 0 inserts, < 0 deletes, 0 is
padding.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.quantiles import dyadic_layer_capacities

from . import bank as bk
from . import state as st
from .bank import DyadicLevelRouter, ShardLevelRouter, shard_of
from .dyadic import DyadicState, feed_blocks, lockstep_quantile_search
from .sharded import _shard_mesh_axes  # one home for the "shards" rule
from .state import VARIANT_SSPM, SketchState


class DyadicShardedState(NamedTuple):
    """Shard-major stacked bank + exactly-tracked total mass."""

    bank: SketchState  # each field (S, bits, k) int32
    mass: jax.Array    # () int32, |F|_1 = I - D

    @property
    def num_shards(self) -> int:
        return self.bank.ids.shape[0]

    @property
    def bits(self) -> int:
        return self.bank.ids.shape[1]

    @property
    def capacity(self) -> int:
        return self.bank.ids.shape[2]

    @property
    def flat_bank(self) -> SketchState:
        """The engine's (S*bits, k) row view (row = s*bits + l)."""
        S, bits, k = self.bank.ids.shape
        return jax.tree.map(lambda x: x.reshape(S * bits, k), self.bank)


def init(
    bits: int,
    num_shards: int,
    total_counters: Optional[int] = None,
    *,
    eps: Optional[float] = None,
    alpha: float = 2.0,
) -> DyadicShardedState:
    """Empty sharded bank; every shard gets the full per-level sizing.

    ``total_counters`` / ``eps`` + ``alpha`` size ONE shard's layers via
    the shared ``dyadic_layer_capacities`` split (the same two
    constructors as ``dyadic.init``); total memory is num_shards × that
    budget — see the module docstring for why the per-shard capacity is
    not divided by S.
    """
    assert num_shards >= 1
    caps = dyadic_layer_capacities(
        bits, total_counters=total_counters, eps=eps, alpha=alpha
    )
    flat = bk.init(list(caps) * num_shards)
    k = flat.ids.shape[1]
    return DyadicShardedState(
        bank=jax.tree.map(
            lambda x: x.reshape(num_shards, bits, k), flat),
        mass=jnp.int32(0),
    )


def layer_capacities(state: DyadicShardedState) -> list:
    """Per-shard live counters per layer (identical across shards)."""
    return bk.row_capacities(jax.tree.map(lambda x: x[0], state.bank))


def space_counters(state: DyadicShardedState) -> int:
    """Total live counters across all shards and layers."""
    return state.num_shards * sum(layer_capacities(state))


# ---------------------------------------------------------------------------
# Update: one composed-router launch, or shard_map over the mesh
# ---------------------------------------------------------------------------



@functools.partial(jax.jit, static_argnames=("variant",))
def _update_block_bank(
    state: DyadicShardedState,
    items: jax.Array,
    weights: jax.Array,
    variant: int,
) -> DyadicShardedState:
    """Single-launch path: the composed router on the (S*bits, k) bank."""
    S, bits, k = state.bank.ids.shape
    router = ShardLevelRouter(bits, S)
    flat = bk.update_block_fused(
        state.flat_bank, items, weights, router, variant)
    return DyadicShardedState(
        bank=jax.tree.map(lambda x: x.reshape(S, bits, k), flat),
        mass=state.mass + weights.astype(jnp.int32).sum(),
    )


@functools.partial(jax.jit, static_argnames=("variant", "mesh", "axes"))
def _update_block_shard_map(
    state: DyadicShardedState,
    items: jax.Array,
    weights: jax.Array,
    variant: int,
    mesh,
    axes,
) -> DyadicShardedState:
    """shard_map ingest: each mesh slice updates its own shards' rows.

    Level routing (the one shared sort + shift broadcast) happens
    replicated — it is O(B log B) vector work on the raw block — and the
    per-shard weight masking rides along as an (S, bits, B) routed
    weight tensor partitioned with the bank, so the update itself moves
    no bytes across devices: each device runs the engine's dense fused
    core on its local (S_loc*bits, k) rows. Jitted with the mesh static,
    so a stream of blocks compiles once.
    """
    from jax.sharding import PartitionSpec as P

    S, bits, k = state.bank.ids.shape
    B = items.shape[0]
    router = ShardLevelRouter(bits, S)
    nodes, w_l = DyadicLevelRouter(bits).route_dense(items, weights)
    w_routed = router.mask_shards(nodes, w_l)                 # (S, bits, B)

    def local_update(bank_loc, nodes_rep, w_loc):
        s_loc = bank_loc.ids.shape[0]
        row_items = jnp.broadcast_to(
            nodes_rep[None], (s_loc, bits, B)).reshape(s_loc * bits, B)
        flat = jax.tree.map(
            lambda x: x.reshape(s_loc * bits, k), bank_loc)
        out = bk.update_rows(
            flat, row_items, w_loc.reshape(s_loc * bits, B), variant)
        return jax.tree.map(lambda x: x.reshape(s_loc, bits, k), out)

    spec3 = SketchState(P(axes, None, None), P(axes, None, None),
                        P(axes, None, None))
    fn = jax.shard_map(
        local_update,
        mesh=mesh,
        in_specs=(spec3, P(None, None), P(axes, None, None)),
        out_specs=spec3,
        check_vma=False,
    )
    return DyadicShardedState(
        bank=fn(state.bank, nodes, w_routed),
        mass=state.mass + weights.astype(jnp.int32).sum(),
    )


def update_block(
    state: DyadicShardedState,
    items: jax.Array,
    weights: jax.Array,
    variant: int = VARIANT_SSPM,
    *,
    path: str = "auto",
) -> DyadicShardedState:
    """Apply one block of signed weighted updates to the whole bank.

    path: 'auto'      — shard_map over the mesh axes bound to the
                        "shards" logical rule when a mesh is active (and
                        divides S), else the single-launch 'bank' path;
          'bank'      — composed shard × level router, one fused launch;
          'shard_map' — force the mesh path (accepts size-1 meshes for
                        tests).
    All paths produce bit-identical banks (the shard_map local program
    runs the same dense fused core on the same routed rows).
    """
    items = jnp.asarray(items, jnp.int32)
    weights = jnp.asarray(weights, jnp.int32)
    if path == "auto":
        axes = _shard_mesh_axes(state.num_shards)
        path = "shard_map" if axes else "bank"
    elif path == "shard_map":
        axes = _shard_mesh_axes(state.num_shards, min_size=1)
        if not axes:
            raise ValueError(
                "path='shard_map' needs an active mesh whose 'shards' "
                "logical axes divide num_shards "
                "(repro.parallel.sharding.use_mesh)")
    if path == "shard_map":
        from repro.parallel import sharding as psh

        return _update_block_shard_map(state, items, weights, variant,
                                       psh.current_mesh(), axes)
    if path != "bank":
        raise ValueError(f"unknown path {path!r}")
    return _update_block_bank(state, items, weights, variant)


def process_stream(
    state: DyadicShardedState,
    items: np.ndarray,
    weights: np.ndarray,
    variant: int = VARIANT_SSPM,
    block: int = 1024,
    path: str = "auto",
) -> DyadicShardedState:
    """Host-side convenience: feed a whole stream in fixed-size blocks
    (the shared pad-and-chunk driver, ``dyadic.feed_blocks``)."""
    return feed_blocks(
        lambda st_, i, w: update_block(st_, i, w, variant, path=path),
        state, items, weights, block)


# ---------------------------------------------------------------------------
# Queries: owner-shard rank / quantile over the dyadic decomposition
# ---------------------------------------------------------------------------

@jax.jit
def rank_many(state: DyadicShardedState, xs: jax.Array) -> jax.Array:
    """Estimated rank(x) = |{v <= x}| per query, from owner shards only.

    Same dyadic decomposition as the single-host bank (≤ one node per
    level, node 2·(y >> (l+1)) iff bit l of y = x+1 is set), but each
    (level, node) frequency is read from the node's owner row
    (shard_of(node), level) — one gather of n·bits rows, no cross-shard
    combination.
    """
    S, bits, k = state.bank.ids.shape
    xs = xs.astype(jnp.int32)
    y = xs + 1                                              # (n,)
    lvl = jnp.arange(bits, dtype=jnp.int32)[None, :]        # (1, bits)
    nodes = 2 * jnp.right_shift(y[:, None], lvl + 1)        # (n, bits)
    take = (jnp.right_shift(y[:, None], lvl) & 1) > 0       # (n, bits)
    owner = shard_of(nodes, S)                              # (n, bits)
    ids_r = state.bank.ids[owner, lvl]                      # (n, bits, k)
    cnt_r = state.bank.counts[owner, lvl]
    # guard the owner-row equality: for xs at the int32 rail, y = xs + 1
    # wraps negative and 2*(y >> (l+1)) can land exactly on BLOCKED (-2),
    # which would otherwise match a capacity-padding slot's INT_MAX count
    eq = (ids_r == nodes[..., None]) & (ids_r >= 0)
    est = jnp.where(eq, cnt_r, 0).sum(axis=-1) * eq.any(axis=-1)
    r = jnp.where(take, jnp.maximum(est, 0), 0).sum(axis=1)
    # y >= 2^bits: the whole-universe node's frequency is the exact mass
    return jnp.where(y >= (1 << bits), state.mass, r).astype(jnp.int32)


def rank(state: DyadicShardedState, x) -> int:
    return int(rank_many(state, jnp.asarray([x], jnp.int32))[0])


@jax.jit
def quantile_many(state: DyadicShardedState, qs: jax.Array) -> jax.Array:
    """Per-query quantiles via the shared ``dyadic.
    lockstep_quantile_search`` (see its float32 rank-target caveat),
    driven by owner-shard ranks."""
    return lockstep_quantile_search(
        lambda xs: rank_many(state, xs), state.mass, state.bits, qs)


def quantile(state: DyadicShardedState, q: float) -> int:
    return int(quantile_many(state, jnp.asarray([q], jnp.float32))[0])


# ---------------------------------------------------------------------------
# Merge / checkpoint consolidation
# ---------------------------------------------------------------------------

@jax.jit
def merge(a: DyadicShardedState, b: DyadicShardedState) -> DyadicShardedState:
    """Row-wise merge of two same-shape banks (same S, same hash).

    Each (shard, level) row of either bank monitored the same node
    substream, so the pairing is exact; masses add. Merge output rows
    carry no BLOCKED mask (capacity relaxes to the padded k — strictly
    more counters, never less accuracy).
    """
    shape = a.bank.ids.shape
    merged = bk.merge_banks(a.flat_bank, b.flat_bank)
    return DyadicShardedState(
        bank=jax.tree.map(lambda x: x.reshape(shape), merged),
        mass=a.mass + b.mass,
    )


def consolidate(state: DyadicShardedState) -> DyadicState:
    """Fold the S shards of every level into ONE single-host DyadicState.

    A per-level tree of ``state.merge`` (BLOCKED-aware; the shared
    ``bank.consolidate`` reduction with a level-vmapped merge) folds
    (S, bits, k) -> (bits, k): the compact checkpoint/telemetry view,
    with the standard merged-summary error bounds on top of the
    per-shard guarantees. The merged bank's rows have full capacity k
    (merge output carries no BLOCKED slots).
    """
    return DyadicState(
        bank=bk.consolidate(state.bank, merge_fn=jax.vmap(st.merge)),
        mass=state.mass)


def __getattr__(name):
    # the pre-redesign client-specific spelling: resolves to the same
    # update_block, warns (once) toward the spec-driven surface.
    if name == "ingest":
        from .api import deprecated_alias

        globals()["ingest"] = deprecated_alias(
            "repro.sketch.dyadic_sharded.ingest",
            "repro.sketch.api.update(SketchSpec(kind='quantile', "
            "shards=S, ...), ...)", update_block)
        return globals()["ingest"]
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "DyadicShardedState",
    "init",
    "layer_capacities",
    "space_counters",
    "update_block",
    "process_stream",
    "rank",
    "rank_many",
    "quantile",
    "quantile_many",
    "merge",
    "consolidate",
]
