"""Hash-sharded SpaceSaving± bank: S per-shard sketches, one launch/block.

Thin client of the unified bank engine (``repro.sketch.bank``,
DESIGN.md §10): the shard dim maps to the engine's row axis through a
``HashShardRouter`` and the fused ingest/queries/merge below delegate to
the engine's partition core. The paper's summaries are mergeable (the
SpaceSaving± Family follow-up proves merged summaries keep the
bounded-deletion guarantee), but merging is the *fallback* here, not the
query path: every item id is owned by exactly one shard of a hash
partition, so the bank is a sharded-by-key frequency store —

  * **State** — one stacked :class:`SketchState` of shape (S, k): shard s
    monitors only items with ``shard_of(x, S) == s``. At equal total
    budget S·k, shard s applies the paper's Thm 2/4 bounds to *its own
    substream* (mass ≈ |F|₁/S with a uniform hash, capacity k = total/S),
    so per-item error matches the single sketch's ε·|F|₁ scaling.

  * **Update** — a block of signed updates is routed shard-by-hash with
    ONE shared sort (the phase-1 packed-key partition
    ``phases._stable_partition_perm`` when the universe is small enough
    to pack, else one argsort), then ingested with a single fused
    launch. The default single-device path (``path='block'``) never
    duplicates B-wide vector work per shard: aggregation, monitored
    matching and the residual-insert compaction all run ONCE on global
    arrays (two more packed-key partitions group residual inserts
    shard-major and [units | non-units] within each shard), and only the
    O(k)-per-shard phases — empty fill, unit water-fill, and the
    residual tournament loop, whose vmapped trip count drops from U to
    max_s(U_s) ≈ U/S — run batched over the (S, k) bank. On a real mesh
    the shard axis maps to the mesh "data" axis via the "shards" logical
    rule in ``repro.parallel.sharding``: each device routes the
    replicated block locally (sorted row broadcast + foreign weights
    masked to 0, every row still ascending so aggregation runs
    ``assume_sorted``) and updates its own S/n shard rows under
    ``shard_map`` with zero cross-device traffic. All paths aggregate a
    shard's row to exactly its own (uid, net) multiset, so every
    per-shard state is bit-identical to a sketch built from that shard's
    substream alone (pinned by tests/test_sharded.py).

  * **Queries** — an item lives in exactly one shard, so ``query_many``
    answers from the owner shard and ``topk`` is a flat top-k over all
    S·k slots: NO merge step, hence no merge cross-term error
    (DESIGN.md §9). The vmapped while-loops in the residual phase run
    max_s(U_s) ≈ U/S sequential steps instead of U — the source of the
    block-ingest speedup BENCH_sharded.json tracks.

  * **Merge** — cross-*bank* reduction (same S, same hash) is shard-wise
    ``state.merge``; ``consolidate`` folds all S shards into one k-counter
    summary for checkpoint compaction, with the usual merged-summary
    error bounds.

Weight convention matches the rest of the package: weight > 0 insert,
< 0 delete, 0 padding; item ids non-negative (negative = sentinel).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from . import bank as bk
from . import state as st
from .bank import HashShardRouter, shard_of  # noqa: F401  (re-exported API)
from .blocks import block_update, block_update_batched
from .state import VARIANT_SSPM, SketchState


class ShardedSketch(NamedTuple):
    """Stacked per-shard states; shard s owns ids with shard_of(id) == s."""

    bank: SketchState  # each field (S, k) int32

    @property
    def num_shards(self) -> int:
        return self.bank.ids.shape[0]

    @property
    def capacity(self) -> int:
        """Per-shard capacity k (total budget = num_shards * k)."""
        return self.bank.ids.shape[1]


def init(total_capacity: int, num_shards: int) -> ShardedSketch:
    """Empty bank splitting ``total_capacity`` counters over S shards.

    The per-shard capacity is ceil(total/S) so an uneven budget never
    rounds a shard below its share (equal-budget comparisons in
    BENCH_sharded.json use divisible totals).
    """
    assert num_shards >= 1
    k = -(-total_capacity // num_shards)
    return ShardedSketch(bank=bk.init(k, num_shards))


def route_block(
    items: jax.Array,
    weights: jax.Array,
    num_shards: int,
    universe_bits: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One-sort hash routing: (B,) block -> (S, B) per-shard views.

    Thin front-end over ``bank.HashShardRouter.route_dense``: sorts the
    block ONCE (packed-key partition when ``universe_bits`` proves
    item*B fits int32, else argsort), then materializes shard s's view
    as the shared sorted id row with foreign weights masked to 0. Every
    row stays ascending, so downstream aggregation runs
    ``assume_sorted`` with no per-shard sort, and each row aggregates to
    exactly the shard's own (uid, net) multiset, preserving bit-identity
    with independently built shards.
    """
    return HashShardRouter(num_shards, universe_bits).route_dense(
        items, weights)


def _axis_sizes(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _shard_mesh_axes(num_shards: int, min_size: int = 2):
    """Mesh axes for the bank's shard dim, or None for the vmap path.

    ``min_size``: the auto path only leaves vmap for a real multi-device
    axis; an explicit path='shard_map' accepts size-1 meshes (tests).
    """
    from repro.parallel import sharding as psh

    mesh = psh.current_mesh()
    if mesh is None:
        return None
    axes = psh.mesh_axis("shards")
    if not axes:
        return None
    n = _axis_sizes(mesh, axes)
    if n < min_size or num_shards % n != 0:
        return None
    return axes


@functools.partial(jax.jit, static_argnames=("variant", "universe_bits"))
def _update_block_fused(
    state: ShardedSketch,
    items: jax.Array,
    weights: jax.Array,
    variant: int,
    universe_bits: Optional[int],
) -> ShardedSketch:
    """Fused single-launch ingest via the bank engine's partition core.

    ``bank._fused_partition``: global phase 1 (one shared sort, one
    in-place segment aggregation, one searchsorted monitored match for
    all shards, ONE packed-key grouping sort building every shard's
    [units | non-units | consumed] layout), then the batched O(k)
    phases and the flat banked residual loop whose trip count is
    max_s(non-unit_s) ≈ U/S instead of U. Per-shard results are
    bit-identical to blocks.block_update on the shard's own substream —
    pinned against ``update_block_serial_reference`` by tests and
    BENCH_sharded.json.
    """
    router = HashShardRouter(state.num_shards, universe_bits)
    return ShardedSketch(
        bank=bk.update_block_fused(state.bank, items, weights, router,
                                   variant))


@functools.partial(
    jax.jit, static_argnames=("variant", "universe_bits", "path", "interpret")
)
def _update_block_routed(
    state: ShardedSketch,
    items: jax.Array,
    weights: jax.Array,
    variant: int,
    universe_bits: Optional[int],
    path: str,
    interpret: Optional[bool],
) -> ShardedSketch:
    """Masked-row ingest: the per-device program (and the Pallas path)."""
    S = state.num_shards
    items_b, w_routed = route_block(items, weights, S, universe_bits)
    if path == "kernel":
        # production kernel path: phases 1-2 fused in one tiled launch
        # (bit-identical to the split banked kernel and the pure-JAX
        # engine; interpret resolves platform-side)
        from repro.kernels.sketch_update.ops import sketch_block_update_fused

        bank = sketch_block_update_fused(
            state.bank, items_b, w_routed, variant, interpret)
    else:
        bank = block_update_batched(
            state.bank, items_b, w_routed, variant, assume_sorted=True)
    return ShardedSketch(bank=bank)


@functools.partial(
    jax.jit, static_argnames=("variant", "universe_bits", "mesh", "axes"))
def _update_block_shard_map(
    state: ShardedSketch,
    items: jax.Array,
    weights: jax.Array,
    variant: int,
    universe_bits: Optional[int],
    mesh,
    axes,
) -> ShardedSketch:
    """shard_map ingest: each mesh slice updates its own S/n shard rows.

    Routing happens replicated (it is O(B log B) vector work on the raw
    block); the bank stays partitioned over the "shards" logical axis the
    whole time, so the update itself moves no bytes across devices.
    Jitted with the mesh static, so a stream of blocks compiles once.
    """
    from jax.sharding import PartitionSpec as P

    S = state.num_shards
    items_b, w_routed = route_block(items, weights, S, universe_bits)

    fn = jax.shard_map(
        lambda b, i, w: block_update_batched(b, i, w, variant,
                                             assume_sorted=True),
        mesh=mesh,
        in_specs=(SketchState(P(axes, None), P(axes, None), P(axes, None)),
                  P(axes, None), P(axes, None)),
        out_specs=SketchState(P(axes, None), P(axes, None), P(axes, None)),
        check_vma=False,
    )
    return ShardedSketch(bank=fn(state.bank, items_b, w_routed))


def update_block(
    state: ShardedSketch,
    items: jax.Array,
    weights: jax.Array,
    variant: int = VARIANT_SSPM,
    *,
    universe_bits: Optional[int] = None,
    path: str = "auto",
    interpret: Optional[bool] = None,
) -> ShardedSketch:
    """Route one block shard-by-hash and ingest it with a single launch.

    path: 'auto'      — shard_map over the mesh axes bound to the
                        "shards" logical rule when a mesh is active (and
                        divides S), else the fused 'block' path;
          'block'     — fused global-phase-1 launch (production CPU /
                        single-device path, see ``_update_block_fused``);
          'vmap'      — masked-row ``block_update_batched`` (the
                        per-device program; kept callable for A/B);
          'shard_map' — force the mesh path;
          'kernel'    — fused tiled Pallas launch (bit-identical).
    All paths produce bit-identical banks. ``universe_bits``: static
    bound log2(universe) enabling the packed single-sort router (as in
    the dyadic bank). ``interpret`` defaults to platform-resolved
    (``repro.platform.resolve_interpret``); passing True explicitly is
    deprecated at this layer.
    """
    if interpret is True:
        from repro.platform import warn_explicit_interpret

        warn_explicit_interpret("sharded.update_block")
    if path == "auto":
        axes = _shard_mesh_axes(state.num_shards)
        path = "shard_map" if axes else "block"
    elif path == "shard_map":
        axes = _shard_mesh_axes(state.num_shards, min_size=1)
        if not axes:
            from repro.parallel import sharding as psh

            mesh = psh.current_mesh()
            bound = psh.mesh_axis("shards") if mesh is not None else None
            if mesh is None or not bound:
                raise ValueError(
                    "path='shard_map' needs an active mesh with a 'shards' "
                    "logical rule (repro.parallel.sharding.use_mesh)")
            raise ValueError(
                f"path='shard_map' needs num_shards divisible by the "
                f"'shards' mesh axes {bound} (total size "
                f"{_axis_sizes(mesh, bound)}); got num_shards="
                f"{state.num_shards}")
    if path == "shard_map":
        from repro.parallel import sharding as psh

        return _update_block_shard_map(
            state, items, weights, variant, universe_bits,
            psh.current_mesh(), axes)
    if path == "block":
        return _update_block_fused(
            state, items, weights, variant, universe_bits)
    return _update_block_routed(
        state, items, weights, variant, universe_bits, path, interpret)


def update_block_serial_reference(
    state: ShardedSketch,
    items: jax.Array,
    weights: jax.Array,
    variant: int = VARIANT_SSPM,
    universe_bits: Optional[int] = None,
) -> ShardedSketch:
    """Reference: route, then update each shard SERIALLY (python loop).

    The bit-identity oracle for the batched launch (acceptance criterion
    of BENCH_sharded.json): same routing, same per-shard ``block_update``,
    no vmap — one shard after another.
    """
    S = state.num_shards
    items_b, w_routed = route_block(
        jnp.asarray(items), jnp.asarray(weights), S, universe_bits)
    outs = []
    for s in range(S):
        shard = jax.tree.map(lambda x: x[s], state.bank)
        outs.append(block_update(
            shard, items_b[s], w_routed[s], variant, assume_sorted=True))
    return ShardedSketch(
        bank=jax.tree.map(lambda *xs: jnp.stack(xs), *outs))


# ---------------------------------------------------------------------------
# Global queries: owner-shard answers, no merge error
# ---------------------------------------------------------------------------

@jax.jit
def query_many(state: ShardedSketch, items: jax.Array) -> jax.Array:
    """Estimated frequency per query item, answered by its owner shard.

    Each id is monitored (if at all) in exactly one shard, so the global
    answer is the owner shard's answer — no cross-shard combination and
    therefore no merge cross-term error.
    """
    items = items.astype(jnp.int32)
    est = jax.vmap(st.query_many, in_axes=(0, None))(state.bank, items)  # (S, n)
    owner = shard_of(items, state.num_shards)                            # (n,)
    return jnp.take_along_axis(est, owner[None, :], axis=0)[0]


def query(state: ShardedSketch, item) -> jax.Array:
    return query_many(state, jnp.asarray([item], jnp.int32))[0]


def topk(state: ShardedSketch, m: int) -> Tuple[jax.Array, jax.Array]:
    """Global top-m (ids, counts): flat top-k over all S·k slots.

    Exact given the per-shard states (every candidate heavy hitter is
    monitored by its owner shard with its full estimated count).
    """
    return bk.topk_bank(state.bank, m)


# ---------------------------------------------------------------------------
# Cross-bank reduction / checkpoint consolidation
# ---------------------------------------------------------------------------

@jax.jit
def merge(a: ShardedSketch, b: ShardedSketch) -> ShardedSketch:
    """Shard-wise mergeable-summaries merge of two same-shape banks.

    Valid because both banks route with the same hash: shard s of either
    bank only ever monitored ids owned by s, so the pairing is exact and
    the merged bank keeps the shard-ownership invariant.
    """
    return ShardedSketch(bank=bk.merge_banks(a.bank, b.bank))


def consolidate(state: ShardedSketch) -> SketchState:
    """Fold all shards into ONE k-counter summary (checkpoint compaction).

    A tree of ``state.merge`` reduces (S, k) -> (k,) (``bank.
    consolidate``): the compact global view for checkpoints/telemetry,
    carrying the standard merged-summary error bounds (unlike queries on
    the live bank, which are merge-error-free). Not an inverse of
    sharding — S·k counters collapse to k.
    """
    return bk.consolidate(state.bank)


def to_dict(state: ShardedSketch) -> dict:
    """Union of per-shard {item: (count, error)} (ids are disjoint)."""
    out = {}
    for s in range(state.num_shards):
        out.update(st.to_dict(jax.tree.map(lambda x: x[s], state.bank)))
    return out


def __getattr__(name):
    # the pre-redesign client-specific spelling: resolves to the same
    # update_block, warns (once) toward the spec-driven surface.
    if name == "ingest":
        from .api import deprecated_alias

        globals()["ingest"] = deprecated_alias(
            "repro.sketch.sharded.ingest",
            "repro.sketch.api.update(SketchSpec(kind='frequency', "
            "shards=S, ...), ...)", update_block)
        return globals()["ingest"]
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "ShardedSketch",
    "init",
    "shard_of",
    "route_block",
    "update_block",
    "update_block_serial_reference",
    "query",
    "query_many",
    "topk",
    "merge",
    "consolidate",
    "to_dict",
]
