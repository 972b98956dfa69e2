"""One spec-driven front-end over every SpaceSaving± backend.

After the engine refactors the repo exposes four client surfaces with
four divergent spellings (``blocks.block_update``,
``sharded.update_block``, ``dyadic.update_block(..., path=)``,
``dyadic_sharded.update_block``).  The SpaceSaving± Family follow-up
(PAPERS.md) treats all of them as ONE mergeable family behind one
contract; this module is that contract as code:

  * :class:`SketchSpec` — a frozen (hashable → jit-static) description
    of WHAT to build: ``kind`` ('frequency' | 'quantile'), sizing
    (``k`` total counters or the paper's ``eps``+``alpha`` Thm-4 /
    §4.2 prescription via the shared ``capacity_for`` /
    ``dyadic_layer_capacities`` helpers), ``variant``
    ('sspm' | 'lazy'), ``shards`` (None = single-host), ``bits``
    (universe bound; required for quantile kinds) and ``backend``
    ('bank' fused engine | 'block' vmapped two-phase | 'kernel' Pallas
    | 'serial' scan baseline).

  * an **adapter registry** — each (kind, sharded?) pair registers one
    adapter object translating the uniform surface onto its client
    module.  New layouts plug in by registering an adapter; consumers
    never learn a fifth spelling.

  * the **uniform functional surface** — ``make``, ``update``,
    ``query``/``query_many``/``topk``, ``rank``/``rank_many``/
    ``quantile``/``quantile_many`` (quantile kinds only, with
    actionable errors otherwise), ``merge``, ``consolidate``,
    ``save``/``restore``.  Every call is bit-identical to the direct
    client/engine spelling it wraps — pinned across the full spec grid
    by tests/test_api_parity.py.

Checkpoints (``save``/``restore``) are flat dicts of numpy-compatible
arrays carrying an integer ``layout`` tag, and ``restore`` also accepts
the pre-redesign ``stats._SketchBank`` layouts (``ids/counts/errors``
[+ ``shards``], no tag) so existing ``train/checkpoint.py`` checkpoints
keep loading.

The stateful companion (host-side buffering, padding, cached donated
jitted ingest, windowed deletion scheduling) is
:class:`repro.sketch.session.StreamSession`.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.quantiles import dyadic_layer_capacities
from repro.core.spacesaving import capacity_for

from . import bank as bk
from . import blocks
from . import dyadic as dy
from . import dyadic_sharded as dysh
from . import sharded as shd
from . import state as st
from .state import VARIANT_LAZY, VARIANT_SSPM, SketchState

KINDS = ("frequency", "quantile")
# variant name -> engine-layer integer. The family variants ('double',
# 'unbiased') map to VARIANT_SSPM because their underlying banks run
# plain SpaceSaving updates on insert-only streams (deletions feed the
# second bank as insertions — repro.sketch.family); the spec-level name
# still selects the family adapter via the registry axis.
VARIANTS = {"sspm": VARIANT_SSPM, "lazy": VARIANT_LAZY,
            "double": VARIANT_SSPM, "unbiased": VARIANT_SSPM}
FAMILY_VARIANTS = ("double", "unbiased")
BACKENDS = ("bank", "block", "kernel", "serial")

# integer layout tags (strings would not survive the np.savez round trip
# of train/checkpoint.py); absence of the tag marks a pre-redesign dict.
LAYOUT_FREQUENCY = 1
LAYOUT_QUANTILE = 2
LAYOUT_DOUBLE = 3     # two coupled banks (Double / unbiased SpaceSaving±)
LAYOUT_CRPRECIS = 4   # CR-precis prime-modulus counter array


@dataclasses.dataclass(frozen=True)
class SketchSpec:
    """Frozen, hashable description of one SpaceSaving± summary.

    Sizing: pass exactly one of ``k`` (total live counters — split per
    layer for quantile kinds by ``dyadic_layer_capacities``, per shard
    for hash-sharded frequency banks) or ``eps`` (+ ``alpha``), the
    paper's Thm-4 / §4.2 prescription (``capacity_for`` /
    ``dyadic_layer_capacities``).

    ``bits`` bounds the item universe to [0, 2^bits).  Required for
    quantile kinds (it fixes the dyadic layer count); optional for
    frequency kinds, where it only enables the packed single-sort
    router (``bank.sort_block``).

    ``backend`` picks the execution path, NOT the semantics — every
    backend of a given spec produces bit-identical states:
      'bank'   fused bank-engine launch (production default);
      'block'  per-row vmapped two-phase update;
      'kernel' fused tiled Pallas launch (interpret resolved by
               repro.platform: compiled iff an accelerator is attached);
      'serial' sequential scan baseline (A/B reference).
    ``backends_for(kind, shards)`` lists what a combination supports.

    ``tenants=T`` selects the multi-tenant bank layout
    (``repro.sketch.tenant``): one (T·S, k) bank ingesting composite
    keys ``(tenant << bits) | item``, rows tenant-major, with ``shards``
    meaning per-tenant hash shards. ``bits`` becomes required (it is
    the per-tenant item-universe bound composite keys are packed
    against). Size with ``k``/``eps`` (split evenly across tenants) or
    ``tenant_caps`` (one capacity per tenant — per-tenant BLOCKED
    masks; base variants only). With ``kind='quantile'`` each tenant
    owns ``bits`` rows, one per dyadic layer, sized per tenant by
    ``eps`` (or ``k`` split evenly across tenants), so every tenant's
    rank error is bounded by its own mass (``repro.sketch.tenant``'s
    per-tenant Dyadic SS± layout); its row-tagged keys need
    ``bits`` + the bits of ``tenants·bits`` rows <= 31.
    """

    kind: str = "frequency"
    k: Optional[int] = None
    eps: Optional[float] = None
    alpha: float = 2.0
    variant: str = "sspm"
    shards: Optional[int] = None
    bits: Optional[int] = None
    backend: str = "bank"
    tenants: Optional[int] = None
    tenant_caps: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(
                f"SketchSpec.kind must be one of {KINDS}, got {self.kind!r}")
        if self.variant not in VARIANTS:
            raise ValueError(
                f"SketchSpec.variant must be one of {tuple(VARIANTS)}, got "
                f"{self.variant!r} (the integer VARIANT_* constants belong "
                f"to the engine layer; the spec speaks names)")
        if self.backend not in BACKENDS + ("crprecis",):
            raise ValueError(
                f"SketchSpec.backend must be one of "
                f"{BACKENDS + ('crprecis',)}, got {self.backend!r}")
        if self.tenant_caps is not None and not isinstance(
                self.tenant_caps, tuple):
            # the spec must stay hashable (jit-static); accept any
            # sequence but store the canonical tuple
            object.__setattr__(self, "tenant_caps",
                               tuple(int(c) for c in self.tenant_caps))
        n_sizing = ((self.k is not None) + (self.eps is not None)
                    + (self.tenant_caps is not None))
        if n_sizing != 1:
            raise ValueError(
                "size the spec with exactly one of k (total counters), "
                "eps (+ alpha, paper Thm 4 / §4.2) or tenant_caps "
                f"(per-tenant counters); got k={self.k}, eps={self.eps}, "
                f"tenant_caps={self.tenant_caps}")
        if self.kind == "quantile" and self.bits is None:
            raise ValueError(
                "kind='quantile' needs bits (the dyadic universe bound "
                "[0, 2^bits) fixes the layer count)")
        if self.shards is not None and self.shards < 1:
            raise ValueError(f"shards must be >= 1 or None, got {self.shards}")
        if self.variant in FAMILY_VARIANTS and self.kind != "frequency":
            raise ValueError(
                f"variant={self.variant!r} (the Double/unbiased "
                f"SpaceSaving± family) is a frequency-kind layout; "
                f"kind={self.kind!r} does not support it")
        if self.tenant_caps is not None and self.tenants is None:
            raise ValueError(
                "tenant_caps sizes the multi-tenant layout; set tenants=T "
                "(the per-tenant capacity list has no meaning without it)")
        if self.tenants is not None:
            if self.tenants < 1:
                raise ValueError(
                    f"tenants must be >= 1 or None, got {self.tenants}")
            if self.bits is None:
                raise ValueError(
                    "tenants=T needs bits (the per-tenant item-universe "
                    "bound composite keys (tenant << bits) | item are "
                    "packed against)")
            tb = (self.tenants - 1).bit_length()
            if tb + self.bits > 31:
                raise ValueError(
                    f"composite keys need tenant_bits + bits <= 31 to fit "
                    f"the int32 id dtype; got tenants={self.tenants} "
                    f"({tb} bits) with bits={self.bits}")
            if self.kind == "quantile":
                rb = (self.tenants * self.bits - 1).bit_length()
                if rb + self.bits > 31:
                    raise ValueError(
                        f"per-tenant dyadic rows tag their keys with the "
                        f"row (tenant*bits + layer), so row_bits + bits "
                        f"<= 31 must fit the int32 id dtype; got "
                        f"tenants={self.tenants} x bits={self.bits} rows "
                        f"({rb} bits) with bits={self.bits}")
                if self.tenant_caps is not None or self.shards is not None:
                    raise ValueError(
                        "the per-tenant quantile layout is one row per "
                        "(tenant, dyadic layer), sized by eps or k: "
                        "tenant_caps and shards are frequency-kind "
                        "layouts")
            if self.tenant_caps is not None:
                if len(self.tenant_caps) != self.tenants:
                    raise ValueError(
                        f"tenant_caps has {len(self.tenant_caps)} entries "
                        f"for tenants={self.tenants}")
                if min(self.tenant_caps) < 1:
                    raise ValueError(
                        f"every tenant needs >= 1 counter; got "
                        f"min(tenant_caps)={min(self.tenant_caps)}")
                if self.variant in FAMILY_VARIANTS:
                    raise ValueError(
                        "tenant_caps (per-tenant BLOCKED masks) is a "
                        "base-layout feature; the family's k_I/k_D split "
                        "sizes evenly — use k or eps with "
                        f"variant={self.variant!r}")
        if self.backend not in backends_for(self.kind, self.shards,
                                            self.variant, self.tenants):
            raise ValueError(
                f"backend {self.backend!r} is not supported for "
                f"kind={self.kind!r}, shards={self.shards}, "
                f"variant={self.variant!r}, tenants={self.tenants}; "
                f"supported: "
                f"{backends_for(self.kind, self.shards, self.variant, self.tenants)}")

    @property
    def variant_id(self) -> int:
        """The engine-layer integer variant (VARIANT_LAZY / VARIANT_SSPM)."""
        return VARIANTS[self.variant]

    @property
    def capacity(self) -> int:
        """Resolved total live-counter budget of one frequency summary."""
        if self.kind != "frequency":
            raise ValueError(
                "capacity is the frequency-kind budget; quantile kinds size "
                "per layer — use layer_capacities()")
        if self.tenant_caps is not None:
            return int(sum(self.tenant_caps))
        if self.k is not None:
            return int(self.k)
        return capacity_for(self.eps, self.alpha,
                            "lazy" if self.variant == "lazy" else "ss_pm")

    def layer_capacities(self) -> list:
        """Per-layer counters of one quantile summary (shared helper) —
        one tenant's, where ``k`` splits evenly across ``tenants``."""
        if self.kind != "quantile":
            raise ValueError("layer_capacities() applies to quantile kinds")
        k = self.k
        if k is not None and self.tenants:
            k = -(-k // self.tenants)
        return dyadic_layer_capacities(
            self.bits, total_counters=k, eps=self.eps, alpha=self.alpha)


def backends_for(kind: str, shards: Optional[int], variant: str = "sspm",
                 tenants: Optional[int] = None) -> Tuple[str, ...]:
    """Execution paths a (kind, sharded?, variant, tenants?) combination
    supports.

    The family variants run only through the fused bank engine (their
    coupled banks are engine banks by construction); the deterministic
    CR-precis layout is reachable as ``backend='crprecis'`` on unsharded
    sspm frequency specs (it is a different summary, not an execution
    path of the SpaceSaving± store — sharding it would break its linear
    row arithmetic for no space gain). Multi-tenant layouts, of either
    kind, are fused-engine banks only (their whole point is the
    one-launch routed ingest).
    """
    if tenants:
        return ("bank",)
    if variant in FAMILY_VARIANTS:
        return ("bank",) if kind == "frequency" else ()
    if kind == "quantile" and shards:
        # the composed shard × level bank only runs the fused engine
        # (its shard_map path is selected automatically under a mesh)
        return ("bank",)
    if kind == "frequency" and not shards:
        # crprecis has no lazy/sspm distinction; it hangs off the sspm
        # default so the grid carries exactly one cell for it
        extra = ("crprecis",) if variant == "sspm" else ()
        return BACKENDS + extra
    return BACKENDS


def variants_for(kind: str) -> Tuple[str, ...]:
    """Variant names a kind supports (the family is frequency-only)."""
    return tuple(VARIANTS) if kind == "frequency" else ("sspm", "lazy")


# ---------------------------------------------------------------------------
# Input validation: one home for the block conventions
# ---------------------------------------------------------------------------

def validate_block(spec: SketchSpec, items, weights, *,
                   prior_mass: int = 0) -> int:
    """Check one (items, weights) block against the package conventions.

    The conventions every adapter assumes (DESIGN.md §11): item ids are
    non-negative int (negative values are the EMPTY/BLOCKED sentinels),
    weight > 0 inserts, < 0 deletes, weight == 0 marks padding (the id
    of a zero-weight slot is ignored), items and weights are equal-length
    1-D, and quantile kinds need every REAL (nonzero-weight) item inside
    the dyadic universe [0, 2^bits).

    ``prior_mass`` is the positive mass already merged into the state
    this block is headed for (sessions track it across ingests).  A
    counter can hold at most that much, so the block is rejected when
    ``prior_mass`` plus the block's worst PER-ITEM net weight could
    carry a counter past int32 — the per-block magnitude-sum check
    alone misses a saturated counter meeting a near-rail state, which
    is exactly the precondition the SK201 range pass assumes
    (``repro.analysis.range_interp``).  Returns the block's positive
    mass (0 for traced blocks) so callers can accumulate it.

    Traced (jit-abstract) inputs skip the value checks — validation
    happens where values exist: at the host boundary
    (:class:`repro.sketch.session.StreamSession` and the non-jitted
    ``api.update``), never inside a compiled ingest.
    """
    traced = isinstance(items, jax.core.Tracer) or isinstance(
        weights, jax.core.Tracer)
    i_shape = np.shape(items)
    w_shape = np.shape(weights)
    if len(i_shape) != 1:
        raise ValueError(
            f"items must be 1-D (one block of ids), got shape {i_shape}; "
            f"flatten batches host-side or use StreamSession.extend")
    if i_shape != w_shape:
        raise ValueError(
            f"items/weights length mismatch: {i_shape} vs {w_shape}; pad "
            f"the short side with weight-0 entries (the padding convention)")
    if traced:
        return 0
    i = np.asarray(items)
    w = np.asarray(weights)
    if i.dtype.kind not in "iu" or w.dtype.kind not in "iu":
        raise ValueError(
            f"items/weights must be integer arrays (ids and signed counts), "
            f"got dtypes {i.dtype}/{w.dtype}")
    real = w != 0
    if (i[real] < 0).any():
        bad = int(i[real][i[real] < 0][0])
        raise ValueError(
            f"negative item id {bad}: ids must be >= 0 (negative ids are "
            f"the EMPTY/BLOCKED sentinels). To pad a block, keep any id "
            f"and set its weight to 0.")
    int32_max = np.iinfo(np.int32).max
    if (i[real].astype(np.int64) > int32_max).any():
        bad = int(i[real][i[real].astype(np.int64) > int32_max][0])
        raise ValueError(
            f"item id {bad} exceeds int32 (the device-side id dtype); "
            f"hash or re-bucket ids into [0, 2^31) before ingest")
    if np.abs(w.astype(np.int64)).max(initial=0) > int32_max:
        raise ValueError(
            "weights must fit int32 (the device-side count dtype)")
    wsum = int(np.abs(w.astype(np.int64)).sum())
    if wsum > int32_max:
        # a single block whose weight magnitudes sum past int32 could
        # push a counter through _INT_MAX mid-aggregation; the fused
        # cores saturate rather than wrap, but saturation loses mass —
        # reject at the host boundary where the caller can still split.
        raise ValueError(
            f"block weight magnitudes sum to {wsum} > int32 max "
            f"({int32_max}): a single block this heavy could overflow "
            f"the int32 counters (adds saturate, losing mass). Split "
            f"the block or rescale the weights.")
    # per-item cumulative mass vs. near-rail state: a counter already
    # holding up to prior_mass takes this block's NET weight for its
    # item in one merge, so the worst per-item net (not the block sum)
    # is what must still fit under the rail.
    pos_mass = int(w.astype(np.int64).clip(min=0).sum())
    if prior_mass and pos_mass:
        w64 = w[real].astype(np.int64)
        uniq, inv = np.unique(i[real], return_inverse=True)
        net = np.zeros(uniq.size, dtype=np.int64)
        np.add.at(net, inv, w64)
        worst = int(net.max(initial=0))
        if worst > 0 and int(prior_mass) + worst > int32_max:
            bad = int(uniq[int(np.argmax(net))])
            raise ValueError(
                f"item {bad} accumulates net weight {worst} in this "
                f"block while the target state already holds up to "
                f"{int(prior_mass)} positive mass: its counter could "
                f"cross int32 max ({int32_max}) mid-merge (adds "
                f"saturate, losing mass). Split the block, rescale "
                f"weights, or checkpoint-and-reset the session.")
    if spec.kind == "quantile" and spec.tenants is None:
        hi = 1 << spec.bits
        if (i[real] >= hi).any():
            bad = int(i[real][i[real] >= hi][0])
            raise ValueError(
                f"item {bad} is outside the dyadic universe [0, 2^{spec.bits}"
                f"); raise SketchSpec.bits or bucket ids before ingest")
    if spec.tenants is not None:
        hi = spec.tenants << spec.bits
        if (i[real].astype(np.int64) >= hi).any():
            bad = int(i[real][i[real].astype(np.int64) >= hi][0])
            raise ValueError(
                f"composite key {bad} is outside the tenant key space "
                f"[0, {spec.tenants} << {spec.bits}); pack keys with "
                f"tenant.pack_keys(tenant, item, item_bits={spec.bits}) "
                f"and keep items inside [0, 2^{spec.bits})")
    return pos_mass


# ---------------------------------------------------------------------------
# Adapters: the four client layouts behind one protocol
# ---------------------------------------------------------------------------

def _no_rank(spec: SketchSpec):
    raise ValueError(
        f"rank/quantile queries need kind='quantile'; this spec is "
        f"kind={spec.kind!r}. Build a SketchSpec(kind='quantile', "
        f"bits=..., ...) to get the dyadic bank.")


class _FrequencyAdapter:
    """shards=None frequency: the flat (k,) SketchState."""

    def make(self, spec: SketchSpec) -> SketchState:
        return st.init(spec.capacity)

    def update(self, spec, state, items, weights):
        v = spec.variant_id
        if spec.backend == "bank":
            return bk.update_single(state, items, weights, v, spec.bits)
        if spec.backend == "block":
            return blocks.block_update(state, items, weights, v)
        if spec.backend == "serial":
            return blocks.block_update_serial(state, items, weights, v)
        # 'kernel': the fused tiled launch on the flat sketch viewed as a
        # one-row bank (same routing as bank.update_single, so the fused
        # partition path and this stay bit-identical); interpret resolves
        # platform-side (repro.platform) instead of hardcoding True.
        from repro.kernels.sketch_update.ops import sketch_block_update_fused
        from repro.sketch.bank import HashShardRouter

        router = HashShardRouter(1, spec.bits)
        row_items, row_weights = router.route_dense(
            items.astype(jnp.int32), weights.astype(jnp.int32))
        bank1 = jax.tree.map(lambda x: x[None], state)
        out = sketch_block_update_fused(bank1, row_items, row_weights, v)
        return jax.tree.map(lambda x: x[0], out)

    def query_many(self, spec, state, items):
        return st.query_many(state, items)

    def topk(self, spec, state, m):
        return st.topk(state, m)

    def rank_many(self, spec, state, xs):
        _no_rank(spec)

    quantile_many = rank_many

    def merge(self, spec, a, b):
        return st.merge(a, b)

    def consolidate(self, spec, state):
        return state

    def save(self, spec, state) -> Dict[str, Any]:
        return {
            "layout": np.int32(LAYOUT_FREQUENCY),
            "ids": np.asarray(state.ids),
            "counts": np.asarray(state.counts),
            "errors": np.asarray(state.errors),
        }

    def restore(self, spec, d) -> SketchState:
        return _sketch_fields(d)


class _ShardedFrequencyAdapter:
    """shards=S frequency: the hash-partitioned ShardedSketch bank."""

    # spec backend -> sharded.update_block path name
    _PATHS = {"bank": "auto", "block": "vmap", "kernel": "kernel"}

    def make(self, spec: SketchSpec) -> shd.ShardedSketch:
        return shd.init(spec.capacity, spec.shards)

    def update(self, spec, state, items, weights):
        v = spec.variant_id
        if spec.backend == "serial":
            return shd.update_block_serial_reference(
                state, items, weights, v, universe_bits=spec.bits)
        return shd.update_block(state, items, weights, v,
                                universe_bits=spec.bits,
                                path=self._PATHS[spec.backend])

    def query_many(self, spec, state, items):
        return shd.query_many(state, items)

    def topk(self, spec, state, m):
        return shd.topk(state, m)

    def rank_many(self, spec, state, xs):
        _no_rank(spec)

    quantile_many = rank_many

    def merge(self, spec, a, b):
        return shd.merge(a, b)

    def consolidate(self, spec, state):
        return shd.consolidate(state)

    def save(self, spec, state) -> Dict[str, Any]:
        return {
            "layout": np.int32(LAYOUT_FREQUENCY),
            "ids": np.asarray(state.bank.ids),
            "counts": np.asarray(state.bank.counts),
            "errors": np.asarray(state.bank.errors),
            "shards": np.int32(spec.shards),
        }

    def restore(self, spec, d) -> shd.ShardedSketch:
        fields = _sketch_fields(d)
        got = fields.ids.shape[0]
        if got != spec.shards:
            raise ValueError(
                f"checkpoint has {got} shards, spec asks for {spec.shards}; "
                f"restore with a matching spec (or consolidate first)")
        return shd.ShardedSketch(bank=fields)


class _DyadicAdapter:
    """shards=None quantile: the (bits, k) dyadic layer bank."""

    def make(self, spec: SketchSpec) -> dy.DyadicState:
        return dy.init(spec.bits, total_counters=spec.k, eps=spec.eps,
                       alpha=spec.alpha)

    def update(self, spec, state, items, weights):
        return dy.update_block(state, items, weights, spec.variant_id,
                               path=spec.backend)

    def query_many(self, spec, state, items):
        # leaf-layer reads: layer 0 monitors x >> 0 = x itself
        return st.query_many(jax.tree.map(lambda x: x[0], state.bank), items)

    def topk(self, spec, state, m):
        # BLOCKED-aware flat top-k of the leaf row (st.topk would surface
        # the INT_MAX counts of capacity-padding slots)
        return bk.topk_bank(jax.tree.map(lambda x: x[:1], state.bank), m)

    def rank_many(self, spec, state, xs):
        return dy.rank_many(state, xs)

    def quantile_many(self, spec, state, qs):
        return dy.quantile_many(state, qs)

    def merge(self, spec, a, b):
        return dy.merge(a, b)

    def consolidate(self, spec, state):
        return state

    def save(self, spec, state) -> Dict[str, Any]:
        return {
            "layout": np.int32(LAYOUT_QUANTILE),
            "ids": np.asarray(state.bank.ids),
            "counts": np.asarray(state.bank.counts),
            "errors": np.asarray(state.bank.errors),
            "mass": np.int32(state.mass),
        }

    def restore(self, spec, d) -> dy.DyadicState:
        return dy.DyadicState(bank=_sketch_fields(d),
                              mass=jnp.int32(np.asarray(d["mass"])))


class _DyadicShardedAdapter:
    """shards=S quantile: the mesh-distributed shard × level bank."""

    def make(self, spec: SketchSpec) -> dysh.DyadicShardedState:
        return dysh.init(spec.bits, spec.shards, total_counters=spec.k,
                         eps=spec.eps, alpha=spec.alpha)

    def update(self, spec, state, items, weights):
        return dysh.update_block(state, items, weights, spec.variant_id,
                                 path="auto")

    def query_many(self, spec, state, items):
        # leaf-layer reads from each id's owner (shard, level-0) row
        items = items.astype(jnp.int32)
        owner = bk.shard_of(items, state.num_shards)
        leaf = jax.tree.map(lambda x: x[:, 0], state.bank)  # (S, k)
        return bk.query_rows(leaf, owner, items)

    def topk(self, spec, state, m):
        return bk.topk_bank(jax.tree.map(lambda x: x[:, 0], state.bank), m)

    def rank_many(self, spec, state, xs):
        return dysh.rank_many(state, xs)

    def quantile_many(self, spec, state, qs):
        return dysh.quantile_many(state, qs)

    def merge(self, spec, a, b):
        return dysh.merge(a, b)

    def consolidate(self, spec, state):
        return dysh.consolidate(state)

    def save(self, spec, state) -> Dict[str, Any]:
        return {
            "layout": np.int32(LAYOUT_QUANTILE),
            "ids": np.asarray(state.bank.ids),
            "counts": np.asarray(state.bank.counts),
            "errors": np.asarray(state.bank.errors),
            "mass": np.int32(state.mass),
            "shards": np.int32(spec.shards),
        }

    def restore(self, spec, d) -> dysh.DyadicShardedState:
        fields = _sketch_fields(d)
        got = fields.ids.shape[0]
        if got != spec.shards:
            raise ValueError(
                f"checkpoint has {got} shards, spec asks for {spec.shards}; "
                f"restore with a matching spec (or consolidate first)")
        return dysh.DyadicShardedState(
            bank=fields, mass=jnp.int32(np.asarray(d["mass"])))


def _sketch_fields(d) -> SketchState:
    return SketchState(
        ids=jnp.asarray(np.asarray(d["ids"]), jnp.int32),
        counts=jnp.asarray(np.asarray(d["counts"]), jnp.int32),
        errors=jnp.asarray(np.asarray(d["errors"]), jnp.int32),
    )


# registry key: (kind, sharded?, axis, tenants?) — new layouts register
# here instead of teaching every consumer a fifth client module. The
# third axis discriminates same-kind layout families: 'base' is the
# plain SpaceSaving± store, 'double'/'unbiased' the coupled two-bank
# family layouts, 'crprecis' the deterministic linear-counter baseline.
# The fourth discriminates the multi-tenant bank layouts (composite-key
# routing, tenant-major rows — repro.sketch.tenant).
_REGISTRY: Dict[Tuple[str, bool, str, bool], Any] = {}


def spec_axis(spec: SketchSpec) -> str:
    """The registry's layout-family axis of a spec."""
    if spec.backend == "crprecis":
        return "crprecis"
    if spec.variant in FAMILY_VARIANTS:
        return spec.variant
    return "base"


def register_adapter(kind: str, sharded: bool, adapter,
                     axis: str = "base", tenants: bool = False) -> None:
    """Plug a new backend layout into the spec-driven surface."""
    _REGISTRY[(kind, sharded, axis, tenants)] = adapter


def adapter_for(spec: SketchSpec):
    try:
        return _REGISTRY[(spec.kind, spec.shards is not None,
                          spec_axis(spec), spec.tenants is not None)]
    except KeyError:
        raise ValueError(
            f"no adapter registered for kind={spec.kind!r}, "
            f"sharded={spec.shards is not None}, "
            f"axis={spec_axis(spec)!r}, "
            f"tenants={spec.tenants is not None}") from None


register_adapter("frequency", False, _FrequencyAdapter())
register_adapter("frequency", True, _ShardedFrequencyAdapter())
register_adapter("quantile", False, _DyadicAdapter())
register_adapter("quantile", True, _DyadicShardedAdapter())

# the SpaceSaving± family layouts (Double / unbiased SS± + CR-precis)
# live in family.py and register on their own registry axes — imported
# after the registry exists (family.py never imports api at module
# scope, so this is acyclic).
from . import family as _family  # noqa: E402

register_adapter("frequency", False, _family.DoubleAdapter(), axis="double")
register_adapter("frequency", True, _family.DoubleAdapter(), axis="double")
register_adapter("frequency", False, _family.DoubleAdapter(unbiased=True),
                 axis="unbiased")
register_adapter("frequency", True, _family.DoubleAdapter(unbiased=True),
                 axis="unbiased")
register_adapter("frequency", False, _family.CRPrecisAdapter(),
                 axis="crprecis")

# the multi-tenant bank layouts (same acyclic post-registry import):
# base sspm/lazy through TenantAdapter, the family variants through the
# tenant-aware DoubleAdapter — per-tenant rows on BOTH coupled banks.
from . import tenant as _tenant  # noqa: E402

register_adapter("frequency", False, _tenant.TenantAdapter(), tenants=True)
register_adapter("frequency", True, _tenant.TenantAdapter(), tenants=True)
register_adapter("quantile", False, _tenant.TenantLevelsAdapter(),
                 tenants=True)
for _sharded in (False, True):
    register_adapter("frequency", _sharded, _family.DoubleAdapter(),
                     axis="double", tenants=True)
    register_adapter("frequency", _sharded,
                     _family.DoubleAdapter(unbiased=True),
                     axis="unbiased", tenants=True)
del _sharded


# ---------------------------------------------------------------------------
# The uniform functional surface
# ---------------------------------------------------------------------------

def make(spec: SketchSpec):
    """Empty state for ``spec`` (a pure pytree; all ops stay functional)."""
    return adapter_for(spec).make(spec)


def update(spec: SketchSpec, state, items, weights=None, *, path=None):
    """Ingest one block of signed weighted updates; returns the new state.

    ``weights=None`` means all-ones (unit inserts).  Concrete (host)
    inputs are validated against the block conventions
    (``validate_block``); traced inputs pass through — jit ``update``
    freely with ``spec`` static.
    """
    if path is not None:
        warnings.warn(
            "api.update(..., path=...) is deprecated; the execution path "
            "is part of the spec — use dataclasses.replace(spec, "
            "backend=...) instead", DeprecationWarning, stacklevel=2)
        spec = dataclasses.replace(spec, backend=path)
    if weights is None:
        weights = np.ones(np.shape(items), np.int32)
    # validate BEFORE any device cast: jnp.asarray under x64-off would
    # silently truncate 64-bit ids, defeating the checks
    validate_block(spec, items, weights)
    if not isinstance(items, jax.Array):     # device arrays pass through
        items = jnp.asarray(np.asarray(items).astype(np.int32))
    if not isinstance(weights, jax.Array):
        weights = jnp.asarray(np.asarray(weights).astype(np.int32))
    return adapter_for(spec).update(spec, state, items, weights)


def query_many(spec: SketchSpec, state, items) -> jax.Array:
    """Estimated frequency per query id (leaf-layer reads for quantile)."""
    return adapter_for(spec).query_many(spec, state,
                                        jnp.asarray(items, jnp.int32))


def query(spec: SketchSpec, state, item) -> jax.Array:
    return query_many(spec, state, jnp.asarray([item], jnp.int32))[0]


def topk(spec: SketchSpec, state, m: int) -> Tuple[jax.Array, jax.Array]:
    """Top-m (ids, counts) heavy hitters by estimated count.

    On ``tenants=T`` specs the ids are COMPOSITE keys (items of
    different tenants are different keys); per-tenant heavy hitters in
    raw item ids come from :func:`tenant_topk`.
    """
    return adapter_for(spec).topk(spec, state, m)


def tenant_topk(spec: SketchSpec, state, tenant,
                m: int) -> Tuple[jax.Array, jax.Array]:
    """ONE tenant's top-m (raw items, counts); never crosses tenants.

    Only meaningful on multi-tenant specs (``tenants=T``): the answer
    reads the tenant's own row slice and nothing else.
    """
    ad = adapter_for(spec)
    if spec.tenants is None or not hasattr(ad, "topk_tenant"):
        raise ValueError(
            f"tenant_topk needs a multi-tenant spec (tenants=T); this spec "
            f"has tenants={spec.tenants}. Use topk for the global answer.")
    return ad.topk_tenant(spec, state, tenant, m)


def rank_many(spec: SketchSpec, state, xs) -> jax.Array:
    """Estimated rank(x) = |{v <= x}| per query (quantile kinds only)."""
    return adapter_for(spec).rank_many(spec, state,
                                       jnp.asarray(xs, jnp.int32))


def rank(spec: SketchSpec, state, x) -> int:
    return int(rank_many(spec, state, jnp.asarray([x], jnp.int32))[0])


def quantile_many(spec: SketchSpec, state, qs) -> jax.Array:
    """Smallest x with rank(x) >= q·|F|₁ per query (quantile kinds only)."""
    return adapter_for(spec).quantile_many(
        spec, state, jnp.asarray(qs, jnp.float32))


def quantile(spec: SketchSpec, state, q: float) -> int:
    return int(quantile_many(spec, state, jnp.asarray([q], jnp.float32))[0])


def merge(spec: SketchSpec, a, b):
    """Mergeable-summaries merge of two same-spec states (cross-host)."""
    return adapter_for(spec).merge(spec, a, b)


def consolidate(spec: SketchSpec, state):
    """Fold a sharded state into its single-host summary (checkpoint
    compaction); identity for unsharded specs."""
    return adapter_for(spec).consolidate(spec, state)


# ---------------------------------------------------------------------------
# Checkpointing: tagged flat dicts, legacy layouts accepted
# ---------------------------------------------------------------------------

def save(spec: SketchSpec, state) -> Dict[str, Any]:
    """Flat numpy dict (npz/checkpoint-safe) with an integer layout tag.

    The unsharded frequency layout is byte-for-byte the historical
    ``stats._SketchBank.state_dict`` layout plus the tag, so checkpoints
    written through this surface load in old readers and vice versa.
    """
    return adapter_for(spec).save(spec, state)


def infer_spec(spec: SketchSpec, d: Dict[str, Any]) -> SketchSpec:
    """Adapt ``spec``'s layout axes (kind, shards) to a checkpoint dict.

    Pre-redesign dicts carry no tag: kind falls back to the presence of
    ``mass`` (quantile banks always track |F|₁), shardedness to the
    ``shards`` key — exactly the discrimination the old
    ``_SketchBank.load_state_dict`` applied.
    """
    known = {LAYOUT_FREQUENCY: "frequency", LAYOUT_QUANTILE: "quantile",
             LAYOUT_DOUBLE: "double/unbiased family",
             LAYOUT_CRPRECIS: "crprecis"}
    tag = int(np.asarray(d["layout"])) if "layout" in d else None
    if tag is not None and tag not in known:
        raise ValueError(
            f"unknown checkpoint layout tag {tag} (known: "
            f"{ {t: n for t, n in known.items()} }); "
            f"the dict is corrupted or written by a newer layout")
    kind = ("quantile" if tag == LAYOUT_QUANTILE or
            (tag is None and "mass" in d) else "frequency")
    raw_shards = d.get("shards")
    n_shards = int(np.asarray(raw_shards)) if raw_shards is not None else 0
    shards = n_shards or None
    changes: Dict[str, Any] = {}
    if kind != spec.kind:
        changes["kind"] = kind
        if kind == "quantile" and spec.bits is None:
            changes["bits"] = int(np.asarray(d["ids"]).shape[-2])
    if shards != spec.shards:
        changes["shards"] = shards
    raw_tenants = d.get("tenants")
    n_tenants = int(np.asarray(raw_tenants)) if raw_tenants is not None else 0
    tenants = n_tenants or None
    if tenants != spec.tenants:
        changes["tenants"] = tenants
        if spec.tenant_caps is not None:
            # the caps were sized for a different fleet; the restored
            # state carries its own per-row BLOCKED capacity masks, so
            # re-size the spec by the dict's live counters
            changes["tenant_caps"] = None
            changes["k"] = int((np.asarray(d["ids"]) != st.BLOCKED).sum())
        if tenants is not None and spec.bits is None:
            changes["bits"] = int(np.asarray(d["item_bits"]))
    # layout-family axes: the family tag carries which variant wrote it
    # (1 = double, 2 = unbiased); the crprecis tag forces its backend.
    if tag == LAYOUT_DOUBLE:
        want = "unbiased" if int(np.asarray(d.get("family", 1))) == 2 \
            else "double"
        if spec.variant != want:
            changes["variant"] = want
        if spec.backend != "bank":
            changes["backend"] = "bank"
    elif tag == LAYOUT_CRPRECIS:
        if spec.backend != "crprecis":
            changes["backend"] = "crprecis"
        if spec.variant != "sspm":
            changes["variant"] = "sspm"
    else:
        if spec.variant in FAMILY_VARIANTS:
            changes["variant"] = "sspm"
        if spec.backend == "crprecis":
            changes["backend"] = "bank"
    if changes and "backend" not in changes:
        # the stored layout may not support the spec's backend
        probe = dataclasses.replace(spec, **changes, backend="bank")
        if spec.backend not in backends_for(probe.kind, probe.shards,
                                            probe.variant, probe.tenants):
            changes["backend"] = "bank"
    return dataclasses.replace(spec, **changes) if changes else spec


def _validate_checkpoint(spec: SketchSpec, d: Dict[str, Any]) -> None:
    """Reject truncated/corrupted checkpoint dicts BEFORE any state is
    built — ``restore`` either returns a complete state or raises, never
    a half-loaded one.

    Checks: required keys present (``mass`` included for quantile
    kinds), counter fields integer-typed (a float dtype means the dict
    was corrupted or written by something else — casting would silently
    truncate, and NaN poisoning only exists in float arrays), and the
    three counter fields shape-consistent.
    """
    axis = spec_axis(spec)
    if axis == "crprecis":
        # linear counter array: no ids/errors, just counters + moduli
        for key in ("counts", "primes"):
            if key not in d:
                raise ValueError(
                    f"checkpoint dict is missing key {key!r} (truncated "
                    f"write?); a crprecis checkpoint needs counts + primes")
            if np.asarray(d[key]).dtype.kind not in "iu":
                raise ValueError(
                    f"checkpoint field {key!r} has dtype "
                    f"{np.asarray(d[key]).dtype}; crprecis counters and "
                    f"moduli are integer arrays")
        return
    required = ["ids", "counts", "errors"]
    if spec.kind == "quantile":
        required.append("mass")
    triples = [("ids", "counts", "errors")]
    if axis in FAMILY_VARIANTS:
        # the delete-side bank rides along under _del suffixes
        required += ["ids_del", "counts_del", "errors_del"]
        triples.append(("ids_del", "counts_del", "errors_del"))
    missing = [k for k in required if k not in d]
    if missing:
        raise ValueError(
            f"checkpoint dict is missing key(s) {missing} (truncated "
            f"write?); a {spec.kind!r} checkpoint needs {required}")
    for keys in triples:
        shapes = {}
        for key in keys:
            arr = np.asarray(d[key])
            if arr.dtype.kind not in "iu":
                raise ValueError(
                    f"checkpoint field {key!r} has dtype {arr.dtype}; sketch "
                    f"counters are integer arrays — refusing to cast a "
                    f"float/object dtype silently (corrupted or foreign "
                    f"checkpoint)")
            shapes[key] = arr.shape
        if len(set(shapes.values())) != 1:
            raise ValueError(
                f"checkpoint counter fields disagree in shape: {shapes}; the "
                f"dict is truncated or mixes two checkpoints")
    if spec.kind == "quantile":
        mass = np.asarray(d["mass"])
        if mass.dtype.kind not in "iu" or mass.size != (spec.tenants or 1):
            raise ValueError(
                f"checkpoint field 'mass' must hold one integer |F|₁ per "
                f"tenant ({spec.tenants or 1}), got dtype {mass.dtype}, "
                f"shape {mass.shape}")


def restore(spec: SketchSpec, d: Dict[str, Any]):
    """State from a ``save`` dict — or a pre-redesign stats layout.

    The spec must match the dict's layout; use ``infer_spec`` first when
    restoring checkpoints whose shard count / kind may have drifted from
    the configured spec (that is what ``StreamSession.load`` does).
    Truncated or corrupted dicts (missing keys, float dtypes, mismatched
    shapes, unknown layout tags) raise ``ValueError`` before any state
    is constructed — never a half-loaded state.
    """
    inferred = infer_spec(spec, d)
    if (inferred.kind, inferred.shards, spec_axis(inferred),
            inferred.tenants) != \
            (spec.kind, spec.shards, spec_axis(spec), spec.tenants):
        raise ValueError(
            f"checkpoint layout is kind={inferred.kind!r}, "
            f"shards={inferred.shards}, axis={spec_axis(inferred)!r}, "
            f"tenants={inferred.tenants}, but the spec says "
            f"kind={spec.kind!r}, shards={spec.shards}, "
            f"axis={spec_axis(spec)!r}, tenants={spec.tenants}; restore "
            f"through infer_spec(spec, d) (StreamSession.load does)")
    _validate_checkpoint(spec, d)
    return adapter_for(spec).restore(spec, d)


# ---------------------------------------------------------------------------
# Deprecation plumbing shared by the per-client shims
# ---------------------------------------------------------------------------

def deprecated_alias(old: str, new: str, fn):
    """Wrap ``fn`` so calls through the OLD spelling warn once per name.

    The wrapper forwards verbatim (``__wrapped__`` pins identity in
    tests) — old call sites keep the same objects and semantics, they
    just learn where the one canonical spelling lives now.
    """
    warned = []

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if not warned:
            warned.append(True)
            warnings.warn(
                f"{old} is deprecated; use {new} (the spec-driven "
                f"repro.sketch.api surface)", DeprecationWarning,
                stacklevel=2)
        return fn(*args, **kwargs)

    return wrapper


__all__ = [
    "KINDS",
    "VARIANTS",
    "FAMILY_VARIANTS",
    "BACKENDS",
    "LAYOUT_FREQUENCY",
    "LAYOUT_QUANTILE",
    "LAYOUT_DOUBLE",
    "LAYOUT_CRPRECIS",
    "SketchSpec",
    "backends_for",
    "variants_for",
    "spec_axis",
    "validate_block",
    "register_adapter",
    "adapter_for",
    "make",
    "update",
    "query",
    "query_many",
    "topk",
    "tenant_topk",
    "rank",
    "rank_many",
    "quantile",
    "quantile_many",
    "merge",
    "consolidate",
    "save",
    "infer_spec",
    "restore",
    "deprecated_alias",
]
