"""Pallas TPU kernels: SpaceSaving± block update over a VMEM counter store.

TPU adaptation of the paper's §3.6 low-latency structure (see DESIGN.md §3):
the (ids, counts, errors) arrays live in VMEM laid out (R, 128) —
rows × lanes — and minCount / maxError are vectorized reductions over
all k = R*128 counters instead of heap operations. The whole block of B
updates is applied in one kernel launch: one HBM round-trip for the state
per *block*, not per update.

Four kernels live here:

``sketch_update_kernel_fused`` — the production path (DESIGN.md §14):
ONE tiled launch per block covering the whole stacked (R, K) bank. The
grid runs over row tiles; each grid step holds a (row_tile, K) state
tile and a (row_tile, B) stream tile in VMEM (double-buffered by the
grid pipeline) and fuses the saturating phase-1 scatter, the bulk
empty fill, the unit-weight water-fill and the lockstep residual
tournament. Phase-1 *prep* (sorts, match census — reads only ids,
does not lower in Mosaic) stays in XLA via ``bank.phase1_dense_prep``
and feeds the kernel a per-cell delta; prep + launch trace as one jit
program. The tile body is written in what Mosaic lowers (lane rolls,
compares, selects, lane reductions); it compiles for TPU v5e
(tests/test_tpu_compile.py). Row independence + active-mask freezing
make any row_tile bit-identical to the engine oracle
``bank.update_block_fused``.

The other three kernels below run in interpret mode only: Mosaic
refuses ``dynamic_slice`` (``sketch_residual_kernel``), one-dimensional
gathers (``sketch_residual_kernel_banked``) and the serial kernel's
non-float32 reductions. No user spec selects them.

``sketch_residual_kernel`` — the two-phase split path's phase 2. The
wrapper (ops.py) segment-aggregates the block and scatter-adds all
monitored deltas in one vectorized pass (they commute); only the residual
— unmonitored inserts and unmonitored SS± deletions — enters this kernel.
The loop is a dynamic-trip-count while over ``n_res`` residual uniques,
each step an O(R + LANES) two-level row tournament (per-row min/max
summaries updated incrementally, (R,)-wide final reduce) instead of a flat
O(k) argmin/argmax. The body is shared with the pure-JAX layer
(``repro.sketch.phases.residual_phase``) so the two paths are
bit-identical.

``sketch_residual_kernel_banked`` — the whole-bank variant: ONE launch
covers a stacked (R, K) bank (dyadic layers, hash shards, shard × level
rows). The wrapper runs the engine's dense phase 1
(``repro.sketch.bank.phase1_dense``) and this kernel runs every row's
residual loop in lockstep via the shared ``bank.residual_phase_banked``
body — flat per-row argmin/argmax with one-hot where-mask updates, no
batched scatters — so the kernel path is bit-identical to the pure-JAX
banked path by construction.

``sketch_update_kernel_serial`` — the pre-two-phase baseline: a serial
fori_loop over all B raw updates, each with flat O(k) reductions. Kept for
A/B benchmarking (bench_kernels reports the speedup) and as a second
reference implementation.

Weights are signed: w > 0 weighted insert, w < 0 weighted delete
(variant: 1 = Lazy SS± Alg 3 / 2 = SS± Alg 4), w = 0 no-op (padding).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.platform import resolve_interpret
from repro.sketch.phases import residual_phase
from repro.sketch.state import LANES, VARIANT_LAZY, sat_add

_INT_MAX = 2**31 - 1  # python ints: pallas kernels must not close over arrays
EMPTY = -1


# ---------------------------------------------------------------------------
# Fused tiled kernel: phases 1-2 on VMEM-resident (row_tile, K) tiles
# ---------------------------------------------------------------------------
# Mosaic lowers none of cumsum, one-dimensional gathers, int32
# argmin/argmax or a flattening reshape, so the tile body below spells
# the engine's phases with what it does lower: lane rolls
# (``pltpu.roll``), compares, selects and lane reductions. Every row of a
# tile reads its own (B,) stream row, so no index ever crosses rows.


def _lanes(shape) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _roll_left(x, s: int):
    """Lane j reads lane (j + s) mod L (a static circular shift)."""
    L = x.shape[1]
    return pltpu.roll(x, (L - s) % L, 1)


def _roll_right(x, s: int):
    """Lane j reads lane (j - s) mod L."""
    return pltpu.roll(x, s % x.shape[1], 1)


def _excl_count(mask) -> jax.Array:
    """Per lane: how many lanes before it (same row) hold ``mask``.

    The exclusive prefix sum by log-step shifted adds (``cumsum`` does
    not lower in Mosaic).
    """
    x = mask.astype(jnp.int32)
    lane = _lanes(x.shape)
    c = x
    s = 1
    while s < x.shape[1]:
        c = c + jnp.where(lane >= s, _roll_right(c, s), 0)
        s *= 2
    return c - x


def _shift_left_rows(x, amt):
    """Row r of ``x`` read from lane ``j + amt[r]`` (circular).

    ``amt`` is an (RT, 1) column in [0, L]; one select per bit of it.
    """
    L = x.shape[1]
    b = 1
    while b < L:
        x = jnp.where((amt & b) != 0, _roll_left(x, b), x)
        b *= 2
    return x


def _expand(vals, sel, width: int):
    """Spread consecutive values over the set lanes of ``sel``.

    ``vals``: (RT, L) arrays whose lane n holds the n-th value; ``sel``:
    (RT, width) mask. Returns, per array, an (RT, width) array whose
    lane j holds value number ``#(sel before j)`` wherever ``sel[j]``
    (anything elsewhere): the gather ``v[excl_count(sel)]`` without a
    gather. The n-th value moves right by ``d_n`` = the unset lanes
    before its target, which never decreases in n, so log-step moves
    never collide: ``d`` is first carried from the targets back to the
    sources (left moves, low bit first), then the values move right
    (high bit first).
    """
    L = vals[0].shape[1]
    if L >= width:
        vals = [v[:, :width] for v in vals]
    else:
        vals = [jnp.concatenate(
            [v, jnp.zeros((v.shape[0], width - L), v.dtype)], axis=1)
            for v in vals]
    sel = sel.astype(jnp.int32)
    d = _excl_count(1 - sel)            # unset lanes before each lane
    bits = []
    b = 1
    while b < width:
        bits.append(b)
        b *= 2
    live = sel                          # carry d from targets to sources
    for b in bits:
        move = live * ((d & b) != 0)
        inc = _roll_left(move, b)
        d = jnp.where(inc != 0, _roll_left(d, b), d)
        live = inc + live * (1 - move)
    # live now marks the first #sel lanes (the sources), each holding d
    for b in reversed(bits):
        move = live * ((d & b) != 0)
        inc = _roll_right(move, b)
        vals = [jnp.where(inc != 0, _roll_right(v, b), v) for v in vals]
        d = jnp.where(inc != 0, _roll_right(d, b), d)
        live = inc + live * (1 - move)
    return vals


def _argmin_first(x, lane):
    """(min, first lane holding it) per row, both (RT, 1)."""
    m = jnp.min(x, axis=1, keepdims=True)
    return m, jnp.min(jnp.where(x == m, lane, x.shape[1]), axis=1,
                      keepdims=True)


def _argmax_first(x, lane):
    m = jnp.max(x, axis=1, keepdims=True)
    return m, jnp.min(jnp.where(x == m, lane, x.shape[1]), axis=1,
                      keepdims=True)


def _any(mask) -> jax.Array:
    """Scalar: does any row of an (RT, 1) mask hold? Reduced over a
    lane-dense broadcast, so no padding lane of the column can vote."""
    m = jnp.broadcast_to(mask.astype(jnp.int32), (mask.shape[0], LANES))
    return jnp.max(m) > 0


def _fill_tile(ids, counts, errors, uids, nets, i0, off):
    """Phase 1.5 (``phases.fill_empty_slots``): the j-th empty slot takes
    the j-th consumed insert, at stream lanes ``off, off + 1, ...``."""
    empty = ids == -1
    take = empty & (_excl_count(empty) < i0)
    u, n = _expand([_shift_left_rows(uids, off), _shift_left_rows(nets, off)],
                   empty, ids.shape[1])
    return (jnp.where(take, u, ids), jnp.where(take, n, counts),
            jnp.where(take, 0, errors))


def _waterfill_tile(ids, counts, errors, uu, m):
    """Phase 1.75 (``phases.waterfill_unit_inserts``) for a tile of rows:
    the same water level, pop counts and pop positions, with the two
    position runs read by ``_expand`` instead of a gather."""
    B = uu.shape[1]

    def n_leq(x):
        d = jnp.clip(sat_add(x, jnp.negative(counts)), 0, m)
        return jnp.where(counts <= x, d + 1, 0)

    lo = jnp.min(counts, axis=1, keepdims=True)
    hi = sat_add(lo, m)

    def probe(_, lh):
        lo, hi = lh
        mid = sat_add(lo, sat_add(hi, jnp.negative(lo)) // 2)
        ge = jnp.sum(n_leq(mid), axis=1, keepdims=True) >= m
        return jnp.where(ge, lo, sat_add(mid, 1)), jnp.where(ge, mid, hi)

    T, _ = jax.lax.fori_loop(0, B.bit_length() + 1, probe, (lo, hi))
    f_tm1 = jnp.sum(n_leq(T - 1), axis=1, keepdims=True)
    r = m - f_tm1
    elig = counts <= T
    extra = elig & (_excl_count(elig) < r)
    t = jnp.where(counts <= T - 1,
                  jnp.clip(sat_add(T, jnp.negative(counts)), 0, m), 0) \
        + extra.astype(jnp.int32)
    evicted = t > 0
    new_counts = sat_add(counts, t)
    f_tm2 = jnp.sum(jnp.where(counts < T - 1,
                              jnp.clip(sat_add(T - 1, jnp.negative(counts)),
                                       0, m), 0), axis=1, keepdims=True)
    under = counts <= T - 1
    K = ids.shape[1]
    (at_line,) = _expand([_shift_left_rows(uu, f_tm2)], under, K)
    (at_extra,) = _expand([_shift_left_rows(uu, f_tm1)], elig, K)
    new_ids = jnp.where(extra, at_extra, jnp.where(evicted, at_line, ids))
    return (new_ids, new_counts,
            jnp.where(evicted, new_counts - 1, errors))


def _residual_tile(ids2, cnt2, err2, uids, nets, start, n_ins, w_del,
                   variant: int):
    """Phase 2 (``bank.residual_phase_banked``) for a tile of rows: the
    row's i-th insert is read by a lane one-hot over its stream row, and
    the min/max slot is the first lane equal to the row's min/max."""
    RT, K = ids2.shape
    B = uids.shape[1]
    lane = _lanes((RT, K))
    lane_b = _lanes((RT, B))

    def ins_cond(carry):
        return _any(carry[0] < n_ins)

    def ins_step(carry):
        i, ids2, cnt2, err2 = carry
        active = i < n_ins
        at = lane_b == jnp.clip(i, 0, B - 1)
        uid = jnp.sum(jnp.where(at, uids, 0), axis=1, keepdims=True)
        w = jnp.sum(jnp.where(at, nets, 0), axis=1, keepdims=True)
        mc, sel = _argmin_first(cnt2, lane)
        hot = (lane == sel) & active
        return (
            i + active.astype(jnp.int32),
            jnp.where(hot, uid, ids2),
            jnp.where(hot, sat_add(mc, w), cnt2),
            jnp.where(hot, mc, err2),
        )

    _, ids2, cnt2, err2 = jax.lax.while_loop(
        ins_cond, ins_step, (start, ids2, cnt2, err2))

    if variant != VARIANT_LAZY:
        def sp_cond(carry):
            rem, _, _, _, maxe = carry
            return _any((rem > 0) & (maxe > 0))

        def sp_step(carry):
            rem, cnt2, err2, sel, maxe = carry
            active = (rem > 0) & (maxe > 0)
            d = jnp.where(active, jnp.minimum(rem, maxe), 0)
            hot = (lane == sel) & active
            nd = jnp.negative(d)
            cnt2 = jnp.where(hot, sat_add(cnt2, nd), cnt2)
            err2 = jnp.where(hot, sat_add(err2, nd), err2)
            maxe, sel = _argmax_first(err2, lane)
            return sat_add(rem, nd), cnt2, err2, sel, maxe

        maxe0, sel0 = _argmax_first(err2, lane)
        _, cnt2, err2, _, _ = jax.lax.while_loop(
            sp_cond, sp_step, (w_del, cnt2, err2, sel0, maxe0))
    return ids2, cnt2, err2


def _fused_kernel_tile(scalars_ref, uids_ref, nets_ref, delta_ref, ids_ref,
                       counts_ref, errors_ref, ids_out, counts_out,
                       errors_out, *, variant: int):
    """One grid step: the whole update pipeline for a tile of bank rows.

    The XLA prep (``bank.phase1_dense_prep``: sorts, searchsorted
    matching, grouping) hands this kernel per-row tensors; every row's
    stream row is laid out [units | non-units | consumed-by-fill | rest]
    from lane 0. Everything per *cell* happens here on the VMEM-resident
    tile in one launch:

      phase 1    saturating scatter of the monitored delta;
      phase 1.5  bulk empty fill;
      phase 1.75 unit-weight water-fill;
      phase 2    the lockstep residual tournament and deletion spread.

    The arithmetic is the engine's (``phases`` and
    ``bank.residual_phase_banked``) step for step, so results are
    bit-identical to ``bank.update_rows``. Row independence makes tiling
    exact: each row's result never reads another row, and the lockstep
    loops' extra trips are frozen no-ops for finished rows.
    """
    # scalars = (RT, 4) columns [i0, mu, nnu, w_del] for this tile's rows
    sc = scalars_ref[...]
    i0, mu, nnu, w_del = (sc[:, c:c + 1] for c in range(4))
    uids = uids_ref[...]
    nets = nets_ref[...]
    ids = ids_ref[...]
    counts = sat_add(counts_ref[...], delta_ref[...])
    errors = errors_ref[...]
    ids, counts, errors = _fill_tile(ids, counts, errors, uids, nets, i0,
                                     mu + nnu)
    ids, counts, errors = _waterfill_tile(ids, counts, errors, uids, mu)
    ids, counts, errors = _residual_tile(ids, counts, errors, uids, nets,
                                         mu, mu + nnu, w_del, variant)
    ids_out[...] = ids
    counts_out[...] = counts
    errors_out[...] = errors


def choose_row_tile(num_rows: int, k_pad: int, block: int,
                    budget_bytes: int) -> int:
    """Largest row tile that divides ``num_rows``, fits the VMEM budget
    and meets the TPU tiling rule (a multiple of 8, or all rows).

    Per grid step, counted in int32 words per row: the in/out state and
    delta tiles double-buffered by the pipeline (14 x K), the stream tile
    (uids + nets, double-buffered: 4 x B) and the body's live
    temporaries (about 12 x K + 6 x B). The budget is half of VMEM
    (repro.platform.vmem_budget_bytes). ``num_rows`` must be a multiple
    of 8 unless it fits in one tile; ``sketch_update_kernel_fused`` pads
    rows to make it so.
    """
    bytes_per_row = 4 * (26 * k_pad + 10 * block)
    fit = max(1, budget_bytes // bytes_per_row)
    if num_rows <= fit:
        return num_rows
    rt = (min(fit, num_rows) // 8) * 8
    while rt >= 8:
        if num_rows % rt == 0:
            return rt
        rt -= 8
    return 8


def sketch_update_kernel_fused(
    ids: jax.Array,      # (R, K) int32 bank, K a multiple of LANES
    counts: jax.Array,   # (R, K) int32 (padding slots inert: BLOCKED ids)
    errors: jax.Array,   # (R, K) int32
    delta: jax.Array,    # (R, K) int32 monitored phase-1 addend (prep)
    h_uids: jax.Array,   # (R, B) int32 grouped residual layout per row
    h_net: jax.Array,    # (R, B) int32 net weights aligned with h_uids
    i0: jax.Array,       # (R,) int32 inserts consumed by the bulk fill
    mu: jax.Array,       # (R,) int32 unit-weight insert count per row
    nnu: jax.Array,      # (R,) int32 non-unit insert count per row
    w_del: jax.Array,    # (R,) int32 summed unmonitored deletions per row
    *,
    variant: int = 2,
    interpret: Optional[bool] = None,
    row_tile: int | None = None,
):
    """ONE ``pallas_call`` for the whole bank update: grid over row
    tiles, phases 1-2 fused per tile.

    Replaces the split path (phase 1 applied in XLA + a separate
    residual-only launch): the state makes one HBM round trip per block
    instead of two, and the grid pipeline streams the next tile's
    operands into VMEM while the current tile updates (Mosaic's
    ``emit_pipeline`` two-slot copy machinery — see DESIGN.md §14).
    ``row_tile`` must divide R and be a multiple of 8 or R itself; None
    picks the largest such tile fitting the platform VMEM budget
    (``choose_row_tile``). A bank of more than one tile whose R is not a
    multiple of 8 gets inert BLOCKED rows appended for the launch.
    """
    assert ids.ndim == 2 and ids.shape[1] % LANES == 0, ids.shape
    R, K = ids.shape
    B = h_uids.shape[1]
    scalars = jnp.stack([i0, mu, nnu, w_del], axis=1).astype(jnp.int32)
    if row_tile is None:
        from repro.platform import vmem_budget_bytes

        row_tile = choose_row_tile(-(-R // 8) * 8, K, B,
                                   vmem_budget_bytes())
        if row_tile >= R:
            row_tile = R
    pad = (-R) % row_tile
    if pad:
        # inert rows: BLOCKED slots, an all-zero stream, zero scalars
        rows = lambda x, v: jnp.pad(x, ((0, pad), (0, 0)), constant_values=v)
        ids, counts, errors = (rows(ids, -2), rows(counts, _INT_MAX),
                               rows(errors, 0))
        delta, h_uids, h_net, scalars = (rows(delta, 0), rows(h_uids, 0),
                                         rows(h_net, 0), rows(scalars, 0))
    Rp = R + pad
    interpret = resolve_interpret(interpret)
    if not interpret and row_tile != Rp and row_tile % 8:
        raise ValueError(
            f"row_tile={row_tile} breaks the TPU tiling rule: a tile of "
            f"rows is a multiple of 8 or all {Rp} rows")
    grid = (Rp // row_tile,)
    out_shape = [jax.ShapeDtypeStruct((Rp, K), jnp.int32)] * 3
    kern = functools.partial(_fused_kernel_tile, variant=variant)
    state_spec = pl.BlockSpec((row_tile, K), lambda i: (i, 0))
    stream_spec = pl.BlockSpec((row_tile, B), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((row_tile, 4), lambda i: (i, 0))
    out = pl.pallas_call(
        kern,
        grid=grid,
        out_shape=out_shape,
        in_specs=[scalar_spec, stream_spec, stream_spec,
                  state_spec, state_spec, state_spec, state_spec],
        out_specs=[state_spec] * 3,
        input_output_aliases={4: 0, 5: 1, 6: 2},  # state updated in place
        interpret=interpret,
    )(scalars, h_uids, h_net, delta, ids, counts, errors)
    return [x[:R] for x in out] if pad else out


# ---------------------------------------------------------------------------
# Two-phase path, phase 2: residual tournament loop
# ---------------------------------------------------------------------------

def _residual_kernel(scalars_ref, uids_ref, nets_ref, ids_ref, counts_ref,
                     errors_ref, ids_out, counts_out, errors_out, *,
                     variant: int):
    # scalars = [start, end, w_del]: the non-unit eviction range in the
    # grouped residual list (empty fills and the unit-weight water-fill
    # ran outside the kernel) and the summed unmonitored deletion weight
    # for the bulk spread.
    ids, counts, errors = residual_phase(
        ids_ref[...], counts_ref[...], errors_ref[...],
        uids_ref[...], nets_ref[...],
        scalars_ref[0], scalars_ref[1], scalars_ref[2], variant,
    )
    ids_out[...] = ids
    counts_out[...] = counts
    errors_out[...] = errors


def sketch_residual_kernel(
    ids: jax.Array,      # (R, 128) int32, phases 1-1.75 already applied
    counts: jax.Array,   # (R, 128) int32
    errors: jax.Array,   # (R, 128) int32
    r_uids: jax.Array,   # (B,) int32 grouped residual uniques (see _phase1)
    r_net: jax.Array,    # (B,) int32 net weights aligned with r_uids
    start: jax.Array,    # () int32 first non-unit insert (loop start)
    n_ins: jax.Array,    # () int32 end of the non-unit insert range
    w_del: jax.Array,    # () int32 summed unmonitored deletion weight
    *,
    variant: int = 2,
    interpret: Optional[bool] = None,
):
    assert ids.ndim == 2 and ids.shape[1] == LANES, ids.shape
    B = r_uids.shape[0]
    R = ids.shape[0]
    out_shape = [jax.ShapeDtypeStruct((R, LANES), jnp.int32)] * 3
    kern = functools.partial(_residual_kernel, variant=variant)
    state_spec = pl.BlockSpec((R, LANES), lambda: (0, 0))
    upd_spec = pl.BlockSpec((B,), lambda: (0,))
    scalar_spec = pl.BlockSpec((3,), lambda: (0,))
    scalars = jnp.stack([start.astype(jnp.int32), n_ins.astype(jnp.int32),
                         w_del.astype(jnp.int32)])
    return pl.pallas_call(
        kern,
        out_shape=out_shape,
        in_specs=[scalar_spec, upd_spec, upd_spec,
                  state_spec, state_spec, state_spec],
        out_specs=[state_spec] * 3,
        input_output_aliases={3: 0, 4: 1, 5: 2},  # state updated in place
        interpret=resolve_interpret(interpret),
    )(scalars, r_uids, r_net, ids, counts, errors)


# ---------------------------------------------------------------------------
# Banked residual kernel: every bank row's phase 2 in one launch
# ---------------------------------------------------------------------------

def _residual_kernel_banked(scalars_ref, uids_ref, nets_ref, ids_ref,
                            counts_ref, errors_ref, ids_out, counts_out,
                            errors_out, *, variant: int):
    # scalars = (4, R) rows [uoff, start, n_ins, w_del]: each bank row's
    # grouped-layout offset, non-unit eviction range and summed
    # unmonitored deletion weight. The body is the engine's banked loop,
    # shared verbatim (it closes over no arrays).
    from repro.sketch.bank import residual_phase_banked

    ids, counts, errors = residual_phase_banked(
        ids_ref[...], counts_ref[...], errors_ref[...],
        uids_ref[...], nets_ref[...],
        scalars_ref[0], scalars_ref[1], scalars_ref[2], scalars_ref[3],
        variant,
    )
    ids_out[...] = ids
    counts_out[...] = counts
    errors_out[...] = errors


def sketch_residual_kernel_banked(
    ids: jax.Array,      # (R, K) int32 bank, phases 1-1.75 applied,
    counts: jax.Array,   #        K a multiple of LANES (padded inert)
    errors: jax.Array,
    h_uids: jax.Array,   # (G,) int32 flattened grouped residual layout
    h_net: jax.Array,    # (G,) int32 net weights aligned with h_uids
    uoff: jax.Array,     # (R,) int32 row offsets into the grouped layout
    start: jax.Array,    # (R,) int32 first non-unit insert per row
    n_ins: jax.Array,    # (R,) int32 end of the non-unit range per row
    w_del: jax.Array,    # (R,) int32 summed unmonitored deletions per row
    *,
    variant: int = 2,
    interpret: Optional[bool] = None,
):
    assert ids.ndim == 2 and ids.shape[1] % LANES == 0, ids.shape
    R, K = ids.shape
    G = h_uids.shape[0]
    out_shape = [jax.ShapeDtypeStruct((R, K), jnp.int32)] * 3
    kern = functools.partial(_residual_kernel_banked, variant=variant)
    state_spec = pl.BlockSpec((R, K), lambda: (0, 0))
    upd_spec = pl.BlockSpec((G,), lambda: (0,))
    scalar_spec = pl.BlockSpec((4, R), lambda: (0, 0))
    scalars = jnp.stack([uoff.astype(jnp.int32), start.astype(jnp.int32),
                         n_ins.astype(jnp.int32), w_del.astype(jnp.int32)])
    return pl.pallas_call(
        kern,
        out_shape=out_shape,
        in_specs=[scalar_spec, upd_spec, upd_spec,
                  state_spec, state_spec, state_spec],
        out_specs=[state_spec] * 3,
        input_output_aliases={3: 0, 4: 1, 5: 2},  # state updated in place
        interpret=resolve_interpret(interpret),
    )(scalars, h_uids, h_net, ids, counts, errors)


# ---------------------------------------------------------------------------
# Serial baseline: one flat-reduce step per raw update
# ---------------------------------------------------------------------------

def _apply_one(ids, counts, errors, item, w, variant: int):
    """Branchless weighted SpaceSaving± update on (R,128) arrays."""
    # ---- insert path (w > 0) ------------------------------------------
    wi = jnp.maximum(w, 0)
    # sentinel slots (EMPTY/BLOCKED, both negative) never match: an
    # id-(-1) update must not resurrect an empty slot's garbage count
    eq = (ids == item) & (ids >= 0)
    monitored = eq.any()
    # flat argmin/argmax over the 2D store (row-major == 1D semantics)
    flat_eq = eq.reshape(-1)
    slot_mon = jnp.argmax(flat_eq)

    empty = ids == EMPTY
    has_empty = empty.any()
    slot_empty = jnp.argmax(empty.reshape(-1))

    cnt_for_min = jnp.where(empty, _INT_MAX, counts)
    jmin = jnp.argmin(cnt_for_min.reshape(-1))
    min_count = cnt_for_min.reshape(-1)[jmin]

    sel_i = jnp.where(monitored, slot_mon, jnp.where(has_empty, slot_empty, jmin))
    cnt_mon = counts.reshape(-1)[slot_mon]
    err_mon = errors.reshape(-1)[slot_mon]
    new_cnt_i = jnp.where(monitored, cnt_mon + wi, jnp.where(has_empty, wi, min_count + wi))
    new_err_i = jnp.where(monitored, err_mon, jnp.where(has_empty, 0, min_count))

    ids_i = ids.reshape(-1).at[sel_i].set(item).reshape(ids.shape)
    counts_i = counts.reshape(-1).at[sel_i].set(new_cnt_i).reshape(counts.shape)
    errors_i = errors.reshape(-1).at[sel_i].set(new_err_i).reshape(errors.shape)

    # ---- delete path (w < 0) ------------------------------------------
    wd = jnp.maximum(-w, 0)
    cnt_d = counts.reshape(-1).at[slot_mon].add(jnp.where(monitored, -wd, 0)).reshape(counts.shape)

    if variant == 1:  # Lazy: ignore unmonitored deletions
        counts_d, errors_d = cnt_d, errors
    else:  # SS±: spread over max-error items
        def cond(carry):
            rem, _, errs = carry
            return (rem > 0) & (errs.max() > 0)

        def body(carry):
            rem, cnts, errs = carry
            jerr = jnp.argmax(errs.reshape(-1))
            max_err = errs.reshape(-1)[jerr]
            d = jnp.minimum(rem, max_err)
            cnts = cnts.reshape(-1).at[jerr].add(-d).reshape(cnts.shape)
            errs = errs.reshape(-1).at[jerr].add(-d).reshape(errs.shape)
            return rem - d, cnts, errs

        rem0 = jnp.where(monitored, 0, wd)
        _, counts_d, errors_d = jax.lax.while_loop(cond, body, (rem0, cnt_d, errors))

    # ---- select by sign -------------------------------------------------
    is_ins = w > 0
    is_del = w < 0
    ids_out = jnp.where(is_ins, ids_i, ids)
    counts_out = jnp.where(is_ins, counts_i, jnp.where(is_del, counts_d, counts))
    errors_out = jnp.where(is_ins, errors_i, jnp.where(is_del, errors_d, errors))
    return ids_out, counts_out, errors_out


def _serial_kernel(items_ref, weights_ref, ids_ref, counts_ref, errors_ref,
                   ids_out, counts_out, errors_out, *, variant: int, block: int):
    # Load the counter store into registers/VMEM once per block.
    def body(i, carry):
        ids, counts, errors = carry
        item = items_ref[i]
        w = weights_ref[i]
        return _apply_one(ids, counts, errors, item, w, variant)

    ids, counts, errors = jax.lax.fori_loop(
        0, block, body, (ids_ref[...], counts_ref[...], errors_ref[...])
    )
    ids_out[...] = ids
    counts_out[...] = counts
    errors_out[...] = errors


def sketch_update_kernel_serial(
    ids: jax.Array,      # (R, 128) int32
    counts: jax.Array,   # (R, 128) int32
    errors: jax.Array,   # (R, 128) int32
    items: jax.Array,    # (B,) int32
    weights: jax.Array,  # (B,) int32 signed
    *,
    variant: int = 2,
    interpret: Optional[bool] = None,
):
    assert ids.ndim == 2 and ids.shape[1] == LANES, ids.shape
    B = items.shape[0]
    R = ids.shape[0]
    out_shape = [jax.ShapeDtypeStruct((R, LANES), jnp.int32)] * 3
    kern = functools.partial(_serial_kernel, variant=variant, block=B)
    state_spec = pl.BlockSpec((R, LANES), lambda: (0, 0))
    upd_spec = pl.BlockSpec((B,), lambda: (0,))
    return pl.pallas_call(
        kern,
        out_shape=out_shape,
        in_specs=[upd_spec, upd_spec, state_spec, state_spec, state_spec],
        out_specs=[state_spec] * 3,
        input_output_aliases={2: 0, 3: 1, 4: 2},  # state updated in place
        interpret=resolve_interpret(interpret),
    )(items, weights, ids, counts, errors)
