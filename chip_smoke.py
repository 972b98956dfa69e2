#!/usr/bin/env python3
"""Bring-up smoke run of the multi-tenant sketch service on a TPU.

    python chip_smoke.py             # one chip: the served path
    python chip_smoke.py --chips 4   # four chips: sharded ingest on a mesh
    python chip_smoke.py --kernels   # one chip: the Pallas kernel paths

One chip (the default). ``serve.SketchService`` over
``SketchSpec(kind="frequency", k=16384*2048, bits=17, tenants=16384)``:
16,384 tenants x 2,048 counters, 384 MiB of int32 state on the device,
donated into every ingest. It replays a seeded
``benchmarks.common.mixed_traffic`` day (zipf-sized tenants, ~1M
inserts, delete ratio 0.5, ~10% query probes) with top-k subscriptions
for the 8 largest tenants, ticking as ``benchmarks.bench_service._replay``
does, so idle tenants spill and re-admit. Mid-day, one block's worth of
inserts reaches a few thousand tenants at once, so the touched-rows
ingest runs both one chunk and many per block. Checks, on the 8
largest tenants (which overflow their counters) and 8 random ones:

  * bit identity: each tenant row equals ``tenant.reference_row_update``
    replayed on the CPU over the recorded blocks (re-admissions applied
    where the service made them);
  * the SS+- bound: every estimate is within 2*alpha*|F|_1/k of the
    exact count (alpha = I/(I-D) of that tenant, the constant
    ``core.capacity_for`` uses), and every item above that bound is
    monitored in the tenant's row. (An item exactly at the bound may be
    unmonitored: its estimate 0 is then off by exactly the bound.)

Four chips (``--chips 4``). A hash-sharded ``SketchSpec(shards=16)``
ingested through ``api.update`` under ``parallel.sharding.use_mesh`` over
4 devices (so ``path='auto'`` takes the shard_map path) must equal the
one-device ``path='block'`` ingest of the same blocks bit for bit, and
its bank must span all 4 devices.

Kernels (``--kernels``). The fused Pallas kernel, compiled for the
chip, must equal the bank engine bit for bit at R in {16, 256} rows, K
in {1024, 4096} counters and B = 8192, on full banks; so must every
``backend='kernel'`` spec path against ``backend='bank'``.

Lines before the last report set-up seconds, counts and each check. The
last line is one JSON object naming the device. No TPU, or any failed
phase, exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def require_tpu(n: int):
    """The first ``n`` TPU devices; exits when JAX finds fewer."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        fail("no TPU: JAX_PLATFORMS=cpu holds JAX to the CPU")
    import jax

    try:
        devs = jax.devices("tpu")
    except RuntimeError as e:
        fail(f"no TPU found: {e}")
    if len(devs) < n:
        fail(f"{n} TPU devices needed, {len(devs)} found")
    return devs[:n]


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# One chip: the served path
# ---------------------------------------------------------------------------

def _exact(ops, tenants):
    """Exact per-tenant counts, inserted mass I and deleted mass D."""
    from benchmarks.common import UNIVERSE

    out = {t: [np.zeros(UNIVERSE, np.int64), 0, 0] for t in tenants}
    for op in ops:
        if op[0] == "update" and op[1] in out:
            f = out[op[1]]
            np.add.at(f[0], op[2], op[3])
            w = op[3].astype(np.int64)
            f[1] += int(w[w > 0].sum())
            f[2] += int(-w[w < 0].sum())
    return out


def _reference_rows(svc, spec, tenants, k):
    """Each tenant's row replayed on the CPU over the recorded blocks,
    with the service's re-admissions applied at the same points."""
    import jax
    import jax.numpy as jnp

    from repro.sketch import bank as bk
    from repro.sketch import tenant as tn

    cpu = jax.devices("cpu")[0]
    router = bk.TenantRouter(spec.tenants, spec.bits, 1)
    variant = spec.variant_id

    @jax.jit
    def step(row, ci, cw, r):
        return tn.reference_row_update(row, ci, cw, router, r, variant)

    @jax.jit
    def readmit(row):
        one = jax.tree.map(lambda x: x[None], row)
        rows = jnp.zeros((1,), jnp.int32)
        out = tn.admit_rows(tn.clear_rows(one, rows), rows, one)
        return jax.tree.map(lambda x: x[0], out)

    admits = {}
    for at, t in svc.trace_admits:
        admits.setdefault(t, []).append(at)
    refs = {}
    with jax.default_device(cpu):
        fresh = jax.tree.map(lambda x: x[0], tn.init_tenants(k, 1).bank)
        blocks = [(jnp.asarray(ci), jnp.asarray(cw))
                  for ci, cw in svc.trace_blocks]
        for t in tenants:
            row, due = fresh, sorted(admits.get(t, []))
            r = jnp.int32(t)
            for i, (ci, cw) in enumerate(blocks):
                while due and due[0] == i:
                    row, due = readmit(row), due[1:]
                row = step(row, ci, cw, r)
            for _ in due:
                row = readmit(row)
            refs[t] = jax.device_get(row)
    return refs


def _replay(svc, ops, block: int, t0: float, every: int = 32) -> list:
    """``benchmarks.bench_service._replay``'s policy (tick whenever a
    block's worth of updates is pending), with a progress line every
    ``every`` ticks; returns the query tickets."""
    tickets, pending = [], 0
    for op in ops:
        if op[0] == "update":
            svc.submit(op[1], op[2], op[3])
            pending += len(op[2])
            if pending >= block:
                svc.tick()
                pending = 0
                st = svc.stats
                if st["ticks"] % every == 0:
                    log(f"  tick {st['ticks']}: blocks={st['blocks']} "
                        f"spills={st['spills']} admits={st['admits']} "
                        f"wall_s={time.perf_counter() - t0:.3f}")
        else:
            tickets.append(svc.query(op[1], op[2]))
    svc.tick()
    return tickets


def service_phase(tenants: int = 16384, k_per_tenant: int = 2048,
                  bits: int = 17, block: int = 8192,
                  n_updates: int = 1_048_576, spill_after: int = 4,
                  n_top: int = 8, n_random: int = 8, wide_keys: int = 4,
                  seed: int = 0) -> dict:
    """Replay one traffic day through ``SketchService``; check it.

    Raises AssertionError on any failed check.
    """
    import jax

    from benchmarks.common import UNIVERSE, mixed_traffic
    from repro.serve import SketchService
    from repro.sketch import api
    from repro.sketch import bank as bk
    from repro.sketch import tenant as tn

    t0 = time.perf_counter()
    ops = mixed_traffic(tenants, n_updates, delete_ratio=0.5, seed=seed)
    sizes = np.zeros(tenants, np.int64)
    for op in ops:
        if op[0] == "update":
            sizes[op[1]] += len(op[2])
    top = [int(t) for t in np.argsort(-sizes, kind="stable")[:n_top]]
    rest = np.setdiff1d(np.flatnonzero(sizes), top)
    rng = np.random.default_rng(seed)
    rand = sorted(int(t) for t in rng.choice(rest, n_random, replace=False))
    sample = top + rand
    # mid-day, a block's worth of inserts spread over many tenants (the
    # sampled ones among them): its block reaches more tenants than one
    # chunk of the touched-rows ingest holds
    wide = max(len(sample), min(tenants, block // wide_keys))
    others = np.setdiff1d(np.arange(tenants), sample)
    burst = [("update", int(t),
              rng.integers(0, UNIVERSE, wide_keys).astype(np.int32),
              rng.choice([1, 2, 3], wide_keys).astype(np.int32))
             for t in np.concatenate([sample, rng.choice(
                 others, wide - len(sample), replace=False)])]
    ops = ops[:len(ops) // 2] + burst + ops[len(ops) // 2:]
    log(f"traffic: tenants={tenants} "
        f"updates={sum(len(o[2]) for o in ops if o[0] == 'update')} "
        f"queries={sum(len(o[2]) for o in ops if o[0] == 'query')} "
        f"ops={len(ops)} wide_burst_tenants={wide} "
        f"gen_s={time.perf_counter() - t0:.3f}")

    spec = api.SketchSpec(kind="frequency", k=tenants * k_per_tenant,
                          bits=bits, tenants=tenants)
    svc = SketchService(spec, block=block, spill_after=spill_after)
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(svc.session.state))
    log(f"state: {tenants} x {k_per_tenant} counters, "
        f"{state_bytes / 2**20:.0f} MiB on "
        f"{svc.session.state.bank.ids.devices()}")
    for t in top:
        svc.subscribe_topk(t, 16)

    # set-up: compile ingest, batched query and batched top-k with one
    # all-padding tick (zero weights change no counter)
    t0 = time.perf_counter()
    svc.submit(top[0], np.zeros(1, np.int32), np.zeros(1, np.int32))
    svc.query(top[0], np.zeros(1, np.int32))
    svc.tick()
    jax.block_until_ready(svc.session.state)
    log(f"setup: compile + first tick {time.perf_counter() - t0:.3f} s")

    svc.trace_blocks = []
    t0 = time.perf_counter()
    tickets = _replay(svc, ops, block, t0)
    jax.block_until_ready(svc.session.state)
    st = svc.stats
    log(f"replay: ticks={st['ticks']} blocks={st['blocks']} "
        f"spills={st['spills']} admits={st['admits']} "
        f"tickets={len(tickets)} wall_s={time.perf_counter() - t0:.3f}")
    assert all(t.resolved for t in tickets), "unresolved query tickets"
    assert st["spills"] > 0 and st["admits"] > 0, \
        "the day must exercise spill and re-admission"
    for t in top:
        assert svc.topk_result(t) is not None, f"no top-k for tenant {t}"
    reach = [len(np.unique(tn.unpack_keys(np.asarray(ci)[np.asarray(cw) != 0],
                                          bits)[0]))
             for ci, cw in svc.trace_blocks]
    cap = bk.touched_chunk_rows(block, k_per_tenant)
    over_one = sum(r > cap for r in reach)
    log(f"blocks: most tenants in one block={max(reach)} chunk_rows={cap} "
        f"blocks_over_one_chunk={over_one}")
    if tenants > cap:
        assert over_one and over_one < len(reach), \
            "the day must hold blocks of one chunk and of several"

    snaps = {t: svc.tenant_snapshot(t) for t in sample}
    exact = _exact(ops, sample)
    over = [t for t in top if np.count_nonzero(exact[t][0]) > k_per_tenant]
    log(f"sample: largest={top} random={rand}; largest tenants over "
        f"{k_per_tenant} distinct live items: {len(over)}")

    t0 = time.perf_counter()
    refs = _reference_rows(svc, spec, sample, k_per_tenant)
    bad = [t for t in sample
           if not all(np.array_equal(np.asarray(snaps[t][f])[0],
                                     np.asarray(getattr(refs[t], f)))
                      for f in ("ids", "counts", "errors"))]
    readmits = sum(t in sample for _, t in svc.trace_admits)
    log(f"check bit_identity rows={len(sample)} mismatched={bad} "
        f"readmissions_replayed={readmits} "
        f"cpu_reference_s={time.perf_counter() - t0:.3f}")
    assert not bad, f"rows differ from the CPU reference: {bad}"

    worst = 0.0
    for t in sample:
        f, ins, dels = exact[t]
        items = np.flatnonzero(f != 0)
        touched = np.unique(np.concatenate(
            [op[2] for op in ops if op[0] == "update" and op[1] == t]))
        # probes in ticks of exactly ``block`` keys (the tail repeats
        # keys), so the batched query compiles for one shape
        est = np.concatenate([
            svc.query(t, np.resize(touched[s:s + block], block))
            .result()[:len(touched[s:s + block])]
            for s in range(0, len(touched), block)]).astype(np.int64)
        bound = 2.0 * ins / k_per_tenant   # 2 alpha |F|_1 / k, alpha = I/(I-D)
        err = np.abs(est - f[touched])
        worst = max(worst, float(err.max() / bound))
        assert (err <= bound).all(), (
            f"tenant {t}: error {err.max()} over the bound {bound:.1f}")
        keys = np.asarray(snaps[t]["ids"])[0]
        ids = tn.unpack_keys(keys[keys >= 0], bits)[1]  # composite keys
        heavy = items[f[items] > bound]
        missing = np.setdiff1d(heavy, ids)
        assert not len(missing), (
            f"tenant {t}: items over the bound not monitored: {missing[:8]} "
            f"(counts {f[missing[:8]]}, bound {bound})")
        at = items[f[items] == bound]
        if len(at):
            log(f"tenant {t}: items exactly at the bound {bound}: {at[:8]}, "
                f"monitored: {np.isin(at[:8], ids)}")
    log(f"check eps_bound tenants={len(sample)} ok "
        f"worst_error_over_bound={worst:.4f}")
    log(f"check heavy_hitters tenants={len(sample)} ok")
    return {"stats": dict(st), "sample": sample}


# ---------------------------------------------------------------------------
# Four chips: hash-sharded ingest on a mesh
# ---------------------------------------------------------------------------

def mesh_phase(n_devices: int = 4, shards: int = 16,
               k_per_shard: int = 1 << 12, bits: int = 22,
               block: int = 8192, n_inserts: int = 1 << 18,
               seed: int = 0) -> dict:
    """Mesh ingest via ``path='auto'`` vs the one-device ``'block'`` path.

    The stream holds more distinct items than a shard has counters, so
    evictions and the deletion spread run on every device.
    Raises AssertionError on any failed check.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from benchmarks.common import dist_stream, stream_blocks
    from repro.parallel import sharding as psh
    from repro.sketch import api
    from repro.sketch import sharded as shd
    from repro.sketch.state import EMPTY

    devs = jax.devices()[:n_devices]
    assert len(devs) == n_devices, f"{n_devices} devices needed"
    spec = api.SketchSpec(kind="frequency", k=shards * k_per_shard,
                          shards=shards, bits=bits)
    stream = dist_stream("zipf", n_inserts, 0.5, universe=1 << bits,
                         order="interleaved", seed=seed)
    items, weights, _ = stream_blocks(stream, block)
    items = items.reshape(-1, block)
    weights = weights.reshape(-1, block)
    log(f"mesh: {n_devices} devices, shards={shards} x {k_per_shard} "
        f"counters, {len(items)} blocks of {block}")

    mesh = Mesh(np.asarray(devs), ("data",))
    t0 = time.perf_counter()
    with psh.use_mesh(mesh):
        axes = shd._shard_mesh_axes(shards)
        assert axes, "the mesh does not bind the shard axis"
        state = api.make(spec)
        for ci, cw in zip(items, weights):
            state = api.update(spec, state, ci, cw)
        jax.block_until_ready(state)
    log(f"mesh ingest (incl. compile) {time.perf_counter() - t0:.3f} s")

    sharding = state.bank.ids.sharding
    on = sorted(d.id for d in sharding.device_set)
    log(f"mesh bank sharding: {sharding} on devices {on}")
    assert len(on) == n_devices, f"bank spans devices {on} only"
    shards_at = {s.device.id for s in state.bank.ids.addressable_shards}
    assert len(shards_at) == n_devices, f"bank pieces on {shards_at} only"

    with jax.default_device(devs[0]):
        ref = api.make(spec)
        for ci, cw in zip(items, weights):
            ref = shd.update_block(ref, jnp.asarray(ci), jnp.asarray(cw),
                                   spec.variant_id, universe_bits=bits,
                                   path="block")
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(ref)))
    distinct = len(np.unique(stream[:, 0]))
    empty = int((np.asarray(ref.bank.ids) == EMPTY).sum())
    log(f"check mesh_vs_one_device bit_identical={same} "
        f"distinct_items={distinct} counters={shards * k_per_shard} "
        f"empty_slots={empty}")
    assert same, "mesh ingest differs from the one-device block path"
    assert distinct > shards * k_per_shard and not empty, \
        "the stream never overflowed the counters: no eviction was checked"
    return {"devices": on}


# ---------------------------------------------------------------------------
# The fused Pallas kernel, compiled, against the bank engine
# ---------------------------------------------------------------------------

def _full_bank(rng, rows: int, k: int, universe: int):
    """A bank with every slot monitored: distinct ids per row, counts
    and errors as a run of evictions leaves them."""
    from repro.sketch.state import SketchState

    ids = np.stack([rng.choice(universe, k, replace=False)
                    for _ in range(rows)]).astype(np.int32)
    counts = rng.integers(1, 500, (rows, k)).astype(np.int32)
    errors = (counts * rng.random((rows, k)) * 0.5).astype(np.int32)
    return SketchState(ids, counts, errors)


def kernel_phase(shapes=((16, 1024), (16, 4096), (256, 1024), (256, 4096)),
                 block: int = 8192, n_blocks: int = 3, bits: int = 20,
                 seed: int = 0) -> dict:
    """``sketch_block_update_fused`` compiled for the chip vs the bank
    engine on the same device, on full banks (every phase runs), then
    the spec paths a user can pick (``backend='kernel'``) vs
    ``backend='bank'``. Raises AssertionError on any difference."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.kernels.sketch_update.ops import sketch_block_update_fused
    from repro.sketch import api
    from repro.sketch import bank as bk

    rng = np.random.default_rng(seed)
    universe = 1 << bits

    def blocks():
        for _ in range(n_blocks):
            items = rng.zipf(1.2, block) % universe
            w = rng.choice([-2, -1, 1, 1, 1, 3], block)
            yield (jnp.asarray(items, jnp.int32), jnp.asarray(w, jnp.int32))

    def same(a, b):
        return all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    for rows, k in shapes:
        router = bk.HashShardRouter(rows, bits)
        kern = eng = jax.tree.map(jnp.asarray,
                                  _full_bank(rng, rows, k, universe))
        t0 = time.perf_counter()
        for items, w in blocks():
            ri, rw = router.route_dense(items, w)
            kern = sketch_block_update_fused(kern, ri, rw, 2)
            eng = bk.update_rows(eng, ri, rw, 2)
        ok = same(kern, eng)
        log(f"check kernel R={rows} K={k} B={block} blocks={n_blocks} "
            f"bit_identical={ok} wall_s={time.perf_counter() - t0:.3f}")
        assert ok, f"fused kernel differs from the engine at R={rows} K={k}"

    specs = {
        "frequency": api.SketchSpec(k=4096, bits=bits, backend="kernel"),
        "sharded": api.SketchSpec(k=16 * 1024, shards=16, bits=bits,
                                  backend="kernel"),
        "dyadic": api.SketchSpec(kind="quantile", k=17 * 1024, bits=17,
                                 backend="kernel"),
    }
    for name, spec in specs.items():
        ref = dataclasses.replace(spec, backend="bank")
        a, b = api.make(spec), api.make(ref)
        for items, w in blocks():
            items = items % (1 << spec.bits)
            a = api.update(spec, a, items, w)
            b = api.update(ref, b, items, w)
        ok = same(a, b)
        log(f"check backend=kernel {name} bit_identical={ok}")
        assert ok, f"backend='kernel' differs from 'bank' for {name}"
    return {"shapes": list(shapes)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the served path; 4: the mesh phase only")
    ap.add_argument("--kernels", action="store_true",
                    help="one chip: the fused Pallas kernel and the "
                         "backend='kernel' paths against the bank engine, "
                         "instead of the served path")
    args = ap.parse_args()
    if args.kernels and args.chips != 1:
        ap.error("--kernels runs on one chip")
    try:
        import repro  # noqa: F401
        import benchmarks.common  # noqa: F401
    except ImportError as e:
        fail(f"the repo's packages are not next to this script: {e}")
    devs = require_tpu(args.chips)
    from repro.platform import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {devs}")
    if args.chips == 4:
        mesh_phase(n_devices=4)
    elif args.kernels:
        kernel_phase()
    else:
        service_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
