"""Multi-tenant service bench: a heavy-traffic day on one fused bank.

Three tables into BENCH_service.json:

  * ``service`` — replay a zipf-skewed mixed insert/delete/query day
    (``common.mixed_traffic``) through :class:`repro.serve.SketchService`
    at >= 1000 tenants, per delete ratio: sustained updates/sec through
    the coalesced tick loop, batched point-query throughput (one
    owner-row gather), p99 per-ticket query latency, a sampled-row
    serial-reference parity bill, and the compiled-ingest cache growth
    (the one-compile-per-layout satellite: every tenant layout of the
    day shares ONE compiled ingest).
  * ``fused_vs_sessions`` — the tentpole race: the SAME per-tenant
    traffic at EQUAL total counter budget through (a) one multi-tenant
    fused bank vs (b) one ``StreamSession`` per tenant; the acceptance
    bar is fused >= 2x. A separate untimed pass pins bit-identity of a
    sampled tenant subset against independently-fed per-tenant sketches.
  * ``roofline`` — the fused multi-tenant block held to the same
    achieved-vs-peak standard as BENCH_kernels.json
    (``roofline.sketch_ingest_cost`` at the service's (T*S, k_row,
    block) shape).

Parity is SAMPLED here (rows are independent under the partition
router, so per-row parity is exact evidence, and tests/test_tenant.py
pins the exhaustive small-scale grid); the sample size is a column, not
a hidden cap.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmarks.common import (
    UNIVERSE_BITS,
    csv_print,
    device_hw,
    min_time,
    mixed_traffic,
    write_bench_json,
)

COLUMNS = {
    "service": [
        "tenants", "shards", "delete_ratio", "updates", "queries",
        "blocks", "updates_per_s", "batched_queries_per_s", "p99_query_ms",
        "parity_rows", "parity_ok", "cache_entries_added",
    ],
    "fused_vs_sessions": [
        "tenants", "k_per_tenant", "total_budget", "block",
        "updates", "ms_fused", "ms_sessions", "speedup",
        "parity_tenants", "bit_identical",
    ],
    "roofline": [
        "tenants", "rows", "k_row", "block", "ms_per_block",
        "updates_per_s", "achieved_bytes_per_s", "peak_fraction",
        "arith_intensity", "bound",
    ],
}


def _replay(svc, ops, block: int):
    """Feed one traffic day through the service; returns (wall_s,
    resolved tickets). Ticks whenever a block's worth of updates is
    pending — the coalescing policy the module docstring describes."""
    tickets = []
    pending = 0
    t0 = time.perf_counter()
    for op in ops:
        if op[0] == "update":
            _, t, items, weights = op
            svc.submit(t, items, weights)
            pending += len(items)
            if pending >= block:
                svc.tick()
                pending = 0
        else:
            _, t, items = op
            tickets.append(svc.query(t, items))
    svc.tick()
    return time.perf_counter() - t0, tickets


def _sampled_parity(svc, spec, sample_rows: np.ndarray) -> bool:
    """Replay the service's recorded block sequence through the serial
    per-row oracle for ``sample_rows``; exact bit-identity per row."""
    import jax

    from repro.sketch import api
    from repro.sketch import bank as bk
    from repro.sketch import tenant as tn

    shards = spec.shards or 1
    router = bk.TenantRouter(spec.tenants, spec.bits, shards)
    fresh = api.make(spec)
    final = svc.session.state
    for r in sample_rows:
        row = jax.tree.map(lambda x: x[int(r)], fresh.bank)
        for ci, cw in svc.trace_blocks:
            row = tn.reference_row_update(row, ci, cw, router, int(r),
                                          spec.variant_id)
        got = jax.tree.map(lambda x: x[int(r)], final.bank)
        for a, b in zip(row, got):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                return False
    return True


def _service_table(tenants: int, block: int, n_updates: int,
                   delete_ratios, k_per_tenant: int, runs: int,
                   parity_rows: int, rng: np.random.Generator) -> List[list]:
    import jax.numpy as jnp

    from repro.serve import SketchService
    from repro.sketch import api
    from repro.sketch import session as ses
    from repro.sketch import tenant as tn

    spec = api.SketchSpec(kind="frequency", k=tenants * k_per_tenant,
                          bits=UNIVERSE_BITS, tenants=tenants)
    rows = []
    entries_before_all = ses.ingest_cache_stats()["entries"]
    for dr in delete_ratios:
        ops = mixed_traffic(tenants, n_updates, delete_ratio=dr,
                            seed=int(dr * 10) + 1)
        n_up = sum(len(o[2]) for o in ops if o[0] == "update")
        n_q = sum(len(o[2]) for o in ops if o[0] == "query")
        entries0 = ses.ingest_cache_stats()["entries"]

        # untimed traced pass: the parity evidence
        svc = SketchService(spec, block=block)
        svc.trace_blocks = []
        _replay(svc, ops, block)
        sample = rng.choice(spec.tenants * (spec.shards or 1),
                            size=min(parity_rows, spec.tenants),
                            replace=False)
        parity_ok = _sampled_parity(svc, spec, sample)

        # timed passes (no trace): min-of-N wall, p99 from the last pass
        best, tickets = float("inf"), []
        for _ in range(runs):
            svc_t = SketchService(spec, block=block)
            wall, tickets = _replay(svc_t, ops, block)
            best = min(best, wall)
        lat = np.asarray([t.latency_s for t in tickets]) \
            if tickets else np.asarray([0.0])
        p99_ms = float(np.percentile(lat, 99) * 1e3)

        # batched point-query throughput: one owner-row gather
        qt = rng.integers(0, tenants, 4096)
        qi = rng.integers(0, 1 << UNIVERSE_BITS, 4096)
        keys = jnp.asarray(tn.pack_keys(qt, qi, UNIVERSE_BITS)
                           .astype(np.int32))
        state = svc_t.session.state
        t_q = min_time(lambda: api.query_many(spec, state, keys),
                       max(runs, 2))
        added = ses.ingest_cache_stats()["entries"] - entries0
        rows.append([
            tenants, spec.shards or 1, dr, n_up, n_q, svc.stats["blocks"],
            n_up / best, len(keys) / t_q, p99_ms,
            len(sample), parity_ok, added,
        ])
        assert parity_ok, f"sampled-row parity failed at delete_ratio={dr}"
    added_all = ses.ingest_cache_stats()["entries"] - entries_before_all
    assert added_all <= 1, (
        f"one-compile-per-layout violated: {added_all} new compiled-ingest "
        f"entries for one tenant layout (ingest_cache_spec regression)")
    return rows


def _fused_vs_sessions(tenants: int, k_per_tenant: int, block: int,
                       n_updates: int, runs: int, parity_tenants: int,
                       rng: np.random.Generator):
    from repro.sketch import api
    from repro.sketch import tenant as tn
    from repro.sketch.session import BlockFeeder, StreamSession

    spec_mt = api.SketchSpec(kind="frequency", k=tenants * k_per_tenant,
                             bits=UNIVERSE_BITS, tenants=tenants)
    spec_1 = api.SketchSpec(kind="frequency", k=k_per_tenant,
                            bits=UNIVERSE_BITS)
    ops = [o for o in mixed_traffic(tenants, n_updates, delete_ratio=0.5,
                                    query_frac=0.0, seed=7)
           if o[0] == "update"]
    n_up = sum(len(o[2]) for o in ops)

    # pre-coalesced fused blocks: the service tick's ingest shape
    keys = np.concatenate([
        tn.pack_keys(np.full(len(o[2]), o[1], np.int64),
                     o[2].astype(np.int64), UNIVERSE_BITS)
        for o in ops]).astype(np.int32)
    weights = np.concatenate([o[3] for o in ops]).astype(np.int32)
    nb = -(-len(keys) // block)
    pad = nb * block - len(keys)
    keys = np.pad(keys, (0, pad))
    weights = np.pad(weights, (0, pad))
    blocks = [(keys[s:s + block], weights[s:s + block])
              for s in range(0, len(keys), block)]

    def run_fused():
        sess = StreamSession(spec_mt, block=block)
        feeder = BlockFeeder(sess)
        for ci, cw in blocks:
            feeder.feed(ci, cw)
        feeder.flush()
        return sess

    # per-tenant-session baseline: each tenant buffers its own substream
    # through its own session (the generous spelling — buffered extend,
    # not one padded dispatch per fragment)
    sess_block = max(64, min(256, block))

    def run_sessions():
        import jax

        sessions = [StreamSession(spec_1, block=sess_block)
                    for _ in range(tenants)]
        for _, t, items, w in ops:
            sessions[t]._append(items, w)  # pre-validated int32 traffic
        for s in sessions:
            s.flush()
        jax.block_until_ready(sessions[-1].state)
        return sessions

    fused = run_fused()       # compile both sides before timing
    run_sessions()
    t_fused = t_sessions = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        fused = run_fused()
        t_fused = min(t_fused, time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_sessions()
        t_sessions = min(t_sessions, time.perf_counter() - t0)

    # untimed parity pass: per-tenant twins fed the SAME per-block
    # fragment sequence (identical op order => bit-identical rows)
    sample_t = sorted(rng.choice(tenants, size=min(parity_tenants, tenants),
                                 replace=False).tolist())
    twins = {t: api.make(spec_1) for t in sample_t}
    import jax.numpy as jnp
    for ci, cw in blocks:
        tt, it = tn.unpack_keys(ci.astype(np.int64), UNIVERSE_BITS)
        for t in sample_t:
            m = (tt == t) & (cw != 0)
            if m.any():
                twins[t] = api.update(spec_1, twins[t],
                                      jnp.asarray(it[m].astype(np.int32)),
                                      jnp.asarray(cw[m]))
    bit_identical = True
    for t in sample_t:
        probe = np.unique(np.concatenate(
            [o[2] for o in ops if o[1] == t] or [np.zeros(1, np.int32)]))
        pk = tn.pack_keys(np.full(len(probe), t, np.int64),
                          probe.astype(np.int64), UNIVERSE_BITS)
        q_mt = np.asarray(api.query_many(
            spec_mt, fused.state, jnp.asarray(pk.astype(np.int32))))
        q_1 = np.asarray(api.query_many(
            spec_1, twins[t], jnp.asarray(probe.astype(np.int32))))
        i_mt, v_mt = api.tenant_topk(spec_mt, fused.state, t, k_per_tenant)
        i_1, v_1 = api.topk(spec_1, twins[t], k_per_tenant)
        if not (np.array_equal(q_mt, q_1)
                and np.array_equal(np.asarray(i_mt), np.asarray(i_1))
                and np.array_equal(np.asarray(v_mt), np.asarray(v_1))):
            bit_identical = False
    row = [tenants, k_per_tenant, tenants * k_per_tenant, block, n_up,
           t_fused * 1e3, t_sessions * 1e3,
           t_sessions / max(t_fused, 1e-12),
           len(sample_t), bit_identical]
    return row, t_fused, len(blocks)


def _roofline_row(tenants: int, k_per_tenant: int, block: int,
                  t_fused: float, n_blocks: int) -> list:
    from repro.roofline.model import sketch_ingest_cost, sketch_roofline

    rows = tenants  # S=1 at the bench shape
    cost = sketch_ingest_cost(num_rows=rows, k=k_per_tenant, block=block)
    wall = t_fused / max(n_blocks, 1)
    roof = sketch_roofline(cost, wall, device_hw())
    return [tenants, rows, k_per_tenant, block, wall * 1e3,
            n_blocks * block / max(t_fused, 1e-12),
            roof["achieved_bytes_per_s"], roof["peak_fraction"],
            roof["arith_intensity"], roof["bound"]]


def run(smoke: bool = False, write_json: bool = True,
        tenants: int = 1024, n_updates: int = 200_000,
        block: int = 8192, k_per_tenant: int = 8, runs: int = 2) -> Dict:
    if smoke:
        tenants, n_updates, block, runs = 32, 4000, 1024, 1
    rng = np.random.default_rng(0)
    results: Dict[str, List[list]] = {}

    results["service"] = _service_table(
        tenants, block, n_updates, (0.0, 0.5), k_per_tenant, runs,
        parity_rows=8 if smoke else 32, rng=rng)

    fvs_row, t_fused, n_blocks = _fused_vs_sessions(
        tenants, k_per_tenant, block, n_updates, runs,
        parity_tenants=8 if smoke else 64, rng=rng)
    results["fused_vs_sessions"] = [fvs_row]

    results["roofline"] = [_roofline_row(tenants, k_per_tenant, block,
                                         t_fused, n_blocks)]

    for name, cols in COLUMNS.items():
        csv_print(name, cols, results[name])

    assert fvs_row[-1], "fused vs per-tenant sessions parity broke"
    if not smoke:
        speedup = fvs_row[7]
        assert speedup >= 2.0, (
            f"fused multi-tenant ingest only {speedup:.2f}x the per-tenant"
            f"-session baseline (acceptance bar: >= 2x)")
    if write_json:
        write_bench_json(results, COLUMNS, "BENCH_service.json")
    return results


if __name__ == "__main__":
    run()
