"""Kernel benchmarks: parity + interpret-mode throughput for the Pallas
kernels (sketch_update, flash_attention) against their jnp oracles.

For sketch_update the benchmark races THREE generations of the kernel
path per cell (DESIGN.md §3, §14): the seed serial O(B·k) scan, the
split two-phase path (phase 1 in XLA + residual-only launch), and the
production fused tiled kernel (phases 1-2 in ONE ``pallas_call``);
reports both speedups, the residual fraction, bit-identity of the fused
launch against the engine oracle ``bank.update_block_fused``, and the
roofline columns (achieved vs peak bytes/s, arithmetic intensity) from
the sketch-ingest cost model (``repro.roofline.model``) against the
preset for the attached device's ``device_kind`` (``repro.platform``).
Results
are written to ``BENCH_kernels.json`` at the repo root so the perf
trajectory is machine-readable across PRs.

Wall-times from a CPU run are interpret-mode numbers — correctness and
relative-shape trends only; such a run has no device peaks, so its
``peak_fraction`` column reads "not measured".
"""
from __future__ import annotations

import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import (
    UNIVERSE_BITS,
    csv_print,
    device_hw,
    dist_stream,
    min_time,
    write_bench_json,
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON_PATH = os.path.join(_REPO_ROOT, "BENCH_kernels.json")

SKETCH_DISTRIBUTIONS = ("zipf", "binomial", "caida")
SKETCH_SHAPES = ((1024, 1024), (4096, 4096))  # (k, B)

# single source of truth for both csv_print and the JSON artifact
SKETCH_COLUMNS = ["dist", "state", "k", "block", "parity", "bit_identical",
                  "serial_ms", "two_phase_ms", "fused_ms", "speedup",
                  "fused_speedup", "residual_frac", "achieved_bytes_per_s",
                  "peak_fraction", "arith_intensity"]
FLASH_COLUMNS = ["kernel", "seq", "parity", "ms"]
DECODE_COLUMNS = ["kernel", "cache", "parity", "ms"]


def bench_sketch_update(runs: int = 3, shapes=SKETCH_SHAPES):
    from repro.kernels.sketch_update.ops import (
        sketch_block_update,
        sketch_block_update_fused,
        sketch_block_update_serial,
    )
    from repro import sketch as js
    from repro.roofline.model import sketch_ingest_cost, sketch_roofline
    from repro.sketch import bank as bk

    # end-to-end fused client: route (packed single sort — items live in
    # [0, 2^UNIVERSE_BITS)) + prep + ONE tiled kernel launch, all one jit
    # program; interpret is platform-resolved like the split path's, so
    # fused vs split compare like for like on either backend
    router = bk.HashShardRouter(1, UNIVERSE_BITS)

    @jax.jit
    def fused_ingest(state, items, weights):
        bank1 = jax.tree.map(lambda x: x[None], state)
        ri, rw = router.route_dense(items, weights)
        out = sketch_block_update_fused(bank1, ri, rw, 2)
        return jax.tree.map(lambda x: x[0], out)

    hw = device_hw()
    rows = []
    for dist in SKETCH_DISTRIBUTIONS:
        for k, block in shapes:
            # three cells per shape: "cold" times an insert block on an
            # empty sketch (residual fraction 1 by construction); "warm"
            # times a second insert block, where the residual fraction is
            # the unseen-unique rate of the distribution; "mixed" times an
            # interleaved insert/delete block on the warm state, covering
            # the unmonitored-deletion spreading path.
            stream = dist_stream(dist, 2 * block, 0.0, seed=1)
            blk1 = stream[:block]
            blk2 = stream[block:2 * block]
            # fresh seed: seed=1 would replay blk1's RNG prefix and make
            # every mixed item monitored
            mixed = dist_stream(dist, block, 0.5, order="interleaved",
                                seed=2)[:block]
            items1 = jnp.asarray(blk1[:, 0], jnp.int32)
            weights1 = jnp.asarray(blk1[:, 1], jnp.int32)
            cold = js.init(k)
            warm = sketch_block_update(cold, items1, weights1)
            warm.ids.block_until_ready()
            for label, state, blk in (
                ("cold", cold, blk1), ("warm", warm, blk2), ("mixed", warm, mixed),
            ):
                items = jnp.asarray(blk[:, 0], jnp.int32)
                weights = jnp.asarray(blk[:, 1], jnp.int32)
                out_k = sketch_block_update(state, items, weights)
                out_j = js.block_update(state, items, weights)
                parity = all(
                    np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(out_k, out_j)
                )
                # fused launch vs the engine oracle: bit-identical, every cell
                out_f = fused_ingest(state, items, weights)
                bank1 = jax.tree.map(lambda x: x[None], state)
                out_o = bk.update_block_fused(bank1, items, weights, router, 2)
                bit_identical = all(
                    np.array_equal(np.asarray(a), np.asarray(b[0]))
                    for a, b in zip(out_f, out_o)
                )
                # warm all paths, then time
                sketch_block_update_serial(state, items, weights).ids.block_until_ready()
                t_two = min_time(lambda: sketch_block_update(state, items, weights), runs)
                t_fused = min_time(lambda: fused_ingest(state, items, weights), runs)
                t_serial = min_time(
                    lambda: sketch_block_update_serial(state, items, weights),
                    runs)
                n_uniq, n_mon, n_res = js.block_partition_stats(state, items, weights)
                res_frac = n_res / max(n_uniq, 1)
                # exact residual lockstep trip count for the cost model:
                # the non-unit insert run length from the fused prep
                ri, rw = router.route_dense(items, weights)
                _, _, _, _, _, nnu, _ = bk.phase1_dense_prep(
                    bank1, ri, rw, 2)
                trips = int(np.asarray(nnu).max())
                cost = sketch_ingest_cost(num_rows=1, k=k, block=block,
                                          residual_trips=trips)
                roof = sketch_roofline(cost, t_fused, hw)
                rows.append([
                    dist, label, k, block, parity, bit_identical,
                    t_serial * 1e3, t_two * 1e3, t_fused * 1e3,
                    t_serial / max(t_two, 1e-12),
                    t_two / max(t_fused, 1e-12), res_frac,
                    roof["achieved_bytes_per_s"], roof["peak_fraction"],
                    roof["arith_intensity"],
                ])
    csv_print("kernel_sketch_update", SKETCH_COLUMNS, rows)
    return rows


def bench_flash_attention(runs: int = 2):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref

    rows = []
    for (B, S, H, KV, hd) in ((1, 256, 4, 2, 64), (1, 512, 8, 2, 128)):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
        out = flash_attention(q, k, v, causal=True)
        ref = flash_attention_ref(q, k, v, causal=True)
        parity = bool(jnp.allclose(out, ref, atol=3e-5, rtol=3e-5))
        t0 = time.perf_counter()
        for _ in range(runs):
            flash_attention(q, k, v, causal=True).block_until_ready()
        dt = (time.perf_counter() - t0) / runs
        rows.append([f"flash_B{B}_S{S}_H{H}", S, parity, dt * 1e3])
    csv_print("kernel_flash_attention", FLASH_COLUMNS, rows)
    return rows


def bench_decode_attention(runs: int = 2):
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref

    rows = []
    for (B, KV, G, hd, C) in ((2, 2, 4, 64, 512), (1, 4, 2, 128, 2048)):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, KV, G, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, C, KV, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, C, KV, hd), jnp.float32)
        valid = jax.random.uniform(ks[3], (B, C)) < 0.8
        ctx, mass = decode_attention(q, k, v, valid)
        ctx_r, mass_r = decode_attention_ref(q, k, v, valid)
        parity = bool(
            jnp.allclose(ctx, ctx_r, atol=3e-5, rtol=3e-5)
            and jnp.allclose(mass, mass_r, atol=2e-5, rtol=2e-4)
        )
        t0 = time.perf_counter()
        for _ in range(runs):
            decode_attention(q, k, v, valid)[0].block_until_ready()
        dt = (time.perf_counter() - t0) / runs
        rows.append([f"decode_C{C}_KV{KV}", C, parity, dt * 1e3])
    csv_print("kernel_decode_attention", DECODE_COLUMNS, rows)
    return rows


def _write_json(results: dict, path: str = JSON_PATH) -> None:
    write_bench_json(results, {
        "sketch_update": SKETCH_COLUMNS,
        "flash_attention": FLASH_COLUMNS,
        "decode_attention": DECODE_COLUMNS,
    }, path)


def run(smoke: bool = False, write_json: bool = True, **kw):
    if smoke:
        results = {
            "sketch_update": bench_sketch_update(runs=1, shapes=((256, 256),)),
            "flash_attention": bench_flash_attention(runs=1),
            "decode_attention": bench_decode_attention(runs=1),
        }
    else:
        results = {
            "sketch_update": bench_sketch_update(),
            "flash_attention": bench_flash_attention(),
            "decode_attention": bench_decode_attention(),
        }
    if write_json and not smoke:
        _write_json(results)
    return results


if __name__ == "__main__":
    run()
