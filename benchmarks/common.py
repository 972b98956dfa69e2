"""Shared helpers for the paper-experiment benchmarks.

Scaled defaults: the paper uses |F|1 = 1e5 (synthetic) / 1e6 (CAIDA)
averaged over 5 runs; CI defaults here are 2e4 / 3 runs so the whole
suite stays minutes on one CPU core. ``--full`` restores paper scale.
Trends, not absolute values, are the comparison target (DESIGN.md §7).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Iterable, List, Optional

import numpy as np

from repro.core.baselines import CSSS, CountMedian, CountMin
from repro.core.spacesaving import LazySpaceSavingPM, SpaceSavingPM
from repro.core.streams import bounded_stream

DISTRIBUTIONS = ("zipf", "binomial", "caida")
UNIVERSE = 1 << 16
UNIVERSE_BITS = 16


def device_hw():
    """Roofline preset of the attached accelerator, or None on the CPU.

    A CPU run has no device peaks to read against, so its roofline
    columns say "not measured" (``roofline.model.sketch_roofline``).
    """
    from repro.platform import has_accelerator, hw_config

    return hw_config() if has_accelerator() else None


def dist_stream(dist: str, n_insert: int, delete_ratio: float = 0.5,
                *, seed: int = 0, universe: int = UNIVERSE,
                delete_pattern: str = "random",
                order: str = "inserts_first") -> np.ndarray:
    """The one bounded-deletion stream factory every bench shares.

    Thin front-end over ``repro.core.streams.bounded_stream`` pinning the
    benchmarks' common universe so scripts stop re-spelling the same
    kwargs (and silently diverging on them).
    """
    return bounded_stream(dist, n_insert, delete_ratio, universe=universe,
                          delete_pattern=delete_pattern, order=order,
                          seed=seed)


def zipf_stream(n_insert: int, delete_ratio: float = 0.5, *, seed: int = 0,
                order: str = "inserts_first") -> np.ndarray:
    """Zipf marginal (the paper's synthetic default, §5.2)."""
    return dist_stream("zipf", n_insert, delete_ratio, seed=seed, order=order)


def adversarial_stream(n_insert: int, delete_ratio: float = 0.5,
                       *, seed: int = 0) -> np.ndarray:
    """The paper's adversarial case: targeted deletions, inserts first.

    Deleting the heaviest monitored items maximizes unmonitored-deletion
    spreading — the locality-minimizing worst case for SS± (§5.3).
    """
    return dist_stream("zipf", n_insert, delete_ratio, seed=seed,
                       delete_pattern="targeted", order="inserts_first")


def mixed_traffic(num_tenants: int, n_updates: int, *,
                  delete_ratio: float = 0.5, skew: float = 1.2,
                  query_frac: float = 0.1, query_size: int = 8,
                  burst: int = 64, dist: str = "zipf",
                  universe: int = UNIVERSE, seed: int = 0) -> List[tuple]:
    """A heavy-traffic day in op form: the shared multi-tenant generator.

    Returns a seeded, reproducible list of interleaved ops

        ("update", tenant, items, weights)   signed int32 fragments
        ("query",  tenant, items)            point-query probes

    Tenant sizes are zipf-skewed (tenant ranks weighted ``(r+1)^-skew``,
    sizes drawn multinomially so they sum to ``n_updates``): a few whale
    tenants, a long tail — the service bench's population shape. Each
    tenant's own substream is a standard ``dist_stream`` bounded-deletion
    stream (per-tenant seed), chopped into ``burst``-sized update ops;
    after a burst, with probability ``query_frac``, a query op probes
    ``query_size`` items drawn from that burst. The global interleaving
    permutes ops ACROSS tenants while preserving each tenant's own op
    order (a fixed-permutation label trick), so per-tenant
    insert-before-delete validity survives the shuffle.
    """
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, num_tenants + 1, dtype=np.float64)
    p = ranks ** -float(skew)
    p /= p.sum()
    sizes = rng.multinomial(int(n_updates), p)
    per_tenant_ops: List[List[tuple]] = []
    for t in range(num_tenants):
        ops_t: List[tuple] = []
        if sizes[t] > 0:
            sub = dist_stream(dist, int(sizes[t]), delete_ratio,
                              seed=seed + 7919 * t, universe=universe)
            for s in range(0, len(sub), burst):
                chunk = sub[s:s + burst]
                items = np.ascontiguousarray(chunk[:, 0], np.int32)
                weights = np.ascontiguousarray(chunk[:, 1], np.int32)
                ops_t.append(("update", t, items, weights))
                if rng.random() < query_frac:
                    probes = rng.choice(items, size=min(query_size,
                                                        len(items)))
                    ops_t.append(("query", t, probes.astype(np.int32)))
        per_tenant_ops.append(ops_t)
    labels = np.repeat(np.arange(num_tenants),
                       [len(o) for o in per_tenant_ops])
    rng.shuffle(labels)
    cursors = [0] * num_tenants
    out: List[tuple] = []
    for t in labels:
        out.append(per_tenant_ops[t][cursors[t]])
        cursors[t] += 1
    return out


def stream_blocks(stream: np.ndarray, block: int):
    """(items, weights) int32 arrays zero-padded to a multiple of block."""
    n = len(stream)
    nb = max(1, -(-n // block))
    items = np.zeros(nb * block, np.int32)
    weights = np.zeros(nb * block, np.int32)
    items[:n] = stream[:, 0]
    weights[:n] = stream[:, 1]
    return items, weights, nb


def exact_freqs(stream: np.ndarray, universe: int = UNIVERSE) -> np.ndarray:
    f = np.zeros(universe, np.int64)
    np.add.at(f, stream[:, 0], stream[:, 1])
    return f


def run_sketch(sketch, stream: np.ndarray) -> float:
    """Feed the stream; returns seconds per update."""
    t0 = time.perf_counter()
    if hasattr(sketch, "process"):
        sketch.process(stream)
    else:
        for item, sign in stream:
            sketch.update(int(item), int(sign))
    return (time.perf_counter() - t0) / len(stream)


def mse(sketch, freqs: np.ndarray, sample: np.ndarray) -> float:
    if hasattr(sketch, "query_many"):
        est = np.asarray(sketch.query_many(sample), dtype=np.float64)
    else:
        est = np.asarray([sketch.query(int(i)) for i in sample], dtype=np.float64)
    return float(np.mean((est - freqs[sample]) ** 2))


def recall_precision(sketch, freqs: np.ndarray, phi: float,
                     est: Optional[np.ndarray] = None):
    """phi-heavy-hitter recall/precision vs exact ``freqs``.

    ``est``: optional precomputed estimates aligned with the nonzero
    candidates of ``freqs`` — callers that already ran query_many (e.g.
    bench_sharded, which reuses one estimate vector across phis) pass it
    to skip the per-sketch query here.
    """
    live = freqs.sum()
    thresh = phi * live
    true_hot = set(np.nonzero(freqs >= thresh)[0].tolist())
    cand = np.nonzero(freqs > 0)[0]
    if est is None:
        if hasattr(sketch, "query_many"):
            est = np.asarray(sketch.query_many(cand), dtype=np.float64)
        else:
            est = np.asarray([sketch.query(int(i)) for i in cand],
                             dtype=np.float64)
    reported = set(cand[est >= thresh].tolist())
    tp = len(true_hot & reported)
    recall = tp / max(len(true_hot), 1)
    precision = tp / max(len(reported), 1)
    return recall, precision


def make_sketches(budget: int, alpha: float, universe: int = UNIVERSE,
                  n_stream: int = 0, seed: int = 0) -> Dict[str, object]:
    """The paper's §5 lineup at EQUAL space (``budget`` counters each).

    This mirrors the paper's Fig 5 setup ("the sketch space is 500 logU
    bits" for every sketch): SS± variants spend the budget on k counters;
    Count-Min / Count-Median arrange the same counter budget as
    depth x width with the customary depth 5; CSSS runs its sampling
    front-end over an equally-sized Count-Median.
    """
    depth = 5
    width = max(2, budget // depth)
    eps_implied = alpha / budget
    return {
        "lazy_sspm": LazySpaceSavingPM(capacity=budget),
        "sspm": SpaceSavingPM(capacity=budget),
        "count_min": CountMin(width=width, depth=depth, seed=seed),
        "count_median": CountMedian(width=width, depth=depth, seed=seed),
        "csss": CSSS(eps=eps_implied, delta=1.0 / universe, alpha=alpha,
                     universe=universe, stream_len=max(n_stream, 1000),
                     seed=seed, sample_const=4.0),
    }


def min_time(fn: Callable, runs: int) -> float:
    """Min-of-N wall time of a jitted callable returning a JAX pytree.

    One warmup call (compile), then min over ``runs`` — robust to the
    CPU-contention outliers that would dominate a mean at the ms scale.
    Shared by the kernel/sharded benches (was duplicated per script).
    """
    import jax

    def ready(out):
        jax.tree.map(lambda x: x.block_until_ready(), out)

    ready(fn())
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def run_spec(spec, stream: np.ndarray, block: int, *, runs: int = 3,
             window=None):
    """The one spec-driven bench driver: feed ``stream`` through a fresh
    :class:`repro.sketch.StreamSession` per run, min-of-N seconds.

    Replaces the per-script pad-and-feed loops: any (kind × shards ×
    variant × backend) cell is one ``SketchSpec`` away.  Returns
    ``(best_seconds, final_session)`` — callers query the session for
    accuracy metrics so the timed path is exactly the production path.
    """
    from repro.sketch.session import StreamSession

    items = np.ascontiguousarray(stream[:, 0], np.int32)
    weights = np.ascontiguousarray(stream[:, 1], np.int32)

    def one_pass():
        s = StreamSession(spec, block=block, window=window)
        s.extend(items, weights)
        s.flush()
        jax_block_until_ready(s.state)
        return s

    sess = one_pass()  # warmup: compile every (spec, block) shape
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        sess = one_pass()
        best = min(best, time.perf_counter() - t0)
    return best, sess


def jax_block_until_ready(tree) -> None:
    import jax

    jax.tree.map(lambda x: x.block_until_ready(), tree)


def session_overhead(spec, direct_fn, warm_fn, stream: np.ndarray,
                     block: int, n_blocks: int, runs: int = 5):
    """Race StreamSession.ingest_block against the direct engine call on
    the SAME evolving state sequence (bit-identical work), min-of-N.

    ``direct_fn(state, items, weights) -> state`` is the raw jitted
    spelling (e.g. ``bank.update_block_fused`` with a pinned router);
    ``warm_fn(items, weights) -> state`` builds the warm start from the
    stream's first block; the session runs its cached jitted ingest for
    the same spec.  Because both loops visit identical states, the
    difference is pure session dispatch/buffer overhead — the <5%
    acceptance number of DESIGN.md §11 (the shared scaffolding of both
    session-overhead bench cells).  Returns
    (sec_direct, sec_session, overhead_pct), both times over the whole
    ``n_blocks`` sequence.
    """
    import jax
    import jax.numpy as jnp

    from repro.sketch.session import StreamSession

    def cut(col, b):
        return jnp.asarray(stream[b * block:(b + 1) * block, col], jnp.int32)

    warm_state = warm_fn(cut(0, 0), cut(1, 0))
    blocks_i = [cut(0, b) for b in range(1, n_blocks + 1)]
    blocks_w = [cut(1, b) for b in range(1, n_blocks + 1)]

    def fresh_state():
        # per-pass buffer copy: the session's compiled ingest donates its
        # state on accelerators, so reusing warm_state across passes would
        # hit deleted buffers there; copy on both sides for symmetry.
        return jax.tree.map(lambda x: x.copy(), warm_state)

    def run_direct():
        st = fresh_state()
        for i, w in zip(blocks_i, blocks_w):
            st = direct_fn(st, i, w)
        jax_block_until_ready(st)
        return st

    def run_session():
        s = StreamSession(spec, block=block, state=fresh_state())
        for i, w in zip(blocks_i, blocks_w):
            s.ingest_block(i, w)
        jax_block_until_ready(s.state)
        return s.state

    # interleave the trials: contended CPUs drift over a bench process's
    # lifetime, and back-to-back min_time blocks would charge that drift
    # entirely to whichever side runs second.
    run_direct()                             # compile both sides first
    run_session()
    t_direct = t_session = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        run_direct()
        t_direct = min(t_direct, time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_session()
        t_session = min(t_session, time.perf_counter() - t0)
    return t_direct, t_session, 100.0 * (t_session - t_direct) / t_direct


def _json_default(obj):
    """np scalars -> python; anything else is a bug, not a bool."""
    if isinstance(obj, np.generic):
        return obj.item()
    raise TypeError(f"not JSON-serializable: {type(obj).__name__}")


def write_bench_json(results: Dict[str, list], columns: Dict[str, List[str]],
                     path: str) -> None:
    """The BENCH_*.json artifact contract: one table per key, rows as
    column-name dicts (machine-readable perf trajectory across PRs)."""
    import json

    payload = {
        name: [dict(zip(cols, r)) for r in results[name]]
        for name, cols in columns.items() if name in results
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=_json_default)
        f.write("\n")
    print(f"\n# wrote {path}")


def csv_print(name: str, header: List[str], rows: Iterable[Iterable]) -> None:
    print(f"\n# {name}")
    print(",".join(header))
    for r in rows:
        print(",".join(f"{x:.6g}" if isinstance(x, float) else str(x) for x in r))
