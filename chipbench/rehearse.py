#!/usr/bin/env python3
"""CPU rehearsal of the chip benchmark, and the AOT compile for a v5e.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py [--seconds 3]
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --aot

The first form runs every cell of ``BENCHMARK.json`` end to end on the
CPU, at a tiny size (tenants, counters, block and rate cut down; the
mix's shape kept), with and without the trace, through the same set-up,
window and check as ``run.py``. Every device number prints as "not
measured": a CPU run measures no chip.

``--aot`` compiles each configuration's ingest at its full size for one
chip of a described ``v5e:2x2`` (no chip needed) and prints the
compiler's ``memory_analysis()``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def tiny_cell(name: str, bench_path: str):
    """The cell ``name`` at the rehearsal's size: the configuration cut
    by its kind (``tiny``); an open mix slowed to fit the CPU, a
    saturated one given small epochs."""
    from chipbench import kinds
    from chipbench.harness import load_cell

    cell = load_cell(name, bench_path)
    conf = kinds.load(cell.config.kind).tiny(cell.config)
    mix = cell.mix
    if mix.arrival == "open":
        mix = dataclasses.replace(mix, rate=2000.0)
    else:
        mix = dataclasses.replace(mix, epoch_updates=1 << 14, epochs=4)
    return dataclasses.replace(cell, config=conf, mix=mix)


def rehearse(seconds: float, seed: int) -> int:
    import jax

    from chipbench.run import execute

    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    with open(bench_path) as f:
        bench = json.load(f)
    ok = True
    for w in bench["workloads"]:
        for trace in (False, True):
            cell = tiny_cell(w["name"], bench_path)
            out = execute(cell, seed, seconds, trace, jax.devices()[:1],
                          bench, device_metrics=False,
                          t_start=time.perf_counter())
            print(json.dumps({"workload": w["name"], "trace": int(trace),
                              **out}), flush=True)
            ok &= out["correct"]
    return 0 if ok else 1


def aot() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import kinds
    from chipbench.harness import Config
    from repro.sketch import api
    from repro.sketch.session import _ingest_fn

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        conf = Config.load(os.path.join(ROOT, c["file"]))
        spec = kinds.load(conf.kind).spec(conf)
        state = jax.eval_shape(lambda: api.make(spec))
        state = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            state)
        blk = jax.ShapeDtypeStruct((conf.block,), jnp.int32, sharding=one)
        fn = _ingest_fn(spec, conf.block)
        t0 = time.perf_counter()
        compiled = jax.jit(fn, donate_argnums=(0,)).lower(
            state, blk, blk).compile()
        m = compiled.memory_analysis()
        print(json.dumps({
            "config": c["name"], "compile_s": round(
                time.perf_counter() - t0, 3),
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "generated_code_bytes": m.generated_code_size_in_bytes}),
            flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    ap.add_argument("--aot", action="store_true")
    args = ap.parse_args()
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        print("rehearse: set JAX_PLATFORMS=cpu; this runs no chip",
              file=sys.stderr)
        return 2
    return aot() if args.aot else rehearse(args.seconds, args.seed)


if __name__ == "__main__":
    sys.exit(main())
