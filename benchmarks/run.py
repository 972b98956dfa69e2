"""Benchmark orchestrator: one bench per paper table/figure + kernels +
the sharded-bank scaling bench + the dry-run/roofline summary.

    PYTHONPATH=src python -m benchmarks.run            # full CI suite
    PYTHONPATH=src python -m benchmarks.run --only fig5
    PYTHONPATH=src python -m benchmarks.run --smoke    # bitrot guard: tiny
                                                       # shapes, no JSON
"""
from __future__ import annotations

import argparse
import sys
import time


def _roofline_summary():
    from pathlib import Path
    from repro.roofline.report import (
        load_records, markdown_table, sketch_kernel_table,
    )

    kj = Path("BENCH_kernels.json")
    if kj.exists():
        print("\n# sketch_ingest_roofline (BENCH_kernels.json)")
        print(sketch_kernel_table(kj))
    d = Path("experiments/dryrun")
    if not d.exists() or not list(d.glob("*__single.json")):
        print("# roofline: no dry-run artifacts found "
              "(run python -m repro.launch.dryrun --all); skipping")
        return
    recs = load_records(d, "single")
    print(f"\n# roofline_summary ({len(recs)} single-pod cells)")
    print(markdown_table(recs))


BENCHES = {
    "fig4": ("benchmarks.bench_mse_space", "Fig 4: MSE vs space"),
    "fig5": ("benchmarks.bench_delete_ratio", "Fig 5: MSE vs delete ratio"),
    "fig6": ("benchmarks.bench_update_time", "Fig 6: update time"),
    "fig7": ("benchmarks.bench_recall_precision", "Fig 7: recall/precision"),
    "quantiles": ("benchmarks.bench_quantiles",
                  "Figs 8-10 + dyadic bank throughput (BENCH_quantiles.json)"),
    "kernels": ("benchmarks.bench_kernels",
                "Pallas kernel parity/time + fused-vs-split race + "
                "sketch-ingest roofline (BENCH_kernels.json)"),
    "sharded": ("benchmarks.bench_sharded",
                "hash-sharded bank vs single sketch (BENCH_sharded.json)"),
    "elastic": ("benchmarks.bench_elastic",
                "live resize + fault recovery (BENCH_elastic.json)"),
    "compression": ("benchmarks.bench_compression", "grad compression bytes"),
    "h2o": ("benchmarks.bench_h2o_quality", "SS± KV-cache retention quality"),
    "family": ("benchmarks.bench_family",
               "SS± family frontier: double/unbiased/crprecis "
               "(BENCH_family.json)"),
    "service": ("benchmarks.bench_service",
                "multi-tenant service: heavy-traffic day, fused-vs-"
                "sessions race + roofline (BENCH_service.json)"),
    "analysis": ("benchmarks.bench_analysis",
                 "repro.analysis gate: rule counts + wall per layer "
                 "(BENCH_analysis.json)"),
}

# --smoke shape overrides: every bench still executes end to end (import,
# trace, compile, report) so bitrot fails CI, but at seconds-scale sizes
# and with JSON artifacts suppressed. Benches without size knobs already
# run at smoke scale (h2o decodes a smoke config; compression emulates 8
# CPU devices on tiny grads).
SMOKE_KW = {
    "fig4": dict(n_insert=2000, runs=1),
    "fig5": dict(n_total=4000, runs=1),
    "fig6": dict(runs=1, smoke=True),
    "fig7": dict(n_insert=2000, runs=1),
    "quantiles": dict(smoke=True, write_json=False),
    "kernels": dict(smoke=True, write_json=False),
    "sharded": dict(smoke=True, write_json=False),
    "elastic": dict(smoke=True, write_json=False),
    "compression": {},
    "h2o": {},
    "family": dict(smoke=True, write_json=False),
    "service": dict(smoke=True, write_json=False),
    "analysis": dict(smoke=True, write_json=False),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no JSON artifacts (CI bitrot guard)")
    args = ap.parse_args()

    from repro.platform import enable_compile_cache

    enable_compile_cache()
    names = [args.only] if args.only else list(BENCHES)
    t_all = time.time()
    failed = []
    for name in names:
        mod_name, desc = BENCHES[name]
        print(f"\n{'='*70}\n== {name}: {desc}\n{'='*70}", flush=True)
        t0 = time.time()
        if name == "compression":
            # needs 8 emulated CPU devices: run in a subprocess with
            # XLA_FLAGS so this process keeps its own device view. The
            # bench counts bytes, not time, and the child is held to the
            # CPU: where this process holds a chip, a child reaching for
            # it would fail or hang.
            import os
            import subprocess

            from repro.platform import xla_host_device_flags
            env = dict(os.environ)
            env["XLA_FLAGS"] = xla_host_device_flags(8)
            env["JAX_PLATFORMS"] = "cpu"
            out = subprocess.run(
                [sys.executable, "-m", mod_name], env=env,
                capture_output=True, text=True, timeout=600,
            )
            print(out.stdout)
            if out.returncode != 0:
                print(out.stderr[-1500:])
                failed.append(name)
        else:
            # one failing bench must neither abort the remaining benches
            # nor let the manifest loop exit 0 — record it and keep going
            # (the subprocess test in tests/test_bench_run.py pins this).
            try:
                mod = __import__(mod_name, fromlist=["run"])
                mod.run(**(SMOKE_KW[name] if args.smoke else {}))
            except Exception:
                import traceback

                traceback.print_exc()
                failed.append(name)
        status = "FAILED" if name in failed else "done"
        print(f"== {name} {status} in {time.time()-t0:.1f}s", flush=True)
    _roofline_summary()
    if failed:
        print(f"\nFAILED benches: {', '.join(failed)} "
              f"({time.time()-t_all:.1f}s)")
        return 1
    print(f"\nall benchmarks done in {time.time()-t_all:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
