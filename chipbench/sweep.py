#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate the
service sustains, by a sweep on the chip.

    python3 chipbench/sweep.py --workload <cell> --seconds <s> \
        --seed <n> --rates <r> [<r> ...]

Runs the cell once per rate (its mix's ``rate`` replaced), all in one
process, and prints per rate: updates acknowledged per second in the
window, the latency tails, how late the driver ran (in each half of the
window), and the operations due in the window still unsubmitted at its
close. A rate is sustained when the window acknowledges at least 95% of
what it offered and the driver's lag in the second half of the window
stays within twice that of the first (or of one flush interval): above
the knee the backlog grows for as long as the window lasts. The
open-loop cell then runs at about four fifths of the knee (its mix's
``rate``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from chipbench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    from chipbench.harness import Driver, load_cell, settle_host

    cell = load_cell(args.workload)
    if cell.mix.arrival != "open":
        run.fail(f"{args.workload} is not an open-loop cell")
    run.require_chips(cell.chips)
    run.use_compile_cache()
    flush = (cell.config.flush_interval_ms or 1e3)
    for rate in args.rates:
        c = dataclasses.replace(cell, mix=dataclasses.replace(
            cell.mix, rate=float(rate)))
        drv = Driver(c, args.seed, args.seconds)
        drv.warm_up()
        settle_host()
        t0 = time.perf_counter()
        win = drv.run()
        offered = drv.traffic.n_updates / args.seconds
        acked = win.updates_acked / args.seconds
        lag95 = float(np.percentile(win.lags, 95)) if win.lags else 0.0
        half = len(win.lags) // 2
        lag_halves = [float(np.percentile(h, 95)) * 1e3 if len(h) else 0.0
                      for h in (win.lags[:half], win.lags[half:])]
        st0, st1 = win.stats0, win.stats1
        print(json.dumps({
            "rate": rate, "offered_per_s": offered, "acked_per_s": acked,
            "query_p95_ms": float(np.percentile(win.query_lat, 95)) * 1e3,
            "update_visible_p95_ms":
                float(np.percentile(win.update_lat, 95)) * 1e3,
            "driver_lag_p95_ms": lag95 * 1e3,
            "driver_lag_p95_ms_by_half": lag_halves,
            "behind_at_close": win.behind_at_close,
            "ticks": len(win.ticks),
            "spills": st1["spills"] - st0["spills"],
            "admits": st1["admits"] - st0["admits"],
            "sustained": bool(acked >= 0.95 * offered
                              and lag_halves[1] < 2 * max(lag_halves[0],
                                                          flush)),
            "wall_s": time.perf_counter() - t0}), flush=True)
        del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
