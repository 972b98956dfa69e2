#!/usr/bin/env python3
"""The control of ``correct``, on the chip, at a cell's own size.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, one run of the cell as ``run.py`` makes it (its window,
its load), compared twice: as the program served it (the readings that
set each limit's lower end), and with the control of the
configuration's kind in the program's place (``compare(control=True)``
of ``chipbench/kinds/<kind>.py``), which has to fail. All seeds run in
one process, so set-up compiles once. Prints one JSON line per seed,
then the largest sound reading and the smallest control reading of each
compared number.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from chipbench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    from chipbench.harness import load_cell

    cell = load_cell(args.workload)
    devices = run.require_chips(cell.chips)
    run.use_compile_cache()
    sound, ctl = {}, {}
    for seed in args.seeds:
        out = run.execute(cell, seed, args.seconds, False, devices, bench,
                          t_start=time.perf_counter(), control=True)
        nums = {k: v["value"] for k, v in out["checks"].items()}
        for k, v in nums.items():
            sound[k] = max(sound.get(k, v), v)
        for k, v in out["control_checks"].items():
            ctl[k] = min(ctl.get(k, v), v)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "metrics": out["metrics"], "checks": nums,
                          "control_checks": out["control_checks"]}),
              flush=True)
    print(json.dumps({"largest_sound": sound, "smallest_control": ctl}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
