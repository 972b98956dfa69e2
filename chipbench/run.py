#!/usr/bin/env python3
"""The chip benchmark of the multi-tenant SpaceSaving± sketch service.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on (``chipbench/harness.py`` describes set-up and the window),
checks what the timed path produced against the plain reference of the
configuration's kind (``chipbench/kinds/<kind>.py``), and prints, as
the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``) and, last, ``checks``: each compared number beside
its limit, which also end standard error.

``--trace 0`` reports the cell's end-to-end metrics (host clock, tracing
off). ``--trace 1`` runs the same window under the JAX profiler and
reports the cell's per-layer metrics, each read by its own file under
``chipbench/metrics/``.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def fail(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chips(n: int):
    """The first ``n`` TPU devices; exits when JAX finds fewer."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no device: {e}")
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < n:
        fail(f"{n} chips needed, {len(devs)} found")
    return devs[:n]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def profile_options():
    """Device activity and the benchmark's own annotations only: no
    Python call tracing (millions of events a second) and no HLO protos,
    so a traced window stays small on disk and cheap on the host."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


@dataclasses.dataclass
class RunView:
    """What a per-layer metric's reader is given."""

    window: object
    config: object
    trace: object = None
    least_bytes: list = None
    peaks: dict = None


def per_layer_metrics(bench: dict, cell_name: str, view: RunView) -> dict:
    """Each per-layer metric of the cell, read by its own file; a reader
    that finds nothing returns None and the metric is left out."""
    out = {}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and cell_name not in cells:
            continue
        moved = e2e[m["moves"]]
        if cells is None and "workloads" in moved \
                and cell_name not in moved["workloads"]:
            continue
        reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
        v = reader.read(view)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def percentile(xs, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(xs, np.float64), q))


def host_stalls(win) -> str:
    """Where a slow window's time went on the host: the longest tick, the
    longest gap between ticks, the driver's worst lag (each with when it
    started, seconds into the window), the collector's pauses and the
    process's page faults."""
    spans = [(e - s, s) for s, e in win.ticks] or [(0.0, 0.0)]
    gaps = [(s1 - e0, e0) for (_, e0), (s1, _) in zip(win.ticks,
                                                       win.ticks[1:])]
    tick, gap = max(spans), max(gaps or [(0.0, 0.0)])
    lag = max(win.lags or [0.0])
    pauses = [p for _, p in win.gc_pauses]
    full = sum(1 for g, _ in win.gc_pauses if g == 2)
    return (f"host: tick_max_ms={tick[0] * 1e3:.3f}@{tick[1]:.3f} "
            f"gap_max_ms={gap[0] * 1e3:.3f}@{gap[1]:.3f} "
            f"lag_max_ms={lag * 1e3:.3f} gc_n={len(pauses)} "
            f"gc_gen2_n={full} gc_s={sum(pauses):.6f} "
            f"gc_max_ms={max(pauses or [0.0]) * 1e3:.3f} "
            f"minflt={win.page_faults[0]} majflt={win.page_faults[1]}")


def execute(cell, seed: int, seconds: float, trace: bool, devices,
            bench: dict, device_metrics: bool = True,
            t_start: float = T_START, fault=None,
            control: bool = False) -> dict:
    """One run of ``cell``; returns the result object (see the module
    docstring). ``device_metrics=False`` (the CPU rehearsal) writes "not
    measured" for every device number. ``fault`` breaks the timed path
    underneath (tests only). ``control`` also compares the kind's control
    (see ``chipbench/kinds/``) and returns its numbers under
    ``control_checks``."""
    import gc

    import jax

    from chipbench import cost
    from chipbench import trace as tr
    from chipbench.harness import CompileWatch, Driver, settle_host

    watch = CompileWatch()
    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda name: contextlib.nullcontext()))
    drv = Driver(cell, seed, seconds, annotate=annotate)
    log(f"traffic: ops={drv.traffic.n_ops} updates={drv.traffic.n_updates} "
        f"tenants_with_traffic={int((drv.traffic.sizes > 0).sum())} "
        f"traffic_s={drv.setup_times['traffic_s']:.3f} "
        f"bank_s={drv.setup_times['bank_s']:.3f} "
        f"since_start_s={time.perf_counter() - t_start:.3f}")
    t0 = time.perf_counter()
    drv.warm_up()
    if fault is not None:
        fault(drv)
    settle_host()
    log(f"warm-up: query_pads={drv.query_pads()} "
        f"cache_misses={watch.cache_misses} "
        f"warm_s={time.perf_counter() - t0:.3f}")
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(log_dir, profiler_options=profile_options())
    setup_s = time.perf_counter() - t_start
    try:
        win = drv.run(watch)
    finally:
        if trace:
            jax.profiler.stop_trace()
    if win.compiles:
        log(f"compiled inside the window: {win.compiles}")
    mem_peak = None
    if device_metrics:
        mem_peak = max(int(d.memory_stats().get("peak_bytes_in_use", 0))
                       for d in devices)
    st0, st1 = win.stats0, win.stats1
    log(f"window: seconds={win.seconds} ops={win.ops_submitted} "
        f"updates_acked={win.updates_acked} ticks={len(win.ticks)} "
        f"blocks={st1['blocks'] - st0['blocks']} "
        f"spills={st1['spills'] - st0['spills']} "
        f"admits={st1['admits'] - st0['admits']} "
        f"queries={len(win.query_lat)} wraps={win.wraps} "
        f"unacknowledged={win.unacknowledged}")
    log(host_stalls(win))

    # the window's blocks: their least bytes, for the roofline
    kind = drv.kind
    nb_window = sum(b1 - b0 for b0, b1, _, _ in drv.ticks[:len(win.ticks)])
    least = kind.least_bytes(cell.config, drv.svc.trace_blocks[:nb_window])

    summary = None
    if trace and device_metrics:
        summary = tr.summarize(tr.load(log_dir))
        log(f"trace: window_s={summary.window_s:.6f} "
            f"busy_s={summary.busy_s:.6f} programs="
            + json.dumps({k: [round(v[0], 6), v[1]]
                          for k, v in sorted(summary.programs.items())}))

    if log_dir:
        shutil.rmtree(log_dir, ignore_errors=True)

    # the check, once the window has closed and the peak was read
    t0 = time.perf_counter()
    sample = kind.draw_sample(drv.traffic.sizes,
                              {t for _, t in drv.svc.trace_admits}, seed)
    rec = drv.served.record(drv, sample, win)
    del drv
    gc.collect()
    res = kind.compare(rec, sample)
    nums = res["numbers"]
    correct = kind.verdict(nums)
    log(f"check: sample={sample} covered={res['covered']} "
        f"reference_s={time.perf_counter() - t0:.3f}")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": mem_peak if device_metrics
              else "not measured"}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    e2e_cells = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    if not trace:
        vals = {"updates_per_s": win.updates_acked / win.seconds,
                "setup_s": setup_s}
        if win.query_lat:
            vals["query_p95_ms"] = percentile(win.query_lat, 95) * 1e3
        if win.lags:
            vals["update_visible_p95_ms"] = percentile(
                win.update_lat, 95) * 1e3
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in vals.items() if k in units
                   and (e2e_cells[k] is None or cell.name in e2e_cells[k])}
    else:
        peaks = cost.peaks(dev.device_kind) if device_metrics else None
        view = RunView(window=win, config=cell.config, trace=summary,
                       least_bytes=least, peaks=peaks)
        metrics = per_layer_metrics(bench, cell.name, view)
        if device_metrics:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
    out = {"correct": bool(correct),
           "attempted": int(win.ops_submitted),
           "failed": int(win.unacknowledged),
           "metrics": metrics, "device": device}
    if trace and device_metrics:
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.top_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_by_host]}
    if control:
        out["control_checks"] = kind.compare(rec, sample,
                                             control=True)["numbers"]
    out["checks"] = {k: {"value": nums[k], "limit": kind.LIMITS[k]}
                     for k in kind.LIMITS}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    try:
        import repro.serve  # noqa: F401
    except ImportError as e:
        fail(f"the program (src/repro) is not in this checkout: {e}")
    with open(bench_path) as f:
        bench = json.load(f)
    from chipbench.harness import load_cell

    cell = load_cell(args.workload, bench_path)
    devices = require_chips(cell.chips)
    log(f"compile cache: {use_compile_cache()}")
    log(f"devices: {devices}")
    out = execute(cell, args.seed, args.seconds, bool(args.trace), devices,
                  bench)
    for k, v in out["checks"].items():
        log(f"check {k} = {v['value']} (limit {v['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
