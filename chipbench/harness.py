"""One run of one cell: set-up, the measured window, and what it left.

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json``, and what is particular to the configuration's kind
of sketch in ``chipbench/kinds/<kind>.py``. Set-up generates the traffic
from the seed, builds the kind's ``serve.SketchService`` over the
configuration, with its subscriptions, and warms up every program shape
the window uses: the ingest, the point-query pad sizes the traffic can
reach, and the kind's own programs. The window then drives the
service's public entry points (``submit``, ``query``, ``tick``) from one
thread:

* open loop: every operation is due at a fixed time from the window's
  start; the driver submits each once it is due, and ticks when the next
  update would overflow a block, or when the oldest pending operation
  has waited the configuration's flush interval;
* saturated: the next operation is always ready; the driver ticks when
  the next update would overflow a block.

A tick is the service's consistency barrier: an update is acknowledged,
and a query answered, when the tick that takes it returns. Latencies are
taken from the operation's due time to that return. After the close the
driver stops submitting new work, and ticks until every operation due
in the window is acknowledged (for at most a minute).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import resource
import time
from time import perf_counter_ns
from typing import Dict, List, Optional

import numpy as np

from chipbench import kinds
from chipbench.traffic import QUERY, UPDATE, Mix, Traffic, generate

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
DRAIN_S = 60.0


@dataclasses.dataclass(frozen=True)
class Config:
    """What the harness reads of a configuration file; ``params`` keeps
    every other key of the file for the kind module. ``kind`` is
    required: it names ``chipbench/kinds/<kind>.py``."""

    name: str
    kind: str
    tenants: int
    block: int
    spill_after: Optional[int] = None
    flush_interval_ms: Optional[float] = None
    params: dict = dataclasses.field(default_factory=dict)

    @staticmethod
    def load(path: str) -> "Config":
        with open(path) as f:
            d = json.load(f)
        if "kind" not in d:
            raise ValueError(f"{path}: a configuration names its kind")
        fields = {f.name for f in dataclasses.fields(Config)} - {"params"}
        return Config(**{k: v for k, v in d.items() if k in fields},
                      params={k: v for k, v in d.items()
                              if k not in fields})


@dataclasses.dataclass
class Cell:
    name: str
    config: Config
    mix: Mix
    chips: int


def load_cell(name: str, bench_path: Optional[str] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, its files found by name."""
    with open(bench_path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(name=name,
                config=Config.load(os.path.join(ROOT, conf["file"])),
                mix=Mix.load(os.path.join(BENCH_DIR, "mixes",
                                          w["traffic"] + ".json")),
                chips=int(w["chips"]))


class CompileWatch:
    """Counts programs traced or compiled while ``armed`` (JAX's own
    monitoring events), so a compile inside the window shows."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.armed = False
        self.seen: List[str] = []
        self.cache_misses = 0

        def on_duration(event, duration, **kw):
            if self.armed and event in self.EVENTS:
                self.seen.append(f"{event.rsplit('/', 1)[-1]}:"
                                 f"{kw.get('fun_name', '?')}")

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclasses.dataclass
class Window:
    """What the driver measured (host clock, seconds from the start)."""

    seconds: float
    updates_acked: int = 0            # by ticks that ended in the window
    ops_submitted: int = 0
    update_lat: List[float] = dataclasses.field(default_factory=list)
    query_lat: List[float] = dataclasses.field(default_factory=list)
    lags: List[float] = dataclasses.field(default_factory=list)
    submit_s: float = 0.0             # summed span of submit calls
    submit_updates: int = 0
    ticks: List[tuple] = dataclasses.field(default_factory=list)  # (t0, t1)
    compiles: List[str] = dataclasses.field(default_factory=list)
    stats0: Dict[str, int] = dataclasses.field(default_factory=dict)
    stats1: Dict[str, int] = dataclasses.field(default_factory=dict)
    unacknowledged: int = 0
    behind_at_close: int = 0          # operations due but not submitted
    wraps: int = 0
    # the host under the window: each collector pause (generation,
    # seconds) and the page faults (minor, major) of this process
    gc_pauses: List[tuple] = dataclasses.field(default_factory=list)
    page_faults: tuple = (0, 0)


class Driver:
    """The service under one cell's traffic, with what the check needs."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 annotate=None):
        c = cell.config
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.kind = kinds.load(c.kind)
        t0 = time.perf_counter()
        self.traffic: Traffic = generate(cell.mix, c.tenants, seed, seconds)
        t1 = time.perf_counter()
        self.served = self.kind.Served(cell, self.traffic)
        self.svc = self.served.svc
        self.setup_times = {"traffic_s": t1 - t0,
                            "bank_s": time.perf_counter() - t1}
        self.flush_s = (None if c.flush_interval_ms is None
                        else c.flush_interval_ms / 1000.0)
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        # per tick since the service was built, what the driver handed
        # it: (its update operations in submission order, the tenants it
        # queried); an update is an op index of the traffic, or a
        # (tenant, items, weights) of the warm-up
        self.log: List[tuple] = []

    # -- set-up ------------------------------------------------------------

    def query_pads(self) -> List[int]:
        """Pad sizes of the batched point query the window can reach: up
        to the keys of every query due within two seconds."""
        tr = self.traffic
        q = tr.kind == QUERY
        if not q.any():
            return []
        if tr.due is None:
            most = int(q.sum())
        else:
            d = tr.due[q]
            most = int((np.searchsorted(d, d + 2.0) - np.arange(len(d)))
                       .max())
        keys = most * self.cell.mix.query_keys
        pads, p = [], 128
        while True:
            pads.append(p)
            if p >= keys:
                return pads
            p *= 2

    def warm_up(self) -> None:
        """Compile every program shape the window uses; change no row.

        A zero-weight update ingests one all-padding block; one tick per
        pad size runs the batched query; the kind warms up its own
        programs (``Served.warm_up``). What it submits is logged like the
        window's operations.
        """
        import jax

        svc = self.svc
        quiet = int(np.argmin(self.traffic.sizes))  # least traffic
        zero = np.zeros(1, np.int32)
        ups, qs = [], []

        def tick():
            svc.tick()
            self.log.append((list(ups), list(qs)))
            ups.clear()
            qs.clear()

        svc.submit(quiet, zero, zero)
        ups.append((quiet, zero, zero))
        tick()
        probe = np.zeros(self.cell.mix.query_keys, np.int32)
        for pad in self.query_pads():
            for _ in range(pad // self.cell.mix.query_keys):
                svc.query(quiet, probe)
                qs.append(quiet)
            tick()

        def query(tenant, items):
            svc.query(tenant, items)
            qs.append(tenant)

        self.served.warm_up(tick, query, quiet)
        jax.block_until_ready(svc.session.state)

    # -- the window --------------------------------------------------------

    def run(self, watch: Optional[CompileWatch] = None) -> Window:
        svc, tr = self.svc, self.traffic
        B = self.cell.config.block
        win = Window(seconds=self.seconds)
        svc.trace_blocks = []
        svc.trace_admits = []
        self.first_tick = len(self.log)
        self.ticks: List[tuple] = []       # (b0, b1, a0, a1) of the trace
        pend_upd: List[int] = []           # pending update ops
        pend_q: List[tuple] = []           # (op, ticket)
        oldest = [None]                    # due of the oldest pending op
        pending = [0]
        kind, tenant, start, length = tr.kind, tr.tenant, tr.start, tr.length
        keys, weights, due = tr.keys, tr.weights, tr.due
        n = tr.n_ops
        op_due = np.zeros(n) if due is None else due
        acked = np.zeros(n, bool)
        tick_of_query: Dict[int, tuple] = {}
        if watch is not None:
            watch.armed = True
        win.stats0 = dict(svc.stats)
        clock = time.perf_counter
        t0 = clock()

        def tick(closed: bool):
            b0, a0 = len(svc.trace_blocks), len(svc.trace_admits)
            s = clock()
            with self.annotate("chipbench.tick"):
                svc.tick()
            e = clock()
            i = len(self.ticks)
            self.ticks.append((b0, len(svc.trace_blocks), a0,
                               len(svc.trace_admits)))
            self.log.append((list(pend_upd),
                             [int(tenant[op]) for op, _ in pend_q]))
            now = e - t0
            if not closed:
                win.ticks.append((s - t0, now))
            for op in pend_upd:
                acked[op] = True
                win.update_lat.append(now - op_due[op])
                if not closed and now <= win.seconds:
                    win.updates_acked += int(length[op])
            for op, ticket in pend_q:
                acked[op] = True
                win.query_lat.append(now - op_due[op])
                tick_of_query[op] = (i, ticket.result())
            self.served.after_tick(i)
            pend_upd.clear()
            pend_q.clear()
            pending[0] = 0
            oldest[0] = None

        def submit(op: int, now: float):
            s0, ln, t = start[op], length[op], int(tenant[op])
            if kind[op] == UPDATE:
                a = clock()
                svc.submit(t, keys[s0:s0 + ln], weights[s0:s0 + ln])
                win.submit_s += clock() - a
                win.submit_updates += int(ln)
                pend_upd.append(op)
                pending[0] += int(ln)
            else:
                pend_q.append((op, svc.query(t, keys[s0:s0 + ln])))
            if oldest[0] is None:
                oldest[0] = op_due[op]
            win.ops_submitted += 1

        def loop():
            i = 0
            if due is None:
                # saturated: the next operation is always ready
                while True:
                    now = clock() - t0
                    if now >= win.seconds:
                        break
                    op = i % n
                    if op == 0 and i:
                        win.wraps += 1
                    if kind[op] == UPDATE and pending[0] + length[op] > B:
                        tick(False)
                        continue
                    op_due[op] = now
                    submit(op, now)
                    i += 1
                last = i
            else:
                while True:
                    now = clock() - t0
                    if now >= win.seconds:
                        break
                    if i < n and due[i] <= now:
                        if kind[i] == UPDATE and pending[0] + length[i] > B:
                            tick(False)
                            continue
                        win.lags.append(now - due[i])
                        submit(i, now)
                        i += 1
                        continue
                    if oldest[0] is not None and self.flush_s is not None \
                            and now - oldest[0] >= self.flush_s:
                        tick(False)
                        continue
                    wake = min(due[i] if i < n else win.seconds,
                               win.seconds,
                               oldest[0] + self.flush_s
                               if oldest[0] is not None and self.flush_s
                               else win.seconds)
                    if wake - now > 2e-4:
                        with self.annotate("chipbench.wait"):
                            time.sleep(wake - now - 1e-4)
                last = int(np.searchsorted(due, win.seconds, side="left"))
            return i, last

        gc_start = [0]

        def on_gc(phase, info):   # on a clock the window never reads
            if phase == "start":
                gc_start[0] = perf_counter_ns()
            else:
                win.gc_pauses.append((info["generation"], 1e-9 * (
                    perf_counter_ns() - gc_start[0])))

        ru0 = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(on_gc)
        try:
            with self.annotate("chipbench.window"):
                i, last = loop()
        finally:
            gc.callbacks.remove(on_gc)
        ru1 = resource.getrusage(resource.RUSAGE_SELF)
        win.page_faults = (ru1.ru_minflt - ru0.ru_minflt,
                           ru1.ru_majflt - ru0.ru_majflt)
        win.stats1 = dict(svc.stats)
        win.behind_at_close = max(0, last - i)
        if watch is not None:
            watch.armed = False
            win.compiles = list(watch.seen)

        # after the close: acknowledge everything due in the window
        drain_end = clock() + DRAIN_S
        while clock() < drain_end:
            if due is not None and i < last:
                if kind[i] == UPDATE and pending[0] + length[i] > B:
                    tick(True)
                    continue
                submit(i, clock() - t0)
                i += 1
                continue
            if pend_upd or pend_q:
                tick(True)
            break
        win.unacknowledged = int((~acked[:min(last, n)]).sum())
        self.tick_of_query = tick_of_query
        return win


def settle_host() -> None:
    """Collect set-up's garbage and freeze it out of later collections,
    so the window's collector pauses scan only the window's objects."""
    gc.collect()
    gc.freeze()
