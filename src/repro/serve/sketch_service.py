"""SketchService: the multi-tenant sketch-serving loop.

``serve/engine.py`` turned the model stack's step functions into a
batched serving loop; this module does the same for the sketch stack.
One service hosts ONE ``SketchSpec(tenants=T)`` layout — a single
(T*S, k) bank — and turns interleaved per-tenant traffic into the
engine's favorite shape: a few exactly block-sized fused launches per
tick instead of one dispatch per tenant.

The loop (``tick``) is the serving analogue of the engine's decode
step, and every stage is batched across tenants:

  1. **re-admission** — spilled tenants touched by this tick's traffic
     or queries re-admit FIRST, all at once (``tenant.admit_tenants`` —
     exact, via ``state.merge`` against their cleared rows), so no
     update or query ever sees a cold row;
  2. **coalesced ingest** — every tenant's pending fragments (packed to
     composite keys at ``submit`` time) concatenate, in deterministic
     tenant order, with the window expiries that came due
     (``StreamSession.schedule_batch`` — per-tenant horizons), and the
     combined stream chunks into zero-weight-padded blocks fed through
     the PR 8 :class:`~repro.sketch.session.BlockFeeder` double-buffered
     path: host staging of block i overlaps device compute of i-1;
  3. **batched point queries** — every ticket's keys answer in ONE
     owner-row gather (``api.query_many``), then slice back per ticket;
  4. **subscriptions** — due continuous top-k subscriptions answer in
     ONE batched row gather (``tenant.topk_tenants``) when the layout
     allows (base axis, uniform m), else per tenant; quantile
     subscriptions run the per-tenant lockstep search
     (``tenant.tenant_quantile_many``) on a composite-key dyadic bank;
  5. **eviction** — tenants idle for ``spill_after`` ticks (no traffic,
     no subscription) spill their rows to tagged numpy dicts, all in
     one device read (``tenant.spill_tenants``), and their rows clear
     in place; the bank keeps serving everyone else.

A tick is the service's consistency barrier: after ``tick()`` returns,
every update submitted before it is visible to every query answered by
it, exactly once (the feeder flush joins the device).

Telemetry: each stage that has work runs inside a span — ``admit``,
``ingest``, ``query``, ``subscriptions``, ``spill`` (every tick while
spill is on: its scan for idle tenants is work) — and every point
where the tick blocks on the device (the feeder's waits, the query and
top-k reads, the spill read) inside a nested ``wait`` span. A span is
a ``jax.profiler.TraceAnnotation`` named ``sketch.<name>`` carrying
``tick=<n>``, so it lands on a profiler trace's host plane, on the
device's clock; it also adds its nanoseconds and a count to
``stats['<name>_ns']`` and ``stats['<name>_n']``. ``wait_n`` is thus
the tick's host syncs. ``stats['ingest_chunks']`` counts the trips of
the ingest's chunk loop the fed blocks take (``bank.ingest_chunks``).
Every value in ``stats`` is a flat int, so ``dict(svc.stats)`` is a
snapshot.

Crash/resume: ``save()`` bundles the session checkpoint WITH schedule
(per-tenant window FIFOs ride the ``sched_batch_tenants`` tags), the
spill store and the tick cursor; ``load`` of that bundle resumes
bit-identically (tests/test_sketch_service.py races a crashed service
against an uninterrupted twin).
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.sketch import api
from repro.sketch import bank as bk
from repro.sketch import tenant as tn
from repro.sketch.session import BlockFeeder, StreamSession


# smallest padded key count of a tick's batched point query
_MIN_QUERY_PAD = 128

# the tick's spans (see the module docstring), each with its stats keys
_SPANS = ("admit", "ingest", "query", "subscriptions", "spill", "wait")


class _Span:
    """One ``with`` block of the tick: a profiler annotation
    ``sketch.<name>`` (metadata ``tick``), timed into ``stats``."""

    __slots__ = ("_stats", "_keys", "_note", "_t0")

    _KEYS = {n: (f"sketch.{n}", f"{n}_ns", f"{n}_n") for n in _SPANS}

    def __init__(self, stats: Dict[str, int], name: str, tick: int):
        self._stats = stats
        self._keys = keys = self._KEYS[name]
        self._note = jax.profiler.TraceAnnotation(keys[0], tick=tick)

    def __enter__(self):
        self._note.__enter__()
        self._t0 = time.perf_counter_ns()

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self._t0
        self._note.__exit__(*exc)
        _, ns, n = self._keys
        self._stats[ns] += dt
        self._stats[n] += 1


class QueryTicket:
    """One pending point-query: resolves at the next ``tick``.

    ``result()`` forces a tick if still unresolved — a query is never
    answered from a state older than the updates submitted before it.
    ``latency_s`` (valid once resolved) is resolve-time minus
    submit-time: the number the service bench quotes as p99.
    """

    __slots__ = ("tenant", "items", "_service", "_value",
                 "t_submit", "t_resolve")

    def __init__(self, service: "SketchService", tenant: int,
                 items: np.ndarray):
        self._service = service
        self.tenant = int(tenant)
        self.items = items
        self._value: Optional[np.ndarray] = None
        self.t_submit = time.perf_counter()
        self.t_resolve: Optional[float] = None

    def result(self) -> np.ndarray:
        if self._value is None:
            self._service.tick()
        assert self._value is not None  # tick resolves every ticket
        return self._value

    @property
    def resolved(self) -> bool:
        return self._value is not None

    @property
    def latency_s(self) -> float:
        if self.t_resolve is None:
            raise ValueError("ticket not resolved yet; call result() "
                             "or tick() first")
        return self.t_resolve - self.t_submit


class SketchService:
    """Multi-tenant serving front-end over one ``SketchSpec``.

    Frequency mode (``spec.tenants`` set): per-tenant counts / top-k on
    the (T*S, k) tenant bank, any registered variant (sspm / lazy /
    double / unbiased). Quantile mode (``spec.kind == 'quantile'``):
    pass ``tenant_bits`` — the composite-key dyadic layout; per-tenant
    quantile subscriptions, no top-k, no spill.

    ``window``: per-tenant bounded-deletion horizon in TICKS — after
    ``window`` further ticks with traffic from tenant t, a tick's batch
    expires (re-ingests negated) on t's own schedule. ``spill_after``:
    spill a tenant's rows after that many idle ticks (base frequency
    axis only). ``depth``: feeder in-flight depth.
    """

    def __init__(self, spec: api.SketchSpec, *, block: int = 8192,
                 window: Optional[int] = None, depth: int = 2,
                 spill_after: Optional[int] = None,
                 tenant_bits: Optional[int] = None, donate: bool = True):
        if spec.kind == "quantile":
            if tenant_bits is None:
                raise ValueError(
                    "quantile-mode service needs tenant_bits: the dyadic "
                    "spec has no tenants axis, so the key split "
                    "(tenant_bits high | item_bits low) must be given")
            if spec.shards is not None:
                raise ValueError(
                    "quantile-mode service supports unsharded dyadic "
                    "specs only (tenant_rank_many reads one DyadicState)")
            if spill_after is not None:
                raise ValueError(
                    "spill is row-granular; the dyadic layout has no "
                    "per-tenant rows to spill — use spill_after=None")
            if tenant_bits < 1 or tenant_bits >= spec.bits:
                raise ValueError(
                    f"tenant_bits={tenant_bits} must leave item bits: "
                    f"0 < tenant_bits < bits={spec.bits}")
            self.num_tenants = 1 << tenant_bits
            self.item_bits = spec.bits - tenant_bits
        else:
            if spec.tenants is None:
                raise ValueError(
                    "frequency-mode service needs a tenant layout: build "
                    "the spec with tenants=T (SketchSpec(tenants=...))")
            if tenant_bits is not None:
                raise ValueError(
                    "tenant_bits is the quantile-mode key split; "
                    "frequency specs carry tenants= in the spec itself")
            self.num_tenants = spec.tenants
            self.item_bits = spec.bits
        self.spec = spec
        self.session = StreamSession(spec, block=block, window=window,
                                     donate=donate)
        self.stats = {"updates": 0, "queries": 0, "ticks": 0, "blocks": 0,
                      "spills": 0, "admits": 0, "ingest_chunks": 0}
        for name in _SPANS:
            self.stats[f"{name}_ns"] = self.stats[f"{name}_n"] = 0
        self._wait = functools.partial(self._span, "wait")
        self.feeder = BlockFeeder(self.session, depth=depth,
                                  wait=self._wait)
        self.spill_after = spill_after
        if spill_after is not None and not self._spillable():
            raise ValueError(
                f"spill_after needs the base tenant-bank layout (variant "
                f"sspm/lazy); variant={spec.variant!r} keeps all rows "
                f"resident — use spill_after=None")
        # per-tenant pending (items, weights) fragments, composite keys
        self._pending: Dict[int, List[Tuple[np.ndarray, np.ndarray]]] = {}
        self._tickets: List[QueryTicket] = []
        self._topk_subs: Dict[int, Dict[str, Any]] = {}
        self._quant_subs: Dict[int, Dict[str, Any]] = {}
        self._spilled: Dict[int, Dict[str, Any]] = {}
        self._last_active: Dict[int, int] = {}
        self._tick = 0
        # optional parity hook: a list here records every (items,
        # weights) block fed, so a bench can replay the exact block
        # sequence through tenant.reference_row_update; with it set,
        # ``trace_admits`` records (len(trace_blocks), tenant) at each
        # re-admission, whose merge reorders the tenant's slots
        self.trace_blocks: Optional[List[Tuple[np.ndarray, np.ndarray]]] \
            = None
        self.trace_admits: List[Tuple[int, int]] = []
        # the router the engine ingests with, for the chunk count
        self._router = (tn.router_for(self.num_tenants, self.item_bits,
                                      spec.shards or 1)
                        if isinstance(self.session.state, tn.TenantBank)
                        else None)

    def _spillable(self) -> bool:
        return isinstance(self.session.state, tn.TenantBank)

    def _span(self, name: str) -> _Span:
        return _Span(self.stats, name, self._tick)

    @property
    def tick_count(self) -> int:
        return self._tick

    # -- traffic intake ----------------------------------------------------

    def _check_tenant(self, tenant: int) -> int:
        tenant = int(tenant)
        if not 0 <= tenant < self.num_tenants:
            raise ValueError(
                f"tenant {tenant} out of range [0, {self.num_tenants})")
        return tenant

    def _pack(self, tenant: int, items) -> np.ndarray:
        items = np.asarray(items).ravel()
        if len(items) and (items.min() < 0
                           or items.max() >= (1 << self.item_bits)):
            raise ValueError(
                f"items must lie in [0, 2^{self.item_bits}) — larger ids "
                f"would alias another tenant's key range; rebucket or "
                f"raise bits")
        keys = tn.pack_keys(np.full(len(items), tenant, np.int64),
                            items.astype(np.int64), self.item_bits)
        return keys.astype(np.int64)

    def submit(self, tenant: int, items, weights=None) -> None:
        """Queue one tenant's signed weighted updates for the next tick
        (``weights=None`` = unit inserts; negative weights = deletions).
        """
        tenant = self._check_tenant(tenant)
        keys = self._pack(tenant, items)
        if weights is None:
            weights = np.ones(len(keys), np.int32)
        else:
            weights = np.asarray(weights).ravel()
        api.validate_block(self.spec, keys, weights)
        self._pending.setdefault(tenant, []).append(
            (keys.astype(np.int32), weights.astype(np.int32)))
        self.stats["updates"] += len(keys)

    def query(self, tenant: int, items) -> QueryTicket:
        """Point-query estimates for one tenant's raw items; resolves at
        the next ``tick`` (or on ``result()``)."""
        tenant = self._check_tenant(tenant)
        items = np.asarray(items).ravel()
        ticket = QueryTicket(self, tenant, items)
        self._tickets.append(ticket)
        self.stats["queries"] += len(items)
        return ticket

    # -- continuous subscriptions ------------------------------------------

    def subscribe_topk(self, tenant: int, m: int, every: int = 1) -> None:
        """Refresh tenant's top-m each ``every`` ticks (``topk_result``)."""
        if self.spec.kind != "frequency":
            raise ValueError("top-k subscriptions need a frequency spec")
        tenant = self._check_tenant(tenant)
        self._topk_subs[tenant] = {
            "m": int(m), "every": max(1, int(every)),
            "due": self._tick, "value": None}

    def subscribe_quantile(self, tenant: int, qs, every: int = 1) -> None:
        """Refresh tenant's quantiles each ``every`` ticks
        (``quantile_result``)."""
        if self.spec.kind != "quantile":
            raise ValueError(
                "quantile subscriptions need a quantile-mode service "
                "(SketchSpec(kind='quantile') + tenant_bits)")
        tenant = self._check_tenant(tenant)
        self._quant_subs[tenant] = {
            "qs": np.asarray(qs, np.float32).ravel(),
            "every": max(1, int(every)), "due": self._tick, "value": None}

    def unsubscribe(self, tenant: int) -> None:
        self._topk_subs.pop(int(tenant), None)
        self._quant_subs.pop(int(tenant), None)

    def topk_result(self, tenant: int):
        return self._topk_subs[int(tenant)]["value"]

    def quantile_result(self, tenant: int):
        return self._quant_subs[int(tenant)]["value"]

    # -- the serving loop --------------------------------------------------

    def tick(self) -> None:
        """One batched service step (see the module docstring's stages)."""
        # 1) exact re-admission before any of this tick's work
        touched = set(self._pending) | {t.tenant for t in self._tickets}
        readmit = sorted(touched & set(self._spilled))
        if readmit:
            with self._span("admit"):
                self._admit(readmit)
        # 2) coalesce updates + due window expiries across tenants
        if self._pending:
            with self._span("ingest"):
                self._ingest_pending()
        # 3) all point queries in one owner-row gather, the key count
        # padded to a power of two so a day of ticks compiles the
        # gather a handful of times, not once per distinct count
        if self._tickets:
            with self._span("query"):
                all_keys = np.concatenate(
                    [self._pack(t.tenant, t.items) for t in self._tickets])
                n_pad = max(_MIN_QUERY_PAD,
                            1 << (len(all_keys) - 1).bit_length())
                all_keys = np.pad(all_keys, (0, n_pad - len(all_keys)))
                est = api.query_many(
                    self.spec, self.session.state,
                    jnp.asarray(all_keys.astype(np.int32)))
                with self._wait():
                    est = np.asarray(est)
                now = time.perf_counter()
                s = 0
                for t in self._tickets:
                    n = len(t.items)
                    t._value = est[s:s + n]
                    t.t_resolve = now
                    s += n
                self._tickets.clear()
        # 4) due subscriptions, batched where the layout allows
        if self._topk_subs or self._quant_subs:
            with self._span("subscriptions"):
                self._refresh_subscriptions()
        # 5) evict cold tenants
        if self.spill_after is not None:
            with self._span("spill"):
                self._spill_idle()
        self._tick += 1
        self.stats["ticks"] += 1

    def _ingest_pending(self) -> None:
        frags_i: List[np.ndarray] = []
        frags_w: List[np.ndarray] = []
        ends: List[int] = []   # end of each tenant's run in the stream
        for t in sorted(self._pending):
            parts = self._pending[t]
            ki = (np.concatenate([i for i, _ in parts])
                  if len(parts) > 1 else parts[0][0])
            kw = (np.concatenate([w for _, w in parts])
                  if len(parts) > 1 else parts[0][1])
            frags_i.append(ki)
            frags_w.append(kw)
            n = len(ki)
            # the tick's batch ages on tenant t's OWN horizon; expiries
            # due now join the same coalesced stream (after the batch)
            for di, dw in self.session.schedule_batch(ki, kw, tenant=t):
                frags_i.append(di)
                frags_w.append(dw)
                n += len(di)
            ends.append((ends[-1] if ends else 0) + n)
            self._last_active[t] = self._tick
        self._pending.clear()
        if not frags_i:
            return
        items = (np.concatenate(frags_i) if len(frags_i) > 1
                 else frags_i[0])
        weights = (np.concatenate(frags_w) if len(frags_w) > 1
                   else frags_w[0])
        B = self.session.block
        for s in range(0, len(items), B):
            ci, cw = items[s:s + B], weights[s:s + B]
            pad = B - len(ci)
            if pad:
                ci = np.pad(ci, (0, pad))  # weight-0 tail = padding
                cw = np.pad(cw, (0, pad))
            if self.trace_blocks is not None:
                self.trace_blocks.append((ci.copy(), cw.copy()))
            self.feeder.feed(ci, cw)
            self.stats["blocks"] += 1
        self.stats["ingest_chunks"] += self._chunks(np.asarray(ends), B)
        self.feeder.flush()  # the tick's consistency barrier

    def _chunks(self, ends: np.ndarray, B: int) -> int:
        """Trips of the ingest's chunk loop over this tick's blocks.

        One tenant owns one row on the chunked path, and ``ends`` closes
        each tenant's run of the coalesced stream, so a block reaches the
        runs that overlap it: those starting before its end, less those
        ending at or before its start. A run counts as reached whatever
        its weights."""
        lo = np.arange(0, int(ends[-1]), B)   # each block's start
        if self._router is None:
            return len(lo)
        starts = np.r_[0, ends[:-1]]
        reached = (np.searchsorted(starts, lo + B, side="left")
                   - np.searchsorted(ends, lo, side="right"))
        k = self.session.state.bank.ids.shape[1]
        return int(bk.ingest_chunks(self._router, B, k, reached).sum())

    def _refresh_subscriptions(self) -> None:
        due_topk = [t for t, s in self._topk_subs.items()
                    if self._tick >= s["due"] and t not in self._spilled]
        if due_topk:
            base = isinstance(self.session.state, tn.TenantBank)
            ms = {self._topk_subs[t]["m"] for t in due_topk}
            if base and len(ms) == 1:
                m = ms.pop()
                shards = self.spec.shards or 1
                items, vals = tn.topk_tenants(
                    self.session.state, jnp.asarray(due_topk, jnp.int32),
                    m, num_shards=shards, item_bits=self.item_bits)
                with self._wait():
                    items = np.asarray(items)
                with self._wait():
                    vals = np.asarray(vals)
                for i, t in enumerate(due_topk):
                    self._topk_subs[t]["value"] = (items[i], vals[i])
            else:
                for t in due_topk:
                    sub = self._topk_subs[t]
                    ids, vals = api.tenant_topk(
                        self.spec, self.session.state, t, sub["m"])
                    with self._wait():
                        ids = np.asarray(ids)
                    with self._wait():
                        vals = np.asarray(vals)
                    sub["value"] = (ids, vals)
            for t in due_topk:
                self._topk_subs[t]["due"] = self._tick \
                    + self._topk_subs[t]["every"]
        for t, sub in self._quant_subs.items():
            if self._tick < sub["due"]:
                continue
            qv = tn.tenant_quantile_many(
                self.session.state, t, jnp.asarray(sub["qs"]),
                self.item_bits)
            with self._wait():
                sub["value"] = np.asarray(qv)
            sub["due"] = self._tick + sub["every"]

    def _spill_idle(self) -> None:
        keep = set(self._topk_subs) | set(self._quant_subs) \
            | set(self._pending)
        idle = [t for t, last in self._last_active.items()
                if t not in keep and t not in self._spilled
                and self._tick - last >= self.spill_after]
        if not idle:
            return
        bank, spills = tn.spill_tenants(
            self.session.state.bank, idle, self.spec.shards or 1,
            self.item_bits, wait=self._wait, donate=self.session.donate)
        self.session.state = tn.TenantBank(bank=bank)
        self._spilled.update(zip(idle, spills))
        self.stats["spills"] += len(idle)

    def _admit(self, tenants: List[int]) -> None:
        if self.trace_blocks is not None:
            self.trace_admits.extend(
                (len(self.trace_blocks), t) for t in tenants)
        bank = tn.admit_tenants(
            self.session.state.bank, [self._spilled.pop(t) for t in tenants],
            donate=self.session.donate)
        self.session.state = tn.TenantBank(bank=bank)
        for t in tenants:
            self._last_active[t] = self._tick
        self.stats["admits"] += len(tenants)

    # -- synchronous conveniences ------------------------------------------

    def tenant_snapshot(self, tenant: int) -> Dict[str, Any]:
        """A tenant's counters in the spill format (``tenant.spill_rows``)
        as they stand, whether resident or spilled; evicts nothing."""
        tenant = self._check_tenant(tenant)
        if tenant in self._spilled:
            return dict(self._spilled[tenant])
        return tn.spill_rows(self.session.state.bank, tenant,
                             self.spec.shards or 1, self.item_bits)

    def _settle(self, tenant: Optional[int] = None) -> None:
        if self._pending or self._tickets:
            self.tick()
        if tenant is not None and tenant in self._spilled:
            self._admit([tenant])

    def topk(self, tenant: int, m: int):
        """Current top-m for one tenant (raw items, counts); settles
        pending traffic first."""
        tenant = self._check_tenant(tenant)
        self._settle(tenant)
        ids, vals = api.tenant_topk(self.spec, self.session.state,
                                    tenant, m)
        return np.asarray(ids), np.asarray(vals)

    def quantile(self, tenant: int, qs) -> np.ndarray:
        """Current per-tenant quantiles (quantile mode); settles first."""
        tenant = self._check_tenant(tenant)
        self._settle(tenant)
        return np.asarray(tn.tenant_quantile_many(
            self.session.state, tenant,
            jnp.asarray(np.asarray(qs, np.float32).ravel()),
            self.item_bits))

    # -- crash / resume ----------------------------------------------------

    def save(self) -> Dict[str, Any]:
        """Checkpoint bundle: session (WITH per-tenant schedule), the
        spill store and the tick cursor. Pending (unticked) traffic and
        unresolved tickets are deliberately NOT checkpointed — a tick is
        the durability boundary, as a request is only acknowledged by
        the tick that ingests it."""
        return {
            "session": self.session.save(include_schedule=True),
            "spilled": {int(t): dict(d) for t, d in self._spilled.items()},
            "tick": int(self._tick),
            "last_active": {int(t): int(v)
                            for t, v in self._last_active.items()},
        }

    def load(self, d: Dict[str, Any]) -> None:
        self.session.load(d["session"])
        self.feeder = BlockFeeder(self.session, depth=self.feeder.depth,
                                  wait=self._wait)
        self._spilled = {int(t): dict(v) for t, v in d["spilled"].items()}
        self._last_active = {int(t): int(v)
                             for t, v in d["last_active"].items()}
        self._tick = int(d["tick"])
        self._pending.clear()
        self._tickets.clear()


__all__ = ["QueryTicket", "SketchService"]
