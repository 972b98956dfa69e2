"""StreamSession: the stateful host-side companion of the sketch API.

Every consumer of the sketch package used to hand-roll the same glue:
``stats._SketchBank`` chunked-and-padded batches to a fixed block,
``examples/quantile_monitor.py`` buffered observations and scheduled
sliding-window expiry deletions, and the benches re-spelled the
pad-and-feed loop per script.  :class:`StreamSession` owns that
machinery once, on top of the functional ``repro.sketch.api`` surface:

  * **block buffering** — ``observe``/``extend`` accumulate updates
    host-side (numpy, no per-item python lists for array input) and
    flush full fixed-size blocks, zero-weight padding the tail, so the
    jitted ingest traces ONE (spec, block) shape;
  * **cached jitted ingest** — one compiled update per (spec, block),
    shared across sessions via a process-lifetime cache keyed on the
    hashable spec (intentionally unbounded: evicting would silently
    retrace live sessions); state buffers are donated on accelerators
    (the CPU backend cannot reuse donated buffers, so donation is
    skipped there to avoid the per-call warning);
  * **windowed deletion scheduling** — the paper's bounded-deletion
    regime by construction: ``push`` expires whole batches after
    ``window`` pushes (the stats trackers), ``observe`` expires
    individual items after ``window`` observations (the quantile
    monitor); expiries re-ingest with negated weights and the
    insertion/deletion totals track the empirical alpha;
  * **queries / merge / checkpointing** — thin delegations to the api
    (each flushes pending updates first), with ``save``/``load``
    speaking the tagged checkpoint dicts *and* the pre-redesign stats
    layouts (``api.infer_spec`` adapts kind/shards to what the dict
    actually holds); ``save(include_schedule=True)`` additionally
    serializes the scheduling state (buffer, expiry FIFOs, counters,
    block cursor) so a crash/resume round-trip loses and double-counts
    nothing;
  * **fault tolerance hooks** — an optional block ``replay`` log (the
    last N ingested blocks, keyed by a monotone block sequence number)
    feeds ``repro.sketch.elastic.recover_session``; an optional
    ``fault_plan`` (``repro.sketch.faults.FaultPlan``) injects
    drop/duplicate/corrupt/delay faults at the block boundary — the
    replay log records the INTENDED block before injection, so recovery
    restores the truth; an optional ``monitor``
    (``repro.train.straggler.StragglerMonitor``) observes per-shard
    flush timings (inflated by injected delays) so a slow shard walks
    the straggler → flag → recovery path.

Ingest through a session is bit-identical to calling ``api.update``
(and therefore the direct engine/client spellings) on the same padded
blocks — the session adds scheduling, never semantics.  Measured
overhead at the headline bench cells is <5% vs the raw fused engine
call (BENCH_sharded.json / BENCH_quantiles.json ``session_overhead``).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

import jax

from . import api
from .api import SketchSpec


def ingest_cache_spec(spec: SketchSpec) -> SketchSpec:
    """Normalize a spec to its compiled-ingest cache identity.

    The jitted ingest's trace depends on the spec only through what the
    adapter's ``update`` actually reads: kind / variant / backend / bits
    / shards (+ the state SHAPES, which jit keys on by itself). The
    tenant axis deliberately keeps the update path tenant-count-blind —
    adapters derive the tenant count from the state's leading axis — so
    a thousand per-tenant layouts that agree on those fields share ONE
    cache entry instead of growing the process-lifetime cache without
    bound. Tenant specs therefore collapse onto a ``tenants=1``
    canonical form (capacity folded back into a plain ``k``); non-tenant
    specs are their own identity.
    """
    if spec.tenants is None:
        return spec
    changes = {"tenants": 1, "tenant_caps": None}
    if spec.tenant_caps is not None:
        changes["k"] = int(sum(spec.tenant_caps))
    return dataclasses.replace(spec, **changes)


@functools.lru_cache(maxsize=None)
def _ingest_fn_cached(spec: SketchSpec, block: int, donate: bool = True):
    def ingest(state, items, weights):
        return api.adapter_for(spec).update(spec, state, items, weights)

    # platform-resolved: donation is on iff an accelerator is attached
    # (repro.platform.donate_state_buffers; DESIGN.md §14 on why CPU
    # keeps it off). Donation changes buffer reuse only, never results —
    # pinned by tests/test_platform.py.
    from repro.platform import donate_state_buffers

    donate_args = (0,) if donate and donate_state_buffers() else ()
    return jax.jit(ingest, donate_argnums=donate_args)


def _ingest_fn(spec: SketchSpec, block: int, donate: bool = True):
    """The compiled (state, items, weights) -> state ingest for one
    (spec, block, donate) cell — cached for the process lifetime so
    every session (and bench) of that cell shares one trace (unbounded
    on purpose: an eviction would silently retrace a live session).
    Tenant specs are normalized first (:func:`ingest_cache_spec`) so the
    cache stays bounded by LAYOUTS, not by tenant populations.

    ``donate=True`` donates the state buffers on accelerators (the CPU
    backend cannot reuse donated buffers, so donation is skipped there):
    ingest then consumes the previous state, and any reference a caller
    captured before the update dies with it.  Callers that EXPOSE their
    state to consumers (the stats trackers' public ``.state``) pass
    ``donate=False`` to keep captured references valid, matching the
    pre-redesign behavior."""
    return _ingest_fn_cached(ingest_cache_spec(spec), int(block), donate)


def ingest_cache_stats() -> Dict[str, int]:
    """Cache-accounting hook for benches and tests: how many compiled
    ingest entries exist (``entries``) and the lru hit/miss counters.
    ``benchmarks/bench_service.py`` asserts one-compile-per-layout with
    the ``entries`` delta across a multi-tenant run."""
    info = _ingest_fn_cached.cache_info()
    return {"entries": int(info.currsize), "hits": int(info.hits),
            "misses": int(info.misses)}


class StreamSession:
    """Stateful streaming front-end over one :class:`SketchSpec`.

    ``block``: fixed ingest block length (one compilation per spec).
    ``window``: optional bounded-deletion horizon — in *pushes* for the
    batch path (``push``), in *observations* for the item path
    (``observe``).  ``state``: resume from an existing backend state
    (e.g. a restored checkpoint) instead of an empty one.
    ``replay``: keep the last N ingested blocks (sequence-numbered, as
    ingested — insertions AND expiry deletions) for
    ``elastic.recover_session``; size it to at least the checkpoint
    cadence in blocks.  ``fault_plan``: a ``faults.FaultPlan`` injected
    at the block boundary (sharded specs only).  ``monitor``: a
    ``StragglerMonitor`` observing per-shard flush timings.
    """

    def __init__(self, spec: SketchSpec, block: int = 8192,
                 window: Optional[int] = None, state=None,
                 donate: bool = True, replay: int = 0,
                 fault_plan=None, monitor=None):
        if block < 2:
            raise ValueError(f"block must be >= 2, got {block}")
        if fault_plan is not None and spec.shards is None:
            raise ValueError(
                "fault_plan injects shard-granular faults; the spec must "
                "be sharded (shards=S)")
        self.spec = spec
        self.block = int(block)
        self.window = window
        self.donate = donate
        self.state = state if state is not None else api.make(spec)
        # resolve the cached compiled ingest ONCE — ingest_block stays a
        # plain dispatch (the <5% overhead budget of DESIGN.md §11)
        self._compiled = _ingest_fn(spec, self.block, donate)
        self.insertions = 0
        self.deletions = 0
        # positive mass validated into this session so far — the
        # prior_mass bound api.validate_block holds each new block
        # against (a counter can never exceed it, so per-item nets are
        # rejected before they could carry one past int32). A caller-
        # provided resumed ``state`` starts at 0: its history is
        # unknown, so the bound is best-effort until restored by the
        # caller (``session.ingested_mass = ...`` after a checkpoint
        # load).
        self.ingested_mass = 0
        # resize bound widening, accumulated by elastic.reshard_session
        self.error_slack = 0
        # buffered (items, weights) fragments awaiting a flush
        self._buf_i: List[np.ndarray] = []
        self._buf_w: List[np.ndarray] = []
        self._buf_n = 0
        # windowed-deletion queues (batch- and item-granularity). Batch
        # FIFOs are keyed per tenant (None = the classic single-stream
        # schedule) so a multi-tenant service expires each tenant's
        # batches on that tenant's OWN horizon; the None deque is
        # created eagerly because the stats trackers alias it through
        # the ``batch_fifo`` property.
        self._batch_fifos: Dict[Optional[int],
                                Deque[Tuple[np.ndarray, np.ndarray]]] = {
            None: collections.deque()}
        self._item_fifo: Deque[Tuple[int, int]] = collections.deque()
        # fault-tolerance machinery (all inert by default; deque with
        # maxlen=0 silently retains nothing, so the hot path below can
        # append unconditionally only when replay > 0)
        self.replay = int(replay)
        self._seq = 0  # blocks ingested so far; block i carries seq i
        self._replay: Deque[Tuple[int, np.ndarray, np.ndarray]] = (
            collections.deque(maxlen=max(self.replay, 0)))
        self.fault_plan = fault_plan
        self.monitor = monitor
        self._deferred = {}  # due seq -> [(items, weights)] delayed slices

    @property
    def replay_log(self) -> Tuple[Tuple[int, np.ndarray, np.ndarray], ...]:
        """The retained (seq, items, weights) blocks, oldest first."""
        return tuple(self._replay)

    # -- low-level ingest --------------------------------------------------

    def ingest_block(self, items, weights) -> None:
        """Feed ONE exactly block-sized, already-padded block (hot path).

        No buffering, no conversions — jit canonicalizes numpy/jax
        array operands itself (a host ``jnp.asarray`` here costs ~30µs
        per operand for nothing). This is the call the session-overhead
        bench races against the raw engine launch.

        The replay log records the block BEFORE fault injection: faults
        corrupt the live state, never the recovery truth.
        """
        self._seq += 1
        if self.replay:
            self._replay.append(
                (self._seq, np.asarray(items), np.asarray(weights)))
        if self.fault_plan is None and self.monitor is None:
            self.state = self._compiled(self.state, items, weights)
            return
        self._ingest_faulty(self._seq, items, weights)

    def _ingest_faulty(self, seq: int, items, weights) -> None:
        """Fault-injected / monitored spelling of one block ingest.

        Delay faults land their shard's slice at its due block, so even
        a faulted run ingests every observation exactly once (only
        drop/corrupt lose data — that is their point).
        """
        from . import faults as flt

        shards = self.spec.shards or 1
        # delayed slices that came due re-deliver BEFORE the new block
        for due in sorted(k for k in self._deferred if k <= seq):
            for di, dw in self._deferred.pop(due):
                self.state = self._compiled(self.state, di, dw)
        delay_s = {}
        if self.fault_plan is not None:
            out = flt.inject(self.fault_plan, seq, shards,
                             np.asarray(items), np.asarray(weights))
            delay_s = out.delay_s
            primary, extra = out.blocks[0], out.blocks[1:]
            dt = self._timed_ingest(*primary)
            for bi, bw in extra:
                self.state = self._compiled(self.state, bi, bw)
            for due, di, dw in out.deferred:
                self._deferred.setdefault(due, []).append((di, dw))
            if out.poison_rows:
                self.state = flt.poison_rows(self.state, out.poison_rows)
        else:
            dt = self._timed_ingest(items, weights)
        if self.monitor is not None:
            # per-shard timing: every host reports the primary block's
            # wall time (injection overhead — re-deliveries, poisoning —
            # is harness bookkeeping, not a host's step), and a delayed
            # shard's host reports the injected slowdown on top
            for r in range(shards):
                self.monitor.observe(r, dt + delay_s.get(r, 0.0))

    def _timed_ingest(self, items, weights) -> float:
        """One compiled ingest, timed to completion when a monitor needs
        the wall time (block_until_ready costs pipelining, so plain
        fault-injected runs skip it)."""
        t0 = time.perf_counter()
        self.state = self._compiled(self.state, items, weights)
        if self.monitor is not None:
            jax.block_until_ready(self.state)
        return time.perf_counter() - t0

    def ingest(self, items, weights) -> None:
        """Validate, chunk to the session block, pad, and ingest now.

        Validation runs on the RAW arrays (casting first would wrap
        64-bit ids / truncate floats silently, defeating the checks);
        the int32 cast happens after it proves lossless.
        """
        items = np.asarray(items).ravel()
        weights = np.asarray(weights).ravel()
        self.ingested_mass += api.validate_block(
            self.spec, items, weights, prior_mass=self.ingested_mass)
        items = items.astype(np.int32)
        weights = weights.astype(np.int32)
        for s in range(0, len(items), self.block):
            ci = items[s:s + self.block]
            cw = weights[s:s + self.block]
            pad = self.block - len(ci)
            if pad:
                ci = np.pad(ci, (0, pad))  # weight-0 tail = padding
                cw = np.pad(cw, (0, pad))
            self.ingest_block(ci, cw)

    # -- buffered streaming ------------------------------------------------

    def extend(self, items, weights=None) -> None:
        """Buffer a fragment of signed weighted updates; auto-flush full
        blocks. ``weights=None`` = unit inserts.

        As in ``ingest``: validate raw, cast after (a pre-cast would
        silently wrap 64-bit ids and truncate float weights).
        """
        items = np.asarray(items).ravel()
        if weights is None:
            weights = np.ones(len(items), np.int32)
        else:
            weights = np.asarray(weights).ravel()
        self.ingested_mass += api.validate_block(
            self.spec, items, weights, prior_mass=self.ingested_mass)
        self._append(items.astype(np.int32), weights.astype(np.int32))

    def _append(self, items: np.ndarray, weights: np.ndarray) -> None:
        """Pre-validated int32 fragments -> buffer, auto-flushing."""
        self._buf_i.append(items)
        self._buf_w.append(weights)
        self._buf_n += len(items)
        if self._buf_n >= self.block:
            self._drain(keep_partial=True)

    def observe(self, item: int, weight: int = 1) -> None:
        """One observation; with ``window`` set, expire the observation
        that falls off the horizon (bounded deletion).

        Validates the scalar inline (the full ``validate_block`` per
        single item would dominate this path) and BEFORE touching any
        session state, so a rejected observation never poisons the
        expiry FIFO or the insertion totals.
        """
        item = int(item)
        weight = int(weight)
        if item < 0:
            raise ValueError(
                f"negative item id {item}: ids must be >= 0 (negative ids "
                f"are the EMPTY/BLOCKED sentinels)")
        if self.spec.kind == "quantile" and item >= (1 << self.spec.bits):
            raise ValueError(
                f"item {item} is outside the dyadic universe "
                f"[0, 2^{self.spec.bits}); raise SketchSpec.bits or bucket "
                f"ids before ingest")
        int32_max = int(np.iinfo(np.int32).max)
        if abs(weight) > int32_max:
            raise ValueError(
                f"weight {weight} does not fit int32 (the device-side "
                f"count dtype)")
        if weight > 0 and self.ingested_mass + weight > int32_max:
            raise ValueError(
                f"observation of weight {weight} on a session already "
                f"holding {self.ingested_mass} positive mass could carry "
                f"a counter past int32 max ({int32_max}); rescale or "
                f"checkpoint-and-reset the session")
        expire = (self.window is not None
                  and len(self._item_fifo) >= self.window)
        if expire:
            old_i, old_w = self._item_fifo[0]
            frag_i = np.asarray([item, old_i], np.int32)
            frag_w = np.asarray([weight, -old_w], np.int32)
        else:
            frag_i = np.asarray([item], np.int32)
            frag_w = np.asarray([weight], np.int32)
        self._append(frag_i, frag_w)
        self.insertions += weight
        if weight > 0:
            self.ingested_mass += weight
        if self.window is not None:
            self._item_fifo.append((item, weight))
            if expire:
                self._item_fifo.popleft()
                self.deletions += old_w

    def flush(self) -> None:
        """Ingest everything buffered (padding the final partial block),
        then deliver any still-pending delayed fault slices.

        Without the second step a delay fault near the end of the stream
        would silently drop its slice (nothing arrives with
        ``seq >= due`` to trigger redelivery), breaking the "delay
        defers + redelivers exactly once" contract of
        ``repro.sketch.faults``. Draining here keeps the contract: a
        flushed session has ingested every observation exactly once.
        """
        self._drain(keep_partial=False)
        self._drain_deferred()

    def _drain_deferred(self) -> None:
        """Deliver every pending delayed slice (end-of-stream redelivery)."""
        for due in sorted(self._deferred):
            for di, dw in self._deferred.pop(due):
                self.state = self._compiled(self.state, di, dw)

    def _drain(self, keep_partial: bool) -> None:
        if not self._buf_n:
            return
        items = np.concatenate(self._buf_i) if len(self._buf_i) > 1 \
            else self._buf_i[0]
        weights = np.concatenate(self._buf_w) if len(self._buf_w) > 1 \
            else self._buf_w[0]
        n_full = (len(items) // self.block) * self.block
        for s in range(0, n_full, self.block):
            self.ingest_block(items[s:s + self.block],
                              weights[s:s + self.block])
        tail = len(items) - n_full
        if not keep_partial and tail:
            pad = self.block - tail
            self.ingest_block(np.pad(items[n_full:], (0, pad)),
                              np.pad(weights[n_full:], (0, pad)))
        keep_tail = keep_partial and tail
        rest_i = items[n_full:] if keep_tail else items[:0]
        rest_w = weights[n_full:] if keep_tail else weights[:0]
        self._buf_i = [rest_i] if len(rest_i) else []
        self._buf_w = [rest_w] if len(rest_w) else []
        self._buf_n = len(rest_i)

    # -- windowed batch scheduling (the stats trackers' machinery) ---------

    def push(self, items, weights, tenant: Optional[int] = None) -> None:
        """Ingest one aggregated batch NOW and schedule its expiry.

        After ``window`` further pushes the batch re-ingests with
        negated weights — at most 1/window of the live mass deleted per
        step, the exact alpha <= 2 regime Thm 4 sizes capacity for.
        Immediate ingest keeps the block sequence — and therefore the
        sketch state — bit-identical to the pre-session stats trackers;
        anything still buffered from ``extend``/``observe`` flushes
        FIRST so a mixed-use session never reorders a push's deletions
        ahead of buffered insertions.  (Counters track pushed batches
        only: ``extend`` is raw streaming, outside the window
        accounting.)

        ``tenant`` selects which per-tenant expiry FIFO the batch ages
        on (the window counts pushes PER TENANT, so a hot tenant cannot
        flush a cold tenant's history); ``None`` is the classic
        single-stream schedule.
        """
        self.flush()
        items = np.asarray(items).ravel()
        weights = np.asarray(weights).ravel()
        self.ingest(items, weights)  # validates raw, casts internally
        for di, dw in self.schedule_batch(
                items.astype(np.int32), weights.astype(np.int32), tenant):
            self.ingest(di, dw)

    def schedule_batch(self, items: np.ndarray, weights: np.ndarray,
                       tenant: Optional[int] = None,
                       ) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Account one already-ingested batch on the window schedule and
        return the expiry updates now due (negated-weight fragments),
        WITHOUT ingesting them — the sketch service coalesces the due
        expiries of many tenants into its fused blocks instead of paying
        one padded ingest per expiry the way ``push`` does.

        ``push`` is exactly ``ingest`` + ``schedule_batch`` + ingesting
        the due fragments; counters move here so both paths agree.
        """
        self.insertions += int(weights.sum())
        if self.window is None:
            return []
        fifo = self._batch_fifos.setdefault(tenant, collections.deque())
        fifo.append((items, weights))
        due: List[Tuple[np.ndarray, np.ndarray]] = []
        while len(fifo) > self.window:
            di, dw = fifo.popleft()
            self.deletions += int(dw.sum())
            due.append((di, -dw))
        return due

    @property
    def batch_fifo(self) -> Deque[Tuple[np.ndarray, np.ndarray]]:
        """Live (items, weights) batches awaiting expiry on the default
        (tenant=None) schedule (checkpointed by the stats trackers, which
        mutate this deque in place — its identity is stable across
        ``load``)."""
        return self._batch_fifos[None]

    @property
    def batch_fifos(self) -> Dict[Optional[int],
                                  Deque[Tuple[np.ndarray, np.ndarray]]]:
        """All per-tenant expiry FIFOs, keyed by tenant (None = default)."""
        return self._batch_fifos

    @property
    def alpha_bound(self) -> float:
        """Empirical alpha = I / (I - D) (paper Table 2)."""
        live = max(self.insertions - self.deletions, 1)
        return self.insertions / live

    # -- queries (flush first: a query sees every prior update) ------------

    def query_many(self, items) -> jax.Array:
        self.flush()
        return api.query_many(self.spec, self.state, items)

    def query(self, item) -> jax.Array:
        self.flush()
        return api.query(self.spec, self.state, item)

    def topk(self, m: int) -> Tuple[jax.Array, jax.Array]:
        self.flush()
        return api.topk(self.spec, self.state, m)

    def rank_many(self, xs) -> jax.Array:
        self.flush()
        return api.rank_many(self.spec, self.state, xs)

    def rank(self, x) -> int:
        self.flush()
        return api.rank(self.spec, self.state, x)

    def quantile_many(self, qs) -> jax.Array:
        self.flush()
        return api.quantile_many(self.spec, self.state, qs)

    def quantile(self, q: float) -> int:
        self.flush()
        return api.quantile(self.spec, self.state, q)

    # -- merge / consolidation / checkpointing -----------------------------

    def merge_from(self, other: "StreamSession") -> None:
        """Cross-host reduction (mergeable summaries); counters add.

        Specs must agree on everything but ``backend`` (an execution
        path, not a layout): merging different k/variant/bits/shards
        would either break the guarantees silently (variant) or die in
        a shape error deep inside ``state.merge`` (k).  Window schedules
        must match too — merging a window=W session into a window=W'
        one would mix expiry semantics: the merged state holds the other
        session's live mass, but its pending expiries would fire on the
        wrong horizon (or never), silently breaking the bounded-deletion
        alpha the capacity was sized for.  Compatible windowed sessions
        carry the other's pending expiry FIFOs over, so every scheduled
        deletion still fires exactly once.
        """
        import dataclasses

        if dataclasses.replace(self.spec, backend="bank") != \
                dataclasses.replace(other.spec, backend="bank"):
            raise ValueError(
                f"cannot merge sessions of different layouts: "
                f"{self.spec} vs {other.spec} (only `backend` may differ)")
        if self.window != other.window:
            raise ValueError(
                f"cannot merge sessions with mismatched window schedules "
                f"(window={self.window} vs window={other.window}): the "
                f"absorbed session's pending expiries would fire on the "
                f"wrong horizon, silently mixing deletion semantics. "
                f"Re-create both sessions with the same window, or flush "
                f"the windows (push window more batches / observe window "
                f"more items) before merging.")
        self.flush()
        other.flush()
        self.state = api.merge(self.spec, self.state, other.state)
        self.insertions += other.insertions
        self.deletions += other.deletions
        self.error_slack += other.error_slack
        # carry pending expiries: the merged state contains the other
        # session's live mass, so its scheduled deletions must still fire
        # (per tenant — an absorbed tenant's batches keep aging on that
        # tenant's own horizon)
        for t, fifo in other._batch_fifos.items():
            self._batch_fifos.setdefault(
                t, collections.deque()).extend(fifo)
        self._item_fifo.extend(other._item_fifo)

    def consolidated(self):
        """Single-host summary (identity when unsharded)."""
        self.flush()
        return api.consolidate(self.spec, self.state)

    def save(self, include_schedule: bool = False) -> dict:
        """Tagged checkpoint dict of the sketch state.

        ``include_schedule=False`` (the legacy contract): flush pending
        updates into the state, save the sketch only — scheduling state
        (fifos, counters) is the caller's to persist; the stats trackers
        do.

        ``include_schedule=True``: do NOT flush — serialize the live
        scheduling state alongside the sketch (``sched_*`` keys: the
        unflushed buffer, both expiry FIFOs, the insertion/deletion
        totals, the block-sequence cursor, the window and the resize
        ``error_slack``) so a ``load`` of this dict resumes the session
        mid-stream with no observation lost, double-counted, or expired
        on the wrong horizon.  This is also the checkpoint
        ``elastic.recover_session`` rebuilds from (``sched_seq`` keys
        its replay).
        """
        if not include_schedule:
            self.flush()
            return api.save(self.spec, self.state)
        d = api.save(self.spec, self.state)
        cat = lambda frags: (np.concatenate(frags) if len(frags) > 1
                             else frags[0] if frags
                             else np.zeros(0, np.int32))
        d["sched_buf_items"] = cat(self._buf_i)
        d["sched_buf_weights"] = cat(self._buf_w)
        d["sched_item_fifo_items"] = np.asarray(
            [i for i, _ in self._item_fifo], np.int32)
        d["sched_item_fifo_weights"] = np.asarray(
            [w for _, w in self._item_fifo], np.int32)
        # batch FIFOs flatten across tenants in a deterministic key
        # order (None first, then ascending tenant); sched_batch_tenants
        # tags each batch's owner FIFO (-1 = the default None schedule)
        # — the failing-before regression: pre-tenant checkpoints
        # collapsed every tenant's pending expiries onto one FIFO
        keys = sorted(self._batch_fifos,
                      key=lambda t: (t is not None, t if t is not None else 0))
        flat_b = [(t, b, w) for t in keys for b, w in self._batch_fifos[t]]
        d["sched_batch_items"] = cat([b for _, b, _ in flat_b])
        d["sched_batch_weights"] = cat([w for _, _, w in flat_b])
        d["sched_batch_lens"] = np.asarray(
            [len(b) for _, b, _ in flat_b], np.int64)
        d["sched_batch_tenants"] = np.asarray(
            [-1 if t is None else int(t) for t, _, _ in flat_b], np.int64)
        d["sched_insertions"] = self.insertions
        d["sched_deletions"] = self.deletions
        d["sched_seq"] = self._seq
        d["sched_window"] = -1 if self.window is None else int(self.window)
        d["sched_error_slack"] = self.error_slack
        # pending delayed fault slices: a crash between a delay fault and
        # its due block must not lose the slice across save/load
        flat = [(due, di, dw) for due in sorted(self._deferred)
                for di, dw in self._deferred[due]]
        d["sched_deferred_due"] = np.asarray(
            [due for due, _, _ in flat], np.int64)
        d["sched_deferred_lens"] = np.asarray(
            [len(di) for _, di, _ in flat], np.int64)
        d["sched_deferred_items"] = cat([np.asarray(di, np.int32)
                                         for _, di, _ in flat])
        d["sched_deferred_weights"] = cat([np.asarray(dw, np.int32)
                                           for _, _, dw in flat])
        return d

    def load(self, d: dict) -> None:
        """Restore from a ``save`` dict or a pre-redesign stats layout,
        adapting the spec's kind/shards to what the dict holds.

        ALL scheduling state resets together — buffers, expiry FIFOs and
        the insertion/deletion totals — so the session is never half-old
        (counters describing batches whose expiries were dropped).
        A ``save(include_schedule=True)`` dict then restores the full
        scheduling state on top (crash/resume resumes mid-stream);
        callers that persist scheduling state out-of-band (the stats
        trackers) restore their counters and FIFO after this call.
        """
        self._buf_i, self._buf_w, self._buf_n = [], [], 0
        # keep the None deque's OBJECT identity: the stats trackers hold
        # a live alias through the batch_fifo property
        none_fifo = self._batch_fifos[None]
        none_fifo.clear()
        self._batch_fifos = {None: none_fifo}
        self._item_fifo.clear()
        self.insertions = 0
        self.deletions = 0
        self.error_slack = 0
        self._seq = 0
        self._replay.clear()
        self._deferred = {}
        self.spec = api.infer_spec(self.spec, d)
        self.state = api.restore(self.spec, d)
        self._compiled = _ingest_fn(self.spec, self.block, self.donate)
        if "sched_seq" in d:
            self._restore_schedule(d)

    def _restore_schedule(self, d: dict) -> None:
        saved_w = int(np.asarray(d["sched_window"]))
        saved_window = None if saved_w < 0 else saved_w
        if self.window != saved_window:
            raise ValueError(
                f"checkpoint carries window={saved_window} but this "
                f"session was built with window={self.window}; resuming "
                f"would re-schedule its pending expiries on the wrong "
                f"horizon. Construct the session with "
                f"window={saved_window} to resume this checkpoint.")
        bi = np.asarray(d["sched_buf_items"], np.int32)
        bw = np.asarray(d["sched_buf_weights"], np.int32)
        self._buf_i = [bi] if len(bi) else []
        self._buf_w = [bw] if len(bw) else []
        self._buf_n = len(bi)
        self._item_fifo = collections.deque(
            (int(i), int(w)) for i, w in zip(
                np.asarray(d["sched_item_fifo_items"]),
                np.asarray(d["sched_item_fifo_weights"])))
        cat_i = np.asarray(d["sched_batch_items"], np.int32)
        cat_w = np.asarray(d["sched_batch_weights"], np.int32)
        lens = np.asarray(d["sched_batch_lens"], np.int64)
        # pre-tenant checkpoints carry no tenant tags: everything loads
        # onto the default (None) schedule, the pre-tenant behavior
        tags = np.asarray(d.get("sched_batch_tenants",
                                np.full(len(lens), -1)), np.int64)
        s = 0
        for n, t in zip(lens, tags):
            n = int(n)
            key = None if int(t) < 0 else int(t)
            self._batch_fifos.setdefault(
                key, collections.deque()).append(
                    (cat_i[s:s + n], cat_w[s:s + n]))
            s += n
        self.insertions = int(np.asarray(d["sched_insertions"]))
        self.deletions = int(np.asarray(d["sched_deletions"]))
        self._seq = int(np.asarray(d["sched_seq"]))
        self.error_slack = int(np.asarray(d["sched_error_slack"]))
        # older schedule checkpoints predate deferred-slice carry-over
        if "sched_deferred_due" in d:
            dd_i = np.asarray(d["sched_deferred_items"], np.int32)
            dd_w = np.asarray(d["sched_deferred_weights"], np.int32)
            self._deferred = {}
            s = 0
            for due, n in zip(np.asarray(d["sched_deferred_due"], np.int64),
                              np.asarray(d["sched_deferred_lens"], np.int64)):
                due, n = int(due), int(n)
                self._deferred.setdefault(due, []).append(
                    (dd_i[s:s + n], dd_w[s:s + n]))
                s += n


@jax.jit
def _ready_token(state):
    """One element of ``state``: ready once the ingest that produced
    ``state`` is, and still valid after ``state`` itself is donated."""
    return jax.tree.leaves(state)[0].reshape(-1)[:1]


class BlockFeeder:
    """Host-side two-slot feeder that keeps the compiled ingest saturated.

    The device half of the double-buffered ingest pipeline (DESIGN.md
    §14) streams tiles inside the fused kernel; this is the host half.
    ``feed(items, weights)`` *stages* block i (async ``jax.device_put``
    of the padded arrays) and *dispatches* block i-1 — so the host→device
    transfer and numpy conversion of the next block overlap the device
    compute of the current one, the same two-slot copy idiom as the
    kernel's VMEM pipeline:

        slot A: block i-1  dispatched, computing on device
        slot B: block i    staging host->device

    At most ``depth`` ingests stay in flight (backpressure via
    ``block_until_ready`` on the oldest) so a fast host cannot queue
    unbounded device work. ``flush()`` dispatches the last staged block
    and synchronizes. What the feeder waits on is a one-element read of
    each ingest's output (``_ready_token``), not the output itself: a
    donating session consumes that output in the next ingest, and a
    donated buffer cannot be waited on.

    Blocks must be exactly session-block-sized and zero-weight padded
    (the ``StreamSession.ingest_block`` contract). Feeding through a
    feeder is bit-identical to calling ``ingest_block`` sequentially —
    only the overlap changes (pinned in tests/test_platform.py).
    """

    def __init__(self, session: StreamSession, depth: int = 2):
        self.session = session
        self.depth = max(1, int(depth))
        self._staged: Optional[Tuple[jax.Array, jax.Array]] = None
        self._inflight: Deque = collections.deque()

    def feed(self, items, weights) -> None:
        staged = (
            jax.device_put(np.asarray(items, dtype=np.int32)),
            jax.device_put(np.asarray(weights, dtype=np.int32)),
        )
        if self._staged is not None:
            self._dispatch(*self._staged)
        self._staged = staged

    def _dispatch(self, items, weights) -> None:
        self.session.ingest_block(items, weights)
        self._inflight.append(_ready_token(self.session.state))
        while len(self._inflight) > self.depth:
            jax.block_until_ready(self._inflight.popleft())

    def flush(self):
        """Dispatch the staged block, wait for the device, return state."""
        if self._staged is not None:
            self._dispatch(*self._staged)
            self._staged = None
        while self._inflight:
            jax.block_until_ready(self._inflight.popleft())
        return self.session.state


__all__ = ["BlockFeeder", "StreamSession", "_ingest_fn",
           "ingest_cache_spec", "ingest_cache_stats"]
