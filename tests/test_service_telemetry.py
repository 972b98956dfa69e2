"""The sketch service's telemetry: the spans around the tick's stages and
its host syncs (``stats['<name>_ns']``/``['<name>_n']``, and
``sketch.<name>`` annotations on a profiler trace's host plane), and the
count of the ingest's chunk-loop trips (``stats['ingest_chunks']``)."""
import glob

import numpy as np
import pytest

import jax

from repro.serve import SketchService
from repro.sketch import api
from repro.sketch import bank as bk

BITS = 8
T = 64          # tenants: more than one chunk of rows at block 64, k 16
BLOCK = 64
STAGES = ("admit", "ingest", "query", "subscriptions", "spill")


def _service(**kw) -> SketchService:
    spec = api.SketchSpec(kind="frequency", k=T * 16, bits=BITS, tenants=T)
    return SketchService(spec, block=BLOCK, **kw)


def _cap(svc: SketchService) -> int:
    return bk.touched_chunk_rows(BLOCK, svc.session.state.bank.ids.shape[1])


def _delta(svc: SketchService, before: dict) -> dict:
    return {k: v - before[k] for k, v in svc.stats.items() if v != before[k]}


def _ran(delta: dict):
    return {s for s in STAGES if delta.get(f"{s}_n")}


def test_stage_counters_advance_only_when_their_stage_runs():
    svc = _service(spill_after=1)
    snap = dict(svc.stats)
    svc.tick()                              # only the spill scan runs
    assert _ran(_delta(svc, snap)) == {"spill"}

    snap = dict(svc.stats)
    svc.submit(0, [1, 2, 3])
    svc.submit(1, [4])
    svc.tick()
    assert _ran(_delta(svc, snap)) == {"ingest", "spill"}

    snap = dict(svc.stats)
    svc.submit(0, [1])
    svc.tick()                              # tenant 1 idle: it spills
    d = _delta(svc, snap)
    assert d["spills"] == 1 and _ran(d) == {"ingest", "spill"}

    snap = dict(svc.stats)
    ticket = svc.query(1, [4])
    svc.tick()                              # the query re-admits tenant 1
    d = _delta(svc, snap)
    assert d["admits"] == 1 and _ran(d) == {"admit", "query", "spill"}
    np.testing.assert_array_equal(ticket.result(), [1])

    svc.subscribe_topk(1, 2)                # tenant 1 is resident
    snap = dict(svc.stats)
    svc.tick()
    d = _delta(svc, snap)
    assert _ran(d) == {"subscriptions", "spill"} and d["wait_n"] == 2
    for s in STAGES:
        assert svc.stats[f"{s}_ns"] > 0


def test_no_spill_stage_without_spill():
    svc = _service()
    svc.submit(0, [1, 2])
    svc.tick()
    svc.tick()
    assert svc.stats["spill_n"] == svc.stats["spill_ns"] == 0
    assert svc.stats["admit_n"] == 0


def test_host_syncs_at_flush_query_and_topk():
    svc = _service()
    # one block: the flush waits for it once
    snap = dict(svc.stats)
    svc.submit(0, [1, 2, 3])
    svc.tick()
    d = _delta(svc, snap)
    assert d["blocks"] == 1 and d["wait_n"] == 1
    # three blocks in flight at depth 2: each is waited on once
    snap = dict(svc.stats)
    svc.submit(0, np.arange(3 * BLOCK) % 16)
    svc.tick()
    d = _delta(svc, snap)
    assert d["blocks"] == 3 and d["wait_n"] == 3
    # the query's read
    snap = dict(svc.stats)
    svc.query(0, [1])
    svc.tick()
    assert _delta(svc, snap)["wait_n"] == 1
    # the batched top-k: its ids and its counts
    svc.subscribe_topk(0, 2)
    snap = dict(svc.stats)
    svc.tick()
    d = _delta(svc, snap)
    assert d["wait_n"] == 2 and d["subscriptions_n"] == 1
    # every wait nests in a stage, so no stage is shorter than its waits
    assert 0 < svc.stats["wait_ns"] <= sum(svc.stats[f"{s}_ns"]
                                           for s in STAGES)


def test_host_syncs_at_the_spill_reads():
    svc = _service(spill_after=1)
    svc.submit(0, [1, 2, 3])
    svc.submit(1, [5])
    svc.tick()
    snap = dict(svc.stats)
    svc.submit(0, [1])
    svc.tick()                              # tenant 1 idle: it spills
    d = _delta(svc, snap)
    assert d["spills"] == 1 and d["blocks"] == 1
    assert d["wait_n"] == 1 + 3             # the flush, ids/counts/errors


def _reference_chunks(svc: SketchService, blocks) -> int:
    """Chunk-loop trips from the fed blocks themselves: the tenants each
    block gives a nonzero weight, a chunk of rows at a time."""
    cap = _cap(svc)
    n = 0
    for ci, cw in blocks:
        rows = len(np.unique(ci[cw != 0] >> BITS))
        n += -(-rows // cap)
    return n


@pytest.mark.parametrize("reach", ["one", "cap", "cap+1"])
def test_ingest_chunks_per_rows_reached(reach):
    svc = _service()
    cap = _cap(svc)
    assert bk.takes_touched(svc._router, BLOCK, svc.session.state.bank
                            .ids.shape[1]) and cap < T
    rows = {"one": 1, "cap": cap, "cap+1": cap + 1}[reach]
    svc.trace_blocks = []
    for t in range(rows):
        svc.submit(t, [t % 16])
    svc.tick()
    assert svc.stats["blocks"] == 1
    assert svc.stats["ingest_chunks"] == -(-rows // cap)
    assert svc.stats["ingest_chunks"] == _reference_chunks(
        svc, svc.trace_blocks)


def test_ingest_chunks_count_a_tenant_in_each_block_it_spans():
    svc = _service()
    cap = _cap(svc)
    svc.trace_blocks = []
    for t in range(cap + 1):                # one item each
        svc.submit(t, [1])
    svc.submit(cap + 1, np.arange(BLOCK) % 16)   # runs into block two
    svc.tick()
    assert svc.stats["blocks"] == 2
    # block one: cap + 2 tenants (2 chunks); block two: one tenant
    assert svc.stats["ingest_chunks"] == 3
    assert svc.stats["ingest_chunks"] == _reference_chunks(
        svc, svc.trace_blocks)


def test_ingest_chunks_one_per_block_off_the_chunked_path():
    spec = api.SketchSpec(kind="frequency", k=8 * 16, bits=BITS, tenants=8)
    svc = SketchService(spec, block=BLOCK)
    assert not bk.takes_touched(svc._router, BLOCK,
                                svc.session.state.bank.ids.shape[1])
    for t in range(8):
        svc.submit(t, np.arange(20) % 16)
    svc.tick()
    assert svc.stats["blocks"] == 3 and svc.stats["ingest_chunks"] == 3
    assert list(bk.ingest_chunks(svc._router, BLOCK, 16, [0, 5, 9])) \
        == [1, 1, 1]


def test_stats_snapshot_is_unchanged_by_a_tick():
    svc = _service(spill_after=1)
    svc.subscribe_topk(2, 2)
    svc.submit(0, [1, 2])
    svc.submit(1, [3])
    svc.tick()
    snap = dict(svc.stats)
    kept = dict(snap)
    svc.query(0, [1])
    svc.submit(2, [7, 7])
    svc.tick()
    assert snap == kept
    assert snap != svc.stats
    assert all(type(v) is int for v in svc.stats.values())


def _host_events(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sketch.") or e.name == "outer":
                    events.append((e.name, e.start_ns, e.end_ns,
                                   dict(e.stats)))
    return events


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    svc = _service(spill_after=1)
    svc.subscribe_topk(0, 2)
    svc.submit(0, [1, 2])
    svc.submit(1, [3])
    svc.tick()                              # compile outside the trace
    svc.submit(0, [1])
    svc.tick()
    svc.query(1, [3])
    svc.tick()
    snap = dict(svc.stats)
    first = svc.tick_count
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("outer"):
            svc.submit(0, np.arange(2 * BLOCK) % 16)
            svc.submit(2, [5])
            svc.tick()
            svc.query(2, [5])
            svc.tick()
            svc.submit(3, [6])
            svc.tick()                      # tenant 2 has spilled by now
            svc.query(2, [5])
            svc.tick()                      # and re-admits
    finally:
        jax.profiler.stop_trace()
    d = _delta(svc, snap)
    assert d["spills"] >= 1 and d["admits"] >= 1
    events = _host_events(tmp_path)
    (outer,) = [e for e in events if e[0] == "outer"]
    spans = [e for e in events if e[0] != "outer"]
    names = {e[0] for e in spans}
    assert names == {f"sketch.{s}" for s in STAGES + ("wait",)}
    for name, s, e, stats in spans:
        assert outer[1] <= s and e <= outer[2]
        assert first <= stats["tick"] < svc.tick_count
    # each wait nests in a stage span of its own tick
    stages = [e for e in spans if e[0] != "sketch.wait"]
    for _, s, e, stats in (e for e in spans if e[0] == "sketch.wait"):
        assert any(s0 <= s and e <= e0 and st["tick"] == stats["tick"]
                   for _, s0, e0, st in stages)
    for name in STAGES + ("wait",):
        mine = [e for e in spans if e[0] == f"sketch.{name}"]
        assert len(mine) == d[f"{name}_n"]
        traced = sum(e - s for _, s, e, _ in mine)
        assert abs(traced - d[f"{name}_ns"]) <= max(0.05 * d[f"{name}_ns"],
                                                    1e6)
