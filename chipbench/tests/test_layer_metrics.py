"""The readers of the program's spans and counters (``SketchService.stats``
deltas over the window) on hand-built windows: every value known, and
None where the counter did not move or the program has no such key."""
import importlib

import pytest

from chipbench.harness import Window
from chipbench.run import RunView

BASE = {"updates": 0, "queries": 0, "ticks": 0, "blocks": 0, "spills": 0,
        "admits": 0}
SPANS = ("admit", "ingest", "query", "subscriptions", "spill", "wait")


def _stats(ticks, blocks, chunks, **spans):
    """``spans``: name -> (ns, n); every other span stays at zero."""
    d = dict(BASE, ticks=ticks, blocks=blocks, ingest_chunks=chunks)
    for s in SPANS:
        ns, n = spans.get(s, (0, 0))
        d[f"{s}_ns"], d[f"{s}_n"] = ns, n
    return d


# 10 ticks and 4 blocks in the window, on top of a service that had
# already run 5 ticks
S0 = _stats(5, 2, 2, admit=(1_000_000, 1), ingest=(9_000_000, 5),
            query=(500_000, 2), subscriptions=(700_000, 5),
            spill=(4_000_000, 5), wait=(6_000_000, 12))
S1 = _stats(15, 6, 12, admit=(31_000_000, 4), ingest=(59_000_000, 15),
            query=(2_500_000, 6), subscriptions=(3_700_000, 15),
            spill=(44_000_000, 15), wait=(46_000_000, 42))
EXPECTED = {
    "tick_admit_ms": 3.0,
    "tick_ingest_ms": 5.0,
    "tick_answer_ms": 0.5,
    "tick_spill_ms": 4.0,
    "tick_device_wait_ms": 4.0,
    "host_syncs_per_tick": 3.0,
    "ingest_chunks_per_block": 2.5,
}
# the counter each reader needs moved
MOVED = {
    "tick_admit_ms": ("admit_n",),
    "tick_ingest_ms": ("ingest_n",),
    "tick_answer_ms": ("query_n", "subscriptions_n"),
    "tick_spill_ms": ("spill_n",),
    "tick_device_wait_ms": ("wait_n",),
    "host_syncs_per_tick": ("wait_n",),
    "ingest_chunks_per_block": ("ingest_chunks",),
}


def _read(name, s0, s1):
    win = Window(seconds=51.0, stats0=dict(s0), stats1=dict(s1))
    reader = importlib.import_module(f"chipbench.metrics.{name}")
    return reader.read(RunView(window=win, config=None))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_value(name):
    assert _read(name, S0, S1) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_none_where_the_counter_did_not_move(name):
    s1 = dict(S1)
    for key in MOVED[name]:
        s1[key] = S0[key]
    assert _read(name, S0, s1) is None


def test_answer_reads_either_span():
    s1 = dict(S1, query_n=S0["query_n"], query_ns=S0["query_ns"])
    assert _read("tick_answer_ms", S0, s1) == pytest.approx(0.3)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_none_on_a_program_without_the_counters(name):
    s0 = _stats(5, 2, 0)
    s1 = _stats(15, 6, 0)
    bare0 = {k: s0[k] for k in BASE}
    bare1 = {k: s1[k] for k in BASE}
    assert _read(name, bare0, bare1) is None
