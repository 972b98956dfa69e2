"""The quantile kind (``chipbench/kinds/quantile.py``): the CPU rehearsal
of ``tenant_quantiles.value_reports`` comes out correct, its control and
the faults it can have (a layer credited to the wrong row, a dropped top
layer, answers one tick stale) come out not correct, and the new
readers read a recorded run."""
import gzip
import json
import os

import jax
import numpy as np
import pytest

from chipbench import trace as tr
from chipbench.harness import Window
from chipbench.kinds import quantile
from chipbench.rehearse import tiny_cell
from chipbench.run import ROOT, RunView, execute, per_layer_metrics

CELL = "tenant_quantiles.value_reports"
BENCH = os.path.join(ROOT, "BENCHMARK.json")
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small.xplane.pb.gz")


def _bench():
    with open(BENCH) as f:
        return json.load(f)


def _run(fault=None, control=False, trace=False, seed=2**31 + 3):
    return execute(tiny_cell(CELL, BENCH), seed, 1.5, trace,
                   jax.devices()[:1], _bench(), device_metrics=False,
                   fault=fault, control=control)


def test_sound_run_and_control():
    out = _run(control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    nums = {k: v["value"] for k, v in out["checks"].items()}
    assert 0 < nums["rank_error_over_bound"] <= 1
    ctl = out["control_checks"]
    assert not quantile.verdict(ctl)
    assert ctl["quantile_answers_differing"] > 0


def _bent_ingest(drv, bend):
    """The service's ingest with the layer-tagged block bent by ``bend``
    before the bank takes it (mass kept exact)."""
    import jax.numpy as jnp

    from repro.sketch import bank as bk
    from repro.sketch import tenant as tn

    state = drv.svc.session.state
    router = bk.TenantLevelRouter(state.num_tenants, state.bits)

    def ingest(tl, keys, weights):
        tagged, w = bend(*router.expand(keys, weights), keys.shape[0])
        mass = tl.mass.at[jnp.right_shift(keys, router.bits)].add(
            weights, mode="drop")
        return tn.TenantLevels(
            bank=bk.update_block_fused(tl.bank, tagged, w, router.rows),
            mass=mass)

    drv.svc.session._compiled = jax.jit(ingest)


def _layer_to_wrong_row(drv):
    # layer 1's nodes tagged with, and so ingested into, layer 2's row
    _bent_ingest(drv, lambda k, w, B: (k.at[B:2 * B].add(1 << 16), w))


def _top_layer_dropped(drv):
    _bent_ingest(drv, lambda k, w, B: (k, w.at[15 * B:].set(0)))


def _answers_one_tick_stale(drv):
    svc = drv.svc
    real = svc._refresh_subscriptions
    shown = {}

    def stale():
        real()
        for t, sub in svc._quant_subs.items():
            sub["value"], shown[t] = shown.get(t, sub["value"]), sub["value"]

    svc._refresh_subscriptions = stale


FAULTS = {
    "layer_to_wrong_row": (_layer_to_wrong_row, "rows_differing"),
    "top_layer_dropped": (_top_layer_dropped, "rows_differing"),
    "answers_one_tick_stale": (_answers_one_tick_stale,
                               "quantile_answers_differing"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_timed_path_faults_fail(fault):
    fn, number = FAULTS[fault]
    out = _run(fault=fn)
    assert not out["correct"]
    assert out["checks"][number]["value"] > 0


def test_dropped_top_layer_breaks_the_rank_bound():
    out = _run(fault=_top_layer_dropped)
    assert out["checks"]["rank_error_over_bound"]["value"] > 1


def test_readers_on_a_recorded_run():
    """A traced CPU rehearsal reports the two program readers; the
    roofline reader reads a recorded chip trace as ``ingest_roofline``
    does, and nothing without a trace."""
    out = _run(trace=True)
    assert out["correct"]
    m = out["metrics"]
    assert set(m) == {"level_rows_per_block", "tick_quantile_ms"}
    assert 16 <= m["level_rows_per_block"]["value"] <= 16 * 64
    assert m["tick_quantile_ms"]["value"] > 0

    from jax.profiler import ProfileData

    with gzip.open(RECORDED, "rb") as f:
        summary = tr.summarize(ProfileData.from_serialized_xspace(f.read()))
    s, n = summary.program_s(("ingest",))
    least = [1 << 20] * n
    view = RunView(window=Window(seconds=1.0), config=None, trace=summary,
                   least_bytes=least, peaks={"hbm_bytes_per_s": 819e9})
    got = per_layer_metrics(_bench(), CELL, view)
    assert set(got) == {"level_ingest_roofline"}
    assert got["level_ingest_roofline"]["value"] == pytest.approx(
        n * (1 << 20) / (s * 819e9) * 100)
    assert per_layer_metrics(_bench(), CELL, RunView(
        window=Window(seconds=1.0), config=None)) == {}


def test_block_least_bytes():
    bits, caps = 16, [300, 200, 100, 2]     # pad to 384, 256, 128, 128
    keys = np.array([(3 << bits) | 5, (3 << bits) | 9, (7 << bits) | 1,
                     (9 << bits) | 2], np.int32)
    w = np.array([1, -1, 2, 0], np.int32)   # tenant 9 only pads
    assert quantile.block_least_bytes(keys, w, bits, caps) == \
        2 * (384 + 256 + 128 + 128) * 3 * 4 * 2 + 4 * 8


def test_configuration_states_its_layer_capacities():
    from chipbench.harness import load_cell

    cell = load_cell(CELL, BENCH)
    spec = quantile.spec(cell.config)
    caps = spec.layer_capacities()
    assert caps == [6400] * 4 + [4096 >> i for i in range(12)]
    assert sum(-(-c // 128) * 128 for c in caps) == 34432
    assert cell.config.params["rows"] == 2048 * 16
