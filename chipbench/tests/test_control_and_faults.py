"""The comparison that decides ``correct`` fails what it must: its
control (the reference answering one tick stale) and the faults each
cell can have, planted under a whole run of the harness at a small size
on the CPU (the look for a chip skipped)."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench.rehearse import tiny_cell
from chipbench.run import ROOT, execute

CELLS = ["tenant_hh.zipf_spill", "flow_hh.prefix_fanout"]


def _run(name, fault=None, control=False, seed=2**31 + 3):
    path = os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    return execute(tiny_cell(name, path), seed, 1.5, False,
                   jax.devices()[:1], bench, device_metrics=False,
                   fault=fault, control=control)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_and_control(name):
    out = _run(name, control=True)
    assert out["correct"], out["checks"]
    ctl = out["control_checks"]
    assert ctl["query_answers_differing"] + ctl["topk_answers_differing"] > 0


def _state_unchanged(drv):
    drv.svc.session._compiled = lambda state, items, weights: state


def _half_batch(drv):
    ingest = drv.svc.session._compiled
    B = drv.cell.config.block

    def half(state, items, weights):
        keep = jnp.arange(B) % 2 == 0   # every other entry, however full
        return ingest(state, items, jnp.where(keep, weights, 0))

    drv.svc.session._compiled = half


def _submit_drops_half(drv):
    real = drv.svc.submit

    def half(tenant, items, weights=None):
        n = (len(items) + 1) // 2
        real(tenant, items[:n], None if weights is None else weights[:n])

    drv.svc.submit = half


def _tick_defers_half(drv):
    svc = drv.svc
    real = svc.tick

    def defer():
        held = {t: svc._pending.pop(t) for t in sorted(svc._pending)[1::2]}
        real()
        for t, parts in held.items():
            svc._pending.setdefault(t, []).extend(parts)

    svc.tick = defer


# (fault, the number it has to fail): the last two sit above the point
# where the program records its blocks, so only the driver's record of
# what it submitted shows them
FAULTS = {
    "state_unchanged": (_state_unchanged, "rows_differing"),
    "half_batch": (_half_batch, "rows_differing"),
    "submit_drops_half": (_submit_drops_half, "feed_differing"),
    "tick_defers_half": (_tick_defers_half, "feed_differing"),
}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_timed_path_faults_fail(name, fault):
    fn, number = FAULTS[fault]
    out = _run(name, fault=fn)
    assert not out["correct"]
    assert out["checks"][number]["value"] > 0


def test_altered_query_answer_fails(monkeypatch):
    from repro.sketch import api

    real = api.query_many
    monkeypatch.setattr(api, "query_many",
                        lambda *a, **k: real(*a, **k) + 1)
    out = _run("tenant_hh.zipf_spill")
    assert not out["correct"]
    assert out["checks"]["query_answers_differing"]["value"] > 0


def test_altered_topk_answer_fails(monkeypatch):
    from repro.sketch import tenant as tn

    real = tn.topk_tenants

    def altered(*a, **k):
        items, vals = real(*a, **k)
        return items, vals.at[:, 0].add(1)

    monkeypatch.setattr(tn, "topk_tenants", altered)
    out = _run("flow_hh.prefix_fanout")
    assert not out["correct"]
    assert out["checks"]["topk_answers_differing"]["value"] > 0
