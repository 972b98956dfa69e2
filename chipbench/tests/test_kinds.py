"""Configurations name their kind, and the harness finds everything
kind-specific in ``chipbench/kinds/<kind>.py``: the two frequency cells
make the same traffic and the same check numbers as before the lookup,
and a kind that exists only in a test runs end to end through
``run.execute``."""
import dataclasses
import hashlib
import json
import os
import sys
import types

import jax
import numpy as np
import pytest

from chipbench import check, harness
from chipbench.rehearse import tiny_cell
from chipbench.run import ROOT, execute
from chipbench.traffic import Mix, generate

BENCH = os.path.join(ROOT, "BENCHMARK.json")

# sha256 of every array of the traffic at the cell's own tenants and a
# 51 s window, from the generator before it took hot tenants, targeted
# deletions and bursty arrivals
TRAFFIC = {
    ("zipf_spill", 16384): {
        1: "834664059b29f3bd3363c0beb087fc2dc06f496ef9202fa22318291309e0ed3d",
        2**31 + 5:
            "9e9447101e3941938c38ba8cb627ba7d2ca1a4db4d688fa6ce966dd91002e415",
        4140000001:
            "4a517e9fee7a5738e6ab914c2faae503573a155127c61f50270ae216bf0d4a52",
    },
    ("prefix_fanout", 32768): {
        1: "872f8c3b540d963f74c4cab1e04410dd44a96f52ef103d03dd52037fe1776686",
        2**31 + 5:
            "cfffd892c9032581af801bccffc4f5309110eec2d1296c120bdce57e039b85e6",
        4140000001:
            "8614256d22cb9628763a6caa5fb51b0f5924c6cf868df692e9dbea70fd7dcdd3",
    },
}

# the rehearsal's check numbers under a stepped clock (1.5 s window),
# from the harness before the kind lookup: (attempted, error_over_bound);
# every other compared number read 0
CHECKS = {
    ("tenant_hh.zipf_spill", 2**31 + 3): (308, 0.378698224852071),
    ("tenant_hh.zipf_spill", 4140000001): (308, 0.3155818540433925),
    ("flow_hh.prefix_fanout", 2**31 + 3): (4927, 0.3198223209328151),
    ("flow_hh.prefix_fanout", 4140000001): (4927, 0.32017787659811003),
}


def _digest(tr) -> str:
    h = hashlib.sha256()
    for f in ("kind", "tenant", "start", "length", "keys", "weights", "due",
              "sizes"):
        a = getattr(tr, f)
        if a is not None:
            h.update(f.encode())
            h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("mix,tenants", sorted(TRAFFIC))
def test_existing_mixes_make_the_same_traffic(mix, tenants):
    m = Mix.load(os.path.join(ROOT, "chipbench", "mixes", mix + ".json"))
    for seed, want in TRAFFIC[(mix, tenants)].items():
        assert _digest(generate(m, tenants, seed, 51.0)) == want, seed


class _SteppedClock:
    """``time`` for the harness: each reading 0.1 ms after the last, and
    sleeps that return at once, so a window's ticks are the same on
    every run."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        self.t += 1e-4
        return self.t

    @staticmethod
    def sleep(_):
        pass


@pytest.mark.parametrize("name,seed", sorted(CHECKS))
def test_existing_cells_check_the_same_numbers(monkeypatch, name, seed):
    monkeypatch.setattr(harness, "time", _SteppedClock())
    with open(BENCH) as f:
        bench = json.load(f)
    out = execute(tiny_cell(name, BENCH), seed, 1.5, False,
                  jax.devices()[:1], bench, device_metrics=False)
    nums = {k: v["value"] for k, v in out["checks"].items()}
    attempted, err = CHECKS[(name, seed)]
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == attempted
    assert nums.pop("error_over_bound") == err
    assert set(nums.values()) == {0}


def test_config_keeps_its_other_keys_for_the_kind(tmp_path):
    path = os.path.join(ROOT, "chipbench", "configs", "tenant_hh.json")
    conf = harness.Config.load(path)
    assert conf.params["k_per_tenant"] == 2048 and conf.params["bits"] == 17
    assert "guarantees" in conf.params and "kind" not in conf.params
    with open(path) as f:
        d = json.load(f)
    assert conf.kind == d.pop("kind")
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(d))
    with pytest.raises(ValueError, match="kind"):
        harness.Config.load(str(bare))


# -- a kind that exists only here ---------------------------------------------


def _toy_kind() -> types.ModuleType:
    """Per-tenant rows of ``k`` counters, no subscriptions; its check is
    the shared feed rules alone."""
    from repro.serve import SketchService
    from repro.sketch import api

    toy = types.ModuleType("chipbench.kinds.toy")
    toy.LIMITS = {"feed_differing": 0, "ops_unacknowledged": 0}

    def spec(config):
        return api.SketchSpec(k=config.tenants * config.params["k"],
                              bits=config.params["bits"],
                              tenants=config.tenants)

    class Served:
        def __init__(self, cell, traffic):
            self.cell = cell
            self.svc = SketchService(spec(cell.config),
                                     block=cell.config.block)

        def warm_up(self, tick, query, quiet):
            pass

        def after_tick(self, i):
            pass

        def record(self, drv, sample, win):
            c, tr = self.cell.config, drv.traffic

            def update(u):
                if isinstance(u, tuple):
                    return u
                s0, ln = tr.start[u], tr.length[u]
                return (int(tr.tenant[u]), tr.keys[s0:s0 + ln],
                        tr.weights[s0:s0 + ln])

            return check.Fed(
                item_bits=c.params["bits"], block=c.block,
                tenants=c.tenants, spill_after=None, kept=[],
                fed=[[update(u) for u in ups] for ups, _ in drv.log],
                asked=[qs for _, qs in drv.log], first_tick=drv.first_tick,
                program_blocks=self.svc.trace_blocks,
                program_admits=self.svc.trace_admits,
                unacknowledged=win.unacknowledged)

    def compare(rec, sample, control=False):
        return {"numbers": {
            "feed_differing": check.feed_differing(
                rec, check.expected_feed(rec)),
            "ops_unacknowledged": rec.unacknowledged}, "covered": {}}

    toy.spec, toy.Served, toy.compare = spec, Served, compare
    toy.tiny = lambda config: config
    toy.least_bytes = lambda config, blocks: [len(ci) * 8
                                              for ci, _ in blocks]
    toy.draw_sample = lambda sizes, admitted, seed: [int(np.argmax(sizes))]
    toy.verdict = lambda nums: all(nums[k] <= v
                                   for k, v in toy.LIMITS.items())
    return toy


def _drop_half(drv):
    real = drv.svc.submit

    def half(tenant, items, weights=None):
        n = (len(items) + 1) // 2
        real(tenant, items[:n], None if weights is None else weights[:n])

    drv.svc.submit = half


@pytest.mark.parametrize("fault", [None, _drop_half], ids=["sound", "fault"])
def test_a_kind_added_as_a_file_runs_end_to_end(monkeypatch, tmp_path,
                                                fault):
    monkeypatch.setitem(sys.modules, "chipbench.kinds.toy", _toy_kind())
    conf = tmp_path / "toy.json"
    conf.write_text(json.dumps({"name": "toy", "kind": "toy", "tenants": 64,
                                "block": 256, "k": 16, "bits": 16,
                                "flush_interval_ms": 50}))
    with open(BENCH) as f:
        bench = json.load(f)
    bench = dict(bench, configs=[{"name": "toy", "file": str(conf)}],
                 workloads=[{"name": "toy.zipf_spill", "config": "toy",
                             "traffic": "zipf_spill", "chips": 1}],
                 per_layer=[])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    cell = harness.load_cell("toy.zipf_spill", str(path))
    cell = dataclasses.replace(cell, mix=dataclasses.replace(cell.mix,
                                                             rate=1000.0))
    out = execute(cell, 2**31 + 9, 1.0, False, jax.devices()[:1], bench,
                  device_metrics=False, fault=fault)
    assert set(out["checks"]) == {"feed_differing", "ops_unacknowledged"}
    assert out["attempted"] > 0 and "updates_per_s" in out["metrics"]
    assert out["correct"] is (fault is None)
