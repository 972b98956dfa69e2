"""Platform resolution, donation policy, HW presets and the roofline
cost model (DESIGN.md §14), plus the BlockFeeder host-side pipeline.

All tests assume the CPU CI backend (no accelerator) — the branch both
``resolve_interpret`` and ``donate_state_buffers`` take there is exactly
what these pin.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import platform
from repro.roofline.model import (
    HW_PRESETS,
    hw_for,
    sketch_ingest_cost,
    sketch_roofline,
)
from repro.sketch.api import SketchSpec
from repro.sketch.session import BlockFeeder, StreamSession, _ingest_fn


# -- interpret / donation resolution ------------------------------------


def test_resolve_interpret_tristate():
    # None -> platform-resolved: interpret iff no accelerator
    assert platform.resolve_interpret(None) == (not platform.has_accelerator())
    # explicit bools pass through untouched (the CI pin relies on this)
    assert platform.resolve_interpret(True) is True
    assert platform.resolve_interpret(False) is False


def test_interpret_refused_on_tpu(monkeypatch):
    # on a TPU no kernel entry point may resolve to the interpreter: the
    # default compiles, and an explicit interpret=True pin raises
    monkeypatch.setattr(platform, "default_backend", lambda: "tpu")
    assert platform.resolve_interpret(None) is False
    assert platform.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        platform.resolve_interpret(True)


def test_cpu_backend_resolution():
    if platform.default_backend() != "cpu":
        pytest.skip("accelerator attached")
    assert not platform.has_accelerator()
    assert platform.resolve_interpret(None) is True
    # donation stays off on the CPU by policy (it is forced on where a
    # test must exercise the donated path)
    assert platform.donate_state_buffers() is False


def test_donation_flag_does_not_change_results():
    """donate=True vs donate=False traces differ only in buffer reuse;
    query results are identical (the S2 regression)."""
    spec = SketchSpec(k=64)
    rng = np.random.default_rng(0)
    items = rng.integers(0, 1000, 256).astype(np.int32)
    states = []
    for donate in (True, False):
        s = StreamSession(spec, block=128, donate=donate)
        s.ingest(items, np.ones(256, np.int32))
        states.append(s.query_many(jnp.asarray(items[:32])))
    np.testing.assert_array_equal(np.asarray(states[0]),
                                  np.asarray(states[1]))
    # distinct cache cells: the donate flag is part of the key
    assert _ingest_fn(spec, 128, True) is not _ingest_fn(spec, 128, False)


def test_xla_host_device_flags():
    assert platform.xla_host_device_flags(8) == \
        "--xla_force_host_platform_device_count=8"


# -- HW presets + roofline cost model -----------------------------------


def test_hw_presets_registry():
    # keyed by device_kind; no CPU preset, so a CPU run has no peaks
    assert "TPU v5 lite" in HW_PRESETS and "cpu" not in HW_PRESETS
    for name, hw in HW_PRESETS.items():
        assert hw.peak_flops > 0 and hw.hbm_bw > 0, name
        assert hw.peak_int_ops > 0, name
    with pytest.raises(KeyError, match="device_kind"):
        hw_for("not_a_preset")


def test_hw_config_matches_backend():
    kind = jax.devices()[0].device_kind
    assert platform.device_kind() == kind
    if kind in HW_PRESETS:
        assert platform.hw_config() is HW_PRESETS[kind]
    else:
        # an unknown device (the CPU among them) raises, never defaults
        with pytest.raises(KeyError, match=repr(kind)):
            platform.hw_config()
    assert platform.hw_config("TPU v5 lite") is HW_PRESETS["TPU v5 lite"]


def test_sketch_ingest_cost_shape():
    c = sketch_ingest_cost(num_rows=4, k=200, block=512)
    assert c["bytes"] > 0 and c["flops"] > 0
    # k pads to the lane width: k=200 and k=256 cost the same state bytes
    c2 = sketch_ingest_cost(num_rows=4, k=256, block=512)
    assert c["bytes"] == c2["bytes"]
    # residual trips only add flops, never bytes
    c3 = sketch_ingest_cost(num_rows=4, k=200, block=512, residual_trips=7)
    assert c3["bytes"] == c["bytes"] and c3["flops"] > c["flops"]


def test_sketch_roofline_columns():
    cost = sketch_ingest_cost(num_rows=1, k=4096, block=4096)
    roof = sketch_roofline(cost, wall_s=1e-3, hw=HW_PRESETS["TPU v5 lite"])
    for col in ("achieved_bytes_per_s", "peak_fraction", "arith_intensity",
                "bound_s", "bound"):
        assert col in roof, col
    assert roof["achieved_bytes_per_s"] == pytest.approx(cost["bytes"] / 1e-3)
    assert 0 < roof["arith_intensity"] < 10  # int32 scatter is memory-bound
    assert roof["bound"] in ("memory", "compute")
    # no preset (a CPU run): the device columns are not measured
    off = sketch_roofline(cost, wall_s=1e-3, hw=None)
    assert off["peak_fraction"] == off["bound"] == "not measured"
    assert off["achieved_bytes_per_s"] == roof["achieved_bytes_per_s"]


# -- BlockFeeder: pipelined == sequential -------------------------------


def _blocks(n_blocks, block, seed=5):
    rng = np.random.default_rng(seed)
    items = rng.integers(0, 4096, (n_blocks, block)).astype(np.int32)
    weights = rng.choice([-1, 1, 1, 2], (n_blocks, block)).astype(np.int32)
    return items, weights


@pytest.mark.parametrize("depth", [1, 2])
def test_block_feeder_bit_identical(depth):
    spec = SketchSpec(k=128, shards=4)
    items, weights = _blocks(5, 256)
    seq = StreamSession(spec, block=256)
    for i in range(5):
        seq.ingest_block(items[i], weights[i])
    fed = StreamSession(spec, block=256)
    feeder = BlockFeeder(fed, depth=depth)
    for i in range(5):
        feeder.feed(items[i], weights[i])
    state = feeder.flush()
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(seq.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_block_feeder_flush_idempotent():
    spec = SketchSpec(k=64)
    feeder = BlockFeeder(StreamSession(spec, block=128))
    items, weights = _blocks(1, 128)
    feeder.feed(items[0], weights[0])
    s1 = feeder.flush()
    s2 = feeder.flush()  # nothing staged: no double ingest
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- host-device mesh recipe --------------------------------------------


def test_host_device_mesh_error_cites_recipe():
    from repro.parallel.sharding import host_device_mesh

    n = len(jax.devices())
    if n >= 64:
        pytest.skip("unexpectedly many devices")
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        host_device_mesh(64)
