"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The TPU compiler ships with jaxlib, so the served path's programs and
every Pallas kernel a user spec can select are compiled here for one
chip of a ``v5e:2x2`` topology, at the widths ``chip_smoke.py`` runs:
what Mosaic or XLA would refuse on the chip fails here first. Nothing
executes; shapes go in, not arrays.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file. The persistent compilation cache is off around the compiles (an
entry written for a described chip cannot be read back without one).
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.sketch import api
from repro.sketch import bank as bk
from repro.sketch import tenant as tn
from repro.sketch.state import SketchState

TENANTS, K_TENANT, BLOCK, BITS = 16384, 2048, 8192, 17
HBM_BYTES = 16 * 2**30
# the per-tenant Dyadic SS± layout: 2,048 tenants x 16 layer rows of the
# widest layer's ceil(2*alpha*bits/eps) = 6,400 counters (eps 0.01)
Q_TENANTS, Q_BITS, Q_K = 2048, 16, 6400


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tenant_bank(sharding):
    shape = (TENANTS, K_TENANT)
    return tn.TenantBank(bank=SketchState(*[_sds(sharding, shape)] * 3))


def test_tenant_bank_ingest(one_chip):
    """The service's donated ingest at 16,384 x 2,048: compiles, fits one
    chip's HBM, and updates the bank in place."""
    spec = api.SketchSpec(kind="frequency", k=TENANTS * K_TENANT,
                          bits=BITS, tenants=TENANTS)
    ad = api.adapter_for(spec)
    c = jax.jit(lambda s, i, w: ad.update(spec, s, i, w),
                donate_argnums=(0,)).lower(
        _tenant_bank(one_chip), _sds(one_chip, (BLOCK,)),
        _sds(one_chip, (BLOCK,))).compile()
    mem = c.memory_analysis()
    state = 3 * TENANTS * K_TENANT * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def _level_bank(sharding):
    shape = (Q_TENANTS * Q_BITS, Q_K)
    return tn.TenantLevels(bank=SketchState(*[_sds(sharding, shape)] * 3),
                           mass=_sds(sharding, (Q_TENANTS,)))


def test_tenant_levels_ingest(one_chip):
    """The per-tenant dyadic ingest at 2,048 tenants x 16 layers x 6,400:
    the block expands 16-fold on the device, takes the touched-rows path,
    compiles, fits one chip's HBM, and updates the bank in place."""
    spec = api.SketchSpec(kind="quantile", tenants=Q_TENANTS, bits=Q_BITS,
                          eps=0.01)
    assert max(spec.layer_capacities()) == Q_K
    assert bk.takes_touched(bk.TenantLevelRouter(Q_TENANTS, Q_BITS).rows,
                            Q_BITS * BLOCK, Q_K)
    ad = api.adapter_for(spec)
    c = jax.jit(lambda s, i, w: ad.update(spec, s, i, w),
                donate_argnums=(0,)).lower(
        _level_bank(one_chip), _sds(one_chip, (BLOCK,)),
        _sds(one_chip, (BLOCK,))).compile()
    mem = c.memory_analysis()
    state = 3 * Q_TENANTS * Q_BITS * Q_K * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_tenant_quantiles_search(one_chip):
    """The service's batched quantile search: 8 tenants x 3 quantiles in
    one program at the full layout."""
    jax.jit(tn.tenant_quantiles).lower(
        _level_bank(one_chip), _sds(one_chip, (8,)),
        _sds(one_chip, (8, 3), jnp.float32)).compile()


@pytest.mark.parametrize("move", ["evict", "admit"])
def test_batched_spill_moves_in_place(one_chip, move):
    """The service's donated batched spill and re-admission at 16,384 x
    2,048 update the bank in place: it aliases the output, and no temp
    comes near a copy of it."""
    evict, admit = tn._batch_moves(True)
    n = tn.SPILL_BATCH
    rows = _sds(one_chip, (n,))
    bank = _tenant_bank(one_chip).bank
    if move == "evict":
        c = evict.lower(bank, rows).compile()
    else:
        c = admit.lower(bank, rows, SketchState(
            *[_sds(one_chip, (n, K_TENANT))] * 3)).compile()
    mem = c.memory_analysis()
    state = 3 * TENANTS * K_TENANT * 4
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < TENANTS * K_TENANT * 4 // 16


@pytest.mark.parametrize("n_keys", [128, 65536])
def test_query_rows(one_chip, n_keys):
    router = bk.TenantRouter(TENANTS, BITS, 1)
    jax.jit(lambda s, k: tn.query_many_tenant(s, k, router)).lower(
        _tenant_bank(one_chip), _sds(one_chip, (n_keys,))).compile()


def test_topk_rows(one_chip):
    jax.jit(lambda s, t: tn.topk_tenants(
        s, t, 16, num_shards=1, item_bits=BITS)).lower(
        _tenant_bank(one_chip), _sds(one_chip, (8,))).compile()


@pytest.mark.parametrize("rows,k", [(16, 4096), (256, 1024)])
def test_fused_sketch_kernel(one_chip, rows, k):
    from repro.kernels.sketch_update.kernel import sketch_update_kernel_fused

    state = [_sds(one_chip, (rows, k))] * 4
    stream = [_sds(one_chip, (rows, BLOCK))] * 2
    scal = [_sds(one_chip, (rows,))] * 4
    c = jax.jit(lambda *a: sketch_update_kernel_fused(
        *a, interpret=False)).lower(*state, *stream, *scal).compile()
    assert "tpu_custom_call" in c.as_text()


def test_decode_attention(one_chip):
    from repro.kernels.decode_attention.kernel import decode_attention_kernel

    B, KV, G, hd, C = 8, 8, 4, 128, 8192
    bf = jnp.bfloat16
    jax.jit(lambda q, k, v, m: decode_attention_kernel(
        q, k, v, m, interpret=False)).lower(
        _sds(one_chip, (B, KV, G, hd), bf), _sds(one_chip, (B, KV, C, hd), bf),
        _sds(one_chip, (B, KV, C, hd), bf),
        _sds(one_chip, (B, 1, C))).compile()


def test_flash_attention(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention_kernel

    bf = jnp.bfloat16
    jax.jit(lambda q, k, v: flash_attention_kernel(
        q, k, v, group=4, interpret=False)).lower(
        _sds(one_chip, (32, 2048, 128), bf), _sds(one_chip, (8, 2048, 128), bf),
        _sds(one_chip, (8, 2048, 128), bf)).compile()
