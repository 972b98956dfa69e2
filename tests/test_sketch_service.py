"""The multi-tenant sketch service: correctness of the coalesced tick
loop against exact oracles and against twins that never spill, never
checkpoint, and never share the bank.

Everything runs in the exact regime (per-tenant capacity >= distinct
items), so service answers are true frequencies — mismatches localize to
the service loop, not sketch error.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.sketch import api
from repro.sketch import session as ses
from repro.sketch import tenant as tn
from repro.serve import SketchService

BITS = 8


def _freq_spec(T=8, k_t=16, **kw):
    return api.SketchSpec(kind="frequency", k=T * k_t, bits=BITS,
                          tenants=T, **kw)


def test_submit_query_tick_exact_counts():
    svc = SketchService(_freq_spec(), block=64)
    svc.submit(0, [1, 2, 1, 3], [5, 2, 3, 1])
    svc.submit(1, [1, 9], [7, 4])
    svc.submit(0, [2], [-1])          # bounded deletion, same tick
    t0 = svc.query(0, [1, 2, 3, 4])
    t1 = svc.query(1, [1, 9])
    svc.tick()
    np.testing.assert_array_equal(t0.result(), [8, 1, 1, 0])
    np.testing.assert_array_equal(t1.result(), [7, 4])
    assert t0.resolved and t0.latency_s >= 0
    assert svc.stats["ticks"] == 1 and svc.stats["updates"] == 7


def test_ticket_result_forces_tick():
    svc = SketchService(_freq_spec(), block=64)
    svc.submit(3, [5, 5, 5])
    ticket = svc.query(3, [5])
    assert not ticket.resolved
    np.testing.assert_array_equal(ticket.result(), [3])  # implicit tick
    assert svc.stats["ticks"] == 1


def test_tenants_share_item_ids_without_crosstalk():
    svc = SketchService(_freq_spec(), block=64)
    for t in range(8):
        svc.submit(t, np.full(t + 1, 42))
    svc.tick()
    for t in range(8):
        np.testing.assert_array_equal(svc.query(t, [42]).result(), [t + 1])


def test_topk_subscription_matches_direct_topk():
    svc = SketchService(_freq_spec(), block=64)
    svc.subscribe_topk(2, 3)
    svc.subscribe_topk(5, 3)
    rng = np.random.default_rng(1)
    for _ in range(3):
        for t in (2, 5):
            svc.submit(t, rng.integers(0, 16, 20))
        svc.tick()
    for t in (2, 5):
        items, vals = svc.topk_result(t)
        di, dv = api.tenant_topk(svc.spec, svc.session.state, t, 3)
        np.testing.assert_array_equal(np.asarray(items), np.asarray(di))
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(dv))
    svc.unsubscribe(2)
    assert 2 not in svc._topk_subs


def test_per_tenant_window_isolation():
    """Hot-tenant traffic must not expire a cold tenant's batches: each
    tenant expires on its OWN tick schedule (the per-tenant FIFO split;
    a shared global FIFO fails this)."""
    svc = SketchService(_freq_spec(), block=64, window=2)
    svc.submit(1, [7, 7, 7])          # cold tenant: one batch, tick 0
    svc.tick()
    for _ in range(5):                # hot tenant hammers for 5 ticks
        svc.submit(0, [3, 3, 3, 3])
        svc.tick()
    # cold tenant has had no further traffic: nothing of hers expired
    np.testing.assert_array_equal(svc.query(1, [7]).result(), [3])
    # hot tenant keeps exactly the last `window` ticks' mass
    np.testing.assert_array_equal(svc.query(0, [3]).result(), [8])
    # one more cold batch: her window advances by HER schedule only
    svc.submit(1, [7])
    svc.tick()
    np.testing.assert_array_equal(svc.query(1, [7]).result(), [4])
    svc.submit(1, [7])
    svc.tick()                        # third batch -> first expires
    np.testing.assert_array_equal(svc.query(1, [7]).result(), [2])


def test_spill_readmit_matches_never_spilled_twin():
    spec = _freq_spec()
    svc = SketchService(spec, block=64, spill_after=2)
    twin = SketchService(spec, block=64)
    rng = np.random.default_rng(2)

    def both(fn):
        fn(svc), fn(twin)

    for t in range(4):
        items = rng.integers(0, 16, 30)
        both(lambda s, t=t, items=items: s.submit(t, items))
    both(lambda s: s.tick())
    for _ in range(4):                # tenants 1-3 idle past spill_after
        both(lambda s: s.submit(0, [1, 2]))
        both(lambda s: s.tick())
    assert svc.stats["spills"] >= 1
    spilled = set(svc._spilled)
    assert spilled and 0 not in spilled
    # queries + further traffic re-admit exactly
    probe = np.arange(16)
    for t in range(4):
        np.testing.assert_array_equal(svc.query(t, probe).result(),
                                      twin.query(t, probe).result())
    assert svc.stats["admits"] >= 1
    both(lambda s: s.submit(2, [9, 9]))
    both(lambda s: s.tick())
    np.testing.assert_array_equal(svc.query(2, probe).result(),
                                  twin.query(2, probe).result())


def test_save_load_resume_matches_uninterrupted():
    spec = _freq_spec()
    kw = dict(block=64, window=3)
    a = SketchService(spec, **kw)      # uninterrupted
    b = SketchService(spec, **kw)      # checkpointed + resumed
    rng_a, rng_b = (np.random.default_rng(3) for _ in range(2))

    def phase(svc, rng, lo, hi):
        for i in range(lo, hi):
            t = i % 5
            svc.submit(t, rng.integers(0, 16, 10))
            svc.tick()

    phase(a, rng_a, 0, 4)
    phase(b, rng_b, 0, 4)
    d = b.save()
    c = SketchService(spec, **kw)
    c.load(d)
    assert c.tick_count == b.tick_count
    phase(a, rng_a, 4, 9)
    phase(c, rng_b, 4, 9)
    probe = np.arange(16)
    for t in range(5):
        np.testing.assert_array_equal(a.query(t, probe).result(),
                                      c.query(t, probe).result())


def test_spill_wave_wider_than_a_launch_matches_twin(donated):
    """More tenants than one batched launch holds spill in one tick and
    re-admit in one tick, on the donating path; every answer matches a
    twin that never spills."""
    T = tn.SPILL_BATCH + 8
    spec = _freq_spec(T=T, k_t=8)
    svc = SketchService(spec, block=64, spill_after=1)
    twin = SketchService(spec, block=64, donate=False)
    rng = np.random.default_rng(3)
    for t in range(T):
        items = rng.integers(0, 8, 6)
        for s in (svc, twin):
            s.submit(t, items)
    for s in (svc, twin):
        s.tick()
        s.submit(0, [1])
        s.tick()
    assert set(svc._spilled) == set(range(1, T))
    probe = np.arange(8)
    tickets = [(svc.query(t, probe), twin.query(t, probe))
               for t in range(T)]
    svc.tick()
    twin.tick()
    assert svc.stats["admits"] == T - 1
    for a, b in tickets:
        np.testing.assert_array_equal(a.result(), b.result())


def test_save_load_roundtrips_spilled_tenants():
    svc = SketchService(_freq_spec(), block=64, spill_after=1)
    svc.submit(3, [4, 4, 5])
    svc.tick()
    for _ in range(3):
        svc.submit(0, [1])
        svc.tick()
    assert 3 in svc._spilled
    d = svc.save()
    svc2 = SketchService(_freq_spec(), block=64, spill_after=1)
    svc2.load(d)
    assert 3 in svc2._spilled
    np.testing.assert_array_equal(svc2.query(3, [4, 5]).result(), [2, 1])


def test_quantile_mode_subscription():
    spec = api.SketchSpec(kind="quantile", eps=0.02, bits=10)
    svc = SketchService(spec, block=128, tenant_bits=2)
    assert svc.num_tenants == 4 and svc.item_bits == 8
    rng = np.random.default_rng(4)
    data = {t: rng.integers(0, 256, 400) for t in range(4)}
    svc.subscribe_quantile(1, [0.5])
    for t, vals in data.items():
        svc.submit(t, vals)
    svc.tick()
    med = float(np.asarray(svc.quantile_result(1))[0])
    true = np.quantile(data[1], 0.5)
    # eps-rank error over the shared dyadic mass
    assert abs(med - true) <= 0.02 * 4 * 400 * 2 + 8
    direct = np.asarray(svc.quantile(2, [0.25, 0.75]))
    for q, g in zip((0.25, 0.75), direct):
        rank = np.searchsorted(np.sort(data[2]), g, side="right")
        assert abs(rank - q * 400) <= 2 * 0.02 * 1600 + 1


def test_validation_errors():
    spec = _freq_spec(T=4)
    svc = SketchService(spec, block=64)
    with pytest.raises(ValueError, match="out of range"):
        svc.submit(4, [1])
    with pytest.raises(ValueError, match="alias"):
        svc.submit(0, [1 << BITS])
    with pytest.raises(ValueError, match="frequency-mode"):
        SketchService(api.SketchSpec(kind="frequency", k=8, bits=BITS),
                      block=64)
    with pytest.raises(ValueError, match="tenant_bits"):
        SketchService(api.SketchSpec(kind="quantile", eps=0.1, bits=10),
                      block=64)
    with pytest.raises(ValueError, match="quantile"):
        svc.subscribe_quantile(0, [0.5])
    qsvc = SketchService(api.SketchSpec(kind="quantile", eps=0.1, bits=10),
                         block=64, tenant_bits=2)
    with pytest.raises(ValueError, match="frequency"):
        qsvc.subscribe_topk(0, 3)
    with pytest.raises(ValueError, match="spill"):
        SketchService(_freq_spec(T=4, variant="double", alpha=2.0),
                      block=64, spill_after=1)
    with pytest.raises(ValueError, match="not resolved"):
        _ = svc.query(0, [1]).latency_s


def test_double_variant_service():
    """Non-spillable variants still serve: bounded-deletion traffic on
    the double backend, exact in the large-capacity regime."""
    svc = SketchService(_freq_spec(T=4, k_t=12, variant="double",
                                   alpha=2.0), block=64)
    svc.submit(1, [3, 3, 3, 3, 5])
    svc.tick()
    svc.submit(1, [3], [-2])
    svc.tick()
    np.testing.assert_array_equal(svc.query(1, [3, 5]).result(), [2, 1])


def test_service_stats_and_blocks():
    svc = SketchService(_freq_spec(), block=32)
    svc.trace_blocks = []
    svc.submit(0, np.arange(16) % 16)
    svc.submit(7, np.arange(16) % 16)
    svc.tick()
    assert svc.stats["blocks"] == len(svc.trace_blocks) == 1
    big = np.random.default_rng(5).integers(0, 16, 100)
    svc.submit(3, big)
    svc.tick()
    assert svc.stats["blocks"] >= 4  # 100 keys / 32-wide blocks
    assert all(len(i) == 32 for i, _ in svc.trace_blocks)


@pytest.fixture
def donated(monkeypatch):
    """Force the accelerator donation policy on the CPU.

    The compiled-ingest cache keys on the donate flag but not on the
    policy, so it is cleared on the way in and out: no donating entry
    leaks into other tests.
    """
    from repro import platform

    ses._ingest_fn_cached.cache_clear()
    monkeypatch.setattr(platform, "donate_state_buffers", lambda: True)
    yield
    ses._ingest_fn_cached.cache_clear()


def test_donated_service_matches_undonated_twin(donated):
    """The default service path on a chip donates the bank into every
    ingest. Drive it through multi-block ticks (feeder backpressure),
    spill and re-admission, top-k subscriptions and save/load against
    a twin that never donates: any use of a donated buffer raises here,
    on the CPU, instead of on the chip."""
    spec = _freq_spec(T=8, k_t=16)
    svc = SketchService(spec, block=32, spill_after=2)
    twin = SketchService(spec, block=32, spill_after=2, donate=False)
    rng = np.random.default_rng(6)

    def both(fn):
        return fn(svc), fn(twin)

    both(lambda s: s.subscribe_topk(0, 4))
    before = svc.session.state.bank.ids
    for step in range(6):
        hot = rng.integers(0, 16, 150)         # ~5 blocks per tick
        both(lambda s, hot=hot: s.submit(0, hot))
        if step < 2:
            for t in range(1, 8):
                items = rng.integers(0, 16, 10)
                both(lambda s, t=t, items=items: s.submit(t, items))
        both(lambda s: s.tick())
    assert before.is_deleted()                  # the bank was donated
    assert svc.stats["spills"] >= 1 and svc.stats["blocks"] >= 20
    probe = np.arange(16)
    for t in range(8):                          # re-admits spilled rows
        a, b = both(lambda s, t=t: s.query(t, probe).result())
        np.testing.assert_array_equal(a, b)
    assert svc.stats["admits"] >= 1
    for x, y in zip(svc.topk_result(0), twin.topk_result(0)):
        np.testing.assert_array_equal(x, y)

    resumed = SketchService(spec, block=32, spill_after=2)
    resumed.load(svc.save())
    hot = rng.integers(0, 16, 100)
    for s in (resumed, twin):
        s.submit(3, [1, 1, 2])
        s.submit(0, hot)
        s.tick()
    for t in range(8):
        np.testing.assert_array_equal(resumed.query(t, probe).result(),
                                      twin.query(t, probe).result())
    for x, y in zip(resumed.topk(0, 4), twin.topk(0, 4)):
        np.testing.assert_array_equal(x, y)
