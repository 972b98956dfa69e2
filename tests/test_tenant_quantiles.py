"""The per-tenant Dyadic SS± layout (``SketchSpec(kind='quantile',
tenants=T)``): each tenant's dyadic layers as rows of one bank, ingested
through the touched-rows path, against each tenant's own single-tenant
dyadic bank (rows bit-equal), the Python oracle
``core.quantiles.DyadicQuantile`` (within eps·|F_t|_1) and the batched
subscriptions against per-tenant ``quantile()``."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.quantiles import make_dss_pm
from repro.serve import SketchService
from repro.sketch import api
from repro.sketch import bank as bk
from repro.sketch import dyadic as dy
from repro.sketch import tenant as tn

T, BITS, EPS, BLOCK = 64, 16, 0.25, 512
QS = [0.1, 0.5, 0.9, 0.99]


def _spec():
    return api.SketchSpec(kind="quantile", tenants=T, bits=BITS, eps=EPS)


def _streams(seed=7):
    """Per tenant: Zipf(1.0) values over 2^16, inserts then deletions of
    half of them in random order (delete ratio 0.5, alpha 2); tenant t
    sends about 3,000 / (t + 1) inserts."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, (1 << BITS) + 1)
    p /= p.sum()
    out = {}
    for t in rng.permutation(T).tolist():
        n = max(2, 3000 // (t + 1))
        ins = rng.choice(1 << BITS, n, p=p)
        dels = rng.permutation(ins)[:n // 2]
        out[t] = (np.r_[ins, dels].astype(np.int64),
                  np.r_[np.ones(n), -np.ones(n // 2)].astype(np.int64))
    return out


def _feed(svc, streams, ticks=3, burst=64):
    """Each tenant's stream in bursts, interleaved over ``ticks`` ticks."""
    cursor = dict.fromkeys(streams, 0)
    per_tick = {t: -(-len(v[0]) // ticks) for t, v in streams.items()}
    for _ in range(ticks):
        for t, (vals, w) in streams.items():
            end = min(len(vals), cursor[t] + per_tick[t])
            for s in range(cursor[t], end, burst):
                e = min(end, s + burst)
                svc.submit(t, vals[s:e], w[s:e])
            cursor[t] = end
        svc.tick()


@pytest.fixture(scope="module")
def served():
    svc = SketchService(_spec(), block=BLOCK)
    svc.trace_blocks = []
    for t in (0, 1, 5, 33):
        svc.subscribe_quantile(t, QS)
    streams = _streams()
    _feed(svc, streams)
    return svc, streams


def test_rows_equal_each_tenants_own_dyadic_bank(served):
    """Each tenant's 16 rows are bit-equal to a single-tenant dyadic bank
    fed the tenant's own entries of every block, and its mass is
    exact."""
    svc, streams = served
    bank = svc.session.state.bank
    singles = {t: dy.init(BITS, eps=EPS) for t in range(T)}
    mask = (1 << BITS) - 1
    for ci, cw in svc.trace_blocks:
        live = cw != 0
        ten = ci >> BITS
        for t in np.unique(ten[live]).tolist():
            m = live & (ten == t)
            items = np.zeros(BLOCK, np.int32)
            w = np.zeros(BLOCK, np.int32)
            items[:m.sum()], w[:m.sum()] = ci[m] & mask, cw[m]
            singles[t] = dy.update_block(singles[t], jnp.asarray(items),
                                         jnp.asarray(w))
    for t, one in singles.items():
        rows = slice(t * BITS, (t + 1) * BITS)
        ids = np.asarray(bank.ids[rows])
        want = np.asarray(one.bank.ids)
        np.testing.assert_array_equal(np.where(ids >= 0, ids & mask, ids),
                                      want)
        tags = ids[ids >= 0] >> BITS
        np.testing.assert_array_equal(
            tags, (t * BITS + np.nonzero(ids >= 0)[0]))
        np.testing.assert_array_equal(np.asarray(bank.counts[rows]),
                                      np.asarray(one.bank.counts))
        np.testing.assert_array_equal(np.asarray(bank.errors[rows]),
                                      np.asarray(one.bank.errors))
        assert int(svc.session.state.mass[t]) == int(one.mass) \
            == int(streams[t][1].sum())


@pytest.mark.parametrize("tenant", [0, 1, 2, 5, 33, 63])
def test_ranks_and_quantiles_within_eps_of_the_oracle(served, tenant):
    """Ranks within eps·|F_t|_1 of the Python oracle's and of the exact
    ranks; each quantile's exact rank range meets q·|F_t|_1 ±
    eps·|F_t|_1, as the oracle's does."""
    svc, streams = served
    vals, w = streams[tenant]
    oracle = make_dss_pm(BITS, EPS, 2.0)
    for x, s in zip(vals.tolist(), w.tolist()):
        oracle.update(x, s)
    exact = np.zeros(1 << BITS, np.int64)
    np.add.at(exact, vals, w)
    cum = np.cumsum(exact)
    mass = int(w.sum())
    assert oracle.mass == mass
    xs = np.unique(np.r_[np.linspace(0, (1 << BITS) - 1, 97).astype(int),
                         vals[:32]])
    got = np.asarray(tn.tenant_ranks(
        svc.session.state, jnp.asarray([tenant], jnp.int32),
        jnp.asarray(xs[None], jnp.int32)))[0]
    want = np.asarray([oracle.rank(int(x)) for x in xs])
    assert np.abs(got - want).max() <= EPS * mass
    assert np.abs(got - cum[xs]).max() <= EPS * mass

    def off(q, x):
        lo = cum[x - 1] if x > 0 else 0
        return max(0.0, q * mass - cum[x], lo - q * mass)

    for q, x in zip(QS, svc.quantile(tenant, QS).tolist()):
        assert off(q, x) <= EPS * mass
        assert off(q, oracle.quantile(q)) <= EPS * mass


def test_batched_subscriptions_equal_per_tenant_quantile(served):
    """One search answers every due subscription; each answer is the
    tenant's own ``quantile()`` bit for bit, whatever the other
    subscriptions ask."""
    svc, _ = served
    svc.subscribe_quantile(2, [0.25])
    svc.subscribe_quantile(63, [0.5, 0.75])
    snap = dict(svc.stats)
    svc.tick()
    assert svc.stats["quantiles_n"] - snap["quantiles_n"] == 1
    assert svc.stats["wait_n"] - snap["wait_n"] == 1     # one read
    for t, sub in svc._quant_subs.items():
        got = svc.quantile_result(t)
        assert len(got) == len(sub["qs"])
        np.testing.assert_array_equal(got, svc.quantile(t, sub["qs"]))


def test_level_rows_and_the_touched_path(served):
    """The layer rows a block reaches are 16 for each tenant it reaches,
    and the expanded blocks take the touched-rows ingest, at this size
    and at the benchmark's (2,048 tenants, eps 0.01, block 8,192)."""
    svc, _ = served
    k = svc.session.state.bank.ids.shape[1]
    router = bk.TenantLevelRouter(T, BITS)
    assert bk.takes_touched(router.rows, BITS * BLOCK, k)
    full = api.SketchSpec(kind="quantile", tenants=2048, bits=16, eps=0.01)
    assert bk.takes_touched(bk.TenantLevelRouter(2048, 16).rows,
                            16 * 8192, max(full.layer_capacities()))
    reached = np.asarray([len(np.unique(ci[cw != 0] >> BITS))
                          for ci, cw in svc.trace_blocks])
    assert svc.stats["level_rows"] == BITS * reached.sum()
    cap = bk.touched_chunk_rows(BITS * BLOCK, k)
    assert svc.stats["ingest_chunks"] == (-(-(BITS * reached) // cap)).sum()


def test_spec_layout_and_checkpoint_round_trip(served):
    svc, _ = served
    spec = _spec()
    assert api.backends_for("quantile", None, tenants=T) == ("bank",)
    with pytest.raises(ValueError, match="31"):
        api.SketchSpec(kind="quantile", tenants=4096, bits=16, eps=0.1)
    with pytest.raises(ValueError, match="frequency-kind"):
        api.SketchSpec(kind="quantile", tenants=4, bits=8,
                       tenant_caps=(4,) * 4)
    with pytest.raises(ValueError, match="pack_keys"):
        api.validate_block(spec, np.asarray([T << BITS]), np.asarray([1]))
    with pytest.raises(ValueError, match="spill"):
        SketchService(spec, block=BLOCK, spill_after=2)
    d = api.save(spec, svc.session.state)
    back = api.restore(api.infer_spec(spec, d), d)
    for a, b in zip((*svc.session.state.bank, svc.session.state.mass),
                    (*back.bank, back.mass)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a point query reads the key's tenant's leaf layer
    keys = tn.pack_keys(np.asarray([0, 1]), np.asarray([0, 1]), BITS)
    leaf = np.asarray(api.query_many(spec, svc.session.state, keys))
    for (t, x), got in zip([(0, 0), (1, 1)], leaf):
        ids = np.asarray(svc.session.state.bank.ids[t * BITS])
        cnt = np.asarray(svc.session.state.bank.counts[t * BITS])
        hit = ids == ((t * BITS) << BITS | x)
        assert got == (cnt[hit].sum() if hit.any() else 0)
