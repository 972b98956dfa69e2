"""The traffic generator: seeded, valid bounded-deletion streams, and
the marginals of the generators it copies (``mixed_traffic`` and the
CAIDA surrogate)."""
import dataclasses
import json
import time

import numpy as np
import pytest

from chipbench.traffic import QUERY, UPDATE, Mix, generate, sample_items

OPEN = Mix(name="open", arrival="open", tenant_skew=1.2, item_dist="zipf",
           universe_bits=16, delete_ratio=0.5, burst=64, query_frac=0.1,
           query_keys=8, rate=4000.0, topk_subscriptions=8)
SAT = Mix(name="sat", arrival="saturated", tenant_skew=1.0,
          item_dist="caida", universe_bits=16, delete_ratio=0.5, burst=8,
          epoch_updates=1 << 15, epochs=3, topk_subscriptions=8)


def _updates(tr):
    u = np.flatnonzero(tr.kind == UPDATE)
    idx = np.concatenate([np.arange(tr.start[i], tr.start[i] + tr.length[i])
                          for i in u])
    ten = np.repeat(tr.tenant[u], tr.length[u]).astype(np.int64)
    return ten, tr.keys[idx], tr.weights[idx]


def _min_running(ten, items, w):
    """The lowest count any (tenant, item) reaches, in stream order."""
    key = (ten << 20) | items
    order = np.argsort(key, kind="stable")
    ks, ws = key[order], w[order].astype(np.int64)
    cs = np.cumsum(ws)
    starts = np.r_[0, np.flatnonzero(ks[1:] != ks[:-1]) + 1]
    base = np.repeat(cs[starts] - ws[starts], np.diff(np.r_[starts, len(ks)]))
    return int((cs - base).min())


@pytest.mark.parametrize("mix", [OPEN, SAT], ids=["open", "saturated"])
def test_no_count_goes_negative(mix):
    tr = generate(mix, 512, 2**31 + 5, 20.0)
    ten, items, w = _updates(tr)
    assert _min_running(ten, items, w) == 0
    # and played twice over (a saturated run that wraps) it stays valid
    assert _min_running(np.r_[ten, ten], np.r_[items, items],
                        np.r_[w, w]) == 0


def test_every_seed_sends_the_same_sizes():
    a = generate(OPEN, 512, 1, 20.0)
    b = generate(OPEN, 512, 2, 20.0)
    assert np.array_equal(np.sort(a.sizes), np.sort(b.sizes))
    assert not np.array_equal(a.sizes, b.sizes)
    assert (a.kind == QUERY).sum() == (b.kind == QUERY).sum()
    assert a.n_updates == b.n_updates


@pytest.mark.parametrize("mix", [OPEN, SAT], ids=["open", "saturated"])
def test_seeded(mix):
    a = generate(mix, 256, 2**31 + 11, 5.0)
    b = generate(mix, 256, 2**31 + 11, 5.0)
    c = generate(mix, 256, 2**31 + 12, 5.0)
    for f in ("kind", "tenant", "start", "length", "keys", "weights"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.keys, c.keys)


def test_open_loop_schedule():
    tr = generate(OPEN, 1024, 7, 51.0)
    # each tenant's deletions round down: at most one update per tenant
    assert OPEN.rate * 51.0 - 1024 <= tr.n_updates <= OPEN.rate * 51.0 + 1
    assert (np.diff(tr.due) >= 0).all() and tr.due[-1] < 51.0
    assert tr.due[-1] > 51.0 - 2 * OPEN.burst / OPEN.rate
    # ten times the rate over the longest window: generated in seconds
    t0 = time.perf_counter()
    big = generate(dataclasses.replace(OPEN, rate=OPEN.rate * 10), 16384,
                   7, 51.0)
    assert time.perf_counter() - t0 < 20.0
    assert big.due[-1] < 51.0


def test_stream_shape_per_tenant():
    tr = generate(OPEN, 512, 3, 30.0)
    ten, items, w = _updates(tr)
    ins = np.bincount(ten[w > 0], minlength=512)
    dels = np.bincount(ten[w < 0], minlength=512)
    assert np.array_equal(dels, np.floor(0.5 * ins).astype(int))
    assert np.array_equal(tr.sizes, ins + dels)
    u = tr.kind == UPDATE
    assert tr.length[u].max() == OPEN.burst and tr.length[u].min() >= 1
    # each query probes the burst that precedes it, of the same tenant
    last = {}
    for i in range(tr.n_ops):
        t = tr.tenant[i]
        if tr.kind[i] == UPDATE:
            last[t] = set(tr.keys[tr.start[i]:tr.start[i] + tr.length[i]])
        else:
            probes = tr.keys[tr.start[i]:tr.start[i] + tr.length[i]]
            assert set(probes) <= last[t]
    share = (tr.kind == QUERY).sum() / u.sum()
    assert 0.07 < share < 0.13


def test_marginals_match_mixed_traffic():
    """Tenant shares, item shares, deletions and query rate as
    ``benchmarks.common.mixed_traffic`` draws them."""
    from benchmarks.common import mixed_traffic

    T, n_ins = 256, 60_000
    ops = mixed_traffic(T, n_ins, delete_ratio=0.5, seed=1)
    o_ten = np.concatenate([np.full(len(o[2]), o[1]) for o in ops
                            if o[0] == "update"])
    o_items = np.concatenate([o[2] for o in ops if o[0] == "update"])
    o_w = np.concatenate([o[3] for o in ops if o[0] == "update"])
    o_q = sum(o[0] == "query" for o in ops)
    o_u = sum(o[0] == "update" for o in ops)
    tr = generate(dataclasses.replace(OPEN, rate=1.5 * n_ins), T, 1, 1.0)
    ten, items, w = _updates(tr)
    assert len(w) == pytest.approx(len(o_w), rel=0.001)
    for a, b in ((o_w < 0).mean(), (w < 0).mean()), :
        assert a == pytest.approx(b, abs=0.005)
    # the largest tenants' shares of updates (the seed deals sizes out
    # to tenant ids, so compare them by rank)
    sa = np.sort(np.bincount(o_ten, minlength=T))[::-1] / len(o_ten)
    sb = np.sort(np.bincount(ten, minlength=T))[::-1] / len(ten)
    assert np.abs(sa[:8] - sb[:8]).max() < 0.02
    # item shares of inserts (rank = id)
    ia = np.bincount(o_items[o_w > 0], minlength=1 << 16) / (o_w > 0).sum()
    ib = np.bincount(items[w > 0], minlength=1 << 16) / (w > 0).sum()
    assert np.abs(ia[:16] - ib[:16]).max() < 0.01
    assert o_q / o_u == pytest.approx(
        (tr.kind == QUERY).sum() / (tr.kind == UPDATE).sum(), abs=0.02)


def test_caida_marginal_matches_the_surrogate():
    from repro.core.streams import caida_like_insertions

    n = 400_000
    a = caida_like_insertions(n, 1 << 16, seed=3)
    b = sample_items(np.random.default_rng(3), "caida", n, 1 << 16)
    fa = np.bincount(a, minlength=1 << 16) / n
    fb = np.bincount(b, minlength=1 << 16) / n
    assert np.abs(fa[:32] - fb[:32]).max() < 0.005
    # the uniform background: the far tail's share
    assert fa[1024:].sum() == pytest.approx(fb[1024:].sum(), abs=0.005)


def test_hot_tenants_take_all_traffic():
    hot = dataclasses.replace(OPEN, hot_tenants=16)
    a = generate(hot, 512, 2**31 + 21, 20.0)
    b = generate(hot, 512, 2**31 + 22, 20.0)
    for tr in (a, b):
        assert (tr.sizes > 0).sum() == 16
        assert set(np.unique(tr.tenant)) == set(np.flatnonzero(tr.sizes))
    # the seed draws the hot set; the skew shares traffic among the 16
    assert set(np.flatnonzero(a.sizes)) != set(np.flatnonzero(b.sizes))
    assert np.array_equal(np.sort(a.sizes), np.sort(b.sizes))
    ins = np.sort(a.sizes[a.sizes > 0])[::-1] / a.sizes.sum()
    want = np.arange(1, 17) ** -1.2
    assert np.abs(ins - want / want.sum()).max() < 0.01
    flat = generate(dataclasses.replace(hot, tenant_skew=0.0), 512, 5, 20.0)
    live = flat.sizes[flat.sizes > 0]
    assert len(live) == 16 and live.max() - live.min() <= 2


@pytest.mark.parametrize("mix", [OPEN, SAT], ids=["open", "saturated"])
def test_targeted_deletes_least_frequent_first(mix):
    from repro.core.streams import deletions_targeted

    tr = generate(dataclasses.replace(mix, delete_pattern="targeted"), 128,
                  2**31 + 23, 10.0)
    ten, items, w = _updates(tr)
    assert _min_running(ten, items, w) == 0
    assert _min_running(np.r_[ten, ten], np.r_[items, items],
                        np.r_[w, w]) == 0
    # each tenant's (one epoch's) stream: inserts, then the deletions the
    # paper's targeted rule picks from them, in its order
    epoch = generate(dataclasses.replace(mix, delete_pattern="targeted",
                                         epochs=1), 128, 2**31 + 23, 10.0)
    ten, items, w = _updates(epoch)
    for t in np.unique(ten)[:24]:
        mine, sign = items[ten == t], w[ten == t]
        n_ins = int((sign > 0).sum())
        assert (sign[:n_ins] == 1).all() and (sign[n_ins:] == -1).all()
        want = deletions_targeted(mine[:n_ins], n_ins // 2)
        assert np.array_equal(mine[n_ins:], want)


def test_bursty_arrivals_keep_the_rate():
    even = generate(OPEN, 512, 2**31 + 25, 51.0)
    for cv in (0.5, 2.0):
        tr = generate(dataclasses.replace(OPEN, arrival_cv=cv), 512,
                      2**31 + 25, 51.0)
        assert np.array_equal(tr.keys, even.keys)
        assert (np.diff(tr.due) >= 0).all() and tr.due[-1] < 51.0
        # the window offers the same updates: the mean rate is kept
        assert tr.n_updates == even.n_updates
        u = np.flatnonzero(tr.kind == UPDATE)
        gaps = np.diff(tr.due[u]) / tr.length[u][:-1]
        assert gaps.std() / gaps.mean() == pytest.approx(cv, rel=0.15)


def test_an_item_distribution_added_as_a_file(monkeypatch, tmp_path):
    import sys
    import types

    toy = types.ModuleType("chipbench.dists.toy")
    toy.sample = lambda rng, n, universe, skew: rng.integers(0, 4, n)
    monkeypatch.setitem(sys.modules, "chipbench.dists.toy", toy)
    path = tmp_path / "toy_items.json"
    d = {k: v for k, v in dataclasses.asdict(OPEN).items() if k != "name"}
    path.write_text(json.dumps(dict(d, item_dist="toy")))
    mix = Mix.load(str(path))
    tr = generate(mix, 64, 9, 5.0)
    assert set(np.unique(tr.keys)) <= {0, 1, 2, 3}
    path.write_text(json.dumps(dict(d, item_dist="no_such_dist")))
    with pytest.raises(ValueError):
        Mix.load(str(path))


def test_unknown_mix_keys_are_refused(tmp_path):
    d = {k: v for k, v in dataclasses.asdict(OPEN).items() if k != "name"}
    path = tmp_path / "typo.json"
    path.write_text(json.dumps(dict(d, hot_tenant=64)))   # hot_tenants
    with pytest.raises(ValueError, match="hot_tenant"):
        Mix.load(str(path))
    path.write_text(json.dumps(dict(d, _note="underscored keys are notes")))
    assert Mix.load(str(path)) == dataclasses.replace(OPEN, name="typo")

