"""Tenant isolation: the multi-tenant bank vs independent per-tenant
sketches, bit for bit.

Four groups:

  * **Routing**: TenantRouter's owner map (tenant-major rows, composed
    per-tenant hash shards), composite key pack/unpack, and foreign-
    weight masking in route_dense.
  * **Isolation parity** (the PR's acceptance bill): a multi-tenant
    ``SketchSpec(tenants=T)`` fed coalesced composite-key blocks answers
    every per-tenant query/top-k EXACTLY like independently built
    per-tenant sketches fed the same fragments — across variant
    {sspm, lazy, double} x delete ratio {0.0, 0.5, 0.9}, sharded and
    not, plus the serial per-row oracle and a hypothesis fuzz.
  * **Spill / re-admission**: cold-row eviction round-trips (spill ->
    clear -> admit) preserve every query and top-k bit-for-bit, survive
    npz serialization, and re-impose per-tenant capacity masks.
  * **Session plumbing**: the compiled-ingest cache normalizes tenant
    layouts onto one entry (``ingest_cache_spec``), and per-tenant
    window FIFOs round-trip through ``save(include_schedule=True)`` —
    the failing-before regression: pre-tenant checkpoints collapsed all
    tenants onto one expiry horizon.
"""
import io

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as hyp_st

import jax
import jax.numpy as jnp

from repro.sketch import api, bank as bk, tenant as tn
from repro.sketch import session as ses
from helpers import random_strict_stream

BITS = 8
UNIVERSE = 1 << BITS


def _tenant_streams(seed, T, n=400, delete_frac=0.3):
    """One strict bounded-deletion stream per tenant."""
    rng = np.random.default_rng(seed)
    return [random_strict_stream(rng, n, UNIVERSE, delete_frac)
            for _ in range(T)]


def _interleave(streams, seed=0):
    """Fragments of all tenants' streams, globally interleaved while
    preserving each tenant's own order: [(tenant, items, weights)]."""
    rng = np.random.default_rng(seed)
    frags = []
    for t, (items, weights) in enumerate(streams):
        for a in range(0, len(items), 37):
            frags.append((t, np.asarray(items[a:a + 37], np.int32),
                          np.asarray(weights[a:a + 37], np.int32)))
    labels = np.repeat(np.arange(len(streams)),
                       [sum(1 for f in frags if f[0] == t)
                        for t in range(len(streams))])
    rng.shuffle(labels)
    per = {t: [f for f in frags if f[0] == t] for t in range(len(streams))}
    cur = {t: 0 for t in per}
    out = []
    for t in labels:
        out.append(per[t][cur[t]])
        cur[t] += 1
    return out


def _blocks_of(frags, T, block=96):
    """Coalesce interleaved fragments into padded composite-key blocks
    AND per-tenant per-block raw fragments (the parity twins' feed)."""
    keys = np.concatenate([
        tn.pack_keys(np.full(len(i), t, np.int64), i.astype(np.int64), BITS)
        for t, i, _ in frags]).astype(np.int32)
    weights = np.concatenate([w for _, _, w in frags]).astype(np.int32)
    nb = -(-len(keys) // block)
    keys = np.pad(keys, (0, nb * block - len(keys)))
    weights = np.pad(weights, (0, nb * block - len(weights)))
    blocks = [(keys[s:s + block], weights[s:s + block])
              for s in range(0, len(keys), block)]
    per_tenant = []
    for ci, cw in blocks:
        tt, it = tn.unpack_keys(ci.astype(np.int64), BITS)
        per_tenant.append({
            t: (it[(tt == t) & (cw != 0)].astype(np.int32),
                cw[(tt == t) & (cw != 0)])
            for t in range(T) if ((tt == t) & (cw != 0)).any()})
    return blocks, per_tenant


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------

def test_pack_unpack_roundtrip():
    t = np.asarray([0, 3, 7], np.int64)
    x = np.asarray([0, 200, 255], np.int64)
    k = tn.pack_keys(t, x, BITS)
    tt, xx = tn.unpack_keys(k, BITS)
    np.testing.assert_array_equal(tt, t)
    np.testing.assert_array_equal(xx, x)


def test_router_owner_map_unsharded():
    r = bk.TenantRouter(8, BITS, 1)
    assert r.num_rows == 8 and r.universe_bits == BITS + 3
    keys = tn.pack_keys(np.arange(8), np.full(8, 5), BITS)
    rows = np.asarray(r.owner_of(jnp.asarray(keys, jnp.int32)))
    np.testing.assert_array_equal(rows, np.arange(8))


def test_router_owner_map_sharded_matches_per_tenant_hash():
    S = 4
    r = bk.TenantRouter(3, BITS, S)
    items = np.arange(UNIVERSE, dtype=np.int32)
    per_tenant = np.asarray(bk.shard_of(jnp.asarray(items), S))
    for t in range(3):
        keys = tn.pack_keys(np.full(UNIVERSE, t), items, BITS)
        rows = np.asarray(r.owner_of(jnp.asarray(keys, jnp.int32)))
        np.testing.assert_array_equal(rows, t * S + per_tenant)


def test_route_dense_masks_foreign_weights():
    r = bk.TenantRouter(4, BITS, 1)
    keys = tn.pack_keys(np.asarray([0, 1, 2, 3]), np.asarray([9, 9, 9, 9]),
                        BITS).astype(np.int32)
    ri, rw = r.route_dense(jnp.asarray(keys), jnp.ones(4, jnp.int32))
    rw = np.asarray(rw)
    assert rw.shape == (4, 4)
    # each row keeps exactly its own tenant's unit weight
    np.testing.assert_array_equal(rw.sum(axis=1), np.ones(4))
    ri = np.asarray(ri)
    for row in range(4):
        hot = rw[row] > 0
        np.testing.assert_array_equal(ri[row][hot] >> BITS, [row])


def test_spec_validation():
    with pytest.raises(ValueError, match="frequency"):
        api.SketchSpec(kind="quantile", bits=8, eps=0.1, tenants=4)
    with pytest.raises(ValueError, match="tenant"):
        api.SketchSpec(kind="frequency", k=8, bits=8, tenant_caps=(4, 4))
    with pytest.raises(ValueError):
        api.SketchSpec(kind="frequency", k=8, tenants=4)  # bits required
    with pytest.raises(ValueError, match="31"):
        api.SketchSpec(kind="frequency", k=8, bits=30, tenants=16)
    # composite keys outside the tenant universe are rejected
    spec = api.SketchSpec(kind="frequency", k=8, bits=8, tenants=2)
    with pytest.raises(ValueError, match="pack_keys"):
        api.validate_block(spec, np.asarray([2 << BITS]),
                           np.asarray([1]))


# ---------------------------------------------------------------------------
# Isolation parity
# ---------------------------------------------------------------------------

def _mt_spec(T, variant, shards, k_t):
    kw = dict(kind="frequency", k=T * k_t, bits=BITS, tenants=T,
              variant=variant)
    if variant == "double":
        kw["alpha"] = 2.0
    if shards > 1:
        kw["shards"] = shards
    return api.SketchSpec(**kw)


def _solo_spec(variant, shards, k_t):
    kw = dict(kind="frequency", k=k_t, bits=BITS, variant=variant)
    if variant == "double":
        kw["alpha"] = 2.0
    if shards > 1:
        kw["shards"] = shards
    return api.SketchSpec(**kw)


def _assert_parity(T, variant, shards, k_t, delete_frac, seed):
    spec_mt = _mt_spec(T, variant, shards, k_t)
    spec_1 = _solo_spec(variant, shards, k_t)
    frags = _interleave(_tenant_streams(seed, T, delete_frac=delete_frac),
                        seed=seed)
    blocks, per_tenant = _blocks_of(frags, T)
    st_mt = api.make(spec_mt)
    twins = [api.make(spec_1) for _ in range(T)]
    for (ci, cw), pt in zip(blocks, per_tenant):
        st_mt = api.update(spec_mt, st_mt, jnp.asarray(ci),
                           jnp.asarray(cw))
        for t, (it, wt) in pt.items():
            twins[t] = api.update(spec_1, twins[t], jnp.asarray(it),
                                  jnp.asarray(wt))
    probe = np.arange(UNIVERSE, dtype=np.int32)
    for t in range(T):
        pk = tn.pack_keys(np.full(UNIVERSE, t, np.int64),
                          probe.astype(np.int64), BITS).astype(np.int32)
        q_mt = np.asarray(api.query_many(spec_mt, st_mt, jnp.asarray(pk)))
        q_1 = np.asarray(api.query_many(spec_1, twins[t],
                                        jnp.asarray(probe)))
        np.testing.assert_array_equal(
            q_mt, q_1, err_msg=f"tenant {t} query parity "
            f"({variant}, S={shards}, del={delete_frac})")
        # double's top-k candidates are the insert bank's k_I slots
        m = 4 if variant == "double" else k_t
        i_mt, v_mt = api.tenant_topk(spec_mt, st_mt, t, m)
        i_1, v_1 = api.topk(spec_1, twins[t], m)
        np.testing.assert_array_equal(np.asarray(i_mt), np.asarray(i_1))
        np.testing.assert_array_equal(np.asarray(v_mt), np.asarray(v_1))
    return st_mt, spec_mt


@pytest.mark.parametrize("variant", ["sspm", "lazy", "double"])
@pytest.mark.parametrize("delete_frac", [0.0, 0.5, 0.9])
def test_isolation_parity(variant, delete_frac):
    # k_t=6 with alpha=2 splits exactly per tenant (k_I=4, k_D=2) so the
    # double layout's per-row capacities match the solo twin's
    _assert_parity(T=5, variant=variant, shards=1, k_t=6,
                   delete_frac=delete_frac, seed=11)


@pytest.mark.parametrize("variant", ["sspm", "double"])
def test_isolation_parity_sharded(variant):
    _assert_parity(T=3, variant=variant, shards=2, k_t=6,
                   delete_frac=0.4, seed=13)


@pytest.mark.parametrize("variant_id", [1, 2])
@pytest.mark.parametrize("shards", [1, 2])
def test_fused_matches_serial_reference(variant_id, shards):
    T = 5
    router = tn.router_for(T, BITS, shards)
    tb = tn.init_tenants(6, num_tenants=T, num_shards=shards)
    frags = _interleave(_tenant_streams(3, T), seed=3)
    blocks, _ = _blocks_of(frags, T)
    ref = tb
    for ci, cw in blocks:
        tb = tn.update_block(tb, jnp.asarray(ci), jnp.asarray(cw), router,
                             variant_id)
        ref = tn.update_serial_reference(ref, ci, cw, router, variant_id)
    for a, b in zip(tb.bank, ref.bank):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# block shapes of the touched-rows cases: "mixed" is random traffic;
# "repeats" repeats already-monitored keys inside a block (segment
# heads); "padding" adds -1 padding and weight-0 keys; "caps" gives the
# tenants capacities below k (BLOCKED slots); "empties" keeps EMPTY slots
# in every row; "deletes" deletes monitored keys (a negative net).
TOUCHED_CASES = ["mixed", "repeats", "padding", "caps", "empties", "deletes"]


def _touched_block(case, rng, tenants, heavy, B, spread, step):
    """(tenant, item, weight) arrays of one block of a touched-rows case:
    every tenant of ``tenants`` gets an entry, ``heavy`` gets the rest."""
    t = np.concatenate([tenants, rng.choice(heavy, B - spread)])
    items = rng.integers(0, 200, B)
    w = rng.choice([-1, 1, 1, 2], B)
    w[spread:][rng.random(B - spread) < 0.1] = 0
    if case == "repeats":
        # a few keys per tenant, inserted again block after block
        items = rng.integers(0, 6, B)
        w = rng.choice([-1, 1, 1, 1, 2], B)
    elif case == "empties":
        items = rng.integers(0, 24, B)   # < k distinct keys per tenant
    elif case == "deletes":
        items = rng.integers(0, 40, B)
        w = (np.where(rng.random(B) < 0.85, -1, 1) if step % 2
             else rng.choice([1, 2, 3], B))
    return t, items, w


@pytest.mark.parametrize("chunks,variant_id,case", [
    pytest.param(c, v, case, id=f"{c}-{v}" if case == "mixed"
                 else f"{c}-{v}-{case}")
    for case in TOUCHED_CASES for c in (1, 4) for v in (1, 2)])
def test_touched_rows_path_matches_whole_bank(variant_id, chunks, case):
    """A bank wider than one chunk of ``touched_chunk_rows``: blocks
    reaching one chunk's worth of tenants, or four chunks' (the last
    partial), equal the whole-bank ingest bit for bit, through
    evictions and deletions, in every case of ``TOUCHED_CASES``."""
    B, k = 256, 64
    cap = bk.touched_chunk_rows(B, k)
    T = 4 * cap + 8
    spread = (chunks - 1) * cap + cap // 2 + 4
    router = tn.router_for(T, BITS, 1)
    assert bk.takes_touched(router, B, k)
    rng = np.random.default_rng([chunks, TOUCHED_CASES.index(case)])
    caps = (rng.integers(1, k + 1, T).tolist() if case == "caps" else k)
    bank = tn.init_tenants(caps, num_tenants=T).bank
    ref = bank
    whole = jax.jit(bk._fused_partition, static_argnums=(3, 4))
    heavy = rng.choice(T, 2, replace=False)
    grew = dropped = False   # a kept id's count rose / fell in a block
    for step in range(4):
        others = rng.choice(np.setdiff1d(np.arange(T), heavy), spread - 2,
                            replace=False)
        t, items, w = _touched_block(case, rng, np.concatenate(
            [heavy, others]), heavy, B, spread, step)
        keys = tn.pack_keys(t, items, BITS)
        if case == "padding":
            pad = rng.random(B) < 0.2
            keys[pad] = -1
            w[pad] = 0
            w[rng.random(B) < 0.1] = 0
        keys = jnp.asarray(keys.astype(np.int32))
        w = jnp.asarray(w.astype(np.int32))
        bank = bk.update_block_fused(bank, keys, w, router, variant_id)
        prev, ref = ref, whole(ref, keys, w, router, variant_id)
        kept = (np.asarray(ref.ids) == np.asarray(prev.ids)) \
            & (np.asarray(prev.ids) >= 0)
        delta = np.asarray(ref.counts) - np.asarray(prev.counts)
        grew |= bool((kept & (delta > 0)).any())
        dropped |= bool((kept & (delta < 0)).any())
    ids, errors = np.asarray(ref.ids), np.asarray(ref.errors)
    if case == "empties":
        assert ((ids == -1).sum(1) > 0).all()   # every row kept an EMPTY
    elif case == "repeats":
        assert grew
    elif case == "deletes":
        assert dropped
    else:
        assert int((errors > 0).sum()) > 0  # evictions ran
    if case == "caps":
        assert int((ids == -2).sum()) > 0
    for a, b in zip(bank, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rows,block", [(40, 64), (8, 64)])
def test_owner_row_match_equals_search_match(rows, block):
    """The touched path's owner-row match gives the slots and the
    ``monitored`` mask the whole-bank searchsorted match gives, on random
    banks with EMPTY and BLOCKED slots, whether the keys are compared all
    at once (block <= 3 rows) or a slice at a time (block > 3 rows)."""
    k = 16
    rng = np.random.default_rng(rows)
    router = tn.router_for(rows, BITS, 1)
    for _ in range(3):
        caps = rng.integers(1, k + 1, rows)
        ids = np.full((rows, k), -2, np.int64)
        counts = np.full((rows, k), 2**31 - 1, np.int64)
        for r, c in enumerate(caps):
            live = rng.choice(UNIVERSE, rng.integers(0, c + 1), replace=False)
            ids[r, :c] = -1
            counts[r, :c] = 0
            slots = rng.choice(c, len(live), replace=False)
            ids[r, slots] = tn.pack_keys(np.full(len(live), r), live, BITS)
            counts[r, slots] = rng.integers(1, 50, len(live))
        bank = bk.SketchState(jnp.asarray(ids, jnp.int32),
                              jnp.asarray(counts, jnp.int32),
                              jnp.zeros((rows, k), jnp.int32))
        # half the keys are monitored ids (repeated), the rest random
        # keys of any tenant the key width allows, padding, weight 0
        monitored_ids = ids[ids >= 0]
        keys = np.where(
            rng.random(block) < 0.5, rng.choice(monitored_ids, block),
            tn.pack_keys(rng.integers(0, 1 << router.tenant_bits, block),
                         rng.integers(0, UNIVERSE, block), BITS))
        w = rng.choice([-2, -1, 0, 1, 2], block)
        pad = rng.random(block) < 0.1
        keys[pad], w[pad] = -1, 0
        uids, net, valid = bk._partition_aggregate(
            jnp.asarray(keys, jnp.int32), jnp.asarray(w, jnp.int32),
            router.universe_bits)
        c_search, m_search = bk._match_search(bank, uids, net)
        owner = np.asarray(router.owner_of(uids))
        slot, m_owner = map(np.asarray, bk._match_owner_rows(
            bank.ids, uids, valid, router.owner_of(uids)))
        m_search = np.asarray(m_search & valid)
        assert m_search.any()
        np.testing.assert_array_equal(m_owner, m_search)
        # the matched slots hold the keys, and adding each matched net
        # there gives the counts the search match gives
        np.testing.assert_array_equal(ids[owner[m_owner], slot[m_owner]],
                                      np.asarray(uids)[m_owner])
        c_owner = counts.copy()
        np.add.at(c_owner, (owner[m_owner], slot[m_owner]),
                  np.asarray(net)[m_owner])
        np.testing.assert_array_equal(c_owner, np.asarray(c_search))


@given(seed=hyp_st.integers(0, 2**16), delete_frac=hyp_st.sampled_from(
    [0.0, 0.3, 0.7]))
@settings(max_examples=15, deadline=None)
def test_isolation_parity_fuzz(seed, delete_frac):
    _assert_parity(T=3, variant="sspm", shards=1, k_t=4,
                   delete_frac=delete_frac, seed=seed)


def test_global_topk_speaks_composite_keys():
    spec = api.SketchSpec(kind="frequency", k=16, bits=BITS, tenants=4)
    st_mt = api.make(spec)
    keys = tn.pack_keys(np.asarray([2] * 9), np.asarray([7] * 9), BITS)
    st_mt = api.update(spec, st_mt, jnp.asarray(keys.astype(np.int32)),
                       jnp.ones(9, jnp.int32))
    ids, vals = api.topk(spec, st_mt, 1)
    t, x = tn.unpack_keys(int(np.asarray(ids)[0]), BITS)
    assert (t, x, int(np.asarray(vals)[0])) == (2, 7, 9)


def test_tenant_caps_row_capacities():
    spec = api.SketchSpec(kind="frequency", bits=BITS, tenants=3,
                          tenant_caps=(2, 5, 3))
    st_mt = api.make(spec)
    open_slots = (np.asarray(st_mt.bank.ids) != -2).sum(axis=1)
    np.testing.assert_array_equal(open_slots, [2, 5, 3])
    assert spec.capacity == 10


# ---------------------------------------------------------------------------
# Spill / exact re-admission
# ---------------------------------------------------------------------------

def _built_bank(T=4, S=1, k_t=6, seed=5, n=400):
    spec = api.SketchSpec(kind="frequency", k=T * k_t, bits=BITS,
                          tenants=T, shards=S if S > 1 else None)
    st_mt = api.make(spec)
    frags = _interleave(_tenant_streams(seed, T, n=n), seed=seed)
    blocks, _ = _blocks_of(frags, T)
    for ci, cw in blocks:
        st_mt = api.update(spec, st_mt, jnp.asarray(ci), jnp.asarray(cw))
    return spec, st_mt


@pytest.mark.parametrize("shards", [1, 2])
def test_spill_admit_roundtrip_preserves_queries(shards):
    spec, st_mt = _built_bank(S=shards)
    S = spec.shards or 1
    probe = np.arange(UNIVERSE, dtype=np.int32)
    pk = tn.pack_keys(np.full(UNIVERSE, 1, np.int64),
                      probe.astype(np.int64), BITS).astype(np.int32)
    before_q = np.asarray(api.query_many(spec, st_mt, jnp.asarray(pk)))
    before_topk = api.tenant_topk(spec, st_mt, 1, 6)

    d = tn.spill_rows(st_mt.bank, 1, S, BITS)
    cleared = tn.clear_rows(st_mt.bank, tn.tenant_rows(1, S))
    # cleared rows answer zero and keep their capacity mask
    gone = np.asarray(api.query_many(
        spec, tn.TenantBank(bank=cleared), jnp.asarray(pk)))
    assert (gone == 0).all()
    np.testing.assert_array_equal(
        np.asarray(cleared.ids == -2).sum(axis=1),
        np.asarray(st_mt.bank.ids == -2).sum(axis=1))

    # npz round-trip: the spill format is a flat numpy dict
    buf = io.BytesIO()
    np.savez(buf, **d)
    buf.seek(0)
    d2 = dict(np.load(buf))

    admitted = tn.TenantBank(bank=tn.admit_spill(cleared, d2))
    after_q = np.asarray(api.query_many(spec, admitted, jnp.asarray(pk)))
    np.testing.assert_array_equal(before_q, after_q)
    # re-admission is content-exact but may reorder equal-count slots
    # (merge packs by count), which flips top-k tie-breaks: compare as
    # (count, item) multisets
    after_topk = api.tenant_topk(spec, admitted, 1, 6)
    pairs = lambda tk: sorted(zip(np.asarray(tk[1]).tolist(),
                                  np.asarray(tk[0]).tolist()))
    assert pairs(before_topk) == pairs(after_topk)
    # other tenants untouched, bit for bit
    for t in (0, 2, 3):
        rows = tn.tenant_rows(t, S)
        np.testing.assert_array_equal(np.asarray(st_mt.bank.ids[rows]),
                                      np.asarray(admitted.bank.ids[rows]))


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("donate", [False, True])
def test_batched_spill_admit_equals_one_tenant_at_a_time(
        shards, donate, monkeypatch):
    """spill_tenants / admit_tenants over more tenants than one launch
    holds give, bit for bit, each tenant's spill_rows dict, the bank
    clear_rows leaves and the bank admit_spill one tenant at a time
    builds; a donated bank is consumed."""
    from repro import platform

    monkeypatch.setattr(platform, "donate_state_buffers", lambda: True)
    T = tn.SPILL_BATCH + 5
    spec, st_mt = _built_bank(T=T, S=shards, n=60)
    S = spec.shards or 1
    tenants = [int(t) for t in np.random.default_rng(1).permutation(T)[2:]]
    rows = np.concatenate([tn.tenant_rows(t, S) for t in tenants])
    want = [tn.spill_rows(st_mt.bank, t, S, BITS) for t in tenants]
    cleared = tn.clear_rows(st_mt.bank, rows)
    readmitted = cleared
    for d in want[::-1]:
        readmitted = tn.admit_spill(readmitted, d)

    bank, got = tn.spill_tenants(st_mt.bank, tenants, S, BITS,
                                 donate=donate)
    assert st_mt.bank.ids.is_deleted() == donate
    assert [int(d["tenant"]) for d in got] == tenants
    for d, e in zip(got, want):
        assert d.keys() == e.keys()
        for key in d:
            np.testing.assert_array_equal(d[key], e[key])
    for x, y in zip(bank, cleared):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    back = tn.admit_tenants(bank, got[::-1], donate=donate)
    for x, y in zip(back, readmitted):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_admit_spill_rejects_truncated_dict():
    spec, st_mt = _built_bank()
    d = tn.spill_rows(st_mt.bank, 0, 1, BITS)
    d.pop("counts")
    with pytest.raises(ValueError, match="missing"):
        tn.admit_spill(st_mt.bank, d)


# ---------------------------------------------------------------------------
# Quantile tenancy (composite-key dyadic bank)
# ---------------------------------------------------------------------------

def test_tenant_quantiles_against_numpy():
    T_BITS, I_BITS = 2, 8
    spec = api.SketchSpec(kind="quantile", eps=0.02, bits=T_BITS + I_BITS)
    st_q = api.make(spec)
    rng = np.random.default_rng(9)
    per_tenant = {}
    for t in range(1 << T_BITS):
        vals = rng.integers(0, 1 << I_BITS, 600)
        per_tenant[t] = np.sort(vals)
        keys = tn.pack_keys(np.full(len(vals), t, np.int64),
                            vals.astype(np.int64), I_BITS)
        st_q = api.update(spec, st_q, jnp.asarray(keys.astype(np.int32)),
                          jnp.ones(len(vals), jnp.int32))
    qs = jnp.asarray([0.25, 0.5, 0.75], jnp.float32)
    for t in range(1 << T_BITS):
        mass = int(np.asarray(tn.tenant_mass(st_q, t, I_BITS)))
        assert mass == len(per_tenant[t])
        got = np.asarray(tn.tenant_quantile_many(st_q, t, qs, I_BITS))
        for q, g in zip((0.25, 0.5, 0.75), got):
            true_rank = q * mass
            got_rank = np.searchsorted(per_tenant[t], g, side="right")
            # dyadic rank error <= eps * TOTAL mass; per-tenant range
            # differences double the endpoint error
            slack = 2 * 0.02 * mass * (1 << T_BITS) + 1
            assert abs(got_rank - true_rank) <= slack


# ---------------------------------------------------------------------------
# Session plumbing: cache normalization + per-tenant window FIFOs
# ---------------------------------------------------------------------------

def test_ingest_cache_normalizes_tenant_layouts():
    # unique total k so other tests' cache entries can't mask a miss;
    # specs differing only in tenant metadata (tenant count, uniform k
    # vs explicit caps) normalize onto ONE compiled-ingest entry —
    # capacity masks live in state, not in the trace
    specs = [
        api.SketchSpec(kind="frequency", k=52, bits=BITS, tenants=2),
        api.SketchSpec(kind="frequency", k=52, bits=BITS, tenants=4),
        api.SketchSpec(kind="frequency", bits=BITS, tenants=4,
                       tenant_caps=(13, 13, 13, 13)),
    ]
    norm = {ses.ingest_cache_spec(s) for s in specs}
    assert len(norm) == 1
    before = ses.ingest_cache_stats()["entries"]
    sessions = [ses.StreamSession(s, block=64) for s in specs]
    assert ses.ingest_cache_stats()["entries"] - before <= 1
    for spec, s in zip(specs, sessions):
        keys = tn.pack_keys(np.full(5, spec.tenants - 1, np.int64),
                            np.arange(5, dtype=np.int64), BITS)
        s.ingest(keys, np.ones(5, np.int32))
        pk = jnp.asarray(keys.astype(np.int32))
        np.testing.assert_array_equal(
            np.asarray(s.query_many(pk)), np.ones(5))


def test_ingest_cache_spec_identity_for_plain_specs():
    spec = api.SketchSpec(kind="frequency", k=8, bits=BITS)
    assert ses.ingest_cache_spec(spec) is spec


def test_per_tenant_window_fifos_roundtrip():
    """The failing-before regression: checkpoints must keep each
    tenant's window FIFO separate — a resumed session that collapsed
    them onto one horizon diverges from the uninterrupted twin."""
    spec = api.SketchSpec(kind="frequency", k=64, bits=BITS, tenants=4)

    def feed(s, lo, hi):
        for i in range(lo, hi):
            t = i % 3
            keys = tn.pack_keys(np.full(6, t, np.int64),
                                np.arange(6, dtype=np.int64) + 10 * t, BITS)
            s.push(keys, np.ones(6, np.int32), tenant=t)

    twin = ses.StreamSession(spec, block=32, window=2)
    feed(twin, 0, 12)

    s1 = ses.StreamSession(spec, block=32, window=2)
    feed(s1, 0, 7)
    d = s1.save(include_schedule=True)
    assert "sched_batch_tenants" in d
    s2 = ses.StreamSession(spec, block=32, window=2)
    s2.load(d)
    feed(s2, 7, 12)

    probe = tn.pack_keys(
        np.repeat(np.arange(4), UNIVERSE).astype(np.int64),
        np.tile(np.arange(UNIVERSE), 4).astype(np.int64),
        BITS).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(twin.query_many(jnp.asarray(probe))),
        np.asarray(s2.query_many(jnp.asarray(probe))))
    assert (twin.insertions, twin.deletions) == \
        (s2.insertions, s2.deletions)


def test_legacy_schedule_dict_loads_onto_default_fifo():
    spec = api.SketchSpec(kind="frequency", k=32, bits=BITS, tenants=2)
    s = ses.StreamSession(spec, block=32, window=3)
    keys = tn.pack_keys(np.zeros(4, np.int64),
                        np.arange(4, dtype=np.int64), BITS)
    s.push(keys, np.ones(4, np.int32))  # default (None) schedule
    d = s.save(include_schedule=True)
    d.pop("sched_batch_tenants")  # pre-tenant checkpoint shape
    s2 = ses.StreamSession(spec, block=32, window=3)
    fifo_before = s2.batch_fifo
    s2.load(d)
    assert s2.batch_fifo is fifo_before  # stats trackers alias this deque
    assert len(s2.batch_fifo) == 1 and list(s2.batch_fifos) == [None]


def test_tenant_checkpoint_roundtrip_and_infer():
    spec, st_mt = _built_bank(S=2)
    d = api.save(spec, st_mt)
    inferred = api.infer_spec(
        api.SketchSpec(kind="frequency", k=24, bits=BITS), d)
    assert inferred.tenants == 4 and inferred.shards == 2
    st_r = api.restore(inferred, d)
    for a, b in zip(st_mt.bank, st_r.bank):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_recover_session_on_tenant_spec():
    from repro.sketch.elastic import recover_session

    spec = api.SketchSpec(kind="frequency", k=32, bits=BITS, tenants=4)
    s = ses.StreamSession(spec, block=32, replay=16)
    keys = tn.pack_keys(np.full(32, 2, np.int64),
                        np.arange(32, dtype=np.int64) % UNIVERSE, BITS)
    s.ingest(keys, np.ones(32, np.int32))
    saved = s.save(include_schedule=True)
    s.ingest(keys, np.ones(32, np.int32))
    want = np.asarray(api.query_many(
        spec, s.state, jnp.asarray(keys.astype(np.int32))))
    # crash: state lost, rebuild = checkpoint + replay
    s.state = api.make(spec)
    report = recover_session(s, saved)
    assert report.replayed_blocks == 1
    got = np.asarray(api.query_many(
        spec, s.state, jnp.asarray(keys.astype(np.int32))))
    np.testing.assert_array_equal(want, got)
