"""The plain frequency reference (``chipbench.references.frequency``)
states the semantics the program serves: on random blocks that fill
rows, evict, and delete monitored and unmonitored items, every row the
engine ingests equals the reference's, and so does a re-admitted row."""
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references.frequency import Row, exact_counts


@pytest.mark.parametrize("k", [16, 128])
def test_rows_match_the_engine(k):
    from repro.sketch import api

    T, bits, B = 8, 10, 256
    spec = api.SketchSpec(kind="frequency", k=T * k, bits=bits, tenants=T)
    state = api.make(spec)
    rows = [Row(k) for _ in range(T)]
    rng = np.random.default_rng(k)
    for _ in range(12):
        ten = rng.integers(0, T, B)
        items = rng.zipf(1.3, B) % (1 << bits)
        w = rng.choice([-2, -1, 1, 1, 1, 2, 5, 0], B)
        keys = (ten << bits) | items
        state = api.update(spec, state, jnp.asarray(keys, jnp.int32),
                           jnp.asarray(w, jnp.int32))
        for t in range(T):
            m = ten == t
            rows[t].update(keys[m], w[m])
    for t in range(T):
        for f in ("ids", "counts", "errors"):
            got = np.asarray(getattr(state.bank, f))[t].astype(np.int64)
            assert np.array_equal(got, getattr(rows[t], f)), (t, f)


def test_readmit_matches_the_program():
    from repro.sketch import tenant as tn
    from repro.sketch.state import SketchState

    k = 32
    row = Row(k)
    rng = np.random.default_rng(0)
    row.update(rng.integers(0, 100, 200), rng.choice([1, 1, 2, -1], 200))
    one = SketchState(*(jnp.asarray(getattr(row, f)[None], jnp.int32)
                        for f in ("ids", "counts", "errors")))
    rows = jnp.zeros((1,), jnp.int32)
    back = tn.admit_rows(tn.clear_rows(one, rows), rows, one)
    row.readmit()
    for f in ("ids", "counts", "errors"):
        assert np.array_equal(np.asarray(getattr(back, f))[0], getattr(row, f))


def test_queries_topk_and_exact_counts():
    row = Row(6)
    row.update(np.array([5, 5, 7, 9, 3, 0]), np.array([1, 1, 2, 1, 0, 4]))
    assert list(row.query(np.array([5, 7, 3, 11]))) == [2, 2, 0, 0]
    ids, vals = row.topk(6)
    assert list(ids) == [0, 5, 7, 9, -1, -1]
    assert list(vals) == [4, 2, 2, 1, -2**31, -2**31]
    uids, f, ins, dels = exact_counts(np.array([1, 1, 2, 1]),
                                      np.array([1, 1, 1, -1]))
    assert list(uids) == [1, 2] and list(f) == [1, 1] and (ins, dels) == (3, 1)
