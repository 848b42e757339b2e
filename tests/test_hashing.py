"""OP+OSRP invariants."""

import numpy as np

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.hashing import OPOSRP

cols_strategy = st.lists(st.integers(0, 2**40), min_size=1, max_size=100, unique=True).map(
    lambda xs: np.asarray(xs, dtype=np.uint64)
)


@given(cols_strategy, st.sampled_from([16, 64, 256]))
def test_output_range_and_determinism(cols, k):
    h = OPOSRP(k, seed=3)
    out1, out2 = h.transform_row(cols), h.transform_row(cols)
    np.testing.assert_array_equal(out1, out2)
    assert (out1 >= 0).all() and (out1 < 2 * k).all()
    # one output feature per nonzero bin at most
    assert len(np.unique(out1 // 2)) == len(out1)


@given(cols_strategy)
def test_input_order_invariance(cols):
    h = OPOSRP(32, seed=1)
    a = h.transform_row(cols)
    b = h.transform_row(np.random.default_rng(0).permutation(cols))
    np.testing.assert_array_equal(np.sort(a), np.sort(b))


def test_padded_matches_rowwise():
    h = OPOSRP(64, seed=9)
    rng = np.random.default_rng(1)
    cols = rng.integers(0, 2**40, size=(20, 30)).astype(np.uint64)
    valid = rng.random((20, 30)) < 0.8
    oc, ov = h.transform_padded(cols, valid)
    for i in range(20):
        row = h.transform_row(cols[i][valid[i]]) if valid[i].any() else np.zeros(0, np.int64)
        assert set(oc[i][ov[i]].tolist()) == set(row.tolist())


def test_collision_compression():
    # hashing into few bins must produce <= 2k distinct features
    h = OPOSRP(8, seed=0)
    cols = np.arange(10_000, dtype=np.uint64)
    out = h.transform_row(cols)
    assert len(out) <= 16
