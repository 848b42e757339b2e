"""SSD-PS: log-structured semantics, compaction bound, manifests."""

import numpy as np
import pytest

from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.core.keys import deterministic_init
from repro.core.ssd_ps import SSDParameterServer


def test_roundtrip(tmp_path):
    ssd = SSDParameterServer(str(tmp_path), dim=4, file_capacity=16)
    keys = np.arange(100, dtype=np.uint64)
    vals = np.random.default_rng(0).random((100, 4)).astype(np.float32)
    ssd.write_batch(keys, vals)
    np.testing.assert_allclose(ssd.read_batch(keys[::7]), vals[::7])


def test_overwrite_latest_wins(tmp_path):
    ssd = SSDParameterServer(str(tmp_path), dim=2, file_capacity=8)
    keys = np.arange(32, dtype=np.uint64)
    for i in range(5):
        ssd.write_batch(keys, np.full((32, 2), float(i), np.float32))
    np.testing.assert_allclose(ssd.read_batch(keys), np.full((32, 2), 4.0))


def test_space_bound_after_churn(tmp_path):
    """Paper: >50%-stale compaction bounds disk at <=2x live rows."""
    ssd = SSDParameterServer(str(tmp_path), dim=4, file_capacity=32)
    keys = np.arange(256, dtype=np.uint64)
    rng = np.random.default_rng(0)
    for _ in range(30):
        sub = rng.choice(keys, size=64, replace=False).astype(np.uint64)
        ssd.write_batch(sub, rng.random((64, 4)).astype(np.float32))
    assert ssd.space_amplification() <= 2.5  # 2x + one in-flight batch
    assert ssd.n_live_rows == 256


def test_missing_key_deterministic_init(tmp_path):
    ssd = SSDParameterServer(str(tmp_path), dim=6, file_capacity=8, init_cols=3)
    got = ssd.read_batch(np.array([42, 43], dtype=np.uint64))
    exp = deterministic_init(np.array([42, 43], dtype=np.uint64), 3, 0.01)
    np.testing.assert_allclose(got[:, :3], exp)
    assert (got[:, 3:] == 0).all()  # optimizer slots start at zero


def test_manifest_restore(tmp_path):
    ssd = SSDParameterServer(str(tmp_path), dim=3, file_capacity=8)
    keys = np.arange(50, dtype=np.uint64)
    vals = np.random.default_rng(1).random((50, 3)).astype(np.float32)
    ssd.write_batch(keys, vals)
    ssd.write_batch(keys[:20], vals[:20] * 2)
    m = ssd.manifest()
    ssd2 = SSDParameterServer.from_manifest(str(tmp_path), m)
    got = ssd2.read_batch(keys)
    np.testing.assert_allclose(got[:20], vals[:20] * 2)
    np.testing.assert_allclose(got[20:], vals[20:])


def test_read_amplification_counted(tmp_path):
    ssd = SSDParameterServer(str(tmp_path), dim=2, file_capacity=16)
    keys = np.arange(64, dtype=np.uint64)
    ssd.write_batch(keys, np.zeros((64, 2), np.float32))
    ssd.read_batch(keys[:1])  # reads a whole 16-row file for 1 key
    assert ssd.stats.read_amplification >= 8


@settings(max_examples=20, deadline=None, suppress_health_check=[HealthCheck.function_scoped_fixture])
@given(
    ops=st.lists(
        st.tuples(st.integers(0, 63), st.floats(-10, 10, allow_nan=False)),
        min_size=1,
        max_size=100,
    )
)
def test_matches_dict_model(tmp_path, ops):
    """Arbitrary interleaved writes/reads == a plain dict (property test)."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        ssd = SSDParameterServer(d, dim=1, file_capacity=4)
        model: dict[int, float] = {}
        for i, (key, val) in enumerate(ops):
            if i % 3 == 2 and model:  # read check
                ks = np.asarray(sorted(model), dtype=np.uint64)
                got = ssd.read_batch(ks)[:, 0]
                exp = np.asarray([model[int(k)] for k in ks], np.float32)
                np.testing.assert_allclose(got, exp, rtol=1e-6)
            ssd.write_batch(
                np.asarray([key], np.uint64), np.asarray([[val]], np.float32)
            )
            model[key] = np.float32(val)
        ks = np.asarray(sorted(model), dtype=np.uint64)
        np.testing.assert_allclose(
            ssd.read_batch(ks)[:, 0],
            np.asarray([model[int(k)] for k in ks], np.float32),
            rtol=1e-6,
        )
