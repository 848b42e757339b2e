"""Gradient compression: quantization error bounds, error feedback, and the
int8 wire format on the cluster's remote serving reads."""

import numpy as np

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.compression import (
    ErrorFeedbackCompressor,
    dequantize_int8,
    quantize_int8,
    sparse_decode,
    sparse_encode,
)


@given(
    st.integers(1, 32),
    st.integers(1, 64),
    st.floats(0.01, 100.0),
)
@settings(max_examples=30, deadline=None)
def test_quantization_error_bound(n, d, scale):
    rng = np.random.default_rng(42)
    x = (rng.standard_normal((n, d)) * scale).astype(np.float32)
    q, s = quantize_int8(x)
    err = np.abs(dequantize_int8(q, s) - x)
    per_row_bound = np.abs(x).max(axis=1, keepdims=True) / 127.0
    assert (err <= per_row_bound * 0.5 + 1e-6).all()


def test_sparse_packet_roundtrip_and_size():
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 2**40, 100).astype(np.uint64)
    vals = rng.standard_normal((100, 16)).astype(np.float32)
    pkt = sparse_encode(keys, vals, quantize=True)
    k2, v2 = sparse_decode(pkt)
    np.testing.assert_array_equal(k2, keys)
    assert np.abs(v2 - vals).max() < np.abs(vals).max() / 100
    raw = keys.nbytes + vals.nbytes
    assert pkt.nbytes < raw * 0.5  # ~3.2x compression incl. keys


def _quantize_clusters(tmp_path, dim=16, n_keys=400):
    """Two identical clusters, one with the int8 wire format enabled, both
    seeded with the same pushed rows."""
    from repro.core.node import Cluster, NetworkModel

    out = []
    for tag, wq in (("exact", False), ("quant", True)):
        cl = Cluster(2, str(tmp_path / tag), dim=dim, cache_capacity=512,
                     file_capacity=64, network=NetworkModel(wire_quantize=wq))
        keys = np.arange(n_keys, dtype=np.uint64)
        rows = (np.sin(np.arange(n_keys * dim)).reshape(n_keys, dim)).astype(np.float32)
        cl.push(keys, rows, unpin=False)
        out.append((cl, keys, rows))
    return out


def test_wire_quantize_applies_to_remote_serving_reads(tmp_path):
    (exact, keys, rows), (quant, _, _) = _quantize_clusters(tmp_path)
    got_exact = exact.pull(keys, requester=0, pin=False)
    got_quant = quant.pull(keys, requester=0, pin=False)
    np.testing.assert_array_equal(got_exact, rows)
    # remote segments crossed the wire in int8: close but not exact
    assert not np.array_equal(got_quant, rows)
    assert np.abs(got_quant - rows).max() <= np.abs(rows).max() / 127.0 + 1e-6
    # requester-local segments never touch the NIC and stay exact
    local = quant.owner_of(keys) == 0
    np.testing.assert_array_equal(got_quant[local], rows[local])
    assert quant.network.quantized_messages > 0
    assert quant.network.quantize_bytes_saved > 0
    # the Fig-4b accounting sees the smaller on-wire packets
    assert quant.network.bytes_moved < exact.network.bytes_moved


def test_wire_quantize_never_touches_training_pulls(tmp_path):
    (exact, keys, rows), (quant, _, _) = _quantize_clusters(tmp_path)
    got = quant.pull(keys, requester=0, pin=True)  # pinned = training pull
    np.testing.assert_array_equal(got, rows)
    assert quant.network.quantized_messages == 0
    quant.unpin(keys)
    # pushes stay exact too (they carry training state)
    quant.push(keys, rows + 1.0, unpin=False)
    np.testing.assert_array_equal(
        quant.pull(keys, requester=0, pin=True), rows + 1.0
    )
    quant.unpin(keys)


def test_error_feedback_unbiased_over_time():
    """Sum of applied (dequantized) updates converges to the sum of true
    gradients — the residual never grows."""
    rng = np.random.default_rng(1)
    comp = ErrorFeedbackCompressor((8, 32))
    total_true = np.zeros((8, 32), np.float32)
    total_applied = np.zeros((8, 32), np.float32)
    for _ in range(200):
        g = rng.standard_normal((8, 32)).astype(np.float32)
        q, s = comp.compress(g)
        total_true += g
        total_applied += dequantize_int8(q, s)
    # residual bounded => averages match closely
    assert np.abs(total_true - total_applied).max() < 1.0
    assert np.abs(comp.residual).max() < 0.5
