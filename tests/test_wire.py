"""The training wire (DESIGN.md §13): quantized gradient push with error
feedback, conflict-class delta/dedup encoding, and bytes-on-wire metering.

Contract under test:

* exact mode (default WireConfig) — nothing changes, bitwise;
* lossy mode — serial and pipelined runs stay bitwise-equal to each other
  (device reuse off), the final loss tracks the exact run within a pinned
  tolerance, and the error-feedback residual survives checkpoint/restore;
* dedup mode — bitwise lossless, strictly fewer bytes on the wire;
* metering — the NIC charges encoded bytes (pushes and quantized serving
  replies), and NIC_STALL faults fire on the bytes actually moved.
"""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.ctr_models import TINY
from repro.core.compression import (
    CLAMP_MAG,
    KeyedRowStore,
    PUSH_HEADER_BYTES,
    WireConfig,
    decode_push,
    encode_push,
    quantize_int8,
    quantize_rows_f16,
    dequantize_rows_f16,
    raw_push_row_bytes,
)
from repro.core.faults import NIC_STALL, NODE_KILL, FaultInjector, FaultSpec
from repro.core.node import Cluster, NetworkModel
from repro.data.synthetic_ctr import SyntheticCTRStream
from repro.train.trainer import CTRTrainer, TrainerConfig

# bounded-loss-delta harness tolerance: final-loss delta between the lossy
# and exact 20-batch TINY runs (observed ~3e-4; pinned with 30x headroom)
LOSS_DELTA_TOL = 1e-2


# ------------------------------------------------------- wire format units


@given(st.integers(1, 48), st.integers(1, 24), st.integers(0, 8), st.floats(1e-4, 1e3))
@settings(max_examples=25, deadline=None)
def test_push_roundtrip_decode_equals_applied(n, emb, opt, scale):
    """decode_push(packet, base) must reconstruct bitwise the rows the
    sender reports as applied — the wire cannot diverge from the cluster."""
    rng = np.random.default_rng(7)
    width = emb + opt
    base = (rng.standard_normal((n, width)) * scale).astype(np.float32)
    new = base + (rng.standard_normal((n, width)) * scale * 0.01).astype(np.float32)
    res = np.zeros((n, width), np.float32)
    pkt, applied, new_res, n_bad = encode_push(new, base, res, emb)
    assert n_bad == 0
    np.testing.assert_array_equal(decode_push(pkt, base), applied)
    # error feedback closes the loop: residual == what the wire dropped
    np.testing.assert_allclose(applied + new_res, new + res, rtol=0, atol=1e-5 * scale)
    # the packet really is smaller than the raw key+f32 wire
    assert pkt.nbytes < n * raw_push_row_bytes(width) or n * width < 8


def test_push_zero_rows():
    z = np.zeros((0, 4), np.float32)
    pkt, applied, res, n_bad = encode_push(z, z, z, 2)
    assert pkt.n_rows == 0 and applied.shape == (0, 4) and n_bad == 0
    assert pkt.nbytes == PUSH_HEADER_BYTES
    np.testing.assert_array_equal(decode_push(pkt, z), applied)


def test_push_single_element_rows():
    new = np.array([[3.0], [-1.5], [0.0]], np.float32)
    base = np.zeros((3, 1), np.float32)
    pkt, applied, res, _ = encode_push(new, base, np.zeros_like(base), 1)
    np.testing.assert_allclose(applied, new, atol=np.abs(new).max() / 127 + 1e-7)
    np.testing.assert_array_equal(decode_push(pkt, base), applied)
    assert applied[2, 0] == 0.0  # zero row stays exactly zero


def test_push_non_contiguous_inputs():
    rng = np.random.default_rng(3)
    big = rng.standard_normal((32, 17)).astype(np.float32)
    new, base = big[::2, 1:9], big[1::2, 1:9]  # strided views
    assert not new.flags["C_CONTIGUOUS"]
    res = np.zeros((16, 8), np.float32)
    pkt, applied, _, _ = encode_push(new, base, res, 4)
    np.testing.assert_array_equal(decode_push(pkt, np.ascontiguousarray(base)), applied)


def test_push_bf16_inputs_widen():
    jnp = pytest.importorskip("jax.numpy")
    rng = np.random.default_rng(5)
    new32 = rng.standard_normal((8, 6)).astype(np.float32)
    new_bf = np.asarray(jnp.asarray(new32, dtype=jnp.bfloat16))
    base = np.zeros((8, 6), np.float32)
    pkt, applied, _, _ = encode_push(new_bf, base, base.copy(), 3)
    assert applied.dtype == np.float32
    # bf16 keeps ~3 decimal digits; the int8 wire adds <1% on top
    np.testing.assert_allclose(applied, new32, atol=np.abs(new32).max() * 0.02)


def test_push_absolute_rows_when_no_base():
    rng = np.random.default_rng(11)
    new = rng.standard_normal((6, 4)).astype(np.float32)
    stale = rng.standard_normal((6, 4)).astype(np.float32)
    has_base = np.array([True, False, True, False, False, True])
    pkt, applied, _, _ = encode_push(
        new, stale, np.zeros_like(new), 2, has_base=has_base
    )
    np.testing.assert_array_equal(pkt.is_delta, has_base)
    # absolute rows ignore the (stale) base entirely
    np.testing.assert_allclose(applied[~has_base], new[~has_base], atol=0.05)
    np.testing.assert_array_equal(decode_push(pkt, stale), applied)


def test_f16_scale_underflow_and_overflow():
    tiny = np.full((2, 4), 1e-9, np.float32)  # absmax/127 underflows f16
    q, s = quantize_rows_f16(tiny)
    assert (s > 0).all() and np.isfinite(s.astype(np.float32)).all()
    huge = np.full((2, 4), 3e38, np.float32)  # absmax/127 overflows f16
    q2, s2 = quantize_rows_f16(huge)
    assert np.isfinite(s2.astype(np.float32)).all()
    assert np.abs(dequantize_rows_f16(q2, s2)).max() <= 127.0 * 65504.0


# --------------------------------------------------------- non-finite guard


def test_quantize_int8_raises_on_nonfinite():
    x = np.ones((4, 3), np.float32)
    x[2, 1] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        quantize_int8(x)
    x[2, 1] = np.inf
    with pytest.raises(ValueError, match="non-finite"):
        quantize_int8(x)


def test_quantize_int8_clamp_mode_stays_finite():
    x = np.ones((4, 3), np.float32)
    x[0, 0], x[1, 1], x[2, 2] = np.nan, np.inf, -np.inf
    q, s = quantize_int8(x, nonfinite="clamp")
    assert np.isfinite(s).all()
    out = q.astype(np.float32) * s
    assert np.isfinite(out).all()
    assert out[0, 0] == 0.0  # nan -> 0
    assert out[1, 1] == pytest.approx(CLAMP_MAG)
    assert out[2, 2] == pytest.approx(-CLAMP_MAG)
    # untouched finite rows are unaffected
    np.testing.assert_allclose(out[3], x[3], atol=1e-2)


def test_encode_push_counts_nonfinite_rows():
    new = np.ones((5, 4), np.float32)
    new[1, 2] = np.inf
    new[4, 0] = np.nan
    base = np.zeros_like(new)
    with pytest.raises(ValueError):
        encode_push(new, base, np.zeros_like(new), 2)
    pkt, applied, _, n_bad = encode_push(
        new, base, np.zeros_like(new), 2, nonfinite="clamp"
    )
    assert n_bad == 2
    assert np.isfinite(applied).all()


# ------------------------------------------------------------ KeyedRowStore


@given(st.integers(1, 200), st.integers(2, 16))
@settings(max_examples=15, deadline=None)
def test_keyed_row_store_roundtrip(n, width):
    rng = np.random.default_rng(n * width)
    keys = np.unique(rng.integers(1, 2**60, n).astype(np.uint64))
    rows = rng.standard_normal((len(keys), width)).astype(np.float32)
    store = KeyedRowStore(width, expected=4)  # force arena growth
    store.put(keys, rows, seq=0)
    got, found = store.get(keys)
    assert found.all()
    np.testing.assert_array_equal(got, rows)
    # state/load round trip
    clone = KeyedRowStore(width)
    clone.load(store.state())
    got2, found2 = clone.get(keys)
    assert found2.all()
    np.testing.assert_array_equal(got2, rows)


def test_keyed_row_store_window_eviction():
    store = KeyedRowStore(2, window=2)
    for seq in range(6):
        store.put(np.array([seq + 1], np.uint64), np.full((1, 2), seq, np.float32), seq=seq)
    # after seq 5 with window 2, only stamps 4 and 5 survive
    alive = store.contains(np.arange(1, 7).astype(np.uint64))
    assert alive.tolist() == [False, False, False, False, True, True]
    # upsert re-stamps an existing key, rescuing it from eviction
    store.put(np.array([5], np.uint64), np.zeros((1, 2), np.float32), seq=7)
    assert store.contains(np.array([5], np.uint64)).all()
    assert not store.contains(np.array([6], np.uint64)).any()


# ----------------------------------------------------- NIC metering (wire)


def test_quantized_serving_reply_meters_payload_only():
    """A quantized reply must not re-charge the keys the request already
    moved: encoded reply bytes = int8 payload + f32 scales, keys excluded."""
    net = NetworkModel(wire_quantize=True)
    keys = np.arange(100, dtype=np.uint64)
    vals = np.random.default_rng(0).standard_normal((100, 16)).astype(np.float32)
    net.reply(keys, vals, serving=True)
    expected = 100 * 16 + 100 * 4  # int8 payload + f32 scale, NO key bytes
    assert net.bytes_moved == expected
    assert net.quantize_bytes_saved == vals.nbytes - expected


def test_cluster_push_with_packet_meters_encoded_bytes(tmp_path):
    dim = 16
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 2**40, 256).astype(np.uint64)
    rows = rng.standard_normal((256, dim)).astype(np.float32)

    def push_bytes(packet):
        cl = Cluster(4, str(tmp_path / f"m{packet is not None}"), dim=dim,
                     cache_capacity=1024, file_capacity=128)
        cl.pull(keys, pin=True)
        cl.network.bytes_moved = 0
        cl.push(keys, rows, unpin=True, packet=packet)
        return cl.network.bytes_moved, cl.network

    raw_bytes, _ = push_bytes(None)
    pkt, applied, _, _ = encode_push(
        rows, np.zeros_like(rows), np.zeros_like(rows), 8
    )
    enc_bytes, net = push_bytes(pkt)
    assert enc_bytes < raw_bytes / 3, (enc_bytes, raw_bytes)
    assert net.push_enc_messages == 3  # one per remote owner segment
    assert net.push_bytes_saved == raw_bytes - enc_bytes
    # fresh() must zero the new counters too
    assert net.fresh().push_enc_messages == 0 and net.fresh().push_bytes_saved == 0


def test_nic_stall_fires_on_encoded_push(tmp_path):
    cl = Cluster(2, str(tmp_path / "stall"), dim=8, cache_capacity=512,
                 file_capacity=64)
    keys = np.arange(64, dtype=np.uint64)
    rows = np.ones((64, 8), np.float32)
    cl.pull(keys, pin=True)
    # armed after the pull: the stall's transfer counter only sees the push,
    # so the fault fires on the *encoded* packet transfer
    inj = FaultInjector([FaultSpec(NIC_STALL, at_op=1, stall_s=0.5)]).arm(cl)
    pkt, _, _, _ = encode_push(rows, np.zeros_like(rows), np.zeros_like(rows), 4)
    before = cl.network.virtual_time
    cl.push(keys, rows, unpin=True, packet=pkt)
    inj.disarm()
    assert inj.all_fired()
    assert cl.network.stalls == 1
    assert cl.network.stall_time == pytest.approx(0.5)
    # the stall's extra latency landed in virtual time on encoded transfers
    assert cl.network.virtual_time > before


# ------------------------------------------------- trainer-level contracts


def _cluster(tmp_path, tag):
    return Cluster(2, str(tmp_path / tag), dim=TINY.emb_dim * 2,
                   cache_capacity=2048, file_capacity=128, init_cols=TINY.emb_dim)


def _stream():
    return SyntheticCTRStream(TINY.n_sparse_keys, TINY.nnz_per_example,
                              TINY.n_slots, TINY.batch_size, seed=5)


def _run(tmp_path, tag, tcfg, n=8, pipelined=True):
    cl = _cluster(tmp_path, tag)
    tr = CTRTrainer(TINY, cl, tcfg)
    losses = [r["loss"] for r in tr.run(_stream(), n, pipelined=pipelined)]
    cl.flush_all()
    rows = cl.pull(np.arange(TINY.n_sparse_keys, dtype=np.uint64), pin=False)
    return {"losses": losses, "rows": rows, "trainer": tr, "cluster": cl}


def test_lossy_serial_equals_lossy_pipelined(tmp_path):
    """Quantization happens at deposit time, so version forwarding and the
    deferred push both carry the dequantized rows — the lossy pipeline is
    bitwise-equal to the lossy serial run (device reuse off: the device
    copy intentionally keeps pre-quantization rows)."""
    q = lambda: TrainerConfig(wire_quantize_train=True, device_reuse=False)
    serial = _run(tmp_path, "ls", q(), pipelined=False)
    pipe = _run(tmp_path, "lp", q(), pipelined=True)
    np.testing.assert_array_equal(serial["losses"], pipe["losses"])
    np.testing.assert_array_equal(serial["rows"], pipe["rows"])
    assert pipe["cluster"].total_pins() == 0


def test_bounded_loss_delta_and_push_ratio(tmp_path):
    """The lossy acceptance harness: final loss within the pinned tolerance
    of the exact run, >=3x training push bytes-on-wire reduction, NIC push
    savings recorded, and per-conflict-class pull counters populated."""
    exact = _run(tmp_path, "ex", TrainerConfig(), n=20)
    lossy = _run(tmp_path, "lq", TrainerConfig(wire_quantize_train=True), n=20)
    delta = abs(exact["losses"][-1] - lossy["losses"][-1])
    assert delta < LOSS_DELTA_TOL, delta
    wc = lossy["trainer"].client.wire_counters()
    assert wc["wire_push_rows"] > 0
    ratio = wc["wire_push_raw_bytes"] / wc["wire_push_enc_bytes"]
    assert ratio >= 3.0, ratio
    net = lossy["cluster"].network
    assert net.push_enc_messages > 0 and net.push_bytes_saved > 0
    # the zipf stream exercises every conflict class
    assert wc["wire_pull_fresh_rows"] > 0
    assert wc["wire_pull_device_rows"] > 0
    assert wc["wire_pull_forwarded_rows"] > 0
    # quantized training moved measurably fewer bytes than exact training
    assert net.bytes_moved < exact["cluster"].network.bytes_moved
    # exact mode never touches the push wire counters
    assert exact["trainer"].client.wire_counters()["wire_push_rows"] == 0


def test_dedup_window_is_bitwise_lossless(tmp_path):
    """Repeat-key pulls served from the pushed-row window are bitwise the
    cluster rows, so the whole run stays bitwise-equal to the exact run —
    while moving strictly fewer bytes."""
    base = _run(tmp_path, "db", TrainerConfig(), n=12)
    dd = _run(tmp_path, "dd", TrainerConfig(wire_dedup_window=4), n=12)
    np.testing.assert_array_equal(base["losses"], dd["losses"])
    np.testing.assert_array_equal(base["rows"], dd["rows"])
    st = dd["trainer"].ps.stats
    assert st.rows_dedup_served > 0
    wc = dd["trainer"].client.wire_counters()
    assert wc["wire_pull_dedup_rows"] == st.rows_dedup_served
    assert dd["cluster"].network.bytes_moved < base["cluster"].network.bytes_moved
    assert dd["cluster"].total_pins() == 0


def test_lossy_ride_through_matches_fault_free_lossy_run(tmp_path):
    """The ride-through path (drain + serial replay) must produce the same
    results AND the same bytes-on-wire semantics as the pipelined lossy
    path: a mid-run node kill leaves losses and rows bitwise-equal to the
    fault-free lossy run, with push compression still metered."""
    cfg = lambda **kw: TrainerConfig(
        wire_quantize_train=True, device_reuse=False, **kw
    )
    clean = _run(tmp_path, "rt_clean", cfg(), n=10)
    chaos_cl = _cluster(tmp_path, "rt_chaos")
    tr = CTRTrainer(TINY, chaos_cl, cfg(ride_through=True))
    inj = FaultInjector([FaultSpec(NODE_KILL, at_op=40, node_id=1)]).arm(chaos_cl)
    got = [r["loss"] for r in tr.run(_stream(), 10)]
    inj.disarm()
    assert inj.all_fired()
    assert chaos_cl.fault_counters["node_recoveries"] >= 1
    np.testing.assert_array_equal(got, clean["losses"])
    chaos_cl.flush_all()
    rows = chaos_cl.pull(np.arange(TINY.n_sparse_keys, dtype=np.uint64), pin=False)
    np.testing.assert_array_equal(rows, clean["rows"])
    wc = tr.client.wire_counters()
    assert wc["wire_push_enc_bytes"] > 0
    assert wc["wire_push_raw_bytes"] / wc["wire_push_enc_bytes"] >= 3.0
    assert chaos_cl.total_pins() == 0 and tr.ps.n_inflight() == 0


def test_error_feedback_survives_checkpoint_restore(tmp_path):
    """EF residuals are model state: a resume must carry them forward (the
    'wire_ef' checkpoint subtree), and the resumed trainer keeps training."""
    cl = _cluster(tmp_path, "ck")
    tcfg = TrainerConfig(
        wire_quantize_train=True,
        checkpoint_every=5,
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    tr = CTRTrainer(TINY, cl, tcfg)
    tr.run(_stream(), 10)
    saved = tr.client.wire_state()
    assert saved and TINY.groups[0].name in saved
    assert len(saved[TINY.groups[0].name]["keys"]) > 0

    cl2 = _cluster(tmp_path, "ck2")
    tcfg2 = TrainerConfig(
        wire_quantize_train=True,
        checkpoint_every=5,
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    tr2 = CTRTrainer(TINY, cl2, tcfg2)
    step = tr2.resume()
    assert step == 10
    restored = tr2.client.wire_state()
    name = TINY.groups[0].name
    # the restored residual store holds exactly the checkpointed rows
    sk = np.argsort(saved[name]["keys"])
    rk = np.argsort(restored[name]["keys"])
    np.testing.assert_array_equal(saved[name]["keys"][sk], restored[name]["keys"][rk])
    np.testing.assert_array_equal(saved[name]["rows"][sk], restored[name]["rows"][rk])
    # and the resumed trainer still trains
    res = tr2.run(_stream(), 4)
    assert len(res) == 4 and all(np.isfinite(r["loss"]) for r in res)


def test_exact_mode_engine_state_is_inert(tmp_path):
    """Default WireConfig must not allocate wire state or touch the push
    path — the exact-mode contract is 'compiled in, default off'."""
    cl = _cluster(tmp_path, "inert")
    tr = CTRTrainer(TINY, cl, TrainerConfig())
    assert tr.ps._ef is None and tr.ps._pushed is None
    assert not tr.ps.wire.enabled
    assert tr.client.wire_state() == {}
    tr.run(_stream(), 3)
    wc = tr.client.wire_counters()
    assert wc["wire_push_enc_bytes"] == 0 and wc["wire_push_rows"] == 0
    # pull-class accounting still works in exact mode (bench visibility)
    assert wc["wire_pull_fresh_rows"] > 0
