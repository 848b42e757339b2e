"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.embedding_lookup import embedding_lookup_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_adagrad import adagrad_pallas
from repro.kernels.scatter_add import scatter_add_pallas


# ---------------------------------------------------------------- lookup
@pytest.mark.parametrize("N,D,B", [(16, 128, 8), (64, 256, 32), (128, 512, 7), (32, 2048, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_lookup_sweep(N, D, B, dtype):
    key = jax.random.PRNGKey(N + D + B)
    table = jax.random.normal(key, (N, D), dtype)
    ids = jax.random.randint(key, (B,), 0, N)
    out = embedding_lookup_pallas(table, ids, interpret=True)
    np.testing.assert_array_equal(out, ref.embedding_lookup_ref(table, ids))


# ---------------------------------------------------------------- scatter
@pytest.mark.parametrize("N,D,B", [(16, 128, 8), (64, 256, 64), (8, 512, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scatter_add_with_duplicates(N, D, B, dtype):
    key = jax.random.PRNGKey(N * D + B)
    table = jax.random.normal(key, (N, D), jnp.float32).astype(dtype)
    ids = jax.random.randint(key, (B,), 0, N)  # heavy duplication when B > N
    grads = jax.random.normal(jax.random.fold_in(key, 1), (B, D), jnp.float32).astype(dtype)
    out = ops.scatter_add(table, ids, grads, use_pallas=True, interpret=True)
    expect = ref.scatter_add_ref(table, ids, grads)
    atol = 1e-5 if dtype == jnp.float32 else 0.1
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=atol, rtol=atol
    )


@given(st.integers(1, 40), st.integers(2, 30))
@settings(max_examples=20, deadline=None)
def test_scatter_add_property(B, N):
    key = jax.random.PRNGKey(B * 31 + N)
    D = 128
    table = jnp.zeros((N, D), jnp.float32)
    ids = jax.random.randint(key, (B,), 0, N)
    grads = jnp.ones((B, D), jnp.float32)
    out = ops.scatter_add(table, ids, grads, use_pallas=True, interpret=True)
    counts = np.bincount(np.asarray(ids), minlength=N).astype(np.float32)
    np.testing.assert_allclose(np.asarray(out[:, 0]), counts)


# ---------------------------------------------------------------- embedding bag
def _bag_inputs(B, nnz, n_slots, emb, dtype=jnp.float32, seed=None):
    key = jax.random.PRNGKey(B * 7 + nnz * 3 + n_slots + emb if seed is None else seed)
    N = max(8, 2 * B)
    table = jax.random.normal(key, (N, emb), jnp.float32).astype(dtype)
    ids = jax.random.randint(jax.random.fold_in(key, 1), (B, nnz), 0, N)
    slot_of = jax.random.randint(jax.random.fold_in(key, 2), (B, nnz), 0, n_slots)
    valid = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.8, (B, nnz))
    return table, ids, slot_of, valid


BAG_SHAPES = [
    # B, nnz, n_slots, emb
    (4, 12, 6, 8),
    (8, 1, 1, 16),
    (16, 32, 8, 4),
    (2, 64, 16, 128),
    (8, 16, 32, 256),  # emb > block tile: exercises d-tiling
    (4, 8, 4, 96),  # emb not a divisor of the default tile: gcd tiling
]


@pytest.mark.parametrize("B,nnz,n_slots,emb", BAG_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_pallas_sweep(B, nnz, n_slots, emb, dtype):
    table, ids, slot_of, valid = _bag_inputs(B, nnz, n_slots, emb, dtype)
    out = embedding_bag_pallas(
        table, ids, slot_of, valid, n_slots=n_slots, block_d=128, interpret=True
    )
    expect = ref.embedding_bag_ref(table, ids, slot_of, valid, n_slots)
    atol = 1e-5 if dtype == jnp.float32 else 0.1
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=atol, rtol=atol
    )


@pytest.mark.parametrize("B,nnz,n_slots,emb", BAG_SHAPES)
def test_embedding_bag_portable_sweep(B, nnz, n_slots, emb):
    """The segment-sum fallback (the production path off-TPU) vs the oracle."""
    table, ids, slot_of, valid = _bag_inputs(B, nnz, n_slots, emb)
    out = ops.embedding_bag(table, ids, slot_of, valid, n_slots, use_pallas=False)
    expect = ref.embedding_bag_ref(table, ids, slot_of, valid, n_slots)
    np.testing.assert_allclose(out, expect, atol=1e-5, rtol=1e-5)


@given(st.integers(1, 16), st.integers(1, 24), st.integers(1, 12))
@settings(max_examples=15, deadline=None)
def test_embedding_bag_property(B, nnz, n_slots):
    """All-valid ones-table: pooled[b, s] counts the nonzeros in slot s."""
    table = jnp.ones((32, 8), jnp.float32)
    key = jax.random.PRNGKey(B * 131 + nnz * 17 + n_slots)
    ids = jax.random.randint(key, (B, nnz), 0, 32)
    slot_of = jax.random.randint(jax.random.fold_in(key, 1), (B, nnz), 0, n_slots)
    valid = jnp.ones((B, nnz), bool)
    out = np.asarray(ops.embedding_bag(table, ids, slot_of, valid, n_slots, use_pallas=False))
    for b in range(B):
        counts = np.bincount(np.asarray(slot_of[b]), minlength=n_slots)
        np.testing.assert_allclose(out[b, :, 0], counts)


def test_embedding_bag_float_mask_consistent_across_paths():
    """valid is a MASK (!= 0), not weights: a float mask must pool the same
    on the Pallas and portable paths."""
    table, ids, slot_of, _ = _bag_inputs(4, 8, 4, 8, seed=11)
    fmask = jnp.array(np.random.default_rng(0).choice([0.0, 0.5, 1.0], (4, 8)))
    a = ops.embedding_bag(table, ids, slot_of, fmask, 4, use_pallas=False)
    b = ops.embedding_bag(table, ids, slot_of, fmask, 4, use_pallas=True, interpret=True)
    np.testing.assert_allclose(a, b, atol=1e-6)
    expect = ref.embedding_bag_ref(table, ids, slot_of, fmask != 0, 4)
    np.testing.assert_allclose(a, expect, atol=1e-6)


def test_embedding_bag_grad_bitwise_vs_ref_autodiff():
    """The custom VJP (take_along_axis + scatter_add) must equal autodiff
    through the dense one-hot/einsum chain BITWISE for f32."""
    table, ids, slot_of, valid = _bag_inputs(8, 24, 6, 16, seed=42)
    g1 = jax.grad(
        lambda t: (ops.embedding_bag(t, ids, slot_of, valid, 6, use_pallas=False).sum()) ** 2
    )(table)
    g2 = jax.grad(
        lambda t: (ref.embedding_bag_ref(t, ids, slot_of, valid, 6).sum()) ** 2
    )(table)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))


def test_embedding_bag_grad_pallas_path():
    """grad through the Pallas forward + sorted-scatter backward vs ref."""
    table, ids, slot_of, valid = _bag_inputs(4, 12, 4, 8, seed=3)
    g1 = jax.grad(
        lambda t: ops.embedding_bag(
            t, ids, slot_of, valid, 4, use_pallas=True, interpret=True
        ).sum()
    )(table)
    g2 = jax.grad(lambda t: ref.embedding_bag_ref(t, ids, slot_of, valid, 4).sum())(table)
    np.testing.assert_allclose(g1, g2, atol=1e-5, rtol=1e-5)


def test_grouped_forward_matches_seed_math():
    """forward_grouped through the fused op == the seed one-hot/einsum math,
    loss included (the hetero multi-table device step is unchanged)."""
    from repro.configs.ctr_models import TINY_HETERO
    from repro.models import ctr as ctr_model

    cfg = TINY_HETERO
    key = jax.random.PRNGKey(0)
    tower = ctr_model.init_tower(cfg, key)
    B = 32
    tables, inputs = {}, {}
    for gi, g in enumerate(cfg.groups):
        k = jax.random.fold_in(key, gi + 1)
        n_working = 64
        tables[g.name] = jax.random.normal(k, (n_working, g.emb_dim))
        inputs[g.name] = {
            "slot_ids": jax.random.randint(jax.random.fold_in(k, 1), (B, 8), 0, n_working),
            "slot_of": jax.random.randint(jax.random.fold_in(k, 2), (B, 8), 0, g.n_slots),
            "valid": jax.random.bernoulli(jax.random.fold_in(k, 3), 0.9, (B, 8)),
        }
    labels = jnp.asarray(np.random.default_rng(0).integers(0, 2, B), jnp.float32)

    logits = ctr_model.forward_grouped(cfg, tower, tables, inputs)

    seed_tower = lambda h: ctr_model._tower_mlp(tower, h)
    pooled = [
        ref.embedding_bag_ref(
            tables[g.name], inputs[g.name]["slot_ids"], inputs[g.name]["slot_of"],
            inputs[g.name]["valid"], g.n_slots,
        ).reshape(B, -1)
        for g in cfg.groups
    ]
    seed_logits = seed_tower(jnp.concatenate(pooled, axis=-1))
    np.testing.assert_allclose(logits, seed_logits, atol=1e-6, rtol=1e-6)
    loss = ctr_model.loss_fn_grouped(cfg, tower, tables, inputs, labels)
    seed_bce = ctr_model._bce_with_logits(seed_logits, labels)
    np.testing.assert_allclose(loss, seed_bce, atol=1e-6, rtol=1e-6)


# ---------------------------------------------------------------- adagrad
@pytest.mark.parametrize("B,D", [(8, 128), (256, 512), (16, 1024)])
def test_fused_adagrad(B, D):
    key = jax.random.PRNGKey(B + D)
    p = jax.random.normal(key, (B, D))
    a = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (B, D)))
    g = jax.random.normal(jax.random.fold_in(key, 2), (B, D))
    p1, a1 = adagrad_pallas(p, a, g, 0.1, interpret=True)
    p2, a2 = ref.adagrad_ref(p, a, g, 0.1)
    np.testing.assert_allclose(p1, p2, atol=1e-6)
    np.testing.assert_allclose(a1, a2, atol=1e-6)


@pytest.mark.parametrize("B,D", [(13, 40), (1, 1), (7, 129), (8, 128), (60_000, 8), (264, 128), (5, 300)])
def test_adagrad_update_pads_to_pallas_path(B, D, monkeypatch):
    """Non-(8,128)-tiling working sets must take the Pallas kernel (padded),
    not silently fall back to the reference path."""
    calls = []
    real = ops.adagrad_pallas
    monkeypatch.setattr(
        ops, "adagrad_pallas", lambda *a, **k: calls.append(a[0].shape) or real(*a, **k)
    )
    key = jax.random.PRNGKey(B * 101 + D)
    p = jax.random.normal(key, (B, D))
    a = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (B, D)))
    g = jax.random.normal(jax.random.fold_in(key, 2), (B, D))
    p1, a1 = ops.adagrad_update(p, a, g, 0.1, use_pallas=True, interpret=True)
    assert len(calls) == 1, "Pallas kernel must be invoked"
    pb, pd = calls[0]
    assert pb % 8 == 0 and pd % 128 == 0, f"padded shape {calls[0]} must tile"
    assert (p1.shape, a1.shape) == ((B, D), (B, D))
    p2, a2 = ref.adagrad_ref(p, a, g, 0.1)
    np.testing.assert_allclose(p1, p2, atol=1e-6)
    np.testing.assert_allclose(a1, a2, atol=1e-6)


def test_scatter_add_assume_sorted_fast_path():
    """Pre-sorted ids skip the wrapper argsort but accumulate identically."""
    key = jax.random.PRNGKey(17)
    N, D, B = 24, 128, 64
    table = jax.random.normal(key, (N, D))
    ids = jnp.sort(jax.random.randint(key, (B,), 0, N))  # heavy duplication
    grads = jax.random.normal(jax.random.fold_in(key, 1), (B, D))
    out = ops.scatter_add(table, ids, grads, assume_sorted=True, use_pallas=True, interpret=True)
    np.testing.assert_allclose(out, ref.scatter_add_ref(table, ids, grads), atol=1e-5, rtol=1e-5)


def test_working_table_accumulate_sorted(monkeypatch):
    """WorkingTable.accumulate(assume_sorted=True) forwards the flag so the
    kernel path never re-sorts sorted-unique MEM-PS working sets."""
    from repro.core.hbm_ps import WorkingTable

    seen = {}
    real = ops.scatter_add
    monkeypatch.setattr(
        "repro.core.hbm_ps.kops.scatter_add",
        lambda *a, **k: seen.update(k) or real(*a, **k),
    )
    table = jnp.zeros((8, 8), jnp.float32)
    slots = jnp.array([1, 3, 3, 7], jnp.int32)
    out = WorkingTable.accumulate(table, slots, jnp.ones((4, 8)), assume_sorted=True)
    assert seen.get("assume_sorted") is True
    exp = np.zeros((8, 8), np.float32)
    np.add.at(exp, np.asarray(slots), 1.0)
    np.testing.assert_allclose(out, exp)


# ---------------------------------------------------------------- attention
CASES = [
    # B, H, Hkv, Sq, Skv, Dh, causal, window, q_offset
    (2, 4, 2, 128, 128, 32, True, 0, 0),
    (1, 4, 1, 256, 256, 16, True, 0, 0),
    (1, 2, 2, 128, 256, 32, False, 0, 0),
    (2, 4, 2, 128, 256, 64, True, 64, 128),
    (1, 1, 1, 1, 128, 32, True, 0, 127),
    (1, 8, 4, 128, 128, 128, True, 32, 0),
]


@pytest.mark.parametrize("case", CASES)
def test_flash_attention_vs_ref(case):
    B, H, Hkv, Sq, Skv, Dh, causal, window, qoff = case
    ks = jax.random.split(jax.random.PRNGKey(sum(case[:6])), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, Dh))
    k = jax.random.normal(ks[1], (B, Hkv, Skv, Dh))
    v = jax.random.normal(ks[2], (B, Hkv, Skv, Dh))
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_offset=qoff, interpret=True
    )
    expect = ref.attention_ref(q, k, v, causal=causal, window=window, q_offset=qoff)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", CASES)
def test_blockwise_attention_vs_ref(case):
    B, H, Hkv, Sq, Skv, Dh, causal, window, qoff = case
    ks = jax.random.split(jax.random.PRNGKey(1 + sum(case[:6])), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, Dh))
    k = jax.random.normal(ks[1], (B, Hkv, Skv, Dh))
    v = jax.random.normal(ks[2], (B, Hkv, Skv, Dh))
    out = ops.attention_blockwise(q, k, v, causal=causal, window=window, q_offset=qoff, block_k=64)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window, q_offset=qoff)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


def test_blockwise_gradients_match_ref():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32))
    k = jax.random.normal(ks[1], (1, 1, 64, 32))
    v = jax.random.normal(ks[2], (1, 1, 64, 32))
    g1 = jax.grad(lambda *a: ops.attention_blockwise(*a, causal=True, block_k=16).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: ref.attention_ref(*a, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


def test_flash_custom_vjp_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32))
    k = jax.random.normal(ks[1], (1, 1, 128, 32))
    v = jax.random.normal(ks[2], (1, 1, 128, 32))
    g1 = jax.grad(lambda *a: ops.attention(*a, causal=True, impl="flash").sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: ref.attention_ref(*a, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


def test_kv_len_masking_matches_truncation():
    """kv_len masking == physically truncating the cache."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (2, 2, 1, 32))
    k = jax.random.normal(ks[1], (2, 2, 64, 32))
    v = jax.random.normal(ks[2], (2, 2, 64, 32))
    out = ref.attention_ref(q, k, v, causal=False, kv_len=40)
    exp = ref.attention_ref(q, k[:, :, :40], v[:, :, :40], causal=False)
    np.testing.assert_allclose(out, exp, atol=1e-6)
    out_b = ops.attention_blockwise(q, k, v, causal=False, kv_len=40, block_k=16)
    np.testing.assert_allclose(out_b, exp, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- gmm (MoE)
@pytest.mark.parametrize(
    "E,K,N,sizes",
    [
        (4, 128, 128, [100, 0, 300, 56]),
        (3, 256, 128, [128, 128, 128]),
        (5, 128, 256, [7, 250, 1, 0, 130]),
    ],
)
def test_gmm_vs_ref(E, K, N, sizes):
    key = jax.random.PRNGKey(E * K + N)
    T = sum(sizes)
    x = jax.random.normal(key, (T, K))
    w = jax.random.normal(jax.random.fold_in(key, 1), (E, K, N)) * 0.1
    gs = jnp.array(sizes, jnp.int32)
    out = ops.gmm(x, w, gs, use_pallas=True, interpret=True)
    np.testing.assert_allclose(out, ref.gmm_ref(x, w, gs), atol=2e-4, rtol=2e-4)


@given(st.lists(st.integers(0, 60), min_size=2, max_size=6))
@settings(max_examples=10, deadline=None)
def test_gmm_property_group_isolation(sizes):
    """Zeroing one expert's weights zeroes exactly that group's rows."""
    E = len(sizes)
    T = sum(sizes)
    if T == 0:
        return
    key = jax.random.PRNGKey(sum(sizes))
    x = jax.random.normal(key, (T, 128))
    w = jax.random.normal(jax.random.fold_in(key, 1), (E, 128, 128))
    w = w.at[0].set(0.0)
    gs = jnp.array(sizes, jnp.int32)
    out = np.asarray(ops.gmm(x, w, gs, use_pallas=True, interpret=True))
    assert np.allclose(out[: sizes[0]], 0.0)
    if T > sizes[0]:
        assert not np.allclose(out[sizes[0] :], 0.0) or sizes[0] == T
