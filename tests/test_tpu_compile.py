"""Compile the main-path kernels and the CTR train step for a described
TPU v5e chip, at the paper's model-C widths.

Nothing runs: the TPU compiler is installed here and compiles for a chip
that is described, not attached. What Mosaic or XLA would refuse on the
chip (an unaligned block, an SMEM overflow, a program larger than HBM) is
refused here too, at no chip time. ``kops._on_tpu`` still sees the CPU, so
each test steers it to the TPU branch with ``monkeypatch``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.ctr_models import PAPER
from repro.kernels import ops as kops

C = PAPER["C"]
MB = 4_096  # examples per mini-batch: the paper's 4M / 1,000, to a power of two
K = 4  # mini-batches per 16,384-example HDFS batch
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch, one_chip):
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [60_000, 4_096])
def test_adagrad_update_compiles(on_tpu, rows):
    x = on_tpu((rows, C.emb_dim), jnp.float32)
    compiled = _compile(lambda p, a, g: kops.adagrad_update(p, a, g, 0.05), x, x, x)
    assert _has_kernel(compiled)


# (emb, examples, nnz, Pallas expected): CTR rows are 8 wide and take XLA;
# lane-aligned rows take the kernels while their ids fit in SMEM
ROW_CASES = [
    (C.emb_dim, MB, C.nnz_per_example, False),
    (128, 64, C.nnz_per_example, True),
    (128, MB, C.nnz_per_example, False),
]


@pytest.mark.parametrize("emb,B,nnz,pallas", ROW_CASES)
def test_embedding_bag_fwd_bwd_compiles(on_tpu, emb, B, nnz, pallas):
    n_working = B * nnz // 2
    args = (
        on_tpu((n_working, emb), jnp.float32),
        on_tpu((B, nnz), jnp.int32),
        on_tpu((B, nnz), jnp.int32),
        on_tpu((B, nnz), jnp.bool_),
    )

    def fwd_bwd(table, ids, slot_of, valid):
        pooled = lambda t: kops.embedding_bag(t, ids, slot_of, valid, C.n_slots)
        out, vjp = jax.vjp(pooled, table)
        return out, vjp(jnp.ones_like(out))[0]

    before = kops.COUNTERS["row_kernel_xla"]
    compiled = _compile(fwd_bwd, *args)
    assert _has_kernel(compiled) == pallas
    assert (kops.COUNTERS["row_kernel_xla"] > before) != pallas


@pytest.mark.parametrize("emb,B,nnz,pallas", ROW_CASES)
def test_scatter_add_compiles(on_tpu, emb, B, nnz, pallas):
    n = B * nnz
    args = (
        on_tpu((n // 2, emb), jnp.float32),
        on_tpu((n,), jnp.int32),
        on_tpu((n, emb), jnp.float32),
    )
    compiled = _compile(kops.scatter_add, *args)
    assert _has_kernel(compiled) == pallas


def test_feature_extract_compiles(on_tpu):
    plane = on_tpu((16_384, 512), jnp.uint32)
    fn = lambda lo, hi, v: kops.feature_extract(
        lo, hi, v, n_keys=C.n_sparse_keys, n_slots=C.n_slots
    )
    assert _has_kernel(_compile(fn, plane, plane, on_tpu((16_384, 512), jnp.bool_)))


def test_topk_mips_compiles(on_tpu):
    fn = lambda q, c: kops.topk_mips(q, c, 64)
    compiled = _compile(fn, on_tpu((128, 128), jnp.float32), on_tpu((65_536, 128), jnp.float32))
    assert _has_kernel(compiled)


def test_ctr_train_step_compiles_and_fits(on_tpu):
    """The trainer's whole jitted step (k mini-batches, embedding-bag
    fwd/bwd, tower Adam, fused row-Adagrad) at model C's widths and one
    16,384-example HDFS batch's working set."""
    from repro.models import ctr as ctr_model
    from repro.train.optim import AdamW
    from repro.train.train_step import make_ctr_train_step

    k, n_working = K, 2_300_000
    opt = AdamW(lr=1e-3)
    tower = jax.eval_shape(lambda: ctr_model.init_tower(C, jax.random.PRNGKey(0)))
    opt_state = jax.eval_shape(opt.init, tower)
    spec = lambda t: jax.tree.map(lambda x: on_tpu(x.shape, x.dtype), t)
    table = on_tpu((n_working, C.emb_dim), jnp.float32)
    mbs = {
        "slot_ids": on_tpu((k, MB, C.nnz_per_example), jnp.int32),
        "slot_of": on_tpu((k, MB, C.nnz_per_example), jnp.int32),
        "valid": on_tpu((k, MB, C.nnz_per_example), jnp.bool_),
        "labels": on_tpu((k, MB), jnp.float32),
    }
    step = make_ctr_train_step(C, 0.05, opt)
    compiled = _compile(step, spec(tower), spec(opt_state), table, table, mbs)
    assert _has_kernel(compiled)  # the fused Adagrad
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
    assert used < HBM_BYTES, f"train step needs {used} bytes of HBM"
