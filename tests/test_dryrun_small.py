"""Dry-run machinery on a small in-process mesh (8 host devices).

The full 512-device production dry-run runs via ``python -m
repro.launch.dryrun`` (results in EXPERIMENTS.md); this test proves the same
build path (sharding rules, abstract inputs, lower+compile, roofline parse)
works for every family on a mesh with both axes > 1.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import pytest
from jax.sharding import AxisType

from repro.configs import ShapeSpec, get_smoke_config
from repro.launch import dryrun as DR
from repro.launch import roofline as rl

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")

SHAPES = {
    "train": ShapeSpec("train_t", "train", 64, 8),
    "prefill": ShapeSpec("prefill_t", "prefill", 128, 4),
    "decode": ShapeSpec("decode_t", "decode", 128, 8),
}

FAMILIES = ["yi-9b", "olmoe-1b-7b", "hymba-1.5b", "xlstm-1.3b", "whisper-tiny", "pixtral-12b"]


@pytest.fixture(scope="module")
def mesh():
    # Auto axes: the sharding rules constrain through with_sharding_constraint
    return jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


@pytest.mark.parametrize("arch", FAMILIES)
@pytest.mark.parametrize("kind", list(SHAPES))
def test_cell_lowers_and_compiles(arch, kind, mesh):
    cfg = get_smoke_config(arch)
    shape = SHAPES[kind]
    fn, args, shards = DR.build_cell(cfg, shape, mesh)
    with mesh:
        compiled = jax.jit(fn, in_shardings=shards).lower(*args).compile()
    cost = rl.flat_cost(compiled)
    assert cost.get("flops", 0) > 0
    stats = rl.parse_collectives(compiled.as_text())
    assert stats.total_bytes > 0, "sharded program must communicate"
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes > 0


def test_roofline_terms_behave(mesh):
    cfg = get_smoke_config("yi-9b")
    fn, args, shards = DR.build_cell(cfg, SHAPES["train"], mesh)
    with mesh:
        compiled = jax.jit(fn, in_shardings=shards).lower(*args).compile()
    roof = rl.analyze("yi-9b", "train_t", "2x4", compiled, 1e12, 8)
    assert roof.t_compute > 0 and roof.t_memory > 0 and roof.t_collective > 0
    assert roof.bottleneck in ("compute", "memory", "collective")
    d = roof.to_dict()
    assert "roofline_fraction" in d and "useful_flops_ratio" in d
