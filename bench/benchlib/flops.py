"""Operations and bytes the algorithms need, counted from their shapes.

These are what a roofline or a share of peak divides by the time measured;
they count the work the algorithm requires, not what an implementation
happens to do (no padding, no recomputation)."""

from __future__ import annotations


def tower_dims(cfg: dict) -> list[int]:
    """Layer widths of the fully-connected tower, input to logit."""
    return [int(cfg["n_slots"]) * int(cfg["emb_dim"]), *map(int, cfg["mlp_hidden"]), 1]


def tower_train_flops_per_example(cfg: dict) -> int:
    """Forward plus backward matmul FLOPs of the tower for one example:
    2ab forward, 2ab for the input gradient, 2ab for the weight gradient
    of each a-by-b layer (the input gradient of the first layer feeds the
    embedding rows, so it is needed too)."""
    d = tower_dims(cfg)
    return 6 * sum(a * b for a, b in zip(d[:-1], d[1:]))


def pooling_flops(valid_ids: int, emb_dim: int) -> int:
    """Sum-pooling adds forward plus the same adds scattering its gradient
    back to the rows."""
    return 2 * int(valid_ids) * int(emb_dim)


def train_flops(cfg: dict, examples: int, valid_ids: int) -> int:
    return tower_train_flops_per_example(cfg) * int(examples) + pooling_flops(
        valid_ids, int(cfg["emb_dim"])
    )


def roofline_share(flops: float, nbytes: float, seconds: float, peak: dict) -> float | None:
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the time measured, in percent; None without time."""
    if seconds <= 0:
        return None
    least = max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
