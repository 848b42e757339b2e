"""Train-step factories: the exact jitted programs the launcher lowers.

``make_lm_train_step`` builds the LM step for any assigned architecture:
cross-entropy next-token loss (+ MoE aux), microbatch gradient-accumulation
scan (bounds activation memory), remat inside the model, AdamW update.

In ``hier_ps`` embedding mode (the paper's technique as a first-class
feature) the step additionally takes the pulled *working table* and its
row-Adagrad accumulator, and returns both updated — Algorithm 1's device
phase; the host MEM-PS packs them back into one SSD row per key.

``make_ctr_train_step`` is the paper's CTR trainer: k mini-batches per pulled
working set inside ONE jit (Algorithm 1 lines 11-15), row-Adagrad on the
working table, Adam on the dense tower.

``make_ctr_train_step_grouped`` is its multi-table form: one working table
(+ accumulator) per slot group, each at its own embedding width, pulled from
its own named PS table via ``PSClient.session`` — heterogeneous feature
families co-hosted on one cluster.

Every factory routes working-row updates through ``kops.adagrad_update`` —
the fused Pallas Adagrad on TPU (pad-to-tile, so odd working-set shapes stay
on the kernel), the bitwise-identical reference elsewhere — and the CTR
forward passes pool through the fused embedding-bag op (models/ctr.py).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs import ArchConfig
from repro.kernels import ops as kops
from repro.models import get_model
from repro.models.common import constrain_like_params
from repro.train.optim import Adagrad, AdamW


@dataclass(frozen=True)
class TrainSettings:
    optimizer: AdamW = field(default_factory=AdamW)
    microbatches: int = 1
    attn_impl: str = "auto"
    remat: bool = True
    moe_aux_coef: float = 0.01
    row_lr: float = 0.05  # adagrad lr for hier-PS working rows


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token CE; one-hot contraction (SPMD-friendly on sharded
    vocab). logits: [B,S,V] f32; targets: [B,S] int32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
    picked = jnp.einsum("bsv,bsv->bs", logits, onehot)
    return jnp.mean(lse - picked)


def _make_loss_fn(cfg: ArchConfig, settings: TrainSettings, hier: bool):
    model = get_model(cfg)

    def loss_fn(params, working_table, micro):
        kwargs: dict = {}
        if cfg.family == "audio":
            kwargs["frames"] = micro["frames"]
        if cfg.family == "vlm":
            kwargs["image_embeds"] = micro["image_embeds"]
        if hier:
            kwargs["working_table"] = working_table
        logits, aux = model.forward(
            cfg, params, micro["tokens"],
            attn_impl=settings.attn_impl, remat=settings.remat, **kwargs,
        )
        if cfg.family == "vlm":  # image prefix positions carry no LM loss
            logits = logits[:, cfg.n_image_tokens :]
        loss = cross_entropy(logits, micro["targets"])
        return loss + settings.moe_aux_coef * aux, (loss, aux)

    return loss_fn


def make_lm_train_step(cfg: ArchConfig, settings: TrainSettings = TrainSettings()):
    """Dense-embedding LM step.

    step(params, opt_state, batch) -> (params, opt_state, metrics)
    batch: {"tokens": [B,S] int32, "targets": [B,S] int32,
            ["frames"|"image_embeds"]: modality stub inputs}
    """
    assert cfg.embedding_mode == "dense"
    loss_fn = _make_loss_fn(cfg, settings, hier=False)
    opt = settings.optimizer

    def step(params, opt_state, batch):
        n_micro = settings.microbatches
        split = lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
        micros = {k: split(v) for k, v in batch.items()}
        grad_fn = jax.value_and_grad(loss_fn, argnums=0, has_aux=True)

        def micro_step(acc, micro):
            (_, (loss, aux)), grads = grad_fn(params, None, micro)
            grads = constrain_like_params(grads)  # -> reduce-scatter per micro
            return jax.tree.map(jnp.add, acc, grads), (loss, aux)

        zero = constrain_like_params(
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        )
        acc, (losses, auxs) = jax.lax.scan(micro_step, zero, micros)
        grads = jax.tree.map(lambda g: g / n_micro, acc)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, {"loss": jnp.mean(losses), "moe_aux": jnp.mean(auxs)}

    return step


def make_lm_train_step_hier(cfg: ArchConfig, settings: TrainSettings = TrainSettings()):
    """hier_ps LM step: working table rows updated with row-Adagrad.

    step(params, opt_state, batch, working_table, row_accum)
      -> (params, opt_state, metrics, new_table, new_accum)
    batch["tokens"] holds *working slots*; batch["targets"] holds vocab ids.
    """
    assert cfg.embedding_mode == "hier_ps"
    loss_fn = _make_loss_fn(cfg, settings, hier=True)
    opt = settings.optimizer

    def step(params, opt_state, batch, working_table, row_accum):
        n_micro = settings.microbatches
        split = lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
        micros = {k: split(v) for k, v in batch.items()}
        grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)

        def micro_step(acc, micro):
            (_, (loss, aux)), grads = grad_fn(params, working_table, micro)
            grads = (constrain_like_params(grads[0]), grads[1])  # reduce-scatter
            return jax.tree.map(jnp.add, acc, grads), (loss, aux)

        zero = lambda t: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), t)
        acc, (losses, auxs) = jax.lax.scan(
            micro_step, (constrain_like_params(zero(params)), zero(working_table)), micros
        )
        grads = jax.tree.map(lambda g: g / n_micro, acc)
        new_params, new_opt = opt.update(grads[0], opt_state, params)
        new_table, new_accum = kops.adagrad_update(
            working_table, row_accum, grads[1], settings.row_lr
        )
        metrics = {"loss": jnp.mean(losses), "moe_aux": jnp.mean(auxs)}
        return new_params, new_opt, metrics, new_table, new_accum

    return step


# --------------------------------------------------------------------------
# CTR (the paper's own workload)
# --------------------------------------------------------------------------


def make_ctr_train_step(ctr_cfg, row_lr: float = 0.05, tower_opt: AdamW = AdamW(lr=1e-3)):
    """One pulled working set, k mini-batches trained inside one jit
    (Algorithm 1 lines 11-15).

    step(tower, opt_state, working_table, row_accum, minibatches)
      -> (tower, opt_state, table, accum, metrics)
    minibatches: dict of stacked [k, mb, ...] arrays
    (slot_ids, slot_of, valid, labels).
    """
    from repro.models import ctr as ctr_model

    def step(tower, opt_state, working_table, row_accum, minibatches):
        def one_minibatch(carry, mb):
            tower, opt_state, table, accum = carry
            loss, grads = jax.value_and_grad(
                lambda tw, tb: ctr_model.loss_fn(
                    ctr_cfg, tw, tb, mb["slot_ids"], mb["slot_of"], mb["valid"], mb["labels"]
                ),
                argnums=(0, 1),
            )(tower, table)
            with jax.named_scope("tower_adam"):
                tower, opt_state = tower_opt.update(grads[0], opt_state, tower)
            # paper: parameters synchronized across GPUs after EVERY
            # mini-batch — the row update applies to the shared table before
            # the next mini-batch sees it
            with jax.named_scope("row_adagrad"):
                table, accum = kops.adagrad_update(table, accum, grads[1], row_lr)
            return (tower, opt_state, table, accum), loss

        (tower, opt_state, working_table, row_accum), losses = jax.lax.scan(
            one_minibatch, (tower, opt_state, working_table, row_accum), minibatches
        )
        return tower, opt_state, working_table, row_accum, {"loss": jnp.mean(losses)}

    return step


def make_ctr_train_step_grouped(ctr_cfg, row_lr: float = 0.05, tower_opt: AdamW = AdamW(lr=1e-3)):
    """Multi-table CTR step: one working table per slot group, all updated
    inside one jit.

    step(tower, opt_state, tables, accums, minibatches)
      -> (tower, opt_state, tables, accums, metrics)
    tables/accums: {group_name: [n_working_g, emb_g]} per named PS table
    minibatches: {"labels": [k, mb],
                  "inputs": {group_name: {"slot_ids","slot_of","valid"}
                             each stacked [k, mb, nnz_g]}}
    """
    from repro.models import ctr as ctr_model

    def step(tower, opt_state, tables, accums, minibatches):
        def one_minibatch(carry, mb):
            tower, opt_state, tables, accums = carry
            loss, grads = jax.value_and_grad(
                lambda tw, tb: ctr_model.loss_fn_grouped(
                    ctr_cfg, tw, tb, mb["inputs"], mb["labels"]
                ),
                argnums=(0, 1),
            )(tower, tables)
            tower, opt_state = tower_opt.update(grads[0], opt_state, tower)
            # synchronize after every mini-batch (Algorithm 1 line 14),
            # independently per table
            new_tables, new_accums = {}, {}
            for name in tables:
                new_tables[name], new_accums[name] = kops.adagrad_update(
                    tables[name], accums[name], grads[1][name], row_lr
                )
            return (tower, opt_state, new_tables, new_accums), loss

        (tower, opt_state, tables, accums), losses = jax.lax.scan(
            one_minibatch, (tower, opt_state, tables, accums), minibatches
        )
        return tower, opt_state, tables, accums, {"loss": jnp.mean(losses)}

    return step
