"""Plain reference of the CTR training step the benchmark checks.

What the configuration states, written straight in ``jax.numpy`` with no
kernel, cache, pipeline or parameter server: raw ids hash to keys and slots,
rows of unseen keys start from the PS's documented init, each example's rows
sum-pool per slot into the tower's input, the tower (ReLU between layers)
gives one logit, the loss is the mean binary cross-entropy, the tower takes
AdamW with global-norm clipping and the rows take row-wise Adagrad, after
every mini-batch. Rows live in a sorted key -> row store on the host between
steps.

``train`` runs it in float32 with the matmul precision the configuration
states (``matmul_precision``; ``default`` on a TPU is one bfloat16 pass with
float32 accumulation, as the program's own matmuls run), or with
``dtype=bfloat16`` throughout (the control: the nearest precision below
what the configuration states).

It imports nothing of the program and takes nothing the program made: the
weights come from the benchmark, the rows from the init rule, the keys from
the raw records.
"""

from __future__ import annotations

import numpy as np

from benchlib import gen

ROW_BUCKET = 1 << 16  # working tables padded to a multiple, so few shapes compile


class RowStore:
    """Trained rows by key; a key never written reads its init row."""

    def __init__(self, emb: int, init_scale: float):
        self.emb, self.scale = emb, init_scale
        self.keys = np.zeros(0, dtype=np.uint64)
        self.rows = np.zeros((0, emb), dtype=np.float32)
        self.accum = np.zeros((0, emb), dtype=np.float32)

    def get(self, keys: np.ndarray):
        rows = gen.init_rows(keys, self.emb, self.scale)
        accum = np.zeros_like(rows)
        if len(self.keys):
            pos = np.minimum(np.searchsorted(self.keys, keys), len(self.keys) - 1)
            hit = self.keys[pos] == keys
            rows[hit] = self.rows[pos[hit]]
            accum[hit] = self.accum[pos[hit]]
        return rows, accum

    def put(self, keys: np.ndarray, rows: np.ndarray, accum: np.ndarray) -> None:
        allk = np.concatenate([keys, self.keys])
        uk, first = np.unique(allk, return_index=True)  # the new rows win
        self.keys = uk
        self.rows = np.concatenate([rows, self.rows])[first]
        self.accum = np.concatenate([accum, self.accum])[first]


def _minibatch_step(cfg: dict, dtype):
    import jax
    import jax.numpy as jnp

    n_slots, emb = int(cfg["n_slots"]), int(cfg["emb_dim"])
    o, r = cfg["tower_optimizer"], cfg["row_optimizer"]
    n_layers = len(cfg["mlp_hidden"]) + 1

    def loss_fn(tower, table, ids, slot_of, valid, labels):
        mb = ids.shape[0]
        rows = table[ids] * valid[..., None].astype(dtype)
        seg = (jnp.arange(mb)[:, None] * n_slots + slot_of).reshape(-1)
        pooled = jax.ops.segment_sum(rows.reshape(-1, emb), seg, num_segments=mb * n_slots)
        h = pooled.reshape(mb, n_slots * emb)
        for i in range(n_layers):
            h = h @ tower[f"w{i}"] + tower[f"b{i}"]
            if i < n_layers - 1:
                h = jnp.maximum(h, 0)
        z, y = h[:, 0], labels.astype(dtype)
        return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))

    def step(tower, m, v, count, table, accum, ids, slot_of, valid, labels):
        loss, (g, gt) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            tower, table, ids, slot_of, valid, labels
        )
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
        scale = jnp.minimum(1.0, o["clip_norm"] / (gnorm + 1e-9)).astype(dtype)
        count = count + 1
        b1, b2 = o["b1"], o["b2"]
        bc1 = (1 - b1 ** count.astype(jnp.float32)).astype(dtype)
        bc2 = (1 - b2 ** count.astype(jnp.float32)).astype(dtype)
        new_t, new_m, new_v = {}, {}, {}
        for k in tower:
            gk = g[k] * scale
            new_m[k] = b1 * m[k] + (1 - b1) * gk
            new_v[k] = b2 * v[k] + (1 - b2) * jnp.square(gk)
            u = (new_m[k] / bc1) / (jnp.sqrt(new_v[k] / bc2) + o["eps"])
            new_t[k] = tower[k] - o["lr"] * u
        accum = accum + jnp.square(gt)
        table = table - r["lr"] * gt / (jnp.sqrt(accum) + r["eps"])
        return new_t, new_m, new_v, count, table, accum, loss.astype(jnp.float32)

    return jax.jit(step)


def train(cfg: dict, batches, tower0: dict, steps: int, dtype="float32") -> dict:
    """Runs ``steps`` batches from ``tower0`` and the init rows.

    Returns the per-step losses, the first step's gradient norms per leaf
    (``grad``) and a function that gives the rows after the steps for any
    keys (``rows``), with the tower after the steps (``tower``)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    n_keys, nnz, emb = int(cfg["n_sparse_keys"]), int(cfg["nnz_per_example"]), int(cfg["emb_dim"])
    k = int(cfg["minibatches_per_batch"])
    store = RowStore(emb, float(cfg["row_init_scale"]))
    step = _minibatch_step(cfg, dt)
    tower = {n: jnp.asarray(x, dt) for n, x in tower0.items()}
    m = {n: jnp.zeros_like(x) for n, x in tower.items()}
    v = {n: jnp.zeros_like(x) for n, x in tower.items()}
    count = jnp.zeros((), jnp.int32)
    losses, grad = [], None
    precision = cfg["matmul_precision"] if dt == jnp.float32 else "default"
    for s in range(steps):
        raw, lengths, labels = batches[s].raw_ids, batches[s].lengths, batches[s].labels
        valid = np.arange(nnz)[None, :] < lengths[:, None]
        keys = np.where(valid, gen.key_of_raw(raw, n_keys), np.uint64(0))
        wk = np.unique(keys[valid])
        ids = np.searchsorted(wk, keys).astype(np.int32)
        ids[~valid] = 0
        slot_of = np.where(valid, gen.slot_of_key(keys, int(cfg["n_slots"])), 0).astype(np.int32)
        rows, accum = store.get(wk)
        pad = -len(wk) % ROW_BUCKET
        table = jnp.asarray(np.pad(rows, ((0, pad), (0, 0))), dt)
        acc = jnp.asarray(np.pad(accum, ((0, pad), (0, 0))), dt)
        acc0 = acc
        mb = raw.shape[0] // k
        step_losses = []
        with jax.default_matmul_precision(precision):
            for i in range(k):
                sl = slice(i * mb, (i + 1) * mb)
                tower, m, v, count, table, acc, loss = step(
                    tower, m, v, count, table, acc,
                    jnp.asarray(ids[sl]), jnp.asarray(slot_of[sl]),
                    jnp.asarray(valid[sl]), jnp.asarray(labels[sl]),
                )
                step_losses.append(loss)
        losses.append(float(np.mean([float(x) for x in step_losses])))
        if s == 0:
            grad = {n: float(jnp.linalg.norm(x.astype(jnp.float32))) for n, x in m.items()}
            grow = (acc.astype(jnp.float32) - acc0.astype(jnp.float32))
            grad["rows"] = float(jnp.sqrt(jnp.sum(grow)))
        n = len(wk)
        store.put(wk, np.asarray(table.astype(jnp.float32))[:n], np.asarray(acc.astype(jnp.float32))[:n])
    tower_np = {n: np.asarray(x.astype(jnp.float32)) for n, x in tower.items()}
    return {
        "losses": losses,
        "grad": grad,
        "tower": tower_np,
        "rows": lambda keys: store.get(np.asarray(keys, dtype=np.uint64))[0],
    }


def readings(cfg: dict, batches, tower0: dict, steps: int, next_keys: np.ndarray, dtype="float32") -> dict:
    """The reference side of ``benchlib.compare.train_numbers``: losses,
    first-step gradient norms, and the change of every leaf after ``steps``
    steps, the rows' change taken over the next step's working keys."""
    out = train(cfg, batches, tower0, steps, dtype)
    change = {
        n: float(np.linalg.norm(out["tower"][n].astype(np.float64) - np.asarray(tower0[n], np.float64)))
        for n in tower0
    }
    init = gen.init_rows(next_keys, int(cfg["emb_dim"]), float(cfg["row_init_scale"]))
    change["rows"] = float(np.linalg.norm(out["rows"](next_keys).astype(np.float64) - init))
    return {"losses": out["losses"], "grad": out["grad"], "change": change}
