"""Plain float32 reference of DLRM (arXiv:1906.00091; sizes of
arXiv:2009.05230 Table XII), for deciding whether a run is correct.

Written from the model's description in straightforward `jax.numpy`, with
every matmul at "highest" precision (exact float32 on a TPU). It imports
nothing of the program under test and reads none of its state: weights
come from the benchmark's generator (`gen.py`), which recomputes any table
row from its id, so no copy of a table is ever needed.

The model, as the configuration file states it:
  bottom MLP  dense (B, 256) -> ReLU between layers, none after the last
              (as the program under test; facebookresearch/dlrm also puts
              a ReLU after the last bottom layer)
  embeddings  sum of the L looked-up rows of each of T tables
  interaction pairwise dot products of [bottom output, T pooled vectors],
              strictly lower triangle in row-major order, after the bottom
              output: (B, d + (T+1)T/2)
  top MLP     ReLU between layers, logit out; P(click) = sigmoid(logit)
  loss        mean binary cross-entropy on the logits
  SGD         p <- p - lr * grad for every leaf; an embedding row gets the
              sum of the pooled gradients of every lookup of it (Alg. 2)

``cast`` names a lower precision for the control: every operand (inputs,
weights, rows, activations, and in training the gradients that flow back
through them) is rounded to it before each operation, and sums and
products accumulate in float32.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import gen
from flops import bot_dims

CASTS = ("float8_e4m3fn",)


def _layers(cfg: dict):
    top_in = cfg["embed_dim"] + (cfg["num_tables"] + 1) * cfg["num_tables"] // 2
    return gen.mlp_layers(cfg["num_dense"], bot_dims(cfg), cfg["top_mlp"],
                          top_in)


def _rounder(cast: Optional[str]):
    import jax.numpy as jnp
    if cast is None:
        return lambda x: x
    if cast not in CASTS:
        raise ValueError(f"unknown control precision {cast!r}")
    dt = getattr(jnp, cast)
    return lambda x: x.astype(dt).astype(jnp.float32)


def _mlp(layers, x, q):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    for i, layer in enumerate(layers):
        x = jnp.dot(q(x), q(layer["w"]), precision=hi) + q(layer["b"])
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def _interact(bot, pooled, q):
    import jax
    import jax.numpy as jnp
    a = q(jnp.concatenate([bot[:, None, :], pooled], axis=1))
    f = jnp.einsum("bid,bjd->bij", a, a,
                   precision=jax.lax.Precision.HIGHEST)
    li, lj = np.tril_indices(a.shape[1], k=-1)
    return jnp.concatenate([bot, f[:, li, lj]], axis=1)


def _logits(dense_params, pooled, dense, q):
    bot = _mlp(dense_params["bot_mlp"], dense, q)
    z = _interact(bot, pooled, q)
    return _mlp(dense_params["top_mlp"], z, q)[:, 0]


def _bce(logits, labels):
    import jax.numpy as jnp
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def _keys(cfg: dict, seed: int) -> np.ndarray:
    return gen.weight_keys(seed, 2 * len(_layers(cfg)))


# ----------------------------------------------------------------- serving
def _probs_fn(cfg: dict, cast: Optional[str]):
    import jax
    import jax.numpy as jnp
    layers = _layers(cfg)
    R, d = cfg["rows_per_table"], cfg["embed_dim"]
    bound = gen.table_bound(d)
    q = _rounder(cast)

    @jax.jit
    def probs(keys, dense, idx):
        dp = gen.dense_leaves(keys, layers)
        rows = gen.table_rows(keys[-1], R, d, bound, idx)   # (B,T,L,d)
        pooled = jnp.sum(q(rows), axis=2)
        return jax.nn.sigmoid(_logits(dp, pooled, dense, q))
    return probs


def serve_probs(cfg: dict, seed: int, dense: np.ndarray, idx: np.ndarray,
                cast: Optional[str] = None, block: int = 200) -> np.ndarray:
    """P(click) of every sample, computed ``block`` samples at a time."""
    import jax
    fn = _probs_fn(cfg, cast)
    keys = _keys(cfg, seed)
    out = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, dense.shape[0], block):
            out.append(np.asarray(fn(keys, dense[s:s + block],
                                     idx[s:s + block])))
    return np.concatenate(out)


# ---------------------------------------------------------------- training
def train_readings(cfg: dict, seed: int, batches: List[Dict[str, np.ndarray]],
                   lr: float, cast: Optional[str] = None,
                   fault: Optional[str] = None) -> dict:
    """SGD over ``batches`` from the seed's weights. Returns each step's
    loss, each leaf's first gradient norm as read from the state after
    step 1 (||p1 - p0|| / lr), and each leaf's change ||pK - p0|| after the
    last step.

    Tables are held compactly: only the rows the batches look up, in the
    order of their global ids (table * R + row); every other row keeps its
    initial value, so its change is exactly 0 and adds nothing to a norm.

    ``fault`` plants one of the program's faults in the reference in its
    place, to read what each would score: "unchanged" (steps return the
    state as it came), "half_batch" (the loss and gradients over the first
    half of each batch only).
    """
    import jax
    import jax.numpy as jnp
    layers = _layers(cfg)
    R, d, T = cfg["rows_per_table"], cfg["embed_dim"], cfg["num_tables"]
    keys = _keys(cfg, seed)
    gid = [(np.arange(T, dtype=np.int64)[None, :, None] * R
            + b["indices"].astype(np.int64)) for b in batches]
    uniq = np.unique(np.concatenate([g.ravel() for g in gid]))
    cidx = [np.searchsorted(uniq, g).astype(np.int32) for g in gid]
    q = _rounder(cast)
    @jax.jit
    def init(keys, uniq_rows):
        dp = gen.dense_leaves(keys, layers)
        col = jnp.arange(d, dtype=jnp.uint32)
        tab = gen.hashed_uniform(keys[-1], uniq_rows[:, None], col,
                                 gen.table_bound(d))
        return {**dp, "tables": tab}

    def loss_fn(params, dense, ci, labels):
        rows = q(params["tables"])[ci]                     # (B,T,L,d)
        pooled = jnp.sum(rows, axis=2)
        return _bce(_logits(params, pooled, dense, q), labels)

    @jax.jit
    def step(params, dense, ci, labels):
        if fault == "half_batch":
            h = dense.shape[0] // 2
            dense, ci, labels = dense[:h], ci[:h], labels[:h]
        loss, g = jax.value_and_grad(loss_fn)(params, dense, ci, labels)
        if fault == "unchanged":
            return params, loss
        return jax.tree_util.tree_map(lambda p, g: p - lr * g, params,
                                      g), loss

    def norms(a, b):
        b = gen.named_leaves(b)
        return {k: float(jnp.sqrt(jnp.sum(jnp.square(x - b[k]))))
                for k, x in gen.named_leaves(a).items()}

    with jax.default_matmul_precision("highest"):
        p0 = init(keys, jnp.asarray(uniq.astype(np.uint32)))
        p = p0
        losses, grad = [], None
        for k, b in enumerate(batches):
            p, loss = step(p, b["dense"], cidx[k], b["labels"])
            losses.append(float(loss))
            if k == 0:
                grad = {n: x / lr for n, x in norms(p, p0).items()}
        change = norms(p, p0)
    return {"losses": losses, "grad_norms": grad, "change_norms": change}

