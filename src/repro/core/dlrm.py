"""DLRM model — paper Sec. III-D / Fig. 4, Algorithms 1 & 2, in pure JAX.

Single-device reference implementation. The distributed version (paper
Sec. IV-A collective patterns via shard_map) lives in `core/sharding.py`
and must match this bit-for-bit in fp32 — that equivalence is the core
correctness property of the repo (tests/test_dlrm_distributed.py).

Layout conventions:
  dense features : (B, num_dense) float
  sparse indices : (B, T, L) int32      T = num_tables, L = lookups/table
  tables         : (T, R, d) float      stacked (RM2 tables are homogeneous);
                                        placed on a TPU as lane-dense lines
                                        (T, R/p, p*d) — `core/table_layout.py`
  pooled         : (B, T, d) float      sum-pooling (paper default)
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import DLRMConfig
from repro.core.table_layout import gather_rows, scatter_add_rows

Params = Dict[str, object]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _mlp_init(key: jax.Array, dims: Tuple[int, ...], d_in: int) -> List[Dict[str, jax.Array]]:
    layers = []
    prev = d_in
    for w in dims:
        key, k1, k2 = jax.random.split(key, 3)
        # DLRM repo uses uniform(-sqrt(1/n), sqrt(1/n)) — match the scale.
        bound = math.sqrt(1.0 / prev)
        layers.append({
            "w": jax.random.uniform(k1, (prev, w), jnp.float32, -bound, bound),
            "b": jax.random.uniform(k2, (w,), jnp.float32, -bound, bound),
        })
        prev = w
    return layers


def init_dlrm(key: jax.Array, cfg: DLRMConfig) -> Params:
    kb, kt, ke = jax.random.split(key, 3)
    bound = math.sqrt(1.0 / cfg.rows_per_table)
    return {
        "bot_mlp": _mlp_init(kb, cfg.bot_mlp_dims, cfg.num_dense),
        "top_mlp": _mlp_init(kt, cfg.top_mlp, cfg.top_mlp_in),
        "tables": jax.random.uniform(
            ke, (cfg.num_tables, cfg.rows_per_table, cfg.embed_dim),
            jnp.float32, -bound, bound),
    }


# ---------------------------------------------------------------------------
# Forward pieces (paper Alg. 1)
# ---------------------------------------------------------------------------
def mlp_forward(layers: List[Dict[str, jax.Array]], x: jax.Array,
                final_activation: Optional[str] = None) -> jax.Array:
    """ReLU MLP; DLRM's top MLP ends in sigmoid (we return logits and let the
    caller apply sigmoid — numerically stabler BCE)."""
    n = len(layers)
    for i, layer in enumerate(layers):
        x = x @ layer["w"] + layer["b"]
        if i < n - 1:
            x = jax.nn.relu(x)
        elif final_activation == "relu":
            x = jax.nn.relu(x)
    return x


def embedding_bag(tables: jax.Array, indices: jax.Array,
                  d: Optional[int] = None) -> jax.Array:
    """Lookup + sum-pool. tables (T,R,d) — or lines (T,R/p,p*d) with the
    row width ``d`` given — indices (B,T,L) -> (B,T,d)."""
    d = d or tables.shape[-1]

    # vmap over tables: for table t, rows of tab[idx (B,L)] -> (B,L,d)
    def one_table(tab, idx):
        return gather_rows(tab, idx, d).sum(axis=1)    # (B,d)
    out = jax.vmap(one_table, in_axes=(0, 1), out_axes=1)(tables, indices)
    return out                          # (B,T,d)


def feature_interactions(bot_out: jax.Array, pooled: jax.Array) -> jax.Array:
    """FM-style pairwise dot products, excluding diagonal + duplicates
    (paper Sec. III-D), concatenated with the bottom-MLP output.

    bot_out (B,d), pooled (B,T,d) -> (B, d + (T+1)T/2).
    """
    B, T, d = pooled.shape
    a = jnp.concatenate([bot_out[:, None, :], pooled], axis=1)  # (B, s+1=T+1, d)
    f = jnp.einsum("bid,bjd->bij", a, a)                        # (B, s+1, s+1)
    s1 = T + 1
    # strict lower triangle (excludes diagonal; keeps one copy of each pair)
    li, lj = jnp.tril_indices(s1, k=-1)
    flat = f[:, li, lj]                                         # (B, s1(s1-1)/2)
    return jnp.concatenate([bot_out, flat], axis=1)


def dlrm_forward(params: Params, dense: jax.Array, indices: jax.Array,
                 cfg: DLRMConfig) -> jax.Array:
    """Full single-device forward (Alg. 1, n=1). Returns logits (B,)."""
    bot = mlp_forward(params["bot_mlp"], dense)                 # (B, d)
    pooled = embedding_bag(params["tables"], indices, cfg.embed_dim)
    z = feature_interactions(bot, pooled)                       # (B, top_in)
    logits = mlp_forward(params["top_mlp"], z)[:, 0]            # (B,)
    return logits


def dlrm_forward_from_pooled(params: Params, dense: jax.Array,
                             pooled: jax.Array) -> jax.Array:
    """Dense part only, given pooled embeddings — the differentiable piece
    of the distributed step (embedding grads flow through `pooled`).
    Each layer runs under its `jax.named_scope` (``dlrm.bottom_mlp``,
    ``dlrm.interaction``, ``dlrm.top_mlp``), which XLA keeps in its ops'
    metadata (backward ops too), so a device trace names the layer."""
    with jax.named_scope("dlrm.bottom_mlp"):
        bot = mlp_forward(params["bot_mlp"], dense)
    with jax.named_scope("dlrm.interaction"):
        z = feature_interactions(bot, pooled)
    with jax.named_scope("dlrm.top_mlp"):
        return mlp_forward(params["top_mlp"], z)[:, 0]


# ---------------------------------------------------------------------------
# Loss (paper Alg. 2: BCE)
# ---------------------------------------------------------------------------
def bce_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Numerically-stable binary cross entropy with logits, mean-reduced."""
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def predict(params: Params, dense: jax.Array, indices: jax.Array,
            cfg: DLRMConfig) -> jax.Array:
    """P(u,c) in (0,1) — the paper's black-box output (Sec. III-A)."""
    return jax.nn.sigmoid(dlrm_forward(params, dense, indices, cfg))


# ---------------------------------------------------------------------------
# Single-device training step (reference for the distributed version)
# ---------------------------------------------------------------------------
def reference_train_step(params: Params, dense: jax.Array, indices: jax.Array,
                         labels: jax.Array, cfg: DLRMConfig, lr: float
                         ) -> Tuple[Params, jax.Array]:
    """Vanilla-SGD step (paper Alg. 2, n=1).

    Embedding gradients are handled sparsely exactly as Alg. 2 does:
    grads on pooled vectors are expanded (copied) to every looked-up row and
    scatter-added — the dense (T,R,d) gradient is never materialized.
    """
    def dense_loss(dense_params, pooled):
        logits = dlrm_forward_from_pooled(
            {**params, **dense_params}, dense, pooled)
        return bce_loss(logits, labels)

    pooled = embedding_bag(params["tables"], indices, cfg.embed_dim)
    dense_params = {"bot_mlp": params["bot_mlp"], "top_mlp": params["top_mlp"]}
    grads, g_pooled = jax.grad(dense_loss, argnums=(0, 1))(dense_params, pooled)

    new_params = jax.tree_util.tree_map(
        lambda p, g: p - lr * g, dense_params, grads)

    # expand_sparse_grads + sparse row update (Alg. 2)
    B, T, L = indices.shape
    g_rows = jnp.broadcast_to(g_pooled[:, :, None, :],
                              (B, T, L, g_pooled.shape[-1]))
    tables = params["tables"]
    flat_idx = indices.transpose(1, 0, 2).reshape(T, B * L)          # (T, B*L)
    flat_g = g_rows.transpose(1, 0, 2, 3).reshape(T, B * L, -1)      # (T, B*L, d)

    def upd(tab, idx, g):
        return scatter_add_rows(tab, idx, -lr * g)
    tables = jax.vmap(upd)(tables, flat_idx, flat_g)

    loss = dense_loss(dense_params, pooled)
    return {**new_params, "tables": tables}, loss
