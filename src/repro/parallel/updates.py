"""Sparse optimizer row updates (paper Alg. 2's scatter-add phase).

These operate on expanded flat gradients — (T, N) row ids + (T, N, d) row
grads per table group — produced by an exchange's backward routing; the
dense (T, R, d) embedding gradient is never materialized. Tables may be
stored as rows or as lane-dense lines (`core/table_layout.py`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.table_layout import scatter_add_rows


def sgd_row_update(lr: float):
    def update(tables, flat_idx, flat_g):
        def upd(tab, idx, g):
            return scatter_add_rows(tab, idx, (-lr * g).astype(tab.dtype))
        return jax.vmap(upd)(tables, flat_idx, flat_g)
    return update


def adagrad_row_update(lr: float, eps: float = 1e-8):
    """Row-wise AdaGrad (the DLRM repo's sparse optimizer). State: per-row
    accumulator (T, R). Returns fn(tables, acc, idx, g) -> (tables, acc)."""
    def update(tables, acc, flat_idx, flat_g):
        g_sq = jnp.mean(jnp.square(flat_g), axis=-1)           # (T, N) row-wise
        def upd(tab, a, idx, g, gs):
            a = a.at[idx].add(gs)
            scale = jax.lax.rsqrt(a[idx] + eps)                # (N,)
            return scatter_add_rows(
                tab, idx, (-lr * scale[:, None] * g).astype(tab.dtype)), a
        return jax.vmap(upd)(tables, acc, flat_idx, flat_g, g_sq)
    return update
