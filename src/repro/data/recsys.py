"""Synthetic Criteo-like click-log pipeline for DLRM.

Design requirements (the fault-tolerance story):

  * STATELESS and STEP-INDEXED: batch(step) is a pure function of
    (seed, step), so a restarted or re-sharded job regenerates exactly the
    batch stream it would have seen — no iterator state to checkpoint and
    no divergence across data-parallel workers after elastic re-meshing.
  * Index streams are POWER-LAW distributed (Zipf-like), matching the
    production access skew the paper cites ([19]: 40-60% hit rate in a
    64 MB cache). `alpha=0` degenerates to uniform — the paper's
    "zero temporal locality" worst case used by the perf model.
  * Labels come from a planted logistic model so training has signal and
    loss decrease is a meaningful integration test.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import DLRMConfig

RecSysBatch = Dict[str, jax.Array]

# Weight of the table-borne (sparse) component of the planted teacher's
# logit, relative to the dense component's unit scale. Large enough that
# the embedding rows carry REAL label signal — tables-only online
# training (repro.online) must be able to move the served accuracy, and
# a drift rotation of the row space must genuinely hurt a frozen table.
SPARSE_SIGNAL = 0.75


def teacher_click_probs(cfg: DLRMConfig, dense: jax.Array,
                        indices: jax.Array, seed: int = 0) -> jax.Array:
    """The planted logistic teacher's exact P(click) for a batch.

    `make_recsys_batch` samples labels from this; `repro.online` scores
    served probabilities against it as a deterministic accuracy proxy.
    The sparse component is a function of the UNROTATED row ids (the
    teacher predates any drift rotation), so rotating the id space moves
    the row -> signal association and stale tables become wrong.
    """
    wkey = jax.random.PRNGKey(seed + 10_007)
    w = (jax.random.normal(wkey, (cfg.num_dense,), jnp.float32)
         / math.sqrt(cfg.num_dense))
    sig = dense @ w + SPARSE_SIGNAL * jnp.mean(
        (indices[:, :, 0] % 7).astype(jnp.float32) - 3.0, axis=1)
    return jax.nn.sigmoid(2.0 * sig)


def _zipf_indices(key: jax.Array, shape, n_rows: int, alpha: float) -> jax.Array:
    """Power-law row ids: P(rank r) ∝ (r+1)^-alpha via inverse-CDF sampling.

    alpha=0 -> uniform (paper's zero-locality stress case).
    The rank->row permutation is a fixed multiplicative hash so hot rows are
    scattered across the table (defeats trivial range caching, like real IDs).
    """
    u = jax.random.uniform(key, shape, minval=1e-9)
    if alpha == 0.0:
        ranks = (u * n_rows).astype(jnp.int32)
    else:
        # inverse CDF of truncated power law on [1, n_rows]
        a1 = 1.0 - alpha
        if abs(a1) < 1e-6:
            ranks = jnp.exp(u * math.log(n_rows)).astype(jnp.int32) - 1
        else:
            hi = float(n_rows) ** a1
            ranks = (jnp.power(u * (hi - 1.0) + 1.0, 1.0 / a1) - 1.0).astype(jnp.int32)
    ranks = jnp.clip(ranks, 0, n_rows - 1)
    # scatter ranks over row space (odd multiplier -> bijection mod 2^k tables)
    return ((ranks.astype(jnp.uint32) * jnp.uint32(2654435761)) %
            jnp.uint32(n_rows)).astype(jnp.int32)


def make_recsys_batch(cfg: DLRMConfig, step: int, seed: int = 0,
                      alpha: float = 0.0,
                      batch_size: Optional[int] = None) -> RecSysBatch:
    """Pure function (cfg, step, seed) -> batch. See module docstring."""
    b = batch_size or cfg.batch_size
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    kd, ks, kl, kw = jax.random.split(key, 4)

    dense = jax.random.normal(kd, (b, cfg.num_dense), jnp.float32)
    indices = _zipf_indices(
        ks, (b, cfg.num_tables, cfg.lookups_per_table), cfg.rows_per_table, alpha)

    # planted logistic teacher: w fixed by seed (not by step!)
    p = teacher_click_probs(cfg, dense, indices, seed)
    labels = jax.random.bernoulli(kl, p).astype(jnp.float32)
    return {"dense": dense, "indices": indices, "labels": labels}


def recsys_batch_iterator(cfg: DLRMConfig, seed: int = 0, alpha: float = 0.0,
                          start_step: int = 0,
                          batch_size: Optional[int] = None
                          ) -> Iterator[RecSysBatch]:
    """Infinite deterministic stream; restart with start_step=ckpt_step."""
    step = start_step
    while True:
        yield make_recsys_batch(cfg, step, seed, alpha, batch_size)
        step += 1
