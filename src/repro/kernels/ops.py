"""Public jit'd wrappers for the Pallas kernels.

Dispatch is by platform. On a TPU backend every op runs its compiled
kernel (``interpret=False``). The rest is for CPU hosts only, where tests
and the reference runs execute: the per-kernel ops run their kernel
bodies in interpret mode, and the fused serve ops run their composed
pure-jnp references (see below).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ref
from repro.kernels.cached_embedding_bag import cached_embedding_bag_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.fused_serve import (
    fused_bag_interactions_pallas, fused_cached_bag_interactions_pallas,
    fused_grouped_bag_interactions_pallas)
from repro.kernels.interactions import interactions_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def embedding_bag(tables: jax.Array, indices: jax.Array) -> jax.Array:
    """(T, R, d) × (B, T, L) -> (B, T, d) pooled, fp32."""
    return embedding_bag_pallas(tables, indices, interpret=_interpret())


def cached_embedding_bag(fast: jax.Array, bulk: jax.Array,
                         fast_idx: jax.Array, bulk_idx: jax.Array) -> jax.Array:
    """Two-tier cached bag: (T, S+1, d) × (T, R+1, d) × 2×(B, T, L) pre-
    translated slots -> (B, T, d) pooled, fp32."""
    return cached_embedding_bag_pallas(fast, bulk, fast_idx, bulk_idx,
                                       interpret=_interpret())


def interactions(bot_out: jax.Array, pooled: jax.Array,
                 block_b: int = 64) -> jax.Array:
    """(B, d) × (B, T, d) -> (B, d + (T+1)T/2) fp32."""
    return interactions_pallas(bot_out, pooled, block_b=block_b,
                               interpret=_interpret())


# Off-TPU the fused serve ops run the composed pure-jnp reference (XLA:CPU
# compiled, and bit-identical to the composed serve path there) instead of
# the interpreted kernel, which would be minutes per serve batch at real
# shapes. The Pallas kernels themselves are validated against the same
# oracles at tiny shapes in tests/test_fused_serve.py and compiled for the
# TPU in tests/test_tpu_compile.py.
def fused_bag_interactions(tables: jax.Array, indices: jax.Array,
                           bot_out: jax.Array,
                           block_b: int = 64) -> jax.Array:
    """(T,R,d) x (B,T,L) x (B,d) -> (B, d + (T+1)T/2) fused gather->pool->
    interaction features, one kernel launch on TPU."""
    if _interpret():
        return ref.fused_bag_interactions_ref(tables, indices, bot_out)
    return fused_bag_interactions_pallas(tables, indices, bot_out,
                                         block_b=block_b, interpret=False)


def fused_cached_bag_interactions(fast: jax.Array, bulk: jax.Array,
                                  fast_idx: jax.Array, bulk_idx: jax.Array,
                                  bot_out: jax.Array,
                                  block_b: int = 64) -> jax.Array:
    """Two-tier fused serve path: (T,S+1,d) x (T,R+1,d) x 2x(B,T,L) x (B,d)
    -> fused interaction features, one launch on TPU."""
    if _interpret():
        return ref.fused_cached_bag_interactions_ref(
            fast, bulk, fast_idx, bulk_idx, bot_out)
    return fused_cached_bag_interactions_pallas(
        fast, bulk, fast_idx, bulk_idx, bot_out, block_b=block_b,
        interpret=False)


def fused_grouped_bag_interactions(tables_fast: jax.Array,
                                   tables_bulk: jax.Array,
                                   indices_perm: jax.Array,
                                   bot_out: jax.Array, *,
                                   inv_perm,
                                   block_b: int = 64) -> jax.Array:
    """Tiered-plan fused serve path: (Tf,R,d) + (Tb,R,d) table groups,
    indices pre-permuted to concat order, un-permuted output — one launch
    on TPU. ``inv_perm`` must be a static (hashable) tuple."""
    inv_perm = tuple(int(t) for t in inv_perm)
    if _interpret():
        return ref.fused_grouped_bag_interactions_ref(
            tables_fast, tables_bulk, indices_perm, bot_out, inv_perm)
    return fused_grouped_bag_interactions_pallas(
        tables_fast, tables_bulk, indices_perm, bot_out, inv_perm=inv_perm,
        block_b=block_b, interpret=False)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """(B,T,Hq,hd) × (B,S,Hkv,hd)² -> (B,T,Hq,hd)."""
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, block_k: int = 256) -> jax.Array:
    """(B,Hq,hd) × (B,S,Hkv,hd)² × (B,) -> (B,Hq,hd)."""
    return flash_decode_pallas(q, k_cache, v_cache, lengths, block_k=block_k,
                               interpret=_interpret())
