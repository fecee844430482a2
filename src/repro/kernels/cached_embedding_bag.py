"""Pallas TPU kernel: two-tier cached embedding-bag (the planner's fast path).

Executes the hot/cold placement the planner computes (paper Sec. VII-A: a
STATIC freq-aware allocation of embedding rows across a fast HBM-like tier
and a bulk DDR4-like tier). The runtime layout (`core/tiered_embedding.py`):

  fast (T, S+1, d): per-table compact hot-row arrays; slot S is a zeros row
                    (the "miss" slot — cold lookups land here).
  bulk (T, R+1, d): canonical full tables; row R is a zeros row (the "hit"
                    slot — hot lookups land here).

The index stream is pre-translated (CacheEmbedding's `prepare_ids` idea,
hpcaitech/CacheEmbedding): for each lookup either ``fast_idx`` holds the hot
slot and ``bulk_idx`` the pad row, or vice versa. The kernel then needs NO
per-element branching: every lookup DMAs one line from each tier and adds
their rows — exactly one of the two is the zero pad, so the pool is exact.
Gather and pooling are `embedding_bag.gather_pool`; each tier keeps its own
line layout, which is lane-dense on TPU only when its row count (S+1, R+1)
is a multiple of ``128 // d``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.embedding_bag import (Part, flat_indices, gather_pool,
                                         index_spec, line_scratch,
                                         lines_for_kernel)


def _cached_bag_kernel(fi_ref, bi_ref, fast_ref, bulk_ref, out_ref,
                       fbuf, bbuf, sem, *, T, L, d, pf, pb):
    def emit(t, pooled):
        out_ref[pl.ds(t, 1), :] = pooled

    gather_pool([Part(fast_ref, fi_ref, 0, T, pf),
                 Part(bulk_ref, bi_ref, 0, T, pb)], [fbuf, bbuf], sem,
                T=T, L=L, d=d, emit=emit)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cached_embedding_bag_pallas(fast: jax.Array, bulk: jax.Array,
                                fast_idx: jax.Array, bulk_idx: jax.Array,
                                *, interpret: bool = True) -> jax.Array:
    """fast (T, S+1, d), bulk (T, R+1, d) any float dtype; fast_idx/bulk_idx
    (B, T, L) int32 pre-translated slots -> pooled (B, T, d) fp32.

    ``interpret=True`` executes the kernel body on the host (validation
    mode); on TPU pass ``interpret=False``.
    """
    T, S1, d = fast.shape
    T2, R1, d2 = bulk.shape
    B, T3, L = fast_idx.shape
    assert T == T2 == T3 and d == d2, (fast.shape, bulk.shape, fast_idx.shape)
    assert fast_idx.shape == bulk_idx.shape
    name = "cached_embedding_bag_pallas"
    fast_l, pf = lines_for_kernel(name, fast, interpret)
    bulk_l, pb = lines_for_kernel(name, bulk, interpret)
    return pl.pallas_call(
        functools.partial(_cached_bag_kernel, T=T, L=L, d=d, pf=pf, pb=pb),
        grid=(B,),
        in_specs=[index_spec(T, L, lambda b: b),
                  index_spec(T, L, lambda b: b),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, T, d), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, d), jnp.float32),
        scratch_shapes=line_scratch(
            [(pf, fast.dtype), (pb, bulk.dtype)], L, d),
        interpret=interpret,
    )(flat_indices(fast_idx), flat_indices(bulk_idx), fast_l, bulk_l)
