"""Pallas TPU megakernel: the serve hot path in ONE launch.

The composed serve step runs the embedding gather/pool (``embedding_bag.py``
or ``cached_embedding_bag.py``) and the FM feature interaction
(``interactions.py``) as separate kernel launches, with the pooled
``(B, T, d)`` tensor written to HBM by the first and read back by the
second. That round-trip is pure waste on the memory-bound shape the paper's
Sec. III-D analysis identifies as the inference bottleneck: the pooled
block is small enough to stay resident in VMEM for a whole batch block.

``fused_bag_interactions`` fuses gather -> pool -> A·Aᵀ:

  grid (nB, bb) — batch blocks of ``block_b`` samples, one sample per step;
  the step gathers and pools its T bags with the bag kernels' manual-DMA
  routine (``embedding_bag.gather_pool``). A VMEM scratch accumulator
  ``(bb, T+1, d)`` holds the bottom-MLP output (slot 0) and the bag pools
  (slots 1..T); at the last step of each batch block the resident
  accumulator feeds the batched ``A·Aᵀ`` contraction directly — the pooled
  embeddings never touch HBM and the whole pipeline is one kernel launch.

Three variants share the structure:

  fused_bag_interactions_pallas         — single-tier tables (T, R, d)
  fused_cached_bag_interactions_pallas  — two-tier fast/bulk layout with
                                          pre-translated index streams
                                          (``cached_embedding_bag.py``)
  fused_grouped_bag_interactions_pallas — two table GROUPS with distinct
                                          row counts (the tiered plan's
                                          fast/bulk table split), indices
                                          pre-permuted to concat order; the
                                          interaction output is un-permuted
                                          by a static tril gather outside

The strict-lower-triangle extraction (a static gather) happens outside the
kernel, as in ``interactions.py`` — data movement, not compute. The
un-permutation for the grouped variant rides the same gather: with
``pos = [0] + [1 + inv_perm]``, ``f_orig[i, j] = f_perm[pos[i], pos[j]]``,
so gathering ``f[:, pos[li], pos[lj]]`` at the ORIGINAL-order tril indices
restores original table order for free.

Numerics: the same pool as the composed bag kernels (``gather_pool``): a
table's rows sum in fp32 as a sublane reduction over its L slot-placed
lines, then one fp32 ``dot_general``. Against the composed REFERENCE path
(an fp32 sum in L order) the results differ by rounding only, which the
tests bound at 1e-5.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.embedding_bag import (Part, flat_indices, gather_pool,
                                         index_spec, line_scratch,
                                         lines_for_kernel)


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------
def _pad_batch(bot_out: jax.Array, idx_list, block_b: int):
    """Pad the batch dim of bot_out + every index array up to a multiple of
    block_b (zeros: pad samples gather row 0 / slot 0 into accumulator rows
    whose interaction output is sliced off before anyone reads it)."""
    B = bot_out.shape[0]
    bb = min(block_b, B)
    pad = (-B) % bb
    if pad:
        bot_out = jnp.pad(bot_out, ((0, pad), (0, 0)))
        idx_list = [jnp.pad(ix, ((0, pad), (0, 0), (0, 0))) for ix in idx_list]
    return bot_out, idx_list, bb, B + pad


def _finalize(bot_out: jax.Array, f: jax.Array,
              inv_perm: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """(Bp, s1, s1) raw interaction matrix -> (B, d + s1(s1-1)/2) features.

    Static strict-lower-triangle gather + concat with bot_out, exactly as
    ``interactions_pallas`` does outside its kernel. ``inv_perm`` (position
    of each original table in the kernel's table order) folds the
    un-permutation into the same gather.
    """
    B = bot_out.shape[0]
    s1 = f.shape[1]
    li, lj = np.tril_indices(s1, k=-1)
    if inv_perm is not None:
        pos = np.concatenate(([0], 1 + np.asarray(inv_perm, np.int64)))
        li, lj = pos[li], pos[lj]
    return jnp.concatenate(
        [bot_out.astype(jnp.float32), f[:B, li, lj]], axis=1)


def _fused_kernel_body(j, bot_ref, out_ref, acc_ref, parts, bufs, sem, *,
                       bb, T, L, d):
    """One sample of a batch block: bottom-MLP output into accumulator
    slot 0, the T bag pools into slots 1..T, and after the block's last
    sample the batched ``A·Aᵀ`` contraction."""
    acc_ref[pl.ds(j, 1), pl.ds(0, 1), :] = (
        bot_ref[pl.ds(j, 1), :].astype(jnp.float32).reshape(1, 1, d))

    def emit(t, pooled):
        acc_ref[pl.ds(j, 1), pl.ds(t + 1, 1), :] = pooled.reshape(1, 1, d)

    gather_pool(parts, bufs, sem, T=T, L=L, d=d, emit=emit)

    @pl.when(j == bb - 1)
    def _contract():
        a = acc_ref[...]                              # (bb, s1, d) fp32
        out_ref[...] = jax.lax.dot_general(
            a, a, (((2,), (2,)), ((0,), (0,))),       # batch 0, contract d
            preferred_element_type=jnp.float32)


def _fused_call(kernel, *, bb, Bp, T, L, d, s1, n_idx, n_tab, scratch,
                interpret):
    """pallas_call over grid (Bp/bb, bb): one sample per step, the
    accumulator and output block resident for a whole batch block."""
    sample = lambda i, j: i * bb + j
    return pl.pallas_call(
        kernel,
        grid=(Bp // bb, bb),
        in_specs=([index_spec(T, L, sample)] * n_idx
                  + [pl.BlockSpec((bb, d), lambda i, j: (i, 0))]
                  + [pl.BlockSpec(memory_space=pl.ANY)] * n_tab),
        out_specs=pl.BlockSpec((bb, s1, s1), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, s1, s1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, s1, d), jnp.float32)] + scratch,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Single-tier variant
# ---------------------------------------------------------------------------
def _fused_bag_kernel(idx_ref, bot_ref, tab_ref, out_ref, acc_ref, buf, sem,
                      *, bb, T, L, d, p):
    _fused_kernel_body(pl.program_id(1), bot_ref, out_ref, acc_ref,
                       [Part(tab_ref, idx_ref, 0, T, p)], [buf], sem,
                       bb=bb, T=T, L=L, d=d)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fused_bag_interactions_pallas(tables: jax.Array, indices: jax.Array,
                                  bot_out: jax.Array, *, block_b: int = 64,
                                  interpret: bool = True) -> jax.Array:
    """tables (T, R, d), indices (B, T, L) int32, bot_out (B, d)
    -> (B, d + (T+1)T/2) fp32 interaction features, one launch.

    ``interpret=True`` executes the kernel body on the host (validation
    mode); on TPU pass ``interpret=False``.
    """
    T, R, d = tables.shape
    B, T2, L = indices.shape
    assert T == T2 and bot_out.shape == (B, d), \
        (tables.shape, indices.shape, bot_out.shape)
    s1 = T + 1
    lines, p = lines_for_kernel("fused_bag_interactions_pallas", tables,
                                interpret)
    bot_p, (idx_p,), bb, Bp = _pad_batch(bot_out, [indices], block_b)
    f = _fused_call(
        functools.partial(_fused_bag_kernel, bb=bb, T=T, L=L, d=d, p=p),
        bb=bb, Bp=Bp, T=T, L=L, d=d, s1=s1, n_idx=1, n_tab=1,
        scratch=line_scratch([(p, tables.dtype)], L, d),
        interpret=interpret)(flat_indices(idx_p), bot_p, lines)
    return _finalize(bot_out, f)


# ---------------------------------------------------------------------------
# Two-tier (cached fast/bulk) variant
# ---------------------------------------------------------------------------
def _fused_cached_kernel(fi_ref, bi_ref, bot_ref, fast_ref, bulk_ref,
                         out_ref, acc_ref, fbuf, bbuf, sem,
                         *, bb, T, L, d, pf, pb):
    _fused_kernel_body(pl.program_id(1), bot_ref, out_ref, acc_ref,
                       [Part(fast_ref, fi_ref, 0, T, pf),
                        Part(bulk_ref, bi_ref, 0, T, pb)],
                       [fbuf, bbuf], sem, bb=bb, T=T, L=L, d=d)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def fused_cached_bag_interactions_pallas(
        fast: jax.Array, bulk: jax.Array, fast_idx: jax.Array,
        bulk_idx: jax.Array, bot_out: jax.Array, *, block_b: int = 64,
        interpret: bool = True) -> jax.Array:
    """Two-tier layout (``cached_embedding_bag.py``): fast (T, S+1, d) with
    zeros miss slot S, bulk (T, R+1, d) with zeros hit slot R, pre-translated
    fast_idx/bulk_idx (B, T, L); bot_out (B, d) -> fused features, one
    launch. Each lookup DMAs one line from each tier (exactly one row is
    the zero pad), so padded batch rows are harmless by the same argument:
    slot S / slot R are zeros and the padded interaction rows are
    discarded."""
    T, S1, d = fast.shape
    T2, R1, d2 = bulk.shape
    B, T3, L = fast_idx.shape
    assert T == T2 == T3 and d == d2 and fast_idx.shape == bulk_idx.shape
    assert bot_out.shape == (B, d), (bot_out.shape, (B, d))
    s1 = T + 1
    name = "fused_cached_bag_interactions_pallas"
    fast_l, pf = lines_for_kernel(name, fast, interpret)
    bulk_l, pb = lines_for_kernel(name, bulk, interpret)
    # pad index value S / R is NOT zero-filled by _pad_batch's jnp.pad(0) —
    # row 0 of either tier is a real row; pad SAMPLES still only write
    # accumulator rows whose output is sliced off, so 0 is fine.
    bot_p, (fi_p, bi_p), bb, Bp = _pad_batch(bot_out, [fast_idx, bulk_idx],
                                             block_b)
    f = _fused_call(
        functools.partial(_fused_cached_kernel, bb=bb, T=T, L=L, d=d,
                          pf=pf, pb=pb),
        bb=bb, Bp=Bp, T=T, L=L, d=d, s1=s1, n_idx=2, n_tab=2,
        scratch=line_scratch([(pf, fast.dtype), (pb, bulk.dtype)], L,
                             d),
        interpret=interpret)(flat_indices(fi_p), flat_indices(bi_p), bot_p,
                             fast_l, bulk_l)
    return _finalize(bot_out, f)


# ---------------------------------------------------------------------------
# Grouped (tiered-plan fast/bulk table split) variant
# ---------------------------------------------------------------------------
def _fused_grouped_kernel(idx_ref, bot_ref, fast_ref, bulk_ref, out_ref,
                          acc_ref, fbuf, bbuf, sem, *, bb, T, L, d, n_fast,
                          pf, pb):
    # tables [0, n_fast) come from the fast group, the rest from the bulk
    # group; each lookup DMAs from its own group only
    _fused_kernel_body(pl.program_id(1), bot_ref, out_ref, acc_ref,
                       [Part(fast_ref, idx_ref, 0, n_fast, pf),
                        Part(bulk_ref, idx_ref, n_fast, T, pb)],
                       [fbuf, bbuf], sem, bb=bb, T=T, L=L, d=d)


@functools.partial(jax.jit,
                   static_argnames=("inv_perm", "block_b", "interpret"))
def fused_grouped_bag_interactions_pallas(
        tables_fast: jax.Array, tables_bulk: jax.Array,
        indices_perm: jax.Array, bot_out: jax.Array, *,
        inv_perm: Tuple[int, ...], block_b: int = 64,
        interpret: bool = True) -> jax.Array:
    """Tiered-plan table split: tables_fast (Tf, R, d) + tables_bulk
    (Tb, R, d); ``indices_perm`` (B, Tf+Tb, L) already permuted to
    concat(fast, bulk) table order; ``inv_perm`` (static tuple — the plan's
    ``PlanGroups.inv_perm``) restores original order in the output gather.

    An empty group delegates to the single-tier kernel (a (0, R, d) operand
    has no rows to gather from)."""
    Tf = tables_fast.shape[0]
    Tb = tables_bulk.shape[0]
    T = Tf + Tb
    B, T2, L = indices_perm.shape
    assert T == T2, (tables_fast.shape, tables_bulk.shape, indices_perm.shape)
    if Tf == 0 or Tb == 0:
        tables = tables_fast if Tb == 0 else tables_bulk
        f_feats = fused_bag_interactions_pallas(
            tables, indices_perm, bot_out, block_b=block_b,
            interpret=interpret)
        # single-tier output is in PERMUTED order with bot prepended; undo
        # via the same static gather the two-group path uses
        d = bot_out.shape[1]
        s1 = T + 1
        li0, lj0 = np.tril_indices(s1, k=-1)
        f = jnp.zeros((B, s1, s1), jnp.float32)
        f = f.at[:, li0, lj0].set(f_feats[:, d:])
        f = f + jnp.swapaxes(f, 1, 2)
        return _finalize(bot_out, f, inv_perm=inv_perm)
    d = tables_fast.shape[2]
    assert tables_bulk.shape[2] == d and bot_out.shape == (B, d)
    s1 = T + 1
    name = "fused_grouped_bag_interactions_pallas"
    fast_l, pf = lines_for_kernel(name, tables_fast, interpret)
    bulk_l, pb = lines_for_kernel(name, tables_bulk, interpret)
    bot_p, (idx_p,), bb, Bp = _pad_batch(bot_out, [indices_perm], block_b)
    f = _fused_call(
        functools.partial(_fused_grouped_kernel, bb=bb, T=T, L=L, d=d,
                          n_fast=Tf, pf=pf, pb=pb),
        bb=bb, Bp=Bp, T=T, L=L, d=d, s1=s1, n_idx=1, n_tab=2,
        scratch=line_scratch([(pf, tables_fast.dtype),
                              (pb, tables_bulk.dtype)], L, d),
        interpret=interpret)(flat_indices(idx_p), bot_p, fast_l, bulk_l)
    return _finalize(bot_out, f, inv_perm=inv_perm)
