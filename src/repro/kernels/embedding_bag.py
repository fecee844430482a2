"""Pallas TPU kernel: embedding-bag (gather + sum-pool) — THE DLRM hot spot.

Paper context (Sec. IV-D-2): embedding lookups are scattered 64-256 B reads
with no spatial locality; throughput is bound by the memory system's random
access rate, not FLOPs. The kernel gathers with manual DMAs: the table stays
in HBM (``memory_space=ANY``) in its lane-dense line layout
(`repro.core.table_layout`), and each lookup copies the one 128-lane line
that holds its row into VMEM, where the row's d lanes are picked out and
summed. Only pooled vectors are ever written back (the structural analogue
of the paper's "near-memory pooling").

Grid: one step per sample. The step's ``T*L`` indices arrive as their own
SMEM block (``(1, T*L)`` int32, 12.8 KB at RM2's T=40, L=80), so SMEM holds
one sample's indices whatever the batch. Within a step the tables are
walked in order with two VMEM line buffers: the DMAs for table t+1 are in
flight while table t is pooled. Rows are added in L order, one at a time,
into an fp32 accumulator.

The same gather/pool routine (`gather_pool`) serves the cached and fused
kernels (``cached_embedding_bag.py``, ``fused_serve.py``).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.table_layout import LANES, rows_per_line, to_lines


class Part(NamedTuple):
    """One table group a kernel gathers from.

    ``tab``: HBM ref (T_k, R_k/p, p*d); ``idx``: SMEM ref (1, T*L) holding
    this sample's row ids for every table; tables ``lo <= t < hi`` are
    served by this part, table t at ``tab[t - lo]``."""

    tab: object
    idx: object
    lo: int
    hi: int
    p: int


def lines_for_kernel(name: str, tables: jax.Array, interpret: bool):
    """(T, R, d) -> ((T, R/p, p*d) lines, p). Natively the line must span
    the 128 lanes: the TPU cannot DMA a narrower slice."""
    _, r, d = tables.shape
    p = rows_per_line(d, r)
    if not interpret and (p * d) % LANES:
        raise ValueError(
            f"{name}: a table of {r} rows of width {d} has no lane-dense "
            f"line layout ({p * d} lanes per line; need a multiple of "
            f"{LANES}). Use d dividing 128 with rows a multiple of "
            f"{LANES // d if d < LANES and LANES % d == 0 else 1}, or d a "
            f"multiple of 128.")
    return to_lines(tables, p), p


def index_spec(T: int, L: int, sample: Callable) -> pl.BlockSpec:
    """One sample's (1, T*L) indices per grid step, in SMEM. The index
    array is passed as `flat_indices` gives it."""
    return pl.BlockSpec((None, 1, T * L),
                        lambda *g: (sample(*g), 0, 0),
                        memory_space=pltpu.SMEM)


def flat_indices(idx: jax.Array) -> jax.Array:
    """(B, T, L) -> (B, 1, T*L): one SMEM row of indices per sample."""
    B, T, L = idx.shape
    return idx.reshape(B, 1, T * L)


def line_scratch(parts_wd: Sequence[tuple], L: int):
    """Two L-line VMEM buffers per part (``(width, dtype)`` each) and one
    DMA semaphore per buffer slot."""
    return ([pltpu.VMEM((2, L, w), dt) for w, dt in parts_wd]
            + [pltpu.SemaphoreType.DMA((2,))])


def _row(line: jax.Array, slot, d: int, p: int) -> jax.Array:
    """(1, p*d) fp32 line -> (1, d) row in lanes [slot*d, slot*d + d).

    The other rows' lanes are zeroed and the p lane groups folded onto
    lanes [0, d) with rotations; exactly one group is non-zero, so the
    fold adds exact zeros."""
    if p == 1:
        return line
    group = jax.lax.broadcasted_iota(jnp.int32, line.shape, 1) // d
    m = jnp.where(group == slot, line, 0.0)
    folded = m
    for q in range(1, p):
        folded = folded + pltpu.roll(m, shift=q * d, axis=1)
    return folded[:, :d]


def gather_pool(parts: Sequence[Part], bufs, sem, *, T: int, L: int, d: int,
                emit: Callable) -> None:
    """Pool every table of one sample: ``emit(t, pooled (1, d) fp32)``.

    Either every part serves every table (the cached layout: one row per
    part, summed, exactly one of them a zero pad), or the parts split the
    tables between them (the grouped layout)."""
    summed = all(pt.lo == 0 and pt.hi == T for pt in parts)

    def each_part(t, fn):
        for k, pt in enumerate(parts):
            if pt.lo == 0 and pt.hi == T:
                fn(k, pt)
            else:
                pl.when(jnp.logical_and(t >= pt.lo, t < pt.hi))(
                    functools.partial(fn, k, pt))

    def start(t, slot):
        def one(k, pt):
            def body(l, c):
                r = pt.idx[0, t * L + l]
                pltpu.make_async_copy(
                    pt.tab.at[t - pt.lo, pl.ds(r // pt.p, 1), :],
                    bufs[k].at[slot, pl.ds(l, 1), :], sem.at[slot]).start()
                return c
            jax.lax.fori_loop(0, L, body, 0)
        each_part(t, one)

    def wait(t, slot):
        def one(k, pt):
            def body(l, c):
                pltpu.make_async_copy(
                    pt.tab.at[0, pl.ds(0, 1), :],
                    bufs[k].at[slot, pl.ds(0, 1), :], sem.at[slot]).wait()
                return c
            jax.lax.fori_loop(0, L, body, 0)
        each_part(t, one)

    def pool(t, slot):
        def body(l, acc):
            rows = []
            for k, pt in enumerate(parts):
                r = pt.idx[0, t * L + l]
                line = bufs[k][slot, pl.ds(l, 1), :].astype(jnp.float32)
                rows.append(_row(line, r % pt.p, d, pt.p))
            if summed:
                row = rows[0]
                for x in rows[1:]:
                    row = row + x
            else:
                row = rows[-1]
                for pt, x in zip(parts[-2::-1], rows[-2::-1]):
                    row = jnp.where(t < pt.hi, x, row)
            return acc + row
        return jax.lax.fori_loop(0, L, body,
                                 jnp.zeros((1, d), jnp.float32))

    start(0, 0)

    def table(t, c):
        slot = t % 2

        @pl.when(t + 1 < T)
        def _prefetch():
            start(t + 1, 1 - slot)

        wait(t, slot)
        emit(t, pool(t, slot))
        return c

    jax.lax.fori_loop(0, T, table, 0)


def _embedding_bag_kernel(idx_ref, tab_ref, out_ref, buf, sem, *, T, L, d,
                          p):
    def emit(t, pooled):
        out_ref[pl.ds(t, 1), :] = pooled

    gather_pool([Part(tab_ref, idx_ref, 0, T, p)], [buf], sem, T=T, L=L,
                d=d, emit=emit)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_pallas(tables: jax.Array, indices: jax.Array,
                         *, interpret: bool = True) -> jax.Array:
    """tables (T, R, d) any float dtype; indices (B, T, L) int32 -> (B, T, d) fp32.

    ``interpret=True`` executes the kernel body on the host (validation
    mode); on TPU pass ``interpret=False``. Pass tables that are stored as
    lines and viewed as rows (`table_layout.to_rows`): the kernel's own
    reshape back to lines then cancels and the table is not copied.
    """
    T, R, d = tables.shape
    B, T2, L = indices.shape
    assert T == T2, (tables.shape, indices.shape)
    lines, p = lines_for_kernel("embedding_bag_pallas", tables, interpret)
    return pl.pallas_call(
        functools.partial(_embedding_bag_kernel, T=T, L=L, d=d, p=p),
        grid=(B,),
        in_specs=[index_spec(T, L, lambda b: b),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, T, d), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, d), jnp.float32),
        scratch_shapes=line_scratch([(p * d, tables.dtype)], L),
        interpret=interpret,
    )(flat_indices(indices), lines)
