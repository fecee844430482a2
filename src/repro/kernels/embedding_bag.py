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
flight while table t is pooled, and one wait for the bytes of all L lines
ends each table's DMAs.

A table is pooled with whole-vreg operations. Its buffer is
``(p, L, p*d)``: lookup l of row r lands in cell ``[r % p, l]``, and every
other cell is zero (the buffer is zeroed before the table's DMAs start).
Keeping plane q's lane group ``[q*d, (q+1)*d)`` and folding the p groups
onto lanes ``[0, d)`` with rotations leaves lookup l's row in line l, and
one fp32 sublane sum over the L lines pools them. Each cell is written by
one lookup only, so a row repeated in a bag, or two rows of one line, are
each counted once per lookup. Numerics: fp32 sums as a sublane reduction,
whose order depends on the lookups' positions l only, not on the rows'
slots, lines or parts: the same L rows pool to the same bits through any
table. Against a sum in L order the pool differs by rounding only.

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


def line_scratch(parts_pd: Sequence[tuple], L: int, d: int):
    """Two ``(p, L, p*d)`` VMEM line buffers per part (``(p, dtype)``
    each) and one DMA semaphore per buffer slot."""
    return ([pltpu.VMEM((2, p, L, p * d), dt) for p, dt in parts_pd]
            + [pltpu.SemaphoreType.DMA((2,))])


def _rows(buf, slot, d: int) -> jax.Array:
    """``buf[slot]``, (p, L, p*d) slot-placed lines -> (L, p*d) fp32 with
    lookup l's row in lanes [0, d).

    Plane q holds the lines of the lookups whose row sits in slot q, zero
    elsewhere, so keeping each plane's own lane group leaves every line
    with its one row; the p groups then fold onto lanes [0, d) with
    rotations, adding exact zeros."""
    p = buf.shape[1]
    rows = buf[slot, 0].astype(jnp.float32)
    if p == 1:
        return rows
    group = jax.lax.broadcasted_iota(jnp.int32, (1, p * d), 1) // d
    for q in range(1, p):
        rows = jnp.where(group == q, buf[slot, q].astype(jnp.float32), rows)
    folded = rows
    for q in range(1, p):
        folded = folded + pltpu.roll(rows, shift=q * d, axis=1)
    return folded


# DMA starts issued per loop step: the scalar work of issuing a lookup's
# line (an SMEM read, a shift, a mask, the descriptor) is the kernel's floor,
# and a rolled loop adds its own branch and counter to each one.
_UNROLL = 8


def gather_pool(parts: Sequence[Part], bufs, sem, *, T: int, L: int, d: int,
                emit: Callable) -> None:
    """Pool every table of one sample: ``emit(t, pooled (1, d) fp32)``.

    Either every part serves every table (the cached layout: one row per
    part, summed, exactly one of them a zero pad), or the parts split the
    tables between them (the grouped layout). Either way the parts' pools
    add up: a part that serves no lookup of a table leaves its buffer as
    zeroed."""

    serves_all = [pt.lo == 0 and pt.hi == T for pt in parts]

    def each_part(t, fn):
        for k, pt in enumerate(parts):
            if serves_all[k]:
                fn(k, pt)
            else:
                pl.when(jnp.logical_and(t >= pt.lo, t < pt.hi))(
                    functools.partial(fn, k, pt))

    def start(t, slot):
        # every cell a lookup does not write has to read zero; with one
        # slot per line and every table served, each lookup writes its cell
        for k, pt in enumerate(parts):
            if pt.p > 1 or not serves_all[k]:
                bufs[k][slot] = jnp.zeros(bufs[k].shape[1:], bufs[k].dtype)

        def one(k, pt):
            shift = pt.p.bit_length() - 1         # p is a power of two

            def issue(l):
                r = pt.idx[0, t * L + l]
                pltpu.make_async_copy(
                    pt.tab.at[t - pt.lo, pl.ds(r >> shift, 1), :],
                    bufs[k].at[slot, r & (pt.p - 1), pl.ds(l, 1), :],
                    sem.at[slot]).start()

            def unrolled(i, c):
                for u in range(_UNROLL):
                    issue(i * _UNROLL + u)
                return c
            jax.lax.fori_loop(0, L // _UNROLL, unrolled, 0)
            for l in range(L - L % _UNROLL, L):
                issue(l)
        each_part(t, one)

    def wait(t, slot):
        # one wait for the bytes of all L lines of the table's plane
        def one(k, pt):
            plane = bufs[k].at[slot, 0]
            pltpu.make_async_copy(plane, plane, sem.at[slot]).wait()
        each_part(t, one)

    def pool(slot):
        # the parts' rows add first, so the pool of a lookup's row is the
        # same whichever part, slot or line it came from
        rows = _rows(bufs[0], slot, d)[:, :d]
        for buf in bufs[1:]:
            rows = rows + _rows(buf, slot, d)[:, :d]
        return rows.sum(axis=0, keepdims=True)

    start(0, 0)

    def table(t, c):
        slot = t % 2

        @pl.when(t + 1 < T)
        def _prefetch():
            start(t + 1, 1 - slot)

        wait(t, slot)
        emit(t, pool(slot))
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
        scratch_shapes=line_scratch([(p, tables.dtype)], L, d),
        interpret=interpret,
    )(flat_indices(indices), lines)
