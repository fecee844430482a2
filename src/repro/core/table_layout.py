"""Lane-dense embedding-table storage ("lines").

A TPU vector register and every HBM tile are 128 lanes wide. A stacked
table of d=32 rows, ``(T, R, 32)`` f32, cannot be stored as it reads: XLA
either pads each row to 128 lanes (4x the bytes) or transposes the table so
that rows run down the lanes (``major_to_minor=(0, 2, 1)``), and the Pallas
gather kernels, which DMA whole rows, then need a relayout copy of the
whole table on every call. A d=32 row is also not a legal DMA unit: a DMA
slice must span the 128 lanes.

So tables narrower than 128 lanes are stored as *lines*: ``p = 128 // d``
consecutive rows share one 128-lane line, ``(T, R, d) -> (T, R/p, p*d)``.
Row ``r`` is line ``r // p``, lanes ``[(r % p)*d, (r % p + 1)*d)``. The
conversion is a row-major reshape, so it moves no bytes on a host and
costs nothing when a line array is reshaped to rows and straight back
inside one program. Where ``R`` is not a multiple of ``p`` (or ``d`` does
not divide 128) a row is its own line (``p = 1``).

Which layout a placed table uses is decided once, at placement
(`repro.parallel.build`): lines on a TPU, rows elsewhere. The XLA paths
below read either, given the row width ``d``; ``p`` is recovered from the
stored width.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def rows_per_line(d: int, rows: int) -> int:
    """Rows of width ``d`` that share one lane-dense line for a table of
    ``rows`` rows: ``128 // d`` when that divides both 128 and ``rows``,
    else 1."""
    p = LANES // d if d < LANES and LANES % d == 0 else 1
    return p if rows % p == 0 else 1


def to_lines(tables: jax.Array, p: int) -> jax.Array:
    """(..., R, d) rows -> (..., R/p, p*d) lines."""
    *lead, r, d = tables.shape
    return tables.reshape(*lead, r // p, p * d)


def to_rows(lines: jax.Array, d: int) -> jax.Array:
    """(..., R/p, p*d) lines -> (..., R, d) rows."""
    *lead, n, w = lines.shape
    return lines.reshape(*lead, n * (w // d), d)


def num_rows(lines: jax.Array, d: int) -> int:
    """Row count of one table stored as ``lines`` (axis -2 holds lines)."""
    return lines.shape[-2] * (lines.shape[-1] // d)


def gather_rows(lines: jax.Array, idx: jax.Array, d: int) -> jax.Array:
    """One table's rows: lines (R/p, p*d), idx (...) int -> (..., d).

    Gathers whole lines, then keeps the d lanes of each row's slot."""
    p = lines.shape[-1] // d
    if p == 1:
        return jnp.take(lines, idx, axis=0)
    got = jnp.take(lines, idx // p, axis=0).reshape(idx.shape + (p, d))
    slot = (idx % p)[..., None, None]
    return jnp.take_along_axis(got, slot, axis=-2)[..., 0, :]


def scatter_add_rows(lines: jax.Array, idx: jax.Array,
                     upd: jax.Array) -> jax.Array:
    """``rows.at[idx].add(upd)`` on lines: lines (R/p, p*d), idx (N,),
    upd (N, d). Each update is widened to its line with exact zeros in the
    other rows' lanes, so every row receives the same additions in the
    same order as the row layout."""
    d = upd.shape[-1]
    p = lines.shape[-1] // d
    if p == 1:
        return lines.at[idx].add(upd)
    hit = (idx % p)[:, None, None] == jnp.arange(p)[None, :, None]
    wide = jnp.where(hit, upd[:, None, :], jnp.zeros((), upd.dtype))
    return lines.at[idx // p].add(wide.reshape(idx.shape[0], p * d))
