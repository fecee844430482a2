"""Collective primitives for distributed DLRM — paper Algorithms 1 & 2.

These are the raw shard_map-interior building blocks the exchange layer
(`repro.parallel.exchange`) composes: table-wise and row-wise forward
lookup+exchange, and the matching backward gradient routing. All functions
run INSIDE `shard_map` with an axis (or tuple of axes — e.g.
("pod","data","model") on the production mesh, treated as one flattened
processor group, the paper's "no parameters are replicated").

Sharding strategies (paper Sec. IV-A):

  table_wise ("unsharded" in the paper): each processor owns T/n whole
    tables. Forward: all-to-all of indices (batch-major -> table-major),
    local lookup + pool, all-to-all of POOLED rows back (table-major ->
    batch-major). Small, latency-bound messages.

  row_wise ("full sharding"): every table's rows are range-sharded over all
    processors. Two exchange modes:
      * "partial_pool" (default; beyond-paper optimization): each processor
        sum-pools the rows it owns per (sample, table) — legal because sum
        pooling is associative — then a single psum_scatter over the batch
        finishes the pool AND scatters sample-shards. Wire bytes
        B*T*e*(n-1)/n, an L/n-fold reduction over the paper's unpooled
        exchange.
      * "unpooled" (paper-faithful semantics): the unpooled (B,T,L,d) row
        tensor is reduce-scattered over the batch and pooled at the home
        processor — the paper's "exchange of unpooled embeddings".

Backward (Alg. 2): gradients w.r.t. pooled outputs are routed back to row
owners (all-to-all for table_wise; all-gather for row_wise — exactly the
paper's two cases), expanded to every looked-up row (`expand_sparse_grads`)
and scatter-added. The dense (T,R,d) embedding gradient is NEVER
materialized.

Local tables may be stored as rows or as lane-dense lines
(`core/table_layout.py`); the forward functions take the row width ``d``
(default: the stored width, i.e. rows).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from jax.sharding import Mesh

from repro.core import dlrm as dlrm_lib
from repro.core.table_layout import gather_rows, num_rows

Axis = Union[str, Tuple[str, ...]]


def axis_size(mesh: Mesh, axis: Axis) -> int:
    if isinstance(axis, str):
        return mesh.shape[axis]
    n = 1
    for a in axis:
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# Table-wise (paper "unsharded") exchange
# ---------------------------------------------------------------------------
def table_wise_forward(tables_local: jax.Array, indices_local: jax.Array,
                       axis: Axis, d: Optional[int] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Alg. 1, no_sharding branch.

    tables_local : (T/n, R, d) — this processor's whole tables
    indices_local: (B/n, T, L) — this processor's batch slice, all tables
    returns      : pooled (B/n, T, d), owner_indices (B, T/n, L) — the
                   indices this processor looked up (needed again in bwd).
    """
    # indices all-to-all: batch-major -> table-major
    owner_idx = jax.lax.all_to_all(indices_local, axis, split_axis=1,
                                   concat_axis=0, tiled=True)   # (B, T/n, L)
    pooled_owner = dlrm_lib.embedding_bag(tables_local, owner_idx, d)  # (B, T/n, d)
    # pooled-embedding all-to-all: table-major -> batch-major
    pooled = jax.lax.all_to_all(pooled_owner, axis, split_axis=0,
                                concat_axis=1, tiled=True)      # (B/n, T, d)
    return pooled, owner_idx


def table_wise_backward_update(
    tables_local: jax.Array, owner_idx: jax.Array, g_pooled_local: jax.Array,
    axis: Axis, update_fn: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
) -> jax.Array:
    """Alg. 2, no_sharding branch: route pooled grads to owners, expand, update.

    g_pooled_local: (B/n, T, d) grads w.r.t. this processor's pooled outputs.
    update_fn(tables_local, flat_idx (T/n, N), flat_g (T/n, N, d)) applies the
    sparse row update (SGD / AdaGrad — optimizer-specific).
    """
    flat_idx, flat_g = table_wise_expand_grads(owner_idx, g_pooled_local, axis)
    return update_fn(tables_local, flat_idx, flat_g)


def table_wise_expand_grads(ctx: jax.Array, g_pooled: jax.Array, axis: Axis
                            ) -> Tuple[jax.Array, jax.Array]:
    """Alg. 2 no_sharding grad routing: pooled grads -> owners, expanded to
    every looked-up row. Returns (flat_idx (T/n, N), flat_g (T/n, N, d))."""
    g_owner = jax.lax.all_to_all(g_pooled, axis, 1, 0, tiled=True)
    B, Tn, L = ctx.shape
    g_rows = jnp.broadcast_to(g_owner[:, :, None, :],
                              (B, Tn, L, g_owner.shape[-1]))
    flat_idx = ctx.transpose(1, 0, 2).reshape(Tn, B * L)
    flat_g = g_rows.transpose(1, 0, 2, 3).reshape(Tn, B * L, -1)
    return flat_idx, flat_g


# ---------------------------------------------------------------------------
# Row-wise (paper "full sharding") exchange
# ---------------------------------------------------------------------------
def _divisor_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (>= 1)."""
    c = max(1, min(n, target))
    while n % c:
        c -= 1
    return c


def _masked_rows(tables_local: jax.Array, idx: jax.Array,
                 r_start: jax.Array, d: Optional[int] = None) -> jax.Array:
    """Gather locally-owned rows (zeros elsewhere). idx (B', T, L) global ids
    -> (B', T, L, d)."""
    d = d or tables_local.shape[-1]
    rows_local = num_rows(tables_local, d)
    local = idx - r_start
    mine = (local >= 0) & (local < rows_local)
    safe = jnp.where(mine, local, 0)

    def gather_table(tab, i, m):           # (R/n rows), (B',L), (B',L)
        rows = gather_rows(tab, i, d)                        # (B', L, d)
        return rows * m[..., None].astype(rows.dtype)
    return jax.vmap(gather_table, in_axes=(0, 1, 1), out_axes=1)(
        tables_local, safe, mine)                            # (B', T, L, d)


def _masked_partial_pool(tables_local: jax.Array, idx: jax.Array,
                         r_start: jax.Array, d: Optional[int] = None
                         ) -> jax.Array:
    """Partial sum-pool of locally-owned rows. idx (B', T, L) global ids ->
    (B', T, d) partial pools (zeros for rows owned elsewhere)."""
    return _masked_rows(tables_local, idx, r_start, d).sum(axis=2)


def row_wise_forward(tables_local: jax.Array, indices_local: jax.Array,
                     axis: Axis, mesh_n: int,
                     exchange: str = "partial_pool",
                     lookup_chunk: int = 4096, d: Optional[int] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """Alg. 1, full_sharding branch.

    tables_local : (T, R/n, d) — a row range of EVERY table
    indices_local: (B/n, T, L) — GLOBAL row ids
    returns      : pooled (B/n, T, d), gathered global indices (B, T, L)

    At pod scale the gathered batch B is large, so the masked lookup runs in
    batch CHUNKS of `lookup_chunk` samples — the (chunk, T, L, d) unpooled
    row block is the only L-sized tensor ever live (the partial pools
    accumulate per chunk), keeping VMEM/HBM pressure flat in B.
    """
    d = d or tables_local.shape[-1]
    rows_local = num_rows(tables_local, d)
    rank = jax.lax.axis_index(axis)
    r_start = rank * rows_local

    # Index exchange: every owner needs the full batch's indices.
    idx_all = jax.lax.all_gather(indices_local, axis, axis=0, tiled=True)  # (B,T,L)
    B, T, L = idx_all.shape

    if exchange == "unpooled":
        # Paper-faithful: ship UNPOOLED rows; pool at the home processor.
        # Chunked over each rank's output slots so only a (n·C', T, L, d)
        # row block is ever live — wire bytes are unchanged (B·T·L·e/n per
        # chip either way, the paper's full-sharding stress case).
        Bn = B // mesh_n
        Cp = _divisor_chunk(Bn, max(1, lookup_chunk // mesh_n))
        if Bn == Cp:
            rows = _masked_rows(tables_local, idx_all, r_start, d)  # (B,T,L,d)
            unpooled = jax.lax.psum_scatter(rows, axis, scatter_dimension=0,
                                            tiled=True)           # (B/n,T,L,d)
            return unpooled.sum(axis=2), idx_all
        idx_r = idx_all.reshape(mesh_n, Bn, T, L)

        def chunk_body(_, k):
            idx_c = jax.lax.dynamic_slice_in_dim(
                idx_r, k * Cp, Cp, axis=1).reshape(mesh_n * Cp, T, L)
            rows = _masked_rows(tables_local, idx_c, r_start, d)  # (nC',T,L,d)
            unpooled_c = jax.lax.psum_scatter(
                rows, axis, scatter_dimension=0, tiled=True)      # (C',T,L,d)
            return None, unpooled_c.sum(axis=2)                   # pool over L

        _, pooled_chunks = jax.lax.scan(chunk_body, None,
                                        jnp.arange(Bn // Cp))
        return pooled_chunks.reshape(Bn, T, d), idx_all

    # partial_pool (beyond-paper): pool owned rows locally, reduce-scatter.
    if B <= lookup_chunk:
        partial = _masked_partial_pool(tables_local, idx_all, r_start, d)
    else:
        chunk = _divisor_chunk(B, lookup_chunk)
        chunks = idx_all.reshape(B // chunk, chunk, T, L)
        partial = jax.lax.map(
            lambda ic: _masked_partial_pool(tables_local, ic, r_start, d),
            chunks).reshape(B, T, d)

    pooled = jax.lax.psum_scatter(partial, axis, scatter_dimension=0,
                                  tiled=True)                     # (B/n, T, d)
    return pooled, idx_all


def row_wise_expand_grads(tables_local: jax.Array, ctx: jax.Array,
                          g_pooled: jax.Array, axis: Axis
                          ) -> Tuple[jax.Array, jax.Array]:
    """Alg. 2 full_sharding grad routing: all-gather pooled grads, mask to
    locally-owned rows. Returns (flat_idx (T, N), flat_g (T, N, d))."""
    rows_local = num_rows(tables_local, g_pooled.shape[-1])
    rank = jax.lax.axis_index(axis)
    r_start = rank * rows_local
    g_all = jax.lax.all_gather(g_pooled, axis, axis=0, tiled=True)
    B, T, L = ctx.shape
    local = ctx - r_start
    mine = (local >= 0) & (local < rows_local)
    safe = jnp.where(mine, local, 0)
    g_rows = jnp.broadcast_to(g_all[:, :, None, :], (B, T, L, g_all.shape[-1]))
    g_rows = g_rows * mine[..., None].astype(g_rows.dtype)
    flat_idx = safe.transpose(1, 0, 2).reshape(T, B * L)
    flat_g = g_rows.transpose(1, 0, 2, 3).reshape(T, B * L, -1)
    return flat_idx, flat_g


def row_wise_backward_update(
    tables_local: jax.Array, idx_all: jax.Array, g_pooled_local: jax.Array,
    axis: Axis,
    update_fn: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
    lookup_chunk: int = 4096,
) -> jax.Array:
    """Alg. 2, full_sharding branch: all-gather pooled grads, expand to the
    locally-owned rows, scatter-add. Chunked over the batch like the forward
    (the expanded (chunk, T, L, d) grad block is the only L-sized tensor)."""
    rows_local = num_rows(tables_local, g_pooled_local.shape[-1])
    rank = jax.lax.axis_index(axis)
    r_start = rank * rows_local

    g_all = jax.lax.all_gather(g_pooled_local, axis, axis=0, tiled=True)  # (B,T,d)
    B, T, L = idx_all.shape

    def one_chunk(tables, idx_c, g_c):
        # Layout discipline (§Perf iter 6): transpose/cast the SMALL pooled
        # grad (Bc, T, d) BEFORE the L-fold expansion, so the only L-sized
        # tensor is the bf16 scatter operand itself — not an f32 copy chain.
        Bc = idx_c.shape[0]
        d = g_c.shape[-1]
        local = idx_c - r_start
        mine = (local >= 0) & (local < rows_local)
        safe = jnp.where(mine, local, 0)
        g_t = g_c.transpose(1, 0, 2).astype(tables.dtype)     # (T, Bc, d)
        g_rows = jnp.broadcast_to(g_t[:, :, None, :], (T, Bc, L, d))
        mine_t = mine.transpose(1, 0, 2)                       # (T, Bc, L)
        g_rows = g_rows * mine_t[..., None].astype(g_rows.dtype)
        flat_idx = safe.transpose(1, 0, 2).reshape(T, Bc * L)
        flat_g = g_rows.reshape(T, Bc * L, d)
        return update_fn(tables, flat_idx, flat_g)

    if B <= lookup_chunk:
        return one_chunk(tables_local, idx_all, g_all)
    chunk = _divisor_chunk(B, lookup_chunk)
    nc = B // chunk
    idx_c = idx_all.reshape(nc, chunk, T, L)
    g_c = g_all.reshape(nc, chunk, T, -1)

    def body(tables, inp):
        ic, gc = inp
        return one_chunk(tables, ic, gc), None
    tables, _ = jax.lax.scan(body, tables_local, (idx_c, g_c))
    return tables
