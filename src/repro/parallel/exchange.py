"""EmbeddingExchange: one interface per embedding distribution strategy.

An exchange owns everything that depends on WHERE the tables live:

  * the table param layout (which param keys hold tables, and their
    PartitionSpecs over the embedding axis),
  * Alg. 1 forward — indices in, pooled embeddings + a backward context out,
  * Alg. 2 backward — pooled-output grads routed to the row owners and
    expanded to flat (row id, row grad) pairs per table group,
  * the matching sparse-optimizer state layout (AdaGrad accumulators).

`build_step` (repro.parallel.build) composes any exchange with the dense
compute, gradient all-reduce (optionally int8-compressed), and sparse
update stages into one train or serve step — the four hand-written step
factories this layer replaced all become calls into that one composition.

Implementations:
  TableWiseExchange    — paper "unsharded": whole tables per processor.
  RowWiseExchange      — paper "full sharding": rows of every table
                         range-sharded; "partial_pool" or "unpooled" wire
                         modes.
  PlannedTieredExchange— the placement planner's MIXED decision (PR 1
                         hot/cold path): fast-tier tables table_wise,
                         bulk-tier tables row_wise, outputs re-stitched
                         into original table order.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple, Union

import numpy as np

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import DLRMConfig
from repro.core.planner import ShardingPlan
from repro.core.table_layout import to_rows
from repro.parallel import primitives as prim
from repro.parallel.plan import PlanGroups, plan_table_groups

Axis = Union[str, Tuple[str, ...]]
Tables = Dict[str, Any]
FlatGrads = Dict[str, Tuple[Any, Any]]   # key -> (flat_idx (T,N), flat_g (T,N,d))


def acc_key(table_key: str) -> str:
    """Param key -> matching AdaGrad accumulator key
    ("tables" -> "table_acc", "tables_fast" -> "table_acc_fast", ...)."""
    return table_key.replace("tables", "table_acc", 1)


class EmbeddingExchange:
    """Base class; constructed against a concrete (cfg, axis, n)."""

    table_keys: Tuple[str, ...] = ("tables",)

    def __init__(self, cfg: DLRMConfig, axis: Axis, n: int):
        self.cfg = cfg
        self.axis = axis
        self.n = n

    # -- layout ------------------------------------------------------------
    def table_specs(self) -> Dict[str, P]:
        raise NotImplementedError

    def acc_specs(self) -> Dict[str, P]:
        """AdaGrad accumulator specs (shard like the tables' row dims);
        shapes are owned by `build.init_dlrm_opt_state`."""
        raise NotImplementedError

    # -- Alg. 1 / Alg. 2 ---------------------------------------------------
    def forward(self, tables: Tables, indices) -> Tuple[Any, Any]:
        """(B/n, T, L) local indices -> ((B/n, T, d) pooled, backward ctx)."""
        raise NotImplementedError

    def expand_grads(self, tables: Tables, ctx, g_pooled) -> FlatGrads:
        """Route pooled-output grads to row owners; expand to flat pairs."""
        raise NotImplementedError

    # -- fused serve capability --------------------------------------------
    # A LOCAL exchange (all looked-up rows resident on this processor — no
    # collectives in the forward) can run the serve hot path as ONE fused
    # Pallas launch: gather -> VMEM pool accumulator -> interaction
    # contraction (kernels/fused_serve.py), skipping the pooled (B, T, d)
    # HBM round-trip. Distributed and host-tier exchanges keep the composed
    # forward; build_step falls back transparently on this predicate.
    def supports_fused_forward(self) -> bool:
        return False

    def fused_forward(self, tables: Tables, bot_out, indices):
        """(B, d) bottom-MLP output + (B, T, L) local indices -> the
        (B, top_mlp_in) interaction features, fused. Only valid when
        `supports_fused_forward()` is True."""
        raise NotImplementedError(
            f"{type(self).__name__} has no fused serve path")

    def sparse_apply(self, tables: Tables, ctx, g_pooled,
                     update_fn: Callable) -> Tables:
        """Stateless (SGD-style) sparse update applied in place per group.
        Default: expand + update; RowWise overrides with the batch-chunked
        path so pod-scale steps never materialize a (B,T,L,d) grad block."""
        out = dict(tables)
        for k, (fi, fg) in self.expand_grads(tables, ctx, g_pooled).items():
            out[k] = update_fn(tables[k], fi, fg)
        return out

    # -- host-tier session hooks (no-ops for device-resident exchanges) ----
    # An exchange whose tables do NOT entirely live on device (the
    # hoststore's `HostTieredExchange`) needs to see the session's params
    # and every step's indices OUTSIDE jit: to build its param layout, to
    # fault chunks in before the step launches, and to re-attach the
    # donated cache arrays afterwards. Sessions call these hooks
    # unconditionally; device-resident exchanges inherit the no-ops.
    def init_session_params(self, params: Tables, mesh) -> Optional[Tables]:
        """Build + device-place this exchange's param layout from freshly
        initialized params. None means "not handled": the session falls
        back to the standard `shard_dlrm_params` placement."""
        return None

    def begin_batch(self, params: Tables, indices, depth: int,
                    train: bool = False) -> Tuple[Tables, Any]:
        """Called with a step's host-side indices BEFORE the step runs.
        Returns (possibly updated params, an opaque swap plan or None)."""
        return params, None

    def stall_seconds(self, plan, service_s: float) -> float:
        """Modeled seconds of swap stall the step exposes (virtual clock),
        given the plan from `begin_batch` and the measured compute time."""
        return 0.0

    def end_batch(self, params: Tables) -> Tables:
        """Called with the step's RETURNED params (train steps donate their
        inputs — any state the exchange mirrors must re-attach here)."""
        return params


class TableWiseExchange(EmbeddingExchange):
    """Paper "unsharded": each processor owns T/n whole tables; pooled-row
    all-to-alls only (small, latency-bound messages)."""

    def __init__(self, cfg: DLRMConfig, axis: Axis, n: int):
        super().__init__(cfg, axis, n)
        assert cfg.num_tables % n == 0, (cfg.num_tables, n)

    def table_specs(self) -> Dict[str, P]:
        return {"tables": P(self.axis)}

    def acc_specs(self) -> Dict[str, P]:
        return {"table_acc": P(self.axis)}

    def forward(self, tables, indices):
        return prim.table_wise_forward(tables["tables"], indices, self.axis,
                                       self.cfg.embed_dim)

    def expand_grads(self, tables, ctx, g_pooled):
        return {"tables": prim.table_wise_expand_grads(ctx, g_pooled,
                                                       self.axis)}

    def sparse_apply(self, tables, ctx, g_pooled, update_fn):
        return {"tables": prim.table_wise_backward_update(
            tables["tables"], ctx, g_pooled, self.axis, update_fn)}

    def supports_fused_forward(self) -> bool:
        # at n=1 every table is local and the forward has no collectives
        return self.n == 1

    def fused_forward(self, tables, bot_out, indices):
        from repro import kernels
        d = self.cfg.embed_dim
        return kernels.fused_bag_interactions(to_rows(tables["tables"], d),
                                              indices, bot_out)


class RowWiseExchange(EmbeddingExchange):
    """Paper "full sharding": every table's rows range-sharded over the
    axis. `mode` picks the wire format: "partial_pool" (beyond-paper
    reduce-scatter of partial pools) or "unpooled" (paper-faithful)."""

    def __init__(self, cfg: DLRMConfig, axis: Axis, n: int,
                 mode: str = "partial_pool", lookup_chunk: int = 4096):
        super().__init__(cfg, axis, n)
        if mode not in ("partial_pool", "unpooled"):
            raise ValueError(f"unknown row_wise exchange mode {mode!r}")
        assert cfg.rows_per_table % n == 0, (cfg.rows_per_table, n)
        self.mode = mode
        self.lookup_chunk = lookup_chunk

    def table_specs(self) -> Dict[str, P]:
        return {"tables": P(None, self.axis)}

    def acc_specs(self) -> Dict[str, P]:
        return {"table_acc": P(None, self.axis)}

    def forward(self, tables, indices):
        return prim.row_wise_forward(tables["tables"], indices, self.axis,
                                     self.n, self.mode, self.lookup_chunk,
                                     d=self.cfg.embed_dim)

    def expand_grads(self, tables, ctx, g_pooled):
        return {"tables": prim.row_wise_expand_grads(
            tables["tables"], ctx, g_pooled, self.axis)}

    def sparse_apply(self, tables, ctx, g_pooled, update_fn):
        return {"tables": prim.row_wise_backward_update(
            tables["tables"], ctx, g_pooled, self.axis, update_fn,
            self.lookup_chunk)}


def planned_forward(tables_fast, tables_bulk, indices_local, axis: Axis,
                    mesh_n: int, exchange: str, groups: PlanGroups,
                    lookup_chunk: int = 4096, d: Optional[int] = None,
                    ) -> Tuple[Any, Optional[Any], Optional[Any]]:
    """Mixed-mode Alg. 1 executing the planner's placements: fast-tier
    tables table_wise, bulk-tier tables row_wise, pooled outputs re-stitched
    into the original table order.

    tables_fast : (Tf/n, R, d) this processor's whole fast tables
    tables_bulk : (Tb, R/n, d) a row range of every bulk table
    indices_local: (B/n, T, L) all tables, original order
    returns pooled (B/n, T, d), fast ctx (owner indices), bulk ctx (idx_all).
    """
    parts = []
    ctx_fast = ctx_bulk = None
    if groups.fast_ids:
        idx_f = indices_local[:, np.asarray(groups.fast_ids, np.int32), :]
        pooled_f, ctx_fast = prim.table_wise_forward(tables_fast, idx_f, axis,
                                                     d)
        parts.append(pooled_f)
    if groups.bulk_ids:
        idx_b = indices_local[:, np.asarray(groups.bulk_ids, np.int32), :]
        pooled_b, ctx_bulk = prim.row_wise_forward(tables_bulk, idx_b, axis,
                                                   mesh_n, exchange,
                                                   lookup_chunk, d=d)
        parts.append(pooled_b)
    pooled = jnp.concatenate(parts, axis=1)
    pooled = pooled[:, np.asarray(groups.inv_perm, np.int32), :]
    return pooled, ctx_fast, ctx_bulk


class PlannedTieredExchange(EmbeddingExchange):
    """The planner's tier decisions EXECUTED: fast tables table_wise, bulk
    tables row_wise (PR 1's hot/cold path), under one exchange interface."""

    table_keys = ("tables_fast", "tables_bulk")

    def __init__(self, cfg: DLRMConfig, axis: Axis, n: int,
                 plan: ShardingPlan, row_mode: str = "partial_pool",
                 lookup_chunk: int = 4096):
        super().__init__(cfg, axis, n)
        self.groups = plan_table_groups(plan, n)
        if self.groups.bulk_ids:
            assert cfg.rows_per_table % n == 0, (cfg.rows_per_table, n)
        self.row_mode = row_mode
        self.lookup_chunk = lookup_chunk
        self._fast_arr = np.asarray(self.groups.fast_ids, np.int32)
        self._bulk_arr = np.asarray(self.groups.bulk_ids, np.int32)
        # concat(fast, bulk) table order for the fused grouped kernel
        self._perm_arr = np.asarray(
            self.groups.fast_ids + self.groups.bulk_ids, np.int32)

    def table_specs(self) -> Dict[str, P]:
        g = self.groups
        return {"tables_fast": P(self.axis) if g.fast_ids else P(),
                "tables_bulk": P(None, self.axis) if g.bulk_ids else P()}

    def acc_specs(self) -> Dict[str, P]:
        g = self.groups
        return {"table_acc_fast": P(self.axis) if g.fast_ids else P(),
                "table_acc_bulk": P(None, self.axis) if g.bulk_ids else P()}

    def forward(self, tables, indices):
        pooled, ctx_f, ctx_b = planned_forward(
            tables["tables_fast"], tables["tables_bulk"], indices,
            self.axis, self.n, self.row_mode, self.groups,
            self.lookup_chunk, d=self.cfg.embed_dim)
        return pooled, (ctx_f, ctx_b)

    def supports_fused_forward(self) -> bool:
        # both tiers are whole-table local at n=1 (table_wise fast group,
        # full row range of every bulk table) — no forward collectives
        return self.n == 1

    def fused_forward(self, tables, bot_out, indices):
        from repro import kernels
        d = self.cfg.embed_dim
        idx_perm = indices[:, self._perm_arr, :]
        return kernels.fused_grouped_bag_interactions(
            to_rows(tables["tables_fast"], d),
            to_rows(tables["tables_bulk"], d), idx_perm, bot_out,
            inv_perm=self.groups.inv_perm)

    def _split_g(self, g_pooled):
        g = self.groups
        g_f = g_pooled[:, self._fast_arr, :] if g.fast_ids else None
        g_b = g_pooled[:, self._bulk_arr, :] if g.bulk_ids else None
        return g_f, g_b

    def expand_grads(self, tables, ctx, g_pooled):
        ctx_f, ctx_b = ctx
        g_f, g_b = self._split_g(g_pooled)
        out: FlatGrads = {}
        if self.groups.fast_ids:
            out["tables_fast"] = prim.table_wise_expand_grads(
                ctx_f, g_f, self.axis)
        if self.groups.bulk_ids:
            out["tables_bulk"] = prim.row_wise_expand_grads(
                tables["tables_bulk"], ctx_b, g_b, self.axis)
        return out

    def sparse_apply(self, tables, ctx, g_pooled, update_fn):
        ctx_f, ctx_b = ctx
        g_f, g_b = self._split_g(g_pooled)
        out = dict(tables)
        if self.groups.fast_ids:
            out["tables_fast"] = prim.table_wise_backward_update(
                tables["tables_fast"], ctx_f, g_f, self.axis, update_fn)
        if self.groups.bulk_ids:
            out["tables_bulk"] = prim.row_wise_backward_update(
                tables["tables_bulk"], ctx_b, g_b, self.axis, update_fn,
                self.lookup_chunk)
        return out


def make_exchange(cfg: DLRMConfig, axis: Axis, n: int, *,
                  plan: Optional[ShardingPlan] = None,
                  row_wise_exchange: str = "partial_pool",
                  lookup_chunk: int = 4096) -> EmbeddingExchange:
    """Resolve the exchange for a config + optional placed plan: a placed
    plan dictates the mixed tiered exchange; otherwise cfg.sharding picks
    table_wise or row_wise (with `row_wise_exchange` as the wire mode)."""
    if plan is not None and plan.placements:
        return PlannedTieredExchange(cfg, axis, n, plan,
                                     row_mode=row_wise_exchange,
                                     lookup_chunk=lookup_chunk)
    if cfg.sharding == "table_wise":
        return TableWiseExchange(cfg, axis, n)
    return RowWiseExchange(cfg, axis, n, mode=row_wise_exchange,
                           lookup_chunk=lookup_chunk)
