"""repro.parallel — the composable distribution stage layer.

Decomposes the former `core/sharding.py` monolith into:

  primitives — shard_map-interior collectives (Alg. 1/2 building blocks)
  plan       — planner placements -> executable table groups + param split
  updates    — sparse optimizer row updates (SGD / row-wise AdaGrad)
  exchange   — `EmbeddingExchange` strategy interface + implementations
               (TableWise / RowWise / PlannedTiered)
  build      — `build_step`: the ONE composition of exchange + dense
               compute + grad stages, with micro-batch pipelining and
               optional int8 error-feedback gradient compression

`core.sharding` re-exports this namespace for backward compatibility.
"""
from repro.parallel.build import (build_step, init_dlrm_opt_state,
                                  init_dlrm_params, init_error_feedback,
                                  param_specs, shard_dlrm_params,
                                  table_rows_per_line)
from repro.parallel.exchange import (EmbeddingExchange, PlannedTieredExchange,
                                     RowWiseExchange, TableWiseExchange,
                                     acc_key, make_exchange, planned_forward)
from repro.parallel.plan import (PlanGroups, merge_dlrm_params_by_plan,
                                 plan_table_groups, reconcile_plan_with_mesh,
                                 split_dlrm_params_by_plan)
from repro.parallel.primitives import (axis_size, row_wise_backward_update,
                                       row_wise_expand_grads,
                                       row_wise_forward,
                                       table_wise_backward_update,
                                       table_wise_expand_grads,
                                       table_wise_forward)
from repro.parallel.updates import adagrad_row_update, sgd_row_update

__all__ = [
    "EmbeddingExchange", "TableWiseExchange", "RowWiseExchange",
    "PlannedTieredExchange", "make_exchange", "acc_key", "planned_forward",
    "build_step", "param_specs", "shard_dlrm_params", "init_dlrm_params",
    "table_rows_per_line", "init_dlrm_opt_state",
    "init_error_feedback",
    "PlanGroups", "plan_table_groups", "reconcile_plan_with_mesh",
    "split_dlrm_params_by_plan", "merge_dlrm_params_by_plan",
    "axis_size", "table_wise_forward", "table_wise_backward_update",
    "table_wise_expand_grads", "row_wise_forward", "row_wise_backward_update",
    "row_wise_expand_grads", "adagrad_row_update", "sgd_row_update",
]
