"""build_step: ONE composition of exchange + dense compute + grad stages.

Every DLRM serve/train step in the repo is assembled here (the four
hand-written factories that used to live in `core/sharding.py` are now thin
shims over this function). The step is a stage pipeline running inside one
`shard_map`:

      indices ──► [EmbeddingExchange.forward]──► pooled ─► [dense MLP] ─► loss
                      ▲ sparse all-to-all /                      │
                      │ reduce-scatter          value_and_grad   ▼
      tables ◄── [sparse update stage] ◄── [grad routing] ◄── g_pooled
                                            [dense all-reduce (fp32 | int8+EF)]

Micro-batch pipelining (`pipeline_depth=k`): the per-device batch is split
into k micro-batches and the schedule is software-pipelined — the
embedding exchange for micro-batch i+1 is ISSUED before the dense compute
of micro-batch i, so XLA's async collectives can overlap exchange wire
time with MLP FLOPs (the paper's Fig. 12/13 overlap axis, executed instead
of just modeled). Gradient routing for micro-batch i likewise overlaps the
compute of micro-batch i+1. Every depth is numerically equivalent to the
serial step: SGD scatter-adds commute, so they apply per micro-batch
through the exchange's batch-chunked path (memory stays chunk-bounded);
AdaGrad's accumulator must see the full batch's row multiset at once, so
its flat grads are concatenated and applied in one update.

Named scopes: the stages run under `jax.named_scope`s that XLA keeps in
every op's metadata (`op_name`), so a device trace's ops map back to a
layer: ``dlrm.sparse`` (the exchange's forward, fused or composed),
``dlrm.bottom_mlp`` / ``dlrm.interaction`` / ``dlrm.top_mlp`` (the dense
model, forward and backward), ``dlrm.sparse_update`` (sparse grad routing
and the table update) and ``dlrm.dense_update`` (the dense all-reduce and
SGD). They are metadata only: the compiled program is otherwise the same.

Dense-grad compression (`compress_grads=True`): the dense all-reduce stage
runs the int8 block-quantized compressor (`optim/compression.py`) with
persistent per-device error-feedback state carried in the opt state
(leaves shaped (n_devices, *param_shape), sharded over the step axes).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import DLRMConfig
from repro.core import dlrm as dlrm_lib
from repro.core.planner import ShardingPlan
from repro.core.table_layout import rows_per_line, to_lines
from repro.optim.compression import make_compressed_allreduce
from repro.parallel.exchange import (EmbeddingExchange, acc_key,
                                     make_exchange)
from repro.parallel.plan import (PlanGroups, plan_table_groups,
                                 split_dlrm_params_by_plan)
from repro.parallel.primitives import axis_size
from repro.parallel.updates import adagrad_row_update, sgd_row_update

Axis = Union[str, Tuple[str, ...]]
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Param / opt-state layout
# ---------------------------------------------------------------------------
def _mlp_specs(cfg: DLRMConfig):
    return ([{"w": P(), "b": P()} for _ in cfg.bot_mlp_dims],
            [{"w": P(), "b": P()} for _ in cfg.top_mlp])


def param_specs(cfg: DLRMConfig, axis: Axis,
                groups: Optional[PlanGroups] = None) -> Dict[str, Any]:
    """PartitionSpecs for DLRM params under the given strategy.

    With `groups` (plan execution) the tables are split per tier:
    fast tables table-sharded over the axis, bulk tables row-sharded.
    An empty group's (0, R, d) array is replicated (nothing to shard)."""
    ax = axis
    mlp_spec, top_spec = _mlp_specs(cfg)
    if groups is not None:
        return {"bot_mlp": mlp_spec, "top_mlp": top_spec,
                "tables_fast": P(ax) if groups.fast_ids else P(),
                "tables_bulk": P(None, ax) if groups.bulk_ids else P()}
    tables = P(ax) if cfg.sharding == "table_wise" else P(None, ax)
    return {"bot_mlp": mlp_spec, "top_mlp": top_spec, "tables": tables}


def table_rows_per_line(cfg: DLRMConfig, mesh: Mesh, axis: Axis) -> int:
    """Storage layout of placed tables (`core/table_layout.py`): lane-dense
    lines of ``p`` rows on a TPU mesh, rows (``p = 1``) elsewhere. A
    row-sharded table keeps whole lines on each device."""
    if mesh.devices.flat[0].platform != "tpu":
        return 1
    return rows_per_line(cfg.embed_dim,
                         cfg.rows_per_table // axis_size(mesh, axis))


def _placement(cfg: DLRMConfig, mesh: Mesh, axis: Axis,
               plan: Optional[ShardingPlan]):
    groups = None
    if plan is not None and plan.placements:
        groups = plan_table_groups(plan, axis_size(mesh, axis))
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(cfg, axis, groups),
        is_leaf=lambda x: isinstance(x, P))
    return groups, shardings, table_rows_per_line(cfg, mesh, axis)


def _layout_tables(params: Params, groups: Optional[PlanGroups],
                   p: int, d: int) -> Params:
    """Split stacked tables into the plan's groups and store every table
    key in the placement's layout (tables already in lines pass through)."""
    if groups is not None and "tables" in params:
        params = split_dlrm_params_by_plan(params, groups)
    return {k: (to_lines(v, p) if k.startswith("tables") and p > 1
                and v.shape[-1] == d else v)
            for k, v in params.items()}


def shard_dlrm_params(params: Params, cfg: DLRMConfig, mesh: Mesh,
                      axis: Axis, plan: Optional[ShardingPlan] = None
                      ) -> Params:
    """Device-place DLRM params. With a placed `plan`, stacked params are
    first split into the plan's fast/bulk table groups; tables are stored
    in the mesh's layout (`table_rows_per_line`)."""
    groups, shardings, p = _placement(cfg, mesh, axis, plan)
    params = _layout_tables(params, groups, p, cfg.embed_dim)
    return jax.tree_util.tree_map(jax.device_put, params, shardings)


def init_dlrm_params(key: jax.Array, cfg: DLRMConfig, mesh: Mesh,
                     axis: Axis, plan: Optional[ShardingPlan] = None
                     ) -> Params:
    """`dlrm_lib.init_dlrm`, built placed: one jitted program writes every
    param straight into its sharding and layout, so no device ever holds
    more than its own share of the tables. Same values as
    ``shard_dlrm_params(init_dlrm(key, cfg), ...)``."""
    groups, shardings, p = _placement(cfg, mesh, axis, plan)
    return jax.jit(
        lambda k: _layout_tables(dlrm_lib.init_dlrm(k, cfg), groups, p,
                                 cfg.embed_dim),
        out_shardings=shardings)(key)


def _dense_param_abstract(cfg: DLRMConfig) -> Dict[str, Any]:
    """Abstract (ShapeDtypeStruct) dense-param subtree, derived from the
    real initializer so the error-feedback tree can never drift from the
    gradient tree's structure."""
    abs_p = jax.eval_shape(
        functools.partial(dlrm_lib.init_dlrm, cfg=cfg),
        jax.random.PRNGKey(0))
    return {"bot_mlp": abs_p["bot_mlp"], "top_mlp": abs_p["top_mlp"]}


def init_error_feedback(cfg: DLRMConfig, n_devices: int) -> Params:
    """Per-device error-feedback residuals for the compressed dense-grad
    all-reduce: one fp32 copy of each dense param PER device, carried in the
    opt state (leading dim sharded over the step's axes)."""
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros((n_devices,) + s.shape, jnp.float32),
        _dense_param_abstract(cfg))


def init_dlrm_opt_state(cfg: DLRMConfig, optimizer: str,
                        plan: Optional[ShardingPlan] = None,
                        n: Optional[int] = None,
                        compress_grads: bool = False,
                        n_devices: Optional[int] = None) -> Optional[Params]:
    """Optimizer-state pytree matching `build_step`'s expectations.

    AdaGrad carries per-row fp32 accumulators, split per tier when a placed
    plan drives the step (`n` — the embedding-axis size the step was built
    with — is REQUIRED then, since group sizes depend on it). With
    `compress_grads` an "ef" subtree of per-device error-feedback residuals
    is added; `n_devices` must be the TOTAL device count the step shards
    over (the `dp_axes + axis` product — falls back to `n`, which is only
    correct when the step has no extra dp_axes). Plain SGD without
    compression keeps the historical `None` state."""
    state: Params = {}
    if optimizer == "adagrad":
        if plan is None or not plan.placements:
            state["table_acc"] = jnp.zeros(
                (cfg.num_tables, cfg.rows_per_table), jnp.float32)
        else:
            if n is None:
                raise ValueError(
                    "init_dlrm_opt_state needs the embedding-axis size `n` "
                    "when a placed plan is given (the fast/bulk group split "
                    "depends on it)")
            groups = plan_table_groups(plan, n)
            state["table_acc_fast"] = jnp.zeros(
                (len(groups.fast_ids), cfg.rows_per_table), jnp.float32)
            state["table_acc_bulk"] = jnp.zeros(
                (len(groups.bulk_ids), cfg.rows_per_table), jnp.float32)
    if compress_grads:
        nd = n_devices if n_devices is not None else n
        if nd is None:
            raise ValueError("init_dlrm_opt_state needs `n_devices` (or `n`) "
                             "with compress_grads=True")
        state["ef"] = init_error_feedback(cfg, nd)
    return state or None


# ---------------------------------------------------------------------------
# Stage helpers (run INSIDE shard_map)
# ---------------------------------------------------------------------------
def _mb_slices(x: jax.Array, depth: int):
    b = x.shape[0]
    if b % depth:
        raise ValueError(
            f"pipeline_depth={depth} must divide the per-device batch "
            f"({b} local samples); pad the batch or lower the depth")
    m = b // depth
    return [x[i * m:(i + 1) * m] for i in range(depth)]


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _concat_flat_grads(per_mb) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """Concatenate per-micro-batch flat sparse grads along the N axis, per
    table group — equivalent to the serial step's full-batch expansion (the
    scatter-add and the AdaGrad accumulator see the same row multiset)."""
    if len(per_mb) == 1:
        return per_mb[0]
    out = {}
    for k in per_mb[0]:
        out[k] = (jnp.concatenate([f[k][0] for f in per_mb], axis=1),
                  jnp.concatenate([f[k][1] for f in per_mb], axis=1))
    return out


# ---------------------------------------------------------------------------
# The one step factory
# ---------------------------------------------------------------------------
def build_step(
    cfg: DLRMConfig,
    mesh: Mesh,
    *,
    mode: str = "train",
    axis: Axis = ("data", "model"),
    plan: Optional[ShardingPlan] = None,
    exchange: Union[str, EmbeddingExchange] = "partial_pool",
    optimizer: str = "sgd",
    lr: float = 0.01,
    dp_axes: Tuple[str, ...] = (),
    pipeline_depth: int = 1,
    compress_grads: bool = False,
    lookup_chunk: int = 4096,
    fused: bool = True,
) -> Callable:
    """Compose exchange + dense compute + grad/optimizer stages into one
    jitted step.

    mode="train": step(params, opt_state, dense, indices, labels)
                  -> (params, opt_state, loss)
    mode="serve": step(params, dense, indices) -> probs (B,)

    `axis` is the EMBEDDING (table/row) distribution axis; `dp_axes` are
    extra pure data-parallel axes across which the tables are REPLICATED
    (the planner's fast/hot tier at pod scale). The batch shards over
    `dp_axes + axis`; dense grads all-reduce over all of them; table updates
    are additionally psum'd over `dp_axes` to keep replicas identical.

    `exchange` is an `EmbeddingExchange` instance, or a row-wise wire-mode
    string resolved via `make_exchange` (a placed `plan` always selects the
    tiered exchange). `pipeline_depth`/`compress_grads`: see module doc.

    `fused` (serve mode only): run the forward through the exchange's
    fused gather->pool->interaction megakernel when it supports one
    (`EmbeddingExchange.supports_fused_forward` — local TableWise /
    PlannedTiered exchanges). Distributed and host-tier exchanges fall
    back to the composed kernels transparently; pass `fused=False` to
    force the composed path everywhere.
    """
    if mode not in ("train", "serve"):
        raise ValueError(f"mode must be 'train' or 'serve', got {mode!r}")
    n = axis_size(mesh, axis)
    if isinstance(exchange, EmbeddingExchange):
        exch = exchange
    else:
        exch = make_exchange(cfg, axis, n, plan=plan,
                             row_wise_exchange=exchange,
                             lookup_chunk=lookup_chunk)
    depth = int(pipeline_depth)
    if depth < 1:
        raise ValueError(f"pipeline_depth must be >= 1, got {pipeline_depth}")

    ax_tuple = (axis,) if isinstance(axis, str) else tuple(axis)
    full_axes = tuple(dp_axes) + ax_tuple
    n_full = axis_size(mesh, full_axes)

    mlp_spec, top_spec = _mlp_specs(cfg)
    p_specs = {"bot_mlp": mlp_spec, "top_mlp": top_spec,
               **exch.table_specs()}
    data_spec = P(full_axes)

    def _pick_tables(params):
        return {k: params[k] for k in exch.table_keys}

    def _sparse_forward(tables, indices):
        with jax.named_scope("dlrm.sparse"):
            return exch.forward(tables, indices)

    # ---------------- serve: forward pipeline + sigmoid -------------------
    if mode == "serve":
        use_fused = bool(fused) and exch.supports_fused_forward()

        if use_fused:
            # fused megakernel path: gather -> VMEM pool -> interaction in
            # one launch per micro-batch. A fused-capable exchange is LOCAL
            # (no forward collectives), so there is no exchange wire time
            # to software-pipeline ahead — micro-batches run in sequence.
            def serve(params, dense, indices):
                tables = _pick_tables(params)
                idx_mb = _mb_slices(indices, depth)
                den_mb = _mb_slices(dense, depth)
                outs = []
                for i in range(depth):
                    with jax.named_scope("dlrm.bottom_mlp"):
                        bot = dlrm_lib.mlp_forward(params["bot_mlp"],
                                                   den_mb[i])
                    with jax.named_scope("dlrm.sparse"):
                        z = exch.fused_forward(tables, bot, idx_mb[i])
                    with jax.named_scope("dlrm.top_mlp"):
                        logits = dlrm_lib.mlp_forward(params["top_mlp"],
                                                      z)[:, 0]
                    outs.append(jax.nn.sigmoid(logits))
                return (outs[0] if depth == 1
                        else jnp.concatenate(outs, axis=0))
        else:
            def serve(params, dense, indices):
                tables = _pick_tables(params)
                idx_mb = _mb_slices(indices, depth)
                den_mb = _mb_slices(dense, depth)
                outs = []
                nxt = _sparse_forward(tables, idx_mb[0])
                for i in range(depth):
                    pooled_i, _ = nxt
                    if i + 1 < depth:
                        # issue the NEXT micro-batch's exchange before this
                        # micro-batch's MLP compute — the overlap window
                        nxt = _sparse_forward(tables, idx_mb[i + 1])
                    logits = dlrm_lib.dlrm_forward_from_pooled(
                        params, den_mb[i], pooled_i)
                    outs.append(jax.nn.sigmoid(logits))
                return (outs[0] if depth == 1
                        else jnp.concatenate(outs, axis=0))

        smapped = jax.shard_map(serve, mesh=mesh,
                                in_specs=(p_specs, data_spec, data_spec),
                                out_specs=data_spec, check_vma=False)
        return jax.jit(smapped)

    # ---------------- train: fwd/bwd pipeline + grad stages ----------------
    opt_specs: Optional[Params] = None
    if optimizer == "adagrad" or compress_grads:
        opt_specs = {}
        if optimizer == "adagrad":
            opt_specs.update(exch.acc_specs())
        if compress_grads:
            opt_specs["ef"] = jax.tree_util.tree_map(
                lambda _: P(full_axes), _dense_param_abstract(cfg))
    car_fn = (make_compressed_allreduce(full_axes)[0]
              if compress_grads else None)

    def step(params, opt_state, dense, indices, labels):
        dense_params = {"bot_mlp": params["bot_mlp"],
                        "top_mlp": params["top_mlp"]}
        tables = _pick_tables(params)
        idx_mb = _mb_slices(indices, depth)
        den_mb = _mb_slices(dense, depth)
        lab_mb = _mb_slices(labels, depth)

        def local_loss(dp, pl, den, lab):
            logits = dlrm_lib.dlrm_forward_from_pooled(
                {**dp, "tables": None}, den, pl)
            # mean over the GLOBAL batch: local sum / global size
            return dlrm_lib.bce_loss(logits, lab) / (n_full * depth)

        # ---- software-pipelined Alg. 1 forward + dense fwd/bwd ----
        # SGD scatter-adds commute, so its sparse update is applied PER
        # micro-batch through the exchange's batch-chunked path (never
        # materializing an L-expanded grad block at any depth). AdaGrad
        # must see the full batch's row multiset in one accumulator update
        # to match the serial step, so its flat grads (bounded by B_mb*L
        # each) are collected and concatenated.
        sgd_upd = sgd_row_update(lr) if optimizer == "sgd" else None
        new_tables = dict(tables)
        loss = 0.0
        g_dense = None
        flat_mbs = []
        nxt = _sparse_forward(tables, idx_mb[0])
        for i in range(depth):
            pooled_i, ctx_i = nxt
            if i + 1 < depth:
                # exchange for micro-batch i+1 issued BEFORE compute of i
                nxt = _sparse_forward(tables, idx_mb[i + 1])
            loss_i, (g_i, gp_i) = jax.value_and_grad(
                local_loss, argnums=(0, 1))(
                    dense_params, pooled_i, den_mb[i], lab_mb[i])
            loss = loss + loss_i
            g_dense = g_i if g_dense is None else _tree_add(g_dense, g_i)
            # grad routing for micro-batch i overlaps compute of i+1
            with jax.named_scope("dlrm.sparse_update"):
                if optimizer == "sgd":
                    new_tables = exch.sparse_apply(new_tables, ctx_i, gp_i,
                                                   sgd_upd)
                else:
                    flat_mbs.append(exch.expand_grads(tables, ctx_i, gp_i))

        # ---- dense all-reduce stage (the ALLREDUCE phase) ----
        with jax.named_scope("dlrm.dense_update"):
            if compress_grads:
                ef = jax.tree_util.tree_map(lambda e: e[0], opt_state["ef"])
                g_mean, new_ef = car_fn(g_dense, ef)
                grads = jax.tree_util.tree_map(lambda g: g * n_full, g_mean)
            else:
                grads = jax.lax.psum(g_dense, full_axes)
        loss = jax.lax.psum(loss, full_axes)
        with jax.named_scope("dlrm.dense_update"):
            new_dense = jax.tree_util.tree_map(lambda p, g: p - lr * g,
                                               dense_params, grads)

        # ---- sparse update stage (the SPARSE UPDT phase) ----
        # (SGD already applied per micro-batch above.)
        new_opt: Params = {}
        with jax.named_scope("dlrm.sparse_update"):
            if optimizer != "sgd":
                ada = adagrad_row_update(lr)
                for k in exch.table_keys:
                    new_opt[acc_key(k)] = opt_state[acc_key(k)]
                for k, (fi, fg) in _concat_flat_grads(flat_mbs).items():
                    new_tables[k], new_opt[acc_key(k)] = ada(
                        tables[k], opt_state[acc_key(k)], fi, fg)

            if dp_axes:
                # replicated (fast-tier) tables: sum the sparse deltas across
                # the pure-DP replicas so every replica applies the
                # full-batch update.
                for k in exch.table_keys:
                    new_tables[k] = tables[k] + jax.lax.psum(
                        new_tables[k] - tables[k], dp_axes)
                if optimizer != "sgd":
                    for k in exch.table_keys:
                        ak = acc_key(k)
                        a0 = opt_state[ak]
                        new_opt[ak] = a0 + jax.lax.psum(new_opt[ak] - a0,
                                                        dp_axes)

        if compress_grads:
            new_opt["ef"] = jax.tree_util.tree_map(lambda e: e[None], new_ef)

        new_params = {**new_dense, **new_tables}
        return new_params, (new_opt or None), loss

    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(p_specs, opt_specs, data_spec, data_spec, data_spec),
        out_specs=(p_specs, opt_specs, P()),
        check_vma=False,
    )
    return jax.jit(smapped, donate_argnums=(0, 1))
