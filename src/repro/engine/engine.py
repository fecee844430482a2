"""Engine: one declarative session API from config -> plan -> build -> run.

The paper's thesis is that recommender throughput is decided by how the
model is PLACED and DRIVEN — memory tiers, exchange mode, batching. The
pipeline that realizes a placement (profile stream -> plan -> reconcile
with mesh -> step factory -> param init/shard) used to be hand-wired in
every entry point; `Engine` is now the only place it is assembled:

    from repro.engine import Engine

    eng = Engine(get_dlrm("dlrm-rm2-small-unsharded").reduced(),
                 plan="auto", alpha=1.05)
    serve = eng.serve_session(max_batch_queries=8, max_wait_ms=2.0)
    report = serve.run_open_loop(n_queries=200, qps=400.0, sla_ms=50.0)

    train = eng.train_session(ckpt_dir="/tmp/ck")
    train.run(100)

`plan=` accepts "none" (execute cfg.sharding as-is), "auto" (profile the
step-indexed stream and run the placement planner, per serving/training
mode), or a concrete `ShardingPlan` (reconciled against the mesh).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple, Union

from jax.sharding import Mesh

from repro.configs.base import DLRMConfig
from repro.core.planner import ShardingPlan
from repro.engine.planning import (PlanReport, build_auto_plan,
                                   resolve_depth_for_batch)
from repro.engine.serving import ServeSession
from repro.engine.training import LMTrainSession, TrainSession
from repro.launch.mesh import make_host_mesh

PlanArg = Union[None, str, ShardingPlan]


class Engine:
    """Declarative session factory over one model config + mesh.

    Parameters
    ----------
    cfg        : DLRMConfig (serve + train) or an LM ModelConfig (train).
    mesh       : jax Mesh; defaults to a host mesh with `model_axis`
                 model-parallel columns over the local device set.
    plan       : "none" | "auto" | ShardingPlan (DLRM only; see module doc).
    exchange   : row-wise exchange mode when the plan doesn't dictate one.
    optimizer  : sparse optimizer for DLRM training ("sgd" | "adagrad").
    lr         : learning rate for training sessions.
    alpha      : Zipf skew of the synthetic stream (profiling AND data).
    seed       : parameter init + data stream seed.
    fast_mb    : per-chip fast-tier capacity (MiB) for plan="auto";
                 default fits ~half the tables so smoke runs go MIXED.
    dp_axes    : extra PURE data-parallel mesh axes (DLRM only): the
                 tables are replicated across them and the batch shards
                 over dp_axes + axis (`parallel.build_step(dp_axes=...)`).
                 The embedding distribution (planning, table groups, opt
                 state) sees only `axis`. dp_axes + axis must cover the
                 mesh. This is how a replica's sub-mesh goes pure-DP.
    pipeline_depth : micro-batch pipeline depth for the DLRM steps
                 (repro.parallel.build_step). An int pins every shape
                 (clamped to the largest feasible depth dividing the
                 per-device batch). None = planner-resolved: serving
                 resolves the depth PER COMPILED BATCH SHAPE (the
                 executed-schedule sweep at the actual flushed sample
                 count); training uses PlanReport.pipeline_depth under
                 plan="auto", else 1.
    compress_grads : int8 error-feedback compression of the dense-grad
                 all-reduce in DLRM train steps.
    host_capacity_mb : device-memory budget (MiB) that turns the HOST
                 CHUNK TIER on: sessions serve/train through
                 `repro.hoststore.HostTieredExchange` — full weights in
                 host memory, an HBM hot slab + device chunk cache inside
                 the budget, chunks swapping in ahead of compute. Models
                 BIGGER than the budget serve fine; that is the point.
                 Single-board, plan="none", SGD-only.
    host_chunk_rows : rows per swap chunk (default: perf-model pick).
    host_hot_fraction : budget share for the HBM hot slab (default 0.5).
    host_link  : a `perf_model.host_link(...)` Interconnect pricing the
                 host<->device swaps (default PCIe 4.0 x16).
    calibration : path to (or dict of) a measured calibration artifact
                 (repro.core.calibration); overrides the host link terms
                 and supplies measured kernel_times to the perf model.
    fused_serve : "auto" (default) serves through the fused gather->pool->
                 interaction megakernel whenever the session's exchange is
                 local (kernels/fused_serve.py; distributed and host-tier
                 exchanges fall back to the composed kernels); "off"
                 forces the composed path everywhere. The choice a session
                 resolved is recorded on `PlanReport.serve_kernel` and
                 `ServeSession.serve_kernel`.
    verbose    : print the plan summary when a plan is built.
    """

    def __init__(self, cfg, *, mesh: Optional[Mesh] = None,
                 model_axis: int = 1, axis=("data", "model"),
                 dp_axes: Tuple[str, ...] = (),
                 plan: PlanArg = "none", exchange: str = "partial_pool",
                 optimizer: str = "sgd", lr: float = 0.01,
                 alpha: float = 0.0, seed: int = 0,
                 fast_mb: Optional[float] = None,
                 pipeline_depth: Optional[int] = None,
                 compress_grads: bool = False,
                 host_capacity_mb: Optional[float] = None,
                 host_chunk_rows: Optional[int] = None,
                 host_hot_fraction: float = 0.5,
                 host_link=None, calibration=None,
                 fused_serve: str = "auto",
                 profile_batches: int = 4, verbose: bool = False,
                 metrics=None):
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_host_mesh(model=model_axis)
        self.axis = axis
        self.dp_axes = tuple(dp_axes)
        self.exchange = exchange
        self.optimizer = optimizer
        self.lr = lr
        self.alpha = alpha
        self.seed = seed
        self.fast_mb = fast_mb
        self.pipeline_depth = pipeline_depth
        self.compress_grads = compress_grads
        self.profile_batches = profile_batches
        self.verbose = verbose
        # run-scoped MetricsRegistry for everything this engine builds
        # (hoststore exchange swap tallies, serve sessions' phase and
        # batch-wait histograms); None = the process-wide
        # default_registry(), the launcher default
        self.metrics = metrics
        self.is_dlrm = isinstance(cfg, DLRMConfig)
        if isinstance(plan, str) and plan not in ("none", "auto"):
            raise ValueError(f"plan must be 'none', 'auto', or a "
                             f"ShardingPlan; got {plan!r}")
        if not self.is_dlrm and plan not in (None, "none"):
            raise ValueError("plan placement is DLRM-only; LM configs take "
                             "plan='none'")
        if not self.is_dlrm and (compress_grads
                                 or pipeline_depth not in (None, 1)):
            raise ValueError("pipeline_depth/compress_grads are DLRM-only")
        if pipeline_depth is not None and pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got "
                             f"{pipeline_depth}")
        if self.dp_axes:
            if not self.is_dlrm:
                raise ValueError("dp_axes is DLRM-only (the LM substrate "
                                 "has its own sharding rules)")
            ax = (self.axis,) if isinstance(self.axis, str) else tuple(self.axis)
            missing = [a for a in self.dp_axes + ax
                       if a not in self.mesh.shape]
            if missing:
                raise ValueError(f"axes {missing} not in mesh "
                                 f"{dict(self.mesh.shape)}")
            if set(self.dp_axes) & set(ax):
                raise ValueError(f"dp_axes {self.dp_axes} overlap the "
                                 f"embedding axis {ax}")
            covered = 1
            for a in self.dp_axes + ax:
                covered *= self.mesh.shape[a]
            if covered != self.mesh.devices.size:
                raise ValueError(
                    f"dp_axes + axis = {self.dp_axes + ax} cover {covered} "
                    f"devices but the mesh has {self.mesh.devices.size}; "
                    f"the batch must shard over the whole mesh")
        if fused_serve not in ("auto", "off"):
            raise ValueError(f"fused_serve must be 'auto' or 'off', got "
                             f"{fused_serve!r}")
        self.fused_serve = fused_serve
        self.host_capacity_mb = host_capacity_mb
        self.host_chunk_rows = host_chunk_rows
        self.host_hot_fraction = host_hot_fraction
        self.host_link = host_link
        self.calibration = calibration
        if host_capacity_mb is not None:
            if not self.is_dlrm:
                raise ValueError("host_capacity_mb (the host chunk tier) "
                                 "is DLRM-only")
            if host_capacity_mb <= 0:
                raise ValueError(f"host_capacity_mb must be > 0, got "
                                 f"{host_capacity_mb}")
            if plan not in (None, "none"):
                raise ValueError(
                    "host_capacity_mb composes the memory tiers itself "
                    "(hot slab + chunk cache + host store); it requires "
                    "plan='none'")
            if self.dp_axes or self.n_devices != 1:
                raise ValueError(
                    f"the host chunk tier is single-board (1 device); mesh "
                    f"has {self.n_devices} devices. Scale out by giving "
                    f"each fabric board its own Engine/host tier")
            if optimizer != "sgd":
                raise ValueError(
                    "host-tier training is SGD-only (AdaGrad accumulators "
                    "would need their own chunked host tier)")
        self._plan_arg: PlanArg = plan
        self._reports: Dict[str, PlanReport] = {}

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def embed_devices(self) -> int:
        """Size of the embedding distribution axis — what the planner,
        table groups, and sparse opt state are sized against. Equals
        `n_devices` unless dp_axes replicate the tables."""
        from repro.parallel import axis_size
        return int(axis_size(self.mesh, self.axis))

    # -- planning stage ----------------------------------------------------
    def build_plan(self, mode: str = "inference") -> Optional[ShardingPlan]:
        """Resolve the engine's `plan=` argument for a serving ("inference")
        or training mode. Auto plans are profiled once per mode and cached;
        concrete plans are reconciled against the mesh."""
        if self._plan_arg in (None, "none"):
            return None
        if isinstance(self._plan_arg, ShardingPlan):
            from repro.parallel import reconcile_plan_with_mesh
            return reconcile_plan_with_mesh(self._plan_arg, self.embed_devices)
        if mode not in self._reports:
            report = build_auto_plan(
                self.cfg, self.embed_devices, alpha=self.alpha, seed=self.seed,
                fast_mb=self.fast_mb, mode=mode,
                profile_batches=self.profile_batches)
            self._reports[mode] = report
            if self.verbose:
                print(report.summary())
        return self._reports[mode].plan

    def plan_report(self, mode: str = "inference") -> Optional[PlanReport]:
        """The cached profile/prediction report for an auto plan (None when
        plan="none" or the mode hasn't been built yet)."""
        return self._reports.get(mode)

    def _plan_and_exchange(self, mode: str):
        if self.host_capacity_mb is not None:
            # host chunk tier: a FRESH exchange per session — each session
            # owns its own host weights, hot slab, and chunk-cache state
            return None, self._host_exchange()
        plan = self.build_plan(mode)
        return plan, (plan.exchange if plan is not None else self.exchange)

    def _host_exchange(self):
        from repro.core import perf_model
        from repro.hoststore import build_host_exchange
        link = self.host_link
        if link is None:
            link = perf_model.host_link(calibration=self.calibration)
        return build_host_exchange(
            self.cfg,
            device_capacity_bytes=int(self.host_capacity_mb * 2**20),
            alpha=self.alpha, seed=self.seed,
            chunk_rows=self.host_chunk_rows,
            hot_fraction=self.host_hot_fraction, link=link,
            profile_batches=max(1, self.profile_batches),
            metrics=self.metrics)

    def resolve_pipeline_depth(self, mode: str,
                               local_batch_samples: int) -> int:
        """The depth a session will execute: the explicit engine setting,
        or the planner's choice (PlanReport.pipeline_depth) under an auto
        plan, clamped to the largest feasible depth that splits the
        per-device batch (`local_batch_samples` = global samples / devices)
        into whole micro-batches."""
        depth = self.pipeline_depth
        if depth is None:
            report = self._reports.get(mode)
            depth = report.pipeline_depth if report is not None else 1
        depth = min(int(depth), max(1, local_batch_samples))
        while depth > 1 and local_batch_samples % depth:
            depth -= 1
        return depth

    def make_depth_resolver(self, mode: str) -> Callable[[int], int]:
        """Per-batch-shape depth resolver for serving: the executed-schedule
        sweep (`planning.resolve_depth_for_batch`) at the actual flushed
        sample count, under the engine's plan (its sharding mode, exchange,
        and measured hit ratio). `ServeSession` caches the result per
        compiled shape."""
        plan, exchange = self._plan_and_exchange(mode)
        hit = plan.hit_ratio if plan is not None else 0.0
        sharding = (plan.mode if plan is not None and plan.placements
                    else None)
        n = self.n_devices
        pmode = "inference" if mode == "inference" else "training"

        def resolve(batch_samples: int) -> int:
            best, _ = resolve_depth_for_batch(
                self.cfg, n, batch_samples, mode=pmode, sharding=sharding,
                exchange=exchange, hit_ratio=hit,
                compress_grads=self.compress_grads)
            return best

        return resolve

    # -- sessions ----------------------------------------------------------
    def serve_session(self, *, max_batch_queries: int = 8,
                      max_wait_ms: float = 2.0,
                      query_size: Optional[int] = None,
                      params=None, warmup: bool = False) -> ServeSession:
        """Build the full serving pipeline: plan -> serve step -> sharded
        params -> dynamic micro-batcher. `params` serve trained weights —
        stacked ({"tables": ...}), or plan-split (e.g. a `TrainSession`'s
        `.params` from THIS engine; the split must match this session's
        plan groups). Default is fresh init from the engine seed.
        `warmup=True` pre-compiles the capacity batch shape so the first
        real-time `submit` flush doesn't pay the XLA compile."""
        if not self.is_dlrm:
            raise ValueError("serve_session is DLRM-only")
        plan, exchange = self._plan_and_exchange("inference")
        qs = int(query_size or self.cfg.batch_size)
        if self.host_capacity_mb is not None and self.pipeline_depth is None:
            # host tier without an explicit depth: depth 1 (synchronous
            # faulting); pass pipeline_depth explicitly to overlap swaps
            depth, resolver = 1, None
        elif self.pipeline_depth is None:
            # planner depth PER COMPILED BATCH SHAPE: flushed batches vary
            # with load, and the winning depth varies with them
            depth, resolver = None, self.make_depth_resolver("inference")
        else:
            depth = self.resolve_pipeline_depth(
                "inference", (max_batch_queries * qs) // self.n_devices)
            resolver = None
        sess = ServeSession(
            self.cfg, self.mesh, self.axis, plan=plan, exchange=exchange,
            max_batch_queries=max_batch_queries, max_wait_ms=max_wait_ms,
            query_size=query_size, params=params, seed=self.seed,
            alpha=self.alpha, warmup=warmup, pipeline_depth=depth,
            depth_resolver=resolver, dp_axes=self.dp_axes,
            fused=self.fused_serve != "off", metrics=self.metrics)
        # record the kernel selection the session resolved on the cached
        # plan report, so plan_report("inference") tells the whole story
        rep = self._reports.get("inference")
        if rep is not None and rep.serve_kernel != sess.serve_kernel:
            self._reports["inference"] = dataclasses.replace(
                rep, serve_kernel=sess.serve_kernel)
        return sess

    def sharded_fleet(self, *, n_boards: int = 2,
                      board_capacity_bytes: Optional[int] = None,
                      link=None, cache_rows: Optional[int] = None,
                      cache_enabled: bool = True,
                      max_batch_queries: int = 4, max_wait_ms: float = 2.0,
                      query_size: Optional[int] = None,
                      router: str = "round_robin", **kw):
        """Build a `repro.fabric.ShardedFleet` from this engine's config:
        N boards that TOGETHER own one partitioned table set (vs the
        replicated `repro.cluster` fleet), profiled/partitioned with the
        engine's (alpha, seed) stream so the placement sees the traffic
        the fleet will serve. `link` is a `perf_model.fabric_link(...)`
        interconnect; remaining kwargs forward to `ShardedFleet`."""
        if not self.is_dlrm:
            raise ValueError("sharded_fleet is DLRM-only")
        from repro.fabric import ShardedFleet
        return ShardedFleet(
            self.cfg, n_boards=n_boards,
            board_capacity_bytes=board_capacity_bytes, link=link,
            cache_rows=cache_rows, cache_enabled=cache_enabled,
            alpha=self.alpha, seed=self.seed,
            profile_batches=self.profile_batches,
            max_batch_queries=max_batch_queries, max_wait_ms=max_wait_ms,
            query_size=query_size, router=router,
            verbose=self.verbose, **kw)

    def train_session(self, *, ckpt_dir: Optional[str] = None,
                      ckpt_every: int = 50, ckpt_keep: int = 3,
                      batch: int = 8, seq: int = 128,
                      chain_prob: float = 0.8,
                      schedule_steps: int = 100):
        """Build the full training pipeline (plan-aware step + opt state +
        TrainLoop with checkpoint-resume, retaining `ckpt_keep` snapshots).
        DLRM configs get `TrainSession`; LM configs get `LMTrainSession`
        (batch/seq/chain_prob/schedule_steps apply)."""
        if self.is_dlrm:
            plan, exchange = self._plan_and_exchange("training")
            depth = self.resolve_pipeline_depth(
                "training", self.cfg.batch_size // self.n_devices)
            return TrainSession(
                self.cfg, self.mesh, self.axis, plan=plan, exchange=exchange,
                optimizer=self.optimizer, lr=self.lr, seed=self.seed,
                alpha=self.alpha, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
                ckpt_keep=ckpt_keep, pipeline_depth=depth,
                compress_grads=self.compress_grads, dp_axes=self.dp_axes)
        return LMTrainSession(
            self.cfg, self.mesh, lr=self.lr, seed=self.seed, batch=batch,
            seq=seq, chain_prob=chain_prob, schedule_steps=schedule_steps,
            ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, ckpt_keep=ckpt_keep)
