"""ServeSession: the engine's request path for batched DLRM inference.

Wraps the plan-executing serve step (`repro.parallel.build_step`)
behind a dynamic micro-batcher: callers `submit()` fixed-size queries;
micro-batches flush when full or when the oldest query hits its deadline.
Two drivers measure the latency distribution D_Q against the paper's SLA
model (Eq. 1, PPF(D_Q, P) <= C_SLA):

  * `run_serial(n)`   — closed-loop, one query at a time (the seed
                        launcher's behavior): isolates per-query service
                        time, no queueing.
  * `run_open_loop(n, qps)` — Poisson arrivals at a target QPS on a
                        virtual clock; service times are REAL device
                        executions, queueing/batching delays are simulated
                        event-by-event. Deterministic and sleep-free, so it
                        is usable from tests and CI while still reflecting
                        the throughput/tail-latency frontier.

The real-time path publishes what it does on the host clock: each flush
is a `repro.obs.host_span` ``serve.flush`` (flush id, queries, padded
count, reason ``full``/``deadline``/``forced``, query ids) with children
``serve.assemble`` (concat + pad, the host-to-device copy),
``serve.dispatch`` (the step call), ``serve.device_wait``
(`block_until_ready`) and ``serve.copy_out`` (device-to-host, reshape);
a new batch shape's compile is ``serve.compile``. Each child's duration
goes to the session's `MetricsRegistry` as ``serve_<phase>_ms``, and the
batcher's per-query wait as ``serve_batch_wait_ms``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
import jax.numpy as jnp

from repro.configs.base import DLRMConfig
from repro.core import dlrm as dlrm_lib
from repro.core.planner import ShardingPlan
from repro import parallel
from repro.data import make_recsys_batch
from repro.engine.batching import (MicroBatcher, QueryFuture, now_s,
                                   poisson_arrivals)
from repro.obs.attribution import AttributionLog, BlameReport
from repro.obs.metrics import default_registry
from repro.obs.serialize import report_asdict, report_to_json
from repro.obs.trace import Tracer, host_span

Query = Dict[str, jax.Array]


@dataclass(frozen=True)
class SLAReport:
    """Latency distribution + SLA verdict for one serving run."""

    n_queries: int
    mode: str                  # "serial" | "open_loop"
    offered_qps: Optional[float]
    achieved_qps: float
    p50_ms: float
    p90_ms: float
    p99_ms: float
    percentile: float
    ppf_ms: float              # PPF(D_Q, percentile)
    sla_ms: float              # C_SLA
    ok: bool
    mean_batch_queries: float  # avg queries per flushed micro-batch
    blame: Optional[BlameReport] = None  # tail-latency attribution

    def summary(self) -> str:
        offered = ("" if self.offered_qps is None
                   else f" offered={self.offered_qps:.1f}qps")
        text = (
            f"[serve] {self.mode}: {self.n_queries} queries,{offered} "
            f"QPS={self.achieved_qps:.1f} mean_batch="
            f"{self.mean_batch_queries:.2f} p50={self.p50_ms:.2f}ms "
            f"p90={self.p90_ms:.2f}ms p99={self.p99_ms:.2f}ms\n"
            f"[serve] SLA check PPF(D_Q, {self.percentile:.0f}) = "
            f"{self.ppf_ms:.2f}ms {'<=' if self.ok else '>'} "
            f"C_SLA={self.sla_ms}ms -> {'PASS' if self.ok else 'FAIL'}")
        if self.blame is not None:
            text += "\n" + self.blame.summary()
        return text

    def asdict(self) -> dict:
        return report_asdict(self)

    def to_json(self, path: Optional[str] = None) -> str:
        return report_to_json(self, path)


def _report(lat_ms: Sequence[float], batch_sizes: Sequence[int], mode: str,
            offered_qps: Optional[float], achieved_qps: float,
            sla_ms: float, percentile: float,
            blame: Optional[BlameReport] = None) -> SLAReport:
    lat = np.asarray(lat_ms, np.float64)
    p50, p90, p99 = (float(np.percentile(lat, p)) for p in (50, 90, 99))
    ppf = float(np.percentile(lat, percentile))
    return SLAReport(
        n_queries=len(lat), mode=mode, offered_qps=offered_qps,
        achieved_qps=achieved_qps, p50_ms=p50, p90_ms=p90, p99_ms=p99,
        percentile=percentile, ppf_ms=ppf, sla_ms=sla_ms, ok=ppf <= sla_ms,
        mean_batch_queries=float(np.mean(batch_sizes)) if batch_sizes else 0.0,
        blame=blame)


class ServeSession:
    """One served model instance: sharded params + compiled step + batcher.

    Built by `Engine.serve_session()`; do not construct the pipeline by
    hand. Queries are fixed-size (`query_size` samples each — the paper's
    "query of size B", Sec. III-B); the micro-batcher packs up to
    `max_batch_queries` of them into one device execution. `metrics` is
    the `MetricsRegistry` the request path publishes its phase and wait
    histograms to (default: the process-wide `default_registry()`).
    """

    def __init__(self, cfg: DLRMConfig, mesh, axis, *,
                 plan: Optional[ShardingPlan] = None,
                 exchange="partial_pool",
                 max_batch_queries: int = 8,
                 max_wait_ms: float = 2.0,
                 query_size: Optional[int] = None,
                 params=None, seed: int = 0, alpha: float = 0.0,
                 warmup: bool = False,
                 pipeline_depth: Optional[int] = 1,
                 depth_resolver: Optional[Callable[[int], int]] = None,
                 dp_axes: Tuple[str, ...] = (), fused: bool = True,
                 metrics=None):
        self.cfg = cfg
        self.metrics = metrics if metrics is not None else default_registry()
        self.mesh = mesh
        self.plan = plan
        self.seed = seed
        self.alpha = alpha
        self.query_size = int(query_size or cfg.batch_size)
        self.max_batch_queries = int(max_batch_queries)
        self.dp_axes = tuple(dp_axes)
        # pipeline_depth: a fixed int pins every compiled shape to that
        # depth; None resolves the depth PER COMPILED BATCH SHAPE through
        # `depth_resolver` (planner executed-schedule sweep at the actual
        # flushed sample count — Engine wires it), falling back to 1.
        self.pipeline_depth = (None if pipeline_depth is None
                               else int(pipeline_depth))
        self._depth_resolver = depth_resolver
        if self.pipeline_depth is not None and self.pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got "
                             f"{pipeline_depth}")
        if self.max_batch_queries < 1:
            raise ValueError("max_batch_queries must be >= 1")
        ax_tuple = (axis,) if isinstance(axis, str) else tuple(axis)
        n = parallel.axis_size(mesh, self.dp_axes + ax_tuple)
        # every flushed batch splits into whole per-device micro-batches
        fixed = self.pipeline_depth or 1
        if (self.max_batch_queries * self.query_size) % (n * fixed):
            raise ValueError(
                f"capacity batch {self.max_batch_queries}x{self.query_size} "
                f"samples must divide the {n}-device mesh x "
                f"pipeline_depth={fixed}")
        self._n = n
        self._n_embed = parallel.axis_size(mesh, axis)
        self._axis = axis
        self._exchange = exchange
        # an EmbeddingExchange INSTANCE may own session state beyond the
        # device params (the hoststore's host weights + chunk cache); its
        # begin/end-batch hooks bracket every execution below
        self._exchange_inst = (exchange if isinstance(
            exchange, parallel.EmbeddingExchange) else None)
        # resolve string exchanges eagerly (same resolution build_step
        # would do) so the fused-serve decision is known at session build;
        # _exchange_inst above keeps its narrower meaning — an exchange
        # with HOST-SIDE session state whose begin/end hooks must bracket
        # every execution (resolved device-resident exchanges stay out of
        # that path: begin_batch does a host sync per flush)
        self._exch = (self._exchange_inst if self._exchange_inst is not None
                      else parallel.make_exchange(
                          cfg, axis, self._n_embed, plan=plan,
                          row_wise_exchange=exchange))
        self._fused = bool(fused)
        # the serve kernel this session's steps execute — mirrors
        # build_step's selection predicate exactly
        self.serve_kernel = ("fused" if self._fused
                             and self._exch.supports_fused_forward()
                             else "composed")
        self._steps: Dict[int, Callable] = {}
        self._depth_by_samples: Dict[int, int] = {}
        key = jax.random.PRNGKey(seed)
        if params is None and self._exchange_inst is not None:
            params = dlrm_lib.init_dlrm(key, cfg)
        elif params is not None and self._exchange_inst is None \
                and "tables" not in params:
            # plan-split params (e.g. TrainSession.params under plan=auto):
            # only accepted when the split matches THIS session's plan
            # groups, otherwise tables would land in the wrong tier.
            groups = (parallel.plan_table_groups(plan, self._n_embed)
                      if plan is not None and plan.placements else None)
            if groups is None:
                raise ValueError(
                    "params have no 'tables' (plan-split) but this session "
                    "has no placed plan; pass stacked params")
            got = (params["tables_fast"].shape[0],
                   params["tables_bulk"].shape[0])
            want = (len(groups.fast_ids), len(groups.bulk_ids))
            if got != want:
                raise ValueError(
                    f"plan-split params (fast,bulk)={got} do not match this "
                    f"session's plan groups {want}; re-stack them with "
                    f"merge_dlrm_params_by_plan under their own plan first")
        prepared = (self._exchange_inst.init_session_params(params, mesh)
                    if self._exchange_inst is not None else None)
        if prepared is not None:
            self.params = prepared
        elif params is None:
            self.params = parallel.init_dlrm_params(key, cfg, mesh, axis,
                                                    plan=plan)
        else:
            self.params = parallel.shard_dlrm_params(params, cfg, mesh, axis,
                                                     plan=plan)
        self.batcher = MicroBatcher(self.max_batch_queries, max_wait_ms / 1e3,
                                    metrics=self.metrics)
        self._qid = 0
        self._flush_id = 0
        self._compiled: set = set()
        # The measurement drivers compile their shapes untimed on first use;
        # eager warmup only matters for the real-time submit path, where the
        # first flush would otherwise pay the capacity-shape compile.
        if warmup:
            self._ensure_compiled(self.max_batch_queries)

    # -- shapes ------------------------------------------------------------
    def _padded_count(self, n_queries: int) -> int:
        """Smallest query count >= n_queries whose sample total divides the
        mesh x pipeline depth (exists because the capacity batch does)."""
        if n_queries > self.max_batch_queries:
            raise ValueError(
                f"{n_queries} queries exceed the micro-batch capacity "
                f"({self.max_batch_queries})")
        k = n_queries
        div = self._n * (self.pipeline_depth or 1)
        while (k * self.query_size) % div:
            k += 1
        return k

    def depth_for_samples(self, batch_samples: int) -> int:
        """The pipeline depth the step for this batch shape executes: the
        fixed session depth, or (pipeline_depth=None) the per-shape
        planner choice via `depth_resolver`, clamped to the largest
        feasible depth dividing the per-device batch. Cached per shape —
        the resolution runs once per compiled shape, off the hot path."""
        if self.pipeline_depth is not None:
            return self.pipeline_depth
        b = int(batch_samples)
        if b in self._depth_by_samples:
            return self._depth_by_samples[b]
        local = max(1, b // self._n)
        depth = (self._depth_resolver(b) if self._depth_resolver is not None
                 else 1)
        depth = max(1, min(int(depth), local))
        while depth > 1 and local % depth:
            depth -= 1
        self._depth_by_samples[b] = depth
        return depth

    def _get_step(self, depth: int) -> Callable:
        if depth not in self._steps:
            self._steps[depth] = parallel.build_step(
                self.cfg, self.mesh, mode="serve", axis=self._axis,
                exchange=self._exch, plan=self.plan,
                dp_axes=self.dp_axes, pipeline_depth=depth,
                fused=self._fused)
        return self._steps[depth]

    def _ensure_compiled(self, n_queries: int) -> None:
        k = self._padded_count(n_queries)
        b = self.query_size * k
        if b in self._compiled:
            return
        with host_span("serve.compile", self.metrics, samples=b):
            step = self._get_step(self.depth_for_samples(b))
            # zero queries through the flush's own assembly, so its concat
            # is compiled here too and not on the first real flush
            q = self.query_size
            zero = {"dense": np.zeros((q, self.cfg.num_dense), np.float32),
                    "indices": np.zeros((q, self.cfg.num_tables,
                                         self.cfg.lookups_per_table),
                                        np.int32)}
            dense, idx = self._assemble([zero], k)
            step(self.params, dense, idx).block_until_ready()
        self._compiled.add(b)

    # -- execution ---------------------------------------------------------
    @staticmethod
    def _assemble(queries: List[Query], k: int
                  ) -> Tuple[jax.Array, jax.Array]:
        """The queries as one device batch of ``k`` queries, padded by
        repeating query 0 (host arrays are copied to the device here)."""
        parts = list(queries) + [queries[0]] * (k - len(queries))
        return (jnp.concatenate([p["dense"] for p in parts], axis=0),
                jnp.concatenate([p["indices"] for p in parts], axis=0))

    def serve_direct(self, dense: jax.Array, indices: jax.Array) -> np.ndarray:
        """Run the compiled serve step on one exact batch (no batching/pad)."""
        step = self._get_step(self.depth_for_samples(dense.shape[0]))
        return np.asarray(step(self.params, dense, indices))

    def _execute(self, queries: List[Query]
                 ) -> Tuple[np.ndarray, float, float]:
        """Concatenate + pad queries, run the step, split results back.

        Returns (probs (n_queries, query_size), service_seconds,
        swap_stall_seconds). `service_seconds` INCLUDES the swap stall
        (it is the batch's full occupancy of the executor); the stall is
        also returned on its own so attribution can split compute from
        exposed host-tier swap time. Padding replicates query 0 so every
        compiled shape is a mesh-divisible query count; padded outputs
        are discarded.
        """
        k = self._padded_count(len(queries))
        self._ensure_compiled(k)
        m = self.metrics
        with host_span("serve.assemble", m):
            dense, idx = self._assemble(queries, k)
        depth = self.depth_for_samples(k * self.query_size)
        step = self._get_step(depth)
        plan = None
        if self._exchange_inst is not None:
            # fault the batch's cold chunks in BEFORE the step launches
            # (micro-batch by micro-batch, so i+1's swap-in can overlap
            # i's compute on the virtual clock below)
            self.params, plan = self._exchange_inst.begin_batch(
                self.params, np.asarray(idx), depth)
        with host_span("serve.dispatch", m) as dispatch:
            probs = step(self.params, dense, idx)
        with host_span("serve.device_wait", m) as wait:
            probs.block_until_ready()
        service = wait.t1 - dispatch.t0
        stall = 0.0
        if plan is not None:
            # modeled swap stall composes with the MEASURED compute time —
            # the bench_pipeline measured+modeled discipline
            stall = self._exchange_inst.stall_seconds(plan, service)
            service += stall
        with host_span("serve.copy_out", m):
            out = np.asarray(probs).reshape(k, self.query_size)
        return out[:len(queries)], service, stall

    # -- request path ------------------------------------------------------
    def validate_query(self, query: Query) -> None:
        """Shape/dtype-check a query against the session's config BEFORE it
        reaches the jitted step, so a malformed query fails with a clear
        ValueError at submit time instead of an opaque XLA shape error deep
        inside the compiled pipeline. Metadata-only: no device sync."""
        for field in ("dense", "indices"):
            if field not in query:
                raise ValueError(f"query is missing the {field!r} field")
        dense, idx = query["dense"], query["indices"]
        q = self.query_size
        want_dense = (q, self.cfg.num_dense)
        if tuple(dense.shape) != want_dense:
            raise ValueError(
                f"query 'dense' must have shape {want_dense} "
                f"(query_size x cfg.num_dense), got {tuple(dense.shape)}")
        want_idx = (q, self.cfg.num_tables, self.cfg.lookups_per_table)
        if tuple(idx.shape) != want_idx:
            raise ValueError(
                f"query 'indices' must have shape {want_idx} (query_size x "
                f"cfg.num_tables x cfg.lookups_per_table), got "
                f"{tuple(idx.shape)}")
        if not jnp.issubdtype(dense.dtype, jnp.floating):
            raise ValueError(
                f"query 'dense' must be floating point, got {dense.dtype}")
        if not jnp.issubdtype(idx.dtype, jnp.integer):
            raise ValueError(
                f"query 'indices' must be an integer dtype (row ids), got "
                f"{idx.dtype}")

    def submit(self, query: Query, now: Optional[float] = None) -> QueryFuture:
        """Enqueue one query; flushes the micro-batch if it became full or
        the oldest query's deadline has already passed. `now` (seconds) is
        injectable for deterministic tests; defaults to the wall clock."""
        self.validate_query(query)
        t = now_s() if now is None else now
        fut = QueryFuture(self._qid, t, {"dense": query["dense"],
                                         "indices": query["indices"]})
        self._qid += 1
        full = self.batcher.add(fut)
        if full or self.batcher.due(t):
            self._flush(now, "full" if full else "deadline")
        return fut

    def poll(self, now: Optional[float] = None) -> bool:
        """Flush if the oldest queued query has exceeded its deadline.
        Returns True if a flush happened."""
        t = now_s() if now is None else now
        if self.batcher.due(t):
            self._flush(now, "deadline")
            return True
        return False

    def flush(self, now: Optional[float] = None) -> List[QueryFuture]:
        """Force the queued micro-batch through the device."""
        return self._flush(now, "forced")

    def _flush(self, now: Optional[float], reason: str) -> List[QueryFuture]:
        futs = self.batcher.drain(now_s() if now is None else now)
        if not futs:
            return []
        fid, self._flush_id = self._flush_id, self._flush_id + 1
        with host_span("serve.flush", flush=fid, queries=len(futs),
                       padded=self._padded_count(len(futs)), reason=reason,
                       qids=" ".join(str(f.qid) for f in futs)):
            probs, _, _ = self._execute([f.query for f in futs])
        t = now_s() if now is None else now
        for f, p in zip(futs, probs):
            f.complete(p, t)
        return futs

    @property
    def pending(self) -> int:
        return len(self.batcher.queue)

    # -- measurement drivers ----------------------------------------------
    def measure_service_time(self, n_queries: int = 1, repeats: int = 5,
                             seed: Optional[int] = None,
                             alpha: Optional[float] = None) -> float:
        """Median wall-clock seconds to serve one `n_queries`-query batch
        (`n_queries` must be <= the session's micro-batch capacity)."""
        qs = [self._make_query(s, seed, alpha) for s in range(n_queries)]
        self._ensure_compiled(n_queries)
        times = []
        for _ in range(repeats):
            _, service, _ = self._execute(qs)
            times.append(service)
        return float(np.median(times))

    def _make_query(self, step: int, seed: Optional[int] = None,
                    alpha: Optional[float] = None) -> Query:
        """Synthetic query from the session's stream (seed/alpha default to
        the engine's, so measured traffic matches what the plan profiled)."""
        b = make_recsys_batch(self.cfg, step,
                              self.seed if seed is None else seed,
                              self.alpha if alpha is None else alpha,
                              batch_size=self.query_size)
        return {"dense": b["dense"], "indices": b["indices"]}

    def run_serial(self, n_queries: int, *, sla_ms: float = 50.0,
                   percentile: float = 99.0, seed: Optional[int] = None,
                   alpha: Optional[float] = None,
                   tracer: Optional[Tracer] = None,
                   metrics=None) -> SLAReport:
        """Closed-loop: one query per micro-batch, back to back.

        `metrics` scopes the run's meters to a caller-owned
        `MetricsRegistry`; the default is the process-wide
        `default_registry()` (which accumulates ACROSS runs — callers
        doing back-to-back runs in one process should pass their own
        registry per run to keep tallies separable)."""
        self._ensure_compiled(1)
        if tracer is not None:
            tracer.track(1, 0, process="board0", thread="serve")
            tracer.track(1, 3, thread="host-swap")
        log = AttributionLog()
        metrics = metrics if metrics is not None else default_registry()
        lat_ms: List[float] = []
        clock = 0.0            # back-to-back virtual timeline
        for q in range(n_queries):
            _, service, stall = self._execute(
                [self._make_query(q, seed, alpha)])
            done = clock + service
            metrics.counter("queries_served", rid=0).inc()
            metrics.histogram("flush_service_ms").observe(service * 1e3)
            # closed loop: arrival == dispatch, so latency is pure service
            log.record_batch([(q, clock)], rid=0, trigger=clock, start=clock,
                             done=done, compute_s=service - stall,
                             swap_stall_s=stall)
            if tracer is not None:
                tracer.span("serve_batch", "service", clock, done,
                            pid=1, tid=0, args={"queries": 1, "qid": q})
                if stall > 0:
                    tracer.span("swap_stall", "hoststore", done - stall,
                                done, pid=1, tid=3)
            clock = done
            lat_ms.append(service * 1e3)
        busy_s = sum(lat_ms) / 1e3
        return _report(lat_ms, [1] * n_queries, "serial", None,
                       n_queries / max(busy_s, 1e-12), sla_ms, percentile,
                       blame=log.blame(percentile))

    def run_open_loop(self, n_queries: int, qps: float, *,
                      sla_ms: float = 50.0, percentile: float = 99.0,
                      seed: Optional[int] = None,
                      alpha: Optional[float] = None,
                      max_wait_ms: Optional[float] = None,
                      tracer: Optional[Tracer] = None,
                      metrics=None) -> SLAReport:
        """Open-loop load: Poisson arrivals at `qps`, dynamic batching.

        Event-driven virtual clock over the SAME `MicroBatcher` policy the
        real-time submit path uses: arrival times are generated up front;
        each flush's SERVICE time is a real device execution (measured);
        queueing (server busy) and batching (deadline) delays compose with
        it exactly as they would on a single-executor server. Per-query
        latency = completion - arrival; the SLA verdict is Eq. 1 on that
        distribution, and `report.blame` decomposes the tail.

        `metrics` scopes the run's meters (see `run_serial`): pass a
        fresh `MetricsRegistry` per run to avoid the process-wide
        default registry double-counting back-to-back runs.
        """
        arrivals = poisson_arrivals(n_queries, qps,
                                    self.seed if seed is None else seed)
        batcher = MicroBatcher(
            self.max_batch_queries,
            self.batcher.max_wait_s if max_wait_ms is None
            else max_wait_ms / 1e3)
        if tracer is not None:
            tracer.track(1, 0, process="board0", thread="serve")
            tracer.track(1, 1, thread="batching")
            tracer.track(1, 3, thread="host-swap")
        log = AttributionLog()
        metrics = metrics if metrics is not None else default_registry()
        lat_ms: List[float] = []
        batch_sizes: List[int] = []
        free = 0.0            # server busy until this time
        last_done = 0.0
        i = 0
        while i < n_queries or batcher.queue:
            next_arr = arrivals[i] if i < n_queries else float("inf")
            # deadline wins ties, matching MicroBatcher.due (now >= deadline)
            if next_arr < batcher.deadline():
                fut = QueryFuture(i, arrivals[i],
                                  self._make_query(i, seed, alpha))
                i += 1
                if not batcher.add(fut):
                    continue
                trigger = fut.arrival          # the batch just filled
                reason = "full"
            else:
                trigger = batcher.deadline()   # oldest query timed out
                reason = "deadline"
            futs = batcher.drain()
            probs, service, stall = self._execute([f.query for f in futs])
            start = max(trigger, free)
            done = start + service
            free = done
            last_done = done
            metrics.counter("queries_served", rid=0).inc(len(futs))
            metrics.counter("flushes", reason=reason).inc()
            metrics.histogram("flush_service_ms").observe(service * 1e3)
            log.record_batch([(f.qid, f.arrival) for f in futs], rid=0,
                             trigger=trigger, start=start, done=done,
                             compute_s=service - stall, swap_stall_s=stall)
            if tracer is not None:
                tracer.span("batch_fill", "batching", futs[0].arrival,
                            trigger, pid=1, tid=1,
                            args={"queries": len(futs), "reason": reason})
                tracer.instant(f"flush:{reason}", "batching", trigger,
                               pid=1, tid=1, args={"queries": len(futs)})
                tracer.counter("queue_depth", trigger, {"board0": len(futs)},
                               pid=1)
                tracer.counter("queue_depth", done, {"board0": 0}, pid=1)
                tracer.span("serve_batch", "service", start, done,
                            pid=1, tid=0,
                            args={"queries": len(futs),
                                  "service_ms": service * 1e3})
                if stall > 0:
                    tracer.span("swap_stall", "hoststore", done - stall,
                                done, pid=1, tid=3)
            for f, p in zip(futs, probs):
                f.complete(p, done)
                lat_ms.append(f.latency_ms)
            batch_sizes.append(len(futs))
        achieved = n_queries / max(last_done, 1e-12)
        return _report(lat_ms, batch_sizes, "open_loop", qps, achieved,
                       sla_ms, percentile, blame=log.blame(percentile))
