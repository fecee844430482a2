"""Serving launcher — a thin argparse adapter over `repro.engine.Engine`
and, for fleets, `repro.cluster.Cluster`.

The pipeline (profile -> plan -> reconcile -> serve step -> shard params ->
micro-batcher) lives in `repro.engine`; this module only maps flags onto
`Engine(...)` / `ServeSession`. Implements the deployment scenario of paper
Sec. III-B / Fig. 3: queries of size B are ranked under the SLA constraint
PPF(D_Q, P) <= C_SLA (Eq. 1).

  # closed-loop (one query at a time, the per-query service floor)
  PYTHONPATH=src python -m repro.launch.serve --smoke --queries 200

  # open-loop: Poisson arrivals at 300 QPS, dynamic micro-batching
  PYTHONPATH=src python -m repro.launch.serve --smoke --queries 200 \
      --qps 300 --max-batch-queries 8 --max-wait-ms 2

  # fleet: 2 replicas under a flash-crowd burst, p2c routing, autoscaling
  PYTHONPATH=src python -m repro.launch.serve --smoke --queries 100 \
      --replicas 2 --scenario flash_crowd --router p2c --autoscale

Any of --replicas>1 / --scenario / --autoscale / --replay-trace routes
through the cluster path: a `TrafficScenario` event stream (or a recorded
JSONL trace) served by N replica sub-meshes behind the chosen router.
With ``--plan auto`` the engine profiles the index stream, runs the
placement planner, prints the chosen placement + predicted QPS, and
EXECUTES the placements inside the serve step.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

import numpy as np

from repro.configs.registry import get_dlrm
from repro.engine import Engine
from repro.obs import Tracer, default_registry


def _emit_obs(args, tracer, extra_metrics=None, report=None) -> None:
    """Write the run's observability artifacts: Chrome trace JSON
    (--trace-out), merged metrics snapshot (--metrics-out: the process
    registry, e.g. hoststore swap meters, merged with the fleet's
    per-run registry), machine-readable report (--report-json)."""
    if args.trace_out and tracer is not None:
        tracer.write(args.trace_out)
        print(f"[serve] trace -> {args.trace_out} "
              f"({tracer.n_events} events)")
    if args.metrics_out:
        snap = dict(default_registry().snapshot())
        if extra_metrics is not None:
            snap.update(extra_metrics.snapshot())
        with open(args.metrics_out, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"[serve] metrics -> {args.metrics_out} ({len(snap)} series)")
    if args.report_json and report is not None:
        report.to_json(args.report_json)
        print(f"[serve] report -> {args.report_json}")


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="dlrm-rm2-small-unsharded")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--queries", type=int, default=100)
    ap.add_argument("--qps", type=float, default=0.0,
                    help="open-loop Poisson arrival rate; 0 = closed-loop "
                         "(back-to-back queries, no batching delay)")
    ap.add_argument("--max-batch-queries", type=int, default=4,
                    help="dynamic micro-batch capacity (queries)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="micro-batch deadline: oldest query flushes by this")
    ap.add_argument("--sla-ms", type=float, default=50.0,
                    help="C_SLA (paper Eq. 1), milliseconds")
    ap.add_argument("--sla-percentile", type=float, default=99.0)
    ap.add_argument("--exchange", default="partial_pool")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan", choices=["none", "auto"], default="none",
                    help="auto: profile + place tables, execute placements")
    ap.add_argument("--alpha", type=float, default=0.0,
                    help="zipf skew of the query index stream (0 = uniform, "
                         "the paper's zero-locality case; try 1.05 with "
                         "--plan auto)")
    ap.add_argument("--fast-mb", type=float, default=None,
                    help="per-chip fast-tier capacity (MiB) for --plan auto")
    ap.add_argument("--pipeline-depth", type=int, default=0,
                    help="micro-batch pipeline depth inside the serve step "
                         "(overlaps embedding exchange with MLP compute); "
                         "0 = auto (planner-resolved per compiled batch "
                         "shape under the engine's plan)")
    ap.add_argument("--host-capacity-mb", type=float, default=None,
                    help="device embedding budget (MiB): tables beyond it "
                         "serve through the pinned-host chunk tier "
                         "(repro.hoststore) with async swap-in; "
                         "single-board path only")
    ap.add_argument("--host-chunk-rows", type=int, default=None,
                    help="rows per host-tier chunk (default: perf-model "
                         "pick over the PCIe link)")
    ap.add_argument("--host-hot-fraction", type=float, default=0.5,
                    help="share of the device budget spent on the HBM hot "
                         "slab (the rest is the chunk cache — lower it if "
                         "a step's working set overflows the cache)")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="measured-hardware calibration JSON "
                         "(repro.core.calibration): host_link overrides "
                         "the PCIe model, service_multiplier the "
                         "hit-ratio monitor's retiming curve, kernel_times "
                         "the perf model's per-kernel serve times")
    ap.add_argument("--fused-serve", choices=["auto", "off"], default="auto",
                    help="auto: serve through the fused gather->pool->"
                         "interaction megakernel when the exchange is "
                         "local (falls back to the composed kernels "
                         "otherwise); off: always composed")
    # -- fleet / scenario flags (repro.cluster path) -----------------------
    ap.add_argument("--replicas", type=int, default=1,
                    help=">1 serves a fleet of replica sub-meshes behind "
                         "--router (repro.cluster); under --fleet-mode "
                         "sharded this is the BOARD count of one "
                         "partitioned model (repro.fabric)")
    ap.add_argument("--fleet-mode", choices=["replicated", "sharded"],
                    default="replicated",
                    help="replicated: every board a full model copy "
                         "(repro.cluster); sharded: the boards TOGETHER "
                         "own one partitioned table set, lookups routed "
                         "to owners over the modeled fabric "
                         "(repro.fabric.ShardedFleet)")
    ap.add_argument("--board-capacity-mb", type=float, default=None,
                    help="per-board embedding capacity (MiB) for the "
                         "sharded fleet's partitioner; default: fair "
                         "share + 25%% headroom")
    ap.add_argument("--fabric-latency-us", type=float, default=1.0,
                    help="inter-board fabric link latency (microseconds)")
    ap.add_argument("--fabric-gbs", type=float, default=100.0,
                    help="inter-board fabric bandwidth (GB/s per board)")
    ap.add_argument("--fabric-cache-rows", type=int, default=None,
                    help="per-board LFU cache of remote hot rows "
                         "(rows; 0 disables, default ~10%% of the "
                         "board's remote row space)")
    ap.add_argument("--scenario", default=None,
                    help="traffic scenario for the fleet path: stationary, "
                         "diurnal, flash_crowd, zipf_drift (zipf_drift "
                         "enables the hit-ratio monitor + lfu_refresh)")
    ap.add_argument("--router", default="round_robin",
                    help="routing policy: round_robin, jsq, p2c")
    ap.add_argument("--autoscale", action="store_true",
                    help="SLA-driven autoscaling: add boards on sustained "
                         "p99 violation, drop them on sustained slack. "
                         "Replicated fleets re-place params via remesh_tree; "
                         "sharded fleets re-partition row ranges LIVE "
                         "(fabric.elastic MigrationPlan)")
    ap.add_argument("--autoscale-sla-ms", type=float, default=None,
                    help="p99 threshold the autoscaler reacts to; default "
                         "--sla-ms (set lower to scale before the report "
                         "SLA is at risk)")
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--min-replicas", type=int, default=1,
                    help="autoscaler floor (sharded fleets shrink by "
                         "retiring boards down to this)")
    # -- online updates (repro.online) -------------------------------------
    ap.add_argument("--online-every-s", type=float, default=0.0,
                    help="stream continuous training into the serving run "
                         "(repro.online): emit a row-delta batch every "
                         "this many virtual seconds (0 = frozen params, "
                         "the default)")
    ap.add_argument("--online-steps", type=int, default=1,
                    help="trainer SGD steps folded into each delta batch")
    ap.add_argument("--online-lr", type=float, default=0.05,
                    help="online trainer learning rate (tables-only SGD)")
    ap.add_argument("--coherence", choices=["invalidate", "propagate"],
                    default="propagate",
                    help="update->cache protocol on the sharded fleet: "
                         "drop every other board's cached copy of an "
                         "updated row, or piggyback the fresh payload "
                         "into the caches")
    ap.add_argument("--record-deltas", default=None, metavar="PATH",
                    help="write the emitted delta channel as JSONL "
                         "(bit-identical replay via --replay-deltas)")
    ap.add_argument("--replay-deltas", default=None, metavar="PATH",
                    help="consume a recorded delta-channel JSONL (e.g. "
                         "from repro.launch.train --emit-deltas) instead "
                         "of training inline")
    ap.add_argument("--record-trace", default=None, metavar="PATH",
                    help="write the generated scenario events as a JSONL "
                         "trace before serving")
    ap.add_argument("--replay-trace", default=None, metavar="PATH",
                    help="serve a recorded JSONL trace instead of "
                         "generating events (bit-identical replay)")
    # -- observability (repro.obs) -----------------------------------------
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the run's virtual-clock trace as Chrome "
                         "trace-event JSON (open in Perfetto / "
                         "chrome://tracing)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the run's metrics-registry snapshot "
                         "(counters/gauges/histograms) as JSON")
    ap.add_argument("--report-json", default=None, metavar="PATH",
                    help="write the final SLA/fleet report (including the "
                         "per-query blame decomposition) as JSON")
    args = ap.parse_args(argv)

    cfg = get_dlrm(args.config)
    full_cfg = cfg
    if args.smoke:
        cfg = cfg.reduced()

    fleet_path = (args.fleet_mode == "sharded" or args.replicas > 1
                  or args.scenario or args.autoscale or args.record_trace
                  or args.replay_trace)
    if args.host_capacity_mb is not None and fleet_path:
        raise SystemExit(
            "--host-capacity-mb is single-board only: give each fleet "
            "board its own Engine/host tier instead")
    if args.fleet_mode == "sharded":
        return _fabric_main(args, cfg)
    if fleet_path:
        return _cluster_main(args, cfg, full_cfg)

    engine = Engine(cfg, model_axis=args.model_axis, plan=args.plan,
                    exchange=args.exchange, alpha=args.alpha,
                    seed=args.seed, fast_mb=args.fast_mb,
                    pipeline_depth=args.pipeline_depth or None,
                    host_capacity_mb=args.host_capacity_mb,
                    host_chunk_rows=args.host_chunk_rows,
                    host_hot_fraction=args.host_hot_fraction,
                    calibration=args.calibration,
                    fused_serve=args.fused_serve, verbose=True)
    if args.host_capacity_mb is not None:
        tbl_mb = cfg.num_tables * cfg.rows_per_table * cfg.embed_dim \
            * 4 / 2 ** 20
        print(f"[serve] host chunk tier: tables {tbl_mb:.3f} MiB vs device "
              f"budget {args.host_capacity_mb:.3f} MiB")
    session = engine.serve_session(max_batch_queries=args.max_batch_queries,
                                   max_wait_ms=args.max_wait_ms)
    print(f"[serve] serve_kernel={session.serve_kernel}")
    tracer = Tracer() if args.trace_out else None
    if args.qps > 0:
        report = session.run_open_loop(
            args.queries, args.qps, sla_ms=args.sla_ms,
            percentile=args.sla_percentile, tracer=tracer)
    else:
        report = session.run_serial(
            args.queries, sla_ms=args.sla_ms,
            percentile=args.sla_percentile, tracer=tracer)
    print(f"[serve] {cfg.name}:")
    print(report.summary())
    _emit_obs(args, tracer, report=report)
    return 0 if report.ok else 1


def _online_channel(args, cfg, params, events, scen_name):
    """Resolve the --online-*/--replay-deltas flags into a `DeltaChannel`
    (None = frozen serving). Inline training pre-records the whole stream
    (`OnlineSource.run_to`) so the channel a run consumes is identical
    across fleet sizes and replayable via --record-deltas."""
    if args.replay_deltas:
        from repro.online import DeltaChannel
        ch = DeltaChannel.load(args.replay_deltas)
        print(f"[serve] replaying {len(ch)} delta batches from "
              f"{args.replay_deltas}")
        return ch
    if args.online_every_s <= 0:
        return None
    from repro.online import OnlineSource, OnlineTrainer
    from repro.traffic import make_scenario
    if not isinstance(params, dict) or "tables" not in params:
        raise SystemExit(
            "--online-every-s needs stacked params with a 'tables' leaf "
            "(plan-split sessions can't take in-place row updates); use "
            "--plan none")
    trainer = OnlineTrainer(cfg, params, lr=args.online_lr,
                            seed=args.seed, alpha=args.alpha)
    salt_fn = None
    if scen_name == "zipf_drift":
        # train on the drifted stream the fleet is actually serving
        scen = make_scenario(scen_name, alpha=args.alpha)
        salt_fn = lambda t: scen.stream_params(t)[1]
    src = OnlineSource(trainer, interval_s=args.online_every_s,
                       steps_per_update=args.online_steps, salt_fn=salt_fn)
    ch = src.run_to(events[-1].arrival_s)
    print(f"[serve] online: {len(ch)} delta batches (every "
          f"{args.online_every_s:g}s x {args.online_steps} steps, "
          f"lr={args.online_lr:g})")
    if args.record_deltas:
        ch.record(args.record_deltas)
        print(f"[serve] recorded deltas -> {args.record_deltas}")
    return ch


def _fabric_main(args, cfg) -> int:
    """Sharded-fleet path: one partitioned model over --replicas boards,
    lookups routed to owners over the modeled fabric (repro.fabric)."""
    from repro.cluster import SLAAutoscaler
    from repro.core.perf_model import fabric_link
    from repro.fabric import fits_one_board
    from repro.traffic import load_trace, make_scenario, record_trace

    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    cap = (int(args.board_capacity_mb * 2 ** 20)
           if args.board_capacity_mb is not None else None)
    # resolve the scenario BEFORE building the fleet (the _cluster_main
    # discipline): the profile, partition and cache warm-up all consume
    # alpha, so a replayed trace's header — or the zipf_drift alpha guard —
    # must inform construction, not arrive after it
    events = None
    if args.replay_trace:
        meta, events = load_trace(args.replay_trace)
        scen_name = meta.get("scenario", args.scenario or "stationary")
        print(f"[serve] replaying {len(events)} events from "
              f"{args.replay_trace} (scenario={scen_name})")
        if args.alpha == 0.0 and events:
            # profile/cache must see the traffic the trace actually carries
            args.alpha = float(np.median([e.alpha for e in events]))
            if args.alpha:
                print(f"[serve] --alpha 0 on replay: profiling at the "
                      f"trace's median alpha {args.alpha:g}")
    else:
        scen_name = args.scenario or "stationary"
    if scen_name == "zipf_drift" and args.alpha == 0.0:
        args.alpha = 1.05
        print("[serve] zipf_drift with --alpha 0: using alpha=1.05 "
              "(uniform streams have no hot rows to drift)")
    autoscaler = None
    if args.autoscale:
        # the elastic threshold may sit BELOW the report SLA: scale when
        # latency degrades, not only once the SLA is already violated
        autoscaler = SLAAutoscaler(
            args.autoscale_sla_ms or args.sla_ms,
            min_replicas=args.min_replicas, max_replicas=args.max_replicas)
    engine = Engine(cfg, seed=args.seed, alpha=args.alpha, verbose=True)
    tracer = Tracer() if args.trace_out else None
    fleet = engine.sharded_fleet(
        n_boards=args.replicas, board_capacity_bytes=cap,
        link=fabric_link(args.fabric_latency_us, args.fabric_gbs),
        cache_rows=args.fabric_cache_rows,
        cache_enabled=(args.fabric_cache_rows is None
                       or args.fabric_cache_rows > 0),
        max_batch_queries=args.max_batch_queries,
        max_wait_ms=args.max_wait_ms, router=args.router,
        model_axis=args.model_axis, autoscaler=autoscaler,
        tracer=tracer)
    if not fits_one_board(cfg, fleet.partition.board_capacity_bytes):
        print(f"[serve] table set "
              f"({fleet.partition.total_bytes / 2**20:.2f} MiB) exceeds one "
              f"board ({fleet.partition.board_capacity_bytes / 2**20:.2f} "
              f"MiB): only the sharded fleet can hold this model")

    if events is None:
        qps = args.qps
        if qps <= 0:
            # sharded throughput does NOT scale with boards: every batch's
            # lookups occupy all owner boards, so the fleet behaves like one
            # pipeline of capacity-batch rounds (no --replicas multiplier)
            s_cap = fleet.measure_service_time()
            qps = 0.3 * args.max_batch_queries / s_cap
            print(f"[serve] --qps 0: offering 0.3 x sharded capacity = "
                  f"{qps:.1f} qps (capacity batch {s_cap * 1e3:.2f} ms)")
        scenario = make_scenario(scen_name, alpha=args.alpha)
        events = scenario.events(args.queries, qps=qps, seed=args.seed)
        if args.record_trace:
            record_trace(args.record_trace, events, scenario, qps=qps,
                         seed=args.seed, config=cfg.name)
            print(f"[serve] recorded trace -> {args.record_trace}")

    online = _online_channel(args, cfg, fleet._params, events, scen_name)
    report = fleet.run(events, sla_ms=args.sla_ms,
                       percentile=args.sla_percentile, scenario=scen_name,
                       online=online, coherence=args.coherence)
    print(f"[serve] {cfg.name} (sharded, {args.replicas} boards):")
    print(report.summary())
    _emit_obs(args, tracer, extra_metrics=fleet.metrics, report=report)
    return 0 if report.ok else 1


def _cluster_main(args, cfg, full_cfg) -> int:
    """Fleet path: scenario/trace -> router -> N replicas -> ClusterReport."""
    from repro.cluster import Cluster, HitRatioMonitor, SLAAutoscaler
    from repro.traffic import (load_trace, make_scenario, record_trace)

    if args.replicas < 1:
        raise SystemExit("--replicas must be >= 1")
    # resolve the scenario BEFORE building the fleet: a replayed trace's
    # header decides it (so a recorded zipf_drift trace replays with the
    # same monitor/refresh machinery the live run had)
    events = None
    if args.replay_trace:
        meta, events = load_trace(args.replay_trace)
        scen_name = meta.get("scenario", args.scenario or "stationary")
        print(f"[serve] replaying {len(events)} events from "
              f"{args.replay_trace} (scenario={scen_name})")
    else:
        scen_name = args.scenario or "stationary"
    if scen_name == "zipf_drift" and args.alpha == 0.0:
        # a uniform stream has no hot set to erode; without an explicit
        # --alpha use the scenario's default skew so the drift mechanism
        # (and the monitor's baseline) is meaningful
        args.alpha = 1.05
        print("[serve] zipf_drift with --alpha 0: using alpha=1.05 "
              "(uniform streams have no hot rows to drift)")

    monitor = None
    if scen_name == "zipf_drift":
        # drift erodes the frequency-elected fast tier; monitor + refresh;
        # a --calibration artifact replaces the modeled hybrid-memory
        # retiming curve with the measured one
        monitor = HitRatioMonitor(cfg, alpha=args.alpha, seed=args.seed,
                                  model_cfg=full_cfg,
                                  service_multiplier=args.calibration)
    autoscaler = (SLAAutoscaler(args.autoscale_sla_ms or args.sla_ms,
                                min_replicas=args.min_replicas,
                                max_replicas=args.max_replicas)
                  if args.autoscale else None)
    tracer = Tracer() if args.trace_out else None
    cluster = Cluster(
        cfg, n_replicas=args.replicas, model_axis=args.model_axis,
        plan=args.plan, exchange=args.exchange, alpha=args.alpha,
        seed=args.seed, fast_mb=args.fast_mb,
        max_batch_queries=args.max_batch_queries,
        max_wait_ms=args.max_wait_ms, router=args.router,
        autoscaler=autoscaler, monitor=monitor,
        pipeline_depth=args.pipeline_depth or None, tracer=tracer,
        verbose=True)

    if events is None:
        qps = args.qps
        if qps <= 0:
            # default load: ~80% of the fleet's aggregate per-query capacity
            s1 = cluster.replicas[0].session.measure_service_time()
            qps = 0.8 * args.replicas / s1
            print(f"[serve] --qps 0: offering 0.8 x fleet capacity = "
                  f"{qps:.1f} qps (per-query service {s1 * 1e3:.2f} ms)")
        scenario = make_scenario(scen_name, alpha=args.alpha)
        events = scenario.events(args.queries, qps=qps, seed=args.seed)
        if args.record_trace:
            record_trace(args.record_trace, events, scenario, qps=qps,
                         seed=args.seed, config=cfg.name)
            print(f"[serve] recorded trace -> {args.record_trace}")

    online = _online_channel(args, cfg, cluster.replicas[0].session.params,
                             events, scen_name)
    report = cluster.run(events, sla_ms=args.sla_ms,
                         percentile=args.sla_percentile, scenario=scen_name,
                         online=online)
    print(f"[serve] {cfg.name}:")
    print(report.summary())
    _emit_obs(args, tracer, extra_metrics=cluster.metrics, report=report)
    return 0 if report.ok else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
