"""Training launcher — a thin argparse adapter over `repro.engine.Engine`.

The pipeline (plan -> step factory -> param/opt-state init -> sharding ->
checkpointed TrainLoop) lives in `repro.engine`; this module only maps
flags onto `Engine(...)` / `TrainSession`. Runs REAL steps on the local
device set (CPU smoke / TPU pod). For the compile-only multi-pod
validation use `repro.launch.dryrun`.

  PYTHONPATH=src python -m repro.launch.train --workload dlrm \
      --config dlrm-rm2-small-unsharded --steps 200 --ckpt-dir /tmp/ck
  PYTHONPATH=src python -m repro.launch.train --workload lm \
      --arch internlm2-1.8b --smoke --steps 50
"""
from __future__ import annotations

import argparse
import sys
from typing import Optional

from repro.configs.registry import get_arch, get_dlrm
from repro.engine import Engine


def _run_with_deltas(args, session):
    """Run training in --delta-every-steps segments, delta-encoding the
    embedding tables between segments into a recorded
    `repro.online.DeltaChannel` JSONL (--emit-deltas). The stream is what
    `repro.launch.serve --replay-deltas` feeds a live fleet."""
    import numpy as np

    from repro.online import DeltaChannel, diff_tables

    params = session.params
    if not isinstance(params, dict) or "tables" not in params:
        raise SystemExit(
            "--emit-deltas needs stacked params with a 'tables' leaf "
            "(dlrm workload, --plan none, no host tier)")
    from repro.core.table_layout import to_rows

    d = session.cfg.embed_dim
    channel = DeltaChannel()
    seg = max(1, args.delta_every_steps)
    snap = to_rows(np.array(params["tables"]), d)
    reports = []
    done = 0
    version = 0
    while done < args.steps:
        n = min(seg, args.steps - done)
        reports.append(session.run(n))
        done += n
        version += 1
        new = to_rows(np.array(session.params["tables"]), d)
        channel.push(diff_tables(
            snap, new, version=version, t_emit_s=version * args.delta_dt_s,
            step=done, train_loss=reports[-1].last_loss))
        snap = new
    n_batches = channel.record(args.emit_deltas)
    rows = sum(b.n_rows for b in channel.emitted)
    print(f"[train] deltas -> {args.emit_deltas} ({n_batches} batches, "
          f"{rows} row updates)")
    first, last = reports[0], reports[-1]
    import dataclasses

    return dataclasses.replace(
        last, start_step=first.start_step,
        steps_run=sum(r.steps_run for r in reports),
        first_loss=first.first_loss,
        history=[h for r in reports for h in r.history])


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", choices=["dlrm", "lm"], default="dlrm")
    p.add_argument("--config", default="dlrm-rm2-small-unsharded")
    p.add_argument("--arch", default="internlm2-1.8b")
    p.add_argument("--smoke", action="store_true",
                   help="reduced config (CPU-sized)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--alpha", type=float, default=0.0,
                   help="zipf locality of the synthetic index stream")
    p.add_argument("--optimizer", default="sgd", choices=["sgd", "adagrad"])
    p.add_argument("--exchange", default="partial_pool",
                   choices=["partial_pool", "unpooled"])
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--model-axis", type=int, default=1)
    p.add_argument("--plan", choices=["none", "auto"], default="none",
                   help="auto: profile + place tables, execute placements")
    p.add_argument("--fast-mb", type=float, default=None,
                   help="per-chip fast-tier capacity (MiB) for --plan auto")
    p.add_argument("--pipeline-depth", type=int, default=0,
                   help="micro-batch pipeline depth inside the train step "
                        "(overlaps embedding exchange with MLP compute); "
                        "0 = auto (planner-chosen under --plan auto, else 1)")
    p.add_argument("--compress-grads", action="store_true",
                   help="int8 block-quantized dense-grad all-reduce with "
                        "error feedback (optim/compression.py)")
    p.add_argument("--host-capacity-mb", type=float, default=None,
                   help="device embedding budget (MiB): tables beyond it "
                        "train through the pinned-host chunk tier "
                        "(repro.hoststore; SGD only, dirty chunks write "
                        "back to host)")
    p.add_argument("--host-chunk-rows", type=int, default=None,
                   help="rows per host-tier chunk (default: perf-model "
                        "pick over the PCIe link)")
    p.add_argument("--host-hot-fraction", type=float, default=0.5,
                   help="share of the device budget spent on the HBM hot "
                        "slab (the rest is the chunk cache — lower it if "
                        "a step's working set overflows the cache)")
    p.add_argument("--calibration", default=None, metavar="PATH",
                   help="measured-hardware calibration JSON "
                        "(repro.core.calibration): host_link overrides "
                        "the PCIe model")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--emit-deltas", default=None, metavar="PATH",
                   help="record the run's embedding-row updates as a "
                        "delta-channel JSONL (repro.online): the table "
                        "rows each --delta-every-step segment changed, "
                        "versioned + timestamped, consumable by "
                        "repro.launch.serve --replay-deltas")
    p.add_argument("--delta-every-steps", type=int, default=10,
                   help="trainer steps folded into one delta batch")
    p.add_argument("--delta-dt-s", type=float, default=1.0,
                   help="virtual seconds between delta emits (stamps "
                        "t_emit_s = version x this; match it to the "
                        "serving trace's timescale)")
    p.add_argument("--report-json", default=None, metavar="PATH",
                   help="write the run report (train report + plan, when "
                        "one was built) as JSON")
    args = p.parse_args(argv)

    if args.workload == "dlrm":
        cfg = get_dlrm(args.config)
    else:
        cfg = get_arch(args.arch)
        if args.plan != "none":
            print("[train] --plan is DLRM-only; ignoring it for the lm "
                  "workload")
            args.plan = "none"
        if args.pipeline_depth > 1 or args.compress_grads:
            print("[train] --pipeline-depth/--compress-grads are DLRM-only; "
                  "ignoring them for the lm workload")
            args.pipeline_depth, args.compress_grads = 0, False
        if args.host_capacity_mb is not None:
            print("[train] --host-capacity-mb is DLRM-only; ignoring it "
                  "for the lm workload")
            args.host_capacity_mb = None
    if args.smoke:
        cfg = cfg.reduced()

    engine = Engine(cfg, model_axis=args.model_axis, plan=args.plan,
                    exchange=args.exchange, optimizer=args.optimizer,
                    lr=args.lr, alpha=args.alpha, seed=args.seed,
                    fast_mb=args.fast_mb,
                    pipeline_depth=args.pipeline_depth or None,
                    compress_grads=args.compress_grads,
                    host_capacity_mb=args.host_capacity_mb,
                    host_chunk_rows=args.host_chunk_rows,
                    host_hot_fraction=args.host_hot_fraction,
                    calibration=args.calibration, verbose=True)
    session = engine.train_session(ckpt_dir=args.ckpt_dir,
                                   ckpt_every=args.ckpt_every,
                                   batch=args.batch, seq=args.seq,
                                   schedule_steps=args.steps)
    if args.emit_deltas:
        report = _run_with_deltas(args, session)
    else:
        report = session.run(args.steps)
    print(report.summary())
    if args.report_json:
        import json

        plan_report = engine.plan_report("training")
        payload = {"train": report.asdict(),
                   "plan": plan_report.asdict() if plan_report else None}
        with open(args.report_json, "w") as f:
            json.dump(payload, f, indent=2, default=str)
            f.write("\n")
        print(f"[train] report -> {args.report_json}")
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
