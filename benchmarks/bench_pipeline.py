"""Micro-batch pipeline sweep: depth x exchange x batch.

Two views of the same question — how much exchange time can micro-batch
pipelining (repro.parallel.build_step, pipeline_depth=k) hide behind MLP
compute?

  1. MODEL: `perf_model.pipelined_breakdown` on the RecSpeed system — the
     executed-schedule phase breakdown (exchange stage vs compute stage per
     micro-batch) with the `pipeline_overlap` term, swept over depth x
     exchange x batch. depth=1 is the strictly-serial schedule the
     pre-refactor step factories ran.
  2. MEASURED: real serve-step wall clock on the devices this process
     holds (one chip owns one process, so the sweep never starts a child),
     same sweep. On CPU devices the collectives are memcpys and the overlap
     itself is invisible — there this view checks the pipelined step's
     overhead (slicing + k-fold smaller intermediates), not the wire win.

  PYTHONPATH=src python -m benchmarks.bench_pipeline [--tiny]
"""
from __future__ import annotations

import argparse
import dataclasses
import statistics
import sys
import time
from typing import List, Optional

CONFIGS = [
    # (registry name, row-wise exchange mode or None for table_wise)
    ("dlrm-rm2-small-unsharded", None),
    ("dlrm-rm2-small-sharded", "partial_pool"),
    ("dlrm-rm2-small-sharded", "unpooled"),
    ("dlrm-rm2-large-sharded", "partial_pool"),
]


# ---------------------------------------------------------------------------
# Part 1: executed-schedule model sweep
# ---------------------------------------------------------------------------
def model_sweep(batches: List[int], depths: List[int], mode: str):
    """Returns (any_win, best) where best is the strongest modeled
    (config, exchange, batch, depth, speedup) row."""
    from repro.configs.registry import get_dlrm
    from repro.core import perf_model

    sys_cfg = perf_model.recspeed_system()
    print(f"# model: executed schedule on {sys_cfg.name} "
          f"(n={sys_cfg.n_chips}), mode={mode}")
    print("config,exchange,batch,depth,t_step_us,stage_exch_us,"
          "stage_comp_us,overlap_us,speedup_vs_serial,best")
    any_win = False
    top = None
    for name, exch in CONFIGS:
        cfg = get_dlrm(name)
        exch_label = exch or "pooled_a2a"
        for B in batches:
            bcfg = dataclasses.replace(cfg, batch_size=B)
            rows = {}
            for k in depths:
                if B % (k * sys_cfg.n_chips):
                    continue
                rows[k] = perf_model.pipelined_breakdown(
                    bcfg, sys_cfg, mode, pipeline_depth=k,
                    row_wise_exchange=exch or "unpooled")
            if not rows:
                continue
            t1 = rows.get(1).t_step if 1 in rows else None
            best = min(rows, key=lambda k: rows[k].t_step)
            for k, bd in sorted(rows.items()):
                nt = bd.notes
                speed = (t1 / bd.t_step) if t1 else float("nan")
                print(f"{name},{exch_label},{B},{k},{bd.t_step*1e6:.1f},"
                      f"{nt['t_stage_exchange_mb']*1e6:.2f},"
                      f"{nt['t_stage_compute_mb']*1e6:.2f},"
                      f"{nt['pipeline_overlap']*1e6:.1f},"
                      f"{speed:.2f}x,{'*' if k == best else ''}")
            if best > 1:
                any_win = True
                speed_best = (t1 / rows[best].t_step) if t1 else 0.0
                if top is None or speed_best > top["speedup"]:
                    top = {"config": name, "exchange": exch_label,
                           "batch": B, "depth": best,
                           "speedup": speed_best}
    print(f"model: pipeline_depth>1 beats the serial schedule on at least "
          f"one swept config: {any_win}")
    return any_win, top


# ---------------------------------------------------------------------------
# Part 2: measured serve-step sweep (in this process, its devices)
# ---------------------------------------------------------------------------
def measured_sweep(batches: List[int], depths: List[int], iters: int,
                   rounds: int) -> List[dict]:
    """Times the serve step over every (config, exchange, batch, depth)
    on ``jax.devices()``; prints one CSV row per timing and returns them
    as dicts."""
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_dlrm
    from repro.core import dlrm as dlrm_lib
    from repro.data import make_recsys_batch
    from repro.launch.mesh import make_mesh
    from repro.parallel import build_step, shard_dlrm_params

    n = len(jax.devices())
    mesh = make_mesh((1, n), ("data", "model"))
    print(f"# measured: serve step on {n} {jax.devices()[0].platform} "
          f"devices")
    print("config,exchange,batch,depth,t_step_ms,speedup_vs_serial,best")
    rows = []
    for name, exch in CONFIGS:
        cfg = get_dlrm(name).reduced()
        exch_label = exch or "pooled_a2a"
        for B in batches:
            bcfg = dataclasses.replace(cfg, batch_size=B)
            params = dlrm_lib.init_dlrm(jax.random.PRNGKey(0), bcfg)
            b = make_recsys_batch(bcfg, 0)
            times = {}
            for k in depths:
                if B % (k * n):
                    continue
                step = build_step(bcfg, mesh, mode="serve",
                                  exchange=exch or "partial_pool",
                                  pipeline_depth=k)
                sp = shard_dlrm_params(params, bcfg, mesh, ("data", "model"))
                step(sp, b["dense"], b["indices"]).block_until_ready()
                samples = []
                for _ in range(rounds):
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        out = step(sp, b["dense"], b["indices"])
                    out.block_until_ready()
                    samples.append((time.perf_counter() - t0) / iters)
                times[k] = statistics.median(samples)
            if not times:
                continue
            t1 = times.get(1)
            best = min(times, key=times.get)
            for k, t in sorted(times.items()):
                speed = (t1 / t) if t1 else float("nan")
                print(f"{name},{exch_label},{B},{k},{t*1e3:.2f},"
                      f"{speed:.2f}x,{'*' if k == best else ''}")
                rows.append({"config": name, "exchange": exch_label,
                             "batch": B, "depth": k, "t_step_ms": t * 1e3,
                             "speedup": speed})
    return rows


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="1024,4096,16384")
    ap.add_argument("--measured-batches", default="256,1024",
                    help="device-timed sweep batches (reduced config sizes)")
    ap.add_argument("--depths", default="1,2,4,8")
    ap.add_argument("--mode", default="training",
                    choices=["inference", "training"])
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--no-measure", action="store_true",
                    help="model sweep only (no device timing)")
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized: small batch, fewer reps")
    ap.add_argument("--emit-json", action="store_true",
                    help="write BENCH_pipeline.json (claims + scalars)")
    args = ap.parse_args(argv)
    batches = [int(b) for b in args.batches.split(",")]
    measured_batches = [int(b) for b in args.measured_batches.split(",")]
    depths = [int(d) for d in args.depths.split(",")]
    if args.tiny:
        measured_batches, depths = [64], [1, 2, 4]
        args.iters, args.rounds = 2, 3
        # big enough to amortize the per-micro-batch collective latency —
        # the regime where the planner actually picks depth > 1
        batches = [4096]
    ok, top = model_sweep(batches, depths, args.mode)
    measured = []
    if not args.no_measure:
        measured = measured_sweep(measured_batches, depths, args.iters,
                                  args.rounds)
    if args.emit_json:
        from benchmarks._artifacts import write_bench_json
        claims = [("model_overlap", ok,
                   "modeled executed schedule: pipeline_depth>1 beats the "
                   "serial schedule on at least one swept config"
                   + (f" (best {top['speedup']:.2f}x at depth "
                      f"{top['depth']} on {top['config']}/"
                      f"{top['exchange']} B={top['batch']})" if top
                      else ""))]
        if not args.no_measure:
            deep = [r for r in measured if r["depth"] > 1]
            worst = min((r["speedup"] for r in deep), default=0.0)
            meas_ok = bool(deep) and worst >= 0.5
            claims.append((
                "measured_overhead", meas_ok,
                f"real serve-step on this process's devices: {len(deep)} "
                f"pipelined timings collected, worst depth>1 speedup "
                f"{worst:.2f}x >= 0.5x (slicing overhead bounded; CPU "
                f"collectives hide no wire time)"))
        write_bench_json("pipeline", claims, {
            "model_best": top,
            "measured_rows": measured,
        })
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
