#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest offered rate at
which the queue does not grow.

    python3 bench/sweep.py --workload <open-loop cell> --seconds 40 \
        --rates 6.5,7,7.5,8,8.5,9 --orders 3 [--load 0.8] [--seed n]

One set-up, then, for each rate in rising order, ``--orders`` windows
through the cell's own driver on the wall clock, each with the rate's
arrivals in another order (`gen.arrival_offsets`, order seeds
``seed + k``). A window passes when its queue did not grow: the mean
latency of its last quarter of queries is at most ``GROWTH`` times that of
its first quarter, and the last queries drain within ``DRAIN_S`` of the
end of the schedule. The knee is the highest rate at which that rate and
every lower rate passed in every order; the sweep stops at the first rate
that fails.

Prints one JSON line per window and a summary line with the knee and
``--load`` times it, the rate for the cell. That rate is then fixed in the
cell's traffic file; the benchmark never searches for one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

GROWTH = 1.5     # last-quarter mean latency over the first quarter's
DRAIN_S = 1.0    # seconds from the last due query to its answer


def window_passes(row: dict) -> bool:
    return (row["last_quarter_mean_ms"]
            <= GROWTH * row["first_quarter_mean_ms"]
            and row["drain_s"] <= DRAIN_S)


def knee(rows: List[dict]) -> Optional[float]:
    """The highest rate whose windows, and those of every lower rate, all
    passed; None when the lowest rate already failed."""
    best = None
    for qps in sorted({r["qps"] for r in rows}):
        if not all(window_passes(r) for r in rows if r["qps"] == qps):
            break
        best = qps
    return best


def summarize(rec: dict, qps: float, order: int) -> Dict[str, float]:
    import numpy as np
    lat = rec["latency_ms"]
    q = max(1, len(lat) // 4)
    calls = np.asarray(rec["flush_call_ms"])
    return {
        "qps": qps, "order": order, "queries": len(lat),
        "p50_ms": float(np.percentile(lat, 50)),
        "p95_ms": float(np.percentile(lat, 95)),
        "first_quarter_mean_ms": float(lat[:q].mean()),
        "last_quarter_mean_ms": float(lat[-q:].mean()),
        "drain_s": rec["span_s"] - rec["window_s"],
        "mean_flush_queries": float(np.mean(rec["flushes"])),
        "late_p95_ms": float(np.percentile(rec["late_ms"], 95)),
        "flush_call_max_ms": float(calls.max()),
        "flush_calls_over_1s": int(np.sum(calls > 1000.0))}


def main(argv=None) -> int:
    import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--orders", type=int, default=3)
    ap.add_argument("--load", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=987_654_321)
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    if spec.traffic.get("loop") != "open":
        harness.log(f"error: {spec.name} is not an open-loop cell")
        return 2
    harness._import_paths(spec.bench)
    harness.use_compile_cache()
    import jax
    import gen
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < spec.chips:
        harness.log(f"error: needs {spec.chips} TPU chips")
        return 1
    drv = harness.load_module(spec.path("drivers", "serve.py"), "sweep_serve")
    env = harness.Env(spec=spec, seed=args.seed, devices=devs[:spec.chips],
                      trace=False, seconds=args.seconds)
    st = drv.setup(env)

    def run(qps: float, order: int) -> dict:
        st.futures.clear()
        st.pool_of.clear()
        st.offsets = gen.arrival_offsets(int(round(qps * args.seconds)), qps,
                                         args.seed + order)
        row = summarize(drv.window(st, env, args.seconds), qps, order)
        row["passes"] = window_passes(row)
        print(json.dumps(row), flush=True)
        return row

    rows: List[dict] = []
    for qps in sorted(float(x) for x in args.rates.split(",")):
        for k in range(args.orders):
            rows.append(run(qps, k))
            if not rows[-1]["passes"]:
                break
        if not rows[-1]["passes"]:
            break
    found = knee(rows)
    summary = {"knee_qps": found,
               "qps": None if found is None else round(args.load * found, 2),
               "rule": {"growth": GROWTH, "drain_s": DRAIN_S,
                        "orders": args.orders, "seconds": args.seconds}}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
