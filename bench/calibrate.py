#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds, for setting
their limits: the program's sound runs, and the control (the reference in
the program's place, in the next precision below the configuration's).

    python3 bench/calibrate.py --workload <name> --seconds 5 \
        --seeds 11,12,... [--control-seeds 11,12,13] [--faults]

Runs in one process: for each seed, the cell's own set-up and load for a
short window, then the same check as a run, then the control on the same
sample. ``--faults`` (training cells) also reads the faults planted in the
reference in the program's place. Prints one JSON line per seed and a
summary line. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    harness._import_paths(spec.bench)
    harness.use_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < spec.chips:
        harness.log(f"error: needs {spec.chips} TPU chips, JAX finds "
                    f"{len(devs)} {devs[0].platform}")
        return 1
    driver = harness.load_module(
        spec.path("drivers", spec.traffic["driver"] + ".py"), "cal_driver")
    control = [int(s) for s in args.control_seeds.split(",") if s]
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        env = harness.Env(spec=spec, seed=seed, devices=devs[:spec.chips],
                          trace=False, seconds=args.seconds)
        t0 = time.perf_counter()
        st = driver.setup(env)
        rec = driver.window(st, env, args.seconds)
        answers = driver.release(st)
        del st
        gc.collect()
        row = {"seed": seed, "attempted": rec["attempted"],
               "failed": rec["failed"],
               "program": {c["name"]: c["value"]
                           for c in driver.check(answers, env)}}
        cast = env.reference().CASTS[0]
        if seed in control:
            # the control in the program's place, compared as a run is
            row["control"] = {c["name"]: c["value"] for c in
                              driver.control_check(answers, env, cast)}
        if args.faults and hasattr(driver, "fault_readings"):
            row["faults"] = driver.fault_readings(answers, env)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    names = sorted(rows[0]["program"])
    summary = {"workload": spec.name, "seeds": len(rows)}
    for n in names:
        summary[n] = {"program_max": max(r["program"][n] for r in rows)}
        ctl = [r["control"][n] for r in rows if "control" in r]
        if ctl:
            summary[n]["control_min"] = min(ctl)
    print(json.dumps({"summary": summary}), flush=True)
    return 0



if __name__ == "__main__":
    sys.exit(main())
