#!/usr/bin/env python3
"""Run one cell traced and put its time down to the program's layers.

    python3 bench/attribute.py --workload <name> --seed <n> --seconds <s>
                               [--out <file.json>]

Runs the cell as ``bench/run.py ... --trace 1`` does, result line and
all, then prints one more JSON line (also written to ``--out``):

* ``idle_by_span``: per chip, idle time summed by the innermost host span
  covering each piece of it, the benchmark's ``bench.*`` and the
  program's ``serve.*`` alike (`program_obs.idle_by_span`);
* ``device_by_scope``: per chip, device time by the step's named scope,
  ``unscoped`` and ``other programs`` (`program_obs.time_by_scope`; an op
  is the step's only inside one of the step's runs in the trace's "XLA
  Modules" line, where the trace has one), and ``top_ops``: the harness's
  longest ops, each with its scope;
* ``end_to_end_traced``: the cell's end-to-end metrics read off this
  traced run (against a ``--trace 0`` run on the same seed: the cost of
  tracing);
* ``longest_program_spans``: the longest ``serve.*`` phases (name, start
  in seconds into the window, duration), so a stall shows where it was;
* ``busy_s``, ``window_s``, and the program's ``serve_*_ms`` histograms.

Run from the root of a checkout, on the chips the cell needs.
"""
import argparse
import glob
import json
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(BENCH, "drivers"), BENCH]

import harness  # noqa: E402
import program_obs  # noqa: E402
import tracing  # noqa: E402


def main(argv, *, require_chip: bool = True, spec=None) -> int:
    """``require_chip=False`` and ``spec`` are for tests on the CPU, as
    for `harness.main`."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    kept = {}
    load, read = tracing.load, harness.read_metrics

    def load_and_keep(trace_dir):
        from jax.profiler import ProfileData
        kept["summary"] = load(trace_dir)
        files = sorted(glob.glob(os.path.join(trace_dir, "**",
                                              "*.xplane.pb"), recursive=True))
        kept["profile"] = ProfileData.from_file(files[-1])
        kept["spans"] = program_obs.host_spans(kept["profile"])
        return kept["summary"]

    def read_and_keep(run, metrics):
        kept["run"] = run
        return read(run, metrics)

    tracing.load, harness.read_metrics = load_and_keep, read_and_keep
    try:
        rc = harness.main(["--workload", args.workload,
                           "--seed", str(args.seed),
                           "--seconds", str(args.seconds), "--trace", "1"],
                          t_start=T_START, require_chip=require_chip,
                          spec=spec)
    finally:
        tracing.load, harness.read_metrics = load, read
    if rc != 0 or "summary" not in kept:
        return rc or 1
    summary, spans, run = kept["summary"], kept["spans"], kept["run"]
    scopes = program_obs.step_scopes(run) or {}
    module = getattr(run, "_step_module", None)
    runs = (program_obs.module_runs(kept["profile"], module)
            if module else {})
    out = {
        "workload": args.workload, "seed": args.seed,
        "window_s": summary.window_s,
        "busy_s": {c: summary.busy_s(c) for c in summary.chips()},
        "end_to_end_traced": {k: v["value"] for k, v in
                              read(run, run.spec.end_to_end).items()},
        "idle_by_span": {c: program_obs.idle_by_span(summary, spans, chip=c)
                         for c in summary.chips()},
        "step_module": module,
        "device_by_scope": {c: program_obs.time_by_scope(
            summary.ops[c], scopes, runs.get(c) or None)
            for c in summary.chips()},
        "top_ops": [[name, t, scopes.get(name, program_obs.OTHER)]
                    for name, t in summary.top_ops(10)],
        "longest_program_spans": [
            [o.name, o.start - summary.window[0], o.end - o.start]
            for o in sorted((o for o in spans
                             if o.name.startswith(program_obs.PROGRAM_SPAN)
                             and o.name != "serve.flush"),
                            key=lambda o: o.start - o.end)[:8]],
        "program_histograms": {
            k: v for k, v in _registry().items() if k.startswith("serve_")},
    }
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


def _registry() -> dict:
    from repro.obs.metrics import default_registry
    return default_registry().snapshot()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
