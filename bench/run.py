#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cells, their configurations, traffic
and metrics are listed in BENCHMARK.json. The last line of standard output
is one JSON object: correct, attempted, failed, metrics (end-to-end ones
with --trace 0, per-layer ones with --trace 1), device, breakdown (traced
runs) and, last, the numbers compared with their limits. Exits nonzero
with no result line where JAX finds no TPU, or fewer chips than the cell
needs.
"""
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    import harness
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
