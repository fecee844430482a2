"""Benchmark driver: one section per paper table/figure + the roofline
aggregation. `PYTHONPATH=src python -m benchmarks.run [--only NAME]`."""
import argparse
import sys
import time

from benchmarks import (bench_cluster, bench_elastic, bench_engine_serve,
                        bench_fabric, bench_hoststore, bench_online,
                        bench_pipeline, bench_tiered_embedding, fig6_membw,
                        fig8_inference, fig9_latency, fig10_sharding,
                        fig11_training, fig12_13_phases, kernel_bench,
                        roofline, table16_17_upper_bounds)

SECTIONS = [
    ("fig6", fig6_membw.main),
    ("fig8", fig8_inference.main),
    ("fig9", fig9_latency.main),
    ("fig10", fig10_sharding.main),
    ("fig11", fig11_training.main),
    ("fig12_13", fig12_13_phases.main),
    ("table16_17", table16_17_upper_bounds.main),
    ("kernels", lambda extra=(): kernel_bench.main([*extra])),
    ("tiered_embedding", lambda extra=(): bench_tiered_embedding.main(
        [*extra])),
    ("engine_serve", lambda extra=(): bench_engine_serve.main(
        ["--queries", "80", *extra])),
    ("pipeline", lambda extra=(): bench_pipeline.main(["--tiny", *extra])),
    ("cluster", lambda extra=(): bench_cluster.main(["--tiny", *extra])),
    ("fabric", lambda extra=(): bench_fabric.main(["--tiny", *extra])),
    ("elastic", lambda extra=(): bench_elastic.main(["--tiny", *extra])),
    ("hoststore", lambda extra=(): bench_hoststore.main(["--tiny", *extra])),
    ("online", lambda extra=(): bench_online.main(["--tiny", *extra])),
    ("roofline", roofline.main),
]

# sections that can write a BENCH_<name>.json artifact (benchmarks/_artifacts)
EMITS_JSON = {"cluster", "elastic", "fabric", "hoststore", "kernels",
              "online", "pipeline", "tiered_embedding", "engine_serve"}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   choices=[n for n, _ in SECTIONS], metavar="SECTION",
                   help="run a single section; one of: "
                        + ", ".join(n for n, _ in SECTIONS))
    p.add_argument("--emit-json", action="store_true",
                   help="sections that support it write their claims + "
                        "scalars as BENCH_<section>.json at the repo root")
    args = p.parse_args(argv)
    failed = []
    for name, fn in SECTIONS:
        if args.only and name != args.only:
            continue
        t0 = time.time()
        print(f"{'='*72}\n== {name}\n{'='*72}")
        rc = (fn(("--emit-json",)) if args.emit_json and name in EMITS_JSON
              else fn())
        # sections signal a failed headline claim with a nonzero return
        if rc:
            failed.append(name)
        print(f"== {name} done in {time.time()-t0:.1f}s"
              f"{' [FAILED]' if rc else ''}\n")
    if failed:
        print(f"sections with failed claims: {', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
