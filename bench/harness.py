"""One run of one benchmark cell: set-up, measured window, check, result.

Everything specific to a cell is found by name from BENCHMARK.json:

  configs/<file>          the configuration as run (sizes, cuts, precision)
  refs/<reference>.py     its plain reference (named by the configuration)
  traffic/<mix>.json      the traffic mix; its "driver" names drivers/<d>.py
  cells/<workload>.json   the limits that decide ``correct``
  metrics/<metric>.py     one reader per metric, end to end and per layer
                          (``q.part`` falls back to metrics/q.py)

A driver module gives ``setup(env)``, ``window(state, env, seconds)``,
``release(state)`` and ``check(answers, env)``. A metric reader gives
``read(run)``, returning a number or None when it finds nothing to read.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def load_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def _import_paths(bench: str) -> None:
    """The benchmark's own modules import each other by name."""
    for p in (os.path.join(bench, "drivers"), bench):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Spec:
    """Everything one cell's run reads, found by name."""

    name: str
    cell: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench: str = BENCH

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    def path(self, *parts: str) -> str:
        return os.path.join(self.bench, *parts)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_spec(name: str, root: str = ROOT) -> Spec:
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest.get("workloads", [])}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                        f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest.get("configs", [])}
    if cell["config"] not in configs:
        raise SpecError(f"workload {name} names unknown config "
                        f"{cell['config']!r}")
    bench = os.path.join(root, manifest["paths"][0])
    config = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(bench, "traffic",
                                     cell["traffic"] + ".json"))
    limits = load_json(os.path.join(bench, "cells", name + ".json"))
    return Spec(
        name=name, cell=cell, config=config, traffic=traffic,
        limits=limits.get("limits", {}),
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)],
        bench=bench)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass
class Env:
    """What a driver sees: the spec, the seed, the devices it may use."""

    spec: Spec
    seed: int
    devices: list
    trace: bool
    seconds: float
    log: Any = log

    @property
    def cfg(self) -> dict:
        return self.spec.config

    @property
    def traffic(self) -> dict:
        return self.spec.traffic

    def reference(self):
        return load_module(self.spec.path("refs", self.cfg["reference"]
                                          + ".py"),
                           "bench_ref_" + self.cfg["reference"])

    @staticmethod
    def annotate(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)


@dataclass
class Run:
    """What a metric reader sees."""

    spec: Spec
    records: Dict[str, Any]
    peak: Dict[str, float]
    trace: Any = None            # tracing.TraceSummary on --trace 1
    setup_s: float = 0.0

    @property
    def cfg(self) -> dict:
        return self.spec.config

    @property
    def chips(self) -> int:
        return self.spec.chips


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed directory in the checkout (or
    JAX_COMPILATION_CACHE_DIR), caching every program however fast it
    compiled, so only a cell's first run in a checkout compiles."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache as program_cache
    where = program_cache()
    import jax
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts backend compiles and cache loads while ``active``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event in self.EVENTS:
            self.count += 1


def reader_path(spec: Spec, metric: str) -> str:
    """metrics/<metric>.py, or else the reader of the quantity the name
    splits: ``idle_share.train`` falls back to metrics/idle_share.py."""
    own = spec.path("metrics", metric + ".py")
    if os.path.isfile(own) or "." not in metric:
        return own
    return spec.path("metrics", metric.split(".")[0] + ".py")


def read_metrics(run: Run, metrics: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        reader = load_module(reader_path(run.spec, m["name"]),
                             "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(run)
        if v is None:
            log(f"metric {m['name']}: nothing to read, left out")
            continue
        if not math.isfinite(v):
            raise RuntimeError(f"metric {m['name']} read {v}")
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def parse_args(argv):
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, t_start: Optional[float] = None,
         require_chip: bool = True, spec: Optional[Spec] = None) -> int:
    """Run a cell; prints the result line and returns 0, or returns
    nonzero with no result line. ``require_chip=False`` and ``spec`` are for
    tests on the CPU, which skip the look for a chip."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    try:
        spec = spec or load_spec(args.workload)
        peaks = load_json(os.path.join(spec.bench, "peaks.json"))["devices"]
    except SpecError as e:
        log(f"error: {e}")
        return 2
    _import_paths(spec.bench)
    cache = use_compile_cache()
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if require_chip:
        if devs[0].platform != "tpu":
            log(f"error: no TPU: JAX finds {devs[0].platform} devices")
            return 1
        if len(devs) < spec.chips:
            log(f"error: {spec.name} needs {spec.chips} chips, JAX finds "
                f"{len(devs)}")
            return 1
        if kind not in peaks:
            log(f"error: device kind {kind!r} is not in peaks.json")
            return 1
    peak = peaks.get(kind, {})
    used = devs[:spec.chips]
    log(f"{spec.name} seed {args.seed}: {devs[0].platform} {kind} "
        f"x{len(devs)}, using {len(used)}; jax {jax.__version__}; "
        f"compile cache {cache}")
    env = Env(spec=spec, seed=args.seed, devices=used, trace=bool(args.trace),
              seconds=args.seconds)
    driver = load_module(spec.path("drivers", spec.traffic["driver"] + ".py"),
                         "bench_driver_" + spec.traffic["driver"])
    compiles = CompileCounter()

    state = driver.setup(env)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    compiles.active = True
    try:
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        try:
            records = driver.window(state, env, args.seconds)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        compiles.active = False
        log(f"compiles or cache loads inside the window: {compiles.count}")
        mem = memory_peak(used)
        answers = driver.release(state)
        del state
        gc.collect()
        summary = None
        if trace_dir:
            from tracing import load
            summary = load(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    t_check = time.perf_counter()
    checks = driver.check(answers, env)
    log(f"check took {time.perf_counter() - t_check:.3f} s")
    failed = int(records.get("failed", 0))
    correct = (failed == 0 and compiles.count == 0
               and all(c["value"] <= c["limit"] for c in checks))

    run = Run(spec=spec, records=records, peak=peak, trace=summary,
              setup_s=setup_s)
    metrics = read_metrics(run, spec.per_layer if args.trace
                           else spec.end_to_end)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": bool(correct),
              "attempted": int(records.get("attempted", 0)),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        busy = [summary.busy_s(c) for c in summary.chips()]
        for c, b in zip(summary.chips(), busy):
            log(f"chip {c}: busy {b:.6f} s of {summary.window_s:.6f} s, "
                f"idle share {100 * (1 - b / summary.window_s):.4f}%")
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_by_host(10)}
    compared = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                for c in checks}
    compared["compiles_in_window"] = {"value": compiles.count, "limit": 0}
    compared["failed"] = {"value": failed, "limit": 0}
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0
