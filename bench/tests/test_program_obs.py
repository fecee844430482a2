"""CPU tests of `bench/program_obs.py`, the readers of what the program
records about itself, and `bench/attribute.py`.

  * the program's host spans kept from synthetic planes, and each idle gap
    put down to the innermost span; with only ``bench.*`` spans, the same
    breakdown as the harness's;
  * the HLO text -> named scope map, on a hand-written module and on the
    tiny train step and the serve session's own step compiled here; ops
    outside the step's runs in the trace go to other programs;
  * every per-layer metric the harness had reads the same value on the
    same synthetic trace as before;
  * each new reader returns its number, or None where nothing was recorded.
"""
import dataclasses
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), os.path.join(BENCH, "drivers"),
           BENCH, os.path.join(BENCH, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402
import program_obs  # noqa: E402
import tracing  # noqa: E402
from test_bench_harness import (_Ev, _Line, _Plane, _Profile,  # noqa: E402
                                _run, _synthetic, _tiny_spec)

NS = 1e9


def _reader(name):
    spec = harness.load_spec("rm2-small.serve.closed-uniform")
    return harness.load_module(harness.reader_path(spec, name),
                               "test_metric_" + name.replace(".", "_"))


def _with_program_spans():
    """The synthetic trace, with the program's spans inside bench.submit
    and a host event that is neither the benchmark's nor the program's."""
    p = _synthetic()
    p.planes[0].lines[0] = _Line("python", [
        ("bench.window", 1 * NS, 11 * NS),
        ("bench.submit", 1 * NS, 4 * NS),
        ("serve.flush", 1 * NS, 4 * NS),
        ("serve.assemble", 1 * NS, 1.5 * NS),
        ("serve.dispatch", 1.5 * NS, 1.8 * NS),
        ("serve.device_wait", 1.8 * NS, 3.9 * NS),
        ("bench.idle", 6 * NS, 9 * NS),
        ("jit_serve", 1.5 * NS, 1.8 * NS)])
    return p


# ------------------------------------------------------------ host spans
def test_program_spans_are_kept_beside_the_benchmarks():
    spans = program_obs.host_spans(_with_program_spans())
    names = [s.name for s in spans]
    assert "bench.window" not in names and "jit_serve" not in names
    assert names.count("serve.flush") == 1 and "bench.submit" in names
    assert [s.name for s in program_obs.host_spans(
        _with_program_spans(), ("serve.",))][0] == "serve.flush"


def test_idle_goes_to_the_innermost_span():
    p = _with_program_spans()
    # chip 0 also idles in (1.2, 1.4), which bench.submit, serve.flush and
    # serve.assemble all cover: the innermost, serve.assemble, takes it
    ops = p.planes[1].lines[0]
    ops.events = [e for e in ops.events if e.name != "fusion.1"] + [
        _Ev("fusion.9", 0, 1.2 * NS), _Ev("fusion.8", 1.4 * NS, 2 * NS)]
    s = tracing.reduce_profile(p)
    got = dict(program_obs.idle_by_span(s, program_obs.host_spans(p)))
    # (6, 9.5): bench.idle to 9, then nothing but the window
    assert got == {"bench.idle": pytest.approx(3.0),
                   "none": pytest.approx(0.5),
                   "serve.assemble": pytest.approx(0.2)}
    # one gap across sibling spans is cut at their ends
    one = tracing.TraceSummary((0.0, 4.0), {0: [
        tracing.Op("a", 0.0, 1.0), tracing.Op("b", 3.0, 4.0)]})
    spans = [tracing.Op("bench.submit", 0.0, 4.0),
             tracing.Op("serve.flush", 0.5, 3.5),
             tracing.Op("serve.assemble", 0.5, 1.5),
             tracing.Op("serve.dispatch", 1.5, 2.0)]
    got = dict(program_obs.idle_by_span(one, spans, chip=0))
    assert got == {"serve.assemble": pytest.approx(0.5),
                   "serve.dispatch": pytest.approx(0.5),
                   "serve.flush": pytest.approx(1.0)}


def test_harness_breakdown_is_unchanged_by_the_programs_spans():
    """The harness keeps only bench.* spans and puts a whole gap down to
    the one covering most of it, as before; the program's spans only feed
    `idle_by_span`, which cuts the same gaps finer."""
    bench_only = tracing.reduce_profile(_synthetic())
    with_program = tracing.reduce_profile(_with_program_spans())
    assert with_program.host == bench_only.host
    assert with_program.idle_by_host() == bench_only.idle_by_host()
    assert dict(bench_only.idle_by_host()) == {
        "bench.idle": pytest.approx(3.5)}
    assert dict(program_obs.idle_by_span(
        bench_only, program_obs.host_spans(_synthetic()))) == {
        "bench.idle": pytest.approx(3.0), "none": pytest.approx(0.5)}


# ------------------------------------------------------------ scope map
HLO = """HloModule jit_step, entry_computation_layout={()->f32[4]}

%fused_computation.5 (param_0: f32[4], param_1: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %param_1 = f32[4]{0} parameter(1)
  %reshape.1 = f32[4]{0} reshape(%param_1), metadata={op_name="jit(step)/dlrm.sparse_update/vmap(jit(_where))/select_n"}
  ROOT %scatter.3 = f32[4]{0} scatter(%param_0, %reshape.1)
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element(%p), index=0
  %fusion.36 = s32[] fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.27
  ROOT %tuple.2 = (s32[], f32[4]{0}) tuple(%fusion.36, %get-tuple-element.1)
}

%fused_computation.27 (param_0.1: s32[]) -> s32[] {
  ROOT %param_0.1 = s32[] parameter(0)
}

ENTRY %main.9 (a: f32[4], b: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0), metadata={op_name="a"}
  %b = f32[4]{0} parameter(1)
  %fusion.2 = f32[4]{0} fusion(%a, %b), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/dlrm.sparse/vmap(jit(take_along_axis))/gather"}
  %copy.99 = f32[4]{0} copy(%fusion.2)
  %fusion.5 = f32[4]{0} fusion(%copy.99, %b), kind=kCustom, calls=%fused_computation.5
  %while.1 = (s32[], f32[4]{0}) while(%fusion.5), condition=%cond, body=%body, metadata={op_name="jit(step)/transpose(jvp(dlrm.top_mlp))/while"}
  %dot.7 = f32[4]{0} dot(%a, %b), metadata={op_name="jit(step)/transpose(jvp(dlrm.bottom_mlp))/mul;jit(step)/dlrm.interaction/dot_general"}
  %loss.1 = f32[] reduce(%a), metadata={op_name="jit(step)/reduce_sum"}
  ROOT %add.4 = f32[4]{0} add(%fusion.5, %dot.7)
}
"""


def test_hlo_text_maps_to_scopes():
    sc = program_obs.op_scopes(HLO)
    assert sc["fusion.2"] == "dlrm.sparse"            # its own metadata
    assert sc["copy.99"] == "dlrm.sparse"             # its operand's
    assert sc["fusion.5"] == "dlrm.sparse_update"     # its computation's
    assert sc["while.1"] == "dlrm.top_mlp"            # through transpose()
    assert sc["fusion.36"] == "dlrm.top_mlp"          # its loop's caller
    assert sc["dot.7"] == "dlrm.bottom_mlp"           # the first of ';'
    assert sc["loss.1"] is None and sc["a"] is None
    assert program_obs.scope_of("jit(f)/jvp(dlrm.a)/dlrm.b/x") == "dlrm.b"
    assert program_obs.scope_of("jit(f)/x") is None


def test_time_by_scope_counts_overlaps_once():
    sc = {"fusion.2": "dlrm.sparse", "fusion.5": "dlrm.sparse_update",
          "loss.1": None}
    ops = [tracing.Op("fusion.2", 0.0, 2.0), tracing.Op("fusion.2", 1.0, 3.0),
           tracing.Op("fusion.5", 3.0, 4.0), tracing.Op("loss.1", 4.0, 4.5),
           tracing.Op("copy.1", 5.0, 5.25)]
    assert program_obs.time_by_scope(ops, sc) == {
        "dlrm.sparse": 3.0, "dlrm.sparse_update": 1.0,
        program_obs.UNSCOPED: 0.5, program_obs.OTHER: 0.25}


def test_time_by_scope_counts_only_ops_inside_the_steps_runs():
    sc = {"copy.1": "dlrm.sparse", "fusion.5": "dlrm.sparse_update"}
    ops = [tracing.Op("copy.1", 0.0, 1.0),       # in the step's run
           tracing.Op("copy.1", 2.0, 2.5),       # another program's copy.1
           tracing.Op("fusion.5", 3.0, 4.0)]
    got = program_obs.time_by_scope(ops, sc, runs=[(3.0, 4.0), (0.0, 1.5)])
    assert got == {"dlrm.sparse": 1.0, "dlrm.sparse_update": 1.0,
                   program_obs.OTHER: 0.5}


def test_module_runs_come_from_the_xla_modules_line():
    p = _synthetic()
    p.planes[1].lines[1] = _Line("XLA Modules", [
        ("jit_serve(123)", 0, 2 * NS), ("jit_concatenate(9)", 2 * NS, 3 * NS),
        ("jit_serve(123)", 5 * NS, 8 * NS)])
    assert program_obs.module_runs(p, "jit_serve")[0] == [(0.0, 2.0),
                                                          (5.0, 8.0)]
    assert program_obs.step_module(
        "HloModule jit_serve, is_scheduled=true\n\nENTRY %main {") == \
        "jit_serve"


def test_step_text_of_the_tiny_serve_step_is_the_sessions_own(monkeypatch):
    from repro.engine import serving
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    built = []
    get = serving.ServeSession._get_step

    def spy(self, depth):
        built.append((self.max_batch_queries, self.query_size, depth))
        return get(self, depth)
    monkeypatch.setattr(serving.ServeSession, "_get_step", spy)
    spec = _tiny_spec("rm2-small.serve.closed-uniform")
    run = harness.Run(spec=spec, records={}, peak={})
    text = program_obs.step_text(run)
    tr = spec.traffic
    assert built == [(tr["max_batch_queries"], spec.config["batch_size"], 1)]
    assert {"dlrm.sparse", "dlrm.bottom_mlp", "dlrm.top_mlp"} <= set(
        program_obs.op_scopes(text).values())
    assert program_obs.step_module(text)


def test_step_text_of_the_tiny_train_step_carries_every_scope(monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    spec = _tiny_spec("rm2-small.train")
    run = harness.Run(spec=spec, records={}, peak={})
    sc = program_obs.op_scopes(program_obs.step_text(run))
    assert {"dlrm.sparse", "dlrm.sparse_update", "dlrm.dense_update",
            "dlrm.bottom_mlp", "dlrm.interaction",
            "dlrm.top_mlp"} <= set(sc.values())


def test_step_hlo_is_compiled_afresh_not_loaded_from_the_cache(tmp_path):
    """JAX keys its persistent cache on the program without its debug
    info: a cached build without the scopes is handed back for one with
    them, unless the step is compiled afresh."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    def build(scope):
        def step(x):
            if scope:
                with jax.named_scope("dlrm.sparse"):
                    return jnp.sin(x @ x) * 2
            return jnp.sin(x @ x) * 2
        return jax.jit(step)

    x = jnp.ones((8, 8))
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (str(tmp_path), 0.0, 0, True)):
            jax.config.update(k, v)
        cc.reset_cache()
        build(False).lower(x).compile()
        cached = build(True).lower(x).compile().as_text()
        fresh = program_obs.compile_afresh(build(True).lower(x)).as_text()
        assert jax.config.jax_enable_compilation_cache is True
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert "dlrm.sparse" not in cached and "dlrm.sparse" in fresh


# ------------------------------------------------------------ readers
def _summary():
    return tracing.reduce_profile(_synthetic())


def test_existing_readers_read_as_before_on_the_synthetic_trace():
    """The values the harness's readers gave on this trace before the
    program's spans and scopes existed."""
    records = {"flushes": [4, 4], "samples": 1600, "table_itemsize": 4,
               "owned_lookups_per_chip": [1000, 1000], "loop": "closed",
               "window_s": 10.0}
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = harness.Run(spec=_tiny_spec("rm2-small.serve.closed-uniform"),
                      records=records, peak=peak, trace=_summary())
    assert _reader("idle_share").read(run) == pytest.approx(
        100 * ((1 - 6.5 / 10) + (1 - 1.5 / 10)) / 2)
    assert _reader("exchange_exposed_ms").read(run) == pytest.approx(
        1e3 * (1.0 + 1.5) / 2 / 2)
    import flops
    b = flops.sparse_bytes(run.cfg, 1600, 1000, 4)
    assert _reader("sparse_hbm_roofline.serve").read(run) == pytest.approx(
        100 * b / 819e9 / 3.0)


@pytest.fixture()
def registry(monkeypatch):
    """A fresh process-wide registry for the program to publish to."""
    from repro.obs import metrics
    monkeypatch.setattr(metrics, "_DEFAULT", None)
    return metrics.default_registry()


def test_counter_readers_read_nothing_without_the_programs_counters(
        registry):
    run = harness.Run(spec=_tiny_spec("rm2-small.serve.poisson"),
                      records={"loop": "open", "attempted": 3}, peak={},
                      trace=_summary())
    for name in ("batcher_wait_ms", "flush_host_ms", "compile_s"):
        assert _reader(name).read(run) is None, name


def test_counter_readers_read_the_programs_counters(registry):
    spec = _tiny_spec("rm2-small.serve.poisson")      # warms 1+2+3+4
    wait = registry.histogram("serve_batch_wait_ms")
    for _ in range(10):
        wait.observe(0.0)                              # warm-up, one instant
    for v in (2.0, 4.0, 9.0):
        wait.observe(v)
    for phase, vals in (("assemble", (3.0, 5.0)), ("dispatch", (1.0, 1.0)),
                        ("device_wait", (300.0, 3000.0)),
                        ("copy_out", (0.5, 0.5))):
        for v in vals:
            registry.histogram(f"serve_{phase}_ms").observe(v)
    registry.histogram("serve_compile_ms").observe(1500.0)
    registry.histogram("serve_compile_ms").observe(700.0)
    run = harness.Run(spec=spec, records={"loop": "open", "attempted": 3},
                      peak={})
    assert _reader("batcher_wait_ms").read(run) == pytest.approx(5.0)
    assert _reader("flush_host_ms").read(run) == pytest.approx(5.5)
    assert _reader("compile_s").read(run) == pytest.approx(2.2)
    closed = dataclasses.replace(run, records={"loop": "closed"})
    assert _reader("batcher_wait_ms").read(closed) is None


def _scoped_run(monkeypatch, hlo):
    monkeypatch.setattr(program_obs, "step_text", lambda run: hlo)
    p = _Profile([
        _Plane("/host:CPU", [_Line("python", [
            ("bench.window", 0, 10 * NS)])]),
        _Plane("/device:TPU:0", [_Line("XLA Ops", [
            ("fusion.2", 0, 2 * NS), ("fusion.5", 2 * NS, 5 * NS),
            ("copy.99", 5 * NS, 5.5 * NS), ("loss.1", 5.5 * NS, 6 * NS),
            ("copy.1", 6 * NS, 6.5 * NS)])]),
        _Plane("/device:TPU:1", [_Line("XLA Ops", [
            ("fusion.2", 0, 1 * NS), ("fusion.5", 1 * NS, 2 * NS)])])])
    return harness.Run(spec=_tiny_spec("rm2-small.train"), records={},
                       peak={}, trace=tracing.reduce_profile(p))


def test_scope_readers_read_shares_of_busy_time(monkeypatch):
    run = _scoped_run(monkeypatch, HLO)
    # chip 0: busy 6.5 s; sparse 2.5 (fusion.2, copy.99), update 3.0
    # chip 1: busy 2.0 s; sparse 1.0, update 1.0
    sparse = (100 * 2.5 / 6.5 + 100 * 1.0 / 2.0) / 2
    update = (100 * 3.0 / 6.5 + 100 * 1.0 / 2.0) / 2
    assert _reader("sparse_update_share.train").read(run) == \
        pytest.approx(update)
    assert _reader("sparse_device_share.train").read(run) == \
        pytest.approx(sparse + update)
    assert _reader("sparse_device_share.serve").read(run) == \
        pytest.approx(sparse)


def test_scope_readers_read_nothing_without_scopes(monkeypatch):
    unscoped = program_obs.SCOPE.sub("other", HLO)
    run = _scoped_run(monkeypatch, unscoped)
    assert _reader("sparse_device_share.train").read(run) is None
    run = _scoped_run(monkeypatch, HLO)
    run.trace = None
    assert _reader("sparse_update_share.train").read(run) is None

    def broken(run):
        raise RuntimeError("no step")
    run = _scoped_run(monkeypatch, HLO)
    monkeypatch.setattr(program_obs, "step_text", broken)
    assert _reader("sparse_device_share.serve").read(run) is None


# ------------------------------------------------------------ whole runs
def test_traced_run_reports_the_program_metrics(monkeypatch, registry):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    res = _run(_tiny_spec("rm2-small.serve.poisson"), trace=1)
    assert res["correct"] is True
    m = res["metrics"]
    assert m["compile_s"]["value"] > 0
    assert m["batcher_wait_ms"]["value"] > 0


def test_attribute_prints_the_layer_breakdown(monkeypatch, registry,
                                              capsys):
    import json
    import attribute
    load, read = tracing.load, harness.read_metrics
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    spec = _tiny_spec("rm2-small.serve.poisson")
    rc = attribute.main(["--workload", spec.name, "--seed", "2147483999",
                         "--seconds", "1"], require_chip=False, spec=spec)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    res, extra = json.loads(lines[-2]), json.loads(lines[-1])
    assert res["correct"] is True
    assert extra["workload"] == spec.name and extra["window_s"] > 0
    hist = extra["program_histograms"]
    assert {"serve_assemble_ms", "serve_dispatch_ms", "serve_device_wait_ms",
            "serve_copy_out_ms", "serve_compile_ms",
            "serve_batch_wait_ms"} <= set(hist)
    assert extra["top_ops"] == []                 # no TPU ops on the CPU
    assert all(n.startswith("serve.") and d >= 0
               for n, _, d in extra["longest_program_spans"])
    assert set(extra["end_to_end_traced"]) == {"serve_p50_ms",
                                               "serve_p95_ms", "setup_s"}
    assert tracing.load is load and harness.read_metrics is read
