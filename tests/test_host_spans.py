"""Spans and counters of the real-time serve path, and the step's named
device scopes.

  * `repro.obs.host_span`: a duration histogram with a registry, the same
    span (with its args) in a running profiler's host plane, nothing
    recorded for a block that raised;
  * `ServeSession` on an injected clock: one observation per flush in
    each phase histogram, `serve_batch_wait_ms` == drain - arrival for
    every query, the flush reasons; each padded batch shape compiles
    once, under `serve.compile`;
  * a CPU profiler trace of one flush: `serve.flush` holds its four
    children, in order;
  * the compiled HLO of `build_step` carries every `dlrm.*` scope in its
    ops' `op_name` metadata: fused serve, train, and composed serve on
    four virtual CPU devices.
"""
import dataclasses
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_dlrm
from repro.data import make_recsys_batch
from repro.engine import Engine
from repro.obs import MetricsRegistry, host_span

PHASES = ("serve_assemble_ms", "serve_dispatch_ms", "serve_device_wait_ms",
          "serve_copy_out_ms")
SCOPES = ("dlrm.bottom_mlp", "dlrm.sparse", "dlrm.interaction",
          "dlrm.top_mlp")
UPDATE_SCOPES = ("dlrm.sparse_update", "dlrm.dense_update")


def _cfg():
    cfg = get_dlrm("dlrm-rm2-small-unsharded").reduced()
    return dataclasses.replace(cfg, batch_size=8)


def _query(cfg, step):
    b = make_recsys_batch(cfg, step, 0, 0.0)
    return {"dense": np.asarray(b["dense"]),
            "indices": np.asarray(b["indices"])}


def _host_events(trace_dir, prefix):
    """(name, start_ns, end_ns, stats) of host events named prefix*."""
    from jax.profiler import ProfileData
    f = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(f).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda x: (x[1], -x[2]))


# ------------------------------------------------------------ host_span
def test_host_span_observes_its_duration():
    reg = MetricsRegistry()
    with host_span("serve.dispatch", reg) as s:
        time.sleep(0.002)
    assert s.t1 > s.t0 and s.seconds >= 0.002
    h = reg.snapshot()["serve_dispatch_ms"]
    assert h["count"] == 1 and h["sum"] == pytest.approx(s.seconds * 1e3)
    with host_span("serve.dispatch"):            # no registry: no series
        pass
    assert reg.snapshot()["serve_dispatch_ms"]["count"] == 1


def test_host_span_that_raises_records_nothing():
    reg = MetricsRegistry()
    with pytest.raises(KeyError):
        with host_span("serve.copy_out", reg) as s:
            raise KeyError("x")
    assert s.t1 >= s.t0
    assert "serve_copy_out_ms" not in reg.snapshot()


def test_host_span_lands_in_the_profiler_trace(tmp_path):
    reg = MetricsRegistry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with host_span("test.outer", reg, flush=3, reason="full",
                       qids="4 5"):
            with host_span("test.inner"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path), "test.")
    assert [e[0] for e in evs] == ["test.outer", "test.inner"]
    (_, s0, e0, stats), (_, s1, e1, _) = evs
    assert s0 <= s1 and e1 <= e0
    assert stats == {"flush": 3, "reason": "full", "qids": "4 5"}
    assert reg.snapshot()["test_outer_ms"]["count"] == 1


# ------------------------------------------------------------ ServeSession
def test_session_counts_phases_waits_and_compiles_on_an_injected_clock():
    cfg = _cfg()
    reg = MetricsRegistry()
    sess = Engine(cfg, metrics=reg).serve_session(max_batch_queries=4,
                                                  max_wait_ms=50.0)
    assert sess.metrics is reg and sess.batcher.metrics is reg
    arrivals, drains = [], []
    # full: 4 queries arrive at 0.00..0.03, the 4th submit drains at 0.03
    for i in range(4):
        sess.submit(_query(cfg, i), now=0.01 * i)
        arrivals.append(0.01 * i)
    drains += [0.03] * 4
    # deadline: 2 queries at 1.0 and 1.02, poll at 1.06 drains both
    for i, t in enumerate((1.0, 1.02)):
        sess.submit(_query(cfg, 10 + i), now=t)
        arrivals.append(t)
    assert sess.poll(now=1.06)
    drains += [1.06] * 2
    # forced: 1 query at 2.0, flush at 2.004
    sess.submit(_query(cfg, 20), now=2.0)
    arrivals.append(2.0)
    sess.flush(now=2.004)
    drains.append(2.004)

    snap = reg.snapshot()
    for p in PHASES:
        assert snap[p]["count"] == 3, p
    wait = snap["serve_batch_wait_ms"]
    want = [(d - a) * 1e3 for a, d in zip(arrivals, drains)]
    assert wait["count"] == len(want)
    assert wait["sum"] == pytest.approx(sum(want))
    assert wait["max"] == pytest.approx(max(want))
    assert wait["min"] == pytest.approx(min(want))
    # each flush's padded shape (4, 2 and 1 queries) compiled once
    assert snap["serve_compile_ms"]["count"] == 3
    sess.flush(now=3.0)                          # empty: no flush
    assert reg.snapshot()["serve_assemble_ms"]["count"] == 3


def test_flush_spans_nest_in_order_with_their_reasons(tmp_path):
    cfg = _cfg()
    reg = MetricsRegistry()
    sess = Engine(cfg, metrics=reg).serve_session(max_batch_queries=2,
                                                  max_wait_ms=50.0,
                                                  warmup=True)
    sess.submit(_query(cfg, 0), now=0.0)
    sess.flush(now=0.0)                # compiles the 1-query shape
    jax.profiler.start_trace(str(tmp_path))
    try:
        sess.submit(_query(cfg, 1), now=0.0)
        sess.submit(_query(cfg, 2), now=0.001)   # full
        sess.submit(_query(cfg, 3), now=1.0)
        sess.poll(now=1.1)                       # deadline
        sess.submit(_query(cfg, 4), now=2.0)
        sess.flush(now=2.0)                      # forced
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path), "serve.")
    flushes = [e for e in evs if e[0] == "serve.flush"]
    assert [f[3]["reason"] for f in flushes] == ["full", "deadline",
                                                 "forced"]
    assert [f[3]["queries"] for f in flushes] == [2, 1, 1]
    assert [f[3]["padded"] for f in flushes] == [2, 1, 1]
    assert flushes[0][3]["qids"] == "1 2"
    ids = [f[3]["flush"] for f in flushes]
    assert ids == list(range(ids[0], ids[0] + 3))
    assert not [e for e in evs if e[0] == "serve.compile"]
    for name, s, e, _ in flushes:
        kids = [k for k in evs if k[0] != "serve.flush" and s <= k[1]
                and k[2] <= e]
        assert [k[0] for k in kids] == ["serve.assemble", "serve.dispatch",
                                        "serve.device_wait",
                                        "serve.copy_out"]
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1]                  # in order, not overlapping


def test_each_padded_shape_compiles_once_and_serves_the_same(tmp_path):
    cfg = _cfg()
    reg = MetricsRegistry()
    sess = Engine(cfg, metrics=reg).serve_session(max_batch_queries=4,
                                                  max_wait_ms=5.0)
    qs = [_query(cfg, i) for i in range(7)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        futs = [sess.submit(qs[0], now=0.0)]
        sess.flush(now=0.0)                    # the 1-query shape
        futs += [sess.submit(q, now=1.0) for q in qs[1:5]]   # full: 4
        futs += [sess.submit(q, now=2.0) for q in qs[5:7]]
        assert sess.poll(now=2.5)              # deadline: the 2-query shape
        futs.append(sess.submit(qs[0], now=3.0))
        sess.flush(now=3.0)                    # the 1-query shape again
    finally:
        jax.profiler.stop_trace()
    assert reg.snapshot()["serve_compile_ms"]["count"] == 3
    compiles = [e for e in _host_events(str(tmp_path), "serve.compile")]
    assert [c[3]["samples"] for c in compiles] == [8, 32, 16]
    for q, f in zip(qs + [qs[0]], futs):
        np.testing.assert_allclose(
            f.probs, sess.serve_direct(q["dense"], q["indices"]),
            rtol=1e-5, atol=1e-6)


def test_service_time_is_dispatch_plus_device_wait():
    cfg = _cfg()
    reg = MetricsRegistry()
    sess = Engine(cfg, metrics=reg).serve_session(max_batch_queries=2)
    _, service, stall = sess._execute([_query(cfg, 0), _query(cfg, 1)])
    snap = reg.snapshot()
    both = (snap["serve_dispatch_ms"]["sum"]
            + snap["serve_device_wait_ms"]["sum"]) / 1e3
    assert stall == 0.0
    # one timing: the span pair's outer clock reads, a hair above the two
    # durations (the gap between the spans)
    assert both <= service < both + 1e-3


# ------------------------------------------------------------ device scopes
def _op_names(compiled) -> str:
    return " ".join(line for line in compiled.as_text().splitlines()
                    if "op_name=" in line)


def _board(cfg, n=1):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import parallel
    from repro.core import dlrm as dlrm_lib
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(1, n),
                ("data", "model"))
    axis = ("data", "model")
    params = parallel.shard_dlrm_params(
        dlrm_lib.init_dlrm(jax.random.PRNGKey(0), cfg), cfg, mesh, axis)
    return mesh, axis, params, NamedSharding(mesh, P(axis))


def test_fused_serve_and_train_steps_carry_the_scopes():
    from repro import parallel
    cfg = _cfg()
    mesh, axis, params, _ = _board(cfg)
    B, T, L = 8, cfg.num_tables, cfg.lookups_per_table
    dense = jnp.zeros((B, cfg.num_dense), jnp.float32)
    idx = jnp.zeros((B, T, L), jnp.int32)
    serve = parallel.build_step(cfg, mesh, mode="serve", axis=axis)
    names = _op_names(serve.lower(params, dense, idx).compile())
    for s in ("dlrm.bottom_mlp", "dlrm.sparse", "dlrm.top_mlp"):
        assert s in names, s
    train = parallel.build_step(cfg, mesh, mode="train", axis=axis)
    names = _op_names(train.lower(params, None, dense, idx,
                                  jnp.zeros((B,), jnp.float32)).compile())
    for s in SCOPES + UPDATE_SCOPES:
        assert s in names, s
    # backward ops keep the forward's scope
    assert "transpose(jvp(dlrm.top_mlp))" in names


COMPOSED = """
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro import parallel
from repro.configs.registry import get_dlrm
from repro.core import dlrm as dlrm_lib
cfg = dataclasses.replace(get_dlrm("dlrm-rm2-large-sharded").reduced(),
                          batch_size=8)
mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
axis = ("data", "model")
params = parallel.shard_dlrm_params(
    dlrm_lib.init_dlrm(jax.random.PRNGKey(0), cfg), cfg, mesh, axis)
step = parallel.build_step(cfg, mesh, mode="serve", axis=axis)
text = step.lower(params, jnp.zeros((8, cfg.num_dense), jnp.float32),
                  jnp.zeros((8, cfg.num_tables, cfg.lookups_per_table),
                            jnp.int32)).compile().as_text()
names = " ".join(l for l in text.splitlines() if "op_name=" in l)
print("SCOPES", *sorted(s for s in ("dlrm.bottom_mlp", "dlrm.sparse",
      "dlrm.interaction", "dlrm.top_mlp") if s in names))
print("COLLECTIVE", any(("reduce-scatter" in l or "all-to-all" in l)
                        and "dlrm.sparse" in l for l in text.splitlines()))
"""


def test_composed_serve_on_four_devices_carries_the_scopes(subproc):
    r = subproc(COMPOSED, n_devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    out = dict(line.split(" ", 1) for line in r.stdout.splitlines()
               if line.startswith(("SCOPES", "COLLECTIVE")))
    assert out["SCOPES"].split() == sorted(SCOPES)
    assert out["COLLECTIVE"] == "True"      # the exchange is under the scope
