"""What the program records about itself, read back by the benchmark.

Three sources, none of them the benchmark's own bookkeeping:

* counters: the serve path's `repro.obs.host_span` histograms
  (``serve_assemble_ms``, ``serve_dispatch_ms``, ``serve_device_wait_ms``,
  ``serve_copy_out_ms``, ``serve_compile_ms``) and the batcher's
  ``serve_batch_wait_ms``, in the process-wide metrics registry the
  session publishes to by default. One run is one process (`bench/run.py`),
  so the registry holds that run's set-up and window and nothing else.
* device scopes: `build_step` runs each stage under a `jax.named_scope`
  (``dlrm.sparse``, ``dlrm.sparse_update``, ``dlrm.bottom_mlp``, ...).
  XLA keeps the scope in each op's metadata (`op_name`), not in the op's
  name, which is all a trace's op event gives. `op_scopes` maps every
  instruction of a compiled program's HLO text to its innermost ``dlrm.*``
  scope; `step_text` compiles the cell's step again, afresh
  (`compile_afresh`), for that text: for serving, the step of a session
  the program builds as the driver builds it (`ServeSession._get_step`).
  A trace's op events carry no module, so an op of another program that
  shares an instruction name with the step's counts as the step's in the
  metric readers; `bench/attribute.py`, which has the whole trace, keeps
  only the ops inside the step's own runs (`module_runs`).
* host spans: the program's ``serve.*`` profiler annotations, on the same
  clock as the device trace; `idle_by_span` puts each piece of a chip's
  idle time down to the innermost span covering it (`bench/attribute.py`).

A program that records none of these (an older commit) reads as None
everywhere, never as an error.
"""
from __future__ import annotations

import bisect
import re
import traceback
from collections import Counter
from typing import Dict, Iterable, List, Optional, Sequence

import tracing
from harness import log

SCOPE = re.compile(r"dlrm\.[a-z_]+")
UNSCOPED = "unscoped"
OTHER = "other programs"
PROGRAM_SPAN = "serve."


# ------------------------------------------------------------ counters
def histogram(name: str) -> Optional[dict]:
    """Snapshot of the program's histogram ``name`` in the process-wide
    registry; None where the program never recorded it."""
    try:
        from repro.obs.metrics import default_registry
    except ImportError:
        return None
    h = default_registry().snapshot().get(name)
    return h if isinstance(h, dict) and h.get("count") else None


# ------------------------------------------------------------ device scopes
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+) ")
_REF = re.compile(r"%([A-Za-z0-9_.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> Optional[str]:
    """The innermost ``dlrm.*`` scope of an `op_name` (of its first name
    that has one, where XLA joined several with ';')."""
    for part in op_name.split(";"):
        found = SCOPE.findall(part)
        if found:
            return found[-1]
    return None


def op_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """Instruction name -> innermost ``dlrm.*`` scope (None: unscoped),
    for every instruction of every computation in a compiled module.

    An instruction's scope is that of its own metadata; else, for a
    fusion, call or loop, the scope most of its called computations'
    instructions have; else (copies, bitcasts and the like that XLA put in)
    that of its first operand that has one; else that of the instruction
    that calls the computation it is in (a loop body's bookkeeping)."""
    own: Dict[str, Optional[str]] = {}
    refs: Dict[str, List[str]] = {}
    body: Dict[str, List[str]] = {}
    home: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c and line.rstrip().endswith("{"):
                comp = c.group(1)
                body[comp] = []
            continue
        name = m.group(1)
        meta = _OP_NAME.search(line)
        own[name] = scope_of(meta.group(1)) if meta else None
        refs[name] = [r for r in _REF.findall(line[m.end():]) if r != name]
        if comp is not None:
            body[comp].append(name)
            home[name] = comp
    caller = {r: name for name in own for r in refs[name] if r in body}

    memo: Dict[str, Optional[str]] = {}

    def inner(name: str, seen: frozenset) -> Optional[str]:
        """Own metadata, called computations, then operands."""
        if name in memo:
            return memo[name]
        if own[name] is not None or name in seen:
            return own[name]
        seen = seen | {name}
        votes = Counter(s for c in refs[name] if c in body
                        for i in body[c] for s in [inner(i, seen)] if s)
        scope = votes.most_common(1)[0][0] if votes else None
        for r in refs[name]:
            if scope is not None:
                break
            if r in own:
                scope = inner(r, seen)
        if seen == {name}:      # a nested result may be cut short by a
            memo[name] = scope  # cycle: keep only top-level ones
        return scope

    def outer(name: str, depth: int = 0) -> Optional[str]:
        scope = inner(name, frozenset())
        up = caller.get(home.get(name))
        if scope is None and up is not None and depth < 64:
            return outer(up, depth + 1)
        return scope

    return {n: outer(n) for n in own}


def time_by_scope(ops: Sequence[tracing.Op],
                  scopes: Dict[str, Optional[str]],
                  runs: Optional[Sequence[tracing.Interval]] = None
                  ) -> Dict[str, float]:
    """Device time of one chip's ops by scope: the union of the intervals
    of the ops in each scope, so that overlapping ops count once. Ops that
    are the step's but carry no scope are ``unscoped``; ops whose names the
    step does not have, or (given the step's ``runs`` on this chip) that
    ran outside them, ran in other programs (copies in, concatenation)."""
    runs = sorted(runs) if runs is not None else None
    starts = [r[0] for r in runs] if runs is not None else []

    def in_step(o: tracing.Op) -> bool:
        if runs is None:
            return True
        mid = (o.start + o.end) / 2
        i = bisect.bisect_right(starts, mid) - 1
        return i >= 0 and runs[i][1] >= mid

    by: Dict[str, list] = {}
    for o in ops:
        key = ((scopes[o.name] or UNSCOPED)
               if o.name in scopes and in_step(o) else OTHER)
        by.setdefault(key, []).append((o.start, o.end))
    return {k: tracing.total(v) for k, v in by.items()}


_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
MODULES_LINE = "XLA Modules"


def step_module(hlo_text: str) -> Optional[str]:
    """The module name of a compiled program's HLO text."""
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else None


def module_runs(pd, module: str) -> Dict[int, List[tracing.Interval]]:
    """Per chip, the intervals in which the program ``module`` ran, from a
    `jax.profiler.ProfileData`'s "XLA Modules" lines (a TPU names each run
    ``<module>(<fingerprint>)``)."""
    out: Dict[int, List[tracing.Interval]] = {}
    for plane in pd.planes:
        m = tracing.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        runs = out.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name == MODULES_LINE:
                runs.extend((s, e) for n, s, e in tracing._events(line)
                            if n.split("(", 1)[0] == module)
    return out


def step_text(run) -> str:
    """HLO text of the cell's compiled step. Serving: the step a session
    built by the program's `Engine`, as the serve driver builds it, runs
    at the micro-batch capacity (the only shape the throughput cells run),
    from the session's own `_get_step`; the session takes weights placed
    as the driver places them (the run's own are freed by now). Training:
    `build_step` as the train driver calls it. Compiled afresh: the
    compiler is deterministic, so its instruction names are those of the
    executable the run ran, whichever build of it the cache handed out."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    import common
    from repro import parallel
    cfg, tr = run.cfg, run.spec.traffic
    pcfg = common.program_config(cfg)
    mesh = common.mesh_for(jax.devices()[:run.chips])
    T, L = cfg["num_tables"], cfg["lookups_per_table"]
    D, B = cfg["num_dense"], cfg["batch_size"]
    if tr["driver"] == "train":
        init, shardings = common.initial_params(cfg, pcfg, mesh)
        shapes = jax.eval_shape(init, common.weight_keys(cfg, 0))
        params = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shardings)
        data = NamedSharding(mesh, P(common.AXIS))

        def arg(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=data)
        step = parallel.build_step(pcfg, mesh, mode="train", axis=common.AXIS,
                                   lr=tr["lr"], exchange="partial_pool",
                                   optimizer="sgd", pipeline_depth=1)
        args = (params, None, arg((B, D), jnp.float32),
                arg((B, T, L), jnp.int32), arg((B,), jnp.float32))
        return compile_afresh(step.lower(*args)).as_text()
    from repro.engine import Engine
    sess = Engine(pcfg, mesh=mesh, pipeline_depth=1, seed=0).serve_session(
        params=common.place_params(cfg, pcfg, mesh, 0),
        max_batch_queries=tr["max_batch_queries"],
        max_wait_ms=tr["max_wait_ms"], query_size=B)
    b = tr["max_batch_queries"] * B
    step = sess._get_step(sess.depth_for_samples(b))
    # the session's batch is a concatenation of host arrays: placed by
    # the step, not committed to a sharding
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        sess.params)
    del sess
    return compile_afresh(step.lower(
        params, jax.ShapeDtypeStruct((b, D), jnp.float32),
        jax.ShapeDtypeStruct((b, T, L), jnp.int32))).as_text()


def compile_afresh(lowered):
    """``lowered.compile()`` with JAX's persistent cache off. The cache is
    keyed on the program stripped of its debug info, where the named
    scopes live: a cached executable of another build (the parent commit's,
    with other scopes or none) would be handed back for this one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def step_scopes(run) -> Optional[Dict[str, Optional[str]]]:
    """The cell's step's op name -> scope map, made once per traced run;
    None without a trace, or where the step has no ``dlrm.*`` scope. Each
    chip's device time by scope, and the unscoped share, are logged."""
    if run.trace is None or not run.trace.ops:
        return None
    if not hasattr(run, "_step_scopes"):
        run._step_scopes = _step_scopes(run)
    return run._step_scopes


def _step_scopes(run) -> Optional[Dict[str, Optional[str]]]:
    try:
        text = step_text(run)
    except Exception:           # a metric reader must not fail the run
        log("device scopes: the step's HLO text could not be read:\n"
            + traceback.format_exc())
        return None
    scopes = op_scopes(text)
    run._step_module = step_module(text)
    if not any(scopes.values()):
        log("device scopes: the step has no dlrm.* named scope")
        return None
    for chip in run.trace.chips():
        by = time_by_scope(run.trace.ops[chip], scopes)
        busy = run.trace.busy_s(chip)
        parts = ", ".join(f"{k} {v:.6f} s ({100 * v / busy:.4f}%)"
                          for k, v in sorted(by.items(), key=lambda x: -x[1]))
        log(f"chip {chip}: device time by scope, of {busy:.6f} s busy: "
            f"{parts}")
    return scopes


def scope_share(run, names: Iterable[str]) -> Optional[float]:
    """Percent of device busy time in ops under any of the scopes
    ``names``, the mean over chips; None where nothing maps to a scope."""
    scopes = step_scopes(run)
    if scopes is None:
        return None
    names = set(names)
    shares = []
    for chip in run.trace.chips():
        busy = run.trace.busy_s(chip)
        ops = [(o.start, o.end) for o in run.trace.ops[chip]
               if scopes.get(o.name) in names]
        if busy > 0:
            shares.append(100.0 * tracing.total(ops) / busy)
    return sum(shares) / len(shares) if shares else None


# ------------------------------------------------------------ host spans
def host_spans(pd, prefixes: Sequence[str] = ("bench.", PROGRAM_SPAN)
               ) -> List[tracing.Op]:
    """The host plane's spans whose names start with one of ``prefixes``,
    from a `jax.profiler.ProfileData` (the window span excluded)."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for n, s, e in tracing._events(line):
                if n != tracing.WINDOW and n.startswith(tuple(prefixes)):
                    out.append(tracing.Op(n, s, e))
    return out


def idle_by_span(summary: tracing.TraceSummary, spans: List[tracing.Op],
                 n: int = 10, chip: Optional[int] = None) -> List[list]:
    """Idle time of a chip (the first by default) summed by the innermost
    host span covering it: each gap is cut at the covering spans' ends,
    and each piece goes to the shortest span that covers all of it
    ("none" where none does). A gap from one step's end to the next one's
    start thus splits into its copy-out, the caller's own code, the next
    flush's assembly and its dispatch."""
    if not summary.ops:
        return []
    chip = summary.chips()[0] if chip is None else chip
    busy = [(o.start, o.end) for o in summary.ops[chip]]
    spans = sorted(spans, key=lambda h: h.start)
    starts = [h.start for h in spans]
    by: Dict[str, float] = {}
    for s, e in tracing.gaps(busy, *summary.window):
        cover = [h for h in spans[:bisect.bisect_left(starts, e)]
                 if h.end > s]
        cuts = sorted({s, e} | {x for h in cover for x in (h.start, h.end)
                                if s < x < e})
        for a, b in zip(cuts, cuts[1:]):
            inner = min((h for h in cover if h.start <= a and h.end >= b),
                        key=lambda h: h.end - h.start, default=None)
            name = inner.name if inner is not None else "none"
            by[name] = by.get(name, 0.0) + (b - a)
    return [[name, t] for name, t in
            sorted(by.items(), key=lambda x: -x[1])[:n]]
