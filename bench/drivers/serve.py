"""Serving driver: queries through `ServeSession.submit()` / `poll()` on
the wall clock, from a session built by `Engine(...).serve_session(...)`.

The traffic file sets the loop:

* ``"loop": "open"``: queries fall due on a fixed schedule at ``qps``
  (`gen.arrival_offsets` with the mix's ``arrival_seed``) whatever the
  server does; a query's latency runs
  from when it was due to when the session returned its probabilities, so a
  stall on the host delays every query behind it. The window offers
  ``round(qps * seconds)`` queries and lasts until the last of them is
  answered.
* ``"loop": "closed"``: the client submits back to back and every flush is
  a full micro-batch; the window runs ``seconds`` and then to the end of the
  batch in flight, and the rate is samples answered over that time.

Queries are host arrays made before the window from the seed: a pool of
``distinct_queries`` queries of the configuration's batch size, used in
turn. Only the micro-batch shapes that the mix uses are warmed.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

import gen
from common import clock, mesh_for, place_params, program_config


@dataclass
class State:
    session: object
    pool: List[Dict[str, np.ndarray]]
    offsets: np.ndarray = None
    futures: Dict[int, object] = field(default_factory=dict)
    pool_of: Dict[int, int] = field(default_factory=dict)
    table_itemsize: int = 4


def setup(env) -> State:
    from repro.engine import Engine
    cfg, tr = env.cfg, env.traffic
    pcfg = program_config(cfg)
    mesh = mesh_for(env.devices)
    t0 = clock()
    params = place_params(cfg, pcfg, mesh, env.seed)
    params["tables"].block_until_ready()
    env.log(f"weights placed in {clock() - t0:.3f} s: tables "
            f"{params['tables'].shape} {params['tables'].dtype}")
    eng = Engine(pcfg, mesh=mesh, pipeline_depth=1, seed=0)
    sess = eng.serve_session(params=params,
                             max_batch_queries=tr["max_batch_queries"],
                             max_wait_ms=tr["max_wait_ms"],
                             query_size=cfg["batch_size"])
    env.log(f"serve kernel: {sess.serve_kernel}")
    del params
    t0 = clock()
    pool = []
    for i in range(tr["distinct_queries"]):
        rng = np.random.default_rng([env.seed % 2 ** 63, 11, i])
        pool.append(gen.make_batch(rng, cfg["batch_size"], cfg["num_dense"],
                                   cfg["num_tables"],
                                   cfg["lookups_per_table"],
                                   cfg["rows_per_table"], tr["alpha"]))
    env.log(f"{len(pool)} queries generated in {clock() - t0:.3f} s")
    t0 = clock()
    # warm each micro-batch shape the mix flushes; an injected clock keeps
    # the batcher's deadline from splitting a warm-up batch
    for k in tr["warm_queries"]:
        for j in range(k):
            sess.submit(pool[j], now=0.0)
        sess.flush(now=0.0)
    env.log(f"warm-up of {tr['warm_queries']} queries per batch "
            f"{clock() - t0:.3f} s")
    st = State(session=sess, pool=pool,
               table_itemsize=sess.params["tables"].dtype.itemsize)
    if tr["loop"] == "open":
        st.offsets = gen.arrival_offsets(
            int(round(tr["qps"] * env.seconds)), tr["qps"],
            tr["arrival_seed"])
    return st


def _open_loop(st: State, env, seconds: float) -> dict:
    sess = st.session
    offsets = st.offsets
    n = len(offsets)
    due = np.empty(n)
    submit_at = np.full(n, np.nan)
    flushed_at = np.full(n, np.nan)
    flushes: List[int] = []
    flush_call_ms: List[float] = []
    pending: List[int] = []
    ann = env.annotate

    def settle(call_start: float) -> None:
        done = [q for q in pending if st.futures[q].done]
        if done:
            flushes.append(len(done))
            flush_call_ms.append((clock() - call_start) * 1e3)
            for q in done:
                flushed_at[q] = call_start
                pending.remove(q)

    with ann("bench.window"):
        t0 = clock() + 0.01
        due[:] = t0 + offsets
        i = 0
        while i < n or pending:
            now = clock()
            if i < n and now >= due[i]:
                submit_at[i] = now
                st.pool_of[i] = i % len(st.pool)
                with ann("bench.submit"):
                    st.futures[i] = sess.submit(st.pool[st.pool_of[i]])
                pending.append(i)
                i += 1
                settle(now)
                continue
            if pending and now >= sess.batcher.deadline():
                with ann("bench.poll"):
                    sess.poll()
                settle(now)
                continue
            nxt = min(due[i] if i < n else math.inf,
                      sess.batcher.deadline() if pending else math.inf)
            if nxt - now > 2e-4:
                with ann("bench.idle"):
                    time.sleep(min(nxt - now - 1e-4, 0.05))
    done_at = np.array([st.futures[q].completed_at for q in range(n)])
    lat = (done_at - due) * 1e3
    samples = n * env.cfg["batch_size"]
    return {"loop": "open", "window_s": float(offsets[-1]) if n else 0.0,
            "latency_ms": lat, "late_ms": (submit_at - due) * 1e3,
            "queue_wait_ms": (flushed_at - due) * 1e3,
            "flushes": flushes, "flush_call_ms": flush_call_ms,
            "attempted": n,
            "failed": int(np.sum(~np.isfinite(lat))),
            "samples": samples, "span_s": float(np.nanmax(done_at) - t0)}


def _closed_loop(st: State, env, seconds: float) -> dict:
    sess = st.session
    ann = env.annotate
    flushes: List[int] = []
    pending: List[int] = []
    with ann("bench.window"):
        t0 = clock()
        t_end = t0 + seconds
        i = 0
        while clock() < t_end or sess.pending:
            st.pool_of[i] = i % len(st.pool)
            with ann("bench.submit"):
                st.futures[i] = sess.submit(st.pool[st.pool_of[i]])
            pending.append(i)
            i += 1
            done = [q for q in pending if st.futures[q].done]
            if done:
                flushes.append(len(done))
                pending = [q for q in pending if q not in done]
        t_last = clock()
    B = env.cfg["batch_size"]
    answered = sum(1 for f in st.futures.values() if f.done)
    return {"loop": "closed", "window_s": t_last - t0, "flushes": flushes,
            "attempted": i, "failed": i - answered,
            "samples": answered * B}


def window(st: State, env, seconds: float) -> dict:
    loop = env.traffic["loop"]
    rec = (_open_loop if loop == "open" else _closed_loop)(st, env, seconds)
    if rec.get("flush_call_ms"):
        env.log(f"{len(rec['flushes'])} flushes, the longest call "
                f"{max(rec['flush_call_ms']):.1f} ms")
    rec["max_batch_queries"] = env.traffic["max_batch_queries"]
    rec["table_itemsize"] = st.table_itemsize
    rec["owned_lookups_per_chip"] = _owned_lookups(st, env)
    return rec


def _owned_lookups(st: State, env) -> List[int]:
    """Per chip, the answered lookups whose rows that chip holds: a row
    range of every table when row-sharded, else T/n whole tables."""
    cfg, n = env.cfg, len(env.devices)

    def owned(q):
        if cfg["sharding"] == "row_wise":
            share = cfg["rows_per_table"] // n
            return np.bincount((q["indices"] // share).ravel(), minlength=n)
        return np.full(n, q["indices"].size // n)
    per_pool = [owned(q) for q in st.pool]
    total = np.zeros(n, np.int64)
    for qid, fut in st.futures.items():
        if fut.done:
            total += per_pool[st.pool_of[qid]]
    return [int(x) for x in total]


def release(st: State) -> dict:
    """Keep each answered query's probabilities; free the program."""
    answers = {q: (st.pool_of[q], np.asarray(f.probs))
               for q, f in st.futures.items() if f.done}
    pool = st.pool
    st.session = None
    return {"answers": answers, "pool": pool,
            "last": max((q for q, f in st.futures.items() if f.done),
                        key=lambda q: st.futures[q].completed_at,
                        default=None)}


def sample_queries(answers: dict, k: int, seed: int, last) -> List[int]:
    """``k`` answered queries drawn from the seed, the last answered one
    among them."""
    rest = sorted(q for q in answers if q != last)
    rng = np.random.default_rng([seed % 2 ** 63, 13])
    pick = rng.choice(rest, size=min(k - 1, len(rest)), replace=False)
    return sorted(pick.tolist() + ([last] if last is not None else []))


def _sampled(answers: dict, env):
    ans, pool = answers["answers"], answers["pool"]
    pick = sample_queries(ans, env.traffic["check_queries"], env.seed,
                          answers["last"])
    if not pick:
        return None
    dense = np.concatenate([pool[ans[q][0]]["dense"] for q in pick])
    idx = np.concatenate([pool[ans[q][0]]["indices"] for q in pick])
    env.log(f"checking {len(pick)} answered queries ({len(dense)} samples)")
    return dense, idx, [ans[q][1] for q in pick]


def _gap(got: np.ndarray, want: np.ndarray) -> float:
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return math.inf
    return float(np.max(np.abs(got.astype(np.float64) - want)))


def _result(gap: float, env) -> List[dict]:
    return [{"name": "max_abs_dp", "value": gap,
             "limit": env.spec.limits.get("max_abs_dp", 0.0)}]


def check(answers: dict, env) -> List[dict]:
    """Served probabilities of a sample of answered queries against the
    reference: the widest gap over the sample's samples."""
    s = _sampled(answers, env)
    if s is None:
        return _result(math.inf, env)
    dense, idx, got = s
    want = env.reference().serve_probs(env.cfg, env.seed, dense, idx)
    return _result(_gap(np.concatenate(got), want), env)


def control_check(answers: dict, env, cast: str) -> List[dict]:
    """The control in the program's place: the reference in ``cast`` on
    the same sample, compared as a run compares the program."""
    dense, idx, _ = _sampled(answers, env)
    ref = env.reference()
    want = ref.serve_probs(env.cfg, env.seed, dense, idx)
    got = ref.serve_probs(env.cfg, env.seed, dense, idx, cast=cast)
    return _result(_gap(got, want), env)
