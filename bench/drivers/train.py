"""Training driver: SGD steps of `repro.parallel.build_step(mode="train")`,
the step `TrainSession` runs, fed by an input pipeline.

Set-up makes ``distinct_batches`` batches on the host from the seed
(inputs and planted-teacher labels, `gen.make_batch`); a producer thread
puts them on the device in turn, keeping ``prefetch`` batches ahead, as an
input pipeline does. No batch is generated inside the window, so a busy
host cannot starve the step. Set-up builds the step and its state once, and drives it through
the first ``check_steps`` steps through the same call and feed as the
window, reading each loss and, from the state after step 1 and after the
last of them, each leaf's distance from the initial weights (the first
gradient as SGD applied it, and the change). The window then goes on with
that same step and state. It keeps at most two steps in flight: before
dispatching a step it waits for the one two back, which never starves the
device. It ends with a wait on the updated params.
"""
from __future__ import annotations

import math
import queue
import threading
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

import gen
from common import (AXIS, clock, initial_params, initial_params_fn,
                    mesh_for, program_config, weight_keys)


class Feed:
    """Host-made batches, put on the device in turn by a producer
    thread."""

    def __init__(self, env, sharding, keep_host: int):
        cfg, tr, seed = env.cfg, env.traffic, env.seed
        self.pool = [
            gen.make_batch(np.random.default_rng([seed % 2 ** 63, 17, k]),
                           cfg["batch_size"], cfg["num_dense"],
                           cfg["num_tables"], cfg["lookups_per_table"],
                           cfg["rows_per_table"], tr["alpha"],
                           label_seed=seed)
            for k in range(tr["distinct_batches"])]
        self.sharding = sharding
        self.keep_host = keep_host
        self.q: "queue.Queue" = queue.Queue(maxsize=env.traffic["prefetch"])
        self.stop = threading.Event()
        self.error = None
        self.thread = threading.Thread(target=self._produce, daemon=True)
        self.thread.start()

    def _produce(self) -> None:
        import jax
        k = 0
        try:
            while not self.stop.is_set():
                b = self.pool[k % len(self.pool)]
                dev = tuple(jax.device_put(b[x], self.sharding)
                            for x in ("dense", "indices", "labels"))
                item = (b if k < self.keep_host else None, dev)
                while not self.stop.is_set():
                    try:
                        self.q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                k += 1
        except Exception as e:          # surfaced by get()
            self.error = e

    def get(self):
        while True:
            if self.error is not None:
                raise RuntimeError("input pipeline failed") from self.error
            try:
                return self.q.get(timeout=1.0)
            except queue.Empty:
                continue

    def close(self) -> None:
        self.stop.set()
        while True:
            try:
                self.q.get_nowait()
            except queue.Empty:
                break
        self.thread.join(timeout=30)
        if self.thread.is_alive():
            raise RuntimeError("input pipeline did not stop")


@dataclass
class State:
    step: object
    params: object
    feed: Feed
    first_batches: List[dict]
    losses: List[float]
    grad_norms: Dict[str, float]
    change_norms: Dict[str, float]
    window_losses: list = field(default_factory=list)


def _distance_fn(cfg, pcfg, mesh):
    """params -> {leaf: ||leaf - initial leaf||}, the initial weights made
    again from the keys inside the same program (never held as a copy)."""
    import jax
    import jax.numpy as jnp
    init, _ = initial_params(cfg, pcfg, mesh)

    def dist(params, keys):
        cur = gen.named_leaves(params)
        ini = gen.named_leaves(init(keys))
        return {k: jnp.sqrt(jnp.sum(jnp.square(cur[k] - ini[k])))
                for k in cur}
    return jax.jit(dist)


def setup(env) -> State:
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import parallel
    cfg, tr = env.cfg, env.traffic
    pcfg = program_config(cfg)
    mesh = mesh_for(env.devices)
    keys = weight_keys(cfg, env.seed)
    t0 = clock()
    params = initial_params_fn(cfg, pcfg, mesh)(keys)
    params["tables"].block_until_ready()
    env.log(f"weights placed in {clock() - t0:.3f} s: tables "
            f"{params['tables'].shape} {params['tables'].dtype}")
    step = parallel.build_step(pcfg, mesh, mode="train", axis=AXIS,
                               lr=tr["lr"], exchange="partial_pool",
                               optimizer="sgd", pipeline_depth=1)
    n_check = tr["check_steps"]
    feed = Feed(env, NamedSharding(mesh, P(AXIS)), keep_host=n_check)
    dist = _distance_fn(cfg, pcfg, mesh)
    losses, first = [], []
    grad_norms = change_norms = None
    for k in range(n_check):
        host, dev = feed.get()
        t0 = clock()
        params, _, loss = step(params, None, *dev)
        losses.append(float(loss))
        env.log(f"step {k + 1}: loss {losses[-1]!r} ({clock() - t0:.3f} s)")
        first.append(host)
        if k == 0:
            grad_norms = {n: float(v) / tr["lr"]
                          for n, v in dist(params, keys).items()}
    change_norms = {n: float(v) for n, v in dist(params, keys).items()}
    return State(step=step, params=params, feed=feed, first_batches=first,
                 losses=losses, grad_norms=grad_norms,
                 change_norms=change_norms)


def window(st: State, env, seconds: float) -> dict:
    import jax
    ann = env.annotate
    inflight: List = []
    with ann("bench.window"):
        t0 = clock()
        t_end = t0 + seconds
        n = 0
        params = st.params
        while clock() < t_end:
            with ann("bench.feed"):
                _, dev = st.feed.get()
            with ann("bench.dispatch"):
                params, _, loss = st.step(params, None, *dev)
            st.window_losses.append(loss)
            inflight.append(loss)
            n += 1
            if len(inflight) > 2:
                with ann("bench.sync"):
                    inflight.pop(0).block_until_ready()
        with ann("bench.sync"):
            jax.block_until_ready(params)
        t_last = clock()
    st.params = params
    losses = np.array([float(x) for x in st.window_losses])
    B = env.cfg["batch_size"]
    return {"window_s": t_last - t0, "steps": n, "samples": n * B,
            "attempted": n, "failed": int(np.sum(~np.isfinite(losses)))}


def release(st: State) -> dict:
    st.feed.close()
    answers = {"losses": st.losses, "grad_norms": st.grad_norms,
               "change_norms": st.change_norms,
               "batches": st.first_batches}
    st.params = st.step = None
    return answers


def readings(prog: dict, ref: dict) -> Dict[str, float]:
    """The three numbers compared: the widest relative gap of a step's
    loss; and, over the leaves, the widest gap between the program's norm
    and the reference's, of the first gradient and of the change, each
    against the larger of that leaf's reference norm and the median
    leaf's. Leaves whose reference gradient is under a thousandth of the
    median leaf's move by round-off alone and are left out of the change.
    """
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    g_ref, d_ref = ref["grad_norms"], ref["change_norms"]
    g_med = float(np.median(list(g_ref.values())))
    d_med = float(np.median(list(d_ref.values())))
    grad_gap = max(abs(prog["grad_norms"][k] - g_ref[k])
                   / max(g_ref[k], g_med) for k in g_ref)
    moved = [k for k in d_ref if g_ref[k] >= 1e-3 * g_med]
    change_gap = max(abs(prog["change_norms"][k] - d_ref[k])
                     / max(d_ref[k], d_med) for k in moved)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "change_norm_gap": change_gap}


def _compared(got: dict, env) -> List[dict]:
    lim = env.spec.limits
    return [{"name": k, "value": v if math.isfinite(v) else math.inf,
             "limit": lim.get(k, 0.0)} for k, v in got.items()]


def _reference(answers: dict, env, **kw) -> dict:
    return env.reference().train_readings(
        env.cfg, env.seed, answers["batches"], env.traffic["lr"], **kw)


def check(answers: dict, env) -> List[dict]:
    ref = _reference(answers, env)
    for k in sorted(ref["grad_norms"]):
        env.log(f"leaf {k}: grad {answers['grad_norms'][k]!r} vs "
                f"{ref['grad_norms'][k]!r}; change "
                f"{answers['change_norms'][k]!r} vs {ref['change_norms'][k]!r}")
    env.log(f"losses {answers['losses']} vs {ref['losses']}")
    return _compared(readings(answers, ref), env)


def control_check(answers: dict, env, cast: str) -> List[dict]:
    """The reference in ``cast`` in the program's place."""
    ref = _reference(answers, env)
    return _compared(readings(_reference(answers, env, cast=cast), ref), env)


def fault_readings(answers: dict, env) -> Dict[str, Dict[str, float]]:
    """What each fault a one-chip training cell can have reads, planted in
    the reference in the program's place."""
    ref = _reference(answers, env)
    return {f: readings(_reference(answers, env, fault=f), ref)
            for f in ("unchanged", "half_batch")}
