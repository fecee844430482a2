"""TrainSession: the engine's training path (DLRM and LM workloads).

Wraps `runtime.TrainLoop` (resume-from-latest, async checkpointing,
straggler accounting) around the plan-executing DLRM step factory — with
the plan-aware optimizer-state init — or the LM train step. Built by
`Engine.train_session()`; no caller assembles step/params/opt-state/loop
by hand anymore.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs.base import DLRMConfig
from repro.core import dlrm as dlrm_lib
from repro.core.planner import ShardingPlan
from repro import parallel
from repro.data import make_lm_batch, make_recsys_batch
from repro.obs.serialize import report_asdict, report_to_json
from repro.runtime import TrainLoop


@dataclass(frozen=True)
class TrainReport:
    """Result of one `TrainSession.run` call."""

    workload: str              # "dlrm" | "lm"
    config: str
    start_step: int
    steps_run: int
    first_loss: float
    last_loss: float
    history: List[Dict[str, float]]

    def summary(self) -> str:
        return (f"[train] {self.workload} {self.config}: "
                f"steps={self.steps_run} (from {self.start_step}) "
                f"first_loss={self.first_loss:.4f} "
                f"last_loss={self.last_loss:.4f}")

    def asdict(self) -> dict:
        return report_asdict(self)

    def to_json(self, path: Optional[str] = None) -> str:
        return report_to_json(self, path)


class _SessionBase:
    """Shared resume/run plumbing over a `TrainLoop`."""

    workload = "?"

    def __init__(self, cfg, loop: TrainLoop, init_state: Any):
        self.cfg = cfg
        self._loop = loop
        self._state, self.resume_step = loop.resume(init_state)
        self._next_step = self.resume_step

    @property
    def state(self) -> Any:
        return self._state

    def run(self, n_steps: int) -> TrainReport:
        start = self._next_step
        before = len(self._loop.history)
        self._state = self._loop.run(self._state, n_steps, start)
        self._next_step = start + n_steps
        hist = self._loop.history[before:]
        losses = [h["loss"] for h in hist]
        return TrainReport(
            workload=self.workload, config=self.cfg.name, start_step=start,
            steps_run=len(hist), first_loss=losses[0], last_loss=losses[-1],
            history=hist)


class TrainSession(_SessionBase):
    """DLRM training: plan-executing distributed step + TrainLoop."""

    workload = "dlrm"

    def __init__(self, cfg: DLRMConfig, mesh, axis, *,
                 plan: Optional[ShardingPlan] = None,
                 exchange="partial_pool", optimizer: str = "sgd",
                 lr: float = 0.01, seed: int = 0, alpha: float = 0.0,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 ckpt_keep: int = 3, pipeline_depth: int = 1,
                 compress_grads: bool = False,
                 dp_axes: Tuple[str, ...] = ()):
        dp_axes = tuple(dp_axes)
        ax_tuple = (axis,) if isinstance(axis, str) else tuple(axis)
        # table groups / sparse opt state are sized by the EMBEDDING axis;
        # error-feedback residuals by the full batch-sharding device count
        n_embed = parallel.axis_size(mesh, axis)
        n_full = parallel.axis_size(mesh, dp_axes + ax_tuple)
        self.pipeline_depth = int(pipeline_depth)
        step_fn = parallel.build_step(
            cfg, mesh, mode="train", axis=axis, lr=lr, exchange=exchange,
            optimizer=optimizer, plan=plan, dp_axes=dp_axes,
            pipeline_depth=self.pipeline_depth,
            compress_grads=compress_grads)
        key = jax.random.PRNGKey(seed)
        # an EmbeddingExchange instance with session state (hoststore):
        # its hooks own param placement and bracket every step below
        exch_inst = self.exchange_inst = (
            exchange if isinstance(exchange, parallel.EmbeddingExchange)
            else None)
        prepared = (exch_inst.init_session_params(
            dlrm_lib.init_dlrm(key, cfg), mesh)
            if exch_inst is not None else None)
        params = (prepared if prepared is not None else
                  parallel.init_dlrm_params(key, cfg, mesh, axis, plan=plan))
        opt_state = parallel.init_dlrm_opt_state(
            cfg, optimizer, plan, n_embed, compress_grads=compress_grads,
            n_devices=n_full)
        depth = self.pipeline_depth

        def loop_step(state, batch):
            p, o = state
            if exch_inst is not None:
                # fault this batch's cold chunks in (and mark them dirty)
                # before the step; re-attach the DONATED device arrays
                # from the returned params afterwards
                p, _ = exch_inst.begin_batch(
                    p, np.asarray(batch["indices"]), depth, train=True)
            p, o, loss = step_fn(p, o, batch["dense"], batch["indices"],
                                 batch["labels"])
            if exch_inst is not None:
                p = exch_inst.end_batch(p)
            return (p, o), {"loss": loss}

        loop = TrainLoop(
            step_fn=loop_step,
            batch_fn=lambda s: make_recsys_batch(cfg, s, seed, alpha),
            ckpt=(CheckpointManager(ckpt_dir, keep=ckpt_keep)
                  if ckpt_dir else None),
            ckpt_every=ckpt_every)
        super().__init__(cfg, loop, (params, opt_state))

    @property
    def params(self) -> Dict[str, Any]:
        return self._state[0]

    @property
    def opt_state(self) -> Any:
        return self._state[1]


class LMTrainSession(_SessionBase):
    """LM training: `models.lm.make_train_step` + TrainLoop."""

    workload = "lm"

    def __init__(self, cfg, mesh, *, lr: float = 3e-4, seed: int = 0,
                 batch: int = 8, seq: int = 128, chain_prob: float = 0.8,
                 schedule_steps: int = 100,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 ckpt_keep: int = 3):
        from repro.models import transformer as T
        from repro.models import lm
        from repro.models.common import Sharder
        from repro.optim import adamw, cosine_schedule

        sharder = Sharder(mesh) if int(mesh.devices.size) > 1 else Sharder(None)
        opt = adamw(lr, lr_schedule=cosine_schedule(10, schedule_steps))
        step = jax.jit(lm.make_train_step(cfg, opt, sharder),
                       donate_argnums=(0,))
        params = T.init_model(jax.random.PRNGKey(seed), cfg)
        state = {"params": params, "opt": opt.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        loop = TrainLoop(
            step_fn=step,
            batch_fn=lambda s: make_lm_batch(cfg, s, seed, batch, seq,
                                             chain_prob),
            ckpt=(CheckpointManager(ckpt_dir, keep=ckpt_keep)
                  if ckpt_dir else None),
            ckpt_every=ckpt_every)
        super().__init__(cfg, loop, state)

    @property
    def params(self) -> Any:
        return self._state["params"]
