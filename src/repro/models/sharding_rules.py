"""PartitionSpec rules for LM parameters, optimizer state, and KV caches.

Strategy: weights are 2D-sharded — `data` acts as the
FSDP/ZeRO-3 axis, `model` as the tensor-parallel axis. The `pod` axis is
pure data parallelism (params replicated across pods; only gradient
all-reduce crosses it) — the paper's scale-in principle: latency-bound
collectives (TP all-reduces, embedding all-to-alls) stay inside a pod.

Rules are matched on the parameter's key path (dict keys from
transformer.init_model), so they survive arbitrary nesting/stacking.

Divisibility policy: a spec axis is applied only if the dim divides the
mesh axis size — otherwise that dim falls back to replicated (e.g. GQA
kv_heads=8 < model=16 ⇒ wk/wv are FSDP-sharded but NOT tensor-sharded,
i.e. KV heads replicated).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

Params = Any

DATA = "data"
MODEL = "model"


def _fits(shape: Tuple[int, ...], spec: P, mesh_shape) -> P:
    """Zero out spec entries that don't divide; drop specs beyond ndim.

    NOTE (§Perf iteration 8, REFUTED): a minimum-shard-width floor that
    replicates over-sharded tiny dims (whisper-base: d=512/16 = 32-wide TP
    shards) was measured to cut the collective term 35× but inflate the
    per-chip memory term 9× — dropping TP without re-sizing the mesh just
    replicates full-width activation work. The real fix is planner-level
    mesh right-sizing (small models get a smaller `model` degree), which the
    fixed production mesh of the dry-run deliberately does not allow."""
    out = []
    for dim, axes in enumerate(spec):
        if dim >= len(shape) or axes is None:
            out.append(None)
            continue
        ax = axes if isinstance(axes, tuple) else (axes,)
        size = 1
        for a in ax:
            size *= mesh_shape.get(a, 1)
        out.append(axes if (size > 1 and shape[dim] % size == 0) else None)
    while len(out) < len(shape):
        out.append(None)
    return P(*out)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def _rule(path: str, shape: Tuple[int, ...], cfg: ModelConfig,
          fsdp: bool = True) -> P:
    """Spec BEFORE divisibility filtering. Stacked layer params have a
    leading (n_units,) dim — rules index from the trailing dims."""
    nd = len(shape)
    d_ax = DATA if fsdp else None

    def trail(*axes):
        """Spec that right-aligns `axes` against the shape (handles the
        stacked leading dim transparently)."""
        pad = [None] * (nd - len(axes))
        return P(*(pad + list(axes)))

    name = path.rsplit("/", 1)[-1]

    # --- embeddings / head -------------------------------------------------
    if name == "embed":                      # (V, d): vocab over model
        return P(MODEL, d_ax)
    if name == "lm_head":                    # (d, V)
        return P(d_ax, MODEL)
    if name == "frontend_proj":
        return P(d_ax, MODEL)

    # --- attention ----------------------------------------------------------
    if name == "wq":                         # (d, Hq*hd): column parallel
        return trail(d_ax, MODEL)
    if name in ("wk", "wv"):                 # (d, Hkv*hd)
        if cfg.n_kv_heads % 16 == 0 or True:
            # divisibility filter below decides; propose TP on out dim
            return trail(d_ax, MODEL)
    if name == "wo":                         # (Hq*hd, d): row parallel
        return trail(MODEL, d_ax)
    if name in ("bq", "bk", "bv"):
        return trail(MODEL)

    # --- dense MLP ----------------------------------------------------------
    if name in ("w_gate", "w_up"):
        if "moe" in path:                    # (E, d, ff)
            if cfg.moe and cfg.moe.num_experts % 16 == 0:
                return trail(MODEL, d_ax, None)      # expert parallel
            return trail(None, d_ax, MODEL)          # d_ff tensor parallel
        return trail(d_ax, MODEL)           # (d, ff) column parallel
    if name == "w_down":
        if "moe" in path:                    # (E, ff, d)
            if cfg.moe and cfg.moe.num_experts % 16 == 0:
                return trail(MODEL, None, d_ax)
            return trail(None, MODEL, d_ax)
        return trail(MODEL, d_ax)            # (ff, d) row parallel
    if name == "router":                     # (d, E)
        return trail(d_ax, None)

    # --- mamba ---------------------------------------------------------------
    if name == "w_in":                       # (d, 2*di)
        return trail(d_ax, MODEL)
    if name in ("conv_w",):                  # (dc, di)
        return trail(None, MODEL)
    if name in ("conv_b", "dt_bias", "d_skip"):  # (di,)
        return trail(MODEL)
    if name == "w_x":                        # (di, dt_rank+2ds)
        return trail(MODEL, None)
    if name == "w_dt":                       # (dt_rank, di)
        return trail(None, MODEL)
    if name == "a_log":                      # (di, ds)
        return trail(MODEL, None)
    if name == "w_out":                      # (di, d)
        return trail(MODEL, d_ax)

    # --- rwkv6 ---------------------------------------------------------------
    if name in ("w_r", "w_k", "w_v", "w_g"):  # (d, d) / cmix (d, ff)
        return trail(d_ax, MODEL)
    if name == "w_o":                         # (d, d)
        return trail(MODEL, d_ax)
    if name in ("w_decay_a",):                # (d, lora)
        return trail(d_ax, None)
    if name in ("w_decay_b",):                # (lora, d)
        return trail(None, MODEL)

    # norms, mixes, bonus, scalars: replicated
    return P()


def param_specs(cfg: ModelConfig, params: Params, fsdp: bool = True) -> Params:
    """Pytree of PartitionSpec congruent with `params` (abstract or concrete)."""
    mesh_axes = {}  # filled by specs_with_mesh; here only divisibility vs 1

    def spec(path, leaf):
        return _rule(_path_str(path), leaf.shape, cfg, fsdp)

    return jax.tree_util.tree_map_with_path(spec, params)


def filter_specs(specs: Params, params: Params, mesh: Mesh) -> Params:
    """Apply divisibility filtering for a concrete mesh."""
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))

    def f(s, leaf):
        return _fits(leaf.shape, s, shape)
    return jax.tree_util.tree_map(
        f, specs, params, is_leaf=lambda x: isinstance(x, P))


def named_shardings(cfg: ModelConfig, params: Params, mesh: Mesh,
                    fsdp: bool = True) -> Params:
    specs = filter_specs(param_specs(cfg, params, fsdp), params, mesh)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# KV-cache / decode-state specs
# ---------------------------------------------------------------------------
def cache_specs(cfg: ModelConfig, caches: Params, mesh: Mesh,
                batch_axes: Tuple[str, ...] = ("pod", "data")) -> Params:
    """Shard decode state: batch dim over data axes; the KV sequence dim over
    `model` (keeps a 32k×Hkv×hd cache within per-chip HBM even when
    kv_heads < |model|); SSM states: feature dim over model."""
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    b_axes = tuple(a for a in batch_axes if a in shape)

    def spec(path, leaf):
        name = _path_str(path).rsplit("/", 1)[-1]
        nd = len(leaf.shape)
        if name in ("k", "v"):          # (U, B, S, Hkv, hd)
            return _fits(leaf.shape, P(None, b_axes, MODEL, None, None), shape)
        if name == "pos":               # (U, B, S)
            return _fits(leaf.shape, P(None, b_axes, MODEL), shape)
        if name == "conv":              # (U, B, dc-1, di)
            return _fits(leaf.shape, P(None, b_axes, None, MODEL), shape)
        if name == "ssm":               # (U, B, di, ds)
            return _fits(leaf.shape, P(None, b_axes, MODEL, None), shape)
        if name == "wkv":               # (U, B, H, hd, hd)
            return _fits(leaf.shape, P(None, b_axes, MODEL, None, None), shape)
        if name in ("x_prev", "cmix_prev"):   # (U, B, d)
            return _fits(leaf.shape, P(None, b_axes, MODEL), shape)
        return _fits(leaf.shape, P(*([None] * nd)), shape)

    return jax.tree_util.tree_map_with_path(spec, caches)


def batch_specs(batch: Params, mesh: Mesh,
                batch_axes: Tuple[str, ...] = ("pod", "data")) -> Params:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    b_axes = tuple(a for a in batch_axes if a in shape)

    def spec(leaf):
        return _fits(leaf.shape, P(b_axes, *([None] * (len(leaf.shape) - 1))),
                     shape)
    return jax.tree_util.tree_map(spec, batch)
