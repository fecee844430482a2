"""Shared model utilities: param init, dtype policy, sharding context.

Params are plain pytrees (nested dicts of jnp arrays) — no framework. Master
params are fp32; compute is bf16 (TPU-native); the `Sharder` threads activation
sharding constraints through model code without coupling it to a mesh.
"""
from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = Any  # nested dict pytree

COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32


class Sharder:
    """Applies with_sharding_constraint when a mesh is attached; no-op otherwise.

    Axis-name conventions:
      batch    -> ("data",)            (plus "pod" when multi-pod data-parallel)
      model/TP -> ("model",)
    A constraint is only applied if the dim is divisible by the mesh axis size,
    so small smoke configs and odd head counts degrade gracefully to GSPMD
    propagation instead of erroring.
    """

    def __init__(self, mesh: Optional[Mesh] = None, batch_axes: Sequence[str] = ("data",),
                 model_axes: Sequence[str] = ("model",)):
        self.mesh = mesh
        self.batch_axes = tuple(batch_axes)
        self.model_axes = tuple(model_axes)

    def _axis_size(self, names: Sequence[str]) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in names:
            n *= self.mesh.shape[a]
        return n

    def _constrain(self, x: jax.Array, spec: P) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def act(self, x: jax.Array, *dim_axes: Optional[Sequence[str]]) -> jax.Array:
        """Constrain activation x; dim_axes[i] is the mesh-axis tuple for dim i."""
        if self.mesh is None:
            return x
        spec = []
        for i, axes in enumerate(dim_axes):
            if axes is None:
                spec.append(None)
                continue
            axes = tuple(axes)
            size = self._axis_size(axes)
            if size > 1 and x.shape[i] % size == 0:
                spec.append(axes if len(axes) > 1 else axes[0])
            else:
                spec.append(None)
        return self._constrain(x, P(*spec))

    def batch_act(self, x: jax.Array) -> jax.Array:
        """(B, T, d) -> batch over data axes, d over model axes."""
        if x.ndim == 3:
            return self.act(x, self.batch_axes, None, self.model_axes)
        if x.ndim == 2:
            return self.act(x, self.batch_axes, None)
        return x


NULL_SHARDER = Sharder(None)


# ----------------------------------------------------------------- param init
def dense_init(key: jax.Array, d_in: int, d_out: int, scale: float = 1.0,
               dtype=PARAM_DTYPE) -> jax.Array:
    std = scale / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * std).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype=PARAM_DTYPE) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32) * 0.02).astype(dtype)


def split_keys(key: jax.Array, n: int):
    return list(jax.random.split(key, n))


def cast_compute(tree: Params) -> Params:
    """Cast float params to the compute dtype (bf16); leave ints alone."""
    def cast(x):
        if isinstance(x, jax.Array) or hasattr(x, "dtype"):
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(COMPUTE_DTYPE)
        return x
    return jax.tree_util.tree_map(cast, tree)


def count_params(tree: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))
