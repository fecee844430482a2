"""What the drivers share: the program's configuration and mesh for a
configuration file, and the benchmark's weights placed as the program
stores them."""
from __future__ import annotations

import time

import numpy as np

import gen
from flops import bot_dims

AXIS = ("data", "model")


def clock() -> float:
    return time.perf_counter()


def program_config(cfg: dict):
    """The program's DLRMConfig, with every size from the file."""
    from repro.configs.base import DLRMConfig
    return DLRMConfig(
        name=cfg["name"], num_tables=cfg["num_tables"],
        lookups_per_table=cfg["lookups_per_table"],
        embed_dim=cfg["embed_dim"], rows_per_table=cfg["rows_per_table"],
        num_dense=cfg["num_dense"], bot_mlp=tuple(cfg["bot_mlp"]),
        top_mlp=tuple(cfg["top_mlp"]), batch_size=cfg["batch_size"],
        sharding=cfg["sharding"])


def mesh_for(devices):
    """One row of devices: the embedding axis spans them all."""
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices).reshape(1, len(devices)), AXIS)


def layers(cfg: dict):
    top_in = cfg["embed_dim"] + (cfg["num_tables"] + 1) * cfg["num_tables"] // 2
    return gen.mlp_layers(cfg["num_dense"], bot_dims(cfg), cfg["top_mlp"],
                          top_in)


def weight_keys(cfg: dict, seed: int) -> np.ndarray:
    return gen.weight_keys(seed, 2 * len(layers(cfg)))


def initial_params(cfg: dict, pcfg, mesh):
    """keys -> params in the program's table layout (lines of p rows on a
    TPU), and the program's shardings of them."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import parallel
    p = parallel.table_rows_per_line(pcfg, mesh, AXIS)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), parallel.param_specs(pcfg, AXIS),
        is_leaf=lambda x: isinstance(x, P))
    lay = layers(cfg)
    T, R, d = cfg["num_tables"], cfg["rows_per_table"], cfg["embed_dim"]
    bound = gen.table_bound(d)

    def init(keys):
        return {**gen.dense_leaves(keys, lay),
                "tables": gen.table_lines(keys[-1], T, R, d, p, bound)}
    return init, shardings


def initial_params_fn(cfg: dict, pcfg, mesh):
    """A jitted keys -> params that writes every weight straight into the
    program's placement. One compiled program for every seed."""
    import jax
    init, shardings = initial_params(cfg, pcfg, mesh)
    return jax.jit(init, out_shardings=shardings)


def place_params(cfg: dict, pcfg, mesh, seed: int):
    return initial_params_fn(cfg, pcfg, mesh)(weight_keys(cfg, seed))
