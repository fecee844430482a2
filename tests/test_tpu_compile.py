"""Compile rehearsal: the serve path's kernels and steps, compiled for a
described (not attached) TPU v5e at RM2's published widths.

Nothing here runs; each test asks the installed TPU compiler to accept the
program, which is what interpret mode cannot show (tile alignment, SMEM and
VMEM limits, relayout copies of a whole table). The topology is described
in a fixture, never at import time: only one process at a time may load
the TPU library, and every test worker imports this file.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_dlrm
from repro.core.table_layout import rows_per_line, to_rows

T, L = 40, 80
TABLE_BYTES = 40 * 2 ** 20 * 32 * 4       # RM2-small at 2^20 rows, fp32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lines(n_tables, rows, d, sharding):
    """Abstract table stored as lane-dense lines, as placement stores it."""
    p = rows_per_line(d, rows)
    return _sds((n_tables, rows // p, p * d), jnp.float32, sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _no_table_copy(compiled):
    """The program reads and updates the table where it lies: its scratch
    is far below one copy of the table."""
    assert compiled.memory_analysis().temp_size_in_bytes < TABLE_BYTES // 4


# ------------------------------------------------------------ kernels
@pytest.mark.parametrize("d", [32, 128])
@pytest.mark.parametrize("B", [200, 800])
def test_fused_bag_interactions_compiles(one_chip, B, d):
    from repro.kernels.fused_serve import fused_bag_interactions_pallas

    rows = 2 ** 20 * 32 // d                 # 5.4 GB of fp32 rows either way
    c = _compile(
        lambda tl, ix, bot: fused_bag_interactions_pallas(
            to_rows(tl, d), ix, bot, interpret=False),
        _lines(T, rows, d, one_chip), _sds((B, T, L), jnp.int32, one_chip),
        _sds((B, d), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()
    _no_table_copy(c)
    # the line buffers are VMEM scratch: what the program keeps in HBM
    # besides its operands is the batch's padding, ~0.1 MB, not lines
    assert c.memory_analysis().temp_size_in_bytes < TABLE_BYTES // 1024


def test_fused_cached_bag_interactions_compiles(one_chip):
    from repro.kernels.fused_serve import fused_cached_bag_interactions_pallas

    B, d = 200, 32
    # tier row counts (S+1, R+1) that are multiples of 128 // d
    fast, bulk = 2 ** 16, 2 ** 20
    c = _compile(
        lambda fl, bl, fi, bi, bot: fused_cached_bag_interactions_pallas(
            to_rows(fl, d), to_rows(bl, d), fi, bi, bot, interpret=False),
        _lines(T, fast, d, one_chip), _lines(T, bulk, d, one_chip),
        _sds((B, T, L), jnp.int32, one_chip),
        _sds((B, T, L), jnp.int32, one_chip),
        _sds((B, d), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()
    _no_table_copy(c)


def test_fused_grouped_bag_interactions_compiles(one_chip):
    from repro.kernels.fused_serve import fused_grouped_bag_interactions_pallas

    B, d, Tf = 200, 32, 16
    inv_perm = tuple(range(T))
    c = _compile(
        lambda fl, bl, ix, bot: fused_grouped_bag_interactions_pallas(
            to_rows(fl, d), to_rows(bl, d), ix, bot, inv_perm=inv_perm,
            interpret=False),
        _lines(Tf, 2 ** 20, d, one_chip), _lines(T - Tf, 2 ** 20, d, one_chip),
        _sds((B, T, L), jnp.int32, one_chip),
        _sds((B, d), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()
    _no_table_copy(c)


def test_embedding_bag_compiles(one_chip):
    from repro.kernels.embedding_bag import embedding_bag_pallas

    B, d = 200, 32
    c = _compile(
        lambda tl, ix: embedding_bag_pallas(to_rows(tl, d), ix,
                                            interpret=False),
        _lines(T, 2 ** 20, d, one_chip), _sds((B, T, L), jnp.int32, one_chip))
    assert "tpu_custom_call" in c.as_text()
    _no_table_copy(c)


def test_cached_embedding_bag_compiles(one_chip):
    from repro.kernels.cached_embedding_bag import cached_embedding_bag_pallas

    B, d = 200, 32
    c = _compile(
        lambda fl, bl, fi, bi: cached_embedding_bag_pallas(
            to_rows(fl, d), to_rows(bl, d), fi, bi, interpret=False),
        _lines(T, 2 ** 16, d, one_chip), _lines(T, 2 ** 20, d, one_chip),
        _sds((B, T, L), jnp.int32, one_chip),
        _sds((B, T, L), jnp.int32, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_interactions_compiles(one_chip):
    from repro.kernels.interactions import interactions_pallas

    B, d = 200, 32
    c = _compile(functools.partial(interactions_pallas, interpret=False),
                 _sds((B, d), jnp.float32, one_chip),
                 _sds((B, T, d), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


# ------------------------------------------------- the single-board steps
@pytest.fixture()
def board(topo, monkeypatch):
    """RM2-small at 2^20 rows on a one-device mesh of the described chip,
    with the ops dispatching to the compiled kernels as on a TPU host."""
    from repro import parallel
    from repro.core import dlrm as dlrm_lib
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_dlrm("dlrm-rm2-small-unsharded"),
                              rows_per_table=2 ** 20)
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    axis = ("data", "model")
    p = parallel.table_rows_per_line(cfg, mesh, axis)
    assert p == 4                             # lines on a TPU mesh
    shapes = jax.eval_shape(functools.partial(dlrm_lib.init_dlrm, cfg=cfg),
                            jax.random.PRNGKey(0))
    shapes["tables"] = jax.ShapeDtypeStruct(
        (T, cfg.rows_per_table // p, p * cfg.embed_dim), jnp.float32)
    params = jax.tree_util.tree_map(
        lambda s, spec: _sds(s.shape, s.dtype, NamedSharding(mesh, spec)),
        shapes, parallel.param_specs(cfg, axis),
        is_leaf=lambda x: isinstance(x, P))
    data = NamedSharding(mesh, P(axis))
    return cfg, mesh, axis, params, data


def test_serve_step_compiles(board):
    from repro import parallel

    cfg, mesh, axis, params, data = board
    B = cfg.batch_size
    step = parallel.build_step(cfg, mesh, mode="serve", axis=axis)
    c = step.lower(params, _sds((B, cfg.num_dense), jnp.float32, data),
                   _sds((B, T, L), jnp.int32, data)).compile()
    assert "tpu_custom_call" in c.as_text()   # the fused kernel is in it
    _no_table_copy(c)


def test_train_step_compiles(board):
    from repro import parallel

    cfg, mesh, axis, params, data = board
    B = cfg.batch_size
    step = parallel.build_step(cfg, mesh, mode="train", axis=axis)
    c = step.lower(params, None,
                   _sds((B, cfg.num_dense), jnp.float32, data),
                   _sds((B, T, L), jnp.int32, data),
                   _sds((B,), jnp.float32, data)).compile()
    # the update scatters into the donated table; the scratch is the
    # line-widened row-gradient block (B*T*L lines, ~0.33 GB), not a copy
    _no_table_copy(c)
