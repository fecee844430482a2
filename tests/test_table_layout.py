"""Lane-dense table lines (`core/table_layout.py`) and where they are used.

Lines are how a TPU placement stores tables narrower than 128 lanes. Every
XLA path that reads or updates them must give exactly what the row layout
gives; here that is checked on the host, where placement normally keeps
rows, by forcing the TPU's choice of layout.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_dlrm
from repro.core import table_layout as tl


@pytest.mark.parametrize("d,rows,want", [
    (32, 1024, 4), (8, 64, 16), (128, 64, 1), (256, 64, 1),
    (32, 1025, 1),            # rows not a multiple of 4: a row is a line
    (24, 96, 1),              # 24 does not divide 128
])
def test_rows_per_line(d, rows, want):
    assert tl.rows_per_line(d, rows) == want


@pytest.mark.parametrize("d,rows", [(32, 64), (8, 32), (128, 16)])
def test_gather_and_scatter_match_rows(d, rows):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(d), 3)
    tab = jax.random.normal(k1, (rows, d))
    idx = jax.random.randint(k2, (50,), 0, rows)      # with repeats
    upd = jax.random.normal(k3, (50, d))
    lines = tl.to_lines(tab, tl.rows_per_line(d, rows))
    assert tl.num_rows(lines, d) == rows
    np.testing.assert_array_equal(tl.gather_rows(lines, idx, d),
                                  jnp.take(tab, idx, axis=0))
    np.testing.assert_array_equal(
        tl.to_rows(tl.scatter_add_rows(lines, idx, upd), d),
        tab.at[idx].add(upd))


def _force_lines(monkeypatch):
    """Place tables as a TPU mesh would: lines wherever d < 128."""
    from repro.parallel import build

    monkeypatch.setattr(
        build, "table_rows_per_line",
        lambda cfg, mesh, axis: tl.rows_per_line(
            cfg.embed_dim, cfg.rows_per_table // build.axis_size(mesh, axis)))


def _cfg(name="dlrm-rm2-small-unsharded"):
    return dataclasses.replace(get_dlrm(name).reduced(), batch_size=8)


def _run(cfg, plan):
    from repro.data import make_recsys_batch
    from repro.engine import Engine

    eng = Engine(cfg, plan=plan, alpha=1.05, lr=0.05, pipeline_depth=1)
    train = eng.train_session()
    losses = [h["loss"] for h in train.run(3).history]
    serve = eng.serve_session(params=train.params, max_batch_queries=2)
    b = make_recsys_batch(cfg, 7, 0, 1.05)
    return (train.params, losses, serve.serve_kernel,
            serve.serve_direct(b["dense"], b["indices"]))


@pytest.mark.parametrize("name,plan", [
    ("dlrm-rm2-small-unsharded", "none"),   # table-wise, fused serve
    ("dlrm-rm2-small-sharded", "none"),     # row-wise, composed serve
    ("dlrm-rm2-small-unsharded", "auto"),   # tiered groups, fused grouped
])
def test_sessions_on_lines_match_rows(monkeypatch, name, plan):
    """Train then serve the trained weights: with tables stored as lines
    the losses, the trained tables and the served outputs are bit-identical
    to the row layout."""
    cfg = _cfg(name)
    p_rows, l_rows, k_rows, out_rows = _run(cfg, plan)
    _force_lines(monkeypatch)
    p_lines, l_lines, k_lines, out_lines = _run(cfg, plan)
    assert l_lines == l_rows and k_lines == k_rows
    np.testing.assert_array_equal(out_lines, out_rows)
    for k, v in p_rows.items():
        if k.startswith("tables"):
            got = np.asarray(p_lines[k])
            assert got.shape[-1] == 128 // cfg.embed_dim * v.shape[-1]
            np.testing.assert_array_equal(tl.to_rows(got, cfg.embed_dim),
                                          np.asarray(v))


def test_placed_init_matches_init_then_place(monkeypatch):
    """`init_dlrm_params` builds the params in place with the values that
    `init_dlrm` followed by `shard_dlrm_params` gives."""
    from repro import parallel
    from repro.core import dlrm as dlrm_lib
    from repro.launch.mesh import make_host_mesh

    _force_lines(monkeypatch)
    cfg, mesh, axis = _cfg(), make_host_mesh(), ("data", "model")
    key = jax.random.PRNGKey(3)
    got = parallel.init_dlrm_params(key, cfg, mesh, axis)
    want = parallel.shard_dlrm_params(dlrm_lib.init_dlrm(key, cfg), cfg,
                                      mesh, axis)
    assert got["tables"].shape == (cfg.num_tables, cfg.rows_per_table // 4,
                                   128)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
