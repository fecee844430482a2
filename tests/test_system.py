"""End-to-end behaviour tests: the full train loop with checkpoint-resume,
the serve CLI's SLA accounting, planner placement, and elastic re-mesh."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_dlrm
from repro.launch.mesh import make_host_mesh


def test_train_loop_with_resume(tmp_path):
    """Train 6 steps with ckpt_every=3, kill, resume, and verify the resumed
    run continues from step 3 with identical data (step-indexed pipeline)."""
    from repro.checkpoint import CheckpointManager
    from repro.core import dlrm as dlrm_lib
    from repro.data import make_recsys_batch
    from repro.runtime import TrainLoop

    cfg = get_dlrm("dlrm-rm2-small-unsharded").reduced()

    def make_loop(ckpt_dir):
        def step_fn(state, batch):
            params, loss = dlrm_lib.reference_train_step(
                state, batch["dense"], batch["indices"], batch["labels"],
                cfg, 0.05)
            return params, {"loss": loss}
        return TrainLoop(step_fn=step_fn,
                         batch_fn=lambda s: make_recsys_batch(cfg, s),
                         ckpt=CheckpointManager(str(ckpt_dir)), ckpt_every=3)

    params0 = dlrm_lib.init_dlrm(jax.random.PRNGKey(0), cfg)

    # uninterrupted run: 6 steps
    loop_a = make_loop(tmp_path / "a")
    params_a = loop_a.run(jax.tree_util.tree_map(jnp.copy, params0), 6)

    # interrupted run: 3 steps, then resume for 3 more
    loop_b1 = make_loop(tmp_path / "b")
    loop_b1.run(jax.tree_util.tree_map(jnp.copy, params0), 3)
    loop_b2 = make_loop(tmp_path / "b")
    state, start = loop_b2.resume(params0)
    assert start == 3
    params_b = loop_b2.run(state, 3, start)

    for a, b in zip(jax.tree_util.tree_leaves(params_a),
                    jax.tree_util.tree_leaves(params_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_training_loss_decreases_e2e():
    from repro.core import dlrm as dlrm_lib
    from repro.core import sharding as dsh
    from repro.data import make_recsys_batch

    cfg = get_dlrm("dlrm-rm2-small-unsharded").reduced()
    mesh = make_host_mesh()
    step = dsh.make_dlrm_train_step(cfg, mesh, ("data", "model"), lr=0.1)
    params = dlrm_lib.init_dlrm(jax.random.PRNGKey(0), cfg)
    params = dsh.shard_dlrm_params(params, cfg, mesh, ("data", "model"))
    losses = []
    opt = None
    for s in range(80):
        b = make_recsys_batch(cfg, s)
        params, opt, loss = step(params, opt, b["dense"], b["indices"], b["labels"])
        losses.append(float(loss))
    # compare windowed means: single-batch losses are noisy at batch 16
    assert np.mean(losses[-10:]) < np.mean(losses[:10]), \
        (losses[:3], losses[-3:])


def test_planner_place_tables_respects_capacity():
    from repro.core.planner import place_tables

    cfg = get_dlrm("dlrm-rm2-small-unsharded")
    freq = np.linspace(1.0, 40.0, cfg.num_tables)      # table 39 hottest
    table_bytes = cfg.rows_per_table * cfg.embed_dim * 2
    placements, fast_used, bulk_used = place_tables(
        cfg, freq, fast_capacity_bytes=3 * table_bytes,
        bulk_capacity_bytes=40 * table_bytes, n_chips=4)
    fast_ids = {p.table_id for p in placements if p.tier == "fast"}
    assert len(fast_ids) == 12                         # 3 per chip x 4 chips
    # hottest tables got the fast tier
    assert {39, 38, 37}.issubset(fast_ids)
    assert fast_used + bulk_used == 40 * table_bytes


def test_elastic_remesh_roundtrip():
    from jax.sharding import PartitionSpec as P
    from repro.runtime import remesh_tree

    mesh1 = make_host_mesh()
    tree = {"w": jnp.arange(16.0).reshape(4, 4), "b": jnp.ones(3)}
    specs = {"w": P("data"), "b": P()}
    out, report = remesh_tree(tree, specs, mesh1)
    assert report["resharded"] >= 1
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    # non-divisible dim falls back to replication, data preserved
    tree2 = {"w": jnp.ones((3, 3)), "b": jnp.ones(3)}
    out2, report2 = remesh_tree(tree2, specs, mesh1)
    np.testing.assert_array_equal(np.asarray(out2["w"]), np.asarray(tree2["w"]))


CLI_ENV = dict(os.environ, PYTHONPATH=os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))


@pytest.mark.parametrize("cmd", [
    [sys.executable, "-m", "repro.launch.train", "--workload", "dlrm",
     "--smoke", "--steps", "8"],
    [sys.executable, "-m", "repro.launch.serve", "--smoke", "--queries", "10",
     "--sla-ms", "5000"],
])
def test_cli_entrypoints(cmd):
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env=CLI_ENV)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])


def test_compile_cache_dir(monkeypatch):
    """The entry points keep JAX's compile cache where
    JAX_COMPILATION_CACHE_DIR says, and set nothing then; otherwise in one
    fixed directory inside the checkout."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.CHECKOUT / ".jax_compile_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    gitignore = (compile_cache.CHECKOUT / ".gitignore").read_text()
    assert ".jax_compile_cache/" in gitignore.split()
