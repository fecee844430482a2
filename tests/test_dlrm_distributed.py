"""THE core correctness property: distributed DLRM (shard_map, Algorithms
1+2) must match the single-device reference bit-for-bit in fp32 — for both
sharding modes, both exchange modes, and both optimizers. Runs in
subprocesses with 8 virtual devices."""
import pytest

CASE = """
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 8, jax.devices()
from jax.sharding import PartitionSpec as P
from repro.configs.registry import get_dlrm
from repro.core import dlrm as dlrm_lib
from repro.core import sharding as dsh
from repro.data import make_recsys_batch
from repro.launch.mesh import make_mesh
import dataclasses

cfg = get_dlrm("{config}").reduced()
cfg = dataclasses.replace(cfg, batch_size=32, rows_per_table=128, num_tables=8)
mesh = make_mesh((2, 4), ("data", "model"))

params = dlrm_lib.init_dlrm(jax.random.PRNGKey(0), cfg)
ref_params = jax.tree_util.tree_map(lambda x: x.copy(), params)

step = dsh.make_dlrm_train_step(cfg, mesh, ("data", "model"), lr=0.05,
                                row_wise_exchange="{exchange}",
                                optimizer="{optimizer}")
opt = None
if "{optimizer}" == "adagrad":
    opt = {{"table_acc": jnp.zeros((cfg.num_tables, cfg.rows_per_table), jnp.float32)}}
ref_opt = None if opt is None else jax.tree_util.tree_map(lambda x: x.copy(), opt)

sp = dsh.shard_dlrm_params(params, cfg, mesh, ("data", "model"))
losses = []
for s in range(3):
    b = make_recsys_batch(cfg, s)
    sp, opt, loss = step(sp, opt, b["dense"], b["indices"], b["labels"])
    losses.append(float(loss))

# single-device reference: same algorithm, n=1
for s in range(3):
    b = make_recsys_batch(cfg, s)
    if "{optimizer}" == "sgd":
        ref_params, ref_loss = dlrm_lib.reference_train_step(
            ref_params, b["dense"], b["indices"], b["labels"], cfg, 0.05)
    else:
        # adagrad reference via the row update on a single device
        pooled = dlrm_lib.embedding_bag(ref_params["tables"], b["indices"])
        dp = {{"bot_mlp": ref_params["bot_mlp"], "top_mlp": ref_params["top_mlp"]}}
        def dense_loss(dpp, pl):
            return dlrm_lib.bce_loss(dlrm_lib.dlrm_forward_from_pooled(
                {{**ref_params, **dpp}}, b["dense"], pl), b["labels"])
        grads, gp = jax.grad(dense_loss, argnums=(0, 1))(dp, pooled)
        ref_params = {{**jax.tree_util.tree_map(lambda p, g: p - 0.05 * g, dp, grads),
                      "tables": ref_params["tables"]}}
        B, T, L = b["indices"].shape
        g_rows = jnp.broadcast_to(gp[:, :, None, :], (B, T, L, gp.shape[-1]))
        fi = b["indices"].transpose(1, 0, 2).reshape(T, B * L)
        fg = g_rows.transpose(1, 0, 2, 3).reshape(T, B * L, -1)
        upd = dsh.adagrad_row_update(0.05)
        ref_params["tables"], ref_opt["table_acc"] = upd(
            ref_params["tables"], ref_opt["table_acc"], fi, fg)

for key in ("bot_mlp", "top_mlp", "tables"):
    a = jax.tree_util.tree_leaves(jax.device_get(sp[key]))
    b_ = jax.tree_util.tree_leaves(jax.device_get(ref_params[key]))
    for x, y in zip(a, b_):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-5, atol=2e-5, err_msg=key)
print("MATCH", losses)
"""


@pytest.mark.parametrize("config,exchange,optimizer", [
    ("dlrm-rm2-small-unsharded", "unpooled", "sgd"),
    ("dlrm-rm2-small-sharded", "unpooled", "sgd"),
    ("dlrm-rm2-small-sharded", "partial_pool", "sgd"),
    ("dlrm-rm2-large-unsharded", "unpooled", "adagrad"),
    ("dlrm-rm2-large-sharded", "partial_pool", "adagrad"),
])
def test_distributed_matches_reference(subproc, config, exchange, optimizer):
    r = subproc(CASE.format(config=config, exchange=exchange,
                            optimizer=optimizer))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MATCH" in r.stdout


SERVE_CASE = """
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs.registry import get_dlrm
from repro.core import dlrm as dlrm_lib
from repro.core import sharding as dsh
from repro.data import make_recsys_batch
from repro.launch.mesh import make_mesh

cfg = get_dlrm("dlrm-rm2-small-sharded").reduced()
cfg = dataclasses.replace(cfg, batch_size=32, rows_per_table=128, num_tables=8)
mesh = make_mesh((2, 4), ("data", "model"))
params = dlrm_lib.init_dlrm(jax.random.PRNGKey(0), cfg)
serve = dsh.make_dlrm_serve_step(cfg, mesh, ("data", "model"), "{exchange}")
sp = dsh.shard_dlrm_params(params, cfg, mesh, ("data", "model"))
b = make_recsys_batch(cfg, 0)
probs = jax.device_get(serve(sp, b["dense"], b["indices"]))
expect = jax.device_get(dlrm_lib.predict(params, b["dense"], b["indices"], cfg))
np.testing.assert_allclose(probs, expect, rtol=2e-5, atol=2e-6)
print("MATCH")
"""


@pytest.mark.parametrize("exchange", ["unpooled", "partial_pool"])
def test_distributed_serve_matches_reference(subproc, exchange):
    r = subproc(SERVE_CASE.format(exchange=exchange))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MATCH" in r.stdout


CHUNKED_CASE = """
import jax, jax.numpy as jnp, numpy as np, dataclasses, functools
from repro.configs.registry import get_dlrm
from repro.core import dlrm as dlrm_lib
from repro.core import sharding as dsh
from repro.data import make_recsys_batch
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P

# chunked row-wise lookup == unchunked (associativity of partial pooling)
cfg = get_dlrm("dlrm-rm2-small-sharded").reduced()
cfg = dataclasses.replace(cfg, batch_size=64, rows_per_table=128, num_tables=8)
mesh = make_mesh((8,), ("x",))
params = dlrm_lib.init_dlrm(jax.random.PRNGKey(1), cfg)
b = make_recsys_batch(cfg, 0)

def fwd(chunk):
    def f(tables, idx):
        pooled, _ = dsh.row_wise_forward(tables, idx, "x", 8,
                                         "partial_pool", lookup_chunk=chunk)
        return pooled
    return jax.jit(jax.shard_map(f, mesh=mesh,
                                 in_specs=(P(None, "x"), P("x")),
                                 out_specs=P("x"), check_vma=False))

p1 = jax.device_get(fwd(8)(params["tables"], b["indices"]))
p2 = jax.device_get(fwd(10**9)(params["tables"], b["indices"]))
np.testing.assert_allclose(p1, p2, rtol=1e-5, atol=1e-5)
print("MATCH")
"""


def test_chunked_lookup_matches_unchunked(subproc):
    r = subproc(CHUNKED_CASE)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MATCH" in r.stdout
