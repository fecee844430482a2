#!/usr/bin/env python3
"""Smoke test of the DLRM main path on TPU chips.

    python chip_smoke.py             # one chip: RM2-small train + serve
    python chip_smoke.py --chips 4   # four chips: RM2-large row-sharded

Default phase, on one chip: `dlrm-rm2-small-unsharded` at its published
widths (T=40, L=80, d=32, B=200, dense 256, bottom MLP 256-128-32, top MLP
512-128-1) with the rows per table cut from 2^22 to 2^20, so the fp32
tables take 5.4 GB of the chip's 16 GB. Goes `Engine` -> `train_session()`
-> a few SGD steps -> `serve_session(params=train.params)` (the trained
tables, no second copy) -> serial queries and a short open-loop run through
the fused serve kernel, and compares one served batch with the float32
reference `dlrm_lib.predict`.

`--chips 4`: `dlrm-rm2-large-sharded` (d=128, B=600) row-sharded over four
chips with the Alg. 1/2 collectives (`Engine(model_axis=4)`): at 2^18 rows
a few train steps and a served batch, compared with the same weights served
on one chip; at 2^20 rows (21.5 GB fp32, more than one chip holds) train
and serve, and each chip's bytes in use.

Exits nonzero, printing no result, when no TPU is present or any phase
fails. The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Served probabilities against the reference, and four chips against one.
# The serve step runs its f32 matmuls at the TPU's default precision (at
# worst one bf16 pass: relative error 2^-9 per operand), the reference at
# "highest" (exact f32). Rounding every weight and the dense input of RM2
# at these widths to bf16 moves a probability by at most 3.5e-5 (host
# emulation); activations rounded as well about double that. The random
# model's probabilities spread over ~2e-3, so 2e-4 still fails a model
# that ignores its sparse or dense features.
SERVE_TOL = 2e-4
# Pooled embeddings are sums of 80 fp32 rows on both sides, in orders that
# may differ: rounding only.
POOL_RTOL = 1e-5
# The fused kernel's interaction features (d=32 dot products of pooled
# vectors), relative to the largest reference feature: at worst one bf16
# pass per product, 2^-8. One wrong row among a bag's 80 moves its pooled
# vector, and so its dot products, by about a tenth.
FUSED_RTOL = 2 ** -8
GB = 1e9


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"[smoke] FAILED: {what}")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def mem(dev) -> dict:
    s = dev.memory_stats() or {}
    return {"in_use": s.get("bytes_in_use"),
            "peak": s.get("peak_bytes_in_use")}


def cut_config(name: str, rows: int):
    from repro.configs.registry import get_dlrm

    cfg = get_dlrm(name)
    full = cfg.num_tables * cfg.rows_per_table * cfg.embed_dim * 4
    cut = dataclasses.replace(cfg, rows_per_table=rows)
    log(f"{name}: T={cfg.num_tables} L={cfg.lookups_per_table} "
        f"d={cfg.embed_dim} B={cfg.batch_size} dense={cfg.num_dense} "
        f"bot={'-'.join(map(str, cfg.bot_mlp_dims))} "
        f"top={'-'.join(map(str, cfg.top_mlp))}; cut: rows_per_table "
        f"2^{int(math.log2(cfg.rows_per_table))} -> 2^{int(math.log2(rows))}"
        f" (fp32 tables {full / GB:.2f} GB -> "
        f"{cut.num_tables * rows * cut.embed_dim * 4 / GB:.2f} GB)")
    return cut


def one_device_mesh(dev):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray([dev]).reshape(1, 1), ("data", "model"))


def train(eng, steps: int):
    """TrainSession: compile + first step, then `steps` timed steps."""
    import jax

    t0 = time.perf_counter()
    sess = eng.train_session()
    jax.block_until_ready(sess.params)
    t_init = time.perf_counter() - t0
    t0 = time.perf_counter()
    first = sess.run(1)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep = sess.run(steps)
    jax.block_until_ready(sess.params)
    t_step = (time.perf_counter() - t0) / steps
    losses = [first.first_loss] + [h["loss"] for h in rep.history]
    log(f"train: init+place {t_init:.2f}s, compile+first step "
        f"{t_first:.2f}s, {t_step * 1e3:.2f} ms/step over {steps} steps, "
        f"losses {['%.6f' % x for x in losses]}")
    check(all(math.isfinite(x) for x in losses), "train loss not finite")
    return sess


def phase_one_chip(dev) -> None:
    import jax
    import numpy as np

    from repro.core import dlrm as dlrm_lib
    from repro.core.table_layout import to_rows
    from repro.data import make_recsys_batch
    from repro.engine import Engine
    from repro.kernels import ops, ref

    cfg = cut_config("dlrm-rm2-small-unsharded", 2 ** 20)
    eng = Engine(cfg, mesh=one_device_mesh(dev), pipeline_depth=1,
                 lr=0.05, seed=0)
    tr = train(eng, steps=3)
    log(f"after train: bytes in use {mem(dev)}")

    t0 = time.perf_counter()
    serve = eng.serve_session(params=tr.params, max_batch_queries=4,
                              max_wait_ms=5.0)
    log(f"serve_kernel={serve.serve_kernel}")
    check(serve.serve_kernel == "fused", "serve kernel is not the fused one")
    check(serve.params["tables"].unsafe_buffer_pointer()
          == tr.params["tables"].unsafe_buffer_pointer(),
          "serving copied the trained tables")
    log(f"serve: place {time.perf_counter() - t0:.2f}s")
    for k in range(1, serve.max_batch_queries + 1):
        t0 = time.perf_counter()
        t_batch = serve.measure_service_time(k, repeats=3)
        log(f"serve: {k} queries ({k * serve.query_size} samples): compile "
            f"+ 3 runs {time.perf_counter() - t0:.2f}s, median "
            f"{t_batch * 1e3:.3f} ms per batch")
    rep = serve.run_serial(8)
    log(f"serve serial: 8 queries, p50 {rep.p50_ms:.3f} ms, p99 "
        f"{rep.p99_ms:.3f} ms per query of {serve.query_size} samples")
    rep = serve.run_open_loop(50, qps=5.0)
    log(f"serve open loop: 50 queries at 5 qps (virtual clock, measured "
        f"service), mean batch {rep.mean_batch_queries:.2f} queries, p50 "
        f"{rep.p50_ms:.3f} ms, p99 {rep.p99_ms:.3f} ms")
    check(rep.n_queries == 50, "open loop lost queries")

    # one served batch against the float32 reference on the same chip
    b = make_recsys_batch(cfg, 10_000, 0, 0.0)
    got = serve.serve_direct(b["dense"], b["indices"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(dlrm_lib.predict, static_argnames="cfg")(
            serve.params, b["dense"], b["indices"], cfg=cfg))
    diff = float(np.max(np.abs(got - want)))
    log(f"served batch vs dlrm_lib.predict (f32, highest): max |dp| "
        f"{diff:.3e} (tolerance {SERVE_TOL:g}); probs in "
        f"[{got.min():.4f}, {got.max():.4f}]")
    check(got.shape == (cfg.batch_size,) and np.isfinite(got).all(),
          "served batch not finite or wrong shape")
    check(diff <= SERVE_TOL, "served batch disagrees with the reference")

    # the fused kernel's features, and the composed bag kernel (the sharded
    # fleet's lookup), on the same batch against the jnp gather; every side
    # reads the stored lines in place
    d = cfg.embed_dim
    tables, idx = serve.params["tables"], b["indices"]
    bot = jax.jit(lambda p, x: dlrm_lib.mlp_forward(p["bot_mlp"], x))(
        serve.params, b["dense"])
    want_p = jax.jit(lambda t, i: dlrm_lib.embedding_bag(t, i, d))(
        tables, idx)
    with jax.default_matmul_precision("highest"):
        want_f = np.asarray(jax.jit(ref.interactions_ref)(bot, want_p))
    feats = np.asarray(jax.jit(
        lambda t, i, x: ops.fused_bag_interactions(to_rows(t, d), i, x))(
            tables, idx, bot))
    pooled = np.asarray(jax.jit(
        lambda t, i: ops.embedding_bag(to_rows(t, d), i))(tables, idx))
    for what, got_, want_, tol in (
            ("fused kernel features", feats, want_f, FUSED_RTOL),
            ("embedding_bag kernel", pooled, np.asarray(want_p), POOL_RTOL)):
        rel = float(np.max(np.abs(got_ - want_))
                    / max(np.max(np.abs(want_)), 1e-30))
        log(f"{what} vs jnp gather: max rel diff {rel:.3e} "
            f"(tolerance {tol:g})")
        check(rel <= tol, f"{what} disagrees")
    log(f"bytes in use {mem(dev)}")


def phase_four_chips(devs) -> None:
    import jax
    import numpy as np

    from repro.core import dlrm as dlrm_lib
    from repro.data import make_recsys_batch
    from repro.engine import Engine

    check(len(devs) == 4, f"--chips 4 needs four devices, found {len(devs)}")

    # 2^18 rows: four chips against one, same weights
    cfg = cut_config("dlrm-rm2-large-sharded", 2 ** 18)
    eng = Engine(cfg, model_axis=4, pipeline_depth=1, lr=0.05, seed=0)
    tr = train(eng, steps=3)
    serve4 = eng.serve_session(params=tr.params, max_batch_queries=1)
    log(f"4 chips: serve_kernel={serve4.serve_kernel} (row-sharded: Alg. 1 "
        f"collectives)")
    b = make_recsys_batch(cfg, 10_000, 0, 0.0)
    t0 = time.perf_counter()
    got4 = serve4.serve_direct(b["dense"], b["indices"])
    log(f"4 chips: first served batch (compile + run) "
        f"{time.perf_counter() - t0:.2f}s")
    t0 = time.perf_counter()
    got4 = serve4.serve_direct(b["dense"], b["indices"])
    log(f"4 chips: served batch {(time.perf_counter() - t0) * 1e3:.2f} ms")
    eng1 = Engine(cfg, mesh=one_device_mesh(devs[0]), pipeline_depth=1,
                  seed=0)
    serve1 = eng1.serve_session(params=tr.params, max_batch_queries=1)
    log(f"1 chip: serve_kernel={serve1.serve_kernel}")
    got1 = serve1.serve_direct(b["dense"], b["indices"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(dlrm_lib.predict, static_argnames="cfg")(
            serve1.params, b["dense"], b["indices"], cfg=cfg))
    check(np.isfinite(got4).all() and got4.shape == (cfg.batch_size,),
          "4-chip served batch not finite or wrong shape")
    diffs = {what: float(np.max(np.abs(x - y))) for what, x, y in (
        ("4 chips vs 1 chip, same weights", got4, got1),
        ("4 chips vs dlrm_lib.predict (f32, highest)", got4, want),
        ("1 chip vs dlrm_lib.predict (f32, highest)", got1, want))}
    for what, diff in diffs.items():
        log(f"{what}: max |dp| {diff:.3e} (tolerance {SERVE_TOL:g})")
    for what, diff in diffs.items():
        check(diff <= SERVE_TOL, f"{what}: served batches disagree")
    del serve4, serve1, tr, eng, eng1
    gc.collect()

    # 2^20 rows: more than one chip holds; each chip keeps a quarter
    cfg = cut_config("dlrm-rm2-large-sharded", 2 ** 20)
    total = cfg.num_tables * cfg.rows_per_table * cfg.embed_dim * 4
    eng = Engine(cfg, model_axis=4, pipeline_depth=1, lr=0.05, seed=0)
    tr = train(eng, steps=2)
    serve4 = eng.serve_session(params=tr.params, max_batch_queries=1)
    b = make_recsys_batch(cfg, 10_001, 0, 0.0)
    got = serve4.serve_direct(b["dense"], b["indices"])
    check(np.isfinite(got).all(), "2^20-row served batch not finite")
    shards = {s.device.id: s.data.nbytes
              for s in serve4.params["tables"].addressable_shards}
    use = [mem(d) for d in devs[:4]]
    log(f"2^20 rows: tables {total / GB:.2f} GB; table bytes per chip "
        f"{[shards[d.id] for d in devs[:4]]}; bytes in use per chip "
        f"{[u['in_use'] for u in use]}; peak per chip "
        f"{[u['peak'] for u in use]}")
    for d in devs[:4]:
        check(shards[d.id] == total // 4, f"chip {d.id} holds "
              f"{shards[d.id]} table bytes, not a quarter of {total}")
    for u in use:
        check(u["in_use"] is not None
              and 0.2 * total <= u["in_use"] <= 0.35 * total,
              f"bytes in use {u['in_use']} is not about a quarter of "
              f"the {total}-byte tables")
    check(max(u["peak"] for u in use) < 0.5 * total,
          "one chip held more than half the tables at some point")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: RM2-small train+serve on one chip (default); "
                         "4: RM2-large row-sharded over four chips")
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[smoke] no repro package under {src}: run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[smoke] no TPU found: JAX reports {devs[0].platform} "
              f"devices", file=sys.stderr)
        return 1
    dev = devs[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devs)}; jax "
        f"{jax.__version__}; compile cache {cache}")

    if args.chips == 4:
        phase_four_chips(devs)
    else:
        phase_one_chip(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
