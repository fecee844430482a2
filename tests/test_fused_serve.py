"""Fused serve megakernel: equivalence vs the composed path, at every layer.

Layer 1: the Pallas kernels (interpret mode) vs the composed jnp oracle —
shape sweeps that hit the batch-pad path, T=1, block_b > B clamping, and
the tiered/grouped layouts at hot fractions {0, 0.1, 1}.
Layer 2: no-leak — poisoned pad-gather rows must never reach real outputs.
Layer 3: the serve session — fused vs composed sessions are bit-identical
on CPU (the fused ops dispatch to the same composed jnp graph off-TPU),
the kernel choice is recorded, and non-local exchanges fall back.
Layer 4: the measured-kernel-times calibration the bench artifact feeds
into `perf_model.inference_breakdown`.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_dlrm
from repro.kernels import ref
from repro.kernels.fused_serve import (fused_bag_interactions_pallas,
                                       fused_cached_bag_interactions_pallas,
                                       fused_grouped_bag_interactions_pallas)


def _inputs(key, B, T, L, R, d):
    k1, k2, k3 = jax.random.split(key, 3)
    tables = jax.random.normal(k1, (T, R, d), jnp.float32)
    idx = jax.random.randint(k2, (B, T, L), 0, R)
    bot = jax.random.normal(k3, (B, d), jnp.float32)
    return tables, idx, bot


# ------------------------------------------------------- single-tier kernel
@pytest.mark.parametrize("B,T,L,R,d,bb", [
    (6, 3, 4, 16, 8, 4),     # B not a multiple of block_b: pad path
    (4, 1, 5, 32, 16, 4),    # single table
    (3, 2, 2, 8, 8, 64),     # block_b > B: clamps to B
    (8, 5, 3, 24, 16, 4),    # exact blocking
    (5, 3, 13, 64, 32, 4),   # d = 32, p = 4: L not a multiple of 8
    (3, 2, 5, 16, 128, 2),   # d = 128: one row per line (p = 1)
])
def test_fused_matches_composed(B, T, L, R, d, bb):
    tables, idx, bot = _inputs(jax.random.PRNGKey(B * 10 + T), B, T, L, R, d)
    got = fused_bag_interactions_pallas(tables, idx, bot, block_b=bb,
                                        interpret=True)
    want = ref.interactions_ref(bot, ref.embedding_bag_ref(tables, idx))
    assert got.shape == (B, d + (T + 1) * T // 2)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bag", [
    [8, 9, 10, 11, 9, 8],     # every slot of line 2, two of them twice
    [13] * 9,                 # one row, every lookup
])
def test_fused_pools_rows_sharing_a_line(bag):
    B, T, R, d = 3, 2, 16, 32                      # p = 4 rows a line
    tables, _, bot = _inputs(jax.random.PRNGKey(7), B, T, len(bag), R, d)
    idx = jnp.broadcast_to(jnp.asarray(bag, jnp.int32), (B, T, len(bag)))
    got = fused_bag_interactions_pallas(tables, idx, bot, block_b=2,
                                        interpret=True)
    want = ref.fused_bag_interactions_ref(tables, idx, bot)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -------------------------------------------------- two-tier (cached) kernel
def _pack_two_tier(tables, idx, hot_fraction, key, line_rows=1):
    """cached_embedding_bag layout: hot rows packed into a fast tier with
    zeros miss slot S; bulk keeps every row plus a zeros hit slot R. The
    fast tier's row count is padded with zero rows to a multiple of
    ``line_rows``."""
    T, R, d = tables.shape
    hot = np.asarray(jax.random.bernoulli(key, hot_fraction, (T, R)))
    tabs = np.asarray(tables)
    S = max(int(hot.sum(axis=1).max()), 1)
    S1 = -(-(S + 1) // line_rows) * line_rows
    fast = np.zeros((T, S1, d), np.float32)
    slot = np.full((T, R), S, np.int32)
    for t in range(T):
        rows = np.flatnonzero(hot[t])
        fast[t, :len(rows)] = tabs[t, rows]
        slot[t, rows] = np.arange(len(rows))
    bulk = np.concatenate([tabs, np.zeros((T, 1, d), np.float32)], axis=1)
    idx_np = np.asarray(idx)
    t_ax = np.arange(T)[None, :, None]
    fi = jnp.asarray(slot[t_ax, idx_np])
    bi = jnp.asarray(np.where(hot[t_ax, idx_np], R, idx_np))
    return jnp.asarray(fast), jnp.asarray(bulk), fi, bi


@pytest.mark.parametrize("hot_fraction,L,R,d,line_rows", [
    (0.0, 4, 16, 8, 1),
    (0.1, 4, 16, 8, 1),
    (1.0, 4, 16, 8, 1),
    (0.5, 13, 63, 32, 4),     # both tiers 4 rows a line, L % 8 != 0
])
def test_fused_cached_matches_composed(hot_fraction, L, R, d, line_rows):
    B, T = 5, 3
    tables, idx, bot = _inputs(jax.random.PRNGKey(17), B, T, L, R, d)
    fast, bulk, fi, bi = _pack_two_tier(tables, idx, hot_fraction,
                                        jax.random.PRNGKey(18),
                                        line_rows=line_rows)
    got = fused_cached_bag_interactions_pallas(fast, bulk, fi, bi, bot,
                                               block_b=4, interpret=True)
    want = ref.fused_bag_interactions_ref(tables, idx, bot)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and against the cached-layout composed oracle, same translated streams
    want2 = ref.interactions_ref(
        bot, ref.cached_embedding_bag_ref(fast, bulk, fi, bi))
    np.testing.assert_allclose(got, want2, rtol=1e-5, atol=1e-5)


# ------------------------------------------- grouped (tiered-plan) kernel
def _grouped_case(fast_ids, bulk_ids, B=5, L=3, R=16, d=8, seed=23):
    from repro.parallel.plan import PlanGroups

    T = len(fast_ids) + len(bulk_ids)
    tables, idx, bot = _inputs(jax.random.PRNGKey(seed), B, T, L, R, d)
    groups = PlanGroups(tuple(fast_ids), tuple(bulk_ids))
    perm = np.asarray(groups.fast_ids + groups.bulk_ids, np.int32)
    tf = tables[jnp.asarray(groups.fast_ids, jnp.int32)] if fast_ids \
        else tables[:0]
    tb = tables[jnp.asarray(groups.bulk_ids, jnp.int32)] if bulk_ids \
        else tables[:0]
    got = fused_grouped_bag_interactions_pallas(
        tf, tb, idx[:, perm, :], bot, inv_perm=groups.inv_perm,
        block_b=4, interpret=True)
    want = ref.fused_bag_interactions_ref(tables, idx, bot)
    return got, want


def test_fused_grouped_matches_original_order():
    # non-trivial interleaved permutation: fast {2, 0}, bulk {4, 1, 3}
    got, want = _grouped_case([2, 0], [4, 1, 3])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fast_ids,bulk_ids", [
    ([3, 1, 0, 2], []),      # empty bulk: delegates to single-tier
    ([], [1, 3, 0, 2]),      # empty fast
])
def test_fused_grouped_empty_group_delegates(fast_ids, bulk_ids):
    got, want = _grouped_case(fast_ids, bulk_ids)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("L,R,d", [
    (3, 12, 8),
    (13, 16, 32),             # p = 4 in both groups, L % 8 != 0
])
def test_fused_grouped_matches_grouped_ref(L, R, d):
    from repro.parallel.plan import PlanGroups

    groups = PlanGroups((1, 2), (0, 3))
    B = 6
    tables, idx, bot = _inputs(jax.random.PRNGKey(5), B, 4, L, R, d)
    perm = np.asarray(groups.fast_ids + groups.bulk_ids, np.int32)
    tf = tables[jnp.asarray(groups.fast_ids, jnp.int32)]
    tb = tables[jnp.asarray(groups.bulk_ids, jnp.int32)]
    idx_perm = idx[:, perm, :]
    got = fused_grouped_bag_interactions_pallas(
        tf, tb, idx_perm, bot, inv_perm=groups.inv_perm, block_b=4,
        interpret=True)
    want = ref.fused_grouped_bag_interactions_ref(
        tf, tb, idx_perm, bot, groups.inv_perm)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------- no leakage
def test_pad_samples_never_leak_poisoned_row0():
    """_pad_batch pads with index 0: pad SAMPLES gather real row 0. Poison
    row 0 — real outputs must be untouched and finite even though every
    pad sample pools B*T*L copies of the poison."""
    B, T, L, R, d, bb = 5, 3, 4, 16, 8, 4            # pads 5 -> 8
    tables, idx, bot = _inputs(jax.random.PRNGKey(31), B, T, L, R, d)
    idx = jnp.clip(idx, 1, R - 1)                     # real samples avoid row 0
    poisoned = tables.at[:, 0, :].set(1e30)
    got = fused_bag_interactions_pallas(poisoned, idx, bot, block_b=bb,
                                        interpret=True)
    want = ref.fused_bag_interactions_ref(poisoned, idx, bot)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_cached_miss_slots_never_leak():
    """The zeros miss slot S / hit slot R are load-bearing: every step DMAs
    one row from EACH tier, so the non-owning tier's row must contribute
    exactly 0. Poison every non-slot row that the translated streams never
    reference and check nothing bleeds through."""
    B, T, L, R, d = 4, 2, 3, 8, 8
    tables, idx, bot = _inputs(jax.random.PRNGKey(41), B, T, L, R, d)
    fast, bulk, fi, bi = _pack_two_tier(tables, idx, 0.5,
                                        jax.random.PRNGKey(42))
    want = ref.fused_bag_interactions_ref(tables, idx, bot)
    # pad samples (4 -> none at bb=4, force pad with bb=3) index slot 0 of
    # both tiers; poisoning any row OUTSIDE the zero slots must not matter
    got = fused_cached_bag_interactions_pallas(fast, bulk, fi, bi, bot,
                                               block_b=3, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()


# --------------------------------------------------------- ops dispatch
def test_ops_fused_dispatch_bitidentical_to_ref_on_cpu():
    """Off-TPU the ops wrappers run the composed reference graph, so they
    must be BIT-identical to ref — the property the serve-session
    equivalence tests below lean on."""
    from repro.kernels import ops

    B, T, L, R, d = 4, 3, 5, 16, 8
    tables, idx, bot = _inputs(jax.random.PRNGKey(51), B, T, L, R, d)
    got = ops.fused_bag_interactions(tables, idx, bot)
    want = ref.fused_bag_interactions_ref(tables, idx, bot)
    assert np.array_equal(np.asarray(got), np.asarray(want))

    from repro.parallel.plan import PlanGroups
    groups = PlanGroups((2, 0), (1,))
    perm = np.asarray(groups.fast_ids + groups.bulk_ids, np.int32)
    tf = tables[jnp.asarray(groups.fast_ids, jnp.int32)]
    tb = tables[jnp.asarray(groups.bulk_ids, jnp.int32)]
    got = ops.fused_grouped_bag_interactions(
        tf, tb, idx[:, perm, :], bot, inv_perm=groups.inv_perm)
    want = ref.fused_grouped_bag_interactions_ref(
        tf, tb, idx[:, perm, :], bot, groups.inv_perm)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- serve-path wiring
def _cfg():
    return dataclasses.replace(
        get_dlrm("dlrm-rm2-small-unsharded").reduced(), batch_size=8)


def _query(cfg, step, alpha=1.05):
    from repro.data import make_recsys_batch
    b = make_recsys_batch(cfg, step, 0, alpha)
    return b["dense"], b["indices"]


@pytest.mark.parametrize("plan", ["none", "auto"])
def test_serve_session_fused_matches_composed(plan):
    from repro.engine import Engine

    cfg = _cfg()
    s_fused = Engine(cfg, plan=plan, alpha=1.05).serve_session(
        max_batch_queries=4, max_wait_ms=1e6)
    s_comp = Engine(cfg, plan=plan, alpha=1.05,
                    fused_serve="off").serve_session(
        max_batch_queries=4, max_wait_ms=1e6)
    assert s_fused.serve_kernel == "fused"
    assert s_comp.serve_kernel == "composed"
    for step in range(2):
        dense, idx = _query(cfg, step)
        a = s_fused.serve_direct(dense, idx)
        b = s_comp.serve_direct(dense, idx)
        # identical jnp graphs on CPU -> bitwise equal, not just allclose
        assert np.array_equal(a, b)
        assert np.isfinite(a).all() and a.shape == (cfg.batch_size,)


def test_serve_kernel_recorded_on_plan_report():
    from repro.engine import Engine

    eng = Engine(_cfg(), plan="auto", alpha=1.05)
    sess = eng.serve_session(max_batch_queries=4, max_wait_ms=1e6)
    rep = eng.plan_report("inference")
    assert rep is not None
    assert rep.serve_kernel == sess.serve_kernel == "fused"
    assert "serve_kernel=fused" in rep.summary()

    eng_off = Engine(_cfg(), plan="auto", alpha=1.05, fused_serve="off")
    sess_off = eng_off.serve_session(max_batch_queries=4, max_wait_ms=1e6)
    assert sess_off.serve_kernel == "composed"
    assert eng_off.plan_report("inference").serve_kernel == "composed"


def test_row_wise_exchange_falls_back_to_composed():
    """Distributed-style exchanges have no local fused path: the session
    must transparently serve composed — and still match bitwise."""
    from repro import parallel
    from repro.engine import Engine

    cfg = dataclasses.replace(_cfg(), sharding="row_wise")
    ex = parallel.make_exchange(cfg, "model", 1)
    assert not ex.supports_fused_forward()
    with pytest.raises(NotImplementedError):
        ex.fused_forward({}, None, None)

    sess = Engine(cfg, plan="none").serve_session(
        max_batch_queries=4, max_wait_ms=1e6)
    assert sess.serve_kernel == "composed"        # fused requested, denied
    sess_off = Engine(cfg, plan="none", fused_serve="off").serve_session(
        max_batch_queries=4, max_wait_ms=1e6)
    dense, idx = _query(cfg, 0)
    assert np.array_equal(sess.serve_direct(dense, idx),
                          sess_off.serve_direct(dense, idx))


def test_engine_rejects_bad_fused_serve():
    from repro.engine import Engine

    with pytest.raises(ValueError, match="fused_serve"):
        Engine(_cfg(), plan="none", fused_serve="on")


# ----------------------------------------------- kernel_times calibration
def test_kernel_times_from_accepts_both_entry_forms():
    from repro.core.calibration import kernel_times_from

    kt = kernel_times_from({"kernel_times": {
        "fused_bag_interactions": {"us": 412.0, "shape": "B200 T40"},
        "embedding_bag": 389.5,
        "interactions": 55}})
    assert kt == {"fused_bag_interactions": 412.0,
                  "embedding_bag": 389.5, "interactions": 55.0}
    assert all(isinstance(v, float) for v in kt.values())


@pytest.mark.parametrize("bad", [
    {},                                             # no kernel_times at all
    {"kernel_times": {}},                           # empty section
    {"kernel_times": []},                           # wrong container
    {"kernel_times": {"k": "fast"}},                # non-numeric
    {"kernel_times": {"k": True}},                  # bool is not a time
    {"kernel_times": {"k": -3.0}},                  # negative
    {"kernel_times": {"k": float("nan")}},          # non-finite
    {"kernel_times": {"k": {"us": 1.0, "shape": 3}}},   # non-string label
    {"kernel_times": {"k": {"shape": "B1"}}},       # dict without us
])
def test_kernel_times_from_rejects_malformed(bad):
    from repro.core.calibration import kernel_times_from

    with pytest.raises(ValueError):
        kernel_times_from(bad)


def test_inference_breakdown_consumes_measured_kernel_times():
    from repro.core import perf_model

    cfg = get_dlrm("dlrm-rm2-small-unsharded")
    sys_ = perf_model.recspeed_hybrid_system()
    plain = perf_model.inference_breakdown(cfg, sys_)
    cal = {"kernel_times": {
        "fused_bag_interactions": {"us": 412.0, "shape": "B200"},
        "embedding_bag": 900.0, "interactions": 55.0}}
    bd = perf_model.inference_breakdown(cfg, sys_, calibration=cal)
    # the fused entry wins the lookup override (priority over embedding_bag)
    assert bd.t_lookup == pytest.approx(412e-6)
    assert bd.notes["t_lookup_modeled_s"] == pytest.approx(plain.t_lookup)
    assert bd.notes["t_lookup_delta_s"] == pytest.approx(
        412e-6 - plain.t_lookup)
    assert bd.notes["kernel_us_fused_bag_interactions"] == 412.0
    # interactions is delta-reported, never an override (t_dense_fwd also
    # carries the MLP flops)
    assert bd.t_dense_fwd == pytest.approx(plain.t_dense_fwd)
    assert bd.notes["interactions_delta_vs_dense_fwd_s"] == pytest.approx(
        55e-6 - plain.t_dense_fwd)
    # t_fwd recomputed from the measured term
    assert bd.t_fwd == pytest.approx(
        bd.t_idx_a2a + max(bd.t_lookup, bd.t_emb_exchange, bd.t_dense_fwd))

    # without the fused entry the next bag-family kernel takes the override
    bd2 = perf_model.inference_breakdown(
        cfg, sys_, calibration={"kernel_times": {"embedding_bag": 900.0}})
    assert bd2.t_lookup == pytest.approx(900e-6)
    # a kernel_times section with no bag-family entry changes nothing
    bd3 = perf_model.inference_breakdown(
        cfg, sys_, calibration={"kernel_times": {"interactions": 55.0}})
    assert bd3.t_lookup == pytest.approx(plain.t_lookup)
    assert "t_lookup_modeled_s" not in bd3.notes
