"""Hypothesis property tests on system invariants.

`hypothesis` is an OPTIONAL dev dependency (see README): the whole module
skips cleanly when it is absent so tier-1 collection (`pytest -x`) never
dies on the import. CI installs it so these tests actually run there.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import example, given, settings, strategies as st  # noqa: E402

from repro.configs.registry import get_dlrm
from repro.core.collectives import (CollectiveOp, Interconnect, Topology,
                                    collective_time)
from repro.core.perf_model import breakdown, sweep_system
from repro.core.planner import plan_dlrm
from repro.data.recsys import _zipf_indices
from repro.optim.compression import int8_compress, int8_decompress

SETTINGS = dict(max_examples=30, deadline=None)


# ------------------------------------------------------- roofline monotonicity
@settings(**SETTINGS)
@given(lat1=st.floats(0.5, 10.0), lat2=st.floats(0.5, 10.0),
       bw=st.sampled_from([100.0, 400.0, 1000.0]),
       config=st.sampled_from(["dlrm-rm2-small-unsharded",
                               "dlrm-rm2-small-sharded",
                               "dlrm-rm2-large-sharded"]),
       mode=st.sampled_from(["inference", "training"]))
def test_qps_monotone_in_latency(lat1, lat2, bw, config, mode):
    cfg = get_dlrm(config)
    lo, hi = sorted([lat1, lat2])
    q_lo = breakdown(cfg, sweep_system(lo * 1e-6, bw * 1e9), mode).qps
    q_hi = breakdown(cfg, sweep_system(hi * 1e-6, bw * 1e9), mode).qps
    assert q_lo >= q_hi * (1 - 1e-9)


@settings(**SETTINGS)
@given(bw1=st.floats(100.0, 1000.0), bw2=st.floats(100.0, 1000.0),
       lat=st.sampled_from([0.5, 2.0, 10.0]),
       config=st.sampled_from(["dlrm-rm2-small-sharded",
                               "dlrm-rm2-large-sharded"]),
       mode=st.sampled_from(["inference", "training"]))
def test_qps_monotone_in_bandwidth(bw1, bw2, lat, config, mode):
    cfg = get_dlrm(config)
    lo, hi = sorted([bw1, bw2])
    q_lo = breakdown(cfg, sweep_system(lat * 1e-6, lo * 1e9), mode).qps
    q_hi = breakdown(cfg, sweep_system(lat * 1e-6, hi * 1e9), mode).qps
    assert q_hi >= q_lo * (1 - 1e-9)


# -------------------------------------------------- collective algebra
@settings(**SETTINGS)
@given(v=st.floats(1e3, 1e9), n=st.integers(2, 512),
       bw=st.floats(1e9, 1e12), lat=st.floats(1e-7, 1e-4))
def test_allreduce_equals_rs_plus_ag(v, n, bw, lat):
    link = Interconnect(bw, lat, Topology.QUADRATIC)
    ar = collective_time(CollectiveOp.ALL_REDUCE, v, n, link)
    rs = collective_time(CollectiveOp.REDUCE_SCATTER, v, n, link)
    ag = collective_time(CollectiveOp.ALL_GATHER, v, n, link)
    np.testing.assert_allclose(ar.wire_bytes, rs.wire_bytes + ag.wire_bytes,
                               rtol=1e-9)


@settings(**SETTINGS)
@given(v=st.floats(1.0, 1e9), n=st.integers(2, 1024))
def test_wire_bytes_below_payload_times_two(v, n):
    link = Interconnect(1e11, 1e-6, Topology.QUADRATIC)
    for op in (CollectiveOp.ALL_TO_ALL, CollectiveOp.REDUCE_SCATTER,
               CollectiveOp.ALL_GATHER):
        c = collective_time(op, v, n, link)
        assert 0 <= c.wire_bytes < v
    ar = collective_time(CollectiveOp.ALL_REDUCE, v, n, link)
    assert ar.wire_bytes < 2 * v


# ---------------------------------------------------------- planner coherence
@settings(**SETTINGS)
@given(lat=st.floats(0.5, 10.0), bw=st.floats(100.0, 1000.0),
       config=st.sampled_from(list(["dlrm-rm2-small-unsharded",
                                    "dlrm-rm2-large-unsharded"])))
def test_planner_picks_argmax(lat, bw, config):
    cfg = get_dlrm(config)
    sys_ = sweep_system(lat * 1e-6, bw * 1e9)
    plan = plan_dlrm(cfg, sys_)
    assert plan.predicted_qps >= max(plan.qps_table_wise,
                                     plan.qps_row_wise_unpooled,
                                     plan.qps_row_wise_partial) * (1 - 1e-9)


# ------------------------------------------------------------ int8 compression
@settings(**SETTINGS)
@given(seed=st.integers(0, 1000), scale=st.floats(1e-3, 1e3),
       n=st.integers(1, 2048))
def test_int8_roundtrip_error_bound(seed, scale, n):
    """Quantization error <= absmax/254 per block element."""
    x = np.random.RandomState(seed).randn(n).astype(np.float32) * scale
    q, s = int8_compress(jnp.asarray(x))
    out = np.asarray(int8_decompress(q, s, (n,)))
    bound = np.abs(x).max() / 127.0 * 0.5 + 1e-7
    # per-block bound is tighter; global bound suffices as a safety net
    assert np.abs(out - x).max() <= np.abs(x).max() / 127.0 + 1e-6


@settings(**SETTINGS)
@given(seed=st.integers(0, 100))
def test_int8_error_feedback_converges(seed):
    """With error feedback, the RUNNING SUM of compressed values converges to
    the running sum of true values (unbiasedness over steps)."""
    rng = np.random.RandomState(seed)
    true_sum = np.zeros(64, np.float32)
    sent_sum = np.zeros(64, np.float32)
    err = jnp.zeros(64)
    for _ in range(20):
        g = rng.randn(64).astype(np.float32)
        true_sum += g
        gc = jnp.asarray(g) + err
        q, s = int8_compress(gc)
        deq = int8_decompress(q, s, (64,))
        err = gc - deq
        sent_sum += np.asarray(deq)
    # residual bounded by one quantization step, NOT accumulating over steps
    assert np.abs(true_sum - sent_sum).max() <= np.abs(true_sum).max() / 10 + 0.5


# ------------------------------------------------------------ data pipeline
@settings(**SETTINGS)
@given(seed=st.integers(0, 2**31 - 1), alpha=st.floats(0.0, 1.5),
       n_rows=st.sampled_from([128, 4096, 2**20]))
def test_zipf_indices_in_range(seed, alpha, n_rows):
    idx = _zipf_indices(jax.random.PRNGKey(seed), (64,), n_rows, alpha)
    a = np.asarray(idx)
    assert (a >= 0).all() and (a < n_rows).all()


def test_zipf_skew_increases_with_alpha():
    k = jax.random.PRNGKey(0)
    flat = lambda a: np.asarray(_zipf_indices(k, (20000,), 1024, a))
    uni, skew = flat(0.0), flat(1.2)
    top_uni = np.bincount(uni, minlength=1024).max()
    top_skew = np.bincount(skew, minlength=1024).max()
    assert top_skew > 3 * top_uni


# ----------------------------------------------------- row-range partitioning
def _partition_cfg(num_tables=16):
    return dataclasses.replace(
        get_dlrm("dlrm-rm2-small-unsharded").reduced(),
        num_tables=num_tables, batch_size=8)


@settings(**SETTINGS)
@given(n_boards=st.sampled_from([2, 3, 4]),
       headroom=st.floats(1.25, 2.0),
       scale=st.floats(0.5, 4.0))
def test_partition_balanced_and_deterministic_under_zipf(
        n_boards, headroom, scale):
    """The fleet partitioner is a pure function of (freq, capacities) and
    keeps the lookup-load balance within 1.5x fair share under Zipf 1.05
    table popularity — for every board count, capacity headroom, and
    frequency normalization."""
    from repro.fabric import partition_rows

    cfg = _partition_cfg()
    # Zipf 1.05 over 16 tables: the head holds ~24% of the mass, so even
    # k=4 has a feasible 1.5x-fair-share packing (8 tables would not)
    freq = scale * np.arange(1, cfg.num_tables + 1, dtype=np.float64) ** -1.05
    cap = int(np.ceil(headroom * cfg.embedding_bytes / n_boards))
    pm = partition_rows(cfg, freq, n_boards, cap)
    assert pm.load_balance() <= 1.5
    assert max(pm.board_bytes) <= cap
    assert sum(pm.board_bytes) == cfg.embedding_bytes
    # determinism: same inputs -> the SAME map (scale cancels in density
    # ordering, so the shard layout ignores normalization too)
    assert partition_rows(cfg, freq, n_boards, cap) == pm
    assert partition_rows(cfg, freq / scale, n_boards, cap).shards \
        == pm.shards


@settings(**SETTINGS)
@given(n_boards=st.sampled_from([2, 3, 4]),
       rows=st.sampled_from([384, 768, 1000]),
       alpha=st.floats(0.0, 1.2))
def test_row_range_split_covers_rows_exactly(n_boards, rows, alpha):
    """A table too big for any board splits into contiguous ranges that
    cover [0, R) exactly once, deterministically, within capacity."""
    from repro.fabric import partition_rows

    cfg = _partition_cfg(num_tables=1)
    cfg = dataclasses.replace(cfg, rows_per_table=rows)
    row_b = cfg.embed_dim * 2
    cap = int(np.ceil(0.75 * rows)) * row_b      # forces a split
    freq = (np.arange(1, rows + 1, dtype=np.float64) ** -alpha)[None, :]
    pm = partition_rows(cfg, freq, n_boards, cap)
    assert pm.split_tables == (0,)
    ts = sorted(pm.table_shards(0), key=lambda s: s.row_lo)
    assert ts[0].row_lo == 0 and ts[-1].row_hi == rows
    assert all(a.row_hi == b.row_lo for a, b in zip(ts, ts[1:]))
    assert max(pm.board_bytes) <= cap
    assert partition_rows(cfg, freq, n_boards, cap) == pm


# ------------------------------------------------------- host chunk tier
@settings(**SETTINGS)
@given(seed=st.integers(0, 1000), t=st.integers(1, 3),
       r=st.integers(8, 40), chunk_rows=st.integers(1, 5),
       cache_slots=st.integers(2, 6), n_req=st.integers(1, 12))
def test_hoststore_ensure_leaves_requested_rows_resident(
        seed, t, r, chunk_rows, cache_slots, n_req):
    """After `ensure`, every requested row is resident and the accounting
    balances (needed == hits + faults); a request whose chunk working set
    exceeds the cache refuses instead of thrashing."""
    from repro.hoststore import ChunkParamMgr

    rng = np.random.RandomState(seed)
    tables = rng.randn(t, r, 2).astype(np.float32)
    mgr = ChunkParamMgr(tables, chunk_rows, cache_slots)
    t_idx = rng.randint(0, t, n_req)
    r_idx = rng.randint(0, r, n_req)
    needed = np.unique(mgr.chunk_of(t_idx, r_idx))
    if needed.size > cache_slots:
        with pytest.raises(ValueError):
            mgr.ensure(t_idx, r_idx)
        return
    stats = mgr.ensure(t_idx, r_idx)
    assert np.asarray(mgr.is_resident(t_idx, r_idx)).all()
    assert stats.needed_chunks == needed.size
    assert stats.hit_chunks + stats.faulted_chunks == stats.needed_chunks
    # the cache holds the host values at the mapped positions, bitwise
    cache = np.asarray(mgr.device_cache)
    pos = mgr.host_pos
    assert np.array_equal(cache[pos[t_idx, r_idx]], tables[t_idx, r_idx])


@settings(**SETTINGS)
@given(seed=st.integers(0, 1000), chunk_rows=st.integers(1, 4),
       cache_slots=st.integers(2, 5),
       policy=st.sampled_from(["clock", "lfu"]))
def test_hoststore_eviction_never_drops_dirty_chunk(
        seed, chunk_rows, cache_slots, policy):
    """A shadow copy updated in lockstep with the device cache: whatever
    churn the eviction policy produces, `flush()` returns EXACTLY the
    shadow — no dirty chunk was ever dropped or written back stale."""
    from repro.hoststore import ChunkParamMgr

    rng = np.random.RandomState(seed)
    tables = rng.randn(2, 11, 3).astype(np.float32)
    shadow = tables.copy()
    mgr = ChunkParamMgr(tables, chunk_rows, cache_slots, policy=policy)
    for _ in range(15):
        t_i, r_i = rng.randint(0, 2), rng.randint(0, 11)
        mgr.ensure(np.array([t_i]), np.array([r_i]))
        delta = np.float32(rng.randint(1, 5))
        mgr.device_cache = mgr.device_cache.at[
            mgr.host_pos[t_i, r_i]].add(delta)
        mgr.mark_dirty(np.array([t_i]), np.array([r_i]))
        shadow[t_i, r_i] += delta
        # invariant: dirty chunks are always resident
        assert set(mgr.dirty_chunks.tolist()) <= \
            set(mgr.resident_chunks.tolist())
    assert np.array_equal(mgr.flush(), shadow)
    assert mgr.dirty_chunks.size == 0


@settings(**SETTINGS)
@given(t=st.integers(1, 3), r=st.integers(1, 40),
       chunk_rows=st.integers(1, 7))
def test_hoststore_chunks_cover_rows_exactly_once(t, r, chunk_rows):
    """Chunk geometry partitions the (table, row) space: every row falls
    in exactly one chunk's range, ragged tails included, and `chunk_of`
    agrees with `chunk_range`."""
    from repro.hoststore import ChunkParamMgr

    mgr = ChunkParamMgr(np.zeros((t, r, 2), np.float32), chunk_rows, 2)
    seen = np.zeros((t, r), int)
    for c in range(mgr.n_chunks):
        ct, lo, hi = mgr.chunk_range(c)
        assert 0 < hi - lo <= chunk_rows
        seen[ct, lo:hi] += 1
        assert (mgr.chunk_of(np.full(hi - lo, ct), np.arange(lo, hi))
                == c).all()
    assert (seen == 1).all()


# ------------------------------------------------------ fused serve kernel
@settings(max_examples=10, deadline=None)   # interpret mode: Python per step
@given(seed=st.integers(0, 1000), B=st.integers(1, 6), T=st.integers(1, 3),
       L=st.integers(1, 4), bb=st.integers(2, 4))
@example(seed=0, B=1, T=1, L=1, bb=2)   # the smallest shape Hypothesis broke
def test_fused_pad_samples_never_leak(seed, B, T, L, bb):
    """The fused megakernel pads the batch to a block multiple with
    index-0 gathers: for ANY shape/blocking, a poisoned row 0 that only
    pad samples touch must never reach a real sample's features."""
    from repro.kernels import ref
    from repro.kernels.fused_serve import fused_bag_interactions_pallas

    R, d = 16, 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    tables = jax.random.normal(k1, (T, R, d)).at[:, 0, :].set(1e30)
    idx = jax.random.randint(k2, (B, T, L), 1, R)    # real rows avoid 0
    bot = jax.random.normal(k3, (B, d))
    got = fused_bag_interactions_pallas(tables, idx, bot, block_b=bb,
                                        interpret=True)
    want = ref.fused_bag_interactions_ref(tables, idx, bot)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ pooling algebra
@settings(**SETTINGS)
@given(seed=st.integers(0, 1000), splits=st.integers(1, 4))
def test_partial_pool_associativity(seed, splits):
    """sum-pool(rows) == Σ_p sum-pool(rows owned by p) — the identity that
    legitimizes the beyond-paper partial_pool exchange."""
    rng = np.random.RandomState(seed)
    rows = rng.randn(12, 8).astype(np.float32)
    full = rows.sum(0)
    parts = np.array_split(rows, splits, axis=0)
    partial = sum(p.sum(0) for p in parts)
    np.testing.assert_allclose(full, partial, rtol=1e-5, atol=1e-5)
