"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.cached_embedding_bag import cached_embedding_bag_pallas
from repro.kernels.embedding_bag import embedding_bag_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.interactions import interactions_pallas


# ---------------------------------------------------------------- embedding
@pytest.mark.parametrize("B,T,L,R,d", [
    (4, 8, 16, 64, 32),
    (2, 3, 5, 32, 128),           # d = 128: one row per line (p = 1)
    (1, 1, 1, 8, 8),
    (8, 40, 8, 128, 64),          # RM2-shaped (reduced L)
    (3, 4, 13, 64, 32),           # p = 4, L not a multiple of 8
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_matches_ref(B, T, L, R, d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(B * 100 + T))
    tables = jax.random.normal(k1, (T, R, d), dtype)
    idx = jax.random.randint(k2, (B, T, L), 0, R)
    out = embedding_bag_pallas(tables, idx)
    expect = ref.embedding_bag_ref(tables, idx)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, expect, rtol=tol, atol=tol)


@pytest.mark.parametrize("R,d,bag", [
    (3, 4, [1, 1, 1]),                 # row 1 three times, a row per line
    (8, 32, [5] * 11),                 # p = 4: one row, every lookup
    (8, 32, [4, 5, 6, 7]),             # all four slots of one line
    (8, 32, [6, 4, 6, 7, 5, 6, 0, 4, 7]),  # one line's slots, repeated
    (2, 128, [1, 0, 1, 1, 1]),         # p = 1
])
def test_embedding_bag_repeated_indices(R, d, bag):
    """Pooling must count duplicates (sum, not set semantics), also where
    rows share a line."""
    tables = jnp.arange(R * d, dtype=jnp.float32).reshape(1, R, d)
    idx = jnp.array([[bag]])
    out = embedding_bag_pallas(tables, idx)
    np.testing.assert_allclose(out[0, 0], sum(tables[0, r] for r in bag))


# ------------------------------------------------------- cached (tiered) bag
@pytest.mark.parametrize("B,T,L,R,S,d", [
    (4, 3, 8, 64, 16, 32),
    (2, 1, 5, 32, 4, 16),
    (3, 2, 13, 63, 15, 32),       # both tiers 4 rows a line, L % 8 != 0
    (3, 2, 13, 64, 15, 32),       # fast tier 4 rows a line, bulk 1
])
def test_cached_embedding_bag_matches_ref(B, T, L, R, S, d):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(B + S), 3)
    fast = jax.random.normal(k1, (T, S + 1, d)).at[:, S].set(0.0)
    bulk = jax.random.normal(k2, (T, R + 1, d)).at[:, R].set(0.0)
    hot = jax.random.bernoulli(k3, 0.6, (B, T, L))
    fast_idx = jnp.where(hot, jax.random.randint(k3, (B, T, L), 0, S), S)
    bulk_idx = jnp.where(hot, R, jax.random.randint(k3, (B, T, L), 0, R))
    out = cached_embedding_bag_pallas(fast, bulk, fast_idx.astype(jnp.int32),
                                      bulk_idx.astype(jnp.int32))
    expect = ref.cached_embedding_bag_ref(fast, bulk, fast_idx, bulk_idx)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("which", ["embedding_bag", "cached_embedding_bag"])
def test_native_bag_names_a_table_without_lane_dense_lines(which):
    """Natively a DMA'd line must span the 128 lanes. A d=32 table whose
    row count is not a multiple of 4 has no such layout: the kernel says so
    by name before anything is lowered."""
    tables = jnp.zeros((2, 10, 32))
    idx = jnp.zeros((1, 2, 3), jnp.int32)
    with pytest.raises(ValueError, match=f"{which}_pallas"):
        if which == "embedding_bag":
            embedding_bag_pallas(tables, idx, interpret=False)
        else:
            cached_embedding_bag_pallas(tables, tables, idx, idx,
                                        interpret=False)


# -------------------------------------------------------------- interactions
@pytest.mark.parametrize("B,T,d", [(8, 4, 32), (5, 40, 128), (3, 40, 32),
                                   (1, 2, 8)])
def test_interactions_matches_ref(B, T, d):
    k1, k2 = jax.random.split(jax.random.PRNGKey(B + T))
    bot = jax.random.normal(k1, (B, d), jnp.float32)
    pooled = jax.random.normal(k2, (B, T, d), jnp.float32)
    out = interactions_pallas(bot, pooled, block_b=4)
    expect = ref.interactions_ref(bot, pooled)
    assert out.shape == (B, d + (T + 1) * T // 2)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)


def test_interactions_excludes_diagonal_and_duplicates():
    """Paper Sec. III-D: strict lower triangle only — (s+1)s/2 entries."""
    B, T, d = 2, 3, 4
    bot = jnp.ones((B, d))
    pooled = jnp.ones((B, T, d))
    out = interactions_pallas(bot, pooled, block_b=2)
    # all-ones input: every pairwise dot = d
    np.testing.assert_allclose(out[:, d:], d * jnp.ones((B, T * (T + 1) // 2)))


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("B,T,S,Hq,Hkv,hd,causal,win", [
    (2, 16, 16, 4, 2, 16, True, None),
    (1, 24, 24, 4, 4, 8, True, 8),
    (2, 8, 8, 2, 1, 16, False, None),
    (1, 33, 33, 8, 2, 32, True, None),    # non-multiple of block
    (2, 16, 16, 4, 2, 16, True, 4),       # tight window
])
def test_flash_attention_matches_ref(B, T, S, Hq, Hkv, hd, causal, win):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(T + Hq), 3)
    q = jax.random.normal(k1, (B, T, Hq, hd), jnp.float32)
    k = jax.random.normal(k2, (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(k3, (B, S, Hkv, hd), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=causal, window=win,
                                 block_q=8, block_k=8)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_dtypes(dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (1, 16, 4, 16)).astype(dtype)
    k = jax.random.normal(k2, (1, 16, 2, 16)).astype(dtype)
    v = jax.random.normal(k3, (1, 16, 2, 16)).astype(dtype)
    out = flash_attention_pallas(q, k, v, block_q=8, block_k=8)
    expect = ref.flash_attention_ref(q, k, v)
    assert out.dtype == dtype
    np.testing.assert_allclose(out.astype(jnp.float32),
                               expect.astype(jnp.float32), rtol=3e-2, atol=3e-2)


# --------------------------------------------------------------- flash decode
@pytest.mark.parametrize("B,S,Hq,Hkv,hd", [
    (2, 32, 4, 2, 16),
    (3, 64, 8, 8, 8),
    (1, 48, 8, 2, 32),
    (2, 100, 4, 1, 16),            # ragged S vs block
])
def test_flash_decode_matches_ref(B, S, Hq, Hkv, hd):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(S), 4)
    q = jax.random.normal(k1, (B, Hq, hd), jnp.float32)
    kc = jax.random.normal(k2, (B, S, Hkv, hd), jnp.float32)
    vc = jax.random.normal(k3, (B, S, Hkv, hd), jnp.float32)
    lens = jax.random.randint(k4, (B,), 1, S + 1)
    out = flash_decode_pallas(q, kc, vc, lens, block_k=16)
    expect = ref.flash_decode_ref(q, kc, vc, lens)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


def test_flash_decode_respects_lengths():
    """Entries beyond `lengths` must not influence the result."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    B, S, Hq, Hkv, hd = 1, 32, 2, 2, 8
    q = jax.random.normal(k1, (B, Hq, hd))
    kc = jax.random.normal(k2, (B, S, Hkv, hd))
    vc = jax.random.normal(k3, (B, S, Hkv, hd))
    lens = jnp.array([10])
    out1 = flash_decode_pallas(q, kc, vc, lens, block_k=8)
    # poison the tail
    kc2 = kc.at[:, 10:].set(1e9)
    vc2 = vc.at[:, 10:].set(-1e9)
    out2 = flash_decode_pallas(q, kc2, vc2, lens, block_k=8)
    np.testing.assert_allclose(out1, out2, rtol=1e-6)


# ------------------------------------------------------------- ops dispatch
def test_ops_wrappers_run():
    from repro.kernels import ops
    k = jax.random.PRNGKey(0)
    tables = jax.random.normal(k, (2, 16, 8))
    idx = jnp.zeros((2, 2, 3), jnp.int32)
    assert ops.embedding_bag(tables, idx).shape == (2, 2, 8)
    bot = jnp.ones((4, 8))
    pooled = jnp.ones((4, 3, 8))
    assert ops.interactions(bot, pooled).shape == (4, 8 + 6)
