"""The benchmark's own generators: weights, query inputs and labels.

Everything a run feeds the program is made here from ``--seed``, so the
same seed gives the same run and the reference can make the same values
again without reading anything the program holds.

* Weights are a counter-based hash of (seed, leaf, row, column). Each value
  is an integer in [-2^23, 2^23) times a power of two, so it is exact in
  float32 on any device, and the reference recomputes any row it needs
  from the row's id (`table_rows`) without a copy of the table. Bounds are
  powers of two: near DLRM's uniform(-sqrt(1/n), sqrt(1/n)) init for the
  MLPs (n = fan-in), and near 1/sqrt(d) for the tables (`table_bound`).
* Row ids follow a Zipf law over ranks scattered by a multiplicative hash,
  and dense features are N(0, 1); both on the host (numpy), so no
  generation runs on the device inside a measured window. Copied from the
  program's device generators (`repro.data.recsys._zipf_indices`), as is
  the planted logistic teacher behind the labels
  (`repro.data.recsys.teacher_click_probs`).
* Arrival times for an open loop are the exponential quantiles at the
  mix's rate in a shuffled order: a Poisson-like schedule with exactly the
  mix's rate. The order comes from the mix's own ``arrival_seed``, not from
  ``--seed``, so every seed gets the same arrival times and ``--seed``
  draws the queries: on one chip the order of the gaps moves the tail by
  10-20% from seed to seed at 0.8 of the knee, and two runs of one order
  agree within a few percent (PERF.md).
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

M32 = 0xFFFFFFFF
_GOLDEN = 0x9E3779B9
_ZIPF_SCATTER = 2654435761      # odd: a bijection of row ids mod 2^k


# ----------------------------------------------------------------- hashing
def _mix_np(x: np.ndarray) -> np.ndarray:
    """lowbias32 integer hash, uint32 -> uint32 (numpy)."""
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x7FEB352D)
    x ^= x >> np.uint32(15)
    x *= np.uint32(0x846CA68B)
    x ^= x >> np.uint32(16)
    return x


def _mix(x):
    """lowbias32 on jnp uint32 arrays (wrapping multiply, as numpy's)."""
    import jax.numpy as jnp
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> jnp.uint32(16))


def leaf_key(seed: int, leaf: int) -> int:
    """One uint32 key per (seed, leaf); the seed may exceed 32 bits."""
    lo = np.array([seed & M32], np.uint32)
    hi = np.array([(seed >> 32) & M32], np.uint32)
    k = _mix_np(lo ^ np.uint32(0x2545F491))
    k = _mix_np(k ^ hi)
    k = _mix_np(k + np.uint32((leaf * _GOLDEN) & M32))
    return int(k[0])


def pow2_bound(fan: int) -> float:
    """Largest power of two at or below sqrt(1/fan)."""
    return 2.0 ** -math.ceil(math.log2(fan) / 2)


def hashed_uniform(key, row, col, bound: float):
    """Values in [-bound, bound) at (row, col): jnp uint32 arrays that
    broadcast; ``key`` is a uint32 scalar (a traced argument, so one
    compiled program serves every seed)."""
    import jax.numpy as jnp
    h_row = _mix(key ^ _mix(row + jnp.uint32(_GOLDEN)))
    h = _mix(h_row + col * jnp.uint32(0x85EBCA6B))
    q = (h >> jnp.uint32(8)).astype(jnp.int32) - jnp.int32(1 << 23)
    return q.astype(jnp.float32) * jnp.float32(bound * 2.0 ** -23)


# ----------------------------------------------------------------- weights
def mlp_layers(num_dense: int, bot: Sequence[int], top: Sequence[int],
               top_in: int) -> List[Tuple[str, int, int]]:
    """(group, fan_in, fan_out) of every dense layer, bottom then top."""
    out, prev = [], num_dense
    for w in bot:
        out.append(("bot_mlp", prev, w))
        prev = w
    prev = top_in
    for w in top:
        out.append(("top_mlp", prev, w))
        prev = w
    return out


def table_bound(d: int) -> float:
    """Tables are uniform in +-2^-ceil(log2(d)/2), about 1/sqrt(d): rows of
    the scale of trained embeddings, so that the pooled vectors and their
    interactions carry a large share of the logit. (DLRM's sqrt(1/rows)
    init would make every row about 1e-3 at 2^21 rows, and a wrong lookup
    would barely move an answer.)"""
    return pow2_bound(d)


def dense_leaves(keys, layers):
    """The MLP params from their keys: {"bot_mlp": [...], "top_mlp": [...]}
    (jnp; callable inside jit). Leaf 2i is layer i's weight, 2i+1 its
    bias; ``keys`` holds one uint32 per leaf, then the table's."""
    import jax.numpy as jnp
    out: Dict[str, list] = {"bot_mlp": [], "top_mlp": []}
    for i, (group, fan_in, fan_out) in enumerate(layers):
        b = pow2_bound(fan_in)
        r = jnp.arange(fan_in, dtype=jnp.uint32)[:, None]
        c = jnp.arange(fan_out, dtype=jnp.uint32)[None, :]
        w = hashed_uniform(keys[2 * i], r, c, b)
        bias = hashed_uniform(keys[2 * i + 1], jnp.uint32(0),
                              jnp.arange(fan_out, dtype=jnp.uint32), b)
        out[group].append({"w": w, "b": bias})
    return out


def table_lines(key, num_tables: int, rows: int, d: int, p: int,
                bound: float):
    """Stacked tables stored as lines of ``p`` rows, (T, R/p, p*d): line q
    of table t holds rows q*p .. q*p+p-1 side by side. ``p = 1`` is the
    plain (T, R, d) layout."""
    import jax.numpy as jnp
    t = jnp.arange(num_tables, dtype=jnp.uint32)[:, None, None]
    q = jnp.arange(rows // p, dtype=jnp.uint32)[None, :, None]
    k = jnp.arange(p * d, dtype=jnp.uint32)[None, None, :]
    row = t * jnp.uint32(rows) + q * jnp.uint32(p) + k // jnp.uint32(d)
    return hashed_uniform(key, row, k % jnp.uint32(d), bound)


def table_rows(key, rows: int, d: int, bound: float, indices):
    """Rows at ``indices`` (..., T, L) of every table: (..., T, L, d)."""
    import jax.numpy as jnp
    T = indices.shape[-2]
    t = jnp.arange(T, dtype=jnp.uint32)[:, None]
    row = t * jnp.uint32(rows) + indices.astype(jnp.uint32)
    col = jnp.arange(d, dtype=jnp.uint32)
    return hashed_uniform(key, row[..., None], col, bound)


def weight_keys(seed: int, n_leaves: int) -> np.ndarray:
    """uint32 keys: one per MLP leaf, the tables' last."""
    return np.array([leaf_key(seed, i) for i in range(n_leaves + 1)],
                    np.uint32)


# ------------------------------------------------------------------ inputs
def zipf_indices(rng: np.random.Generator, shape, n_rows: int,
                 alpha: float) -> np.ndarray:
    """Power-law row ids, P(rank r) ~ (r+1)^-alpha by inverse CDF, ranks
    scattered over the table by a multiplicative hash; alpha=0 is uniform.
    A copy of `repro.data.recsys._zipf_indices` on the host."""
    u = rng.random(shape, dtype=np.float64) * (1.0 - 1e-9) + 1e-9
    if alpha == 0.0:
        ranks = (u * n_rows).astype(np.int64)
    elif abs(1.0 - alpha) < 1e-6:
        ranks = np.exp(u * math.log(n_rows)).astype(np.int64) - 1
    else:
        a1 = 1.0 - alpha
        hi = float(n_rows) ** a1
        ranks = (np.power(u * (hi - 1.0) + 1.0, 1.0 / a1) - 1.0
                 ).astype(np.int64)
    ranks = np.clip(ranks, 0, n_rows - 1).astype(np.uint64)
    return ((ranks * np.uint64(_ZIPF_SCATTER)) % np.uint64(n_rows)
            ).astype(np.int32)


SPARSE_SIGNAL = 0.75


def teacher_probs(seed: int, dense: np.ndarray,
                  indices: np.ndarray) -> np.ndarray:
    """The planted logistic teacher's P(click): a copy of
    `repro.data.recsys.teacher_click_probs`, with its weight vector drawn
    on the host from the seed."""
    rng = np.random.default_rng([seed, 10_007])
    w = (rng.standard_normal(dense.shape[1]).astype(np.float32)
         / np.float32(math.sqrt(dense.shape[1])))
    sig = dense @ w + SPARSE_SIGNAL * np.mean(
        (indices[:, :, 0] % 7).astype(np.float32) - 3.0, axis=1)
    return 1.0 / (1.0 + np.exp(-2.0 * sig))


def make_batch(rng: np.random.Generator, batch: int, num_dense: int,
               num_tables: int, lookups: int, rows: int, alpha: float,
               label_seed: int = None) -> Dict[str, np.ndarray]:
    """One batch of inputs on the host; with ``label_seed`` also labels
    drawn from the planted teacher."""
    dense = rng.standard_normal((batch, num_dense), dtype=np.float32)
    idx = zipf_indices(rng, (batch, num_tables, lookups), rows, alpha)
    out = {"dense": dense, "indices": idx}
    if label_seed is not None:
        p = teacher_probs(label_seed, dense, idx)
        out["labels"] = (rng.random(batch) < p).astype(np.float32)
    return out


def arrival_offsets(n: int, qps: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of ``n`` open-loop queries:
    the n exponential quantiles at rate ``qps``, in an order drawn from
    ``seed``, summed."""
    if qps <= 0:
        raise ValueError(f"open-loop rate must be > 0, got {qps}")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / qps
    rng = np.random.default_rng([seed, 7])
    return np.cumsum(rng.permutation(gaps))


def named_leaves(tree) -> Dict[str, object]:
    """{"bot_mlp.0.w": leaf, ...} for a params pytree of dicts and lists."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = []
        for k in path:
            parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
        out[".".join(parts)] = leaf
    return out
