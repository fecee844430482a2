"""Operation and byte counts of DLRM's work, from its shapes.

`dense_flops_per_sample` is a copy of the program's arithmetic
(`repro.configs.base.DLRMConfig.flops_per_sample`): 2 x MACs of the bottom
MLP, the interaction matmul over the T+1 vectors, and the top MLP. It counts
no embedding work, which is adds, not multiplies. About 1.32 MFLOP for
RM2-small and 1.76 MFLOP for RM2-large.

`sparse_bytes` is the embedding layer's logical HBM traffic: the rows a chip
owns of every lookup, at the stored item size, plus the index bytes, plus
the pooled output. It counts what the lookups need, not what a kernel
happens to fetch (today's kernels fetch one 128-lane line per row).
"""
from __future__ import annotations


def top_mlp_in(cfg: dict) -> int:
    s = cfg["num_tables"] + 1
    return cfg["embed_dim"] + s * (s - 1) // 2


def bot_dims(cfg: dict):
    dims = list(cfg["bot_mlp"])
    if dims[-1] != cfg["embed_dim"]:
        dims.append(cfg["embed_dim"])
    return dims


def dense_flops_per_sample(cfg: dict) -> int:
    f, prev = 0, cfg["num_dense"]
    for w in bot_dims(cfg):
        f += 2 * prev * w
        prev = w
    s = cfg["num_tables"] + 1
    f += 2 * s * s * cfg["embed_dim"]
    prev = top_mlp_in(cfg)
    for w in cfg["top_mlp"]:
        f += 2 * prev * w
        prev = w
    return f


def sparse_bytes(cfg: dict, samples: int, owned_lookups: int,
                 row_itemsize: int, index_itemsize: int = 4,
                 pooled_itemsize: int = 4) -> int:
    """Logical bytes of one chip's embedding work over ``samples`` samples,
    of which ``owned_lookups`` lookups hit rows that chip holds."""
    T, L, d = cfg["num_tables"], cfg["lookups_per_table"], cfg["embed_dim"]
    return (owned_lookups * d * row_itemsize
            + samples * T * L * index_itemsize
            + samples * T * d * pooled_itemsize)
