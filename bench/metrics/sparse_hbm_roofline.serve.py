"""sparse_hbm_roofline.serve: the embedding lookups' logical bytes at the
chip's HBM bandwidth, over the device time of the sparse kernels, in
percent; the mean over chips that ran such a kernel.

Bytes per chip (`flops.sparse_bytes`): the answered lookups whose rows the
chip holds, times the row width at the table's stored item size, plus the
index and pooled-output bytes. Kernel time: the trace's operations named
after today's Pallas sparse kernels, the fused gather->pool->interaction
kernel and the embedding-bag kernel. The fused kernel's time also covers
the interaction contraction, whose bytes are negligible beside the rows'.
A run with no such operation (XLA's gather on the row-sharded path) reads
nothing.
"""
import flops

KERNELS = r"fused_bag|embedding_bag"


def read(run):
    tr, r = run.trace, run.records
    if tr is None or "owned_lookups_per_chip" not in r:
        return None
    shares = []
    for i, chip in enumerate(tr.chips()):
        t = tr.kernel_s(chip, KERNELS)
        if not t:
            continue
        owned = r["owned_lookups_per_chip"][i]
        b = flops.sparse_bytes(run.cfg, r["samples"], owned,
                               r["table_itemsize"])
        shares.append(b / run.peak["hbm_bytes_per_s"] / t)
    return 100.0 * sum(shares) / len(shares) if shares else None
