"""serve_mfu: model FLOPs of the samples answered per second, over the
chips' bf16 peak, in percent. FLOPs per sample: `flops.
dense_flops_per_sample` (bottom MLP, interaction, top MLP, forward)."""
import flops


def read(run):
    r = run.records
    if r.get("loop") != "closed" or r["window_s"] <= 0:
        return None
    rate = r["samples"] / r["window_s"]
    return (100.0 * flops.dense_flops_per_sample(run.cfg) * rate
            / (run.chips * run.peak["flops_per_s"]))
