"""train_mfu: 3 x forward model FLOPs per sample (forward and backward,
no recompute) times samples per second, over the chips' bf16 peak, in
percent."""
import flops


def read(run):
    r = run.records
    if "steps" not in r or r["window_s"] <= 0:
        return None
    rate = r["samples"] / r["window_s"]
    return (100.0 * 3 * flops.dense_flops_per_sample(run.cfg) * rate
            / (run.chips * run.peak["flops_per_s"]))
