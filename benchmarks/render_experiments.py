"""Render the roofline tables (markdown) from dry-run reports
(baseline + optimized side by side)."""
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(dirname):
    recs = {}
    for p in glob.glob(os.path.join(ROOT, "reports", dirname, "*.json")):
        with open(p) as f:
            r = json.load(f)
        recs[r["cell"]] = r
    return recs


def fmt_ms(x):
    return f"{x*1e3:,.0f}"


def table(base, opt, mesh="single"):
    print(f"| cell | t_comp (ms) | t_mem (ms) | t_coll (ms) | bound | "
          f"GiB/dev | opt t_mem | opt t_coll | opt bound | opt GiB |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for cell in sorted(base):
        if not cell.endswith(mesh):
            continue
        b = base[cell]
        o = opt.get(cell)
        if b.get("status") != "ok":
            print(f"| {cell} | FAIL | | | | | | | | |")
            continue
        rb = b["roofline"]
        mb = b["memory"]["peak_per_device_bytes"] / 2**30
        row = (f"| {cell.replace(':' + mesh, '')} | {fmt_ms(rb['t_compute_s'])} "
               f"| {fmt_ms(rb['t_memory_s'])} | {fmt_ms(rb['t_collective_s'])} "
               f"| {rb['bottleneck'][:4]} | {mb:.1f} ")
        if o and o.get("status") == "ok":
            ro = o["roofline"]
            mo = o["memory"]["peak_per_device_bytes"] / 2**30
            row += (f"| {fmt_ms(ro['t_memory_s'])} | {fmt_ms(ro['t_collective_s'])} "
                    f"| {ro['bottleneck'][:4]} | {mo:.1f} |")
        else:
            row += "| — | — | — | — |"
        print(row)


if __name__ == "__main__":
    base = load("dryrun")
    opt = load("dryrun_optimized")
    mesh = sys.argv[1] if len(sys.argv) > 1 else "single"
    table(base, opt, mesh)
