"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Select with --only; default runs
everything at reduced scale (a few minutes on one core). The roofline
section reads benchmarks/results/dryrun.json produced by
``python -m repro.launch.dryrun --all``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# allow `python benchmarks/run.py` as well as `python -m benchmarks.run`
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

BENCHES = ["fig1", "fig2", "fig10", "fig12", "fig13", "fig14", "table2",
           "sampling", "kernels", "recovery", "serving", "availability",
           "roofline"]


def bench_roofline():
    path = os.path.join(os.path.dirname(__file__), "results", "dryrun.json")
    if not os.path.exists(path):
        print("roofline/SKIP,0.0,run `python -m repro.launch.dryrun --all`")
        return
    from repro.launch.roofline import analyze
    with open(path) as f:
        data = json.load(f)
    for key, e in sorted(data.items()):
        if not e.get("ok"):
            print(f"roofline/{key},0.0,FAILED:{e.get('error','')[:60]}")
            continue
        chips = 512 if e["mesh"].startswith("2x") else 256
        r = analyze(e, chips)
        step = max(r["t_compute"], r["t_memory"], r["t_collective"])
        print(f"roofline/{key},{step * 1e6:.1f},"
              f"dominant={r['dominant']};mfu={r['roofline_mfu']:.2f};"
              f"useful={r['useful_flops_ratio']:.2f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None, choices=BENCHES)
    args = ap.parse_args()
    todo = args.only or BENCHES
    print("name,us_per_call,derived")
    failed = []
    for name in todo:
        t0 = time.time()
        try:
            if name == "roofline":
                bench_roofline()
            else:
                mod = {
                    "fig1": "fig1_hidden_size",
                    "fig2": "fig2_minibatch_vs_fullgraph",
                    "fig10": "fig10_speedup",
                    "fig12": "fig12_scalability",
                    "fig13": "fig13_convergence",
                    "fig14": "fig14_ablation",
                    "table2": "table2_breakdown",
                    "sampling": "sampling_micro",
                    "kernels": "kernels_micro",
                    "recovery": "recovery_bench",
                    "serving": "serving_bench",
                    "availability": "availability_bench",
                }[name]
                __import__(f"benchmarks.{mod}", fromlist=["run"]).run()
        except Exception:
            print(f"{name}/ERROR,0.0,{traceback.format_exc(limit=1)!r}")
            failed.append(name)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
