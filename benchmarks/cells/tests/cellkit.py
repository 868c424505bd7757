"""Helpers of the benchmark's CPU tests: a copy of the benchmark with
two tiny cells added by new files and entries only, one on an R-MAT
graph and one on a typed graph, and a launcher that runs ``run.py``
there on the CPU, steered past its look for a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

CELLS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CELLS))

TINY_CONFIG = {"arch": "graphsage", "hidden_dim": 16, "fanouts": [4, 3],
               "batch_size": 24, "num_machines": 2,
               "trainers_per_machine": 2, "lr": 0.003,
               "matmul_precision": "default"}
TINY_TRAFFIC = {"generator": "rmat", "scale": 10, "edge_factor": 8,
                "a": 0.57, "b": 0.19, "c": 0.19, "feat_dim": 12,
                "num_classes": 5, "train_frac": 0.3, "val_frac": 0.05,
                "data_seed": 3}
# 3 node types, 5 relations in message direction toward the papers
TINY_TYPED_TRAFFIC = {
    "generator": "typed", "scale": 10, "target": "paper",
    "node_types": [{"name": "paper", "published": 1000},
                   {"name": "author", "published": 1200},
                   {"name": "institution", "published": 50}],
    "relations": [
        {"src": "paper", "name": "cites", "dst": "paper",
         "edges_per_src": 4, "dst_skew": 0.5, "undirected": True},
        {"src": "author", "name": "writes", "dst": "paper",
         "edges_per_src": 2, "src_skew": 0.5},
        {"src": "paper", "name": "rev_writes", "dst": "author",
         "reverse_of": "writes"},
        {"src": "author", "name": "affiliated_with", "dst": "institution",
         "edges_per_src": 1, "dst_skew": 0.5},
        {"src": "institution", "name": "rev_affiliated_with",
         "dst": "author", "reverse_of": "affiliated_with"}],
    "label_relation": "cites",
    "features_by_mean": [["author", "rev_writes"],
                         ["institution", "affiliated_with"]],
    "feat_dim": 12, "num_classes": 5, "train_frac": 0.3, "val_frac": 0.05,
    "data_seed": 4}
# writes and rev_writes have equal budgets at both layers, so that a
# planted fault can swap their slot ranges
TINY_RGCN_CONFIG = {"arch": "rgcn", "hidden_dim": 16,
                    "fanouts": [{"cites": 3, "writes": 2, "rev_writes": 2,
                                 "rev_affiliated_with": 2},
                                {"cites": 3, "writes": 2, "rev_writes": 2}],
                    "batch_size": 24, "num_machines": 2,
                    "trainers_per_machine": 2, "lr": 0.003,
                    "matmul_precision": "default"}
NEW_METRIC = """def read(w):
    return float(w.steps)
"""

# Runs run.main() in a fresh interpreter.  The CPU stands in for the chip
# (the look for a TPU and the table of peaks are steered here, in the
# test), and FAULT plants one fault in the program underneath:
# "slots_swapped" has the sampler put two relations' edges in each
# other's slot ranges, where the ranges are of one size.
LAUNCHER = r'''
import json, os, sys
cells = os.path.join(sys.argv[1], "benchmarks", "cells")
sys.path.insert(0, cells)
sys.path.insert(0, os.path.join(sys.argv[1], "src"))
import jax, jax.numpy as jnp
import counts, run
run.require_accelerator = lambda chips: jax.devices()[:chips]
counts.peaks = lambda kind: {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
from repro.training import trainer as T
fault = os.environ.get("FAULT", "")
if fault == "state_unchanged":
    T.adamw_update = lambda params, grads, opt, lr: (params, opt)
elif fault == "half_batch":
    loss = T.nc_loss
    def half(logits, labels, mask):
        return loss(logits, labels, mask.at[mask.shape[0] // 2:].set(False))
    T.nc_loss = half
elif fault in ("one_trainer", "labels_shifted"):
    stack = T.DistGNNTrainer._stack
    def faulty(self, batches):
        if fault == "one_trainer":
            batches = batches[:1]
        else:
            b = dict(batches[0])
            b["labels"] = (b["labels"] + 1) % self.cfg.num_classes
            batches = [b] + list(batches[1:])
        return stack(self, batches)
    T.DistGNNTrainer._stack = faulty
elif fault == "slots_swapped":
    from repro.core.sampler import dispatch as D
    pad = D.pad_typed_block
    def swapped(*args, **kwargs):
        b = pad(*args, **kwargs)
        offs = b.rel_offsets
        sizes = list(offs[1:] - offs[:-1])
        r1, r2 = [(i, j) for i in range(len(sizes))
                  for j in range(i + 1, len(sizes))
                  if sizes[i] == sizes[j] > 0][0]
        a = slice(offs[r1], offs[r1 + 1])
        c = slice(offs[r2], offs[r2 + 1])
        for arr in (b.edge_src, b.edge_dst, b.edge_mask):
            arr[a], arr[c] = arr[c].copy(), arr[a].copy()
        return b
    D.pad_typed_block = swapped
sys.exit(run.main(sys.argv[2:]))
'''


def tiny_benchmark(root: str, limits: dict) -> str:
    """A copy of the benchmark in ``root`` with the cells
    ``tiny-sage-train`` and ``tiny-rgcn-train`` (both held to ``limits``)
    and the metric ``steps_in_window.train`` added: new files and new
    entries of ``BENCHMARK.json`` only."""
    cells = os.path.join(root, "benchmarks", "cells")
    shutil.copytree(CELLS, cells, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {"configs/tiny-sage.json": TINY_CONFIG,
             "traffic/tiny-nc.json": TINY_TRAFFIC,
             "limits/tiny-sage-train.json": limits,
             "configs/tiny-rgcn.json": TINY_RGCN_CONFIG,
             "traffic/tiny-typed-nc.json": TINY_TYPED_TRAFFIC,
             "limits/tiny-rgcn-train.json": limits}
    for path, body in files.items():
        assert not os.path.exists(os.path.join(cells, path)), path
        with open(os.path.join(cells, path), "w") as f:
            json.dump(body, f)
    with open(os.path.join(cells, "metrics", "steps_in_window.train.py"),
              "w") as f:
        f.write(NEW_METRIC)
    for cell, conf, traffic in (("tiny-sage-train", "tiny-sage", "tiny-nc"),
                                ("tiny-rgcn-train", "tiny-rgcn",
                                 "tiny-typed-nc")):
        bench["configs"].append({
            "name": conf, "source": "test",
            "file": f"benchmarks/cells/configs/{conf}.json",
            "reduced": [], "why": "test"})
        bench["workloads"].append({"name": cell, "config": conf,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
    bench["per_layer"].append({"name": "steps_in_window.train",
                               "unit": "steps", "better": "higher",
                               "source": "host_clock", "layer": "trainer",
                               "moves": "train_seeds_per_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def cpu_env() -> dict:
    """The CPU, and nothing on the path but what the harness puts there."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for var in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH"):
        env.pop(var, None)
    return env


def drive(root: str, args: list, fault: str = "", timeout: int = 240):
    """Run ``run.main(args)`` in the copy at ``root`` on the CPU;
    returns the completed process."""
    env = cpu_env()
    env["FAULT"] = fault
    return subprocess.run([sys.executable, "-c", LAUNCHER, root, *args],
                          capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=root)


def result_line(proc) -> dict:
    """The contract line: the last line of standard output."""
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1])
