#!/usr/bin/env python3
"""Readings that the limits in ``limits/<cell>.json`` are set from.

    python3 benchmarks/cells/calibrate.py --workload sage-products-train \\
        --seeds 11 12 13 ... --controls 3 --out readings.jsonl

For each seed, in one process (the graph is made once): the set-up of a
benchmark run (``run.build``: the trainer, the weights, the three
recorded steps through ``train_epoch``), then the numbers ``run.check``
compares, for

* ``program``: the program against the float32 reference;
* ``program@highest``: the same against the reference with its matmuls
  at ``highest`` precision, which no number compares (``refcheck.py``
  says why);

and, on the first ``--controls`` seeds, for what stands in the
program's place against that same reference:

* ``control``: the reference in bfloat16 (weights, features, activations
  and sums; the loss from its logits in float32);
* ``fault:<name>``: the reference with one of ``refcheck.FAULTS``
  planted: the state left unchanged, half of each batch left out, the
  mean over the trainers left out (trainer 0's gradient alone), the
  labels of trainer 0's batch altered.

Each kind's numbers go through ``run.verdict`` with the cell's limits,
which gives its ``correct``: true for the program, false for the
control and each fault.  Needs the chip the cell asks for, as
``run.py`` does; the benchmark's runs never run it.  One JSON object per
seed and kind on standard output and in ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
import world  # noqa: E402


def leaves(got: dict, ref: dict) -> dict:
    """Per parameter leaf: the norms of the first gradient and of the
    change, the program's (or stand-in's) and the reference's."""
    import jax
    import numpy as np

    def norms(tree):
        return {jax.tree_util.keystr(k): float(np.linalg.norm(
            np.asarray(v, np.float64)))
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}
    out = {}
    for part in ("grad", "delta"):
        g, r = norms(got[part]), norms(ref[part])
        out[part] = {k: [g[k], r[k]] for k in r}
    return out


def readings(s: run.Session, new2old, controls: bool) -> list:
    """Per kind, its numbers and ``correct`` as ``run.check`` decides it
    against the cell's limits (a stand-in shares the program's batches,
    and nothing of it runs in a window)."""
    import jax.numpy as jnp
    from refcheck import FAULTS, compare
    prog, ref = run.program_numbers(s, new2old)
    exact = {"batch_mismatches": prog["batch_mismatches"],
             "failed_steps": 0, "window_compiles": 0}
    prog["leaves"] = leaves(run.program_readings(s), ref)
    prog["losses"] = [s.recorder.losses, ref["losses"]]
    out = [("program", prog),
           ("program@highest", compare(run.program_readings(s),
                                       run.train_reference(
                                           s, new2old, precision="highest")))]
    if controls:
        ctl = run.train_reference(s, new2old, dtype=jnp.bfloat16)
        out.append(("control", {**compare(ctl, ref),
                                "leaves": leaves(ctl, ref)}))
        out += [("fault:" + f,
                 compare(run.train_reference(s, new2old, fault=f), ref))
                for f in FAULTS]
    return [(kind, {"correct": run.verdict(
        s.cell.limits, {**exact, **numbers})["correct"], **numbers})
        for kind, numbers in out]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    cell = world.load_cell(args.workload)
    sys.path.insert(0, os.path.join(world.ROOT, "src"))
    run.use_cache()
    run.require_accelerator(cell.chips)
    graph = world.make_graph(cell.traffic)
    sink = open(args.out, "a") if args.out else None
    try:
        for i, seed in enumerate(args.seeds):
            t = time.perf_counter()
            s = run.build(cell, seed, graph)
            new2old = run.teardown(s)
            for kind, numbers in readings(s, new2old, i < args.controls):
                line = json.dumps({"cell": cell.name, "seed": seed,
                                   "kind": kind, **numbers})
                print(line, flush=True)
                if sink:
                    sink.write(line + "\n")
                    sink.flush()
            run.log(f"seed {seed}: {time.perf_counter() - t:.3f} s")
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
