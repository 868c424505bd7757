"""Bring-up smoke of the GNN training step on one TPU chip.

    python chip_smoke.py

Builds ``product-sim`` at scale 17 (131,072 nodes, 13,107 training
seeds), partitions it over 2 machines x 2 trainers (3,276 seeds per
trainer) and takes one epoch's 3 synchronous steps of the paper's
GraphSAGE (``configs/graphsage.py``: 3 layers, hidden 256, fanouts
15/10/5, batch 1000, ``in_dim`` 100) through ``DistGNNTrainer``, on the
kernels that ``impl="auto"`` picks on a TPU.  It prints the device kind,
the step's compile time, each step's time (ended by
``block_until_ready``), the losses, the number of Mosaic kernels
(``tpu_custom_call``) in the compiled step, the device's peak memory, and
the first step's loss beside the jnp reference path's on the same stacked
batch.  The last line of its output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

It exits non-zero, printing no such line, when JAX finds no TPU and when
any check fails.  One process, no children.  A bring-up check, not a
benchmark: every time it prints is one cold sample.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

SCALE = 17
MACHINES, TRAINERS_PER_MACHINE = 2, 2
STEPS = 3
# The kernels sum each layer's messages in f32 on the MXU (HIGHEST
# precision) in another order than XLA's scatter-add; the rest of the
# step is the same XLA program on both paths. Order alone moves a loss
# near 3 by ~1e-6 relative; the bound leaves room for the default-precision
# matmuls downstream to round the slightly different sums differently.
LOSS_RTOL = 1e-3


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _kernel_count(compiled) -> int:
    return sum('custom_call_target="tpu_custom_call"' in line
               for line in compiled.as_text().splitlines())


def run(dataset: str, scale: int, cfg, machines: int, trainers: int,
        steps: int) -> dict:
    """Train ``steps`` synchronous steps of one epoch; return what the
    checks read. Prints one line per phase."""
    import jax
    import numpy as np
    from repro.api import DistGNNTrainer, TrainJobConfig
    from repro.graph import get_dataset
    from repro.models.gnn import apply_gnn, nc_loss

    t0 = time.perf_counter()
    ds = get_dataset(dataset, scale=scale)
    cfg = dataclasses.replace(cfg, in_dim=ds.feats.shape[1],
                              num_classes=ds.num_classes)
    tr = DistGNNTrainer(ds, cfg, TrainJobConfig(
        num_machines=machines, trainers_per_machine=trainers))
    print(f"setup_s: {time.perf_counter() - t0:.3f} ({dataset} scale "
          f"{scale}: {ds.graph.num_nodes} nodes; {tr.num_trainers} "
          f"trainers, {tr.batches_per_epoch} batches/epoch)", flush=True)
    if tr.batches_per_epoch < steps:
        _fail(f"{tr.batches_per_epoch} batches per epoch, need {steps}")
    ref_cfg = dataclasses.replace(tr.node_cfg, impl="ref")

    @jax.jit
    def ref_loss(params, stacked):
        def one(batch):
            logits = apply_gnn(ref_cfg, params, batch)
            return nc_loss(logits, batch["labels"], batch["seed_mask"])
        return jax.vmap(one)(stacked).mean()

    out = {"losses": [], "step_s": []}
    try:
        iters = [ld.epoch(0) for ld in tr.loaders]
        for k in range(steps):
            t0 = time.perf_counter()
            batches = [next(it).model_input() for it in iters]
            t1 = time.perf_counter()
            stacked = jax.block_until_ready(tr._stack(batches))
            print(f"batch {k}: wait {t1 - t0:.4f} s, stack + stage "
                  f"{time.perf_counter() - t1:.4f} s", flush=True)
            if k == 0:
                t0 = time.perf_counter()
                step = tr._step.lower(tr.params, tr.opt, stacked).compile()
                out["compile_s"] = time.perf_counter() - t0
                out["kernels"] = _kernel_count(step)
                print(f"compile_s: {out['compile_s']:.3f}", flush=True)
                print(f"tpu_custom_call: {out['kernels']}", flush=True)
                params0 = tr.params
            t0 = time.perf_counter()
            tr.params, tr.opt, loss, acc = jax.block_until_ready(
                step(tr.params, tr.opt, stacked))
            out["step_s"].append(time.perf_counter() - t0)
            out["losses"].append(float(loss))
            print(f"step {k}: {out['step_s'][-1]:.4f} s loss="
                  f"{float(loss):.6f} acc={float(acc):.4f}", flush=True)
            if k == 0:
                out["ref_loss0"] = float(ref_loss(params0, stacked))
                del params0
            del stacked
    finally:
        tr.stop()
    out["finite"] = bool(np.isfinite(out["losses"]).all())
    return out


def main() -> int:
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"device_kind: {dev.device_kind} (x{len(jax.devices())}); "
          f"compile cache: {cache}", flush=True)
    from repro.configs import get_config
    out = run("product-sim", SCALE, get_config("graphsage"), MACHINES,
              TRAINERS_PER_MACHINE, STEPS)
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak}", flush=True)
    l_kernel, l_ref = out["losses"][0], out["ref_loss0"]
    rel = abs(l_kernel - l_ref) / abs(l_ref)
    print(f"step 0 loss: kernels={l_kernel:.7f} ref={l_ref:.7f} "
          f"rel_diff={rel:.3e} (bound {LOSS_RTOL:g})", flush=True)
    if not out["finite"]:
        _fail(f"non-finite losses {out['losses']}")
    if out["kernels"] <= 0:
        _fail("no tpu_custom_call in the compiled step")
    if not rel <= LOSS_RTOL:
        _fail(f"kernel loss {l_kernel} vs ref {l_ref}: {rel:.3e} > "
              f"{LOSS_RTOL:g}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
