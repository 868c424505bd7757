"""Table 2 analogue: end-to-end pipeline time breakdown — partitioning,
partition load/save, training-data load, and train time, plus the
per-stage busy/starved/backpressured breakdown of the async mini-batch
pipeline (what the paper's Fig. 7 stages actually cost).  The full
per-stage detail also lands in ``BENCH_table2.json`` for CI.

Workloads:
  * ``table2/...``          — homogeneous GraphSAGE on product-sim;
  * ``table2/hetero/...``   — typed-relation RGCN on the mag-hetero
    heterograph (per-relation fanouts, per-ntype KVStore policies), the
    paper's OGBN-MAG-class configuration;
  * ``table2/linkpred/...`` — edge-mini-batch link prediction (the paper's
    second task, §6) through the same async pipeline, with async-vs-sync
    and cache-on/off ablation columns;
  * ``table2/stage/device_prefetch_*`` — the device-staging columns:
    the device-prefetch stage's per-batch busy time under packed one-shot
    staging (DESIGN.md §9) vs the legacy per-array ``device_put`` loop.

Run:  PYTHONPATH=src python -m benchmarks.table2_breakdown [--smoke]
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import numpy as np

from .common import csv_line, hetero_cfg, lp_cfg, make_trainer, small_cfg
from repro.checkpoint import save_kvstore, load_kvstore
from repro.graph import get_dataset


def _breakdown(tag: str, ds, cfg, t_load: float, epochs: int,
               cache_mb: float = 0.0, **tr_kw) -> dict:
    tr = make_trainer(ds, cfg, cache_mb=cache_mb, **tr_kw)   # partitions inside
    t_part = tr.partition_time_s

    with tempfile.TemporaryDirectory() as tmp:
        t0 = time.perf_counter()
        save_kvstore(tr.store, tmp)
        load_kvstore(tr.store, tmp)
        t_ckpt = time.perf_counter() - t0

    t0 = time.perf_counter()
    for e in range(epochs):
        tr.train_epoch(e)
    t_train = time.perf_counter() - t0
    # loader-level observability (repro.api): stage times, cache hit rate
    # and sampler coalescing come from loader.stats_report() — no reaching
    # into trainer internals
    loader_rep = tr.loaders[0].stats_report()
    stage_stats = loader_rep["stages"]
    sampling = tr.sampling_stats()
    tr.stop()

    csv_line(f"{tag}/load_data", t_load * 1e6)
    csv_line(f"{tag}/partition", t_part * 1e6)
    csv_line(f"{tag}/save_load_partition", t_ckpt * 1e6)
    csv_line(f"{tag}/train", t_train * 1e6, f"epochs={epochs}")
    # remote request COUNT (not just bytes): the per-owner coalescing of
    # the typed dispatch shows up here (coalescing_factor = per-relation
    # requests each issued request replaced; 1.0 on untyped runs)
    req = sampling["sampler_requests"]
    csv_line(f"{tag}/remote_requests",
             float(sampling["transport"]["remote_requests"]),
             f"coalescing_factor={req['coalescing_factor']:.1f};"
             f"owner_requests={req['owner_requests']}")
    consumer = stage_stats.pop("consumer")
    csv_line(f"{tag}/consumer_wait",
             consumer["wait_in_s"] * 1e6 / max(consumer["items"], 1),
             f"items={consumer['items']}")
    for name, st in stage_stats.items():
        csv_line(f"{tag}/stage/{name}",
                 st["busy_s"] * 1e6 / max(st["items"], 1),
                 f"items={st['items']};starved_s={st['wait_in_s']:.3f};"
                 f"backpressure_s={st['wait_out_s']:.3f};"
                 f"workers={st.get('workers', 1)}")
    if loader_rep["cache"] is not None:
        csv_line(f"{tag}/loader/cache_hit_rate",
                 loader_rep["cache"]["hit_rate"] * 100.0,
                 f"hits={loader_rep['cache']['hits']};"
                 f"misses={loader_rep['cache']['misses']}")
    if "edges_per_etype" in sampling:
        per = sampling["edges_per_etype"]
        csv_line(f"{tag}/edges_per_etype", float(sum(per.values())),
                 ";".join(f"{k}={v}" for k, v in per.items()))
    return dict(load=t_load, partition=t_part, ckpt=t_ckpt, train=t_train,
                stages=stage_stats, sampling=sampling)


def _cache_ablation(tag: str, ds, cfg, epochs: int, off: dict,
                    cache_mb: float = 64.0, **tr_kw) -> dict:
    """Cache-on vs cache-off column: same workload with a per-trainer
    hot-vertex cache; the paper-style metric is the remote-traffic
    reduction relative to the uncached run (prewarm pulls included in the
    cache-on total, so the saving reported is net)."""
    on = _breakdown(f"{tag}/cache_on", ds, cfg, 0.0, epochs,
                    cache_mb=cache_mb, **tr_kw)
    b_off = off["sampling"]["transport"]["remote_bytes"]
    tp_on = on["sampling"]["transport"]
    reduction = 1.0 - tp_on["remote_bytes"] / max(b_off, 1)
    csv_line(f"{tag}/cache/remote_bytes_off", float(b_off))
    csv_line(f"{tag}/cache/remote_bytes_on", float(tp_on["remote_bytes"]),
             f"budget_mb={cache_mb}")
    csv_line(f"{tag}/cache/saved_remote_bytes",
             float(tp_on["saved_remote_bytes"]),
             f"hit_rate={tp_on['cache_hit_rate']:.3f}")
    csv_line(f"{tag}/cache/remote_traffic_reduction", reduction * 100.0,
             "percent_vs_cache_off")
    return dict(remote_bytes_off=b_off,
                remote_bytes_on=tp_on["remote_bytes"],
                saved=tp_on["saved_remote_bytes"], reduction=reduction)


def _linkpred_rows(scale: int, cache_mb: float) -> dict:
    """Link-prediction rows (§6's second task): the full breakdown on the
    async path, an async-vs-sync train column, and the cache-on/off
    ablation — all through the edge-mini-batch pipeline. Runs one scale
    down from the node rows: LP schedules EVERY owned edge per epoch."""
    ds = get_dataset("product-sim", scale=scale)
    cfg = lp_cfg(ds, batch_edges=64)
    kw = dict(task="link_prediction", num_negs=4)
    out = {"async": _breakdown("table2/linkpred", ds, cfg, 0.0, 1, **kw)}

    tr = make_trainer(ds, cfg, sync=True, non_stop=False, **kw)
    t0 = time.perf_counter()
    tr.train_epoch(0)
    t_sync = time.perf_counter() - t0
    tr.stop()
    speed = t_sync / max(out["async"]["train"], 1e-9)
    csv_line("table2/linkpred/train_sync", t_sync * 1e6,
             f"async_speedup={speed:.2f}x")
    out["sync_train"] = t_sync

    out["cache"] = _cache_ablation("table2/linkpred", ds, cfg, 1,
                                   out["async"], cache_mb=cache_mb, **kw)
    return out


def _worker_scaling_rows(scale: int) -> dict:
    """Sampling-front batches/s vs --sample-workers on the table2
    product-sim config (the PR 4 acceptance number); full detail lands in
    BENCH_sampling.json via benchmarks.sampling_micro."""
    from .sampling_micro import worker_scaling
    out = worker_scaling(scale)
    for r in out["rows"]:
        csv_line(f"table2/sample_workers/{r['workers']}",
                 r["time_s"] * 1e6 / max(r["batches"], 1),
                 f"batches_per_s={r['batches_per_s']:.1f};"
                 f"speedup_vs_w1={r['speedup_vs_w1']:.2f}x")
    return out


def _staging_rows(scale: int, epochs: int = 1) -> dict:
    """Device-staging columns: the device-prefetch stage's per-batch busy
    time with packed one-shot staging (a single transfer of the uint8
    arena, DESIGN.md §9) vs the legacy per-array loop it
    replaced — measured where it runs, as a pipeline stage with
    ``to_device=True``.  The pipeline runs ``sync=True`` (inline stages):
    staging cost is host+PCIe work, and measuring it under the async
    threads would fold the *other* stages' GIL pressure into the number."""
    from .sampling_micro import _homo_world
    from repro.core.kvstore import NetworkModel, Transport
    from repro.core.pipeline import MinibatchPipeline
    from repro.core.sampler import DistributedSampler

    ds, hp, store, seeds = _homo_world(scale)
    pipes = {}
    for packed in (False, True):
        sampler = DistributedSampler(hp.book, hp.partitions, [10, 5], 8,
                                     machine=0,
                                     transport=Transport(NetworkModel()),
                                     seed=3)
        key = "packed" if packed else "per_array"
        pipes[key] = MinibatchPipeline(sampler, store.client(0), "feat",
                                       seeds, batch_size=8, sync=True,
                                       non_stop=False, to_device=True,
                                       packed=packed, seed=4)
    # epoch 0 is warmup (allocator + spec/unpack caches); sync mode
    # rebuilds the pipeline per epoch, so each epoch's stats are
    # independent.  The two arms run back-to-back WITHIN each round so
    # machine-throughput drift hits both equally, and each arm reports
    # its best round (min is the noise-robust statistic for a fixed
    # workload).
    rows = {k: None for k in pipes}
    for e in range(max(epochs, 4) + 1):
        for key, pipe in pipes.items():
            for _mb, _dev in pipe.epoch(e):
                pass
            st = pipe.stats_report()["device_prefetch"]
            us = st["busy_s"] * 1e6 / max(st["items"], 1)
            if e > 0 and (rows[key] is None
                          or us < rows[key]["us_per_batch"]):
                rows[key] = dict(us_per_batch=us, items=st["items"],
                                 busy_s=st["busy_s"])
    for pipe in pipes.values():
        pipe.stop()
    speed = (rows["per_array"]["us_per_batch"]
             / max(rows["packed"]["us_per_batch"], 1e-9))
    rows["packed_speedup"] = speed
    csv_line("table2/stage/device_prefetch_per_array",
             rows["per_array"]["us_per_batch"],
             f"items={rows['per_array']['items']}")
    csv_line("table2/stage/device_prefetch_packed",
             rows["packed"]["us_per_batch"],
             f"items={rows['packed']['items']};"
             f"packed_speedup={speed:.2f}x")
    return rows


def run(scale=12, epochs=2, cache_mb=64.0,
        out_path: str = "BENCH_table2.json", smoke: bool = False):
    if smoke:
        # scale 11 is the floor: the homogeneous config needs >=32 train
        # seeds per trainer (2 machines x 2 trainers)
        scale, epochs = min(scale, 11), 1
    t0 = time.perf_counter()
    ds = get_dataset("product-sim", scale=scale)
    t_load = time.perf_counter() - t0
    cfg = small_cfg(in_dim=ds.feats.shape[1])
    out = {"config": {"scale": scale, "epochs": epochs, "smoke": smoke}}
    out["homogeneous"] = _breakdown("table2", ds, cfg, t_load, epochs)
    out["homogeneous_cache"] = _cache_ablation(
        "table2", ds, cfg, epochs, out["homogeneous"], cache_mb=cache_mb)
    out["sample_workers"] = _worker_scaling_rows(scale)
    out["device_staging"] = _staging_rows(scale, epochs=epochs)

    t0 = time.perf_counter()
    ds_h = get_dataset("mag-hetero", scale=scale)
    t_load_h = time.perf_counter() - t0
    cfg_h = hetero_cfg(ds_h)
    out["hetero"] = _breakdown("table2/hetero", ds_h, cfg_h, t_load_h, epochs)
    out["hetero_cache"] = _cache_ablation(
        "table2/hetero", ds_h, cfg_h, epochs, out["hetero"],
        cache_mb=cache_mb)

    out["linkpred"] = _linkpred_rows(scale - 1, cache_mb)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=2,
                  default=lambda o: o.item() if isinstance(o, np.generic)
                  else str(o))
    print(f"[table2_breakdown] wrote {out_path}")
    return out


def main():
    ap = argparse.ArgumentParser(prog="benchmarks.table2_breakdown")
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--out", default="BENCH_table2.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small scale for CI: same columns, tiny run")
    args = ap.parse_args()
    run(scale=args.scale, epochs=args.epochs, out_path=args.out,
        smoke=args.smoke)


if __name__ == "__main__":
    main()
