"""Training launcher.

Two families:
  * GNN (the paper's workloads):
        python -m repro.launch.train --arch graphsage --dataset product-sim \
            --machines 2 --trainers-per-machine 2 --epochs 5
    heterogeneous (typed relations end-to-end, RGCN on a schema'd dataset):
        python -m repro.launch.train --arch rgcn --dataset mag-hetero \
            --hetero --rel-fanout cites=10 --rel-fanout writes=5 --epochs 3
  * LM (assigned architectures, reduced or full):
        python -m repro.launch.train --arch qwen2-0.5b --smoke --steps 20

LM full configs need a pod; on this host use --smoke (reduced variant) or
the dry-run for the production mesh.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from .compile_cache import use_compile_cache


def run_gnn(args):
    import jax
    from ..configs import get_config
    from ..graph import get_dataset
    from ..api import (DistGNNTrainer, FaultInjector, TrainJobConfig,
                       TrainerDeath)
    from ..core.kvstore import CacheConfig, NetworkModel

    kill_at = None
    if args.inject_fault:
        try:
            e, _, b = args.inject_fault.partition(":")
            kill_at = (int(e), int(b))
        except ValueError:
            raise SystemExit(f"--inject-fault expects EPOCH:BATCH, "
                             f"got {args.inject_fault!r}")
    if (kill_at or args.recover or args.checkpoint_interval) \
            and not args.checkpoint_dir:
        raise SystemExit("--inject-fault / --recover / "
                         "--checkpoint-interval need --checkpoint-dir")

    cfg = get_config(args.arch)
    ds = get_dataset(args.dataset, scale=args.scale)
    import dataclasses
    # link prediction: the model's output is an embedding (dim = hidden),
    # not class logits, and batch_size counts POSITIVE EDGES per batch
    out_dim = (cfg.hidden_dim if args.task == "link_prediction"
               else ds.num_classes)
    cfg = dataclasses.replace(cfg, in_dim=ds.feats.shape[1],
                              num_classes=out_dim,
                              batch_size=min(cfg.batch_size, args.batch_size),
                              num_rels=ds.graph.num_etypes)
    if args.hetero:
        if ds.schema is None:
            raise SystemExit(f"--hetero needs a schema'd dataset "
                             f"(e.g. mag-hetero), got {args.dataset}")
        # per-relation fanouts: every relation gets the layer fanout unless
        # overridden with --rel-fanout <relation>=<k> (0 disables sampling
        # that relation)
        overrides = {}
        for spec in args.rel_fanout or []:
            rel, sep, k = spec.partition("=")
            if not sep or not k.isdigit():
                raise SystemExit(f"--rel-fanout expects <relation>=<int>, "
                                 f"got {spec!r}")
            if rel not in ds.schema.etypes:
                raise SystemExit(f"unknown relation {rel!r}; dataset "
                                 f"relations: {list(ds.schema.etypes)}")
            overrides[rel] = int(k)
        fanouts = [{rel: overrides.get(rel, f) for rel in ds.schema.etypes}
                   for f in cfg.fanouts]
        cfg = dataclasses.replace(cfg, fanouts=fanouts)
        from ..graph import HeteroCSRGraph
        counts = HeteroCSRGraph(ds.graph, ds.schema).type_counts()
        print(f"[hetero] schema: {list(ds.schema.ntypes)} / "
              f"{list(ds.schema.canonical_etypes)}")
        print(f"[hetero] counts: {counts}")
        print(f"[hetero] per-relation fanouts: {fanouts}")
    cache = (CacheConfig.from_mb(args.cache_budget_mb,
                                 policy=args.cache_policy)
             if args.cache_budget_mb > 0 else None)
    injector = None
    if kill_at or args.rpc_fault_rate:
        injector = FaultInjector(seed=args.fault_seed, kill_at=kill_at,
                                 rpc_failure_rate=args.rpc_fault_rate)
    job = TrainJobConfig(
        num_machines=args.machines,
        trainers_per_machine=args.trainers_per_machine,
        partition_method=args.partition, sync=args.sync,
        non_stop=not args.no_nonstop, cache=cache,
        task=args.task, num_negs=args.num_negs, score_fn=args.score_fn,
        neg_mode=args.neg_mode, neg_exclude=args.neg_exclude,
        sample_workers=args.sample_workers,
        packed_staging=not args.no_packed_staging,
        impl=args.impl,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_interval=args.checkpoint_interval,
        fault_injector=injector,
        replication=args.replication,
        max_rpc_retries=args.max_rpc_retries,
        hedge_ms=args.hedge_ms,
        network=NetworkModel(sleep=args.simulate_network))
    tr = DistGNNTrainer(ds, cfg, job)
    print(f"[train] {args.arch}/{args.task} on {args.dataset}: "
          f"{tr.num_trainers} trainers, {tr.batches_per_epoch} batches/epoch, "
          f"seed locality {tr.locality['mean_local_frac']:.2f}")
    metric = "mrr" if args.task == "link_prediction" else "acc"
    e = 0
    if args.recover:
        meta = tr.recover(args.checkpoint_dir)
        e = meta["epoch"]
        print(f"[recover] resuming at epoch {e}, "
              f"batch {meta['batch_index']} (global step "
              f"{meta['global_step']}) from {args.checkpoint_dir}")
    while e < args.epochs:
        try:
            m = tr.train_epoch(e)
        except TrainerDeath as death:
            # elastic recovery (DESIGN.md §10): the dead trainer's world is
            # torn down and a replacement is built from the same job spec
            # (sans injector — the fault schedule already fired), restored
            # from the last consistent checkpoint, and fast-forwarded to
            # its coordinate. Training resumes byte-identically.
            print(f"[fault] trainer killed at epoch {death.epoch}, "
                  f"batch {death.batch_index} — reviving from checkpoint")
            tr.stop()
            if not os.path.exists(os.path.join(args.checkpoint_dir,
                                               "state.json")):
                print("[recover] no checkpoint written yet — "
                      "restarting from epoch 0")
                tr = DistGNNTrainer(ds, cfg, dataclasses.replace(
                    job, fault_injector=None))
                e = 0
                continue
            t0 = time.perf_counter()
            tr = DistGNNTrainer(ds, cfg, dataclasses.replace(
                job, fault_injector=None))
            meta = tr.recover(args.checkpoint_dir)
            e = meta["epoch"]
            print(f"[recover] {time.perf_counter() - t0:.2f}s — resuming "
                  f"at epoch {e}, batch {meta['batch_index']}")
            continue
        phases = " ".join(f"{k}={v:.3f}s" for k, v in m["phase_s"].items())
        print(f"[epoch {e}] loss={m['loss']:.4f} {metric}={m['acc']:.3f} "
              f"time={m['time_s']:.2f}s phase_s: {phases} "
              f"staged_bytes={m['staged_bytes']} "
              f"staging_arena_allocs={m['staging_arena_allocs']}")
        e += 1
    if args.task == "link_prediction":
        val = tr.evaluate_lp()
        print(f"[final] val_mrr={val['mrr']:.3f} "
              f"hits@10={val.get('hits@10', float('nan')):.3f} "
              f"stats={json.dumps(tr.sampling_stats())}")
    else:
        val = tr.evaluate(ds.val_nids)
        print(f"[final] val_acc={val:.3f} "
              f"stats={json.dumps(tr.sampling_stats())}")
    tr.stop()


def run_lm(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..configs import get_config, smoke_variant
    from ..data import TokenStream
    from ..models.lm import init_train_state, make_train_step

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    step = jax.jit(make_train_step(cfg, lr=args.lr))
    params, opt = init_train_state(cfg, seed=0)
    stream = TokenStream(vocab=cfg.vocab_size, batch=args.batch_size,
                         seq=args.seq_len, seed=0, cfg=cfg,
                         packed=not args.no_packed_staging)
    t0 = time.time()
    for i, batch in enumerate(stream):
        if i >= args.steps:
            break
        params, opt, m = step(params, opt, batch)
        if (i + 1) % max(args.steps // 10, 1) == 0:
            print(f"[step {i+1}] loss={float(m['loss']):.4f} "
                  f"ce={float(m['ce']):.4f} gnorm={float(m['grad_norm']):.2f}")
    dt = time.time() - t0
    toks = args.steps * args.batch_size * args.seq_len
    print(f"[done] {args.steps} steps, {toks/dt:.0f} tok/s")
    stream.stop()


def build_parser() -> argparse.ArgumentParser:
    """The launcher CLI. Every flag here must be documented in the
    top-level README's flag table (tests/test_docs.py enforces it)."""
    ap = argparse.ArgumentParser(prog="repro.launch.train")
    ap.add_argument("--arch", required=True,
                    help="model: graphsage|gat|rgcn or an LM arch id")
    ap.add_argument("--dataset", default="product-sim",
                    help="named synthetic dataset (repro.graph.datasets)")
    ap.add_argument("--scale", type=int, default=12,
                    help="dataset scale exponent (graph has ~2^scale nodes)")
    ap.add_argument("--machines", type=int, default=2,
                    help="simulated machines (level-1 partitions)")
    ap.add_argument("--trainers-per-machine", type=int, default=2,
                    help="trainers per machine (level-2 split)")
    ap.add_argument("--partition", default="metis",
                    choices=["metis", "random"],
                    help="graph partitioner (random = Euler baseline)")
    ap.add_argument("--epochs", type=int, default=3,
                    help="GNN training epochs")
    ap.add_argument("--steps", type=int, default=20,
                    help="LM training steps")
    ap.add_argument("--batch-size", type=int, default=8,
                    help="GNN: seeds per batch (positive edges for "
                         "link prediction); LM: sequences per step")
    ap.add_argument("--seq-len", type=int, default=128,
                    help="LM sequence length")
    ap.add_argument("--lr", type=float, default=3e-4,
                    help="LM learning rate")
    ap.add_argument("--task", default="node_classification",
                    choices=["node_classification", "link_prediction"],
                    help="GNN workload: node classification or edge "
                         "mini-batch link prediction (§6)")
    ap.add_argument("--num-negs", type=int, default=16,
                    help="link prediction: uniform negatives per "
                         "positive edge (static (B, K) shape; too few "
                         "can collapse the BCE score head — see "
                         "DESIGN.md §6)")
    ap.add_argument("--score-fn", default="dot",
                    choices=["dot", "distmult"],
                    help="link-prediction scoring head (distmult learns "
                         "one diagonal relation embedding per etype)")
    ap.add_argument("--neg-mode", default="uniform",
                    choices=["uniform", "in-batch"],
                    help="negative sampling: fresh uniform nodes (own "
                         "ego-networks) or in-batch corrupted dsts")
    ap.add_argument("--neg-exclude", action="store_true",
                    help="re-draw negatives that collide with a positive "
                         "pair of the same batch (false-negative filter)")
    ap.add_argument("--hetero", action="store_true",
                    help="typed-relation path: per-relation fanouts, "
                         "per-ntype KVStore policies (schema'd datasets)")
    ap.add_argument("--rel-fanout", action="append", metavar="REL=K",
                    help="override one relation's fanout (repeatable)")
    ap.add_argument("--cache-budget-mb", type=float, default=0.0,
                    help="per-trainer hot-vertex feature cache budget in "
                         "MB (0 disables the cache)")
    ap.add_argument("--cache-policy", default="clock",
                    choices=["clock", "lru"],
                    help="feature-cache eviction policy")
    ap.add_argument("--impl", default=None,
                    choices=["auto", "ref", "pallas"],
                    help="kernel implementation for the GNN aggregations "
                         "and sparse-Adam (auto = Pallas on TPU, jnp/NumPy "
                         "oracle elsewhere; default keeps the model "
                         "config's choice)")
    ap.add_argument("--no-packed-staging", action="store_true",
                    help="ship each batch array to the device separately "
                         "instead of the packed single-device_put staging "
                         "(DESIGN.md §9; bytes are identical either way)")
    ap.add_argument("--sample-workers", type=int, default=1,
                    help="sampling-stage worker threads per trainer "
                         "(batches are byte-identical for any value; "
                         "see DESIGN.md §7)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for consistent training checkpoints "
                         "(params + optimizer + KVStore shards with row "
                         "versions + cache snapshots; DESIGN.md §10)")
    ap.add_argument("--checkpoint-interval", type=int, default=0,
                    help="global steps between checkpoints (0 disables; "
                         "needs --checkpoint-dir)")
    ap.add_argument("--recover", action="store_true",
                    help="restore the --checkpoint-dir checkpoint before "
                         "training and fast-forward the deterministic "
                         "schedule to its (epoch, batch) coordinate")
    ap.add_argument("--inject-fault", metavar="EPOCH:BATCH", default=None,
                    help="chaos testing: kill the trainer right before "
                         "consuming this batch, then auto-revive a "
                         "replacement from the last checkpoint "
                         "(byte-identical resumed training)")
    ap.add_argument("--rpc-fault-rate", type=float, default=0.0,
                    help="chaos testing: probability each feature/gradient "
                         "RPC fails transiently (retried with backoff; "
                         "bytes are unchanged by retries)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the injected failure schedule "
                         "(deterministic chaos)")
    ap.add_argument("--replication", type=int, default=1,
                    help="KVStore feature-plane replica count: each "
                         "partition's shard also lives on its r-1 ring "
                         "successors; reads fail over byte-identically "
                         "when the owner is down (DESIGN.md §12)")
    ap.add_argument("--max-rpc-retries", type=int, default=8,
                    help="per-destination transient-RPC retry budget "
                         "before a peer is treated as dead")
    ap.add_argument("--hedge-ms", type=float, default=None,
                    help="hedged reads: race a replica after this many ms "
                         "without a primary response (needs "
                         "--replication >= 2; default off)")
    ap.add_argument("--smoke", action="store_true",
                    help="LM: reduced same-family config for CPU smoke runs")
    ap.add_argument("--sync", action="store_true",
                    help="disable the async pipeline (unpipelined baseline)")
    ap.add_argument("--no-nonstop", action="store_true",
                    help="drain the pipeline between epochs (ablation)")
    ap.add_argument("--simulate-network", action="store_true",
                    help="enable the network cost model's real sleeps")
    return ap


def main():
    args = build_parser().parse_args()
    use_compile_cache()
    from ..configs import GNN_ARCHS
    if args.arch in GNN_ARCHS:
        run_gnn(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
