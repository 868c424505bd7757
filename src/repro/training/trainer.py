"""Distributed synchronous mini-batch GNN training (§5.1, §5.6).

``DistGNNTrainer`` is a thin composition over the public ``repro.api``
surface: one :class:`~repro.api.DistGraph` world (partition book + KVStore
+ typed relation views), per-trainer :class:`~repro.api.NodeDataLoader` /
:class:`~repro.api.EdgeDataLoader` instances over the async pipeline, and
one *synchronous* SGD step per iteration across all trainers (data
parallelism). Anything this class does, a user script can do with the
same façades — the trainer only adds the multi-trainer stacking and the
jitted step (DESIGN.md §8).

On a real TPU pod each trainer is one chip and the gradient all-reduce is
GSPMD's; in this one-host harness the T trainers' mini-batches are stacked
on a leading axis and the step is jitted with that axis sharded over the
mesh's "data" axis (identical program; with one CPU device the psum
degenerates but the math — mean gradient over all trainers' batches — is
exactly synchronous SGD, so convergence behaviour is faithful).

The constructor options are the Fig. 14 ablation axes:
  partition_method="random"|"metis", use_level2, sync (no pipeline),
  non_stop (never drain the pipeline between epochs).
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import json
import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..api.dataloader import EdgeDataLoader, NodeDataLoader
from ..api.dist_graph import DistGraph
from ..checkpoint import (load_cache, load_kvstore, load_pytree, save_cache,
                          save_kvstore, save_pytree)
from ..core.kvstore import CacheConfig, FaultInjector, NetworkModel
from ..core.sampler import EdgeBatchSampler
from ..graph.datasets import GraphDataset
from ..kernels.pack import StackedArena
from ..models.gnn import (GNNConfig, apply_gnn, init_gnn, init_lp_head,
                          lp_loss_from_scores, lp_metrics, lp_pair_scores,
                          lp_ranks, nc_accuracy, nc_loss)
from ..optim import adamw_init, adamw_update

TASKS = ("node_classification", "link_prediction")
# the phases of a training step, in order; each is the span
# ``trainer.<phase>`` and a key of ``train_epoch()``'s ``phase_s``
PHASES = ("wait_batches", "stage", "step", "sync")


class Span:
    """A host span in the profiler's trace (``jax.profiler.TraceAnnotation``,
    on the device trace's clock) whose host-clock seconds are also added
    to ``seconds[name]``: one mechanism for the span a trace viewer shows
    and the counter a report reads.  Off the profiler it costs the
    annotation's construction and two clock reads."""

    __slots__ = ("seconds", "name", "annotation", "t0")

    def __init__(self, seconds: Dict[str, float], name: str, **metadata):
        self.seconds = seconds
        self.name = name
        self.annotation = jax.profiler.TraceAnnotation(name, **metadata)

    def __enter__(self):
        self.annotation.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.seconds[self.name] += time.perf_counter() - self.t0
        return self.annotation.__exit__(*exc)


@dataclasses.dataclass
class TrainJobConfig:
    num_machines: int = 2
    trainers_per_machine: int = 2
    partition_method: str = "metis"      # "metis" | "random" (Euler baseline)
    use_level2: bool = True              # 2-level partition seed split
    sync: bool = False                   # disable the async pipeline
    non_stop: bool = True                # non-stop pipeline across epochs
    lr: float = 3e-3
    network: Optional[NetworkModel] = None
    pipeline_depths: Optional[dict] = None
    cache: Optional[CacheConfig] = None  # per-trainer hot-vertex cache
    # sampling-stage worker pool per trainer (§5.5's multiple sampling
    # workers); batches are byte-identical for any value (DESIGN.md §7)
    sample_workers: int = 1
    # device staging (DESIGN.md §9): True = the T batches of a step are
    # written into one reused host arena (a segment per dtype) and shipped
    # with a SINGLE jax.device_put + jitted static-slice unpack; False = legacy
    # per-array transfers. Bytes reaching the jitted step are identical.
    packed_staging: bool = True
    # kernel implementation for the model's aggregations (GNNConfig.impl)
    # and the sparse-Adam path: None = keep the model config's own choice
    # ("auto" → pallas on TPU, jnp oracle elsewhere); "ref"/"pallas" force
    impl: Optional[str] = None
    # ---- workload (the paper trains "various GNN workloads") ----------
    # link_prediction: positive-edge batches over each trainer's owned
    # edges, `num_negs` uniform corrupted dsts per edge, `score_fn` head
    # (dot | distmult-per-relation), MRR/Hits@k eval. For this task the
    # model config's batch_size is the EDGE batch B; the node batch the
    # samplers/model use is derived (2B + B*K, DESIGN.md §6).
    task: str = "node_classification"
    # 16, not DGL's customary handful: with few uniform negatives the BCE
    # objective can settle into the all-scores-zero fixed point (loss
    # 2·ln2) on homophilous graphs, ranking WORSE than an untrained
    # encoder; K=16 reliably escapes it (measured in tests/test_linkpred)
    num_negs: int = 16
    score_fn: str = "dot"                # "dot" | "distmult"
    neg_mode: str = "uniform"            # "uniform" | "in-batch"
    neg_exclude: bool = False            # re-draw batch-positive collisions
    # ---- elastic fault tolerance (DESIGN.md §10) ----------------------
    # consistent checkpoints every `checkpoint_interval` global steps into
    # `checkpoint_dir`; a replacement trainer's recover() restores them
    # and fast-forwards the deterministic schedule to the saved coordinate
    checkpoint_dir: Optional[str] = None
    checkpoint_interval: int = 0         # global steps between saves; 0 = off
    # seeded failure schedule (kill_at death + transient RPC faults),
    # attached to the world's shared transport — tests and the chaos
    # benchmark inject through here, production leaves it None
    fault_injector: Optional[FaultInjector] = None
    seed: int = 0
    # ---- availability (DESIGN.md §12) ----------------------------------
    # r-way replica placement for the KVStore feature plane: reads fail
    # over to a live replica on sustained owner outages (byte-identical —
    # writes are synchronous), so training survives a down server with
    # ZERO restarts. 1 = unreplicated (exactly the pre-§12 behavior).
    replication: int = 1
    # per-destination RPC retry budget (was the MAX_RPC_RETRIES constant)
    max_rpc_retries: int = 8
    # hedged reads: after this many ms without a primary response, race a
    # replica and take the first success; None = off
    hedge_ms: Optional[float] = None


class DistGNNTrainer:
    def __init__(self, ds: GraphDataset, model_cfg: GNNConfig,
                 job: TrainJobConfig):
        self.ds = ds
        if job.impl is not None:
            model_cfg = dataclasses.replace(model_cfg, impl=job.impl)
        self.cfg = model_cfg
        self.job = job
        if job.task not in TASKS:
            raise ValueError(f"unknown task {job.task!r}; have {TASKS}")
        self.task = job.task
        if job.checkpoint_interval and not job.checkpoint_dir:
            raise ValueError("checkpoint_interval > 0 needs a checkpoint_dir")
        if self.task == "link_prediction":
            # cfg.batch_size is the EDGE batch; the node samplers (and the
            # model's capacity formulas) run at the derived endpoint-seed
            # capacity — one config object keeps them in lockstep (§2 rule 4)
            node_bs = EdgeBatchSampler.required_node_batch(
                model_cfg.batch_size, job.num_negs, job.neg_mode)
            self.node_cfg = dataclasses.replace(model_cfg,
                                                batch_size=node_bs)
        else:
            self.node_cfg = model_cfg

        # the world: partition + KVStore + typed views, behind one handle
        self.graph = DistGraph(
            ds, num_machines=job.num_machines,
            trainers_per_machine=job.trainers_per_machine,
            partition_method=job.partition_method,
            hetero=model_cfg.typed, seed=job.seed, network=job.network,
            replication=job.replication,
            max_rpc_retries=job.max_rpc_retries, hedge_ms=job.hedge_ms)
        self.hp = self.graph.hp
        self.partition_time_s = self.graph.partition_time_s
        self.transport = self.graph.transport
        if job.fault_injector is not None:
            # every RPC in the world — feature pulls, gradient pushes —
            # flows through this one transport, so attaching the injector
            # here puts the whole stack under the failure schedule
            self.transport.fault_injector = job.fault_injector
        self.store = self.graph.store
        self.labels_new = self.graph.labels
        self.schema = self.graph.schema
        self.hetero = self.graph.hetero
        self.typed = self.graph.typed

        # per-trainer seed split (§5.6.1): node tasks split the training
        # vertices; link prediction splits each machine's OWNED edge range
        # into equalized per-trainer pools — "we may use all edges to
        # train a model" (§6). Both splits live on DistGraph now.
        if self.task == "link_prediction":
            self.e_src, self.e_dst = self.graph.edge_endpoints()
            self.trainer_edges: List[np.ndarray] = self.graph.edge_splits()
            # locality of the positive SOURCES (dsts are local by
            # construction — edges are owned by their dst's machine)
            self.locality = self.graph.locality_report(
                [self.e_src[e] for e in self.trainer_edges])
        else:
            self.trainer_seeds = self.graph.node_splits(
                self.graph.train_nids, use_level2=job.use_level2,
                seed=job.seed)
            self.locality = self.graph.locality_report(self.trainer_seeds)

        # per-trainer loaders (each owns its sampler, client, cache and
        # async pipeline); the trainer only stacks their batches
        self.num_trainers = self.graph.num_trainers
        self.loaders: List[NodeDataLoader] = []
        for ti in range(self.num_trainers):
            gt = self.graph.trainer_view(ti)
            cache = gt.feature_cache(job.cache)
            if self.task == "link_prediction":
                ld = EdgeDataLoader(
                    gt, self.trainer_edges[ti], self.node_cfg.fanouts,
                    batch_size=model_cfg.batch_size, num_negs=job.num_negs,
                    neg_mode=job.neg_mode, neg_exclude=job.neg_exclude,
                    sync=job.sync, non_stop=job.non_stop,
                    depths=job.pipeline_depths, device_prefetch=False,
                    cache=cache, sample_workers=job.sample_workers,
                    seed=job.seed + 200 + ti,
                    sampler_seed=job.seed + 100 + ti,
                    edge_seed=job.seed + 300 + ti)
            else:
                seeds = self.trainer_seeds[ti]
                ld = NodeDataLoader(
                    gt, seeds, self.node_cfg.fanouts,
                    batch_size=self.node_cfg.batch_size,
                    labels=self.labels_new[seeds], sync=job.sync,
                    non_stop=job.non_stop, depths=job.pipeline_depths,
                    device_prefetch=False, cache=cache,
                    sample_workers=job.sample_workers,
                    seed=job.seed + 200 + ti,
                    sampler_seed=job.seed + 100 + ti)
            self.loaders.append(ld)
        # component views (stats, tests, benchmarks)
        self.samplers = [ld.sampler for ld in self.loaders]
        self.edge_samplers = [ld.edge_sampler for ld in self.loaders
                              if isinstance(ld, EdgeDataLoader)]
        self.pipelines = [ld.pipeline for ld in self.loaders]
        self.caches = [ld.cache for ld in self.loaders]

        self.batches_per_epoch = min(len(ld) for ld in self.loaders)
        if self.batches_per_epoch < 1:
            if self.task == "link_prediction":
                raise ValueError(
                    f"edge batch {model_cfg.batch_size} exceeds the "
                    f"per-trainer owned-edge pool "
                    f"({min(len(e) for e in self.trainer_edges)} edges/"
                    f"trainer) — shrink the batch or the trainer count")
            raise ValueError(
                f"batch_size {model_cfg.batch_size} exceeds the per-trainer "
                f"training-set split ({min(len(s) for s in self.trainer_seeds)} "
                f"seeds/trainer) — shrink the batch or the trainer count")

        self.params = init_gnn(self.node_cfg, jax.random.key(job.seed))
        if self.task == "link_prediction":
            self.params = {"gnn": self.params,
                           "lp": init_lp_head(job.score_fn,
                                              self.node_cfg.num_rels,
                                              self.node_cfg.num_classes)}
        self.opt = adamw_init(self.params)
        self._step = self._build_step()
        # host seconds per span name and bytes staged to the device, since
        # construction; ``train_epoch()`` reports each epoch's share
        self.span_s: Dict[str, float] = collections.defaultdict(float)
        self.staged_bytes = 0
        # the step's host arena, kept from step to step (its ``allocs``
        # are reported per epoch as ``staging_arena_allocs``)
        self.staging = StackedArena()
        self._eval_ranks_fn = None
        self._eval_ranks_key = None
        # optimizer steps taken since construction (or since recover());
        # the checkpoint cadence counts these, not per-epoch batches
        self.global_step = 0
        # (epoch, batch_index) a recover() restored — the next
        # train_epoch() call must target that epoch and fast-forwards to
        # that batch (DESIGN.md §10)
        self._resume: Optional[tuple] = None

    # ------------------------------------------------------------------
    def _lp_scores(self, params, batch, cfg: Optional[GNNConfig] = None):
        """Embeddings -> (pos, neg) scores; shared by train and eval
        (eval passes its own cfg — its endpoint capacity differs)."""
        h = apply_gnn(cfg or self.node_cfg, params["gnn"], batch,
                      etype_id=self.schema.etype_id if self.hetero else None)
        kw = dict(head=params["lp"], score_fn=self.job.score_fn,
                  etypes=batch["edge_etypes"])
        pos = lp_pair_scores(h, batch["pos_u"], batch["pos_v"], **kw)
        neg = lp_pair_scores(h, batch["pos_u"], batch["neg_v"], **kw)
        return pos, neg

    def _build_step(self):
        lr = self.job.lr
        if self.task == "link_prediction":
            @jax.jit
            def step(params, opt, stacked):
                def loss_one(p, batch):
                    pos, neg = self._lp_scores(p, batch)
                    loss = lp_loss_from_scores(pos, neg, batch["pair_mask"])
                    mrr = lp_metrics(lp_ranks(pos, neg),
                                     batch["pair_mask"])["mrr"]
                    return loss, mrr

                def loss_fn(p):
                    losses, mrrs = jax.vmap(lambda b: loss_one(p, b))(stacked)
                    return losses.mean(), mrrs.mean()

                (loss, mrr), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                params2, opt2 = adamw_update(params, grads, opt, lr=lr)
                return params2, opt2, loss, mrr
            return step

        cfg = self.node_cfg
        etype_id = self.schema.etype_id if self.hetero else None

        @jax.jit
        def step(params, opt, stacked):
            def loss_one(p, batch):
                logits = apply_gnn(cfg, p, batch, etype_id=etype_id)
                return (nc_loss(logits, batch["labels"], batch["seed_mask"]),
                        nc_accuracy(logits, batch["labels"], batch["seed_mask"]))

            def loss_fn(p):
                losses, accs = jax.vmap(lambda b: loss_one(p, b))(stacked)
                return losses.mean(), accs.mean()   # sync SGD: mean over trainers

            (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            params2, opt2 = adamw_update(params, grads, opt, lr=lr)
            return params2, opt2, loss, acc
        return step

    def _stack(self, batches: List[dict]) -> dict:
        """Stack the T trainers' host batches on a leading axis and stage
        them on the device.  Packed staging (DESIGN.md §9) writes them
        with one copy into the trainer's reused host arena and issues ONE
        ``jax.device_put`` for the whole step's input (then a jitted
        static-slice unpack), each part in a ``stage.*`` span; the legacy
        path moves each leaf separately.  Device bytes are identical
        either way."""
        if self.job.packed_staging:
            with Span(self.span_s, "stage.pack"):
                self.staging.fill(batches)
            with Span(self.span_s, "stage.device_put"):
                staged = self.staging.stage()
            self.staged_bytes += staged.total_bytes()
            with Span(self.span_s, "stage.unpack"):
                return staged.unpack()

        def stack_leaf(*xs):
            return jnp.stack([jnp.asarray(x) for x in xs])
        out = jax.tree.map(stack_leaf, *batches)
        self.staged_bytes += sum(x.nbytes for x in jax.tree.leaves(out))
        return out

    # ------------------------------------------------------------------
    def train_epoch(self, epoch: int) -> dict:
        """Train one epoch.  Each step runs in four spans, in order:
        ``trainer.wait_batches`` (the T loaders' next batches),
        ``trainer.stage`` (``model_input()`` and ``_stack``),
        ``trainer.step`` (dispatch of the jitted step) and
        ``trainer.sync`` (``float`` of loss and accuracy), each carrying
        the step's index as ``step``; no span encloses a whole step.  The
        result holds the epoch's ``phase_s`` (host seconds per phase),
        ``staged_bytes`` and ``staging_arena_allocs`` (host arenas
        allocated: 1 in the first epoch, 0 once the arena is reused)."""
        start = 0
        if self._resume is not None:
            r_epoch, r_batch = self._resume
            if epoch != r_epoch:
                raise ValueError(
                    f"recovered at epoch {r_epoch}, batch {r_batch}; the "
                    f"next train_epoch() must target epoch {r_epoch}, "
                    f"got {epoch}")
            self._resume = None
            start = r_batch
        iters = [ld.epoch(epoch, start_batch=start) for ld in self.loaders]
        inj = self.job.fault_injector
        ckpt_every = self.job.checkpoint_interval
        spans = self.span_s
        span_s0, staged0 = dict(spans), self.staged_bytes
        allocs0 = self.staging.allocs
        t0 = time.perf_counter()
        losses, accs = [], []
        for k in range(start, self.batches_per_epoch):
            # checkpoint BEFORE consuming batch k: coordinate (epoch, k)
            # means "everything up to batch k-1 is applied", so recovery
            # resumes AT batch k (skip step 0 — that's the initial state)
            if (ckpt_every and self.global_step
                    and self.global_step % ckpt_every == 0):
                self.save_checkpoint(self.job.checkpoint_dir,
                                     epoch=epoch, batch_index=k)
            # injected trainer death fires at the same boundary, so a
            # killed trainer's last completed step is unambiguous
            if inj is not None:
                inj.check_death(epoch, k)
            with Span(spans, "trainer.wait_batches", step=k):
                items = [next(it) for it in iters]
            with Span(spans, "trainer.stage", step=k):
                stacked = self._stack([b.model_input() for b in items])
            with Span(spans, "trainer.step", step=k):
                self.params, self.opt, loss, acc = self._step(
                    self.params, self.opt, stacked)
            # the step holds its input until it has run; a name kept past
            # it would hold the device copy through the next step's staging
            del stacked
            self.global_step += 1
            with Span(spans, "trainer.sync", step=k):
                losses.append(float(loss))
                accs.append(float(acc))
        # drain every iterator to ITS epoch boundary. With equal
        # per-trainer batch counts (node tasks, homogeneous LP) this pulls
        # nothing in non-stop mode and just exhausts finite pipelines; on
        # the typed LP path per-etype tail-dropping can leave a trainer a
        # few surplus batches, and abandoning those mid-epoch would poison
        # the next epoch with stale-labeled batches (the pre-api trainer
        # silently did exactly that) or force a pipeline rebuild per epoch
        for it in iters:
            for _ in it:
                pass
        dt = time.perf_counter() - t0
        out = {"epoch": epoch, "loss": float(np.mean(losses)),
               "acc": float(np.mean(accs)), "time_s": dt,
               "batches": self.batches_per_epoch - start,
               "phase_s": {p: spans[f"trainer.{p}"]
                           - span_s0.get(f"trainer.{p}", 0.0)
                           for p in PHASES},
               "staged_bytes": self.staged_bytes - staged0,
               "staging_arena_allocs": self.staging.allocs - allocs0}
        if self.task == "link_prediction":
            out["train_mrr"] = out["acc"]   # the step's aux metric is MRR
        return out

    def evaluate_lp(self, num_batches: int = 20, seed: int = 977,
                    num_negs: Optional[int] = None,
                    batch_edges: Optional[int] = None) -> dict:
        """MRR / Hits@k over a deterministic sample of the graph's edges,
        ALWAYS against fresh uniform negatives (the paper's LP eval
        protocol: rank the true destination against corrupted ones),
        regardless of the training ``neg_mode``.

        Eval uses its own candidate count — ``num_negs`` defaults to 49,
        so ranks span [1, 50] and Hits@10 is a real metric (ranking
        against only the training K would saturate it) — and therefore
        its own endpoint capacity / jitted rank program, cached per
        (B, K). Exclusion is off regardless of ``neg_exclude``: the eval
        candidates must not depend on ANY training setting. The whole
        protocol is an ``EdgeDataLoader(mode="eval")`` over every edge:
        deterministic schedule, ad-hoc sampler coordinates, dedicated
        sampler (the trainers' samplers are owned by their pipeline
        threads). As with ``evaluate``, eval feature pulls are charged to
        the shared transport (sampling RPCs are not) — read
        ``sampling_stats()`` before evaluating for pure training traffic.
        """
        assert self.task == "link_prediction", "trainer is not an LP job"
        B = batch_edges or min(self.cfg.batch_size, 16)
        K = num_negs or 49
        eval_cfg = dataclasses.replace(
            self.node_cfg,
            batch_size=EdgeBatchSampler.required_node_batch(B, K, "uniform"))
        g0 = self.graph.trainer_view(0)
        all_eids = np.arange(g0.num_edges(), dtype=np.int64)
        loader = EdgeDataLoader(
            g0, all_eids, eval_cfg.fanouts, batch_size=B, num_negs=K,
            neg_mode="uniform", neg_exclude=False, mode="eval",
            sampler_seed=self.job.seed + 998,
            edge_seed=self.job.seed + seed)
        if self._eval_ranks_fn is None or self._eval_ranks_key != (B, K):
            @jax.jit
            def eval_ranks(params, batch):
                pos, neg = self._lp_scores(params, batch, cfg=eval_cfg)
                return lp_ranks(pos, neg)
            self._eval_ranks_fn = eval_ranks
            self._eval_ranks_key = (B, K)
        ranks: List[np.ndarray] = []
        with loader:
            for batch in itertools.islice(loader, num_batches):
                r = np.asarray(self._eval_ranks_fn(self.params,
                                                   batch.model_input()))
                ranks.append(r[batch.pair_mask])
        if not ranks:   # fewer owned edges than one batch: degenerate eval
            return {"mrr": float("nan"), "num_edges": 0,
                    **{f"hits@{k}": float("nan") for k in (1, 3, 10)}}
        r = np.concatenate(ranks).astype(np.float64)
        out = {"mrr": float((1.0 / r).mean()), "num_edges": int(len(r))}
        for k in (1, 3, 10):
            out[f"hits@{k}"] = float((r <= k).mean())
        return out

    def evaluate(self, nids_old: np.ndarray, max_batches: int = 50) -> float:
        """Node-classification accuracy over ``nids_old`` through a
        ``NodeDataLoader(mode="eval")``: sequential batches, dedicated
        sampler (the trainers' samplers are owned by their possibly still
        running non_stop pipeline threads — sharing one would race the
        RNG and stats)."""
        nids = self.graph.to_new_nids(np.asarray(nids_old))
        g0 = self.graph.trainer_view(0)
        loader = NodeDataLoader(
            g0, nids, self.cfg.fanouts, batch_size=self.cfg.batch_size,
            labels=self.labels_new[nids], mode="eval",
            sampler_seed=self.job.seed + 999)
        accs = []
        with loader:
            for batch in itertools.islice(loader, max_batches):
                logits = apply_gnn(self.cfg, self.params, batch.model_input(),
                                   etype_id=self.schema.etype_id
                                   if self.hetero else None)
                accs.append(float(nc_accuracy(logits,
                                              jnp.asarray(batch.labels),
                                              jnp.asarray(batch.seed_mask))))
        return float(np.mean(accs)) if accs else float("nan")

    # ---- elastic fault tolerance (DESIGN.md §10) ----------------------
    def save_checkpoint(self, directory: str, *, epoch: int,
                        batch_index: int) -> None:
        """Consistent checkpoint at coordinate ``(epoch, batch_index)``:
        dense params + optimizer, every KVStore shard WITH its row-version
        tables, and each trainer's feature-cache snapshot. Coordinates
        name the state BEFORE batch ``batch_index`` is consumed. The
        coordinate file is written atomically LAST, so a crash mid-save
        leaves the previous checkpoint intact rather than a torn one."""
        os.makedirs(directory, exist_ok=True)
        save_pytree(self.params, os.path.join(directory, "params"))
        save_pytree(self.opt, os.path.join(directory, "opt"))
        save_kvstore(self.store, os.path.join(directory, "kvstore"))
        for ti, cache in enumerate(self.caches):
            if cache is not None:
                save_cache(cache, os.path.join(directory, f"cache{ti}"))
        state = {"epoch": int(epoch), "batch_index": int(batch_index),
                 "global_step": int(self.global_step),
                 "seed": int(self.job.seed), "task": self.task,
                 "num_trainers": int(self.num_trainers),
                 "batches_per_epoch": int(self.batches_per_epoch)}
        tmp = os.path.join(directory, "state.json.tmp")
        with open(tmp, "w") as f:
            json.dump(state, f)
        os.replace(tmp, os.path.join(directory, "state.json"))

    def recover(self, directory: str) -> dict:
        """Restore a :meth:`save_checkpoint` into THIS trainer and arm the
        deterministic fast-forward: the next ``train_epoch()`` must target
        the saved epoch and resumes at the saved batch, after which every
        remaining batch — schedules, neighbor draws, negatives — is
        byte-identical to the uninterrupted run's (the counter-based RNG
        keys every draw by (seed, epoch, batch, stream), DESIGN.md §7).
        The world must match the checkpoint (same seed/task/trainer
        count/batch count) — anything else cannot replay byte-exactly and
        raises. Returns the checkpoint's coordinate metadata."""
        with open(os.path.join(directory, "state.json")) as f:
            state = json.load(f)
        mine = {"seed": int(self.job.seed), "task": self.task,
                "num_trainers": int(self.num_trainers),
                "batches_per_epoch": int(self.batches_per_epoch)}
        for key, want in mine.items():
            if state[key] != want:
                raise ValueError(
                    f"checkpoint {key}={state[key]!r} does not match this "
                    f"trainer's {key}={want!r} — deterministic replay "
                    f"needs an identically-configured world")
        # fast-forward needs fresh pipelines: drain whatever is in flight
        self.stop()
        self.params = load_pytree(self.params,
                                  os.path.join(directory, "params"))
        self.opt = load_pytree(self.opt, os.path.join(directory, "opt"))
        # order matters: restoring the shards flushes every live cache and
        # reinstates the version tables the cache snapshots validate
        # against — so a restored cache can never serve stale rows
        load_kvstore(self.store, os.path.join(directory, "kvstore"))
        for ti, cache in enumerate(self.caches):
            cdir = os.path.join(directory, f"cache{ti}")
            if cache is not None and os.path.isdir(cdir):
                load_cache(cache, cdir)
        self.global_step = int(state["global_step"])
        self._resume = (int(state["epoch"]), int(state["batch_index"]))
        return state

    def stop(self):
        for ld in self.loaders:
            ld.close()

    def sampling_stats(self) -> dict:
        remote = sum(s.stats.seeds_remote for s in self.samplers)
        total = sum(s.stats.seeds_total for s in self.samplers)
        owner_req = sum(s.stats.owner_requests for s in self.samplers)
        rel_req = sum(s.stats.relation_requests for s in self.samplers)
        out = {"remote_seed_frac": remote / max(total, 1),
               "transport": self.transport.stats(),
               # request-count accounting (§5.5 batched RPCs): requests the
               # coalesced dispatch actually issued vs what a per-relation
               # dispatch would have issued (equal on untyped runs)
               "sampler_requests": {
                   "owner_requests": owner_req,
                   "relation_requests": rel_req,
                   "coalescing_factor": rel_req / max(owner_req, 1),
               },
               "mean_seed_locality": self.locality["mean_local_frac"],
               "partition_time_s": self.partition_time_s}
        live = [c for c in self.caches if c is not None]
        if live:
            per = [c.stats() for c in live]
            hits = sum(p["hits"] for p in per)
            misses = sum(p["misses"] for p in per)
            out["cache"] = {
                "hit_rate": hits / max(hits + misses, 1),
                "used_bytes": sum(p["used_bytes"] for p in per),
                "evictions": sum(p["evictions"] for p in per),
                "stale_hits": sum(p["stale_hits"] for p in per),
                "per_trainer": per,
            }
        if self.hetero:
            per = sum(s.stats.edges_per_etype for s in self.samplers)
            out["edges_per_etype"] = {
                rel: int(per[r]) for r, rel in enumerate(self.schema.etypes)}
        return out
