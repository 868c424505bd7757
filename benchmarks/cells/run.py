#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 benchmarks/cells/run.py --workload sage-products-train \\
        --seed 1234 --seconds 30 --trace 0

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration (``configs/<name>.json``) trained on a traffic mix
(``traffic/<name>.json``, the graph and its labelled seeds) through the
program's normal path, ``DistGNNTrainer.train_epoch``.  The traffic
file states an R-MAT or a typed graph (``world.py``); a typed cell's
configuration gives each layer's fanouts per relation, and the check
takes each edge slot's relation from the harness's own
``world.relation_slots``.

Set-up (``setup_s``, from process start to the window): the persistent
compilation cache at ``<checkout>/.jax_cache`` (or
``$JAX_COMPILATION_CACHE_DIR``), the graph made from the traffic file,
``DistGNNTrainer`` at the configuration's layout with the job settings a
user gets by default and ``--seed`` as ``TrainJobConfig.seed``, the
weights made on the device from ``--seed`` and handed to the trainer,
then ``train_epoch`` from epoch 0 until the first three steps and one
step after the compile have run.  Those three steps are recorded for the
check.  Then the loaders are stopped, which empties their queues, and
the following epochs run until at least ``SETTLE_STEPS`` steps have come
through the restarted loaders (``settle``), so that the window opens on
the loaders' steady state and not on queues of a size that depends on
how long the compile took.  The window then calls ``train_epoch`` for
the following epochs until ``--seconds`` have passed, and ends on the
boundary of the epoch that crosses it.  ``--trace 1`` records the window with the profiler and
reports the per-layer metrics (``metrics/<name>.py``) in place of the
end-to-end ones.

Once the window has closed, the device's peak memory is read, the
trainer is stopped and freed, and ``refcheck.py`` trains the three
recorded steps with the plain reference; ``correct`` holds where every
compared number is within its limit (``limits/<cell>.json``), and no
step failed or compiled in the window.  Each number is printed with its
limit as the last lines on standard error and under ``checks``, the last
key of the result line.

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import counts  # noqa: E402
import world  # noqa: E402
import xplane  # noqa: E402

REF_STEPS = 3
SETTLE_STEPS = 4
KERNEL = "fused_gather_aggregate"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)


def log(msg: str) -> None:
    """One line of progress on standard error, with the process's peak
    host memory so far."""
    import resource
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    print(f"[cells] {msg} (host peak {rss:.2f} GiB)", file=sys.stderr,
          flush=True)


def require_accelerator(chips: int) -> list:
    """The TPU chips this run may use; exits where there are too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} TPU chip(s); JAX "
                         f"found {len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


class Recorder:
    """Passes the first ``n`` steps through unchanged and keeps what the
    check compares: each trainer's mini-batch as its loader served it
    (``refcheck.served``), each step's loss, the optimizer's state after
    the first step and the parameters after the last."""

    def __init__(self, trainer, n: int, seed: int):
        import numpy as np
        self.trainer, self.n = trainer, n
        self.rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 5])
        self.batches = [[] for _ in trainer.loaders]
        self.losses: list = []
        self.mu1 = self.params = None
        self._step = trainer._step
        trainer._step = self._record_step
        for i, ld in enumerate(trainer.loaders):
            ld.epoch = self._record_epoch(i, ld.epoch)

    def _record_epoch(self, i: int, serve):
        from refcheck import served

        def epoch(*args, **kwargs):
            for item in serve(*args, **kwargs):
                if len(self.batches[i]) < self.n:
                    self.batches[i].append(served(item.minibatch, self.rng))
                yield item
        return epoch

    def _record_step(self, params, opt, stacked):
        import jax
        out = self._step(params, opt, stacked)
        k = len(self.losses)
        if k < self.n:
            self.losses.append(float(out[2]))
            if k == 0:
                self.mu1 = jax.device_get(out[1].mu)
            if k == self.n - 1:
                self.params = jax.device_get(out[0])
        return out

    def steps(self) -> list:
        """Per step, the trainers' mini-batches."""
        return [list(b) for b in zip(*self.batches)]

    def close(self) -> None:
        self.trainer._step = self._step
        for ld in self.trainer.loaders:
            del ld.epoch


class EdgeCounter:
    """Counts the live edges of every layer of every mini-batch the
    loaders serve while it is open (the traced window only); on a typed
    cell, per layer, ``{relation: live edges}`` by the relation slots."""

    def __init__(self, trainer, num_layers: int,
                 slots: Optional[list] = None,
                 relations: Optional[list] = None):
        self.trainer = trainer
        self.slots, self.relations = slots, relations
        self.live = ([0] * num_layers if slots is None else
                     [dict.fromkeys(relations, 0) for _ in range(num_layers)])
        for ld in trainer.loaders:
            ld.epoch = self._count(ld.epoch)

    def _count(self, serve):
        import numpy as np

        def epoch(*args, **kwargs):
            for item in serve(*args, **kwargs):
                for l, b in enumerate(item.minibatch.blocks):
                    if self.slots is None:
                        self.live[l] += int(np.count_nonzero(b.edge_mask))
                        continue
                    offs = self.slots[l]
                    for r, rel in enumerate(self.relations):
                        self.live[l][rel] += int(np.count_nonzero(
                            b.edge_mask[offs[r]:offs[r + 1]]))
                yield item
        return epoch

    def close(self) -> None:
        for ld in self.trainer.loaders:
            del ld.epoch


@dataclasses.dataclass
class Session:
    cell: world.Cell
    seed: int
    graph: world.Graph
    trainer: object
    arch: object
    conf: dict              # the configuration as the reference reads it
    caps: list
    slots: Optional[list]   # a typed cell's relation slots, else None
    params0: object
    recorder: Recorder
    next_epoch: int
    compiles: list


@dataclasses.dataclass
class Window:
    """What the per-layer metric readers read."""
    steps: int
    seconds: float
    seeds: int
    failed: int
    compiles: int
    stages: tuple           # (before, after): per loader, its stage stats
    transport: tuple        # (before, after): the transport's counters
    trace: Optional[dict]
    flops_per_step: float
    gather_least_s: Optional[float]
    peak: dict


def use_cache() -> str:
    import jax
    from repro.launch.compile_cache import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def count_compiles() -> list:
    import jax
    n = [0]

    def listener(event: str, duration: float, **kwargs) -> None:
        if event in COMPILE_EVENTS:
            n[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listener)
    return n


def build(cell: world.Cell, seed: int,
          graph: Optional[world.Graph] = None) -> Session:
    """Everything up to the window (see the module's docstring)."""
    import jax
    from repro.api import DistGNNTrainer
    from refcheck import arch_module, weights

    compiles = count_compiles()
    t = time.perf_counter()
    if graph is None:
        graph = world.make_graph(cell.traffic)
    ds = world.as_dataset(graph, cell.name)
    log(f"graph: {graph.num_nodes} nodes, {len(graph.src)} edges, "
        f"{time.perf_counter() - t:.3f} s")
    cfg = world.model_config(cell.config, graph)
    t = time.perf_counter()
    tr = DistGNNTrainer(ds, cfg, world.job_config(cell.config, seed))
    log(f"trainer: {tr.num_trainers} trainers, {tr.batches_per_epoch} "
        f"batches/epoch, {time.perf_counter() - t:.3f} s")
    arch = arch_module(cell.config["arch"])
    conf = world.arch_config(cell.config, graph)
    p0 = weights(arch, conf, cfg.in_dim, cfg.num_classes, seed)
    if (jax.tree.structure(p0) != jax.tree.structure(tr.params)
            or any(a.shape != b.shape for a, b in zip(
                jax.tree.leaves(p0), jax.tree.leaves(tr.params)))):
        raise SystemExit("the reference's parameters are not laid out as "
                         "the trainer's")
    tr.params = p0
    params0 = jax.device_get(p0)
    rec = Recorder(tr, REF_STEPS, seed)
    t = time.perf_counter()
    epoch = steps = 0
    try:
        while len(rec.losses) < REF_STEPS or steps < 2:
            steps += tr.train_epoch(epoch)["batches"]
            epoch += 1
    finally:
        rec.close()
    log(f"warm-up: {steps} steps in {epoch} epochs, "
        f"{time.perf_counter() - t:.3f} s, {compiles[0]} programs built")
    t = time.perf_counter()
    epoch, steps = settle(tr, epoch)
    log(f"settle: {steps} steps, {time.perf_counter() - t:.3f} s")
    slots = (None if graph.schema is None else world.relation_slots(
        cfg.batch_size, cfg.fanouts, graph.relations))
    return Session(cell=cell, seed=seed, graph=graph, trainer=tr, arch=arch,
                   conf=conf,
                   caps=world.capacities(cfg.batch_size, cfg.fanouts),
                   slots=slots, params0=params0, recorder=rec,
                   next_epoch=epoch, compiles=compiles)


def settle(tr, epoch: int) -> tuple:
    """Bring the loaders to the state of a long job before the window.

    The loaders fill their queues while the warm-up compiles, and by how
    much depends on how long that took; a window that opened on full
    queues would read their drain.  So every loader is stopped, which
    drops what it holds, and the trainer runs on from empty queues for at
    least ``SETTLE_STEPS`` steps, whole epochs, past the first batches'
    latency.  Returns the next epoch and the steps run."""
    for ld in tr.loaders:
        ld.stop()
    steps = 0
    while steps < SETTLE_STEPS:
        steps += tr.train_epoch(epoch)["batches"]
        epoch += 1
    return epoch, steps


def _snapshot(tr) -> tuple:
    return ([ld.stats_report()["stages"] for ld in tr.loaders],
            dict(tr.sampling_stats()["transport"]))


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    return opts


def measure(s: Session, seconds: float, trace: bool) -> Window:
    """The window: ``train_epoch`` until ``seconds`` have passed."""
    import jax
    tr = s.trainer
    stages0, transport0 = _snapshot(tr)
    counter = (EdgeCounter(tr, len(s.caps), s.slots, s.graph.relations)
               if trace else None)
    tdir = tempfile.mkdtemp(prefix="cells-trace-") if trace else None
    compiles0 = s.compiles[0]
    steps = failed = epochs = 0
    try:
        if trace:
            jax.profiler.start_trace(tdir,
                                     profiler_options=_profile_options())
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation(xplane.EPOCH_SPAN):
                    out = tr.train_epoch(s.next_epoch)
                s.next_epoch += 1
                epochs += 1
                steps += out["batches"]
                if not math.isfinite(out["loss"]):
                    failed += out["batches"]
                if time.perf_counter() - t0 >= seconds:
                    break
            t1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
    finally:
        if counter is not None:
            counter.close()
    stages1, transport1 = _snapshot(tr)
    cfg = tr.node_cfg
    peak = counts.peaks(jax.devices()[0].device_kind) if trace else {}
    summary = least = None
    if trace:
        files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        summary = xplane.summarize(xplane.load_xplane(files[0]), [KERNEL])
        shutil.rmtree(tdir, ignore_errors=True)
        calls = s.arch.gather_calls(s.conf, cfg.in_dim,
                                    cfg.num_classes, s.caps, counter.live)
        least = counts.least_seconds(calls, peak)
    flops = tr.num_trainers * s.arch.flops(s.conf, cfg.in_dim,
                                           cfg.num_classes, s.caps)
    seeds = epochs * sum(min(len(x), tr.batches_per_epoch * cfg.batch_size)
                         for x in tr.trainer_seeds)
    return Window(steps=steps, seconds=t1 - t0, seeds=seeds, failed=failed,
                  compiles=s.compiles[0] - compiles0,
                  stages=(stages0, stages1), transport=(transport0,
                                                        transport1),
                  trace=summary, flops_per_step=flops, gather_least_s=least,
                  peak=peak)


def teardown(s: Session):
    """Stop the trainer and free the program's state; returns the node
    id map the batch check needs."""
    new2old = s.trainer.graph.book.new2old_node.copy()
    s.trainer.stop()
    s.trainer = None
    gc.collect()
    return new2old


def program_readings(s: Session) -> dict:
    import jax
    import numpy as np
    from refcheck import BETA1
    rec = s.recorder
    return {"losses": rec.losses,
            "grad": jax.tree.map(lambda m: np.asarray(m) / (1 - BETA1),
                                 rec.mu1),
            "delta": jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                                  rec.params, s.params0)}


def train_reference(s: Session, new2old, **kw) -> dict:
    """The reference (or, with ``dtype`` or ``fault``, what stands in
    the program's place) over the recorded steps."""
    from refcheck import Reference
    return Reference(s.arch, s.conf, s.caps, float(s.conf["lr"]),
                     slots=s.slots, **kw).train(
        s.params0, s.recorder.steps(), s.graph.feats[new2old],
        s.graph.num_classes)


def program_numbers(s: Session, new2old) -> tuple:
    """The program against the reference; and the reference."""
    import numpy as np
    from refcheck import batch_mismatches, compare
    ref = train_reference(s, new2old)
    numbers = compare(program_readings(s), ref)
    rng = np.random.default_rng([s.seed & 0xFFFFFFFF, s.seed >> 32, 7])
    numbers["batch_mismatches"] = batch_mismatches(
        s.recorder.steps(), s.graph, new2old, rng, slots=s.slots)
    return numbers, ref


def verdict(limits: dict, numbers: dict) -> dict:
    """Each number that ``limits`` names beside its limit, and
    ``correct``: every one of them finite and within its limit."""
    missing = set(limits) - set(numbers)
    if missing:
        raise SystemExit(f"limits for numbers never read: {sorted(missing)}")
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in limits.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    return {"correct": correct, "checks": checks}


def check(s: Session, new2old, failed: int, compiles: int) -> dict:
    """Every compared number, and ``correct``."""
    numbers, _ = program_numbers(s, new2old)
    numbers["failed_steps"] = failed
    numbers["window_compiles"] = compiles
    return verdict(s.cell.limits, numbers)


def end_to_end(cell: world.Cell, w: Window, setup_s: float) -> dict:
    values = {"train_seeds_per_s": w.seeds / w.seconds, "setup_s": setup_s}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def reader(cell: world.Cell, name: str):
    """The reader of the per-layer metric ``name``:
    ``metrics/<name>.py``, whose ``read(window)`` returns its value, or
    None where the window holds nothing to read it from."""
    path = os.path.join(cell.dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "cells_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(cell: world.Cell, w: Window) -> dict:
    out = {}
    for m in cell.per_layer:
        v = reader(cell, m["name"])(w)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = world.load_cell(args.workload)
    src = os.path.join(world.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    cache = use_cache()
    devs = require_accelerator(cell.chips)
    log(f"device {devs[0].device_kind} x{len(devs)}, cache {cache}")

    s = build(cell, args.seed)
    setup_s = time.perf_counter() - T_START
    log(f"setup_s {setup_s:.3f}")
    w = measure(s, args.seconds, bool(args.trace))
    log(f"window: {w.steps} steps, {w.seconds:.3f} s, {w.compiles} compiles")
    stats = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    new2old = teardown(s)
    t = time.perf_counter()
    result = check(s, new2old, w.failed, w.compiles)
    log(f"check: {time.perf_counter() - t:.3f} s")
    line = {"correct": result["correct"], "attempted": w.steps,
            "failed": w.failed}
    if args.trace:
        line["metrics"] = per_layer(cell, w)
        if w.trace is not None:
            device["busy_s"] = w.trace["busy_s"]
            device["window_s"] = w.trace["window_s"]
            line["breakdown"] = {"device_ops": w.trace["device_ops"],
                                 "idle_gaps": w.trace["idle_gaps"]}
    else:
        line["metrics"] = end_to_end(cell, w, setup_s)
    line["device"] = device
    line["checks"] = result["checks"]
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
