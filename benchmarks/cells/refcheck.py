"""The comparison that decides ``correct`` for a training cell.

The reference takes the mini-batches the program's loaders served in its
first steps (their node ids and padded blocks; the feature rows it looks
up again in the graph the benchmark made, so that neither the program's
feature pull nor its staging feeds it) and the weights the benchmark
made, and trains the same steps itself: the configuration's model in
plain ``jax.numpy`` (``models/<arch>.py``) in float32, the masked mean
cross-entropy over each trainer's seeds, the mean over the trainers, and
AdamW.  Its sums over edges are float32 and its matmuls run at the
precision the configuration states (``matmul_precision``: JAX's
default, one bfloat16 pass on a TPU), as the program's dense layers do:
against a reference at ``highest`` precision the program's own matmul
rounding reads as large as a bfloat16 control's, so no limit could part
them.  It imports nothing of the program.  It runs one trainer's batch
at a time, so that it fits beside nothing else once the program's state
is freed.

The numbers that compare the program with it (:func:`compare`; each
cell's ``limits/<cell>.json`` names those it holds to a limit):

* ``loss_gap``: the largest relative gap of a step's loss;
* ``grad_gap``: the gradient of the first step as the optimizer got it
  (its first moment after one step, over ``1 - beta1``), by the worst
  leaf: the gap between the two norms of that leaf, over the reference's
  norm of the leaf or of the median leaf, whichever is larger;
* ``delta_gap``: the same for the change of the parameters over the
  steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone);
* ``loss1_gap``, ``grad_gap_median``, ``delta_gap_median``: the first
  step's loss alone, and the median leaf in place of the worst.

``batch_mismatches`` is exact: every recorded batch must be
made of the graph the benchmark made: sampled feature rows as the
loader served them, labels, sampled edges, and each block's
destinations the next block's sources.

On a typed cell the relation of every edge slot is the harness's own
(``world.relation_slots``, from the configuration's fanouts and the
traffic's relations), never the ``edge_types`` the program served: the
reference aggregates each relation over the slots the harness gives it,
and a sampled edge counts as the graph's only as ``(relation of its
slot, u, v)``, so an edge in another relation's slots is a mismatch.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import sys
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BETA1, BETA2, EPS = 0.9, 0.999, 1e-8
TINY_LEAF = 1e-3          # of the median leaf's reference gradient norm
FAULTS = ("state_unchanged", "half_batch", "one_trainer", "labels_shifted")


def arch_module(arch: str):
    """``models/<arch>.py``, found by the configuration's ``arch``."""
    models = os.path.join(HERE, "models")
    if models not in sys.path:
        sys.path.insert(0, models)
    spec = importlib.util.spec_from_file_location(
        f"cells_model_{arch}", os.path.join(models, arch + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def weights(arch, cfg: dict, in_dim: int, num_classes: int, seed: int):
    """The run's initial weights, made on the device in one jitted call
    from ``--seed`` (all 64 bits of it)."""
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return jax.jit(lambda k: arch.init(cfg, in_dim, num_classes, k))(key)


@dataclasses.dataclass
class Served:
    """What the check keeps of one mini-batch a loader served: its node
    ids, labels and padded blocks, and a sample of its feature rows (the
    rows themselves are the graph's, and are looked up again by id)."""
    input_gids: np.ndarray
    seeds: np.ndarray
    seed_mask: np.ndarray
    labels: np.ndarray
    blocks: list              # per layer: src_gids, num_src, edges
    rows: np.ndarray          # sampled positions in input_feats
    feats_at_rows: np.ndarray


def served(mb, rng: np.random.Generator, rows: int = 4096) -> Served:
    at = rng.integers(0, len(mb.input_gids), rows)
    return Served(
        input_gids=mb.input_gids, seeds=mb.seeds, seed_mask=mb.seed_mask,
        labels=mb.labels, rows=at, feats_at_rows=mb.input_feats[at].copy(),
        blocks=[{"src_gids": b.src_gids, "num_src": b.num_src,
                 "edge_src": b.edge_src, "edge_dst": b.edge_dst,
                 "edge_mask": b.edge_mask} for b in mb.blocks])


def slot_relations(slots: list) -> list:
    """Per layer, the relation id of every edge slot by the static
    offsets of ``world.relation_slots``."""
    return [np.repeat(np.arange(len(offs) - 1, dtype=np.int32),
                      np.diff(offs)) for offs in slots]


def model_batch(s: Served, feats: np.ndarray,
                slots: Optional[list] = None) -> dict:
    """The model's input for a served mini-batch, its feature rows taken
    from ``feats``, the graph's features in the program's node order; on
    a typed cell each block has ``edge_rel``, the relation of every edge
    slot by ``slots``."""
    blocks = [{k: b[k] for k in ("edge_src", "edge_dst", "edge_mask")}
              for b in s.blocks]
    if slots is not None:
        for b, rel in zip(blocks, slot_relations(slots)):
            b["edge_rel"] = rel
    return {"input_feats": feats[s.input_gids], "labels": s.labels,
            "seed_mask": s.seed_mask, "blocks": blocks}


def _loss(arch, cfg, caps, dtype, params, batch):
    logits = arch.forward(cfg, params, batch, caps, dtype).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0]
    m = batch["seed_mask"].astype(jnp.float32)
    return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)


def adamw(params, grads, state, lr: float):
    """AdamW without weight decay, moments in float32."""
    step, mu, nu = state
    step += 1
    mu = jax.tree.map(lambda m, g: BETA1 * m + (1 - BETA1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: BETA2 * v + (1 - BETA2) * g * g, nu, grads)
    bc1, bc2 = 1 - BETA1 ** step, 1 - BETA2 ** step
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + EPS),
        params, mu, nu)
    return params, (step, mu, nu)


class Reference:
    """Trains recorded steps from given weights, trainer by trainer.

    ``dtype`` float32, with matmuls at the configuration's
    ``matmul_precision``, is the reference; bfloat16 is the control.
    ``precision`` overrides the matmul precision.  ``fault`` plants one
    of :data:`FAULTS` in it, so that what each fault does to the
    compared numbers can be read.  ``slots``: a typed cell's relation
    slots (:func:`model_batch`)."""

    def __init__(self, arch, cfg: dict, caps: list, lr: float,
                 dtype=jnp.float32, fault: Optional[str] = None,
                 precision: Optional[str] = None,
                 slots: Optional[list] = None):
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
        self.lr, self.fault, self.dtype = lr, fault, dtype
        self.slots = slots
        self.precision = precision or cfg["matmul_precision"]
        self._grad = jax.jit(jax.value_and_grad(
            lambda p, b: _loss(arch, cfg, caps, dtype, p, b)))

    def _trainer_batch(self, s: Served, t: int) -> dict:
        b = model_batch(s, self.feats, self.slots)
        if self.fault == "half_batch":
            mask = np.array(b["seed_mask"])
            mask[len(mask) // 2:] = False
            b["seed_mask"] = mask
        elif self.fault == "labels_shifted" and t == 0:
            b["labels"] = (b["labels"] + 1) % self.num_classes
        return b

    def step(self, params, batches: list):
        """Mean loss and mean gradient over the trainers' batches."""
        if self.fault == "one_trainer":
            batches = batches[:1]
        loss, grads = 0.0, None
        with jax.default_matmul_precision(self.precision):
            for t, mb in enumerate(batches):
                l, g = self._grad(params, self._trainer_batch(mb, t))
                loss += float(l)
                grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        n = len(batches)
        return loss / n, jax.tree.map(lambda g: g / n, grads)

    def train(self, params0, steps: list, feats: np.ndarray,
              num_classes: int) -> dict:
        """``steps``: per step, the trainers' :class:`Served` batches;
        ``feats``: the graph's features in the program's node order.
        Returns each step's loss, the first gradient and the change of
        the parameters over all the steps (host arrays)."""
        self.feats, self.num_classes = feats, num_classes
        params = jax.tree.map(jnp.asarray, params0)
        zeros = jax.tree.map(jnp.zeros_like, params)
        state = (0, zeros, zeros)
        losses, g1 = [], None
        for batches in steps:
            loss, grads = self.step(params, batches)
            losses.append(loss)
            if g1 is None:
                g1 = jax.device_get(grads)
            if self.fault != "state_unchanged":
                params, state = adamw(params, grads, state, self.lr)
        delta = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                             jax.device_get(params), jax.device_get(params0))
        return {"losses": losses, "grad": g1, "delta": delta}


def _leaf_norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in jax.tree.leaves(tree)])


def leaf_gaps(got, ref, keep: Optional[np.ndarray] = None) -> np.ndarray:
    """Per leaf that ``keep`` marks, ``| |got_leaf| - |ref_leaf| |`` over
    ``max(|ref_leaf|, median |ref_leaf|)``."""
    g, r = _leaf_norms(got), _leaf_norms(ref)
    if keep is None:
        keep = np.ones(len(r), bool)
    floor = np.median(r[keep])
    return np.abs(g - r)[keep] / np.maximum(r[keep], floor)


def compare(got: dict, ref: dict) -> dict:
    """The numbers for one run, ``got`` and ``ref`` as
    :meth:`Reference.train` returns them: the three above; and the first
    step's loss gap alone (``loss1_gap``) and the median leaf's gradient
    and change gaps (``grad_gap_median``, ``delta_gap_median``), steady
    where one small leaf or the steps after the first swing the worst
    case.  ``limits/<cell>.json`` names the ones a cell compares."""
    lg = (np.abs(np.array(got["losses"]) - np.array(ref["losses"]))
          / np.abs(ref["losses"]))
    gnorm = _leaf_norms(ref["grad"])
    keep = gnorm >= TINY_LEAF * np.median(gnorm)
    delta = leaf_gaps(got["delta"], ref["delta"], keep)
    grad = leaf_gaps(got["grad"], ref["grad"])
    return {"loss_gap": float(np.max(lg)), "loss1_gap": float(lg[0]),
            "grad_gap": float(np.max(grad)),
            "grad_gap_median": float(np.median(grad)),
            "delta_gap": float(np.max(delta)),
            "delta_gap_median": float(np.median(delta))}


def batch_mismatches(steps: list, g, new2old: np.ndarray,
                     rng: np.random.Generator, rows: int = 4096,
                     slots: Optional[list] = None) -> int:
    """Rows of the served mini-batches that are not rows of the graph
    ``g`` (a ``world.Graph``): the sampled feature rows, every live
    seed's label, ``rows`` live edges a block drawn from ``rng``, and
    every block's destinations against the next block's sources.
    ``new2old`` maps the program's node ids to ``g``'s.  On a typed
    graph an edge is ``(relation of its slot by slots, u, v)``."""
    n = g.num_nodes
    if not np.array_equal(np.sort(new2old), np.arange(n)):
        return n
    keys = g.edge_keys()
    rels = None
    if g.etypes is not None:
        if slots is None:
            raise ValueError("a typed graph's edges are checked by the "
                             "relation of their slots")
        rels = slot_relations(slots)
    bad = 0
    for batches in steps:
        for s in batches:
            want = g.feats[new2old[s.input_gids[s.rows]]]
            bad += int(np.any(s.feats_at_rows != want, axis=1).sum())
            live = np.nonzero(s.seed_mask)[0]
            bad += int((s.labels[live]
                        != g.labels[new2old[s.seeds[live]]]).sum())
            for l, b in enumerate(s.blocks):
                e = np.nonzero(b["edge_mask"])[0]
                e = e[rng.integers(0, len(e), rows)] if len(e) else e
                u = new2old[b["src_gids"][b["edge_src"][e]]]
                v = new2old[b["src_gids"][b["edge_dst"][e]]]
                key = u * n + v
                if rels is not None:
                    key = (rels[l][e].astype(np.int64) * n + u) * n + v
                pos = np.searchsorted(keys, key)
                hit = keys[np.minimum(pos, len(keys) - 1)] == key
                bad += int((~hit).sum())
                if l + 1 < len(s.blocks):
                    k = s.blocks[l + 1]["num_src"]
                    bad += int((s.blocks[l + 1]["src_gids"][:k]
                                != b["src_gids"][:k]).sum())
            last = s.blocks[-1]["src_gids"]
            bad += int((s.seeds[live] != last[live]).sum())
    return bad
