"""The training step's reused host arena (DESIGN.md §9): one arena
allocated in the first epoch and none after, step k's device input left
bit-unchanged by step k+1's fill, and two epochs that train exactly as
the staging it replaced (``np.stack``, then ``pack`` into a fresh
arena)."""
import jax
import numpy as np
import pytest

from repro.graph import get_dataset
from repro.kernels.pack import PackedBatch, pack, stage_arena
from repro.models.gnn import GNNConfig
from repro.training.trainer import DistGNNTrainer, TrainJobConfig

EPOCHS = 2


def _trainer():
    ds = get_dataset("product-sim", scale=10)
    cfg = GNNConfig(arch="graphsage", in_dim=ds.feats.shape[1],
                    hidden_dim=16, num_classes=ds.num_classes,
                    fanouts=[3, 3], batch_size=24)
    return DistGNNTrainer(ds, cfg, TrainJobConfig(num_machines=2,
                                                  trainers_per_machine=1))


def _stack_then_pack(batches):
    """The staging before the reused arena: ``np.stack``, ``pack`` into a
    fresh arena, one transfer, unpack."""
    spec, arena = pack(jax.tree.map(lambda *xs: np.stack(xs), *batches))
    return PackedBatch(spec, stage_arena(arena)).unpack()


def _bytes(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


def _train(restage=None):
    tr = _trainer()
    if restage is not None:
        tr._stack = restage
    try:
        epochs = [tr.train_epoch(e) for e in range(EPOCHS)]
    finally:
        tr.stop()
    return epochs, _bytes(tr.params)


@pytest.fixture(scope="module")
def trained():
    return _train(), _train(_stack_then_pack)


def test_one_arena_in_the_first_epoch_none_after(trained):
    (epochs, _), _ = trained
    assert [e["staging_arena_allocs"] for e in epochs] == [1, 0]
    assert all(e["staged_bytes"] > 0 for e in epochs)


def test_two_epochs_train_as_the_staging_they_replace(trained):
    (epochs, params), (ref_epochs, ref_params) = trained
    for e, r in zip(epochs, ref_epochs):
        assert (e["loss"], e["acc"]) == (r["loss"], r["acc"])
    assert params == ref_params


def test_next_fill_leaves_the_staged_step_unchanged():
    """The alias hazard: on the CPU backend a dlpack import (or
    ``device_put`` of an aligned buffer) would make the staged input a
    view of the host arena, which the next step's fill overwrites."""
    tr = _trainer()
    try:
        iters = [ld.epoch(0) for ld in tr.loaders]
        steps = [[next(it).model_input() for it in iters] for _ in range(2)]
    finally:
        tr.stop()
    staged, stage = [], tr.staging.stage

    def keep():
        staged.append(stage())
        return staged[-1]
    tr.staging.stage = keep
    want = _bytes(_stack_then_pack(steps[0]))
    got = tr._stack(steps[0])
    tr._stack(steps[1])
    assert tr.staging.allocs == 1
    assert _bytes(got) == want
    assert (np.asarray(staged[0].buffers).tobytes()
            == pack(jax.tree.map(lambda *xs: np.stack(xs),
                                 *steps[0]))[1].tobytes())
