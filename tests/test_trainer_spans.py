"""The training loop's spans and counters, read back from a profiler trace
on the CPU: each step's four phase spans in order on the training thread,
the ``stage.*`` children inside ``trainer.stage``, exact event names with
the step index as metadata, the epoch's ``phase_s``, ``staged_bytes``
and ``staging_arena_allocs``, and a step's staged input released before
the next step stages."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.graph import get_dataset
from repro.kernels.pack import pack
from repro.models.gnn import GNNConfig
from repro.training.trainer import PHASES, DistGNNTrainer, TrainJobConfig

PHASE_SPANS = tuple(f"trainer.{p}" for p in PHASES)
STAGE_CHILDREN = ("stage.pack", "stage.device_put", "stage.unpack")
STEPS = 2


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    ds = get_dataset("product-sim", scale=10)
    cfg = GNNConfig(arch="graphsage", in_dim=ds.feats.shape[1],
                    hidden_dim=16, num_classes=ds.num_classes,
                    fanouts=[3, 3], batch_size=24)
    tr = DistGNNTrainer(ds, cfg, TrainJobConfig(num_machines=2,
                                                trainers_per_machine=1))
    assert tr.batches_per_epoch == STEPS
    seen, live = [], []
    stack = tr._stack

    def spy(batches):
        seen.append(batches)
        live.append(sum(a.nbytes for a in jax.live_arrays()))
        return stack(batches)
    tr._stack = spy
    tdir = str(tmp_path_factory.mktemp("trace"))
    try:
        with jax.profiler.trace(tdir):
            out = tr.train_epoch(0)
    finally:
        tr.stop()
    path, = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    events = []     # (name, start_ns, end_ns, thread, metadata)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(("trainer.", "stage.")):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   (plane.name, i), dict(ev.stats)))
    return out, sorted(events, key=lambda e: e[1]), seen, live


def test_phase_spans_once_a_step_in_order_on_one_thread(traced):
    _, events, _, _ = traced
    phases = [e for e in events if e[0] in PHASE_SPANS]
    assert [e[0] for e in phases] == list(PHASE_SPANS) * STEPS
    assert [e[4].get("step") for e in phases] == [
        k for k in range(STEPS) for _ in PHASES]
    assert len({e[3] for e in phases}) == 1
    for a, b in zip(phases, phases[1:]):
        assert a[1] <= a[2] <= b[1], (a, b)


def test_stage_children_inside_stage(traced):
    _, events, _, _ = traced
    stages = [e for e in events if e[0] == "trainer.stage"]
    children = [e for e in events if e[0].startswith("stage.")]
    assert sorted(e[0] for e in children) == sorted(STAGE_CHILDREN * STEPS)
    for child in children:
        assert any(s[1] <= child[1] and child[2] <= s[2] and s[3] == child[3]
                   for s in stages), child


def test_event_names_are_exact(traced):
    _, events, _, _ = traced
    names = {e[0] for e in events}
    assert names == set(PHASE_SPANS + STAGE_CHILDREN)
    # the T batches are written straight into the arena: nothing stacks
    assert "stage.stack" not in names


def test_phase_seconds_and_staged_bytes(traced):
    out, _, seen, _ = traced
    assert out["batches"] == STEPS
    assert set(out["phase_s"]) == set(PHASES)
    assert all(v >= 0 for v in out["phase_s"].values())
    assert sum(out["phase_s"].values()) <= out["time_s"]
    assert len(seen) == STEPS
    spec, arena = pack(jax.tree.map(lambda *xs: np.stack(xs), *seen[0]))
    assert out["staged_bytes"] == STEPS * spec.total_bytes()
    assert spec.total_bytes() <= arena.nbytes
    assert out["staging_arena_allocs"] == 1


def test_step_input_freed_before_the_next_step_stages(traced):
    """The device copy of a step's input is gone by the time the next
    step stages its own: a span boundary must not keep it alive."""
    out, _, _, live = traced
    per_step = out["staged_bytes"] // STEPS
    assert len(live) == STEPS
    assert live[1] - live[0] < per_step // 2, (live, per_step)
