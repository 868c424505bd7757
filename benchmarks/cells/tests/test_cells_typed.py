"""The harness on a typed (heterogeneous) cell, end to end on the CPU.

A tiny RGCN cell on a typed graph of 3 node types and 5 relations is
added to a copy of the benchmark by new files and entries only, as a
configuration would be: its configuration, traffic and limits files and
its ``BENCHMARK.json`` entries.  The harness runs it through
``DistGNNTrainer.train_epoch`` and the check.  The program must read
correct; each of the faults planted underneath, and a sampler that puts
two relations' edges in each other's slots, must not.
"""
import json
import os
import sys

import numpy as np
import pytest

import cellkit

sys.path.insert(0, cellkit.CELLS)
sys.path.insert(0, os.path.join(cellkit.REPO, "src"))

import refcheck  # noqa: E402
import world  # noqa: E402

CELL = "tiny-rgcn-train"
LIMITS_OF = "sage-products-train"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    with open(os.path.join(cellkit.CELLS, "limits", LIMITS_OF + ".json")) as f:
        limits = json.load(f)
    return cellkit.tiny_benchmark(str(tmp_path_factory.mktemp("bench")),
                                  limits)


def _run(tiny, trace, seed, fault=""):
    return cellkit.drive(tiny, ["--workload", CELL, "--seed", str(seed),
                                "--seconds", "1", "--trace", str(trace)],
                         fault=fault)


def test_typed_world_by_its_traffic():
    t = cellkit.TINY_TYPED_TRAFFIC
    g = world.make_graph(t)
    assert g.schema["ntypes"] == ("paper", "author", "institution")
    assert g.relations == ["cites", "writes", "rev_writes",
                           "affiliated_with", "rev_affiliated_with"]
    # 2**10 papers; the others by their published ratio to papers
    assert np.bincount(g.ntypes).tolist() == [1024, 1229, 51]
    assert np.all(np.diff(g.ntypes) >= 0)             # types contiguous
    types = {n: i for i, n in enumerate(g.schema["ntypes"])}
    by = {}
    for r, (s_t, _, d_t) in enumerate(g.schema["relations"]):
        e = g.etypes == r
        assert e.any()
        assert np.all(g.ntypes[g.src[e]] == types[s_t])
        assert np.all(g.ntypes[g.dst[e]] == types[d_t])
        assert np.all(g.src[e] != g.dst[e])
        pairs = set(zip(g.src[e].tolist(), g.dst[e].tolist()))
        assert len(pairs) == int(e.sum())              # no duplicates
        by[g.relations[r]] = pairs
    assert by["rev_writes"] == {(v, u) for u, v in by["writes"]}
    assert by["cites"] == {(v, u) for u, v in by["cites"]}
    # labels and the split on papers alone
    papers = g.ntypes == 0
    assert np.all(g.labels[papers] >= 0) and np.all(g.labels[~papers] == -1)
    assert np.all(g.split[~papers] == 0)
    assert (g.split == 1).sum() == int(1024 * t["train_frac"])
    # an author's row is the mean of its papers' rows
    writes = g.etypes == g.relations.index("rev_writes")
    a = g.dst[writes][0]
    mine = g.src[writes][g.dst[writes] == a]
    np.testing.assert_allclose(g.feats[a], g.feats[mine].mean(axis=0),
                               rtol=1e-5, atol=1e-6)
    # fixed by data_seed alone
    again = world.make_graph(dict(t))
    assert np.array_equal(again.src, g.src)
    assert np.array_equal(again.feats, g.feats)
    keys = g.edge_keys()
    assert len(keys) == len(g.src) and np.all(np.diff(keys) > 0)


def test_mag240m_traffic_builds_at_a_small_scale():
    """The committed MAG240M-shaped traffic, read by the typed generator
    at 2**10 papers in place of its own scale."""
    with open(os.path.join(cellkit.CELLS, "traffic", "mag240m-nc.json")) as f:
        t = json.load(f)
    g = world.make_graph(dict(t, scale=10))
    assert g.relations == ["cites", "writes", "rev_writes",
                           "affiliated_with", "rev_affiliated_with"]
    # authors by their published ratio to papers; one institution at least
    assert np.bincount(g.ntypes).tolist() == [1024, 1029, 1]
    assert g.feats.shape == (2054, 768) and g.num_classes == 153
    assert (g.split == 1).sum() == int(1024 * t["train_frac"])
    assert world.as_dataset(g, t["name"]).schema.etypes == tuple(g.relations)


def test_typed_dataset_switches_the_typed_path_on():
    g = world.make_graph(cellkit.TINY_TYPED_TRAFFIC)
    ds = world.as_dataset(g, "typed")
    assert ds.schema.etypes == tuple(g.relations)
    assert ds.graph.num_etypes == 5 and ds.graph.num_ntypes == 3
    cfg = world.model_config(cellkit.TINY_RGCN_CONFIG, g)
    assert cfg.typed and cfg.num_rels == 5
    assert world.arch_config(cellkit.TINY_RGCN_CONFIG, g)["num_rels"] == 5
    assert [tuple(o) for o in cfg.layer_rel_offsets(ds.schema.etype_id)] == (
        world.relation_slots(cfg.batch_size, cfg.fanouts, g.relations))
    with pytest.raises(SystemExit):
        world.model_config(dict(cellkit.TINY_RGCN_CONFIG, fanouts=[3, 2]), g)


@pytest.mark.parametrize("trace", [0, 1])
def test_typed_cell_is_correct(tiny, trace):
    proc = _run(tiny, trace, 2**33 + 101 + trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = cellkit.result_line(proc)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["batch_mismatches"]["value"] == 0
    if trace:
        assert line["metrics"]["step_mfu.train"]["value"] > 0
        assert line["metrics"]["steps_in_window.train"]["value"] == (
            line["attempted"])
    else:
        assert line["metrics"]["train_seeds_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", refcheck.FAULTS)
def test_typed_fault_is_not_correct(tiny, fault):
    line = cellkit.result_line(_run(tiny, 0, 9, fault=fault))
    assert line["correct"] is False, (fault, line["checks"])


def test_relation_slot_swap_is_a_batch_mismatch(tiny):
    line = cellkit.result_line(_run(tiny, 0, 10, fault="slots_swapped"))
    assert line["checks"]["batch_mismatches"]["value"] > 0, line["checks"]
    assert line["correct"] is False


@pytest.fixture(scope="module")
def typed_session(tiny):
    """The set-up of a run of the tiny typed cell, in this process."""
    import run
    s = run.build(world.load_cell(CELL, tiny), 2025)
    return s, run.teardown(s)


def test_typed_control_fails_and_program_passes(typed_session):
    """The reference in bfloat16 in the program's place fails the
    limits; the program passes them; the check of a typed graph's edges
    needs the harness's relation slots."""
    import jax.numpy as jnp
    import run
    s, new2old = typed_session
    limits = s.cell.limits
    program, ref = run.program_numbers(s, new2old)
    control = refcheck.compare(
        run.train_reference(s, new2old, dtype=jnp.bfloat16), ref)
    compared = [k for k in limits if k in control]
    assert any(control[k] > limits[k] for k in compared), control
    assert all(program[k] <= limits[k] for k in compared), program
    assert program["batch_mismatches"] == 0
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError):
        refcheck.batch_mismatches(s.recorder.steps(), s.graph, new2old,
                                  rng)
