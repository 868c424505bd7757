"""The benchmark's arithmetic, on the CPU: capacities, FLOP and byte
counts against hand counts at the paper's GraphSAGE and RGCN widths,
relation slots, the R-MAT world pinned to its digests, the RGCN
reference against the program's forward, the reduction of a profiler
trace to busy time, kernel time and idle gaps, and the control: the
reference in bfloat16, put in the program's place, must fail the
comparison that decides ``correct``."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

import cellkit

sys.path.insert(0, cellkit.CELLS)
sys.path.insert(0, os.path.join(cellkit.REPO, "src"))

import counts  # noqa: E402
import refcheck  # noqa: E402
import world  # noqa: E402
import xplane  # noqa: E402

SAGE = {"arch": "graphsage", "hidden_dim": 256, "fanouts": [15, 10, 5],
        "batch_size": 1000}
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_capacities_at_paper_widths():
    assert world.capacities(1000, [15, 10, 5]) == [
        (66_000, 990_000, 1_056_000), (6_000, 60_000, 66_000),
        (1_000, 5_000, 6_000)]


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_rmat_world_is_pinned():
    """The R-MAT graph and the program's input object made from it read
    as they did before the harness learnt typed graphs."""
    g = world.make_graph(cellkit.TINY_TRAFFIC)
    assert _digest(g.src, g.dst, g.feats, g.labels, g.split) == (
        "5a9e15d7d314ea72d1b4b93e92c79297e95ba3f14a97a360b976c2f210eebb09")
    assert g.ntypes is None and g.etypes is None and g.schema is None
    ds = world.as_dataset(g, "tiny")
    c = ds.graph
    assert _digest(c.indptr, c.indices, c.edge_ids, ds.feats, ds.labels,
                   ds.split_mask) == (
        "e750d6784be567f0930e3f63a4b75967a1be5a505527d959475c1715350cc7e0")
    assert (c.etypes, c.ntypes, c.num_etypes, c.num_ntypes, ds.schema,
            ds.num_classes) == (None, None, 1, 1, None, 5)


@pytest.mark.parametrize("name", ["graphsage", "gat"])
def test_rmat_model_config_is_pinned(name):
    from repro.models.gnn import GNNConfig
    with open(os.path.join(cellkit.CELLS, "configs", name + ".json")) as f:
        config = json.load(f)
    g = world.make_graph(cellkit.TINY_TRAFFIC)
    assert world.model_config(config, g) == GNNConfig(
        arch=name, in_dim=12, hidden_dim=256, num_classes=5,
        fanouts=[15, 10, 5], batch_size=1000,
        num_heads=2 if name == "gat" else 1)
    assert world.arch_config(config, g) is config


def test_graphsage_flops_by_hand():
    caps = world.capacities(1000, [15, 10, 5])
    arch = refcheck.arch_module("graphsage")
    # layer 0: two 100->256 matmuls over 66,000 rows, forward and weight
    # gradient (no input gradient: the input is data), and one addition
    # per element of the 990,000 edges' 100-wide rows
    l0 = 2 * (2 * 2 * 66_000 * 100 * 256) + 990_000 * 100
    # layer 1: 256->256 over 6,000 rows, forward, weight and input
    # gradients; the edge sums forward and backward
    l1 = 3 * (2 * 2 * 6_000 * 256 * 256) + 2 * 60_000 * 256
    l2 = 3 * (2 * 2 * 1_000 * 256 * 47) + 2 * 5_000 * 256
    assert l0 + l1 + l2 == 18_512_056_000
    assert arch.flops(SAGE, 100, 47, caps) == l0 + l1 + l2


def test_graphsage_gather_bytes_by_hand():
    caps = world.capacities(1000, [15, 10, 5])
    arch = refcheck.arch_module("graphsage")
    calls = arch.gather_calls(SAGE, 100, 47, caps, [990_000, 60_000, 5_000])
    # forward of layers 0-2, backward of layers 1-2
    assert [(c["width"], c["edges"], c["out_rows"]) for c in calls] == [
        (100, 990_000, 66_000), (256, 60_000, 6_000),
        (256, 60_000, 66_000), (256, 5_000, 1_000), (256, 5_000, 6_000)]
    # layer 0 forward: each edge's 400 B row and two 4 B indices, and the
    # (66,000, 100) float32 output
    assert counts.gather_bytes(calls[0]) == 990_000 * 408 + 66_000 * 400
    assert counts.gather_flops(calls[0]) == 990_000 * 100
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert counts.least_seconds(calls[:1], peak) == pytest.approx(
        430_320_000 / 819e9)


# the RGCN of DistDGLv2 section 6 at its widths: 2 layers, hidden 1024,
# fanouts 25 and 15 a destination split over MAG240M's relations
RGCN = {"arch": "rgcn", "hidden_dim": 1024, "batch_size": 1000,
        "num_rels": 5,
        "fanouts": [{"cites": 10, "writes": 8, "rev_writes": 5,
                     "rev_affiliated_with": 2},
                    {"cites": 10, "writes": 5, "rev_writes": 0}]}


def test_rgcn_flops_by_hand():
    caps = world.capacities(1000, RGCN["fanouts"])
    assert caps == [(16_000, 400_000, 416_000), (1_000, 15_000, 16_000)]
    arch = refcheck.arch_module("rgcn")
    # layer 0, 768 -> 1024, no input gradient (the input is data).  The
    # self matmul on the 16,000 destination rows, forward and weight
    # gradient.  Each of the 4 relations with a budget sums first: one
    # addition a lane of 768 for each of its 16,000 * f_r edge slots
    # (400,000 in all), then its matmul on the 16,000 sums, forward and
    # weight gradient.  Projecting first would run the matmul on all
    # 416,000 source rows, 26x more.
    mm0 = 2 * 16_000 * 768 * 1024
    l0 = 2 * mm0 + 4 * (2 * mm0) + 400_000 * 768
    # layer 1, 1024 -> 153: rev_writes has no budget.  The self matmul on
    # the 1,000 seeds, forward, weight and input gradients.  cites (10,000
    # slots) and writes (5,000) sum first at 1,024 lanes, forward and
    # backward, and run their matmul on the 1,000 sums three times:
    # 960,512,000 and 950,272,000 FLOP against 15,043,572,000 and
    # 15,042,042,000 projecting first (the matmul on 16,000 rows).
    mm1 = 2 * 1_000 * 1024 * 153
    l1 = 3 * mm1 + 2 * 3 * mm1 + 2 * (10_000 + 5_000) * 1024
    assert l0 + l1 == 254_816_256_000
    assert arch.flops(RGCN, 768, 153, caps) == l0 + l1


def test_rgcn_gather_calls_by_hand():
    caps = world.capacities(1000, RGCN["fanouts"])
    arch = refcheck.arch_module("rgcn")
    live = [{"cites": 150_000, "writes": 100_000, "rev_writes": 50_000,
             "affiliated_with": 0, "rev_affiliated_with": 20_000},
            {"cites": 9_000, "writes": 4_000, "rev_writes": 0,
             "affiliated_with": 0, "rev_affiliated_with": 0}]
    calls = arch.gather_calls(RGCN, 768, 153, caps, live)
    # the order that moves fewer bytes.  Layer 0 sums first: one forward
    # sum a relation with a budget, at 768 lanes into the 16,000
    # destination rows (projecting first would sum at 1,024 lanes, and
    # back into the 416,000 source rows too).  Layer 1 projects first:
    # forward into the 1,000 seeds and backward into the 16,000 source
    # rows at 153 lanes, where summing first would move 1,024-lane rows.
    assert [(c["width"], c["edges"], c["out_rows"]) for c in calls] == [
        (768, 150_000, 16_000), (768, 100_000, 16_000),
        (768, 50_000, 16_000), (768, 20_000, 16_000),
        (153, 9_000, 1_000), (153, 9_000, 16_000),
        (153, 4_000, 1_000), (153, 4_000, 16_000)]
    assert counts.gather_bytes(calls[0]) == (150_000 * (768 * 4 + 8)
                                             + 16_000 * 768 * 4)
    assert counts.gather_flops(calls[4]) == 9_000 * 153


def test_relation_slots_by_hand():
    from repro.core.sampler.mfg import relation_capacities
    rels = ["a", "b"]
    assert world.relation_slots(1000, [{"a": 3, "b": 2}], rels) == [
        (0, 3000, 5000)]
    # two layers: the last has 1,000 destinations, the first 1,000 + 4,000
    fanouts = [{"a": 3, "b": 2}, {"b": 4}]
    slots = world.relation_slots(1000, fanouts, rels)
    assert slots == [(0, 15_000, 25_000), (0, 0, 4_000)]
    assert world.capacities(1000, fanouts) == [
        (5_000, 25_000, 30_000), (1_000, 4_000, 5_000)]
    # the program pads its blocks to the same offsets
    assert [tuple(o) for o in relation_capacities(
        1000, fanouts, 2, etype_id=rels.index)] == slots


def test_rgcn_reference_agrees_with_the_program():
    """The reference's forward and the program's ``apply_gnn`` on the
    same typed blocks and seeded random weights, the blocks' relations
    laid out by ``world.relation_slots``."""
    import jax
    from repro.models.gnn import GNNConfig, apply_gnn
    rels = ["r0", "r1", "r2"]
    fanouts = [{"r0": 2, "r1": 1, "r2": 2}, {"r0": 1, "r1": 2}]
    batch_size, in_dim, classes = 6, 5, 4
    cfg = {"arch": "rgcn", "hidden_dim": 7, "fanouts": fanouts,
           "num_rels": len(rels)}
    caps = world.capacities(batch_size, fanouts)
    slots = world.relation_slots(batch_size, fanouts, rels)
    rng = np.random.default_rng(11)
    blocks = []
    for (cap_dst, cap_edge, cap_src), offs in zip(caps, slots):
        blocks.append({
            "edge_src": rng.integers(0, cap_src, cap_edge).astype(np.int32),
            "edge_dst": rng.integers(0, cap_dst, cap_edge).astype(np.int32),
            "edge_mask": rng.random(cap_edge) < 0.7,
            "edge_types": np.repeat(np.arange(3, dtype=np.int32),
                                    np.diff(offs))})
    feats = rng.standard_normal((caps[0][2], in_dim)).astype(np.float32)
    arch = refcheck.arch_module("rgcn")
    params = arch.init(cfg, in_dim, classes, jax.random.key(3))
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.key(4), p.shape),
        params)
    program = GNNConfig(arch="rgcn", in_dim=in_dim, hidden_dim=7,
                        num_classes=classes, fanouts=fanouts,
                        batch_size=batch_size, num_rels=len(rels),
                        impl="ref")
    with jax.default_matmul_precision("highest"):
        got = apply_gnn(program, params, {"input_feats": feats,
                                          "blocks": blocks},
                        etype_id=rels.index)
        ref = arch.forward(cfg, params, {
            "input_feats": feats,
            "blocks": [dict(b, edge_rel=r) for b, r in zip(
                blocks, refcheck.slot_relations(slots))]}, caps, np.float32)
    assert got.shape == (batch_size, classes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_peaks_table_refuses_an_unknown_device():
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        counts.peaks("TPU v9 imaginary")


def _ev(name, start, dur, line="XLA Ops"):
    return (line, name, float(start), float(dur), {})


def test_trace_reduction_by_hand():
    trace = {"device": {"/device:TPU:0": [
        _ev("fused_gather_aggregate", 0, 10),
        _ev("fusion.1", 5, 10),
        _ev("fused_gather_aggregate.2", 20, 10),
        _ev("fusion.1", 50, 10)]},          # after the window: left out
        "host": [_ev(xplane.WINDOW_SPAN, 0, 40, "python"),
                 _ev("PjitFunction(step)", 14, 8, "python"),
                 _ev("np.stack", 30, 10, "python")]}
    s = xplane.summarize(trace, ["fused_gather_aggregate"])
    assert s["window_s"] == pytest.approx(40e-9)
    assert s["busy_s"] == pytest.approx(25e-9)          # [0,15] + [20,30]
    assert s["kernel_s"]["fused_gather_aggregate"] == pytest.approx(20e-9)
    assert s["device_ops"][0] == ["fused_gather_aggregate", 10e-9]
    assert [g[0] for g in s["idle_gaps"]] == ["np.stack",
                                              "PjitFunction(step)"]
    assert [g[1] for g in s["idle_gaps"]] == pytest.approx([10e-9, 5e-9])
    assert xplane.summarize({"device": {}, "host": trace["host"]}) is None


def _busy_by_sweep(events) -> float:
    """Seconds in which at least one event runs, by a sweep over the
    sorted starts and ends."""
    marks = sorted([(s, -1) for _, _, s, d, _ in events]
                   + [(s + d, 1) for _, _, s, d, _ in events])
    marks = [(t, -step) for t, step in marks]     # starts before ends
    busy, depth, since = 0.0, 0, 0.0
    for t, step in marks:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy * 1e-9


def test_recorded_trace_excerpt():
    """The first step of a ``sage-products-train`` window, cut from a
    trace recorded on a TPU v5 lite (device operations of that step,
    host spans over 1 ms, times in ns from the window's start): the
    reduction reads its busy time and kernel time as a plain sweep and
    sum do, and as they read when it was cut."""
    trace = xplane.load_json(os.path.join(DATA, "trace_v5e_excerpt.json"))
    with open(os.path.join(DATA, "trace_v5e_excerpt.expected.json")) as f:
        want = json.load(f)
    s = xplane.summarize(trace, ["fused_gather_aggregate"])
    ops = trace["device"]["/device:TPU:0"]
    kernel = [ev for ev in ops if ev[1].startswith("fused_gather_aggregate")]
    assert len(kernel) == 5          # 3 forward, 2 backward
    assert s["busy_s"] == pytest.approx(_busy_by_sweep(ops), rel=1e-12)
    assert s["kernel_s"]["fused_gather_aggregate"] == pytest.approx(
        sum(ev[3] for ev in kernel) * 1e-9, rel=1e-12)
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert s["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert s["kernel_s"]["fused_gather_aggregate"] == pytest.approx(
        want["kernel_s"], rel=1e-9)
    assert s["idle_gaps"][0][0] == xplane.UNSPANNED


@pytest.fixture(scope="module")
def tiny_session(tmp_path_factory):
    """The set-up of a run of the tiny cell, in this process."""
    import run
    root = cellkit.tiny_benchmark(str(tmp_path_factory.mktemp("ctl")), {})
    s = run.build(world.load_cell("tiny-sage-train", root), 2024)
    return s, run.teardown(s)


def test_control_fails_and_program_passes(tiny_session):
    """The reference in bfloat16 in the program's place fails the limits
    of every cell; the program, at this size on the CPU, passes them."""
    import jax.numpy as jnp
    import run
    s, new2old = tiny_session
    steps = s.recorder.steps()
    program, ref = run.program_numbers(s, new2old)
    control = refcheck.compare(
        run.train_reference(s, new2old, dtype=jnp.bfloat16), ref)
    for name in os.listdir(os.path.join(cellkit.CELLS, "limits")):
        with open(os.path.join(cellkit.CELLS, "limits", name)) as f:
            limits = json.load(f)
        compared = [k for k in limits if k in control]
        assert compared, name
        assert any(control[k] > limits[k] for k in compared), (name, control)
        assert all(program[k] <= limits[k] for k in compared), (name, program)
        assert program["batch_mismatches"] <= limits["batch_mismatches"]
    rng = np.random.default_rng(0)
    assert refcheck.batch_mismatches(steps, s.graph, new2old, rng) == 0
    assert refcheck.batch_mismatches(steps, s.graph, np.roll(new2old, 1),
                                     rng) > 0
