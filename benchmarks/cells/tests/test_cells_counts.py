"""The benchmark's arithmetic, on the CPU: capacities, FLOP and byte
counts against hand counts at the paper's GraphSAGE widths, the
reduction of a profiler trace to busy time, kernel time and idle gaps,
and the control: the reference in bfloat16, put in the program's place,
must fail the comparison that decides ``correct``."""
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
