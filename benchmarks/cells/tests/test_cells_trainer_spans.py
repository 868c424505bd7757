"""What the training loop's spans and counters give the benchmark, on the
CPU at a tiny size: the traced run reports the time a step waited for
its input, and an idle gap between two steps is named after one of the
loop's phase spans, which holds only while no span encloses a whole
step."""
import glob
import json
import os
import sys

import pytest

import cellkit

sys.path.insert(0, cellkit.CELLS)
sys.path.insert(0, os.path.join(cellkit.REPO, "src"))

import world  # noqa: E402
import xplane  # noqa: E402


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    with open(os.path.join(cellkit.CELLS, "limits",
                           "sage-products-train.json")) as f:
        limits = json.load(f)
    return cellkit.tiny_benchmark(str(tmp_path_factory.mktemp("bench")),
                                  limits)


def test_traced_run_reports_input_wait(tiny):
    proc = cellkit.drive(tiny, ["--workload", "tiny-sage-train", "--seed",
                                str(2**31 + 4242), "--seconds", "1",
                                "--trace", "1"])
    line = cellkit.result_line(proc)
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["input_wait_ms_per_step.train"]["value"] >= 0


def test_gap_between_steps_is_named_after_a_phase_span(tiny,
                                                       tmp_path_factory):
    """A gap from the start of the second step's ``trainer.wait_batches``
    to the end of its ``trainer.stage``, as the device would leave it
    idle while the host waits and stages, on a trace of ``train_epoch``
    taken as the harness takes it."""
    import jax
    import run
    s = run.build(world.load_cell("tiny-sage-train", tiny), 31)
    tdir = str(tmp_path_factory.mktemp("trace"))
    try:
        jax.profiler.start_trace(tdir,
                                 profiler_options=run._profile_options())
        try:
            assert s.trainer.train_epoch(s.next_epoch)["batches"] >= 2
        finally:
            jax.profiler.stop_trace()
    finally:
        run.teardown(s)
    path, = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    host = xplane.load_xplane(path)["host"]

    def spans(name):
        return sorted((ev[2], ev[2] + ev[3]) for ev in host if ev[1] == name)
    waits, stages = spans("trainer.wait_batches"), spans("trainer.stage")
    assert len(waits) == len(stages) >= 2
    gap = (waits[1][0], stages[1][1])
    assert xplane._gap_name(host, *gap) in ("trainer.wait_batches",
                                           "trainer.stage")
