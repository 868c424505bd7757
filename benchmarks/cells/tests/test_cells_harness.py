"""The benchmark's harness end to end on the CPU, at a tiny size.

A cell, a configuration, a traffic mix and a per-layer metric are added
to a copy of the benchmark by new files and new entries only; the
harness runs them through ``DistGNNTrainer.train_epoch`` and the check,
with its look for a chip steered in the test.  Faults planted in the
program underneath must turn ``correct`` false.  The real entry point
must refuse to run without a TPU.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

import cellkit

LIMITS_OF = "sage-products-train"


@pytest.fixture(scope="module")
def limits():
    with open(os.path.join(cellkit.CELLS, "limits", LIMITS_OF + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory, limits):
    return cellkit.tiny_benchmark(str(tmp_path_factory.mktemp("bench")),
                                  limits)


def _run(tiny, trace, seed, fault=""):
    return cellkit.drive(tiny, ["--workload", "tiny-sage-train", "--seed",
                                str(seed), "--seconds", "1", "--trace",
                                str(trace)], fault=fault)


def test_untraced_run_reports_end_to_end_metrics(tiny):
    proc = _run(tiny, 0, 2**31 + 12345)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = cellkit.result_line(proc)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"train_seeds_per_s", "setup_s"}
    assert line["metrics"]["train_seeds_per_s"]["value"] > 0
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    assert line["checks"]["window_compiles"]["value"] == 0
    last = proc.stderr.strip().splitlines()[-len(line["checks"]):]
    assert all(ln.startswith("check ") and " limit " in ln for ln in last)


def test_traced_run_reports_per_layer_metrics_and_a_new_one(tiny):
    line = cellkit.result_line(_run(tiny, 1, 77))
    assert line["correct"] is True, line["checks"]
    m = line["metrics"]
    for name in ("sample_ms_per_batch.train", "prefetch_ms_per_batch.train",
                 "remote_mb_per_step.train", "step_mfu.train"):
        assert m[name]["value"] > 0, name
    assert m["steps_in_window.train"]["value"] == line["attempted"]
    # the CPU has no device plane: metrics read from it stay silent
    assert "device_idle_share.train" not in m
    assert "fused_gather_aggregate_roofline.train" not in m


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "one_trainer", "labels_shifted"])
def test_fault_in_the_timed_path_is_not_correct(tiny, fault):
    line = cellkit.result_line(_run(tiny, 0, 5, fault=fault))
    assert line["correct"] is False, (fault, line["checks"])


def _no_result(run_py: str, cwd: str) -> str:
    proc = subprocess.run(
        [sys.executable, run_py, "--workload", LIMITS_OF, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=cellkit.cpu_env(), timeout=120,
        cwd=cwd)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc.stderr


def test_no_tpu_no_result(tmp_path):
    assert "TPU" in _no_result(os.path.join(cellkit.CELLS, "run.py"),
                               str(tmp_path))


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    files has no program to measure."""
    shutil.copy(os.path.join(cellkit.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(cellkit.CELLS, tmp_path / "benchmarks" / "cells")
    _no_result(str(tmp_path / "benchmarks" / "cells" / "run.py"),
               str(tmp_path))
