"""`benchmark/run.py` as the driver starts it: a process of its own."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import ROOT

REHEARSAL = "REHEARSAL (cpu): not a chip result"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def run(args, cwd=ROOT, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py")] + args,
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def result_lines(out):
    return [line for line in out.splitlines()
            if line.startswith("{") and '"correct"' in line]


def test_without_a_chip_it_fails_and_prints_no_result():
    p = run(["--workload", "resnet50_b256", "--seed", "1", "--seconds", "1",
             "--trace", "0"], timeout=120)
    assert p.returncode != 0
    assert not result_lines(p.stdout)
    assert "needs a tpu backend" in p.stderr


def test_with_fewer_chips_than_the_cell_asks_it_fails():
    # a rehearsal given one CPU device where the cell asks for four
    env_flags = "--xla_force_host_platform_device_count=1"
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=env_flags)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "resnet50_dp4_b1024", "--rehearse", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and not result_lines(p.stdout)
    assert "asks for 4 chip(s)" in p.stderr


def test_an_unknown_workload_fails():
    p = run(["--workload", "no_such_cell"], timeout=120)
    assert p.returncode != 0 and not result_lines(p.stdout)


def test_in_a_directory_with_the_benchmark_alone_it_fails(tmp_path):
    paths = BENCH["paths"]
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run(["--workload", "resnet50_b256", "--rehearse", "--seconds", "1"],
            cwd=str(tmp_path), timeout=120)
    assert p.returncode != 0 and not result_lines(p.stdout)


def cells_and_traces():
    """Every cell of `BENCHMARK.json`: the first traced not, the others
    traced, so that both kinds of result line are rehearsed."""
    return [(w["name"], int(i > 0))
            for i, w in enumerate(BENCH["workloads"])]


def rate_of(cell):
    (m,) = [m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [])]
    return m


@pytest.mark.parametrize("cell,trace", cells_and_traces())
def test_rehearsal_of_each_cell_ends_on_the_rehearsal_line(cell, trace):
    p = run(["--workload", cell, "--seed", str(2 ** 31 + 17), "--seconds",
             "1", "--trace", str(trace), "--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-1] == REHEARSAL
    result = json.loads(lines[-2])
    assert list(result)[-1] == "compared" and result["correct"] is True
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"]["platform"] == "cpu"
    if trace:
        assert "compile_s" in result["metrics"]
        assert not any(n.startswith(("mfu", "flash_roofline",
                                     "conv_roofline", "device_idle"))
                       for n in result["metrics"])    # nothing to read here
        moved = [n for n in result["metrics"]
                 if n.startswith("compiles_in_window")]
        assert moved and result["metrics"][moved[0]]["value"] == 0
    else:
        assert set(result["metrics"]) == {rate_of(cell), "setup_s"}
    # the window's end: at most the queue's steps lie after it
    assert 0 <= result["trimmed_steps"] <= 8 and result["last_gap_ms"] >= 0
    for name, check in result["compared"].items():
        assert "compared %s:" % name in p.stderr
    assert any(line.startswith("steps_done_ms: [") for line in lines)
