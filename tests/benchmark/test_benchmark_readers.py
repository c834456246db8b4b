"""The per-layer readers' arithmetic, on a made-up traced run."""
import json
import os

import pytest

from bench_paths import BENCH_DIR, on_path

on_path()
from harness import readers  # noqa: E402

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def metric(name):
    with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)


def bert_run(**device):
    with open(os.path.join(BENCH_DIR, "configs", "bert_base.json")) as f:
        cfg = json.load(f)
    dev = {"steps": 10, "window_s": 1.57, "busy_s": 1.5686,
           "category_s": {"mosaic": 0.4, "convolution": 0.9},
           "collective_s": 0.0, "collective_exposed_s": 0.0}
    dev.update(device)
    return {"cfg": cfg, "traffic": {"batch": 128, "seq": 128}, "chips": 1,
            "samples_per_step": 16384, "peak": PEAK, "notes": [],
            "trace": {"devices": [dev]},
            "window": {"dispatch_s": [0.002, 0.004]},
            "counters_before": {"train_step.compile": 1},
            "counters_after": {"train_step.compile": 1,
                               "train_step.retrace": 0},
            "first_call_s": 7.5, "cache_misses": 0,
            "memory_peak_bytes": 9.5e9}


def read(name, run):
    return readers.read(metric(name), run, BENCH_DIR)


def test_mfu_is_required_operations_over_the_peak():
    run = bert_run()
    tok_per_s = 10 * 16384 / 1.57
    assert read("mfu.tok", run) == pytest.approx(
        100 * 664.4e6 * tok_per_s / 197e12, rel=1e-3)
    assert 0 < read("mfu.tok", run) < 100


def test_flash_time_and_roofline():
    run = bert_run()
    assert read("flash_ms_per_step", run) == pytest.approx(40.0)
    # float32 tensors: 7.3 GB a step, 8.9 ms at 819 GB/s, over 40 ms
    assert read("flash_roofline", run) == pytest.approx(22.3, abs=0.2)
    assert "bound by bytes" in run["notes"][0]


def test_a_reader_with_nothing_to_read_returns_nothing():
    run = bert_run(category_s={"convolution": 0.9})
    assert read("flash_ms_per_step", run) is None
    assert read("flash_roofline", run) is None       # never 0 for a share
    run["trace"] = None
    assert read("mfu.tok", run) is None
    assert read("device_idle_share.tok", run) is None
    assert read("collective_exposed_ms_per_step.img", bert_run()) is None


def test_the_plain_readers():
    run = bert_run()
    assert read("dispatch_ms_per_step.tok", run) == pytest.approx(3.0)
    assert read("compiles_in_window.tok", run) == 0.0
    run["counters_after"]["train_step.retrace"] = 2
    assert read("compiles_in_window.tok", run) == 2.0
    assert read("compile_s", run) == 7.5
    assert read("programs_compiled", run) == 0.0
    assert read("peak_hbm_gb.tok", run) == pytest.approx(9.5)
    assert read("device_idle_share.tok", run) == pytest.approx(
        100 * (1 - 1.5686 / 1.57))


def test_idle_share_is_the_idlest_chips_and_collectives_per_step():
    run = bert_run()
    run["trace"]["devices"].append(dict(run["trace"]["devices"][0],
                                        busy_s=1.5))
    assert read("device_idle_share.tok", run) == pytest.approx(
        100 * (1 - 1.5 / 1.57))
    run = bert_run(collective_s=0.2, collective_exposed_s=0.015)
    assert read("collective_exposed_ms_per_step.img", run) == pytest.approx(
        1.5)
