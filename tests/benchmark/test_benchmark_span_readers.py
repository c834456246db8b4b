"""The readers of the program's own spans and of its named kernels, on
made-up runs; the metrics that use them, found through `harness.spec.Cell`;
and one rehearsal that prints them."""
import gzip
import json
import os

import pytest

from bench_paths import BENCH_DIR, FIXTURES, ROOT, on_path
from test_benchmark_run import REHEARSAL, run as run_benchmark

on_path()
from harness import readers  # noqa: E402
from harness import trace_reduce  # noqa: E402
from harness.spec import Cell  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402

IMG_PHASES = ["gather_ms_per_step.img", "stage_ms_per_step.img",
              "launch_ms_per_step.img", "write_back_ms_per_step.img",
              "dispatch_other_ms_per_step.img"]
TOK_PHASES = ["launch_ms_per_step.tok", "dispatch_other_ms_per_step.tok"]
KERNELS = ["flash_fwd_ms_per_step", "flash_dq_ms_per_step",
           "flash_dkv_ms_per_step"]
SETUP = ["setup_trace_s", "setup_lower_s", "setup_xla_s"]


def cells_by_rule():
    """{cell: the metrics above that it reports}, from `BENCHMARK.json` and
    the configurations' files and from no list of names, so that a later
    cell needs no edit here. A cell that reports `img_per_s` reports the
    `.img` phases, `stage` on a mesh only; one that reports `tok_per_s` the
    `.tok` phases, and the three kernels where its configuration is BERT's
    program; every cell the three set-up spans."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    rates = {w: m["name"] for m in bench["end_to_end"]
             if m["name"] in ("img_per_s", "tok_per_s")
             for w in m["workloads"]}
    files = {c["name"]: c["file"] for c in bench["configs"]}
    out = {}
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, files[w["config"]])) as f:
            cfg = json.load(f)
        names = list(SETUP)
        if rates.get(w["name"]) == "img_per_s":
            names += [m for m in IMG_PHASES
                      if "stage" not in m or w["chips"] > 1]
        if rates.get(w["name"]) == "tok_per_s":
            names += TOK_PHASES
            names += KERNELS if cfg.get("program") == "bert" else []
        out[w["name"]] = names
    return out


CELLS = cells_by_rule()


def metric(name, **params):
    with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
        held = json.load(f)
    held["params"].update(params)
    return held


def read(name, run, **params):
    return readers.read(metric(name, **params), run, BENCH_DIR)


@pytest.fixture
def ring():
    """An empty ring that takes spans, and `put(name, start, length)` to lay
    one down at a time of the caller's choosing (seconds on the ring's
    clock; `tid` 1 unless given)."""
    was_enabled = telemetry.ENABLED
    telemetry.enable()
    telemetry.reset()

    def put(name, start, length, cat="phase", tid=1):
        telemetry.record_span(name, cat, start, length, tid=tid)
    yield put
    telemetry.reset()
    (telemetry.enable if was_enabled else telemetry.disable)()


def window(t0, t1):
    zero = telemetry.span_epoch()
    return {"window": {"t0": zero + t0, "t1": zero + t1}}


def two_steps(put, at=(10.0, 11.0)):
    """Two `fused_step` spans of 100 ms: gather 10, stage 50, launch 20,
    write-back 5, and so 15 ms of their own."""
    for t in at:
        put("fused_step", t, 0.100, cat="step")
        put("fused_step.gather", t + 0.005, 0.010)
        put("fused_step.stage", t + 0.020, 0.050)
        put("fused_step.launch", t + 0.070, 0.020)
        put("fused_step.write_back", t + 0.092, 0.005)


def test_a_phase_is_its_time_in_the_window_over_the_parents_there(ring):
    two_steps(ring, at=(5.0, 10.0, 11.0, 30.0))    # one before, one after
    run = window(9.0, 12.0)
    assert read("gather_ms_per_step.img", run) == pytest.approx(10.0)
    assert read("stage_ms_per_step.img", run) == pytest.approx(50.0)
    assert read("launch_ms_per_step.img", run) == pytest.approx(20.0)
    assert read("write_back_ms_per_step.img", run) == pytest.approx(5.0)
    # a step that skipped a phase still counts as a step
    ring("fused_step", 11.5, 0.030, cat="step")
    assert read("stage_ms_per_step.img", run) == pytest.approx(100.0 / 3)


def test_self_time_is_the_span_less_what_lies_inside_it(ring):
    two_steps(ring)
    # a span inside a phase is covered already; one of another thread, or
    # one that only overlaps the step's end, is no part of it
    ring("jit.trace:run", 10.072, 0.010, cat="jit")
    ring("fused_step.gather", 10.0, 0.050, tid=2)
    ring("checkpoint", 10.095, 0.100, cat="resilience")
    run = window(9.0, 12.0)
    assert read("dispatch_other_ms_per_step.img", run) == pytest.approx(15.0)
    parts = sum(read(name, run) for name in IMG_PHASES[:4])
    assert parts + 15.0 == pytest.approx(100.0 + 50.0 / 2)   # thread 2's


def test_set_up_is_the_union_of_the_spans_that_ended_before_the_window(ring):
    ring("jit.trace:run", 1.0, 2.0, cat="jit")
    ring("jit.trace:matmul", 1.5, 0.5, cat="jit")      # nested: counted once
    ring("jit.trace:step_fn", 4.0, 1.0, cat="jit")
    ring("jit.trace:late", 8.5, 1.0, cat="jit")        # ends in the window
    ring("jit.trace", 20.0, 1.0, cat="jit")            # the reference's
    ring("jit.tracer", 0.0, 0.5, cat="jit")            # another name
    ring("jit.xla:jit(run)", 5.0, 0.25, cat="jit")
    run = window(9.0, 12.0)
    assert read("setup_trace_s", run) == pytest.approx(3.0)
    assert read("setup_xla_s", run) == pytest.approx(0.25)
    assert read("setup_lower_s", run) is None


def test_nothing_to_read_is_none(ring, monkeypatch):
    run = window(9.0, 12.0)
    for name in IMG_PHASES + TOK_PHASES + SETUP:
        assert read(name, run) is None
    two_steps(ring)
    assert read("launch_ms_per_step.tok", run) is None
    assert read("gather_ms_per_step.img", window(20.0, 21.0)) is None
    # a program from before `span_epoch`: nothing, and no error
    monkeypatch.delattr(telemetry, "span_epoch")
    assert read("gather_ms_per_step.img", run) is None


def test_named_operations_are_summed_by_prefix_over_the_steps():
    dev = {"steps": 4, "op_s": {
        "mosaic/flash_fwd.1": 0.020, "mosaic/flash_fwd.7": 0.004,
        "mosaic/flash_dq.1": 0.030, "mosaic/flash_dkv.1": 0.050,
        "mosaic/jvp__.2": 1.0, "fusion/flash_fwd_like": 1.0}}
    run = {"trace": {"devices": [dev, {"steps": 4, "op_s": {}}]}}
    assert read("flash_fwd_ms_per_step", run) == pytest.approx(6.0)
    assert read("flash_dq_ms_per_step", run) == pytest.approx(7.5)
    assert read("flash_dkv_ms_per_step", run) == pytest.approx(12.5)
    unnamed = {"trace": {"devices": [{"steps": 4,
                                      "op_s": {"mosaic/jvp__.2": 1.0}}]}}
    for name in KERNELS:
        assert read(name, unnamed) is None
        assert read(name, {"trace": None}) is None
        assert read(name, {"trace": {"devices": [dict(dev, steps=0)]}}) is None


def test_the_trace_recorded_with_named_kernels():
    """`toy_bert_named.xplane.pb.gz`: `benchmark/tools/record_fixture.py` on
    the v5e at PR 26, started with `python3` as the benchmark's command is.
    Two layers, four steps: every Mosaic call under its kernel's name, and
    the program's spans on the host's line inside the caller's `step`."""
    with gzip.open(os.path.join(FIXTURES,
                                "toy_bert_named.xplane.pb.gz")) as f:
        data = trace_reduce.loads(f.read())
    reduced = trace_reduce.reduce_trace(data)
    (dev,) = reduced["devices"]
    assert dev["steps"] == 4
    mosaic = {k for k in dev["op_s"] if k.startswith("mosaic/")}
    assert sorted(k.split(".")[0] for k in mosaic) == sorted(
        ["mosaic/flash_fwd", "mosaic/flash_dq", "mosaic/flash_dkv"] * 2)
    run = {"trace": reduced}
    parts = [read(name, run) for name in KERNELS]
    assert sum(parts) == pytest.approx(
        1e3 * dev["category_s"]["mosaic"] / 4, rel=1e-9)
    assert all(p > 0 for p in parts)
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    (line,) = [line for line in host.lines
               if any(e.name == "train_step" for e in line.events)]
    assert line.name == "python3"       # not the `python` the reduction asks
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events]
    steps = [e for e in events if e[0] == "step"]
    assert len(steps) == 4
    for _, start, end in steps:
        inside = [n for n, s, e in events if start <= s and e <= end]
        assert inside.count("train_step") == 1
        assert inside.count("train_step.launch") == 1


@pytest.mark.parametrize("name", IMG_PHASES + TOK_PHASES + KERNELS + SETUP)
def test_each_new_metric_resolves_in_its_cells_alone(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    assert sorted(entry["workloads"]) == sorted(
        cell for cell, names in CELLS.items() if name in names)
    for cell in CELLS:
        held = [m for m in Cell(cell).per_layer if m["name"] == name]
        assert len(held) == (name in CELLS[cell])
        for m in held:
            assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                               m["reader_file"]))
            assert {k: m[k] for k in entry if k != "workloads"} == {
                k: v for k, v in entry.items() if k != "workloads"}


def test_the_rule_finds_the_cells_that_are_there():
    assert set(CELLS["resnet50_b256"]) == set(
        m for m in IMG_PHASES if "stage" not in m) | set(SETUP)
    assert set(CELLS["resnet50_dp4_b1024"]) == set(IMG_PHASES + SETUP)
    assert set(CELLS["bert_base_s128"]) == set(TOK_PHASES + KERNELS + SETUP)
    assert CELLS["bert_base_s512"] == CELLS["bert_base_s128"]


def test_a_traced_rehearsal_prints_the_phases_and_the_set_up_times():
    p = run_benchmark(["--workload", "resnet50_dp4_b1024", "--seed",
                       str(2 ** 31 + 26), "--seconds", "1", "--trace", "1",
                       "--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-1] == REHEARSAL
    metrics = json.loads(lines[-2])["metrics"]
    for name in IMG_PHASES + SETUP:
        assert metrics[name]["value"] > 0, name
        assert metrics[name]["unit"] == ("s" if name in SETUP else "ms")
    # children and self time make up the program's own span, which lies
    # inside the benchmark's clock around the call
    assert sum(metrics[n]["value"] for n in IMG_PHASES) <= (
        metrics["dispatch_ms_per_step.img"]["value"])
