"""The readers of the program's own spans and of its named kernels, on
made-up runs; the metrics that use them, found through `harness.spec.Cell`
and held to a rule read from `BENCHMARK.json`, the configurations and each
cell's own program (`cell_rules.py`), never to a list of cells; and one
rehearsal that prints them."""
import functools
import gzip
import json
import os

import pytest

import cell_rules
from bench_paths import BENCH_DIR, FIXTURES, on_path
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
SETUP_COUNTS = ["compile_s", "programs_compiled"]
NAMED = "named_op_ms_per_step.py"
BENCH = cell_rules.benchmark()
# every metric of the reader of named kernels: a later one is held too
NAMED_OPS = [m["name"] for m in BENCH["per_layer"]
             if cell_rules.metric_file(m["name"]).get("reader_file") == NAMED]
RULED = IMG_PHASES + TOK_PHASES + SETUP + SETUP_COUNTS + NAMED_OPS


@functools.lru_cache(maxsize=None)
def by_rule(cell):
    """The metrics above that `cell` reports: every cell the set-up's
    spans and counts; one that reports `img_per_s` the `.img` phases,
    `stage` on a mesh only; one that reports `tok_per_s` the `.tok` phases;
    and a metric of named kernels where the cell reports what it moves and
    its step, lowered for the TPU at the cell's sizes, calls a kernel of
    that name."""
    spec = Cell(cell)
    reported = {m["name"] for m in spec.end_to_end}
    names = set(SETUP + SETUP_COUNTS)
    if "img_per_s" in reported:
        names |= {m for m in IMG_PHASES
                  if "stage" not in m or spec.chips > 1}
    if "tok_per_s" in reported:
        names |= set(TOK_PHASES)
    if any(cell_rules.metric_file(m)["moves"] in reported
           for m in NAMED_OPS):
        run = cell_rules.every_operation_timed(
            "mosaic/%s.0" % k for k in cell_rules.kernels(cell))
        names |= cell_rules.read_by_rule(cell, NAMED, run)
    return names


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


@pytest.mark.parametrize("name", RULED)
def test_each_new_metric_resolves_in_its_cells_alone(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    cells = [w["name"] for w in BENCH["workloads"]]
    assert sorted(entry["workloads"]) == sorted(
        cell for cell in cells if name in by_rule(cell))
    for cell in cells:
        held = [m for m in Cell(cell).per_layer if m["name"] == name]
        assert len(held) == (name in by_rule(cell))
        for m in held:
            if "reader_file" in m:
                assert os.path.exists(os.path.join(BENCH_DIR, "metrics",
                                                   m["reader_file"]))
            else:
                assert m["reader"] in readers.READERS
            assert {k: m[k] for k in entry if k != "workloads"} == {
                k: v for k, v in entry.items() if k != "workloads"}


def test_the_rule_finds_the_cells_that_are_there():
    everywhere = set(SETUP + SETUP_COUNTS)
    assert by_rule("resnet50_b256") == everywhere | set(
        m for m in IMG_PHASES if "stage" not in m)
    assert by_rule("resnet50_dp4_b1024") == everywhere | set(IMG_PHASES)
    assert by_rule("bert_base_s128") == everywhere | set(TOK_PHASES + KERNELS)
    assert by_rule("bert_base_s512") == by_rule("bert_base_s128")
    # the decoders' causal calls are the same three kernels; their windowed
    # calls are `swa_*`, which `flash_*` does not match
    decoder = everywhere | set(TOK_PHASES + KERNELS) | {"moe_gmm_ms_per_step"}
    assert by_rule("qwen3_next_ep16_s4096") == decoder | {"gdn_ms_per_step"}
    assert by_rule("laguna_s_ep32_s4096") == decoder | {"swa_ms_per_step"}


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
