"""The reader that joins a trace's per-instruction device time with the
scope map of the step the program compiled (`metrics/scope_ms_per_step.py`):
on made-up times and maps, on the pair recorded on the v5e
(`tools/record_scoped_fixture.py`), and the cells that report its metrics,
found through `harness.spec.Cell` and held to a rule read from each cell's
own program (`cell_rules.py`), never to a list of cells."""
import gzip
import json
import os

import pytest

import cell_rules
from bench_paths import BENCH_DIR, FIXTURES, on_path

on_path()
from harness import readers  # noqa: E402
from harness import trace_reduce  # noqa: E402
from harness.spec import Cell  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402
from mxnet_tpu.telemetry import hlo_scopes  # noqa: E402

READER = "scope_ms_per_step.py"
PASSES = ["forward_ms_per_step.tok", "recompute_ms_per_step.tok",
          "backward_ms_per_step.tok", "optimizer_ms_per_step.tok"]

FWD = "jit(step_fn)/jvp(forward)/"
BWD = "jit(step_fn)/transpose(jvp(forward))/"
REMAT = BWD + "jvp(forward)/checkpoint/rematted_computation/"
# a made-up step: two layers' worth of names, a loop and a switch
MAP = {
    "fusion.1": ("fusion", FWD + "attention/dot_general"),
    "flash_fwd.1": ("custom-call", FWD + "attention/pallas_call"),
    "fusion.2": ("fusion", FWD + "ffn/dot_general"),
    "fusion.3": ("fusion", REMAT + "ffn/dot_general"),
    "fusion.4": ("fusion", BWD + "ffn/transpose"),
    "fusion.5": ("fusion", BWD + "mlm_head/dot_general"),
    "while.1": ("while", FWD + "gdn/delta_scan/while"),
    "fusion.6": ("fusion", FWD + "gdn/delta_scan/while/body/dot_general"),
    "cond.1": ("conditional", FWD + "moe/cond"),
    "fusion.7": ("fusion", FWD + "moe/cond/branch_0_fun/jit(_routed_rows)/"
                 "rows_4096/mul"),
    "fusion.8": ("fusion", BWD + "moe/cond/branch_1_fun/"
                 "jit(_routed_rows_vjp)/rows_8192/mul"),
    "fusion.9": ("fusion", "jit(step_fn)/optimizer/sub"),
    "copy.1": ("copy", ""),
    "fusion.10": ("fusion", FWD + "embedding/add"),
}
OP_S = {
    "convolution/fusion.1": 0.010, "mosaic/flash_fwd.1": 0.004,
    "convolution/fusion.2": 0.020, "convolution/fusion.3": 0.021,
    "convolution/fusion.4": 0.040, "convolution/fusion.5": 0.012,
    "other/while.1": 0.0065, "fusion/fusion.6": 0.006,
    "other/cond.1": 0.0035, "fusion/fusion.7": 0.003,
    "fusion/fusion.8": 0.005, "fusion/fusion.9": 0.008,
    "copy/copy.1": 0.001, "copy/copy-start.7": 0.002,
    "fusion/fusion.10": 0.0005,
}
CONTAINED = OP_S["other/while.1"] + OP_S["other/cond.1"]


def metric(name):
    with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)


def made_up(steps=2, op_s=OP_S, module="jit_step_fn(123)"):
    return {"trace": {"devices": [{"module": module, "steps": steps,
                                   "op_s": dict(op_s)}]}}


def read(name, run, **params):
    held = metric(name)
    if params:
        held = dict(held, params=params)
    return readers.read(held, run, BENCH_DIR)


@pytest.fixture
def scopes(monkeypatch):
    """`put(maps)` stands in for what the program's steps published."""
    def put(maps):
        monkeypatch.setattr(telemetry, "module_scopes", lambda: maps)
    put({"jit_step_fn": MAP})
    return put


@pytest.mark.parametrize("name,expected_ms", [
    ("forward_ms_per_step.tok", 1e3 * (0.010 + 0.004 + 0.020 + 0.006
                                       + 0.003 + 0.0005) / 2),
    ("recompute_ms_per_step.tok", 1e3 * 0.021 / 2),
    ("backward_ms_per_step.tok", 1e3 * (0.040 + 0.012 + 0.005) / 2),
    ("optimizer_ms_per_step.tok", 1e3 * 0.008 / 2),
    ("attention_layer_ms_per_step", 1e3 * 0.014 / 2),
    ("ffn_ms_per_step", 1e3 * 0.081 / 2),
    ("head_ms_per_step", 1e3 * 0.012 / 2),
    ("embedding_ms_per_step", 1e3 * 0.0005 / 2),
    # the loop's and the switch's own events are in no sum
    ("gdn_layer_ms_per_step", 1e3 * 0.006 / 2),
    ("gdn_scan_ms_per_step", 1e3 * 0.006 / 2),
    ("moe_layer_ms_per_step", 1e3 * 0.008 / 2),
    ("moe_rows_ms_per_step", 1e3 * 0.008 / 2),      # the `rows_*` pattern
])
def test_a_scope_is_the_time_of_its_instructions_a_step(scopes, name,
                                                        expected_ms):
    assert read(name, made_up()) == pytest.approx(expected_ms)


def test_the_passes_and_the_unmapped_share_make_up_what_is_no_container(
        scopes):
    run = made_up()
    everything = sum(OP_S.values()) - CONTAINED
    share = read("scope_unmapped_share.tok", run)
    assert share == pytest.approx(100 * 0.003 / everything)
    passes = sum(read(name, run) for name in PASSES)
    per_step = 1e3 * everything / 2
    assert passes + per_step * share / 100 == pytest.approx(per_step)
    # with neither a pass nor a scope asked for: all that the map holds
    held = dict(metric("forward_ms_per_step.tok"), params={})
    assert readers.read(held, run, BENCH_DIR) == pytest.approx(
        1e3 * (everything - 0.002) / 2)


@pytest.mark.parametrize("case", ["no_program_map", "another_module",
                                  "no_steps", "no_match", "no_trace",
                                  "no_devices"])
def test_nothing_to_read_is_none_never_nought(scopes, case):
    run = made_up()
    name = "forward_ms_per_step.tok"
    if case == "no_program_map":
        scopes({})
    elif case == "another_module":
        scopes({"jit_run": MAP})
    elif case == "no_steps":
        run = made_up(steps=0)
    elif case == "no_match":
        name = "window_attention_layer_ms_per_step"
    elif case == "no_trace":
        run = {"trace": None}
    else:
        run = {"trace": {"devices": []}}
    assert read(name, run) is None
    if case != "no_match":
        assert read("scope_unmapped_share.tok", run) is None
    # a map that the program should have had and has not is said, once
    lost = case in ("no_program_map", "another_module")
    assert len(run.get("notes", [])) == lost
    assert not lost or "'jit_step_fn'" in run["notes"][0]


def test_a_program_from_before_the_map_reads_none(monkeypatch):
    """The parent's tree has no `module_scopes`: the reader is laid over it
    by the driver and must leave the metrics out, not raise."""
    monkeypatch.delattr(telemetry, "module_scopes")
    run = made_up()
    assert read("forward_ms_per_step.tok", run) is None
    assert "notes" not in run       # nothing was lost: nothing to say


def by_rule(cell, monkeypatch):
    """The metrics of this reader that `cell` reports by the rule: those
    that move an end-to-end metric it reports and that read a number over
    its step's own scope map with every instruction timed. A pass is there
    where some instruction lies in it (`recomputed` only where the step
    recomputes a layer), a block where some instruction lies under one of
    the metric's scopes, and `scope_unmapped_share` of its kind always."""
    name, held = cell_rules.scope_map(cell)
    monkeypatch.setattr(telemetry, "module_scopes", lambda: {name: held})
    run = cell_rules.every_operation_timed(
        ["op/" + instr for instr in held], name)
    return cell_rules.read_by_rule(cell, READER, run)


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  cell_rules.benchmark()["workloads"]])
def test_each_metric_is_reported_in_its_cells_and_in_no_other(cell,
                                                              monkeypatch):
    assert cell_rules.listed(cell, READER) == by_rule(cell, monkeypatch)
    for m in Cell(cell).per_layer:
        if m.get("reader_file") == READER:
            assert m["source"] == "device_trace" and m["moves"] in (
                "img_per_s", "tok_per_s")
            assert set(m["params"]) <= {"pass", "scopes", "share_unmapped"}
            assert m["params"].get("pass", "forward") in hlo_scopes.PASSES


def test_the_rule_finds_the_cells_that_are_there(monkeypatch):
    """What the six cells listed before the rule was read from their
    programs, which the rule gives them again."""
    passes = {"forward", "backward", "optimizer"}
    img = {p + "_ms_per_step.img" for p in passes} | {
        "scope_unmapped_share.img"}
    tok = {p + "_ms_per_step.tok" for p in passes} | {
        "scope_unmapped_share.tok", "attention_layer_ms_per_step",
        "head_ms_per_step"}
    bert = tok | {"ffn_ms_per_step", "embedding_ms_per_step"}
    experts = tok | {"recompute_ms_per_step.tok", "moe_layer_ms_per_step",
                     "moe_rows_ms_per_step"}
    expected = {
        "resnet50_b256": img, "resnet50_dp4_b1024": img,
        "bert_base_s128": bert, "bert_base_s512": bert,
        "qwen3_next_ep16_s4096": experts | {
            "gdn_layer_ms_per_step", "gdn_chunk_ms_per_step",
            "gdn_scan_ms_per_step"},
        "laguna_s_ep32_s4096": experts | {
            "window_attention_layer_ms_per_step", "ffn_ms_per_step"}}
    for cell, names in expected.items():
        assert by_rule(cell, monkeypatch) == names, cell


# ------------------------------------------------- the pair recorded on a v5e
@pytest.fixture(scope="module")
def recorded():
    """(the reduced trace, the scope map) of `tools/record_scoped_fixture.py`:
    four steps of the two-layer toy BERT, and what `module_scopes()` held
    for `jit_step_fn` in that run."""
    with gzip.open(os.path.join(FIXTURES,
                                "toy_bert_scoped.xplane.pb.gz")) as f:
        reduced = trace_reduce.reduce_trace(trace_reduce.loads(f.read()))
    with open(os.path.join(FIXTURES, "toy_bert_scoped.scopes.json")) as f:
        return reduced, {k: tuple(v) for k, v in json.load(f).items()}


def test_the_recorded_trace_joins_with_the_recorded_map(recorded, scopes):
    reduced, held = recorded
    scopes({"jit_step_fn": held})
    run = {"trace": reduced}
    (dev,) = reduced["devices"]
    assert dev["module"].startswith("jit_step_fn(") and dev["steps"] == 4
    # every instruction that ran is in the map the program gave
    assert all(key.split("/", 1)[1] in held for key in dev["op_s"])
    assert not any(held[key.split("/", 1)[1]][0] in hlo_scopes.CONTAINERS
                   for key in dev["op_s"])
    blocks = {name: read(name, run) for name in (
        "attention_layer_ms_per_step", "ffn_ms_per_step", "head_ms_per_step",
        "embedding_ms_per_step")}
    passes = {name: read(name, run) for name in PASSES}
    assert all(v > 0 for v in blocks.values())
    assert passes.pop("recompute_ms_per_step.tok") is None     # no remat
    assert all(v > 0 for v in passes.values())
    # at 128 wide the toy's parameter copies (q|k|v packed into one array a
    # step, the prefetches) weigh 8%; the cells read 3-6% on the chip
    share = read("scope_unmapped_share.tok", run)
    assert 0 < share < 10
    # one operation at a time on a core: the passes and what no pass claims
    # add up to the device's busy time
    busy_ms = 1e3 * dev["busy_s"] / dev["steps"]
    assert sum(passes.values()) + busy_ms * share / 100 == pytest.approx(
        busy_ms, rel=1e-6)
    assert sum(blocks.values()) < sum(passes.values())
    # the kernels keep their names under the new scopes
    flash = {key for key in dev["op_s"] if key.startswith("mosaic/flash_")}
    assert len(flash) == 6
    assert all("attention" in hlo_scopes.path(
        held[key.split("/", 1)[1]][1])[1] for key in flash)
