"""`BENCHMARK.json` and the files under `benchmark/` keep to the contract's
characters and lengths, and every name resolves to a file."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap

import pytest

from bench_paths import BENCH_DIR, FIXTURES, ROOT, on_path

on_path()
from harness import readers  # noqa: E402
from harness.spec import Cell  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with all 24 cells has to fit into 43200 s
    runs = 2 + 14 * 24
    assert (runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)
    assert 1 <= len(bench["command"]) <= 32
    assert all(one_line(w) for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert FILE.match(p) and len(p) <= 200 and not p.startswith("/")
        assert ".." not in p.split("/")
    assert bench["command"][1].startswith(bench["paths"][0] + "/")


def test_names_units_and_whys(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_end_to_end_metrics(bench):
    names = [m["name"] for m in bench["end_to_end"]]
    assert "setup_s" in names and 1 <= len(names) <= 16
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def test_every_cell_resolves_and_reports_enough(bench):
    used = set()
    for w in bench["workloads"]:
        cell = Cell(w["name"])
        used.add(w["config"])
        assert cell.cfg["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.rate_metric()["name"] != "setup_s"
        assert len(cell.per_layer) >= 1
        assert cell.reference().leaves(cell.cfg)
        known = {"loss1", "loss2", "loss3", "grad1", "grad1_median",
                 "change3", "change3_median"}
        # every number that `compare` gives is named: held to a limit, or
        # not compared with its reason
        assert cell.limits and not set(cell.limits) & set(cell.not_compared)
        assert set(cell.limits) | set(cell.not_compared) == known
        assert all(0 < v < 1 for v in cell.limits.values())
        assert all(e["why"] for e in cell.not_compared.values())
        if cell.resolved:
            assert set(cell.resolved) == {"mode", "within"}
            assert 0 < cell.resolved["within"] <= 1
        toy = Cell(w["name"], rehearse=True)
        assert toy.traffic["batch"] < cell.traffic["batch"]
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert FILE.match(c["file"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["reduced"] == c["reduced"]


def test_per_layer_metrics_match_their_files_and_cells(bench):
    assert 1 <= len(bench["per_layer"]) <= 128
    cells = {w["name"] for w in bench["workloads"]}
    reports = {w: {m["name"] for m in bench["end_to_end"]
                   if w in m.get("workloads", cells)} for w in cells}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert one_line(m["layer"]) and m["moves"] in end_to_end
        # every metric names its cells, so that a later cell meets none that
        # it would join unasked
        assert m["workloads"] and set(m["workloads"]) <= cells, m["name"]
        for w in m["workloads"]:
            assert m["moves"] in reports[w], (m["name"], w)
        with open(os.path.join(BENCH_DIR, "metrics",
                               m["name"] + ".json")) as f:
            held = json.load(f)
        # which cells report it is said once, in `BENCHMARK.json`: a later
        # PR lists its cell there and edits no metric's file
        assert "workloads" not in held
        assert {k: held[k] for k in m if k != "workloads"} == {
            k: v for k, v in m.items() if k != "workloads"}
        assert held.get("reader_file") or held["reader"] in readers.READERS
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    # a share of a roofline or of a peak is a percentage with its own name
    for m in bench["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
    rooflines = [m for m in bench["per_layer"] if "roofline" in m["name"]]
    for r in rooflines:     # the whole step's share stands beside it
        assert any("mfu" in m["name"].split(".")[0]
                   and m["moves"] == r["moves"] for m in bench["per_layer"])


def test_files_under_paths_are_named_from_allowed_characters(bench):
    for top in bench["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                if name.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert FILE.match(rel), rel
    for name in os.listdir(os.path.join(BENCH_DIR, "traffic")):
        assert name.endswith((".json", ".jsonl", ".toml", ".txt", ".csv"))


def _digests(top):
    out = {}
    for folder, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_later_pr_adds_a_cell_by_files_alone(tmp_path, bench):
    """A new configuration, traffic mix, per-layer metric (with a reader of
    its own) and cell: files added and `BENCHMARK.json` entries, and not one
    file that was there is edited."""
    root = tmp_path / "repo"
    shutil.copytree(BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root / "benchmark")
    copy = str(root / "benchmark")

    with open(os.path.join(copy, "configs", "bert_base.json")) as f:
        cfg = json.load(f)
    cfg.update(name="bert_large", dim=1024, n_layers=24, n_heads=16,
               hidden_dim=4096)
    (root / "benchmark/configs/bert_large.json").write_text(json.dumps(cfg))
    (root / "benchmark/traffic/seq512_b8.json").write_text(json.dumps(
        {"name": "seq512_b8", "kind": "mlm_tokens", "batch": 8, "seq": 512,
         "mask_share": 0.15, "rehearse": {"batch": 2, "seq": 32}}))
    (root / "benchmark/limits/bert_large_s512.json").write_text(json.dumps(
        {"limits": {"loss1": 1e-3},
         "not_compared": {"grad1": {"why": "not read yet"}}}))
    (root / "benchmark/metrics/steps_traced.tok.json").write_text(json.dumps(
        {"name": "steps_traced.tok", "unit": "count", "better": "higher",
         "source": "device_trace", "layer": "Device", "moves": "tok_per_s",
         "reader_file": "steps_traced.tok.py", "params": {"scale": 2}}))
    (root / "benchmark/metrics/steps_traced.tok.py").write_text(
        "def read(run, params):\n"
        "    return params['scale'] * run['trace']['devices'][0]['steps']\n")
    later = json.loads(json.dumps(bench))
    later["configs"].append({
        "name": "bert_large", "source": "arXiv:1810.04805, BERT-large",
        "file": "benchmark/configs/bert_large.json", "reduced": [],
        "why": "more of the same"})
    later["workloads"].append({
        "name": "bert_large_s512", "config": "bert_large",
        "traffic": "seq512_b8", "chips": 1, "why": "phase 2 at full width"})
    for m in later["end_to_end"]:
        if m["name"] == "tok_per_s":
            m["workloads"].append("bert_large_s512")
    later["per_layer"].append({
        "name": "steps_traced.tok", "unit": "count", "better": "higher",
        "source": "device_trace", "layer": "Device", "moves": "tok_per_s",
        "workloads": ["bert_large_s512"]})
    for m in later["per_layer"]:
        if m["name"] in ("mfu.tok", "compile_s"):
            m["workloads"].append("bert_large_s512")
    (root / "BENCHMARK.json").write_text(json.dumps(later))

    cell = Cell("bert_large_s512", bench_dir=copy, root=str(root))
    assert cell.cfg["dim"] == 1024 and cell.traffic["seq"] == 512
    assert cell.rate_metric()["name"] == "tok_per_s"
    assert cell.limits == {"loss1": 1e-3} and "grad1" in cell.not_compared
    assert cell.resolved is None
    assert cell.reference().leaves(cell.cfg)[-1][0].startswith("layers.23.")
    mine = {m["name"]: m for m in cell.per_layer}
    assert {"steps_traced.tok", "mfu.tok", "compile_s"} <= set(mine)
    assert "flash_roofline" not in mine
    run = {"trace": {"devices": [{"steps": 7}]}}
    assert readers.read(mine["steps_traced.tok"], run, copy) == 14
    after = _digests(root / "benchmark")
    assert {k: after[k] for k in before} == before


# ---------------------------------------------------- a new architecture
TOY = os.path.join(FIXTURES, "toy_lm")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# what a later PR writes into `BENCHMARK.json` for a token cell: the
# metrics every token cell reports, among them all that the rules of the
# reader tests give its program (its passes, the kernels it calls); its own
# block metric comes with its files
TOY_JOINS = [
    "tok_per_s", "mfu.tok", "dispatch_ms_per_step.tok",
    "compiles_in_window.tok", "device_idle_share.tok", "peak_hbm_gb.tok",
    "step_hbm_gb.tok", "launch_ms_per_step.tok",
    "dispatch_other_ms_per_step.tok", "setup_trace_s", "setup_lower_s",
    "setup_xla_s", "compile_s", "programs_compiled",
    "forward_ms_per_step.tok", "backward_ms_per_step.tok",
    "optimizer_ms_per_step.tok", "scope_unmapped_share.tok",
    "flash_fwd_ms_per_step", "flash_dq_ms_per_step", "flash_dkv_ms_per_step"]


def copy_with_the_toy(tmp_path, bench):
    """A copy of the benchmark and of its tests with what `fixtures/toy_lm/`
    holds laid into it: a toy causal language model's reference module
    (with its traffic kind, its FLOPs count and its kernel's work), its
    program module, its configuration, mix, limits, a roofline metric and a
    metric of its own scope; and the entries of `BENCHMARK.json`. Returns
    (root, the digests of the files that were there)."""
    root = tmp_path / "repo"
    for top in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(ROOT, top), root / top,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(root)
    shutil.copytree(TOY, root / "benchmark", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    added = {os.path.join("benchmark", k) for k in _digests(TOY)}
    assert not added & set(before)      # files added, none laid over
    later = json.loads(json.dumps(bench))
    later["configs"].append({
        "name": "toy_lm", "source": "tests/benchmark/fixtures/toy_lm",
        "file": "benchmark/configs/toy_lm.json", "reduced": [],
        "why": "a causal decoder: a loss, a traffic kind and counts of its "
               "own"})
    later["workloads"].append({
        "name": "toy_lm_b8", "config": "toy_lm", "traffic": "causal_b8_s128",
        "chips": 1, "why": "8 sequences of 128 tokens, next-token loss"})
    for name in sorted(os.listdir(os.path.join(TOY, "metrics"))):
        with open(os.path.join(TOY, "metrics", name)) as f:
            own = json.load(f)
        later["per_layer"].append(dict(
            {k: own[k] for k in ("name", "unit", "better", "source",
                                 "layer", "moves")},
            workloads=["toy_lm_b8"]))
    for m in later["end_to_end"] + later["per_layer"]:
        if m["name"] in TOY_JOINS:
            m["workloads"].append("toy_lm_b8")
    (root / "BENCHMARK.json").write_text(json.dumps(later))
    return root, before


def in_the_copy(root, code, *args):
    """Python started in the copy as `run.py` is: the copy's own `harness`,
    `reference` and `programs` on the path, the repo's `mxnet_tpu`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(root / "benchmark"), ROOT]))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, *code, *args], cwd=str(root),
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_a_later_pr_adds_an_architecture_by_files_alone(tmp_path, bench):
    """A functional model's loss on `ShardedTrainStep`, a traffic kind, a
    train-FLOPs count and a kernel's work, none of which the harness has:
    the rehearsal runs to its end and is correct, the readers find the
    counts, and no file that was there is edited."""
    root, before = copy_with_the_toy(tmp_path, bench)
    p = in_the_copy(root, [str(root / "benchmark" / "run.py")],
                    "--workload", "toy_lm_b8", "--seed", str(2 ** 31 + 28),
                    "--seconds", "1", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-1] == "REHEARSAL (cpu): not a chip result"
    result = json.loads(lines[-2])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["compared"]) == {
        "loss1", "loss2", "loss3", "grad1", "grad1_median", "change3",
        "change3_median"}
    assert result["metrics"]["compiles_in_window.tok"]["value"] == 0
    assert result["metrics"]["launch_ms_per_step.tok"]["value"] > 0

    # the readers over a made-up traced run, in the copy: required FLOPs and
    # the kernel's work come from the toy's reference module
    script = textwrap.dedent("""
        import json, sys
        from harness import readers
        from harness.spec import Cell
        cell = Cell('toy_lm_b8')
        dev = {'steps': 10, 'window_s': 0.5, 'busy_s': 0.4,
               'category_s': {'mosaic': 0.001}}
        run = {'cfg': cell.cfg, 'traffic': cell.traffic, 'chips': 1,
               'reference': cell.reference(), 'notes': [],
               'samples_per_step': 8 * 128, 'peak': json.loads(sys.argv[1]),
               'trace': {'devices': [dev]}}
        mine = {m['name']: m for m in cell.per_layer}
        print(json.dumps({n: readers.read(mine[n], run, cell.bench_dir)
                          for n in ('mfu.tok', 'toy_lm_attention_roofline')}
                         | {'notes': run['notes'], 'names': sorted(mine),
                            'where': readers.__file__}))
        """)
    p = in_the_copy(root, ["-c", script], json.dumps(PEAK))
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["where"].startswith(str(root))
    d, h, L, V, S, B = 128, 256, 2, 512, 128, 8
    per_token = 3 * (2 * (L * (4 * d * d + 2 * d * h) + d * V) + L * 2 * S * d)
    assert got["mfu.tok"] == pytest.approx(
        100 * per_token * (10 * B * S / 0.5) / PEAK["bf16_flops_per_s"])
    least = max(B * S * 3 * L * 2 * S * d / PEAK["bf16_flops_per_s"],
                B * S * L * 12 * d * 4 / PEAK["hbm_bytes_per_s"])
    assert got["toy_lm_attention_roofline"] == pytest.approx(
        100 * least * 1e3 / (1e3 * 0.001 / 10))
    assert "toy_lm_attention: bound by" in got["notes"][0]
    assert "flash_roofline" not in got["names"]
    after = _digests(root)
    assert {k: after[k] for k in before} == before


def test_a_later_architecture_meets_the_cell_rules_by_entries_alone(
        tmp_path, bench):
    """The reader tests' checks of which cells list which metric, run in the
    copy over its `BENCHMARK.json`: the toy's cell is held to what its own
    program holds (its passes, the block it scopes, the kernels it calls)
    and passes, with no file that was there edited. The tests hold no list
    of cells for it to join."""
    from test_benchmark_span_readers import RULED
    root, before = copy_with_the_toy(tmp_path, bench)
    tests = root / "tests" / "benchmark"
    p = in_the_copy(root, [
        "-m", "pytest", "-q", "-p", "no:cacheprovider", "-p", "no:randomly",
        "%s::test_each_metric_is_reported_in_its_cells_and_in_no_other"
        "[toy_lm_b8]" % (tests / "test_benchmark_scope_readers.py"),
        "%s::test_each_new_metric_resolves_in_its_cells_alone"
        % (tests / "test_benchmark_span_readers.py")])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "%d passed" % (1 + len(RULED)) in p.stdout, p.stdout[-1000:]
    after = _digests(root)
    assert {k: after[k] for k in before} == before


@pytest.mark.parametrize("table,name", [
    ("TRAIN_FLOPS_PER_SAMPLE", "bert"), ("KERNEL_WORK", "attention_core"),
    ("KINDS", "mlm_tokens")])
def test_a_name_the_harness_defines_cannot_be_defined_again(table, name):
    """Nothing shadows a formula of the yardstick: a reference module that
    brings a name which the harness's own table has stops the run."""
    import types

    from harness import flops, traffic
    from harness.spec import lookup
    own = {"TRAIN_FLOPS_PER_SAMPLE": flops.TRAIN_FLOPS_PER_SAMPLE,
           "KERNEL_WORK": flops.KERNEL_WORK, "KINDS": traffic.KINDS}[table]
    plain = types.ModuleType("reference.plain")
    assert lookup(table, name, own, plain) is own[name]
    assert lookup(table, name, own, None) is own[name]
    mine = types.ModuleType("reference.mine")
    setattr(mine, table, {"mine": len})
    assert lookup(table, "mine", own, mine) is len
    with pytest.raises(SystemExit, match="no 'other'"):
        lookup(table, "other", own, mine)
    setattr(mine, table, {name: len})
    with pytest.raises(SystemExit, match="give it another name"):
        lookup(table, name, own, mine)
