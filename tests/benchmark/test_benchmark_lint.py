"""`BENCHMARK.json` and the files under `benchmark/` keep to the contract's
characters and lengths, and every name resolves to a file."""
import hashlib
import json
import os
import re
import shutil

import pytest

from bench_paths import BENCH_DIR, ROOT, on_path

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
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
        assert one_line(m["layer"]) and m["moves"] in end_to_end
        for w in m.get("workloads", cells):
            assert m["moves"] in reports[w], (m["name"], w)
        if "workloads" not in m:    # then every cell that reports `moves`
            assert all(m["moves"] in reports[w] for w in cells)
        with open(os.path.join(BENCH_DIR, "metrics",
                               m["name"] + ".json")) as f:
            held = json.load(f)
        assert {k: held[k] for k in m} == m
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
         "workloads": ["bert_large_s512"],
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
        if m["name"] == "mfu.tok":
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
