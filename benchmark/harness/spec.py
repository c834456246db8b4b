"""Find a cell's files by the names in `BENCHMARK.json`.

Nothing here knows a cell, a configuration, a mix or a metric by name: a
later PR adds files and entries, and edits no file that is there. Code comes
the same way as data: a configuration's `reference` and `program` keys name
modules under `reference/` and `programs/` (`module`), and a traffic kind, a
FLOPs count or a kernel's work that the harness's own tables lack is looked
for in the table of the same name in the reference module (`lookup`).
"""
import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    with open(path) as f:
        return json.load(f)


def module(folder, name):
    """`benchmark/<folder>/<name>.py`, by the name a configuration gives."""
    return importlib.import_module(folder + "." + name)


def lookup(table, name, own, reference):
    """The function that `name` stands for in `own`, one of the harness's
    tables (`table` is its name there: `KINDS`, `TRAIN_FLOPS_PER_SAMPLE`,
    `KERNEL_WORK`), or in the table of that name that the configuration's
    reference module keeps: where a new architecture brings its own. A name
    in both is an error: nothing shadows a formula of the yardstick."""
    theirs = getattr(reference, table, {})
    if name in own and name in theirs:
        raise SystemExit(
            "benchmark: %s has %r of its own, which the harness's %s "
            "defines: give it another name" % (reference.__name__, name,
                                               table))
    if name in own:
        return own[name]
    if name in theirs:
        return theirs[name]
    raise SystemExit("benchmark: no %r in the harness's %s%s" % (
        name, table, " or in %s's" % reference.__name__
        if reference is not None else ""))


class Cell:
    """One entry of `workloads`, with its configuration, its traffic mix,
    its limits and the metrics it reports, each read from its own file."""

    def __init__(self, name, bench_dir=BENCH_DIR, root=None, rehearse=False):
        root = root or os.path.dirname(bench_dir)
        self.bench_dir = bench_dir
        self.benchmark = _load(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in cells:
            raise SystemExit("benchmark: no workload %r in BENCHMARK.json "
                             "(there are: %s)" % (name, ", ".join(cells)))
        self.name = name
        self.entry = cells[name]
        self.chips = self.entry["chips"]
        self.rehearse = rehearse
        configs = {c["name"]: c for c in self.benchmark["configs"]}
        self.cfg = self._sized(_load(os.path.join(
            root, configs[self.entry["config"]]["file"])))
        self.traffic = self._sized(_load(os.path.join(
            bench_dir, "traffic", self.entry["traffic"] + ".json")))
        limits = _load(os.path.join(bench_dir, "limits", name + ".json"))
        self.limits = limits["limits"]
        self.not_compared = limits.get("not_compared", {})
        self.resolved = limits.get("resolved")
        self.end_to_end = [m for m in self.benchmark["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            _load(os.path.join(bench_dir, "metrics", m["name"] + ".json"))
            for m in self.benchmark["per_layer"]
            if name in m.get("workloads", [name]) and m["moves"] in reported]
        self.peaks = _load(os.path.join(bench_dir, "harness", "peaks.json"))

    def _sized(self, data):
        """The file as it is run; a rehearsal takes its toy sizes."""
        data = dict(data)
        toy = data.pop("rehearse", {})
        if self.rehearse:
            data.update(toy)
        return data

    def reference(self):
        return module("reference", self.cfg["reference"])

    def rate_metric(self):
        """The end-to-end rate this cell reports (the one that is not
        `setup_s`)."""
        (m,) = [m for m in self.end_to_end if m["name"] != "setup_s"]
        return m

    def peak(self, device_kind):
        if device_kind not in self.peaks:
            raise SystemExit("benchmark: device kind %r is not in "
                             "harness/peaks.json" % device_kind)
        return self.peaks[device_kind]
