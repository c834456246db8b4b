"""What a cell's program holds, read from the program, for the tests that
check which cells `BENCHMARK.json` lists under a per-layer metric.

The tests that use this hold no list of cells for a new cell to join: a
cell lists a metric of a given reader exactly where that reader, handed
what the cell's program holds, reads a number (`read_by_rule`), so a later
cell meets every metric that fits it by its `BENCHMARK.json` entries alone.
What the program holds comes in two readings, each made once a process:

    scope_map(cell)
        (module, {instruction: Instr}) of the step that the cell's
        configuration compiles: `telemetry.module_scopes()` after one call
        of its runner under a profiler session, on the CPU at the
        rehearsal's sizes, as a traced run reads it on the chip. One
        reading a configuration, at the rehearsal mix of its cell with the
        fewest chips. Its weights are zeros: the map is the compiled
        program's, whatever its values.
    kernels(cell)
        the names of the Pallas kernels that the cell's loss and gradient
        call at the cell's own sizes: the configuration's `program` lowered
        for the TPU from shapes with the kernels' gate on, its
        `kernel_name = "..."` read as `chip_smoke.py` reads them.
"""
import functools
import json
import os
import re
import tempfile

from bench_paths import BENCH_DIR, ROOT, on_path

on_path()
from harness import readers, runners, traffic  # noqa: E402
from harness.spec import Cell, module  # noqa: E402


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def metric_file(name):
    with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)


def listed(cell, reader_file):
    """The metrics read by `reader_file` that `BENCHMARK.json` gives
    `cell`."""
    return {m["name"] for m in Cell(cell).per_layer
            if m.get("reader_file") == reader_file}


def read_by_rule(cell, reader_file, run):
    """The metrics read by `reader_file` that move an end-to-end metric
    `cell` reports and whose reader reads a number over `run`."""
    spec = Cell(cell)
    reported = {m["name"] for m in spec.end_to_end}
    held = [metric_file(m["name"]) for m in spec.benchmark["per_layer"]]
    return {m["name"] for m in held
            if m.get("reader_file") == reader_file
            and m["moves"] in reported
            and readers.read(m, run, BENCH_DIR) is not None}


def every_operation_timed(keys, module_name=""):
    """A traced run of one step of the module `module_name` in which each
    operation of `keys`, keyed as the reduction keys one
    (`<category>/<instruction>`), took a second."""
    return {"trace": {"devices": [{"module": module_name + "(0)",
                                   "steps": 1,
                                   "op_s": dict.fromkeys(keys, 1.0)}]},
            "notes": []}


@functools.lru_cache(maxsize=None)
def _scope_map_of(config):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import telemetry

    (_, _, cell) = min((w["chips"], i, w["name"]) for i, w in enumerate(
        benchmark()["workloads"]) if w["config"] == config)
    spec = Cell(cell, rehearse=True)
    cfg, mix, reference = spec.cfg, spec.traffic, spec.reference()
    shapes = jax.eval_shape(traffic.Maker(reference, cfg, mix).program,
                            traffic.seed_key(0))
    start, batch = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    runner = runners.RUNNERS[cfg["entry"]](cfg, mix, reference, start,
                                           batch, True)
    was_enabled = telemetry.ENABLED
    telemetry.enable()
    try:
        runner.call().block_until_ready()       # compiled outside it
        with tempfile.TemporaryDirectory(prefix="cell_rules_") as tmp:
            with jax.profiler.trace(tmp):
                runner.call().block_until_ready()
        held = telemetry.module_scopes().get(runner.module)
    finally:
        (telemetry.enable if was_enabled else telemetry.disable)()
        name = runner.module
        runner.free()
    assert held, "no scope map of %s after a traced call" % name
    return name, held


def scope_map(cell):
    return _scope_map_of(Cell(cell).entry["config"])


@functools.lru_cache(maxsize=None)
def kernels(cell):
    import jax

    from mxnet_tpu.ops import pallas_stats

    spec = Cell(cell)
    cfg, mix, reference = spec.cfg, spec.traffic, spec.reference()
    assert "program" in cfg, (
        "%s: no functional loss to lower; its kernels are not read" % cell)
    flat, batch = jax.eval_shape(traffic.Maker(reference, cfg, mix).program,
                                 traffic.seed_key(0))
    tree = {}
    for name, leaf in flat.items():         # as `runners.ShardedStep` nests
        node = tree
        *path, last = name.split(".")
        for key in path:
            node = node.setdefault(key, {})
        node[last] = leaf
    loss = module("programs", cfg["program"]).loss_fn(cfg)
    gate = pallas_stats.pallas_on
    pallas_stats.pallas_on = lambda: True
    try:
        text = jax.jit(jax.value_and_grad(loss)).trace(tree, batch).lower(
            lowering_platforms=("tpu",)).as_text()
    finally:
        pallas_stats.pallas_on = gate
    return frozenset(re.findall(r'kernel_name = "(\w+)"', text))
