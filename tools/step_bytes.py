#!/usr/bin/env python3
"""What a benchmark cell's compiled step reads and writes, instruction by
instruction, with no chip.

    JAX_PLATFORMS=cpu python tools/step_bytes.py --workload resnet50_b256
    python tools/step_bytes.py --hlo <dumped *after_optimizations.txt[.gz]>

The first form builds the cell's own runner on the CPU with the real
configuration and mix, takes the step's jitted function and its first call's
arguments where the step hands them to its AOT cache (`_maybe_aot`, before
anything runs), and lowers them as `ShapeDtypeStruct`s placed on a described
`v5e:2x2` device (a mesh of them for a cell on four chips): the TPU compiler
that is installed here builds the program the chip would run, in about a
minute for ResNet-50. A `ShardedTrainStep`'s jitted function carries the
shardings of the CPU's mesh, whatever its arguments say, so that step is
built again for a mesh of described chips (`rebuild_for_mesh`), with the
flash kernels on: their gate asks the default backend, which is the CPU
here. The second form reads a module that a chip run dumped
(`XLA_FLAGS="--xla_dump_to=... --xla_dump_hlo_as_text
--xla_dump_hlo_module_re=jit_run"`, the Gluon step's module; the sharded
step's is `jit_step_fn`); its instruction names are the trace's. A step compiled here has the same structure under other numbers.

Printed: the compiler's memory account, then operand and result bytes of
every instruction of the entry computation by category (a `kind=kOutput`
fusion is a convolution or matrix product with what XLA fused around it;
`transpose(` in an `op_name` is the backward pass), then the fusions that
read one large tensor and return only vectors: passes that a producer's
epilogue could have carried. Bytes are what the instruction's operands and
results hold, so a tensor that three fusions read counts three times. A
Mosaic call is the exception: it moves the blocks its grid asks for, which
may be a third of an array it was handed three times, so where the step
was traced here its operands and results count the blocks that change from
one grid step to the next (`mosaic_traffic`; a dumped module has no grid to
read, and its calls count whole arrays). The second set of columns leaves
out every array that the compiler placed in
the chip's fast memory (`S(1)` in its layout), which moves no HBM byte, and
its sum over the chip's 819 GB/s is the least time the step's memory
traffic can take. It proves structure and counts bytes; a time or a rate
comes only from a chip run (`PERF.md`).
"""
import argparse
import collections
import gzip
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
HBM_GB_PER_S = 819.0        # one v5e chip (benchmark/harness/peaks.json)
LARGE = 1 << 20             # bytes: an activation, not a per-channel vector
ITEM = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
        "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
        "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE = re.compile(r"\b(%s)\[([\d,]*)\](\{[^}]*\})?" % "|".join(ITEM))
_INSTR = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")


def _sizes(types):
    """(bytes, bytes in HBM) of each array in a result type (a tuple gives
    several). An array that the compiler placed in the chip's fast memory
    (`S(1)` in its layout) moves no HBM byte when it is written or read."""
    out = []
    for dtype, dims, layout in _SHAPE.findall(types):
        n = ITEM[dtype]
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append((n, 0 if "S(1)" in layout else n))
    return out


def entry_instructions(text):
    """The entry computation's instructions: name, opcode, sizes of each
    result, operand names, fusion kind, `op_name`."""
    body = text[text.index("ENTRY "):]
    out = []
    for line in body.splitlines()[1:]:
        m = _INSTR.match(line)
        if not m:
            continue
        name, types, opcode, rest = m.groups()
        if opcode.startswith("async-"):     # a dump's `%slice-start.7`
            opcode = name.lstrip("%").split(".")[0]
        args = rest.split("), ")[0] if "), " in rest else rest.rstrip(")")
        kind = re.search(r"kind=(\w+)", rest)
        op_name = re.search(r'op_name="([^"]*)"', rest)
        out.append({"name": name, "opcode": opcode, "results": _sizes(types),
                    "operands": re.findall(r"%[\w.\-]+", args),
                    "kind": kind.group(1) if kind else "",
                    "op_name": op_name.group(1) if op_name else ""})
    return out


def traffic(instructions, mosaic=None):
    """(instruction, sizes of each operand, sizes of each result) for every
    instruction that moves data: a tuple, a get-tuple-element, a parameter,
    a bitcast or a constant only names what another one holds, and the
    `-done` half of an asynchronous copy is counted at its `-start`.
    `mosaic`, from `mosaic_traffic`, gives a Mosaic call the bytes its grid
    moves in place of its whole operands and results."""
    held = {ins["name"]: ins["results"] for ins in instructions}
    rows = []
    for ins in instructions:
        if ins["opcode"] in ("get-tuple-element", "tuple", "parameter",
                             "bitcast", "constant", "copy-done",
                             "slice-done", "all-reduce-done"):
            continue
        # a tuple-shaped operand is read through its elements' names
        reads = [held[name][0] for name in ins["operands"]
                 if len(held.get(name, ())) == 1]
        writes = ins["results"]
        if ins["opcode"] == "custom-call" and mosaic:
            moved = mosaic.get((ins["name"].lstrip("%").rsplit(".", 1)[0],
                                tuple(n for n, _ in reads),
                                tuple(n for n, _ in writes)))
            if moved:
                reads, writes = ([(n, n if hbm else 0)
                                  for n, (_, hbm) in zip(by_grid, whole)]
                                 for by_grid, whole in zip(moved,
                                                           (reads, writes)))
        if ins["opcode"] == "copy-start":
            writes = writes[:1]     # (the copy, its source again, a context)
        elif ins["opcode"] == "slice-start":
            writes = writes[1:2]    # ((its source again), the slice, ...)
            reads = [(writes[0][0], writes[0][0] if hbm else 0)
                     for _, hbm in reads]
        rows.append((ins, reads, writes))
    return rows


def category(ins):
    back = "transpose(" in ins["op_name"]
    if ins["opcode"] == "fusion" and ins["kind"] == "kOutput":
        return "convolution fusions, " + (
            "backward" if back else "forward" if ins["op_name"]
            else "unnamed")
    if ins["opcode"] == "fusion":
        if "optimizer" in ins["op_name"]:
            return "loop fusions, optimizer"
        return "loop fusions, " + ("backward" if back else "forward")
    if ins["opcode"] == "custom-call" and "pallas_call" in ins["op_name"]:
        return "Mosaic calls, " + ("backward" if back else "forward")
    return "other (%s)" % ins["opcode"]


def vector_passes(rows):
    """The rows of fusions that read exactly one large operand and return
    nothing large: a reduction to vectors that stands alone."""
    return [(ins, reads, writes) for ins, reads, writes in rows
            if ins["opcode"] == "fusion" and ins["kind"] != "kOutput"
            and sum(r >= LARGE for r, _ in reads) == 1
            and not any(w >= LARGE for w, _ in writes)]


def report(text, mosaic=None):
    """Print the table; return its counts."""
    say = print
    instructions = entry_instructions(text)
    rows = traffic(instructions, mosaic)
    table = {}
    for ins, reads, writes in rows:
        row = table.setdefault(category(ins), [0, 0, 0, 0, 0])
        row[0] += 1
        for i, sizes in ((1, reads), (3, writes)):
            row[i] += sum(n for n, _ in sizes)
            row[i + 1] += sum(hbm for _, hbm in sizes)
    say("%-40s %5s | %8s %8s %7s | %8s %8s %7s" % (
        "category", "n", "read GB", "written", "ms@819", "HBM read",
        "written", "ms@819"))
    total = [sum(col) for col in zip(*table.values())]
    for cat, row in sorted(table.items()) + [("whole step", total)]:
        n, r, rh, w, wh = row
        say("%-40s %5d | %8.3f %8.3f %7.2f | %8.3f %8.3f %7.2f" % (
            cat, n, r / 1e9, w / 1e9, (r + w) / 1e6 / HBM_GB_PER_S,
            rh / 1e9, wh / 1e9, (rh + wh) / 1e6 / HBM_GB_PER_S))
    passes = vector_passes(rows)
    read = sum(n for _, reads, _ in passes for n, _ in reads)
    say("fusions that read one large operand and return only vectors: %d, "
        "reading %.3f GB (%.1f%% of the step's bytes)" % (
            len(passes), read / 1e9, 100.0 * read / (total[1] + total[3])))
    kinds = collections.Counter(
        "/".join(ins["op_name"].split("/")[-3:]) for ins, _, _ in passes)
    for what, n in kinds.most_common():
        say("  %4d  %s" % (n, what))
    var = sum("jit(_var)" in i["op_name"] for i in instructions)
    say("instructions with jit(_var) in their op_name: %d" % var)
    return {"bytes_read": total[1], "bytes_written": total[3],
            "hbm_bytes_read": total[2], "hbm_bytes_written": total[4],
            "instructions": total[0], "vector_passes": len(passes),
            "vector_pass_bytes": read, "jit_var_instructions": var,
            "fusions": sum(i["opcode"] == "fusion" for i in instructions),
            "table": table}


def _pallas_calls(jaxpr):
    """Every `pallas_call` equation of a jaxpr and of the jaxprs inside
    it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_calls(sub)


def mosaic_traffic(jaxpr):
    """{(kernel's name, bytes of each operand, bytes of each result):
    (bytes its grid reads of each operand, bytes it writes of each result)}
    for the Pallas calls of a traced step. The pipeline moves a block when
    its index changes from one grid step to the next (the grid runs with
    its last dimension fastest); an operand left in `ANY` memory is moved
    by the kernel's own copies, which are not seen here, and counts
    nothing."""
    import jax
    import numpy as np
    out = {}
    for eqn in _pallas_calls(jaxpr):
        mapping = eqn.params["grid_mapping"]
        if mapping.num_index_operands:      # indices from scalar prefetch
            continue
        steps = np.indices(mapping.grid).reshape(
            len(mapping.grid), -1).astype(np.int32)
        whole, moved = [], []
        for block in mapping.block_mappings:
            aval = block.array_aval
            item = np.dtype(aval.dtype).itemsize
            whole.append(item * int(np.prod(aval.shape)))
            if getattr(block.transformed_block_aval, "memory_space",
                       None) is not None:
                moved.append(0)
                continue
            index_map = block.index_map_jaxpr
            index = np.stack(jax.vmap(lambda *i: jax.core.eval_jaxpr(
                index_map.jaxpr, index_map.consts, *i))(*steps))
            fetches = 1 + int(np.any(index[:, 1:] != index[:, :-1],
                                     axis=0).sum())
            moved.append(fetches * item
                         * int(np.prod(block.block_aval.shape)))
        n = mapping.num_inputs
        out[(eqn.params["name"], tuple(whole[:n]), tuple(whole[n:]))] = (
            moved[:n], moved[n:])
    return out


class _Captured(Exception):
    pass


def capture_step(workload, seed):
    """(jitted, args) of the cell's step, from its runner built on the CPU
    at the real size; nothing of the step runs. A `ShardedTrainStep` comes
    as (the step object, args): its jitted function is bound to the CPU's
    mesh and has to be built again for another."""
    sys.path[:0] = [ROOT, BENCH_DIR]
    from harness import runners, traffic as mixes
    from harness.spec import Cell
    from mxnet_tpu.gluon.fused_step import FusedTrainStep
    from mxnet_tpu.parallel.train_step import ShardedTrainStep

    cell = Cell(workload)
    cfg, mix, reference = cell.cfg, cell.traffic, cell.reference()
    start, batch = mixes.make(seed, reference, cfg, mix)
    runner = runners.RUNNERS[cfg["entry"]](cfg, mix, reference, start, batch,
                                           True)
    got = {}

    def fused(self, jitted, step_args, sig, fmt_key):
        got["step"] = (jitted, step_args)
        raise _Captured

    def sharded(self, params, opt_state, batch, step_num, sig):
        got["step"] = (self, (params, opt_state, batch, step_num))
        raise _Captured

    FusedTrainStep._maybe_aot, ShardedTrainStep._maybe_aot = fused, sharded
    try:
        runner.call()
    except _Captured:
        pass
    return cell, got["step"]


def compile_for_v5e(step, args):
    """(the step compiled by the TPU compiler for described v5e chips, what
    its Mosaic calls move): every argument a shape on the described device,
    or on a mesh of them under the partition it has here. `step` is a
    jitted function, or a `ShardedTrainStep`, which is built again for the
    described mesh."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")

    def described_mesh(here):   # the same axes over as many described chips
        return Mesh(np.array(topo.devices[:here.size]).reshape(
            here.devices.shape), here.axis_names)

    rebuilt = not hasattr(step, "lower")
    if not rebuilt:
        jitted = step
        leaves = [x for x in jax.tree_util.tree_leaves(args)
                  if hasattr(x, "shape")]
        meshes = [x.sharding.mesh for x in leaves
                  if isinstance(getattr(x, "sharding", None), NamedSharding)
                  and x.sharding.mesh.size > 1]
        mesh = described_mesh(meshes[0]) if meshes else None
    else:
        from mxnet_tpu.ops import pallas_stats
        # the kernels' gate asks the default backend, which is the CPU
        pallas_stats.pallas_on = lambda: True
        mesh = described_mesh(step.mesh)
        described_step = step.rebuild_for_mesh(mesh)
        described_step._batch_proto = step._batch_proto
        jitted = described_step._build(*args[:2])
        args = args[:3] + (np.int32(args[3]),)

    def described(x):
        if not hasattr(x, "shape"):
            return x
        here = getattr(x, "sharding", None)
        if mesh is None:
            there = SingleDeviceSharding(topo.devices[0])
        elif isinstance(here, NamedSharding) and (
                rebuilt or here.mesh.size > 1):
            there = NamedSharding(mesh, here.spec)
        else:       # held by one chip here: on every chip of the mesh
            there = NamedSharding(mesh, PartitionSpec())
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=there)

    jax.config.update("jax_enable_compilation_cache", False)
    traced = jitted.trace(*jax.tree_util.tree_map(described, args))
    return traced.lower().compile(), mosaic_traffic(traced.jaxpr.jaxpr)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="a cell of BENCHMARK.json")
    ap.add_argument("--hlo", help="a dumped optimized module to read instead")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keep", help="write the compiled module's text here")
    ap.add_argument("--json", help="write the counts here")
    opts = ap.parse_args(argv)
    mosaic = None
    if opts.hlo:
        opener = gzip.open if opts.hlo.endswith(".gz") else open
        with opener(opts.hlo, "rt") as f:
            text = f.read()
    else:
        cell, (step, args) = capture_step(opts.workload, opts.seed)
        compiled, mosaic = compile_for_v5e(step, args)
        print("compiled for a described v5e:2x2 (%d chip(s)); no chip ran "
              "anything" % cell.chips)
        print("memory: %s" % (compiled.memory_analysis(),))
        text = compiled.as_text()
        if opts.keep:
            with open(opts.keep, "w") as f:
                f.write(text)
    counts = report(text, mosaic)
    if opts.json:
        with open(opts.json, "w") as f:
            json.dump(counts, f)


if __name__ == "__main__":
    main()
