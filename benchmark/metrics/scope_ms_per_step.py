"""Device time of the step's instructions that were traced under given
`jax.named_scope`s, a step, in ms: the trace's per-instruction time joined
with the scope map of the step the program compiled.

The trace names an operation by its instruction (`fusion/fusion.183`) and
knows no scope; `mxnet_tpu.telemetry.module_scopes()` holds, for the step
programs that ran under the profiler session, `{instruction: (opcode,
op_name, ...)}`, and `mxnet_tpu.telemetry.hlo_scopes.path` reads an
`op_name` as `(pass, scopes)`. Over the first chip's `op_s`, the seconds of
every key whose instruction the map holds with an opcode that is no
container (a `conditional`'s, a `while`'s or a `call`'s own event covers its
body's operations, which the trace lists too) and that matches:

    {"pass": "backward"}
        one of forward | recomputed | backward | optimizer | none; absent:
        any
    {"scopes": ["ffn", "dense_mlp"]}
        any of these among the instruction's scopes; `rows_*` matches every
        scope that starts with `rows_`; absent: any
    {"share_unmapped": true}
        instead, in %: the seconds of the keys that the map lacks or files
        under the pass `none`, over the seconds of all keys that are not
        known containers

A program without the map (one from before it, a rehearsal without a device
plane), no step, or nothing matched: None, and the harness leaves the metric
out of the line. Where the program keeps maps and has none of the module
that the trace names (the step was dropped before its map was read, or the
read failed: the program's log says which), the run's notes say so once.
"""


def _program():
    """(module_scopes, path, CONTAINERS) of the program, or None."""
    try:
        from mxnet_tpu.telemetry import hlo_scopes, module_scopes
    except ImportError:
        return None
    return module_scopes, hlo_scopes.path, hlo_scopes.CONTAINERS


def _matches(which, scopes, params):
    if "pass" in params and which != params["pass"]:
        return False
    if "scopes" not in params:
        return True
    return any(s.startswith(want[:-1]) if want.endswith("*") else s == want
               for want in params["scopes"] for s in scopes)


def read(run, params):
    devices = run["trace"]["devices"] if run.get("trace") else []
    program = _program()
    if program is None or not devices or not devices[0]["steps"]:
        return None
    module_scopes, path, containers = program
    module = (devices[0]["module"] or "").split("(")[0]
    maps = module_scopes()
    if not maps.get(module):
        note = ("no scope map of the traced module %r (the program holds %s)"
                ": its metrics by scope are left out" % (module, sorted(maps)))
        if note not in run.setdefault("notes", []):
            run["notes"].append(note)
        return None
    held = maps[module]
    matched = unmapped = everything = 0.0
    for key, seconds in devices[0]["op_s"].items():
        opcode, op_name = held.get(key.split("/", 1)[1], (None, ""))[:2]
        if opcode in containers:
            continue
        everything += seconds
        which, scopes = path(op_name)
        if opcode is None or which == "none":
            unmapped += seconds
        if opcode is not None and _matches(which, scopes, params):
            matched += seconds
    if params.get("share_unmapped"):
        return 100.0 * unmapped / everything if everything > 0 else None
    return 1e3 * matched / devices[0]["steps"] if matched > 0 else None
