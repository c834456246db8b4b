"""From a profiler trace (`.xplane.pb`) to per-device numbers.

Read with `jax.profiler.ProfileData`, nothing else. A TPU trace has one
plane per chip (`/device:TPU:<n>`) whose line `XLA Ops` holds every operation
that ran, named by its whole HLO text, `XLA Modules` every program run, and
`Async XLA Ops` the spans of asynchronous copies and collectives; the host's
annotations are on the line of `/host:CPU` that is named after the program
that was started (`python`, `python3`, ...), on the same clock: the line
that holds the benchmark's own `step` annotations.

The program's kernels and steps carry no names of their own yet, so
operations are grouped by what the HLO text says they are:

  mosaic       a `custom-call` to `tpu_custom_call` (a Pallas kernel)
  convolution  a `convolution`, or a fusion of `kind=kOutput` (on a TPU the
               fusions built around a convolution or a matrix product) or
               with "convolution" in its name
  collective   all-reduce, all-gather, reduce-scatter, all-to-all,
               collective-permute, with their -start and -done halves
  copy         copy, copy-start, copy-done
  fusion       every other fusion
  other        the rest
"""
import re

STEP = "step"       # the annotation `run.py` puts around every call
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP = re.compile(r"^%(\S+) = .*? ([a-z][a-z0-9\-]*)\(")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
CATEGORIES = ("convolution", "mosaic", "fusion", "copy", "collective",
              "other")


def op_name_and_category(text):
    """('fusion.248', 'convolution') of an `XLA Ops` event's name."""
    m = _OP.match(text)
    if not m:
        return text.split(" ")[0].lstrip("%"), "other"
    name, opcode = m.groups()
    base = opcode[:-6] if opcode.endswith("-start") else (
        opcode[:-5] if opcode.endswith("-done") else opcode)
    if base in COLLECTIVES:
        return name, "collective"
    if opcode == "custom-call":
        return name, ("mosaic" if 'custom_call_target="tpu_custom_call"'
                      in text else "other")
    if opcode == "convolution":
        return name, "convolution"
    if opcode == "fusion":
        conv = "kind=kOutput" in text or "convolution" in name
        return name, "convolution" if conv else "fusion"
    if base == "copy":
        return name, "copy"
    return name, "other"


def union(intervals):
    """Sorted, disjoint [(start, end)] covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(disjoint):
    return sum(e - s for s, e in disjoint)


def minus(a, b):
    """Disjoint [(start, end)]: the points of `a` that are not in `b` (both
    sorted and disjoint)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(disjoint):
    """The idle stretches between busy ones."""
    return [(a[1], b[0]) for a, b in zip(disjoint, disjoint[1:])]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _reduce_device(plane):
    ops = _line(plane, "XLA Ops")
    if ops is None:
        return None
    by_cat = {c: [] for c in CATEGORIES}
    by_op = {}
    for ev in ops.events:
        name, cat = op_name_and_category(ev.name)
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        by_cat[cat].append((s, e))
        key = cat + "/" + name
        by_op[key] = by_op.get(key, 0.0) + ev.duration_ns
    everything = [iv for ivs in by_cat.values() for iv in ivs]
    if not everything:
        return None
    busy = union(everything)
    # a collective's asynchronous span, from its start to its done
    spans = list(by_cat["collective"])
    line = _line(plane, "Async XLA Ops")
    for ev in (line.events if line is not None else ()):
        if op_name_and_category(ev.name)[1] == "collective":
            spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    others = union([iv for c, ivs in by_cat.items() if c != "collective"
                    for iv in ivs])
    collective = union(spans)
    # the steps: runs of the program that took most of the device's time
    runs = {}
    line = _line(plane, "XLA Modules")
    for ev in (line.events if line is not None else ()):
        runs.setdefault(ev.name, []).append(ev.duration_ns)
    module, steps = None, 0
    if runs:
        module = max(runs, key=lambda k: sum(runs[k]))
        steps = len(runs[module])
    return {
        "plane": plane.name,
        "window_s": (busy[-1][1] - busy[0][0]) * 1e-9,
        "busy_s": length(busy) * 1e-9,
        "module": module, "steps": steps,
        "category_s": {c: sum(e - s for s, e in ivs) * 1e-9
                       for c, ivs in by_cat.items()},
        "collective_s": length(collective) * 1e-9,
        "collective_exposed_s": length(minus(collective, others)) * 1e-9,
        "op_s": {k: v * 1e-9 for k, v in by_op.items()},
        "gaps_ns": sorted(gaps(busy), key=lambda g: g[0] - g[1])[:5],
    }


def _host_spans(data):
    """[(start, end, name)] of the host's annotations: every event of the
    lines of `/host:CPU` that hold a `step` annotation. The thread that
    drives the steps is found by what it holds: its name is the command's."""
    spans = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in line.events]
            if any(name == STEP for _, _, name in events):
                spans += events
    return spans


def _host_doing(spans, when):
    """The innermost host span that covers `when`."""
    inside = [(e - s, name) for s, e, name in spans if s <= when <= e]
    return min(inside)[1] if inside else "no_host_span"


def reduce_trace(data):
    """{"devices": [per-device numbers, by chip], "idle_gaps": [[what the
    host was doing, seconds], ...] of the first chip's five longest gaps}."""
    devices = []
    for plane in sorted((p for p in data.planes
                         if DEVICE_PLANE.match(p.name)),
                        key=lambda p: int(DEVICE_PLANE.match(p.name)[1])):
        dev = _reduce_device(plane)
        if dev is not None:
            devices.append(dev)
    idle = []
    if devices:
        spans = _host_spans(data)
        idle = [[_host_doing(spans, (s + e) / 2), (e - s) * 1e-9]
                for s, e in devices[0]["gaps_ns"]]
    return {"devices": devices, "idle_gaps": idle}


def load(path):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def loads(raw):
    import jax
    return jax.profiler.ProfileData.from_serialized_xspace(raw)


def breakdown(reduced):
    """The traced run's `breakdown`: the categories and the five longest
    operations of the first chip, and its longest idle gaps."""
    dev = reduced["devices"][0]
    cats = [["all_" + c, s] for c, s in sorted(
        dev["category_s"].items(), key=lambda kv: -kv[1]) if s > 0][:5]
    ops = [[k, s] for k, s in sorted(dev["op_s"].items(),
                                     key=lambda kv: -kv[1])[:10 - len(cats)]]
    return {"device_ops": cats + ops, "idle_gaps": reduced["idle_gaps"][:10]}
