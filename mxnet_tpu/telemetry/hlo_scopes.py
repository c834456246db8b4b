"""The scope of every instruction of a compiled step, read from an optimized
HLO module's text.

A device trace names an operation by its instruction (`%fusion.183 = ...`)
and knows nothing of `jax.named_scope`s; the module that was compiled knows
every instruction's `op_name`, the name stack it was traced under:

    jit(step_fn)/jvp(forward)/gdn/delta_scan/while
    jit(step_fn)/transpose(jvp(forward))/jvp(forward)/checkpoint/
        rematted_computation/gdn/mul
    jit(step_fn)/optimizer/sub

`parse` reads the module, `path` reads one `op_name` as a pass of the step
and the scopes it lies under. `telemetry.note_step_program` keeps the map
of the step that a profiler session saw (`telemetry.module_scopes()`), and
`benchmark/metrics/scope_ms_per_step.py` joins it with the trace's
per-instruction device time. `tools/moe_rungs.py` files a window's
operations under the loop bodies that `parse` gives.
(`tools/step_bytes.py` keeps a reading of its own, of the entry computation
alone: it counts bytes from result types and operands, which `Instr` does
not keep.)
"""
import collections
import functools
import re

__all__ = ["CONTAINERS", "PASSES", "Instr", "parse", "path"]

# A container's own event in a trace covers its body's instructions, which
# the trace lists too: a sum over a scope leaves containers out.
CONTAINERS = ("conditional", "while", "call")
PASSES = ("forward", "recomputed", "backward", "optimizer", "none")

Instr = collections.namedtuple("Instr", "opcode op_name computation calls")

_HEADER = re.compile(r"^(?:ENTRY )?(%?[\w.\-]+) \(.*\) -> .* \{$")
# `%name = <type> opcode(`: the type may be a tuple with layouts, but the
# first lower-case word that a space precedes and a bracket follows is the
# opcode (`harness/trace_reduce.py` reads a trace event's name the same way)
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def parse(text):
    """{instruction: Instr(opcode, op_name, computation, calls)} of an
    optimized HLO module's text: every instruction of every computation
    (names are unique module-wide), the `op_name` of its metadata ("" where
    it has none), the computation it lies in and the computations it calls
    (a fusion's, a loop's body and condition, a conditional's branches in
    their order, a reduce's `to_apply`)."""
    out, computation = {}, None
    for line in text.splitlines():
        if computation is None:
            m = _HEADER.match(line)
            if m:
                computation = m.group(1).lstrip("%")
            continue
        if line == "}":
            computation = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, opcode = m.groups()
        # plain finds before any further pattern: a step has tens of
        # thousands of lines, some of them kilobytes long
        at = line.find('op_name="')
        op_name = line[at + 9:line.index('"', at + 9)] if at >= 0 else ""
        calls = ()
        if "=%" in line or "={%" in line:
            calls = _CALLED.findall(line)
            branches = _BRANCHES.search(line)
            if branches:
                calls += [b.strip().lstrip("%")
                          for b in branches.group(1).split(",") if b.strip()]
        out[name] = Instr(opcode, op_name, computation, tuple(calls))
    return _under_their_callers(out)


def _under_their_callers(instrs):
    """`instrs` with every `op_name` that lacks the stack it was called
    under put behind its caller's. A jitted function that the step lowers
    once carries the first call site's stack in most of its instructions,
    but the gathers and scatter-adds in the body of a loop of `ops/moe.py`
    come out as `while/body/rows_4096/scatter-add`: no `jit(...)`, no pass,
    no `moe`. The instruction that calls their computation (the fusion,
    then the `while`) knows where it lies, so a name that does not start
    with `jit(` goes behind the nearest caller's that does, overlapped
    where the one ends as the other begins (`.../while` and `while/body/
    ...`), else in place of the caller's last part, the primitive."""
    caller = {}
    for name, instr in instrs.items():
        for callee in instr.calls:
            caller.setdefault(callee, name)

    def outer(name):
        """The nearest caller's `op_name` that starts with `jit(`."""
        seen = set()
        while name in instrs and name not in seen:
            seen.add(name)
            name = caller.get(instrs[name].computation)
            if name is not None and instrs[name].op_name.startswith("jit("):
                return instrs[name].op_name
        return None

    out = {}
    for name, instr in instrs.items():
        if instr.op_name and not instr.op_name.startswith("jit("):
            above = outer(name)
            if above is not None:
                a, b = above.split("/"), instr.op_name.split("/")
                n = next((n for n in range(min(len(a), len(b)), 0, -1)
                          if a[-n:] == b[:n]), None)
                instr = instr._replace(op_name="/".join(
                    a + b[n:] if n else a[:-1] + b))
        out[name] = instr
    return out


@functools.lru_cache(maxsize=1 << 16)
def path(op_name):
    """(pass, scopes) of an instruction's `op_name`.

    The name is split on `/`; `jit(...)` and `pjit` parts are dropped; a
    part that a transformation wraps (`jvp(x)`, `transpose(jvp(x))`)
    contributes `x`; the last part, the primitive's own name, is left out.
    `scopes` is the tuple of what remains, in order: `("forward", "gdn",
    "delta_scan")`. Structural parts (`cond`, `branch_1_fun`, `while`,
    `body`, `checkpoint`) stay in it: a reader asks only whether a scope is
    among them. `pass` is the first that holds of: `optimizer` (a scope of
    that name), `recomputed` (a part `rematted_computation`), `backward` (a
    part that starts with `transpose(`), `forward` (a scope of that name),
    else `none`.

    What this cannot tell:

    - A fusion is filed under the `op_name` XLA gave the fusion instruction,
      its root's. A fusion that spans two scopes goes to one side whole:
      `add_subtract_fusion`, the head's dW with AdamW's update fused in, is
      the optimizer's.
    - A `custom_vjp`'s backward keeps the scope it was called under, behind
      `transpose(jvp(...))`: it is `backward` under that scope.
    - What a `custom_vjp`'s backward makes again itself (`ops/moe.py`'s
      `_routed_chunks_vjp` rebuilds every chunk's forward) is `backward`:
      `recomputed` counts `jax.checkpoint`'s work alone.
    - A jitted function that the step lowers once and calls from several
      places carries the first call site's stack: where XLA inlines the
      calls (`ops/linear_attention.py`'s `_inverse_pallas`, the flash
      entries: every call on a v5e), each call site's instructions carry
      that site's whole stack (`.../gdn/delta_chunk/jit(_inverse_pallas)/
      gdn_inverse/pallas_call`, three times). The gathers and scatter-adds
      in the body of `ops/moe.py`'s loops come out with the body's own
      stack alone (`while/body/rows_4096/scatter-add`); `parse` has put
      them behind the `while` that calls them, so they keep their pass and
      `moe` here.
    """
    parts = [p for p in op_name.split("/") if p]
    backward = any(p.startswith("transpose(") for p in parts)
    scopes = []
    for part in parts[:-1]:
        m = _WRAPPED.match(part)
        while m and m.group(1) != "jit":
            part = m.group(2)
            m = _WRAPPED.match(part)
        if m or part == "pjit":
            continue
        scopes.append(part)
    if "optimizer" in scopes:
        which = "optimizer"
    elif "rematted_computation" in scopes:
        which = "recomputed"
    elif backward:
        which = "backward"
    elif "forward" in scopes:
        which = "forward"
    else:
        which = "none"
    return which, tuple(scopes)
