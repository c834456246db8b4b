"""What decides `correct`: the timed path's first steps against the plain
reference's.

Set-up drives the step object that the window then drives through its first
three steps, through the window's own call and feed. Once the window has
closed and the program's state is freed, the reference follows the same three
steps from the same weights and batch in float32, and these are compared:

  loss1..3   each step's loss: |program - reference| / |reference|
  grad1      the norm of the first gradient as the optimizer got it (from its
             state after one step), worst leaf: the gap between the two
             norms over the reference's norm of that leaf or of the median
             leaf, whichever is larger
  change3    the norm of each parameter's change over the three steps, worst
             leaf, measured alike; leaves whose reference gradient is under a
             thousandth of the median leaf's are left out (they move by
             round-off alone)
  grad1_median, change3_median
             the same gaps at the median of the large leaves (4,096 numbers
             or more: the kernels and matrices, nearly all of the
             parameters) instead of the worst of all

Where a cell's limits file has a `resolved` rule, the worst leaf is taken
over the leaves whose first gradient the stated precision resolves, by a
measurement on the reference alone: it follows its first step once more in
the rule's `mode` (the precision the configuration states), and a leaf whose
gradient there lies farther from the float32 one than `within` of its norm
(the norm of the vectors' difference, over the same denominator as above) is
left out of `grad1` and `change3`: on such a leaf two sound computations
differ by as much as a fault would.

Each number that a cell compares has a limit of its own in
`benchmark/limits/<cell>.json`; every other number that `compare` gives has
to be named under `not_compared` there with its reason, is read and printed,
and decides nothing.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

STEPS = 3
NOUGHT = 1e-3       # of the median leaf's gradient: nought to rounding
LARGE = 4096        # numbers in a leaf: a kernel or a matrix, not a bias


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def _change_norms(now, start):
    return _norms({k: now[k].astype(jnp.float32)
                   - start[k].astype(jnp.float32) for k in start})


def follow(runner, start, call=None, keep_first=False):
    """Drive `runner` through its first steps with `call` (the window's own)
    and take the readings, as device arrays that are not awaited.
    `start`: {leaf: array}, the weights both sides begin from. With
    `keep_first` the first gradient itself is kept too, for `unresolved`."""
    call = call or runner.call
    losses = [call()]
    first = runner.first_gradient()
    grad = _norms(first)
    if not keep_first:
        del first
    losses += [call() for _ in range(STEPS - 1)]
    now = runner.params()
    start = {k: jax.device_put(start[k], now[k].sharding) for k in grad}
    change = _change_norms({k: now[k] for k in grad}, start)
    out = {"loss": losses, "grad": grad, "change": change}
    if keep_first:
        out["first"] = first
    return out


def to_host(readings):
    """Await the readings and bring them over as floats."""
    return {"loss": [float(x) for x in readings["loss"]],
            "grad": {k: float(v) for k, v in readings["grad"].items()},
            "change": {k: float(v) for k, v in readings["change"].items()}}


def unresolved(first, stated, within):
    """({leaf: distance}, the leaves left out): how far the reference's
    first gradient in the stated precision (`stated`) lies from its float32
    one (`first`), leaf by leaf: the norm of the difference over the float32
    norm of that leaf or of the median leaf, whichever is larger. A leaf
    farther than `within` is one that the stated precision does not
    resolve."""
    norms = to_host({"loss": [], "grad": _norms(first),
                     "change": _change_norms(stated, first)})
    floor = float(np.median(list(norms["grad"].values())))
    far = {k: norms["change"][k] / max(norms["grad"][k], floor)
           for k in norms["grad"]}
    return far, {k for k, d in far.items() if not d <= within}


def _gaps(mine, ref, keys, large, left_out):
    """((worst gap, its leaf), (median gap of the large leaves, None)): the
    gap between the two norms of each leaf, over the reference's norm of
    that leaf or of the median leaf, whichever is larger; the worst is of
    the leaves not `left_out`."""
    floor = float(np.median([ref[k] for k in keys]))
    gaps = [(abs(mine[k] - ref[k]) / max(ref[k], floor), k) for k in keys]
    middle = float(np.median([g for g, k in gaps if k in large]))
    return max(g for g in gaps if g[1] not in left_out), (middle, None)


def compare(mine, ref, leaves, left_out=frozenset()):
    """{number: (value, worst leaf or None)} of host readings `mine` against
    the reference's `ref`. `leaves`: the reference's [(name, shape, kind)];
    `left_out`: the leaves that `unresolved` found."""
    large = {name for name, shape, _ in leaves if math.prod(shape) >= LARGE}
    out = {}
    for i, (a, b) in enumerate(zip(mine["loss"], ref["loss"])):
        out["loss%d" % (i + 1)] = (abs(a - b) / abs(b), None)
    keys = sorted(ref["grad"])
    out["grad1"], out["grad1_median"] = _gaps(mine["grad"], ref["grad"], keys,
                                              large, left_out)
    median = float(np.median([ref["grad"][k] for k in keys]))
    moved = [k for k in keys if ref["grad"][k] >= NOUGHT * median]
    name = "change%d" % STEPS
    out[name], out[name + "_median"] = _gaps(mine["change"], ref["change"],
                                             moved, large, left_out)
    return out


def judge(numbers, limits, not_compared=()):
    """(correct, compared, read): every number that has a limit beside it,
    and the numbers that were read and decide nothing. A compared number
    that is not finite fails; a cell that compares nothing fails; a number
    that the cell's file names neither under `limits` nor under
    `not_compared` is an error of that file."""
    compared, read, correct = {}, {}, bool(limits)
    for name, (value, leaf) in numbers.items():
        if name not in limits:
            if name not in not_compared:
                raise SystemExit(
                    "benchmark: the cell's limits file names %r neither "
                    "under limits nor under not_compared" % name)
            read[name] = value
            continue
        correct = (correct and math.isfinite(value)
                   and value <= limits[name])
        compared[name] = {"value": value, "limit": limits[name]}
        if leaf is not None:
            compared[name]["leaf"] = leaf
    return correct, compared, read
