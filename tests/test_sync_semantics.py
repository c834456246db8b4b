"""WaitToRead hard-barrier contract (round-3 VERDICT weak #7).

reference: NDArray::WaitToRead blocks until the dependency engine has
finished every pending write to the variable — MXNet timing and error
semantics key off it. This test pins the contract in a way that FAILS
if wait_to_read ever returns before execution completes: after the wait,
realizing the value must be near-instant relative to the compute.
chip_smoke.py's barrier check holds the same contract on the chip.
"""
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def _slow_chain(x, iters=60):
    """A deliberately slow dependency chain (hundreds of ms on the CPU
    test machine): iterated matmul keeps the async queue busy."""
    y = x
    for _ in range(iters):
        y = nd.dot(y, x) * (1.0 / 8.0) + x
    return y


def test_wait_to_read_blocks_until_execution_done():
    rng = onp.random.RandomState(0)
    x = nd.array(rng.rand(400, 400).astype("float32") * 0.01)
    # Adapt the chain length until measured execution sits comfortably
    # above timer noise — a machine fast enough to finish 60 iters in
    # <200ms gets a longer chain instead of a flaky ratio assert.
    iters = 60
    for _ in range(5):
        # warm the compile cache so the timed run measures execution
        _slow_chain(x, iters).wait_to_read()
        t0 = time.perf_counter()
        y = _slow_chain(x, iters)
        t_dispatch = time.perf_counter() - t0
        t1 = time.perf_counter()
        y.wait_to_read()
        t_wait = time.perf_counter() - t1
        if t_dispatch + t_wait >= 0.2:
            break
        iters *= 2

    t2 = time.perf_counter()
    _ = y.asnumpy()
    t_read = time.perf_counter() - t2

    # the wait must have absorbed the execution: reading afterwards is
    # near-instant. If wait_to_read returned early, t_read would carry
    # the compute instead and exceed t_wait.
    assert t_wait > 0.0
    assert t_read < max(0.05, 0.5 * (t_dispatch + t_wait)), (
        "wait_to_read returned before execution completed: "
        "dispatch=%.4fs wait=%.4fs read-after-wait=%.4fs"
        % (t_dispatch, t_wait, t_read))


def test_wait_to_read_surfaces_deferred_errors():
    """The barrier is where async execution errors surface (reference:
    ThreadedVar exception_ptr)."""
    a = nd.array(onp.ones((4, 4), "float32"))
    b = nd.array(onp.ones((5, 5), "float32"))
    bad = nd.dot(a, b)          # shape mismatch poisons the output var
    with pytest.raises(Exception):
        bad.wait_to_read()
