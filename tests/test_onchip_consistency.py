"""cpu-vs-accelerator op consistency (round-3 VERDICT task #5 tail).

reference: tests/python/gpu/test_operator_gpu.py re-runs the op suite on
gpu(0) and `test_utils.check_consistency` compares context outputs. Here:
when MXNET_TEST_DEVICE=tpu (the on-chip suite run), every op in the
gradient sweep's spec catalog is executed on BOTH the accelerator and the
host CPU backend from identical inputs and compared. On the CPU-only
suite these tests skip — the harness is exercised the first time the
driver's on-chip run happens.
"""
import os

import numpy as onp
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu.ndarray.ndarray import invoke
from mxnet_tpu.ops import registry

from test_registry_grad_sweep import SPECS, SKIP, ALL_OPS, _auto_inputs

_ON_ACCEL = os.environ.get("MXNET_TEST_DEVICE", "cpu") in ("tpu", "gpu")

pytestmark = pytest.mark.skipif(
    not _ON_ACCEL,
    reason="cpu-vs-accelerator consistency needs MXNET_TEST_DEVICE=tpu")


def _run_on(ctx, name, inputs, kwargs):
    from mxnet_tpu import nd
    with mx.Context(ctx):
        xs = [nd.array(a, dtype=str(a.dtype))
              if isinstance(a, onp.ndarray) else a for a in inputs]
        out = invoke(name, *xs, **kwargs)
        outs = out if isinstance(out, (list, tuple)) else [out]
        return [o.asnumpy() for o in outs]


# training-output ops are in the gradient sweep's SKIP only because their
# backward is deliberately not the forward vjp — the FORWARD consistency
# check here is still valid, so run them with explicit specs
_FWD_OK = {
    "LinearRegressionOutput": dict(
        inputs=[onp.random.RandomState(1).rand(3, 4).astype("float32"),
                onp.random.RandomState(2).rand(3, 4).astype("float32")],
        kwargs={}),
    "MAERegressionOutput": dict(
        inputs=[onp.random.RandomState(3).rand(3, 4).astype("float32"),
                onp.random.RandomState(4).rand(3, 4).astype("float32")],
        kwargs={}),
    "LogisticRegressionOutput": dict(
        inputs=[onp.random.RandomState(5).rand(3, 4).astype("float32"),
                onp.random.RandomState(6).rand(3, 4).astype("float32")],
        kwargs={}),
    "IdentityAttachKLSparseReg": dict(
        inputs=[onp.random.RandomState(7).uniform(
            0.1, 0.9, (3, 4)).astype("float32")], kwargs={}),
    "Softmax": dict(
        inputs=[onp.random.RandomState(8).rand(3, 4).astype("float32"),
                onp.array([0., 2., 1.], "float32")], kwargs={}),
}


@pytest.mark.parametrize("name", ALL_OPS)
def test_op_consistency_cpu_vs_accel(name):
    if name in _FWD_OK:
        spec = _FWD_OK[name]
        accel = _run_on(mx.tpu() if jax.default_backend() == "tpu"
                        else mx.gpu(), name, spec["inputs"], spec["kwargs"])
        host = _run_on(mx.cpu(), name, spec["inputs"], spec["kwargs"])
        for a, h in zip(accel, host):
            onp.testing.assert_allclose(a, h, rtol=2e-2, atol=2e-3,
                                        err_msg=name)
        return
    if name in SKIP:
        pytest.skip(SKIP[name])
    spec = SPECS.get(name)
    if name in SPECS and spec is None:
        pytest.skip("covered elsewhere")
    if spec is None:
        inputs, kwargs = _auto_inputs(name)
        if inputs is None:
            pytest.skip("no auto inputs")
        spec = dict(inputs=inputs, kwargs=kwargs)
    accel = _run_on(mx.tpu() if jax.default_backend() == "tpu"
                    else mx.gpu(), name, spec["inputs"],
                    spec.get("kwargs", {}))
    host = _run_on(mx.cpu(), name, spec["inputs"], spec.get("kwargs", {}))
    assert len(accel) == len(host)
    for a, h in zip(accel, host):
        onp.testing.assert_allclose(a, h, rtol=2e-2, atol=2e-3,
                                    err_msg=name)
