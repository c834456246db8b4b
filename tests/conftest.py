"""Test harness configuration.

Forces the CPU platform with 8 virtual devices BEFORE jax initializes, so the
whole suite exercises multi-device mesh code paths without TPU hardware
(SURVEY.md §4: the reference re-runs its CPU suite on gpu(0); we are
context-parametric the same way via MXNET_TEST_DEVICE).
"""
import os

_accel_run = (os.environ.get("MXNET_TEST_DEVICE", "cpu").split("(")[0]
              in ("tpu", "gpu"))
if not _accel_run:
    os.environ["JAX_PLATFORMS"] = "cpu"
else:
    # On-chip suite run (MXNET_TEST_DEVICE=tpu): keep the real accelerator
    # backend registered — the host cpu backend coexists for the
    # cpu-vs-accel consistency sweep — and let the mesh helpers fall back
    # to the 8 virtual host devices for multi-device tests the single
    # chip can't satisfy (reference: gpu suite re-runs on gpu(0) while
    # multi-GPU tests stay on their own rigs, SURVEY §4).
    os.environ.setdefault("MXNET_MESH_HOST_FALLBACK", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    flags = flags + " --xla_force_host_platform_device_count=8"

if "collective_call_terminate_timeout" not in flags:
    # one host core runs all 8 virtual devices serially: XLA:CPU's default
    # 40 s collective-rendezvous watchdog CHECK-aborts whole test runs
    # whenever per-shard compute skews arrivals (seen on the big-shape
    # mesh tests under suite load)
    flags = (flags
             + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=120"
             + " --xla_cpu_collective_call_terminate_timeout_seconds=600")
os.environ["XLA_FLAGS"] = flags.strip()

if _accel_run:
    # Fail FAST and LOUD if the accelerator silently fell back to the
    # host: a green "on-chip" suite on 8 virtual CPUs would be fake
    # evidence.
    import jax
    _backend = jax.default_backend()
    print("on-chip suite backend:", _backend, flush=True)
    assert _backend != "cpu", (
        "MXNET_TEST_DEVICE=%s but jax initialized the cpu backend — "
        "refusing to record a host run as on-chip evidence"
        % os.environ["MXNET_TEST_DEVICE"])

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_rng(request):
    """reference: tests/python/unittest/common.py (@with_seed) — seed and log
    the RNG per test for reproducibility."""
    seed = np.random.randint(0, 2 ** 31)
    env = os.environ.get("MXNET_TEST_SEED")
    if env:
        seed = int(env)
    import mxnet_tpu as mx
    mx.random.seed(seed)
    np.random.seed(seed)
    request.node.user_properties.append(("mxnet_test_seed", seed))
    yield


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running (full-size model zoo / multi-process)")
    config.addinivalue_line("markers", "lint: tracelint self-check (mx.analysis over mxnet_tpu/; run alone with -m lint)")
    config.addinivalue_line("markers", "obs: observability endpoint tests (live /metrics HTTP server on localhost)")
    config.addinivalue_line("markers", "serve: serving-engine tests (continuous batching, paged KV cache, replica supervision)")
    config.addinivalue_line("markers", "pallas: Pallas kernel parity tests (CPU backend runs the real kernels through the interpreter — parity evidence only, never perf evidence)")
    config.addinivalue_line("markers", "compiler: whole-graph symbolic compiler + AOT executable cache tests (run alone with -m compiler)")
    config.addinivalue_line("markers", "chaos: seeded multi-fault soak over the resilience fault sites (tools/chaos.py; run with -m chaos)")


@pytest.fixture(autouse=True)
def _pallas_interpret_mode(request, monkeypatch):
    """Tests marked `pallas` run every kernel through the Pallas
    interpreter on the CPU backend (this container has no TPU chip); the
    on-chip suite (MXNET_TEST_DEVICE=tpu) clears any inherited interpret
    flag so the native Mosaic path cannot be silently skipped."""
    if request.node.get_closest_marker("pallas") is not None:
        from mxnet_tpu.test_utils import is_accel_test_device
        if is_accel_test_device():
            monkeypatch.delenv("MXNET_FLASH_INTERPRET", raising=False)
        else:
            monkeypatch.setenv("MXNET_FLASH_INTERPRET", "1")
    yield


@pytest.fixture
def resnet18_grad_shapes():
    """resnet18 (classes=1000) parameter shapes: conv1 + 8 basic blocks
    (2 convs + 2 BN pairs each, stage-transition downsamples) + fc — the
    62-tensor gradient set the acceptance tests of the bucketed comm engine
    (tests/test_comm_bucket.py) and of ZeRO (tests/test_zero.py) sync."""
    shapes = [(64, 3, 7, 7), (64,), (64,)]
    widths = [(64, 64), (64, 128), (128, 256), (256, 512)]
    for cin, cout in widths:
        for blk in range(2):
            first_in = cin if blk == 0 else cout
            shapes += [(cout, first_in, 3, 3), (cout,), (cout,),
                       (cout, cout, 3, 3), (cout,), (cout,)]
            if blk == 0 and cin != cout:
                shapes += [(cout, cin, 1, 1), (cout,), (cout,)]
    shapes += [(1000, 512), (1000,)]
    return shapes
