"""Nothing may let a run without a chip pass for a run with one:
`chip_smoke.py` fails where JAX finds no TPU, `mx.tpu()` / `mx.gpu()` raise
where there is no accelerator, and the compile cache sits where it is told
to or at one fixed path."""
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.test_utils import is_accel_test_device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# under MXNET_TEST_DEVICE=tpu this process holds a chip: there is an
# accelerator to resolve, and a child that asks for one would not get it
no_accelerator = pytest.mark.skipif(
    is_accel_test_device(), reason="this run has an accelerator")


def _smoke(*args, **env):
    # conftest sets the host fallback for an on-chip suite run
    base = {k: v for k, v in os.environ.items()
            if k != "MXNET_MESH_HOST_FALLBACK"}
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=dict(base, JAX_PLATFORMS="cpu", **env), cwd=REPO,
        capture_output=True, text=True, timeout=900)


def test_smoke_fails_without_a_chip_and_names_the_platform():
    r = _smoke()
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert r.stdout == ""        # no result line without an accelerator


def test_smoke_fails_when_a_mesh_could_fall_back_to_the_host():
    r = _smoke("--rehearse", MXNET_MESH_HOST_FALLBACK="1")
    assert r.returncode != 0
    assert "MXNET_MESH_HOST_FALLBACK" in r.stderr


@pytest.mark.slow
def test_smoke_rehearsal_passes_on_the_cpu():
    # conftest's XLA_FLAGS give the child its virtual devices for the mesh
    r = _smoke("--rehearse", "--chips", "4")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.splitlines()
    assert lines[-1] == "REHEARSAL (cpu): not a chip result"
    assert lines[-2].startswith("measured: ") and '"ok"' not in lines[-2]


@no_accelerator
@pytest.mark.parametrize("make", [mx.tpu, mx.gpu])
def test_accelerator_context_raises_without_an_accelerator(make):
    assert mx.num_tpus() == mx.num_gpus() == 0
    assert make(0).device_id == 0       # building the label is free
    with pytest.raises(MXNetError, match="cpu"):
        make(0).jax_device
    with pytest.raises(MXNetError):
        mx.nd.zeros((2,), ctx=make(0))


def test_accelerator_id_past_the_last_device_raises(monkeypatch):
    import jax
    from mxnet_tpu import context
    two = jax.local_devices(backend="cpu")[:2]
    monkeypatch.setattr(context, "_accel_devices", lambda: two)
    assert mx.tpu(1).jax_device is two[1]
    with pytest.raises(MXNetError, match="2 accelerator"):
        mx.tpu(2).jax_device
    with pytest.raises(MXNetError):
        mx.tpu(-1).jax_device


def test_cpu_ids_stay_labels_on_one_host():
    import jax
    n = len(jax.local_devices(backend="cpu"))
    assert mx.cpu(n + 3).jax_device.platform == "cpu"
    assert mx.nd.ones((2,), ctx=mx.cpu(n + 3)).asnumpy().sum() == 2


def test_compile_cache_is_placed_from_outside_or_at_one_fixed_path(
        monkeypatch, tmp_path):
    import jax
    from mxnet_tpu.runtime import place_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = place_compile_cache()
        assert first == place_compile_cache()
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
