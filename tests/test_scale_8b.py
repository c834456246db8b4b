"""The Llama-3-8B sharded program at reduced depth (the v5e-64 plan:
`create_mesh(data=4, fsdp=4, model=4)`, PERF.md "Before the chip").

reference: BASELINE.json configs[4] (8B pretraining on v5e-64). The dry
config keeps every LAYER dimension of the 8B (d_model 4096, 32/8 GQA
heads, hidden 14336, SwiGLU, RoPE theta, remat, one-hot vocab-sharded
embedding) and shrinks only depth/vocab/context; the mesh is the same
three-axis (data, fsdp, model) GSPMD layout as the 64-chip plan, 8 ways.
"""
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import jax
jax.config.update('jax_platforms', 'cpu')
import jax.numpy as jnp
from mxnet_tpu.models.llama import CONFIGS, llama_init, llama_loss
from mxnet_tpu.parallel.mesh import create_mesh
from mxnet_tpu.parallel.sharding import LLAMA_RULES
from mxnet_tpu.parallel.train_step import ShardedTrainStep

cfg = CONFIGS['llama3_8b_dry']
assert cfg.dim == 4096 and cfg.hidden_dim == 14336
assert cfg.n_heads == 32 and cfg.n_kv_heads == 8
mesh = create_mesh(data=2, fsdp=2, model=2)
params = llama_init(jax.random.PRNGKey(0), cfg)
step = ShardedTrainStep(lambda p, b: llama_loss(p, b, cfg), params,
                        mesh, rules=LLAMA_RULES, optimizer='adamw',
                        lr=1e-4)
p, s = step.init()
tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 129), 0,
                            cfg.vocab_size)
p, s, loss = step(p, s, {'tokens': tokens})
assert bool(jnp.isfinite(loss)), float(loss)
assert 6.0 < float(loss) < 12.0, float(loss)
leaf = jax.tree_util.tree_leaves(p)[0]
assert len(leaf.sharding.device_set) == 8

# HBM-ledger budget check (the plan: 16 GB/chip on v5e-64, ~12.9 GB/chip
# planned): the dryrun must fit the declared budget AND the per-scope
# breakdown must explain the device bytes. CPU live_arrays counts host
# copies (llama_init's unsharded tree is still live), so the residual
# tolerance here is looser than the accelerator default.
from mxnet_tpu.telemetry import ledger
rep = ledger.check_budget(16 * 2**30, residual_tolerance=0.75)
assert rep['ok'], rep['failures']
assert rep['scopes'].get('params', 0) > 0, rep['scopes']
assert rep['scopes'].get('optimizer', 0) > 0, rep['scopes']
print('SCALE8B OK loss=%.4f params=%dB budget_ok=%s'
      % (float(loss), rep['scopes']['params'], rep['ok']))
"""


@pytest.mark.slow
def test_8b_layer_shapes_train_step_on_3axis_mesh():
    """Runs in a fresh subprocess: the 8B layer shapes peak ~10 GB of
    host RAM, and sharing an interpreter with the rest of the suite's
    live arrays has produced allocator aborts."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    # keep the flags conftest already probed (the cpu collective-watchdog
    # flags only exist in newer jaxlibs — re-adding them unconditionally
    # CHECK-aborts the child on jaxlib 0.4.36); only pin the virtual
    # device count the 3-axis mesh needs
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if "host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (flags
                        + " --xla_force_host_platform_device_count=8").strip()
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=1500)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "SCALE8B OK" in res.stdout
