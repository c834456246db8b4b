"""Model parallelism: group2ctx, the TPU-native way.

reference: the MXNet 1.x model-parallel idiom is manual per-layer device
placement — `with mx.AttrScope(ctx_group='dev1'): ...` plus
`group2ctx={'dev1': gpu(0), 'dev2': gpu(1)}` at bind time
(example/model-parallel/, src/executor/graph_executor.cc). The TPU-native
equivalent is DECLARATIVE: name a mesh axis 'model' and give each layer's
parameters a PartitionSpec; GSPMD inserts the boundary collectives that
graph_executor's copy nodes did.

This example runs the same 2-layer Megatron-split MLP both ways:
  column-parallel fc1 (out dim sharded) -> row-parallel fc2 (in dim
  sharded, psum at the boundary) — and asserts the sharded loss equals
the replicated loss while training both.

Single chip degrades to replication (same program). Simulate a mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/model_parallel_mlp.py --model-parallel 4
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mxnet_tpu.parallel import (ShardingRules, ShardedTrainStep,
                                create_mesh)


def init_params(key, din, dh, dout):
    k1, k2 = jax.random.split(key)
    s1, s2 = (2.0 / din) ** 0.5, (2.0 / dh) ** 0.5
    return {
        "fc1": {"w": jax.random.normal(k1, (din, dh)) * s1,
                "b": jnp.zeros((dh,))},
        "fc2": {"w": jax.random.normal(k2, (dh, dout)) * s2,
                "b": jnp.zeros((dout,))},
    }


def loss_fn(params, batch):
    x, y = batch["x"], batch["y"]
    h = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    logits = h @ params["fc2"]["w"] + params["fc2"]["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


# Megatron split, declared instead of placed:
#   fc1.w (din, dh): column-parallel — shard the OUTPUT dim over 'model'
#   fc2.w (dh, dout): row-parallel  — shard the INPUT dim; GSPMD inserts
#   the psum the reference's group2ctx copy-node placed by hand
MP_RULES = ShardingRules([
    (r"fc1/w", P(None, "model")),
    (r"fc1/b", P("model")),
    (r"fc2/w", P("model", None)),
], default=P())


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model-parallel", type=int, default=2)
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--hidden", type=int, default=256)
    args = p.parse_args()

    n = len(jax.devices())
    mp = args.model_parallel if n >= args.model_parallel else 1
    print("%d device(s); model axis = %d" % (n, mp))

    din, dout = 32, 8
    rng = np.random.RandomState(0)
    batch = {
        "x": jnp.asarray(rng.randn(args.batch, din).astype(np.float32)),
        "y": jnp.asarray(rng.randint(0, dout, args.batch)),
    }

    def train(mesh, rules, tag):
        params = init_params(jax.random.PRNGKey(0), din, args.hidden, dout)
        step = ShardedTrainStep(loss_fn, params, mesh, rules=rules,
                                optimizer="sgd", lr=0.1)
        p_, s_ = step.init()
        p_, s_, l0 = step(p_, s_, batch)
        t0 = time.time()
        for _ in range(args.steps):
            p_, s_, loss = step(p_, s_, batch)
        jax.block_until_ready(loss)
        dt = (time.time() - t0) / args.steps
        print("%s: loss %.4f -> %.4f  (%.2f ms/step)"
              % (tag, float(l0), float(loss), dt * 1e3))
        return float(l0), float(loss)

    mp_mesh = create_mesh(model=mp)
    l0_mp, lN_mp = train(mp_mesh, MP_RULES, "model-parallel")
    rep_mesh = create_mesh(data=1)
    l0_rep, lN_rep = train(rep_mesh, MP_RULES, "replicated  ")

    np.testing.assert_allclose(l0_mp, l0_rep, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(lN_mp, lN_rep, rtol=2e-3, atol=1e-4)
    print("sharded-vs-replicated parity OK — group2ctx semantics, "
          "zero manual copy nodes")


if __name__ == "__main__":
    main()
