"""Config #5: Llama with TP+FSDP sharding over an ICI mesh (reference
north star; no reference analog — MXNet 1.x had only group2ctx manual MP).

Single chip runs the tiny config; on a pod, set --mesh to the real shape
(e.g. --mesh data=4,fsdp=4,model=4 on v5e-64) and pick --config llama3_8b.
Simulate multi-chip on CPU with:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/llama_sharded.py --mesh data=2,fsdp=2,model=2

Demonstrates the full native training path: fused sharded step (fwd+bwd+
collectives+adamw in ONE XLA program), checkpoint save + resume.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from mxnet_tpu.models.llama import CONFIGS, llama_init, llama_loss
from mxnet_tpu.parallel import (create_mesh, LLAMA_RULES, ShardedTrainStep,
                                save_train_state, restore_train_state,
                                latest_step)


def parse_mesh(spec):
    axes = {}
    for part in spec.split(","):
        k, v = part.split("=")
        axes[k.strip()] = int(v)
    return axes


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="llama_tiny", choices=list(CONFIGS))
    p.add_argument("--mesh", default="data=1",
                   help="e.g. data=4,fsdp=4,model=4")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt", default="")
    p.add_argument("--ckpt-every", type=int, default=50)
    args = p.parse_args()

    cfg = CONFIGS[args.config]
    mesh = create_mesh(**parse_mesh(args.mesh))
    print("mesh:", dict(zip(mesh.axis_names, mesh.devices.shape)))

    params = llama_init(jax.random.PRNGKey(0), cfg)
    step = ShardedTrainStep(lambda p_, b: llama_loss(p_, b, cfg), params,
                            mesh, rules=LLAMA_RULES, optimizer="adamw",
                            lr=args.lr)
    params, opt_state = step.init()
    start = 0
    if args.ckpt and latest_step(args.ckpt) is not None:
        params, opt_state, start = restore_train_state(args.ckpt, mesh=mesh,
                                                       rules=LLAMA_RULES)
        print("resumed from step", start)

    key = jax.random.PRNGKey(1)
    for i in range(start, args.steps):
        key, sub = jax.random.split(key)
        toks = jax.random.randint(sub, (args.batch, args.seq + 1), 0,
                                  cfg.vocab_size)
        t0 = time.time()
        params, opt_state, loss = step(params, opt_state, {"tokens": toks})
        loss = float(loss)
        dt = time.time() - t0
        tput = args.batch * args.seq / dt
        print("step %d loss %.4f  %.0f tok/s" % (i, loss, tput))
        if args.ckpt and (i + 1) % args.ckpt_every == 0:
            save_train_state(args.ckpt, params, opt_state, i + 1)
    if args.ckpt:
        save_train_state(args.ckpt, params, opt_state, args.steps)


if __name__ == "__main__":
    main()
