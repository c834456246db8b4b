"""Fault-tolerant training with mxnet_tpu.resilience.

Trains a small MLP under an adversarial fault plan — a flaky transport
endpoint at step 2, a simulated host preemption at step 5, and a
maintenance-event NOTICE observed by the preemption poller (which turns
into a proactive, zero-replay checkpoint) — and shows the run completing
anyway, with the recovery ledger and the telemetry counters that would
feed a fleet dashboard. Checkpoints run the coordinated two-phase commit
(`commit=True`; trivially elected on one process, fleet-elected on a pod).

Run:  JAX_PLATFORMS=cpu python examples/resilient_training.py
Try:  MXNET_TPU_FAULT_PLAN="train.step:hang:4:30" \
      MXNET_TPU_STEP_DEADLINE_S=2 python examples/resilient_training.py
      (a hung step becomes a StallError -> restore -> replay; its
      .format_report() post-mortem carries per-device buffer stats)
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, resilience, telemetry
from mxnet_tpu.gluon import nn

STEPS = 8
BATCH = 32


def build_net():
    mx.random.seed(7)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(64, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    return net, trainer


def main():
    rng = np.random.RandomState(0)
    X = rng.rand(STEPS, BATCH, 20).astype(np.float32)
    Y = rng.randint(0, 10, (STEPS, BATCH)).astype(np.float32)

    def batch_fn(i):  # deterministic per index: replayable after restore
        return nd.array(X[i]), nd.array(Y[i])

    net, trainer = build_net()
    fused = gluon.FusedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer)

    # the same plan could come from MXNET_TPU_FAULT_PLAN in the environment;
    # the preempt.poll entry simulates a TPU-VM maintenance notice — the
    # listener converts it into a proactive (zero-replay) checkpoint
    plan = "run.step:error:2;run.step:preempt:5;preempt.poll:preempt:2"
    print("fault plan: %s" % plan)
    listener = resilience.PreemptionListener(poll_interval_s=0.05)
    with resilience.faults.inject(plan):
        runner = resilience.ResilientRunner.for_fused_step(
            fused, batch_fn, ckpt_dir=tempfile.mkdtemp(prefix="ckpt_"),
            ckpt_every=2, max_restarts=4, step_deadline_s=60,
            commit=True, preempt_listener=listener)
        report = runner.run(STEPS)
    listener.stop()

    print("\n%r" % report)
    print("losses: %s" % np.round(report.losses, 4).tolist())
    snap = telemetry.snapshot()["counters"]
    print("\nrecovery ledger (telemetry):")
    for name in sorted(snap):
        if name.startswith("resilience."):
            print("  %-40s %d" % (name, snap[name]))


if __name__ == "__main__":
    main()
