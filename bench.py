"""Headline benchmark: ResNet-50 training throughput (images/sec/chip)
through the USER-FACING Gluon API — `gluon.model_zoo.vision.resnet50_v1` +
`gluon.Trainer` + `gluon.FusedTrainStep` (the whole train step compiled to
one XLA program; reference analog: CachedOp + engine-overlapped KVStore +
optimizer ops, SURVEY.md §3.2).

BASELINE.md: target >= 0.9x A100 per-chip throughput. A100 ResNet-50 train
(fp16/AMP, batch 256) is ~2500 img/s, so vs_baseline is measured against
0.9 * 2500 = 2250 img/s. Synthetic data, bf16 conv stack with fp32
BatchNorm, SGD+momentum, warm-up then steady-state mean over 50 steps.

BENCH=functional selects the raw functional-JAX path (models/resnet.py) for
comparison; the headline is the Gluon path.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "img/s", "vs_baseline": N}
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as _np

BASELINE_IMG_S = 2250.0


def _emit(payload):
    """Print the single bench JSON line, with the telemetry counters that
    explain WHY a number moved. Every row is
    stamped with its environment fingerprint and appended to the rolling
    bench history (tools/benchdb.py) so tools/check_bench.py can gate on
    regressions without ever comparing rows from different stacks."""
    try:
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools"))
        import benchdb
        fp = benchdb.fingerprint(
            backend=jax.default_backend(),
            device_count=jax.device_count())
        payload["backend"] = fp["backend"]
        payload["fingerprint"] = fp
        payload["fingerprint_id"] = benchdb.fingerprint_id(fp)
        payload["ts"] = round(time.time(), 3)
    except Exception as e:   # fingerprinting must never break the row
        print("# bench fingerprint unavailable: %s" % e, file=sys.stderr)
        benchdb = None
    try:
        from mxnet_tpu import telemetry
        snap = telemetry.snapshot()["counters"] if telemetry.ENABLED else {}
        payload["counters"] = {
            "compile": (snap.get("cachedop.compile", 0)
                        + snap.get("fused_step.compile", 0)
                        + snap.get("train_step.compile", 0)),
            "cachedop_retrace": snap.get("cachedop.retrace", 0),
            "sync_asnumpy": snap.get("ndarray.sync.asnumpy", 0),
            # a noisy run (retried comm, watchdog stalls, restores) must be
            # distinguishable from a clean one in the bench history
            "resilience_faults": snap.get("resilience.faults_injected", 0),
            "resilience_retries": snap.get("resilience.retries", 0),
            "resilience_stalls": snap.get("resilience.stalls", 0),
            "resilience_restores": snap.get("resilience.restores", 0),
            "anomalies": snap.get("telemetry.anomaly.step_time", 0),
        }
        # rolling p50/p99 step latency (telemetry v2): the tail-latency
        # numbers the serving engine will be graded on, landed early. Pick
        # the step site that actually ran this bench.
        quants = telemetry.step_quantiles() or {}
        if quants:
            site = max(quants, key=lambda s: quants[s]["n"])
            payload.setdefault("step_ms_p50",
                               round(quants[site]["p50"], 3))
            payload.setdefault("step_ms_p99",
                               round(quants[site]["p99"], 3))
    except Exception as e:   # telemetry must never break the bench row
        print("# telemetry counters unavailable: %s" % e, file=sys.stderr)
    print(json.dumps(payload))
    if benchdb is not None and "fingerprint_id" in payload:
        benchdb.append(payload)


def _device():
    """The first device. A cpu counts only when JAX_PLATFORMS=cpu asked for
    it from outside: with no chip and no such request jax logs libtpu's
    failure and hands back the cpu devices, which is not a benchmark."""
    dev = jax.devices()[0]
    if dev.platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            "bench: no accelerator: jax found platform %r (%s); the toy cpu "
            "variants run only under JAX_PLATFORMS=cpu"
            % (dev.platform, dev.device_kind))
    return dev


def _sync(x):
    """Device barrier ending a timed window (chip_smoke.py's barrier check
    holds `block_until_ready` to this on the chip)."""
    jax.block_until_ready(x)


LR = 0.1
MOMENTUM = 0.9


def bench_functional(on_accel):
    """Functional-JAX comparison path (round-1 headline)."""
    from mxnet_tpu.models.resnet import (CONFIGS, resnet_init, resnet_loss,
                                         update_running_stats)

    def tmap(f, *t):
        return jax.tree_util.tree_map(f, *t)

    cfg = CONFIGS["resnet50"] if on_accel else CONFIGS["resnet_tiny"]
    batch = 256 if on_accel else 8
    size = 224 if on_accel else 32
    steps, warmup = (50, 10) if on_accel else (5, 2)

    params = resnet_init(jax.random.PRNGKey(0), cfg)
    mom = tmap(jnp.zeros_like, params)
    images = jax.random.normal(jax.random.PRNGKey(1),
                               (batch, size, size, 3), jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0,
                                cfg.classes)
    data = {"images": images, "labels": labels}

    @jax.jit
    def step(params, mom, data):
        (loss, stats), grads = jax.value_and_grad(
            resnet_loss, has_aux=True)(params, data, cfg)
        mom = tmap(lambda m, g: MOMENTUM * m + g.astype(m.dtype), mom, grads)
        params = tmap(lambda p, m: (p - LR * m.astype(p.dtype)).astype(p.dtype),
                      params, mom)
        params = update_running_stats(params, stats, cfg)
        return params, mom, loss

    for _ in range(warmup):
        params, mom, loss = step(params, mom, data)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, mom, loss = step(params, mom, data)
    _sync(loss)
    dt = time.perf_counter() - t0
    return batch * steps / dt, "functional"


def bench_gluon(on_accel, layout="NCHW"):
    """The user-facing path: zoo model + Trainer + FusedTrainStep.
    layout='NHWC' runs the zoo model channels-last (the TPU-native
    layout the functional path uses)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.model_zoo import vision

    ctx = mx.tpu() if on_accel else mx.cpu()
    batch = 256 if on_accel else 8
    size = 224 if on_accel else 32
    steps, warmup = (50, 10) if on_accel else (5, 2)

    mx.random.seed(0)
    with mx.Context(ctx):
        net = (vision.resnet50_v1(classes=1000, layout=layout) if on_accel
               else vision.resnet18_v1(classes=10, layout=layout))
        net.initialize(mx.init.Xavier(rnd_type="gaussian"), ctx=ctx)
        net.cast("bfloat16")  # conv stack bf16; BatchNorm stays fp32
        net.hybridize(static_alloc=True)

        rng = np.random.RandomState(1)
        shape = ((batch, 3, size, size) if layout == "NCHW"
                 else (batch, size, size, 3))
        x = nd.array(rng.randn(*shape), ctx=ctx, dtype="bfloat16")
        y = nd.array(rng.randint(0, 10, (batch,)), ctx=ctx, dtype="float32")
        net(x)  # shape inference + param init

        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": LR, "momentum": MOMENTUM})
        fused = gluon.FusedTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer)

        for _ in range(warmup):
            loss = fused(x, y)
        _sync(loss.data_jax)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = fused(x, y)
        _sync(loss.data_jax)
        dt = time.perf_counter() - t0
    return batch * steps / dt, "gluon"


def bench_bert(on_accel):
    """Config #3: BERT-base masked-LM training tok/s (BASELINE.json
    configs[2]). models/bert.py + fused jit step (forward+loss+backward+
    AdamW in one XLA program), bf16, flash attention. Protocol: seq 128
    (MLPerf phase-1 convention), warm-up then steady-state mean.

    vs_baseline: 0.9 x A100 BERT-base fp16 pretrain throughput
    (~1,100 seq/s @ seq 128 = 140.8k tok/s) -> bar 126,720 tok/s."""
    from mxnet_tpu.models.bert import CONFIGS, bert_init, bert_mlm_loss

    def tmap(f, *t):
        return jax.tree_util.tree_map(f, *t)

    cfg = CONFIGS["bert_base"] if on_accel else CONFIGS["bert_tiny"]
    batch, seq = (128, 128) if on_accel else (4, 32)
    steps, warmup = (50, 10) if on_accel else (4, 2)
    lr, b1, b2, eps, wd = 1e-4, 0.9, 0.999, 1e-6, 0.01

    params = bert_init(jax.random.PRNGKey(0), cfg)
    m = tmap(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    v = tmap(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    tokens = jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size)
    targets = jax.random.randint(k2, (batch, seq), 0, cfg.vocab_size)
    mask = (jax.random.uniform(k3, (batch, seq)) < 0.15).astype(jnp.int32)
    data = {"tokens": tokens, "targets": targets, "mask": mask}

    @jax.jit
    def step(params, m, v, t, data):
        loss, grads = jax.value_and_grad(bert_mlm_loss)(params, data, cfg)
        t = t + 1
        corr = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)

        def upd(p, g, mi, vi):
            g32 = g.astype(jnp.float32)
            mi = b1 * mi + (1 - b1) * g32
            vi = b2 * vi + (1 - b2) * g32 * g32
            newp = p.astype(jnp.float32) - lr * (
                corr * mi / (jnp.sqrt(vi) + eps) + wd * p.astype(jnp.float32))
            return newp.astype(p.dtype), mi, vi

        flat_p, tree = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(m)
        flat_v = jax.tree_util.tree_leaves(v)
        new = [upd(p, g, mi, vi) for p, g, mi, vi in
               zip(flat_p, flat_g, flat_m, flat_v)]
        params = jax.tree_util.tree_unflatten(tree, [n[0] for n in new])
        m2 = jax.tree_util.tree_unflatten(tree, [n[1] for n in new])
        v2 = jax.tree_util.tree_unflatten(tree, [n[2] for n in new])
        return params, m2, v2, t, loss

    t = jnp.int32(0)
    for _ in range(warmup):
        params, m, v, t, loss = step(params, m, v, t, data)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, m, v, t, loss = step(params, m, v, t, data)
    _sync(loss)
    dt = time.perf_counter() - t0
    return batch * seq * steps / dt, "bert"


def bench_bert_gluon(on_accel):
    """Config #3 through the USER-FACING Gluon API: model_zoo BERT
    (fused interleaved-selfatt ops) + Trainer + FusedTrainStep — the BERT
    analog of the Gluon ResNet headline. Same protocol/bar as BENCH=bert."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.model_zoo import bert as zoo_bert

    ctx = mx.tpu() if on_accel else mx.cpu()
    batch, seq = (128, 128) if on_accel else (4, 32)
    steps, warmup = (50, 10) if on_accel else (4, 2)
    vocab = 30522 if on_accel else 256

    mx.random.seed(0)
    with mx.Context(ctx):
        if on_accel:
            net = zoo_bert.bert_12_768_12(dropout=0.0)
        else:
            net = zoo_bert.BERTModel(vocab_size=vocab, units=64,
                                     hidden_size=128, num_layers=2,
                                     num_heads=4, max_length=seq,
                                     dropout=0.0)
        net.initialize(mx.init.Normal(0.02), ctx=ctx)
        net.cast("bfloat16")
        net.hybridize(static_alloc=True)

        rng = np.random.RandomState(1)
        x = nd.array(rng.randint(0, vocab, (batch, seq)), ctx=ctx,
                     dtype="float32")
        y = nd.array(rng.randint(0, vocab, (batch, seq)), ctx=ctx,
                     dtype="float32")
        net(x)

        sce = gluon.loss.SoftmaxCrossEntropyLoss()

        def mlm_loss(out, label):
            # out = (seq_out, pooled, nsp_logits, mlm_logits)
            return sce(out[3], label)

        trainer = gluon.Trainer(net.collect_params(), "adamw",
                                {"learning_rate": 1e-4, "wd": 0.01})
        fused = gluon.FusedTrainStep(net, mlm_loss, trainer)

        for _ in range(warmup):
            loss = fused(x, y)
        _sync(loss.data_jax)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = fused(x, y)
        _sync(loss.data_jax)
        dt = time.perf_counter() - t0
    return batch * seq * steps / dt, "bert_gluon"


def bench_fused_stage(on_accel):
    """ROOFLINE.md fusion project microbench: one ResNet stage-3-shaped
    conv3x3+BN+ReLU block, XLA composed vs Pallas fused
    (MXNET_TPU_USE_PALLAS). Reports the fused/composed speedup and logs
    both programs' HBM bytes from cost_analysis."""
    import numpy as onp
    from mxnet_tpu.ops import fused_conv as fc

    N, H, W, C = (64, 14, 14, 256) if on_accel else (4, 14, 14, 32)
    rng = onp.random.RandomState(0)
    dt = jnp.bfloat16 if on_accel else jnp.float32
    x = jnp.asarray(rng.randn(N, H, W, C), dtype=dt)
    w = jnp.asarray(rng.randn(3, 3, C, C) * 0.05, dtype=dt)
    scale = jnp.asarray(rng.rand(C) + 0.5, dtype=jnp.float32)
    shift = jnp.asarray(rng.randn(C) * 0.1, dtype=jnp.float32)

    res = jnp.asarray(rng.randn(N, H, W, C) * 0.1, dtype=dt)
    composed = jax.jit(
        lambda a: fc._xla_conv_bn_relu(a, w, scale, shift, residual=res))
    fused = jax.jit(
        lambda a: fc._pallas_conv_bn_relu(a, w, scale, shift, residual=res))

    for fn, tag in ((composed, "xla"), (fused, "pallas")):
        lowered = fn.lower(x)
        try:
            cost = lowered.compile().cost_analysis()
            cost = cost[0] if isinstance(cost, list) else cost
            print("# %s bytes accessed: %.3e" % (
                tag, cost.get("bytes accessed", float("nan"))),
                file=sys.stderr)
        except Exception as e:       # cost analysis is best-effort
            print("# %s cost_analysis unavailable: %s" % (tag, e),
                  file=sys.stderr)

    def time_it(fn):
        fn(x).block_until_ready()
        n = 50 if on_accel else 5
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(x)
        out.block_until_ready()
        return n * N / (time.perf_counter() - t0)

    base = time_it(composed)
    fast = time_it(fused)
    return fast, base


def bench_fused_train_stage(on_accel):
    """Round-5 training-fusion microbench: one ResNet stage-3-shaped
    conv3x3+BN(batch stats)+ReLU TRAINING step (fwd+bwd), XLA composed vs
    the fused op (`_contrib_conv_bn_relu_train`: stats in the conv
    epilogue, xhat recomputed in backward). Logs both programs'
    cost_analysis bytes to stderr; value = fused img/s, vs_baseline =
    fused/composed speedup."""
    import numpy as onp
    from mxnet_tpu.ops import fused_conv as fc

    N, H, W, C = (64, 14, 14, 256) if on_accel else (4, 8, 8, 16)
    rng = onp.random.RandomState(0)
    dt = jnp.bfloat16 if on_accel else jnp.float32
    x = jnp.asarray(rng.randn(N, H, W, C), dtype=dt)
    w = jnp.asarray(rng.randn(3, 3, C, C) * 0.05, dtype=dt)
    gamma = jnp.asarray(rng.rand(C) + 0.5, dtype=jnp.float32)
    beta = jnp.asarray(rng.randn(C) * 0.1, dtype=jnp.float32)
    cot = jnp.asarray(rng.rand(N, H, W, C), dtype=dt)

    def composed(x_, w_, g_, b_):
        from jax import lax
        conv = lax.conv_general_dilated(
            x_, w_, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
        mean = jnp.mean(conv, axis=(0, 1, 2))
        var = jnp.var(conv, axis=(0, 1, 2))
        y = (conv - mean) * jax.lax.rsqrt(var + 1e-3) * g_ + b_
        return jnp.maximum(y, 0.0).astype(x_.dtype)

    def fused(x_, w_, g_, b_):
        out, _, _ = fc._cbr_train(1e-3, False, x_, w_, g_, b_, None)
        return out

    def train_step(fn):
        def step(x_, w_, g_, b_):
            loss_fn = lambda *a: jnp.sum(fn(*a).astype(jnp.float32)
                                         * cot.astype(jnp.float32))
            return jax.grad(loss_fn, argnums=(1, 2, 3))(x_, w_, g_, b_)
        return jax.jit(step)

    results = {}
    for fn, tag in ((train_step(composed), "xla_composed"),
                    (train_step(fused), "pallas_fused")):
        lowered = fn.lower(x, w, gamma, beta)
        try:
            cost = lowered.compile().cost_analysis()
            cost = cost[0] if isinstance(cost, list) else cost
            print("# fused_train %s bytes accessed: %.3e" % (
                tag, cost.get("bytes accessed", float("nan"))),
                file=sys.stderr)
        except Exception as e:
            print("# fused_train %s cost_analysis unavailable: %s"
                  % (tag, e), file=sys.stderr)
        out = fn(x, w, gamma, beta)
        _sync(out[0])
        n = 50 if on_accel else 5
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(x, w, gamma, beta)
        _sync(out[0])
        results[tag] = n * N / (time.perf_counter() - t0)
    return results["pallas_fused"], results["xla_composed"]


def bench_fused_bwd(on_accel):
    """BENCH=fused_bwd (ISSUE 10): the fused CBR BACKWARD program vs the
    composed Conv->BN(batch stats)->ReLU backward, isolated via jax.vjp —
    the lowered program is the pure backward, whose inputs are whatever
    each forward SAVED. The composed path materializes/loads its AD
    residuals (xhat, pre-relu activation); the fused custom-vjp re-streams
    conv_out through `_kernel_train_bwd` twice and loads nothing else.
    Logs both programs' cost_analysis bytes (round-3 CPU-backend
    methodology off-chip; interpret-mode wall times are NOT perf
    evidence) and emits bytes_fused/bytes_composed in the row."""
    import numpy as onp
    from jax import lax
    from mxnet_tpu.ops import fused_conv as fc

    N, H, W, C = (64, 14, 14, 256) if on_accel else (4, 8, 8, 16)
    rng = onp.random.RandomState(0)
    dt = jnp.bfloat16 if on_accel else jnp.float32
    x = jnp.asarray(rng.randn(N, H, W, C), dtype=dt)
    w = jnp.asarray(rng.randn(3, 3, C, C) * 0.05, dtype=dt)
    gamma = jnp.asarray(rng.rand(C) + 0.5, dtype=jnp.float32)
    beta = jnp.asarray(rng.randn(C) * 0.1, dtype=jnp.float32)
    cot = (jnp.asarray(rng.rand(N, H, W, C), dtype=dt),
           jnp.zeros((C,), jnp.float32), jnp.zeros((C,), jnp.float32))

    def composed(x_, w_, g_, b_):
        conv = lax.conv_general_dilated(
            x_, w_, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=jnp.float32)
        mean = jnp.mean(conv, axis=(0, 1, 2))
        var = jnp.var(conv, axis=(0, 1, 2))
        xhat = (conv - mean) * jax.lax.rsqrt(var + 1e-3)
        out = jnp.maximum(xhat * g_ + b_, 0.0).astype(x_.dtype)
        return out, mean, var

    def fused(x_, w_, g_, b_):
        return fc._cbr_train(1e-3, False, x_, w_, g_, b_, None)

    speed, bytes_ = {}, {}
    for f, tag in ((composed, "composed"), (fused, "fused")):
        _, vjp = jax.vjp(f, x, w, gamma, beta)
        bwd = jax.jit(lambda c, vjp=vjp: vjp(c))
        try:
            cost = bwd.lower(cot).compile().cost_analysis()
            cost = cost[0] if isinstance(cost, list) else cost
            bytes_[tag] = cost.get("bytes accessed", float("nan"))
            print("# fused_bwd %s bytes accessed: %.3e"
                  % (tag, bytes_[tag]), file=sys.stderr)
        except Exception as e:          # cost analysis is best-effort
            bytes_[tag] = None
            print("# fused_bwd %s cost_analysis unavailable: %s"
                  % (tag, e), file=sys.stderr)
        out = bwd(cot)
        _sync(out[0])
        n = 50 if on_accel else 5
        t0 = time.perf_counter()
        for _ in range(n):
            out = bwd(cot)
        _sync(out[0])
        speed[tag] = n * N / (time.perf_counter() - t0)
    return {
        "metric": ("fused_cbr_bwd_img_per_sec" if on_accel
                   else "fused_cbr_bwd_cpu_img_per_sec"),
        "value": round(speed["fused"], 2),
        "unit": "img/s",
        "vs_baseline": round(speed["fused"] / speed["composed"], 4),
        "bytes_fused": bytes_["fused"],
        "bytes_composed": bytes_["composed"],
    }


def bench_fused_opt(on_accel):
    """BENCH=fused_opt (ISSUE 10): the Pallas flat-segment Adam kernel vs
    the XLA composite `_fused_flat_xla` over a resnet18-sized flat shard
    (one pass over w/g/mean/var instead of separate elementwise loops).
    Emits elems/s, vs_baseline = pallas/xla wall ratio, and both
    programs' cost_analysis bytes. Off-chip the kernel runs through the
    interpreter, whose per-grid-step block-copy emulation (dynamic-slice/
    update-slice pairs) DOMINATES the counted bytes for a pure
    elementwise kernel — the cpu row is a dispatch-correctness smoke
    (expect bytes_fused > bytes_composed and vs_baseline < 1 there); the
    chip-queue row is the evidence, as with BENCH=comm."""
    import numpy as onp
    from mxnet_tpu.ops import fused_optimizer as fo
    from mxnet_tpu.optimizer.optimizer import _fused_flat_xla

    n = 11_700_000 if on_accel else 262_144
    rng = onp.random.RandomState(0)
    w = jnp.asarray(rng.randn(n).astype(onp.float32))
    g = jnp.asarray(rng.randn(n).astype(onp.float32))
    mean = jnp.zeros((n,), jnp.float32)
    var = jnp.abs(g) * 0.1
    lr = jnp.full((n,), 0.001, jnp.float32)
    wd = jnp.full((n,), 0.01, jnp.float32)
    args = (w, g, mean, var, None, lr, wd, jnp.float32(0.9),
            jnp.float32(0.1), jnp.float32(0.999), jnp.float32(0.001),
            jnp.float32(1e-8), jnp.float32(1.0), jnp.float32(0.0))

    impls = {
        "composed": _fused_flat_xla("adam", True, False, False),
        "fused": fo.flat_update_fn("adam", True, False, False),
    }
    speed, bytes_ = {}, {}
    for tag, fn in impls.items():
        try:
            cost = jax.jit(fn).lower(*args).compile().cost_analysis()
            cost = cost[0] if isinstance(cost, list) else cost
            bytes_[tag] = cost.get("bytes accessed", float("nan"))
            print("# fused_opt %s bytes accessed: %.3e"
                  % (tag, bytes_[tag]), file=sys.stderr)
        except Exception as e:
            bytes_[tag] = None
            print("# fused_opt %s cost_analysis unavailable: %s"
                  % (tag, e), file=sys.stderr)
        out = fn(*args)
        _sync(out[0])
        reps = 50 if on_accel else 3
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        _sync(out[0])
        speed[tag] = reps * n / (time.perf_counter() - t0)
    return {
        "metric": ("fused_opt_flat_elems_per_sec" if on_accel
                   else "fused_opt_flat_cpu_elems_per_sec"),
        "value": round(speed["fused"], 2),
        "unit": "elems/s",
        "vs_baseline": round(speed["fused"] / speed["composed"], 4),
        "bytes_fused": bytes_["fused"],
        "bytes_composed": bytes_["composed"],
    }


def resnet18_grad_shapes():
    """resnet18 (classes=1000) parameter shapes: conv1 + 8 basic blocks
    (2 convs + 2 BN pairs each, stage-transition downsamples) + fc — the
    62-tensor gradient set the comm bench AND the acceptance test
    (tests/test_comm_bucket.py) sync."""
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


def bench_comm(on_accel):
    """BENCH=comm: gradient-sync microbench for the bucketed comm engine
    (mx.engine). A resnet18-shaped gradient set (62 tensors, ~11.7M params)
    rides one multi-key kvstore pushpull per step — first bucketed
    (MXNET_TPU_COMM_BUCKET_MB or the 25 MB default), then the per-param
    escape hatch (bucket=0) for the vs_baseline ratio. The JSON row carries
    `collectives_per_step` and `comm_bucket_bytes` from telemetry — the
    numbers that prove buckets, not per-param calls, hit the wire.

    Reading the row: on an accelerator the win is per-launch latency (62
    dispatches -> ~2), so vs_baseline > 1 is expected; the cpu smoke row
    has near-zero launch cost and pays the pack/unpack memcpy instead, so
    its vs_baseline < 1 — there the row is about `collectives_per_step`
    dropping below `params_per_step`, not the time ratio."""
    import mxnet_tpu as mx
    from mxnet_tpu import engine, nd, telemetry

    shapes = resnet18_grad_shapes()
    steps = 20 if on_accel else 5
    rng = _np.random.RandomState(0)
    # two replicas per key: both paths then do a REAL per-key reduce (the
    # 2-device aggregation shape), not a free store replace
    grads = [[nd.array(rng.randn(*s).astype(_np.float32)) for _ in range(2)]
             for s in shapes]
    outs = [[nd.zeros(s) for _ in range(2)] for s in shapes]
    nbytes = sum(g[0].size * 4 for g in grads)

    def run(bucket_mb):
        with engine.bucket_mb_scope(bucket_mb):
            kv = mx.kv.create("device")
            keys = list(range(len(shapes)))
            for k, s in zip(keys, shapes):
                kv.init(k, nd.zeros(s))
            kv.pushpull(keys, grads, out=outs)  # warm the fused programs
            _sync(outs[0][0].data_jax)
            telemetry.reset()
            t0 = time.perf_counter()
            for _ in range(steps):
                # one cat-`step` span per sync: the window the overlap
                # profiler (telemetry.attribution) decomposes
                ts = telemetry.span_clock()
                s0 = time.perf_counter()
                kv.pushpull(keys, grads, out=outs)
                telemetry.record_span("comm.step", "step", ts,
                                      time.perf_counter() - s0)
            _sync(outs[0][0].data_jax)
            dt = (time.perf_counter() - t0) / steps
            snap = telemetry.snapshot()["counters"]
            ovl = telemetry.overlap_report(site="comm.step")["summary"]
            return dt, snap, ovl

    dt_bucket, snap, ovl = run(None)  # env/default cap
    dt_flat, _, ovl_flat = run(0)     # per-param escape hatch
    # the decomposition is a partition: it must sum to step time (the
    # acceptance's 5% bound holds by construction; report the residue)
    parts = (ovl["compute_ms"] + ovl["collective_ms"] + ovl["host_ms"]
             + ovl["idle_ms"])
    payload = {
        "metric": ("comm_grad_sync_mb_per_sec" if on_accel
                   else "comm_grad_sync_cpu_mb_per_sec"),
        "value": round(nbytes / 1e6 / dt_bucket, 2),
        "unit": "MB/s",
        "vs_baseline": round(dt_flat / dt_bucket, 4),  # speedup vs per-param
        "params_per_step": len(shapes),
        "collectives_per_step": snap.get("comm.collectives", 0) // steps,
        "comm_bucket_bytes": snap.get("comm.bucket.bytes", 0) // steps,
        "comm_bucket_count": snap.get("comm.bucket.count", 0) // steps,
        # measured comm-overlap attribution (ROADMAP #4's autotuner input):
        # bucketed vs per-param overlap fraction + exposed collective ms
        "overlap_frac": ovl["overlap_frac"],
        "overlap_frac_flat": ovl_flat["overlap_frac"],
        "collective_ms_per_step": round(ovl["collective_ms"] / steps, 3),
        "collective_ms_per_step_flat":
            round(ovl_flat["collective_ms"] / steps, 3),
        "decomp_residue_pct": round(
            100.0 * abs(ovl["step_ms"] - parts) / max(ovl["step_ms"],
                                                      1e-9), 4),
    }
    return payload


def bench_comm_readiness(on_accel):
    """BENCH=comm extra legs (ISSUE 19): the readiness-ordered flush
    engine and the schedule autotuner, A/B'd against the
    reverse-registration engine on IDENTICAL traffic (same net, same
    seed, same batches — only the flush policy differs). Emitted as
    separate gated rows so check_bench tracks `overlap_frac_*` (up) and
    `collective_ms_*` (down) as first-class series.

    Reading the rows: `first_flush_before_backward_end=1` is the
    readiness engine's proof-of-life — the first bucket's collective
    launched while backward was still running, which the registration
    engine cannot do by construction (it first sees gradients at step
    time). `parity_ok` asserts the legs' final parameters stayed
    bit-identical, i.e. the overlap was free."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, engine, gluon, nd, telemetry
    from mxnet_tpu.gluon import nn

    steps = 12 if on_accel else 5
    widths = (512, 512, 256, 256, 128)
    cap_mb = 0.5   # several buckets per step: flushes can land mid-backward

    def run(comm_ready, env=None):
        prev_env = {}
        for k, v in (env or {}).items():
            prev_env[k] = os.environ.get(k)
            os.environ[k] = v
        try:
            with engine.bucket_mb_scope(None if env else cap_mb):
                mx.random.seed(0)
                rng = _np.random.RandomState(0)
                net = nn.HybridSequential()
                with net.name_scope():
                    for w in widths:
                        net.add(nn.Dense(w, activation="relu"))
                    net.add(nn.Dense(10))
                net.initialize(mx.init.Xavier())
                tr = gluon.Trainer(net.collect_params(), "sgd",
                                   {"learning_rate": 0.05},
                                   update_on_kvstore=True,
                                   comm_ready=comm_ready)
                x = nd.array(rng.randn(64, 256).astype(_np.float32))
                y = nd.array(rng.randn(64, 10).astype(_np.float32))
                loss_fn = gluon.loss.L2Loss()

                def one_step():
                    with autograd.record():
                        loss = loss_fn(net(x), y)
                    loss.backward()
                    tr.step(64)

                sweep = 0
                if env:   # autotuned leg: let the sweep finish first
                    while tr._autotune is None or not tr._autotune.done:
                        one_step()
                        sweep += 1
                        if sweep > 64:
                            break
                else:
                    for _ in range(2):
                        one_step()     # warm the fused programs
                telemetry.reset()
                for _ in range(steps):
                    one_step()
                _sync(net.collect_params().values().__iter__().__next__()
                      .data().data_jax)
                ovl = telemetry.overlap_report(
                    site="trainer.step")["summary"]
                snap = telemetry.snapshot()["counters"]
                params = [p.data().asnumpy()
                          for p in net.collect_params().values()]
                sched = engine.current_schedule()
                frac = ovl.get("overlap_frac")
                if frac is None and snap.get("comm.collectives", 0):
                    # no comm span inside the step window but collectives
                    # DID run: they all launched during backward — the
                    # whole comm phase is hidden, i.e. full overlap
                    frac = 1.0
                return {
                    "overlap_frac": frac,
                    "collective_ms": round(
                        ovl.get("collective_ms", 0.0) / steps, 3),
                    "first_flush_before_backward_end": min(1, snap.get(
                        "comm.ready.first_flush_before_backward_end", 0)),
                    "flush_during_backward": snap.get(
                        "comm.ready.flush_during_backward", 0) // steps,
                    "ready_rounds": snap.get("comm.ready.rounds", 0),
                    "sweep_steps": sweep,
                    "schedule": sched.describe() if sched else None,
                    "params": params,
                }
        finally:
            for k, v in prev_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            if env:
                engine.set_schedule(None)

    reg = run(False)
    rdy = run(True)
    tuned = run(None, env={"MXNET_TPU_COMM_AUTOTUNE": "1",
                           "MXNET_TPU_COMM_AUTOTUNE_STEPS": "1",
                           "MXNET_TPU_COMM_AUTOTUNE_CAPS": "0,0.5,25"})
    parity = all(_np.array_equal(a, b)
                 for a, b in zip(reg["params"], rdy["params"]))
    unit_f, unit_ms = "frac", "ms"
    rows = [
        {"metric": "overlap_frac_comm_ready", "value": rdy["overlap_frac"],
         "unit": unit_f, "overlap_frac_registration": reg["overlap_frac"],
         "first_flush_before_backward_end":
             rdy["first_flush_before_backward_end"],
         "flush_during_backward_per_step": rdy["flush_during_backward"],
         "ready_rounds": rdy["ready_rounds"], "parity_ok": parity},
        {"metric": "collective_ms_comm_ready", "value": rdy["collective_ms"],
         "unit": unit_ms,
         "collective_ms_registration": reg["collective_ms"]},
        {"metric": "overlap_frac_comm_autotuned",
         "value": tuned["overlap_frac"], "unit": unit_f,
         "schedule": tuned["schedule"],
         "sweep_steps": tuned["sweep_steps"],
         "collective_ms_autotuned": tuned["collective_ms"]},
    ]
    return rows


def bench_zero(on_accel):
    """BENCH=zero: ZeRO-1 weight-update sharding microbench. A
    resnet18-shaped parameter set (62 tensors, ~11.7M params) trains
    through the kvstore with the optimizer ON the store, first as the
    ZeRO sharded updater (reduce-scatter → one fused flat shard update per
    dtype-bucket → all-gather), then as the replicated per-parameter
    updater for the vs_baseline ratio. The JSON row carries the ledger
    that grades a ZeRO implementation: `opt_state_bytes_per_rank`
    (sharded-state footprint — divide `opt_state_bytes_replicated` by the
    world size and you should land here), `collectives_per_step`, and
    `fused_update_ms` (mean host wall time of the fused shard dispatch).

    Single-process rows run at world=1 (the comm legs are identity), so
    the number that moves OFF-chip is dispatch count: 62 per-param
    optimizer launches collapse into one fused launch per bucket."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, telemetry

    shapes = resnet18_grad_shapes()
    steps = 20 if on_accel else 5
    rng = _np.random.RandomState(0)
    grads = [nd.array(rng.randn(*s).astype(_np.float32)) for s in shapes]
    nbytes = sum(g.size * 4 for g in grads)

    def run(zero):
        kv = mx.kv.create("device")
        kv.set_optimizer(mx.optimizer.create(
            "sgd", learning_rate=0.1, momentum=0.9, rescale_grad=1.0),
            zero=zero)
        keys = list(range(len(shapes)))
        for k, s in zip(keys, shapes):
            kv.init(k, nd.array(rng.randn(*s).astype(_np.float32)))
        kv.push(keys, grads)  # warm the fused programs + freeze the layout
        _sync(kv._store["0"].data_jax)
        telemetry.reset()
        t0 = time.perf_counter()
        for _ in range(steps):
            kv.push(keys, grads)
        _sync(kv._store["0"].data_jax)
        dt = (time.perf_counter() - t0) / steps
        snap = telemetry.snapshot()
        return dt, snap

    dt_zero, snap = run(True)
    dt_repl, _ = run(False)
    counters = snap["counters"]
    hist = snap["histograms"].get("opt.fused_update_ms", {})
    state_bytes = snap["gauges"].get(
        "opt.state_bytes_per_rank", {}).get("value", 0)
    world = 1  # single-process bench; dist rows come from tools/launch.py
    return {
        "metric": ("zero_update_mb_per_sec" if on_accel
                   else "zero_update_cpu_mb_per_sec"),
        "value": round(nbytes / 1e6 / dt_zero, 2),
        "unit": "MB/s",
        "vs_baseline": round(dt_repl / dt_zero, 4),  # speedup vs replicated
        "params_per_step": len(shapes),
        "world": world,
        "opt_state_bytes_per_rank": int(state_bytes),
        "opt_state_bytes_replicated": int(state_bytes) * world,
        "collectives_per_step": counters.get("comm.collectives", 0) // steps,
        "reduce_scatter_per_step":
            counters.get("comm.reduce_scatter", 0) // steps,
        "all_gather_per_step": counters.get("comm.all_gather", 0) // steps,
        "fused_updates_per_step": hist.get("count", 0) // steps,
        "fused_update_ms": round(hist.get("sum", 0.0)
                                 / max(1, hist.get("count", 0)), 4),
    }


def bench_resilience(on_accel):
    """BENCH=resilience: recovery-path microbench for the resilience v2
    stack. A small Gluon MLP trains under `ResilientRunner` while the
    deterministic fault harness injects (a) a proactive preemption NOTICE
    through the maintenance poller (`preempt.poll` site — coordinated
    off-cadence checkpoint, zero replay) and (b) a reactive mid-run
    preemption (`run.step` site — restore-and-replay from the last
    periodic snapshot). The JSON row carries the ledger that grades a
    recovery stack: `recovery_time_s` (wall time inside restores),
    `replayed_steps` (work redone — the cost proactive checkpoints
    eliminate), and `proactive_ckpt` (notices converted to checkpoints).
    value = recovery_time_s; vs_baseline = fraction of run wall time lost
    to recovery (lower is better for both)."""
    import tempfile

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, resilience as rz
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.resilience.preempt import PreemptionListener

    steps = 12
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    fused = gluon.FusedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer)
    rng = np.random.RandomState(0)
    X = rng.rand(steps, 32, 8).astype(np.float32)
    Y = rng.randint(0, 4, (steps, 32)).astype(np.float32)

    def batch_fn(i):
        # modulo: a rollback skip advances the data index past the last
        # pre-generated batch
        return nd.array(X[i % steps]), nd.array(Y[i % steps])

    ckpt_dir = tempfile.mkdtemp(prefix="bench_resilience_")
    # notice on the 2nd poll (proactive path: zero replay), hard preemption
    # at step 8 (reactive path: off the ckpt_every=3 cadence, so the
    # restore rewinds to step 6 and replays 2 completed steps — the cost
    # the proactive checkpoint avoids)
    listener = PreemptionListener(poll_interval_s=0.05)
    t0 = time.perf_counter()
    with faults.inject("preempt.poll:preempt:2;run.step:preempt:9"):
        runner = rz.ResilientRunner.for_fused_step(
            fused, batch_fn, ckpt_dir=ckpt_dir, ckpt_every=3,
            max_restarts=4, commit=True, preempt_listener=listener)
        report = runner.run(steps)
    listener.stop()
    total_s = time.perf_counter() - t0

    # --- integrity plane (PR 20) -------------------------------------
    # (a) sentinel overhead A/B: identical clean runs with the fused
    # all-finite check off vs on — the check is ONE scalar reduction
    # riding the already-materialised flat buckets plus one host sync,
    # budget <=2%. Measured on a model whose step time is realistic
    # (~20ms): the sync is a fixed per-step cost, and quoting it against
    # a sub-ms toy step would overstate it ~20x;
    # (b) rollback exercise: a corrupt batch plus a corrupt newest
    # snapshot drive rollback-to-last-good and the checksum fallback.
    def _with_integrity(value, fn):
        old = os.environ.get("MXNET_TPU_INTEGRITY")
        os.environ["MXNET_TPU_INTEGRITY"] = value
        try:
            return fn()
        finally:
            if old is None:
                os.environ.pop("MXNET_TPU_INTEGRITY", None)
            else:
                os.environ["MXNET_TPU_INTEGRITY"] = old

    def _ab_fused():
        mx.random.seed(0)
        n2 = gluon.nn.HybridSequential()
        with n2.name_scope():
            n2.add(gluon.nn.Dense(512, activation="relu"),
                   gluon.nn.Dense(512, activation="relu"),
                   gluon.nn.Dense(16))
        n2.initialize(mx.init.Xavier())
        t2 = gluon.Trainer(n2.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9})
        return gluon.FusedTrainStep(
            n2, gluon.loss.SoftmaxCrossEntropyLoss(), t2)

    ab_rng = np.random.RandomState(1)
    ab_x = nd.array(ab_rng.rand(128, 256).astype(np.float32))
    ab_y = nd.array(ab_rng.randint(0, 16, (128,)).astype(np.float32))
    fused_off = _with_integrity("0", _ab_fused)  # sentinel baked at build
    fused_on = _with_integrity("1", _ab_fused)
    fused_off(ab_x, ab_y).asnumpy()  # compile outside the timed window
    _with_integrity("1", lambda: fused_on(ab_x, ab_y).asnumpy())

    def _chunk(fused2):
        n = 10
        t = time.perf_counter()
        for _ in range(n):
            # per-step loss sync in BOTH legs: the runner records a float
            # loss every step (run.py RunReport.losses) whether or not the
            # sentinel is on, so the A/B isolates the sentinel's marginal
            # cost — the fused reduction — not the loop's own sync
            fused2(ab_x, ab_y).asnumpy()
        return n / (time.perf_counter() - t)

    # paired chunks, median-of-8: adjacent off/on chunks share the box's
    # load conditions, so the per-pair ratio cancels drift and the median
    # sheds spike outliers
    pairs = []
    for _ in range(8):
        off = _chunk(fused_off)
        on = _with_integrity("1", lambda: _chunk(fused_on))
        pairs.append((off, on))
    ratios = sorted(on / off for off, on in pairs)
    mid = (ratios[3] + ratios[4]) / 2.0

    def _fresh_fused():
        mx.random.seed(0)
        n3 = gluon.nn.HybridSequential()
        with n3.name_scope():
            n3.add(gluon.nn.Dense(32, activation="relu"),
                   gluon.nn.Dense(4))
        n3.initialize(mx.init.Xavier())
        t3 = gluon.Trainer(n3.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9})
        return gluon.FusedTrainStep(
            n3, gluon.loss.SoftmaxCrossEntropyLoss(), t3)

    def _rollback_leg():
        from mxnet_tpu import telemetry as _telem
        c0 = _telem.snapshot()["counters"].get(
            "checkpoint.corrupt_fallbacks", 0)
        fused3 = _fresh_fused()
        rb_dir = tempfile.mkdtemp(prefix="bench_rollback_")
        # the 3rd prepare is the step-4 snapshot — the NEWEST candidate
        # when the step-4 divergence rolls back, so the checksum fallback
        # path (restore 2, replay) actually runs
        with faults.inject("train.batch:corrupt:5;"
                           "checkpoint.corrupt:corrupt:3;"
                           "run.step:preempt:9"):
            rb_runner = rz.ResilientRunner.for_fused_step(
                fused3, batch_fn, ckpt_dir=rb_dir, ckpt_every=2,
                max_restarts=4)
            rb_report = rb_runner.run(steps)
        fallbacks = _telem.snapshot()["counters"].get(
            "checkpoint.corrupt_fallbacks", 0) - c0
        return rb_report, fallbacks

    rb_report, corrupt_restores = _with_integrity("1", _rollback_leg)

    return {
        "metric": ("resilience_recovery_time_s" if on_accel
                   else "resilience_cpu_recovery_time_s"),
        "value": round(report.recovery_time_s, 4),
        "unit": "s",
        "vs_baseline": round(report.recovery_time_s / total_s, 4),
        "recovery_time_s": round(report.recovery_time_s, 4),
        "replayed_steps": report.replayed_steps,
        "proactive_ckpt": report.proactive_ckpts,
        "restarts": report.restarts,
        "checkpoints": report.checkpoints,
        "rollbacks": rb_report.rollbacks,
        "skipped_batches": rb_report.skipped_batches,
        "corrupt_restores": corrupt_restores,
        "integrity_overhead_pct": round((1.0 - mid) * 100.0, 2),
    }


def bench_serve(on_accel):
    """BENCH=serve: continuous-batching inference bench for mx.serve
    under a BURST-arrival workload with a shared system prompt. Traffic
    arrives in waves (each wave a burst of requests, most sharing one
    system-prompt prefix), served twice over identical traffic:

    * **v2** — chunked multi-stream prefill + prefix sharing; on an
      accelerator speculative decoding joins this leg (decode is
      HBM-bound there — the regime spec exists for). On the CPU smoke
      row spec is measured in a SEPARATE short leg instead: the identity
      draft doubles compute per token, and on a compute-bound backend
      that rightly loses (the README's when-NOT table) — folding it in
      would let an anti-pattern config distort the SLO columns;
    * **v1-like baseline** — prefix sharing off, no draft, one
      max-context prefill row (the PR 12 batch-1-prefill behavior).

    The identity draft (bench models are random weights, so no *trained*
    small draft exists) exercises the full draft/verify machinery at its
    accept-rate upper bound; a distilled draft lands between accept=1
    and accept=0. vs_baseline = v2/v1 tokens_s; the row also carries the
    v1 numbers (baseline_tokens_s, baseline_ttft_ms_p99) so the TTFT win
    under bursts is visible, the serving SLO numbers (ttft/tpot
    p50/p99), and the attribution columns: accept_rate (spec drafts the
    target agreed with, from whichever leg ran spec), spec_tokens_s (the
    spec leg's own rate), prefix_hit_rate (admissions that reused cached
    prompt blocks), kv_blocks_saved (whole blocks of prefill+HBM skipped
    via sharing). Two deliberately oversized requests prove
    load-shedding sheds (structured Overloaded) instead of OOMing."""
    import dataclasses

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models.llama import CONFIGS, llama_init

    if on_accel:
        cfg = CONFIGS["llama_110m"]
        n_req, base_new, blocks, bs, batch = 32, 32, 512, 16, 8
        sys_len, waves = 48, 4
    else:
        cfg = dataclasses.replace(CONFIGS["llama_tiny"],
                                  dtype=jnp.float32, max_seq_len=64)
        n_req, base_new, blocks, bs, batch = 12, 8, 96, 8, 8
        sys_len, waves = 24, 2
    params = llama_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    sys_prompt = rng.randint(1, cfg.vocab_size - 1, size=sys_len).tolist()
    traffic = []
    for i in range(n_req):
        tail = rng.randint(1, cfg.vocab_size - 1,
                           size=rng.randint(2, 8)).tolist()
        # ~2/3 of users share the system prompt — the prefix-cache case
        prompt = (sys_prompt + tail) if i % 3 else tail
        traffic.append((prompt, base_new + (i % 5)))
    per_wave = -(-n_req // waves)

    def quant(vals, q):
        if not vals:
            return None
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(q * len(vals)))]

    def run(v2, spec=False):
        telemetry.reset()
        kw = {}
        if spec:
            kw.update(draft_params=params, draft_cfg=cfg, spec_k=4)
        if not v2:
            kw.update(prefix_sharing=False, prefill_rows=1,
                      chunk_size=cfg.max_seq_len)
        server = mx.serve.InferenceServer(
            params, cfg, max_batch=batch, kv_blocks=blocks,
            block_size=bs, queue_cap=n_req + 4, **kw)
        server.warmup()
        # a throwaway pass before the clock starts: first-dispatch costs
        # (executable load, backend thread pools) are process-warmth, not
        # engine throughput — without it, whichever variant runs first
        # eats them and the A/B is ordering noise
        for _ in range(2):
            server.submit(mx.serve.Request(
                rng.randint(1, cfg.vocab_size - 1, size=6).tolist(),
                max_new_tokens=4))
        server.run()
        telemetry.reset()
        handles = []
        t0 = time.perf_counter()
        for w in range(waves):
            # one burst: the whole wave lands at once, then drains
            for prompt, max_new in traffic[w * per_wave:
                                           (w + 1) * per_wave]:
                handles.append(server.submit(
                    mx.serve.Request(prompt, max_new_tokens=max_new)))
            server.run()
        # two requests that can NEVER fit: admission must shed them with a
        # structured Overloaded, not OOM the pool mid-decode
        shed = 0
        for _ in range(2):
            try:
                server.submit(mx.serve.Request(
                    [1] * 8, max_new_tokens=cfg.max_seq_len * 4))
            except mx.serve.Overloaded:
                shed += 1
        server.run()
        dt = time.perf_counter() - t0
        toks = sum(len(h.result()) for h in handles)
        return toks / dt, handles, shed

    tok_s, handles, shed = run(v2=True, spec=on_accel)
    snap = telemetry.snapshot()
    gauges = snap["gauges"]
    counters = snap["counters"]
    ttft = [h.ttft_ms for h in handles if h.ttft_ms is not None]
    tpot = [ms for h in handles for ms in h.tpot_ms]
    lookups = counters.get("serve.prefix.lookups", 0)
    tok_s_v1, handles_v1, _ = run(v2=False)
    ttft_v1 = [h.ttft_ms for h in handles_v1 if h.ttft_ms is not None]
    if on_accel:
        spec_tok_s = tok_s
        spec_counters = counters
    else:
        # the accept-rate leg: same traffic through draft/verify — the
        # mechanism metric, kept out of the CPU row's SLO columns
        spec_tok_s, _, _ = run(v2=True, spec=True)
        spec_counters = telemetry.snapshot()["counters"]
    drafted = spec_counters.get("serve.spec.drafted", 0)
    return {
        "metric": ("serve_tokens_per_sec" if on_accel
                   else "serve_cpu_tokens_per_sec"),
        "value": round(tok_s, 2),
        "unit": "tok/s",
        # vs the PR 12-shaped engine: batch-1 monolithic prefill, no
        # prefix reuse, no speculation — same traffic, same batch
        "vs_baseline": round(tok_s / tok_s_v1, 4),
        "tokens_s": round(tok_s, 2),
        "baseline_tokens_s": round(tok_s_v1, 2),
        "ttft_ms_p50": round(quant(ttft, 0.50), 3),
        "ttft_ms_p99": round(quant(ttft, 0.99), 3),
        "baseline_ttft_ms_p99": round(quant(ttft_v1, 0.99), 3),
        "tpot_ms_p50": round(quant(tpot, 0.50), 3),
        "tpot_ms_p99": round(quant(tpot, 0.99), 3),
        "accept_rate": (round(spec_counters.get("serve.spec.accepted", 0)
                              / drafted, 4) if drafted else None),
        "spec_tokens_s": round(spec_tok_s, 2),
        "prefix_hit_rate": (round(counters.get("serve.prefix.hits", 0)
                                  / lookups, 4) if lookups else None),
        "kv_blocks_saved": counters.get("serve.prefix.blocks_shared", 0),
        "prefill_chunks": counters.get("serve.prefill_chunks", 0),
        "queue_depth": gauges.get("serve.queue_depth", {}).get("max", 0),
        "shed_requests": counters.get("serve.shed", shed),
        "kv_blocks_peak": gauges.get("serve.kv.blocks_in_use",
                                     {}).get("max", 0),
        "requests": n_req,
        "recoveries": counters.get("serve.recoveries", 0),
    }


def bench_sparse(on_accel):
    """BENCH=sparse (ISSUE 17): embedding-gradient sync A/B — unique-rows
    sparse comm vs the densified-allreduce baseline, on the SAME id
    traffic. A vocab-sharded `ShardedEmbedding` trains through the
    kvstore sparse push path (row dedup -> Pallas segment-sum ->
    in-place row update) while the served lookup path answers
    row_sparse_pulls from the warmed fixed-bucket gather.

    Wire bytes are MODELED from the measured traffic (the single-process
    smoke row has no wire; the models are the exact byte accounting the
    dist store's `_sparse_sync` counters use): per step the batch's ids
    split across `world` model ranks, then

      sparse = slab x (4 + dim*4) x world      (padded all-gather slab,
                                                slab = max rank nnz)
      dense  = vocab x 4 + union x dim*4       (mask allreduce + dense
                                                union allreduce — the
                                                MXNET_TPU_SPARSE_DENSE_PUSH
                                                leg)

    so `comm_bytes_saved` is the per-run total the sparse path keeps off
    the wire — strictly positive whenever the touched fraction is small
    (the acceptance bar). value = pushed rows/s through the REAL sparse
    path; vs_baseline = dense/sparse modeled byte ratio (>1 = sparse
    wins). `lookup_ms_p50/p99` time the REAL served gather; the
    segment-sum dispatch/fallback counters prove which kernel ran."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, telemetry
    from mxnet_tpu.embedding import ShardedEmbedding
    from mxnet_tpu.ndarray import sparse as sp

    vocab, dim = (1_000_000, 64) if on_accel else (50_000, 32)
    nnz, steps, world = (8192, 20, 4) if on_accel else (1024, 6, 4)
    rng = np.random.RandomState(0)

    table = ShardedEmbedding(vocab, dim, optimizer="sgd",
                             learning_rate=0.1, name="bench.sparse")
    kv = mx.kv.create("local")
    svc = kv.init_embedding(0, table, max_batch=nnz)

    # zipf-skewed traffic: the hot-row regime sparse comm exists for
    raw = rng.zipf(1.3, size=(steps, nnz)).astype(np.int64) % vocab
    batches = [np.unique(b).astype(np.int32) for b in raw]

    telemetry.reset()
    row_nb = dim * 4
    sparse_bytes = dense_bytes = pushed = 0
    union_rows = []
    lookup_ms = []
    t0 = time.perf_counter()
    for ids in batches:
        grads = rng.randn(len(ids), dim).astype(np.float32)
        kv.push(0, sp.RowSparseNDArray(grads, sp.jnp.asarray(ids),
                                       (vocab, dim)))
        # model the wire for the same traffic spread over `world` ranks
        per_rank = np.array_split(ids, world)
        slab = max(len(r) for r in per_rank)
        sparse_bytes += slab * (4 + row_nb) * world
        dense_bytes += vocab * 4 + len(ids) * row_nb
        union_rows.append(len(ids))
        pushed += len(ids)
        # served read-back of a hot subset through the compiled gather
        hot = sp.jnp.asarray(ids[:min(256, len(ids))])
        tmp = sp.zeros("row_sparse", (vocab, dim))
        t1 = time.perf_counter()
        kv.row_sparse_pull(0, out=tmp, row_ids=nd.array(hot))
        _sync(tmp._values)
        lookup_ms.append((time.perf_counter() - t1) * 1e3)
    _sync(table.weight)
    dt = time.perf_counter() - t0

    lookup_ms.sort()
    snap = telemetry.snapshot()["counters"]
    pct = 100.0 * float(np.mean(union_rows)) / vocab
    return {
        "metric": ("sparse_embed_push_rows_per_sec" if on_accel
                   else "sparse_embed_cpu_push_rows_per_sec"),
        "value": round(pushed / dt, 2),
        "unit": "rows/s",
        "vs_baseline": round(dense_bytes / sparse_bytes, 4),
        "vocab": vocab,
        "dim": dim,
        "world_model": world,
        "sparse_rows_pct": round(pct, 4),
        "comm_bytes_sparse": int(sparse_bytes),
        "comm_bytes_dense": int(dense_bytes),
        "comm_bytes_saved": int(dense_bytes - sparse_bytes),
        "lookup_ms_p50": round(lookup_ms[len(lookup_ms) // 2], 3),
        "lookup_ms_p99": round(
            lookup_ms[min(len(lookup_ms) - 1,
                          int(0.99 * len(lookup_ms)))], 3),
        "segment_sum_pallas":
            snap.get("ops.pallas.dispatch.segment_sum", 0),
        "segment_sum_fallback": sum(
            v for k, v in snap.items()
            if k.startswith("ops.pallas.fallback.segment_sum.")),
        "serve_retraces": snap.get("serve.retrace", 0),
        "unique_rows": snap.get("embedding.push.unique_rows", 0),
    }


def bench_obs(on_accel):
    """BENCH=obs: observability-plane microbench. A small Gluon MLP trains
    under the live /metrics endpoint while the bench scrapes it, measuring
    what the telemetry plane itself costs: per-scrape latency (p50/p99 µs,
    lock contention against the stepping thread included) and the rolling
    p50/p99 step latency the quantile tracker reports. value = p50 scrape
    latency; vs_baseline = scrape p50 as a fraction of step p50 (how big a
    bite one monitoring poll takes out of a step — smaller is better)."""
    import threading
    import urllib.request

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, telemetry
    from mxnet_tpu.telemetry import export

    # this bench MEASURES the telemetry plane — it cannot run disabled
    if not telemetry.ENABLED:
        print("# BENCH=obs: enabling telemetry (it is the thing under "
              "test)", file=sys.stderr)
        telemetry.enable()

    scrapes = 50
    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(64, activation="relu"), gluon.nn.Dense(8))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    fused = gluon.FusedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer)
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(32, 16).astype(np.float32))
    y = nd.array(rng.randint(0, 8, (32,)).astype(np.float32))

    telemetry.reset()
    server = export.start_http_server(0)  # ephemeral port
    url = "http://127.0.0.1:%d/metrics" % server.port
    try:
        fused(x, y)  # compile outside the measured window
        stop = threading.Event()

        def train():
            while not stop.is_set():
                fused(x, y)

        t = threading.Thread(target=train, daemon=True)
        t.start()
        lat_us = []
        try:
            for _ in range(scrapes):
                t0 = time.perf_counter()
                urllib.request.urlopen(url, timeout=5).read()
                lat_us.append((time.perf_counter() - t0) * 1e6)
        finally:
            stop.set()
            t.join(timeout=10)
        # parity check on a QUIESCED registry (stepping thread joined, a
        # fresh scrape): counters created after the last timed scrape must
        # not read as a false exporter mismatch
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        parsed = export.parse_prometheus_text(body)
        parity = parsed == telemetry.snapshot()["counters"]
        lat_us.sort()
        p50_us = lat_us[len(lat_us) // 2]
        p99_us = lat_us[min(len(lat_us) - 1, int(0.99 * len(lat_us)))]
        q = telemetry.step_quantiles("fused_step") or {}
        step_p50_ms = q.get("p50") or float("nan")
        # federation scrape overhead: /fleet/snapshot with no peers is the
        # local-only fleet view — the fixed cost of the proxy path itself
        # (collect + merge + serialize), before any network fan-out
        fleet_url = "http://127.0.0.1:%d/fleet/snapshot" % server.port
        fleet_us = []
        for _ in range(20):
            t0 = time.perf_counter()
            urllib.request.urlopen(fleet_url, timeout=5).read()
            fleet_us.append((time.perf_counter() - t0) * 1e6)
        fleet_us.sort()
        return {
            "metric": ("obs_scrape_p50_us" if on_accel
                       else "obs_cpu_scrape_p50_us"),
            "value": round(p50_us, 1),
            "unit": "us",
            "vs_baseline": round(p50_us / (step_p50_ms * 1e3), 4)
            if step_p50_ms == step_p50_ms else None,
            "scrape_p99_us": round(p99_us, 1),
            "scrape_parity": bool(parity),
            "step_ms_p50": round(q.get("p50", 0.0), 3),
            "step_ms_p99": round(q.get("p99", 0.0), 3),
            "scrapes": len(lat_us),
            "fleet_scrape_p50_us": round(fleet_us[len(fleet_us) // 2], 1),
            **_bench_request_trace_overhead(),
            **_bench_ledger_overhead(),
        }
    finally:
        export.stop_http_server()


def _bench_request_trace_overhead():
    """Per-request tracing overhead (the ISSUE 12 acceptance ceiling:
    <= 2% of serve tokens/s): the same tiny-llama traffic served with
    request tracing ON (default) and OFF (MXNET_TPU_SERVE_TRACE=0);
    reports both rates and the relative cost."""
    import dataclasses

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models.llama import CONFIGS, llama_init

    cfg = dataclasses.replace(CONFIGS["llama_tiny"], dtype=jnp.float32,
                              max_seq_len=64)
    params = llama_init(jax.random.PRNGKey(0), cfg)

    def run(trace_on):
        prev = os.environ.get("MXNET_TPU_SERVE_TRACE")
        os.environ["MXNET_TPU_SERVE_TRACE"] = "1" if trace_on else "0"
        try:
            telemetry.reset()
            server = mx.serve.InferenceServer(
                params, cfg, max_batch=4, kv_blocks=64, block_size=8,
                max_context=48, queue_cap=32)
            server.warmup()
            rng = np.random.RandomState(0)
            prompts = [rng.randint(1, cfg.vocab_size - 1,
                                   size=rng.randint(4, 12)).tolist()
                       for _ in range(10)]
            handles = [server.submit(mx.serve.Request(p, max_new_tokens=16))
                       for p in prompts]
            t0 = time.perf_counter()
            server.run()
            dt = time.perf_counter() - t0
            toks = sum(len(h.result(timeout=60)) for h in handles)
            return toks / dt
        finally:
            if prev is None:
                os.environ.pop("MXNET_TPU_SERVE_TRACE", None)
            else:
                os.environ["MXNET_TPU_SERVE_TRACE"] = prev

    # cold-start and scheduling noise on the CPU smoke row dwarfs the
    # per-token mark cost: warm both modes once, then interleave pairs
    # and compare MEDIANS (the first measured attempt was order-biased
    # by a cold first run)
    import statistics
    run(True)
    run(False)
    traced_runs, untraced_runs = [], []
    for i in range(3):
        if i % 2 == 0:
            traced_runs.append(run(True))
            untraced_runs.append(run(False))
        else:
            untraced_runs.append(run(False))
            traced_runs.append(run(True))
    traced = statistics.median(traced_runs)
    untraced = statistics.median(untraced_runs)
    return {
        "serve_tok_s_traced": round(traced, 2),
        "serve_tok_s_untraced": round(untraced, 2),
        "request_trace_overhead_pct": round(
            max(0.0, (untraced - traced) / untraced * 100.0), 3),
    }


def _bench_ledger_overhead():
    """HBM-ledger + profiling-plane overhead (the ISSUE 16 acceptance
    ceiling: <= 2% of serve tokens/s): the same tiny-llama traffic served
    with the memory ledger ON (default) and OFF (MXNET_TPU_LEDGER=0 —
    every ledger.account()/reconcile at the KV pool, prefix cache, and
    program-footprint sites goes quiet). Same interleaved-medians shape
    as _bench_request_trace_overhead: cold-start noise on the CPU smoke
    row dwarfs the per-admit accounting cost, so warm both modes first
    and compare medians of interleaved pairs. Each run serves enough
    tokens (~0.2 s on the CPU smoke row) that the once-per-second
    reconcile amortizes the way it does in a real serve process — a
    40 ms burst charges the whole 1.4 ms live_arrays scan to one run
    and reads as a fake 3% regression."""
    import dataclasses
    import statistics

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models.llama import CONFIGS, llama_init

    cfg = dataclasses.replace(CONFIGS["llama_tiny"], dtype=jnp.float32,
                              max_seq_len=64)
    params = llama_init(jax.random.PRNGKey(0), cfg)

    def run(ledger_on):
        prev = os.environ.get("MXNET_TPU_LEDGER")
        os.environ["MXNET_TPU_LEDGER"] = "1" if ledger_on else "0"
        try:
            telemetry.reset()
            server = mx.serve.InferenceServer(
                params, cfg, max_batch=4, kv_blocks=64, block_size=8,
                max_context=48, queue_cap=32)
            server.warmup()
            rng = np.random.RandomState(0)
            prompts = [rng.randint(1, cfg.vocab_size - 1,
                                   size=rng.randint(4, 12)).tolist()
                       for _ in range(24)]
            handles = [server.submit(mx.serve.Request(p, max_new_tokens=32))
                       for p in prompts]
            t0 = time.perf_counter()
            server.run()
            dt = time.perf_counter() - t0
            toks = sum(len(h.result(timeout=60)) for h in handles)
            return toks / dt
        finally:
            if prev is None:
                os.environ.pop("MXNET_TPU_LEDGER", None)
            else:
                os.environ["MXNET_TPU_LEDGER"] = prev

    run(True)
    run(False)
    on_runs, off_runs = [], []
    for i in range(3):
        if i % 2 == 0:
            on_runs.append(run(True))
            off_runs.append(run(False))
        else:
            off_runs.append(run(False))
            on_runs.append(run(True))
    with_ledger = statistics.median(on_runs)
    without = statistics.median(off_runs)
    return {
        "serve_tok_s_ledger": round(with_ledger, 2),
        "serve_tok_s_no_ledger": round(without, 2),
        "ledger_overhead_pct": round(
            max(0.0, (without - with_ledger) / without * 100.0), 3),
    }


def bench_startup_child():
    """The measured body of BENCH=startup, run in a fresh subprocess: the
    program-build work a replica pays at boot — a symbolic Module bind +
    whole-graph training forward, and an mx.serve warmup() (chunk
    prefill + decode + CoW copy). With a warm MXNET_TPU_AOT_CACHE every
    one of these executables restores from disk: compile_count drops to 0
    and cache_hits counts the restored programs. Prints ONE JSON line.
    (`tools/prebake_cache.py` drives the same warmup from a manifest to
    pre-populate a fleet's shared cache.)"""
    t0 = time.perf_counter()
    _device()
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import symbol as sym, telemetry
    from mxnet_tpu.io.io import DataBatch
    from mxnet_tpu.models.llama import LlamaConfig, llama_init
    from mxnet_tpu.serve.kv_cache import KVBlockPool
    from mxnet_tpu.serve.programs import ServePrograms

    # 1) symbolic path: bind + one whole-graph forward+backward program
    data = sym.var("data")
    fc1 = sym.FullyConnected(data, name="fc1", num_hidden=32)
    act = sym.Activation(fc1, name="relu1", act_type="relu")
    fc2 = sym.FullyConnected(act, name="fc2", num_hidden=8)
    net = sym.SoftmaxOutput(fc2, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu(),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (8, 16))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    rng = np.random.RandomState(0)
    batch = DataBatch([mx.nd.array(rng.rand(8, 16).astype(np.float32))],
                      [mx.nd.array(rng.randint(0, 8, (8,))
                                   .astype(np.float32))])
    mod.forward(batch, is_train=True)
    mod.backward()

    # 2) serving path: every warmup executable a replica needs
    cfg = LlamaConfig(vocab_size=128, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=64, rope_theta=10000.0,
                      max_seq_len=32)
    import jax
    params = llama_init(jax.random.PRNGKey(0), cfg)
    pool = KVBlockPool(cfg, num_blocks=16, block_size=8)
    ServePrograms(params, cfg, pool, max_batch=2, max_context=16).warmup()

    c = telemetry.snapshot()["counters"]
    print(json.dumps({
        "startup_s": round(time.perf_counter() - t0, 4),
        "compile_count": (c.get("compiler.compile", 0)
                          + c.get("serve.compile", 0)),
        "cache_hits": c.get("compiler.cache.hits", 0),
        "cache_misses": c.get("compiler.cache.misses", 0),
        "cache_writes": c.get("compiler.cache.writes", 0),
        "fallbacks": c.get("compiler.fallback", 0),
    }))


def bench_startup():
    """BENCH=startup (ISSUE 11): cold vs warm-AOT-cache process start.
    Spawns the same child workload twice against ONE fresh cache
    directory — the first run compiles and writes, the second must
    restore every executable (compile_count 0, cache_hits > 0). A
    pre-set MXNET_TPU_AOT_CACHE is deliberately ignored: the cold child
    must actually be cold, or the row measures a warm restore twice.
    value = the warm child's program-build seconds; vs_baseline =
    cold/warm build-time ratio (how many times faster a fleet replica
    boots once one sibling has paid the compiles)."""
    import shutil
    import subprocess
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="mx_aot_startup_")
    env = dict(os.environ, BENCH="startup_child",
               MXNET_TPU_AOT_CACHE=cache_dir)
    # the cold child is cold for JAX's own compilation cache too
    env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def child(tag):
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True, timeout=900)
        wall = time.perf_counter() - t0
        if proc.returncode != 0:
            raise RuntimeError("startup child (%s) failed:\n%s"
                               % (tag, proc.stderr[-2000:]))
        row = json.loads(
            [ln for ln in proc.stdout.splitlines()
             if ln.startswith("{")][-1])
        row["process_wall_s"] = round(wall, 3)
        return row

    try:
        cold = child("cold")
        warm = child("warm")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return {
        "metric": "startup_warm_s",
        "value": warm["startup_s"],
        "unit": "s",
        "startup_cold_s": cold["startup_s"],
        "startup_warm_s": warm["startup_s"],
        "process_wall_cold_s": cold["process_wall_s"],
        "process_wall_warm_s": warm["process_wall_s"],
        "compile_count_cold": cold["compile_count"],
        "compile_count_warm": warm["compile_count"],
        "cache_hits_warm": warm["cache_hits"],
        "cache_writes_cold": cold["cache_writes"],
        "vs_baseline": round(cold["startup_s"]
                             / max(warm["startup_s"], 1e-9), 4),
    }


def main():
    which = os.environ.get("BENCH", "gluon")
    if which == "startup":
        # a chip belongs to one process: both children need it, so they
        # run before this parent initialises a backend
        _emit(bench_startup())
        return
    if which == "startup_child":
        bench_startup_child()
        return
    from mxnet_tpu.runtime import place_compile_cache
    place_compile_cache()
    on_accel = _device().platform != "cpu"
    if which in ("fused", "fused_train"):
        os.environ.setdefault("MXNET_TPU_USE_PALLAS", "1")
        if not on_accel:
            os.environ.setdefault("MXNET_FLASH_INTERPRET", "1")
        bench_fn = (bench_fused_stage if which == "fused"
                    else bench_fused_train_stage)
        fast, base = bench_fn(on_accel)
        name = ("fused_conv_bn_relu" if which == "fused"
                else "fused_conv_bn_relu_train")
        _emit({
            "metric": ("%s_img_per_sec" % name if on_accel
                       else "%s_cpu_img_per_sec" % name),
            "value": round(fast, 2),
            "unit": "img/s",
            "vs_baseline": round(fast / base, 4),   # vs XLA composed
        })
        return
    if which in ("fused_bwd", "fused_opt"):
        os.environ.setdefault("MXNET_TPU_USE_PALLAS", "1")
        if not on_accel:
            os.environ.setdefault("MXNET_FLASH_INTERPRET", "1")
        _emit((bench_fused_bwd if which == "fused_bwd"
               else bench_fused_opt)(on_accel))
        return
    if which == "comm":
        _emit(bench_comm(on_accel))
        for row in bench_comm_readiness(on_accel):
            _emit(row)
        return
    if which == "zero":
        _emit(bench_zero(on_accel))
        return
    if which == "sparse":
        os.environ.setdefault("MXNET_TPU_USE_PALLAS", "1")
        if not on_accel:
            os.environ.setdefault("MXNET_FLASH_INTERPRET", "1")
        _emit(bench_sparse(on_accel))
        return
    if which == "resilience":
        _emit(bench_resilience(on_accel))
        return
    if which == "obs":
        _emit(bench_obs(on_accel))
        return
    if which == "serve":
        _emit(bench_serve(on_accel))
        return
    if which in ("bert", "bert_gluon"):
        tok_s, _ = (bench_bert if which == "bert"
                    else bench_bert_gluon)(on_accel)
        bert_bar = 126720.0
        name = ("bert_base_train_tok_per_sec" if on_accel
                else "bert_tiny_cpu_tok_per_sec")
        if which == "bert_gluon":
            name = name.replace("tok_per_sec", "gluon_tok_per_sec")
        _emit({
            "metric": name,
            "value": round(tok_s, 2),
            "unit": "tok/s",
            "vs_baseline": round(tok_s / bert_bar, 4),
        })
        return
    if which == "functional":
        img_s, path = bench_functional(on_accel)
    elif which == "gluon_nhwc":
        img_s, path = bench_gluon(on_accel, layout="NHWC")
        path = "gluon_nhwc"
    elif which == "gluon_fused":
        # the full headline model with the TRAINING-form fused
        # conv+BN+ReLU blocks in every bottleneck (ROOFLINE round-5)
        os.environ["MXNET_TPU_FUSED_CONVBN"] = "1"
        os.environ.setdefault("MXNET_TPU_USE_PALLAS", "1")
        if not on_accel:
            os.environ.setdefault("MXNET_FLASH_INTERPRET", "1")
        img_s, path = bench_gluon(on_accel, layout="NHWC")
        path = "gluon_fused"
    else:
        layout = os.environ.get("MXNET_HEADLINE_LAYOUT", "NCHW")
        img_s, path = bench_gluon(on_accel, layout=layout)
    if on_accel:
        name = "resnet50_train_img_per_sec"
        if path != "gluon":
            name += "_" + path
    else:
        # CPU smoke paths measure different tiny models — name them honestly
        # (round-1 key kept for the functional config)
        name = ("resnet_tiny_cpu_img_per_sec" if path == "functional"
                else "resnet18_cpu_%s_img_per_sec" % path)
    _emit({
        "metric": name,
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 4),
    })


if __name__ == "__main__":
    main()
