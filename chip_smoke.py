#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             one TPU chip; non-zero exit anywhere else
    python chip_smoke.py --chips 4   adds the trainer over a four-chip data mesh
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                     the same code at toy size on the CPU

One process, through the entry points a user calls, weights and data made
from a seed:

* trainer: the README quick start at full width — `model_zoo.vision.
  resnet50_v1` (bf16 conv stack, fp32 BatchNorm) + `gluon.Trainer` (SGD with
  momentum) + `gluon.FusedTrainStep` in `mx.tpu()`, batch 256 x 3 x 224 x 224.
  One step timed with its compile, five more timed one by one, then a window
  of five ended by one barrier and a one-element read-back (the barrier
  check: had the barrier returned early, the read-back would carry the work).
  The loss on the repeated batch must fall step by step to below chance.
* mesh (`--chips N`): the same model, seed and global batch through
  `FusedTrainStep(mesh=create_mesh(data=N))`, held to the one-chip loss.
* kernel: three BERT-base masked-LM steps (128 x 128, `value_and_grad` and
  AdamW in one jit) on the Pallas flash attention that every TPU run gets by
  default, held to the same step traced with the plain-XLA attention.

Each requirement is printed as it is met; the first one that is not ends the
run with a non-zero exit. What was measured (times, losses, compile seconds)
goes out as one `measured: {...}` line; the last line of standard output is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`, the
device as JAX reports it and nothing else — on a TPU only: without one the
script names the platform it found and prints no result, and `--rehearse`
ends on `REHEARSAL (cpu): not a chip result`.
"""
import argparse
import importlib.metadata
import json
import os
import re
import time

# SGD with momentum 0.9 on one repeated batch, no warm-up: at 0.1 the loss
# turns after three steps and climbs back to chance; at 0.01 it falls on
# every step. The step's time does not depend on it.
LEARNING_RATE = 0.01
BF16_ULP = 2.0 ** -7        # relative, at most
LOSS_RTOL = BF16_ULP        # flash vs plain-XLA attention, first-step loss
GRAD_COS_MIN = 0.99         # layer-0 attention weight grads, flash vs plain
# one chip vs the mesh, same seed: __graft_entry__.dryrun_multichip holds
# its sharded-vs-single parameters to this (f32 reduction order under bf16)
MESH_RTOL, MESH_ATOL = 2e-3, 3e-3
MESH_LATER_RTOL = 2 * BF16_ULP  # the steps after: bf16 weights, other order
BARRIER_MAX_SHARE = 0.05


def require(cond, what):
    if not cond:
        raise SystemExit("chip_smoke: FAILED: %s" % what)
    print("  [ok] %s" % what, flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def live_programs_taking(shape):
    """Optimized HLO of every live program with an entry parameter of this
    shape: where the partitioner's all-reduce can be read."""
    import jax.extend
    takes = re.compile(re.escape(shape) + r"\S* parameter\(")
    return [text
            for exe in jax.extend.backend.get_backend().live_executables()
            for text in (mod.to_string() for mod in exe.hlo_modules())
            if takes.search(text)]


def trainer_phase(rehearse, chips, platform):
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, telemetry
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import create_mesh

    model, classes, batch, size = (("resnet18_v1", 10, 8, 32) if rehearse
                                   else ("resnet50_v1", 1000, 256, 224))
    print("trainer: %s classes=%d batch=%d x 3 x %d x %d on %d chip(s)"
          % (model, classes, batch, size, size, chips), flush=True)
    ctx = mx.cpu() if rehearse else mx.tpu()
    mesh = create_mesh(data=chips) if chips > 1 else None
    if mesh is not None:
        require(len(set(mesh.devices.flat)) == chips
                and all(d.platform == platform for d in mesh.devices.flat),
                "the mesh holds %d distinct %s devices" % (chips, platform))

    def counters():
        snap = telemetry.snapshot()["counters"]
        return (snap.get("fused_step.compile", 0),
                snap.get("fused_step.retrace", 0))

    compiles0, retraces0 = counters()
    mx.random.seed(0)
    with mx.Context(ctx):
        net = getattr(vision, model)(classes=classes)
        net.initialize(mx.init.Xavier(rnd_type="gaussian"), ctx=ctx)
        net.cast("bfloat16")        # conv stack bf16; BatchNorm stays fp32
        net.hybridize(static_alloc=True)
        rng = np.random.RandomState(1)
        x = nd.array(rng.randn(batch, 3, size, size), ctx=ctx,
                     dtype="bfloat16")
        y = nd.array(rng.randint(0, classes, (batch,)), ctx=ctx,
                     dtype="float32")
        _, forward_s = timed(lambda: net(x).wait_to_read())
        print("  forward (shape inference, compile + run): %.2f s"
              % forward_s, flush=True)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": LEARNING_RATE,
                                 "momentum": 0.9})
        step = gluon.FusedTrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer, mesh=mesh)

        n_steps = 1 + 5 + 5     # compiled with, timed singly, in the window
        loss, first_step_s = timed(lambda: step(x, y).wait_to_read())
        losses = [float(loss.asnumpy())]
        print("  step 1 (compile + run): %.2f s  loss %.4f"
              % (first_step_s, losses[0]), flush=True)
        compiles, retraces = counters()
        require(compiles - compiles0 == 1 and retraces == retraces0,
                "telemetry after the first step: fused_step.compile +1, "
                "fused_step.retrace +0")

        step_s = []
        for i in range(5):
            loss, dt = timed(lambda: step(x, y).wait_to_read())
            step_s.append(dt)
            losses.append(float(loss.asnumpy()))
            print("  step %d: %.2f ms  loss %.4f"
                  % (i + 2, dt * 1e3, losses[-1]), flush=True)

        # barrier check: five steps in flight, one barrier, then one element
        # device-to-host. Every later timing rests on the barrier being real.
        def window():
            outs = [step(x, y) for _ in range(5)]
            outs[-1].wait_to_read()
            return outs
        outs, window_s = timed(window)
        loss = outs[-1]
        value, d2h_s = timed(lambda: float(loss.asnumpy()))
        losses += [float(o.asnumpy()) for o in outs[:-1]] + [value]
        print("  barrier check: window of 5 steps %.2f ms, one-element "
              "read-back after it %.3f ms (%.2f%%)"
              % (window_s * 1e3, d2h_s * 1e3, 100 * d2h_s / window_s),
              flush=True)
        require(d2h_s <= BARRIER_MAX_SHARE * window_s,
                "the read-back after the barrier takes at most %d%% of the "
                "window" % (100 * BARRIER_MAX_SHARE))

    print("  losses, steps 7-%d: %s"
          % (n_steps, " ".join("%.4f" % v for v in losses[6:])), flush=True)
    require(all(np.isfinite(losses)), "every loss is finite")
    chance = float(np.log(classes))
    ulp = BF16_ULP * losses[0]      # a memorised toy batch hovers near 0
    require(all(b <= a + ulp for a, b in zip(losses, losses[1:]))
            and losses[-1] < min(losses[0], chance),
            "the loss on the repeated batch rose on none of %d steps and "
            "fell from %.4f to %.4f, below chance (ln %d = %.4f)"
            % (n_steps, losses[0], losses[-1], classes, chance))
    compiles, retraces = counters()
    require(compiles - compiles0 == 1 and retraces == retraces0,
            "all %d steps ran one compiled program: fused_step.compile +1, "
            "fused_step.retrace +0" % n_steps)

    homes = [loss.data_jax.devices()] + [
        p.data(ctx).data_jax.devices()
        for p in net.collect_params().values()]
    require(all(d.platform == platform for s in homes for d in s),
            "the loss and all %d parameters live on %s devices"
            % (len(homes) - 1, platform))
    require(all(len(s) == chips for s in homes[1:]),
            "every parameter is held by %d chip(s)" % chips)

    all_reduces = 0
    if chips > 1:
        # the step stages the batch onto the mesh itself, so its share per
        # chip shows in the one program compiled to take it
        per_chip = "bf16[%d,3,%d,%d]" % (batch // chips, size, size)
        taking = live_programs_taking(per_chip)
        require(len(taking) == 1,
                "one compiled program takes %s: 1/%d of the batch on each "
                "chip" % (per_chip, chips))
        all_reduces = (taking[0].count(" all-reduce(")
                       + taking[0].count(" all-reduce-start("))
        require(all_reduces > 0,
                "that program holds %d all-reduce op(s)" % all_reduces)

    stats = [d.memory_stats() for d in jax.devices()[:chips]]
    peaks = [s["peak_bytes_in_use"] if s else None for s in stats]
    print("  peak_bytes_in_use per chip: %s" % peaks, flush=True)
    if not rehearse:    # the cpu backend reports no memory statistics
        require(all(s["bytes_in_use"] > 0 for s in stats),
                "bytes_in_use is non-zero on every one of %d chip(s)" % chips)
    return {"model": model, "batch": batch, "chips": chips,
            "forward_s": forward_s, "first_step_s": first_step_s,
            "step_s": step_s, "window_s": window_s, "window_steps": 5,
            "d2h_s": d2h_s, "losses": losses, "all_reduces": all_reduces,
            "peak_bytes_in_use": peaks}


def kernel_phase(rehearse):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.models.bert import CONFIGS, bert_init, bert_mlm_loss

    name, batch, seq = (("bert_tiny", 4, 32) if rehearse
                        else ("bert_base", 128, 128))
    cfg = CONFIGS[name]
    print("kernel: %s masked-LM, batch %d x seq %d, flash attention"
          % (name, batch, seq), flush=True)
    lr, b1, b2, eps, wd = 1e-4, 0.9, 0.999, 1e-6, 0.01
    f32 = jnp.float32
    tmap = jax.tree_util.tree_map

    def make_step():
        # a fresh function each time: the attention path is chosen while
        # tracing, and a second jit of one function would reuse the trace
        def step(params, m, v, t, data):
            loss, grads = jax.value_and_grad(bert_mlm_loss)(params, data, cfg)
            t = t + 1
            corr = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)

            def adamw(p, g, mi, vi):
                g, p32 = g.astype(f32), p.astype(f32)
                mi = b1 * mi + (1 - b1) * g
                vi = b2 * vi + (1 - b2) * g * g
                p32 = p32 - lr * (corr * mi / (jnp.sqrt(vi) + eps) + wd * p32)
                return p32.astype(p.dtype), mi, vi

            new = tmap(adamw, params, grads, m, v)
            params, m, v = (
                tmap(lambda n, i=i: n[i], new,
                     is_leaf=lambda n: isinstance(n, tuple))
                for i in range(3))
            # the gradients that reach layer 0's q/k/v weights pass through
            # every backward kernel above them; the loss at random weights
            # barely depends on the attention pattern
            probe = {k: grads["layers"]["0"]["attn"][k].astype(f32)
                     for k in ("wq", "wk", "wv")}
            return params, m, v, t, loss, probe
        return jax.jit(step)

    params = bert_init(jax.random.PRNGKey(0), cfg)
    m = tmap(lambda p: jnp.zeros(p.shape, f32), params)
    v = tmap(lambda p: jnp.zeros(p.shape, f32), params)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    data = {"tokens": jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size),
            "targets": jax.random.randint(k2, (batch, seq), 0,
                                          cfg.vocab_size),
            "mask": (jax.random.uniform(k3, (batch, seq)) < 0.15
                     ).astype(jnp.int32)}
    args = (params, m, v, jnp.int32(0), data)

    def build(disable_flash):
        if disable_flash:
            os.environ["MXNET_FLASH_DISABLE"] = "1"
        lowered = make_step().lower(*args)
        os.environ.pop("MXNET_FLASH_DISABLE", None)
        return lowered.as_text(), lowered.compile()

    require(os.environ.get("MXNET_FLASH_DISABLE", "0") != "1",
            "MXNET_FLASH_DISABLE is not set")
    (text, flash), compile_s = timed(lambda: build(False))
    # counted in the compiled module: the layers share one lowering of each
    # kernel (the entries are jitted), so the lowered text holds three
    calls = flash.as_text().count('custom_call_target="tpu_custom_call"')
    # the interpreter lowers a kernel to plain HLO, so a rehearsal has none
    want = 0 if rehearse else 3 * cfg.n_layers
    require(calls == want and (rehearse or all(
        'kernel_name = "%s"' % k in text
        for k in ("flash_fwd", "flash_dq", "flash_dkv"))),
        "the lowered step holds %d Mosaic custom calls: forward, dq and "
        "dk/dv for each of %d layers" % (want, cfg.n_layers))
    print("  compile: %.2f s" % compile_s, flush=True)

    (ref_text, ref), ref_compile_s = timed(lambda: build(True))
    require("@tpu_custom_call" not in ref_text,
            "the reference step holds no Mosaic custom call")
    ref_loss, ref_probe = jax.block_until_ready(ref(*args)[4:])

    state, losses, step_s = args[:4], [], []
    for i in range(3):
        out, dt = timed(lambda: jax.block_until_ready(flash(*state, data)))
        state, loss, probe = out[:4], out[4], out[5]
        if i == 0:
            first_probe = probe
        losses.append(float(loss))
        step_s.append(dt)
        print("  step %d: %.2f ms  loss %.4f" % (i + 1, dt * 1e3, losses[-1]),
              flush=True)
    require(all(np.isfinite(losses)), "every loss is finite")
    ref_loss = float(ref_loss)
    require(abs(losses[0] - ref_loss) <= LOSS_RTOL * abs(ref_loss),
            "first-step loss %.5f agrees with the plain-XLA attention's %.5f "
            "to %.1e relative" % (losses[0], ref_loss, LOSS_RTOL))

    def cos(a, b):
        a, b = np.asarray(a, np.float64).ravel(), \
            np.asarray(b, np.float64).ravel()
        return float(a @ b / np.sqrt((a @ a) * (b @ b)))
    grad_cos = {k: cos(first_probe[k], ref_probe[k]) for k in first_probe}
    require(all(c >= GRAD_COS_MIN for c in grad_cos.values()),
            "layer-0 attention gradients agree with the plain-XLA backward: "
            "cosine %s" % {k: round(c, 5) for k, c in grad_cos.items()})
    return {"model": name, "batch": batch, "seq": seq, "custom_calls": calls,
            "compile_s": compile_s, "ref_compile_s": ref_compile_s,
            "step_s": step_s, "losses": losses, "ref_loss": ref_loss,
            "grad_cos": grad_cos}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1,
                    help="also run the trainer over a data mesh of this many "
                         "chips of one host")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size on the CPU, Pallas interpreted; not a "
                         "chip result")
    opts = ap.parse_args()

    if os.environ.get("MXNET_MESH_HOST_FALLBACK"):
        raise SystemExit("chip_smoke: MXNET_MESH_HOST_FALLBACK is set: a mesh "
                         "could land on virtual CPU devices")
    from mxnet_tpu.runtime import place_compile_cache
    cache_dir = place_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    want = "cpu" if opts.rehearse else "tpu"
    if dev.platform != want:
        raise SystemExit(
            "chip_smoke: needs a %s backend%s; jax found platform %r "
            "(%d x %s)" % (want, " (--rehearse runs under JAX_PLATFORMS=cpu)"
                           if opts.rehearse else "", dev.platform,
                           len(devices), dev.device_kind))
    if len(devices) < opts.chips:
        raise SystemExit("chip_smoke: --chips %d but jax found %d %s device(s)"
                         % (opts.chips, len(devices), dev.platform))
    if opts.rehearse:
        os.environ["MXNET_FLASH_INTERPRET"] = "1"
    versions = {"jax": jax.__version__,
                "jaxlib": importlib.metadata.version("jaxlib"),
                "libtpu": importlib.metadata.version("libtpu")}
    print("device: platform=%s kind=%s count=%d  jax %s jaxlib %s libtpu %s"
          % (dev.platform, dev.device_kind, len(devices), versions["jax"],
             versions["jaxlib"], versions["libtpu"]), flush=True)
    print("compile cache: %s" % cache_dir, flush=True)

    result = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices)},
              "versions": versions, "compile_cache": cache_dir}
    result["trainer"] = trainer_phase(opts.rehearse, 1, want)
    if opts.chips > 1:
        mesh = trainer_phase(opts.rehearse, opts.chips, want)
        one, many = result["trainer"]["losses"], mesh["losses"]
        require(abs(many[0] - one[0]) <= MESH_ATOL + MESH_RTOL * abs(one[0]),
                "first-step loss on %d chips %.5f equals the one-chip %.5f "
                "(rtol %.0e, atol %.0e)"
                % (opts.chips, many[0], one[0], MESH_RTOL, MESH_ATOL))
        # the first loss is taken before any update; the all-reduced
        # gradients show in the ones after it
        apart = max(abs(m - o) / abs(o) for m, o in zip(many, one))
        print("  largest gap between the %d losses on %d chips and on one: "
              "%.1e relative" % (len(one), opts.chips, apart), flush=True)
        if not opts.rehearse:   # the toy's BatchNorm over 8 images is chaotic
            require(apart <= MESH_LATER_RTOL,
                    "every loss on %d chips is within %.1e relative of the "
                    "one-chip run's" % (opts.chips, MESH_LATER_RTOL))
        result["trainer_mesh"] = mesh
    result["kernel"] = kernel_phase(opts.rehearse)

    print("measured: %s" % json.dumps(result), flush=True)
    if opts.rehearse:
        print("REHEARSAL (cpu): not a chip result")
        return
    # the driver reads this line and takes these keys, no others
    print(json.dumps({"ok": True, "device": result["device"]}))


if __name__ == "__main__":
    main()
