"""Compiled for a described TPU v5e, with no chip: what the chip's compiler
makes of the main path's pieces at their real sizes. Proves structure, never
numerics or speed. The topology is described inside a fixture and only in
this file, so that one xdist worker alone loads the TPU's library."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to say
        pytest.skip("no v5e:2x2 topology can be described here: %r" % (e,))
    return SingleDeviceSharding(topo.devices[0])


def test_mlm_head_keeps_one_tensor_of_the_logits_size(one_chip):
    """bert_base's head over a step's 16,384 positions: the forward writes
    the float32 logits and nothing else of their size, and the backward
    writes no `softmax - onehot` (XLA builds it inside both products)."""
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu.models.losses import linear_cross_entropy
    n, d, v = 16384, 768, 30522

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape((n, d), jnp.float32), shape((v, d), jnp.float32),
            shape((n,), jnp.int32), shape((n,), jnp.int32))
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.value_and_grad(linear_cross_entropy, (0, 1))
                       ).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    entry = text[text.index("ENTRY "):]
    # instructions of the entry computation whose result holds an (N, V)
    # tensor; a get-tuple-element only names a fusion's output again
    written = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = ([^\n]*?) (?!get-tuple-element)"
        r"[\w\-]+\(", entry, re.M)
        if re.search(r"\[%d,%d\]" % (n, v), m.group(2))]
    assert len(written) == 1, written
    assert "f32[%d,%d]" % (n, v) in entry


def _step_bytes():
    """`tools/step_bytes.py`, which reads a compiled module's entry."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "step_bytes.py")
    spec = importlib.util.spec_from_file_location("step_bytes", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_batch_norm_statistics_ride_in_the_convolutions_epilogue(one_chip):
    """Two stacked Convolution -> BatchNorm -> relu at ResNet-50's stage-1
    size, forward and backward through the registered ops: each forward
    convolution fusion returns both moments of its output (two per-channel
    float32 vectors beside the activation), and no fusion reads one
    activation only to return per-channel vectors: the variance's second
    pass and the backward's zero sum (`jit(_var)`, four such fusions before
    PR 30) are gone."""
    from jax.experimental.compilation_cache import compilation_cache
    import mxnet_tpu  # noqa: F401 — registers the ops
    from mxnet_tpu.ops import registry
    conv, bn, act = (registry.get(n).fn for n in
                     ("Convolution", "BatchNorm", "Activation"))
    n, c, hw = 256, 64, 56

    def loss(x, w1, w2, g1, b1, g2, b2, m1, m2, v, t):
        y = conv(x, w1, kernel=(1, 1), num_filter=c, no_bias=True)
        y = act(bn(y, g1, b1, m1, v, fix_gamma=False), act_type="relu")
        y = conv(y, w2, kernel=(3, 3), pad=(1, 1), num_filter=c, no_bias=True)
        y = act(bn(y, g2, b2, m2, v, fix_gamma=False), act_type="relu")
        return jnp.sum((y * t).astype(jnp.float32))

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    image = shape((n, c, hw, hw), jnp.bfloat16)
    vec = shape((c,), jnp.float32)
    args = (image, shape((c, c, 1, 1), jnp.bfloat16),
            shape((c, c, 3, 3), jnp.bfloat16)) + (vec,) * 7 + (image,)
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.value_and_grad(loss, tuple(range(7)))
                       ).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
    tool = _step_bytes()
    rows = tool.traffic(tool.entry_instructions(text))
    alone = [ins["name"] for ins, _, _ in tool.vector_passes(rows)]
    assert alone == [], alone
    assert "jit(_var)" not in text
    image_bytes, vec_bytes = n * c * hw * hw * 2, c * 4
    forward = [sorted(size for size, _ in writes) for ins, _, writes in rows
               if ins["kind"] == "kOutput" and "transpose(" not in
               ins["op_name"] and "conv_general_dilated" in ins["op_name"]]
    assert forward == [[vec_bytes, vec_bytes, image_bytes]] * 2, forward
