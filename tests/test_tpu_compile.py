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
