"""Compiled for a described TPU v5e, with no chip: what the chip's compiler
makes of the main path's pieces at their real sizes. Proves structure, never
numerics or speed. The topology is described inside a fixture and only in
this file, so that one xdist worker alone loads the TPU's library."""
import functools
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


def _compiled(fn, *args):
    """`fn` compiled for the arguments' described chip, with the persistent
    compile cache off and out of the way."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


def _compiled_text(fn, *args):
    return _compiled(fn, *args).as_text()


def test_mlm_head_keeps_one_tensor_of_the_logits_size(one_chip):
    """bert_base's head over a step's 16,384 positions: the forward writes
    the float32 logits and nothing else of their size, and the backward
    writes no `softmax - onehot` (XLA builds it inside both products)."""
    from mxnet_tpu.models.losses import linear_cross_entropy
    n, d, v = 16384, 768, 30522

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape((n, d), jnp.float32), shape((v, d), jnp.float32),
            shape((n,), jnp.int32), shape((n,), jnp.int32))
    text = _compiled_text(jax.value_and_grad(linear_cross_entropy, (0, 1)),
                          *args)
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
    text = _compiled_text(jax.value_and_grad(loss, tuple(range(7))), *args)
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


@pytest.mark.parametrize("batch,seq", [(128, 128), (32, 512)])
def test_bert_layer_has_one_packed_projection_the_kernels_read_in_place(
        one_chip, monkeypatch, batch, seq):
    """bert_base's encoder layer, forward and backward in float32 at both
    cells' shapes, with the flash kernels on (their gate asks the default
    backend, which is the CPU here): ONE forward product writes the packed
    (B, S, 2304) q|k|v; the three Mosaic calls read it as it is; `flash_dkv`
    fills the array `flash_dq` wrote, and that one cotangent is read by one
    dx product and one dW product and by nothing else: the bias gradient
    comes out of the kernels. Nothing of an activation's size is sliced,
    copied, reduced or concatenated on the way."""
    from mxnet_tpu.models import bert
    from mxnet_tpu.ops import pallas_stats
    monkeypatch.setattr(pallas_stats, "pallas_on", lambda: True)
    cfg = bert.BertConfig(dtype=jnp.float32)

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    lp = jax.tree_util.tree_map(shape, jax.eval_shape(
        lambda: bert.bert_init(jax.random.PRNGKey(0), cfg))["layers"]["0"])
    x = shape(jax.ShapeDtypeStruct((batch, seq, cfg.dim), jnp.float32))
    text = _compiled_text(jax.grad(
        lambda lp, x, t: jnp.sum(bert._encoder_layer(lp, x, cfg) * t),
        argnums=(0, 1)), lp, x, x)
    entry = _step_bytes().entry_instructions(text)
    packed_bytes = batch * seq * 3 * cfg.dim * 4
    kernels = {ins["name"].lstrip("%").rsplit(".", 1)[0]: ins
               for ins in entry if ins["opcode"] == "custom-call"
               and "pallas_call" in ins["op_name"]}
    assert sorted(kernels) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert text.count('custom_call_target="tpu_custom_call"') == 3

    def sizes(ins):
        return sorted(size for size, _ in ins["results"])

    def packed_array_of(ins):
        """The name the instruction's (B, S, 2304) result is read under:
        its own, or that of the get-tuple-element that takes it out."""
        if sizes(ins) == [packed_bytes]:
            return ins["name"]
        taken = [e["name"] for e in entry
                 if e["opcode"] == "get-tuple-element"
                 and e["operands"] == [ins["name"]]
                 and sizes(e) == [packed_bytes]]
        assert len(taken) == 1, (ins["name"], taken)
        return taken[0]

    def readers(name):
        return [ins for ins in entry if name in ins["operands"]]

    projections = [ins for ins in entry if ins["kind"] == "kOutput"
                   and sizes(ins) == [packed_bytes]]
    assert len(projections) == 1, [ins["name"] for ins in projections]
    qkv = projections[0]["name"]
    assert "transpose(" not in projections[0]["op_name"]
    # q, k and v of all three kernels are the product's result itself, and
    # nobody else reads it
    for kernel in kernels.values():
        assert kernel["operands"][:3] == [qkv] * 3, kernel["operands"]
    assert sorted(ins["name"] for ins in readers(qkv)) == sorted(
        ins["name"] for ins in kernels.values())
    # the cotangent: written by flash_dq, filled in place by flash_dkv
    dq = packed_array_of(kernels["flash_dq"])
    assert [ins["name"] for ins in readers(dq)] == [
        kernels["flash_dkv"]["name"]]
    assert kernels["flash_dkv"]["operands"][-1] == dq
    cotangent_readers = readers(packed_array_of(kernels["flash_dkv"]))
    assert all(ins["kind"] == "kOutput" for ins in cotangent_readers), [
        (ins["name"], ins["opcode"]) for ins in cotangent_readers]
    assert sorted(sizes(ins)[-1] for ins in cotangent_readers) == sorted(
        [batch * seq * cfg.dim * 4, cfg.dim * 3 * cfg.dim * 4])


# entry, (B, S, H, Hkv, D), dtype, causal: shapes no cell runs, one of each
# path through the kernels
KERNEL_SHAPES = [
    ("packed", (8, 512, 16, 16, 64), "bfloat16", False),   # bert_large
    ("packed", (2, 1024, 8, 8, 128), "float32", True),     # k-blocks, scratch
    ("packed", (6, 200, 4, 4, 64), "float32", False),      # ragged
    ("bshd", (4, 2048, 32, 8, 128), "bfloat16", True),     # GQA, D on lanes
    ("bshd", (2, 640, 4, 4, 64), "float32", True),         # 384-row blocks
    ("bshd", (16, 64, 2, 2, 128), "float32", False),
    ("bhsd", (1, 256, 8, 2, 64), "float32", True),         # GQA, a row a block
    ("bhsd", (2, 1024, 4, 4, 64), "bfloat16", False),
]


@pytest.mark.parametrize("entry,dims,dtype,causal", KERNEL_SHAPES)
def test_flash_kernels_compile_within_the_vmem_they_ask_for(
        one_chip, monkeypatch, entry, dims, dtype, causal):
    """Forward and backward kernels on each view, compiled by Mosaic for
    the described v5e under the VMEM the kernels ask for: 16 MiB, or a
    tile's estimate where that is more. The request is small on purpose
    (XLA clears that much of the VMEM it keeps activations in), so a tile
    whose estimate is too low has to fail here and not on the chip."""
    import sys
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.ops import pallas_stats
    fa = sys.modules["mxnet_tpu.parallel.flash_attention"]
    monkeypatch.setattr(pallas_stats, "pallas_on", lambda: True)
    B, S, H, Hkv, D = dims

    def shape(*dims_):
        return jax.ShapeDtypeStruct(dims_, jnp.dtype(dtype),
                                    sharding=one_chip)

    if entry == "packed":
        args = (shape(B, S, 3 * H * D), shape(3 * H * D))

        def attend(qkv, bias):
            return fa.flash_attention_packed(qkv, H, causal, bias=bias)
    elif entry == "bshd":
        args = (shape(B, S, H, D), shape(B, S, Hkv, D), shape(B, S, Hkv, D))

        def attend(q, k, v):
            return fa.flash_attention_bshd(q, k, v, causal)
    else:
        args = (shape(B, H, S, D), shape(B, Hkv, S, D), shape(B, Hkv, S, D))

        def attend(q, k, v):
            return fa.flash_attention(q, k, v, causal)
    tile = fa._choose_tile(entry, B, H, Hkv, S, S, D,
                           jnp.dtype(dtype).itemsize)
    assert tile.vmem <= 2 * fa._VMEM_LIMIT, tile
    text = _compiled_text(jax.grad(
        lambda *a: attend(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(len(args)))), *args)
    assert text.count('custom_call_target="tpu_custom_call"') == 3


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_grouped_product_kernels_compile_at_the_cells_shapes(
        one_chip, monkeypatch, dtype):
    """`moe_gmm` and `moe_tgmm` at `qwen3_next_ep16_s4096`'s shapes (12,288
    rows of 2,048, 32 held experts of width 512, float32 weights cast a
    block at a time), forward and backward, compiled by Mosaic for the
    described v5e: the forward, the gradient to the rows and the gradient
    to the weights are one named call each, behind the same gate as the
    flash kernels."""
    from mxnet_tpu.ops import moe, pallas_stats
    monkeypatch.setattr(pallas_stats, "pallas_on", lambda: True)

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)

    args = (shape((12288, 2048), dtype), shape((32, 2048, 512), "float32"),
            shape((96,), "int32"), shape((1,), "int32"))
    text = _compiled_text(jax.grad(
        lambda rows, w, tile_expert, n_used: (moe.grouped_matmul(
            rows, w, tile_expert, n_used).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1)), *args)
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    # the instructions carry the kernels' names, as the device trace does
    calls = re.findall(r"%(moe_t?gmm)[.\d]* = \S+ custom-call\(", text)
    assert sorted(calls) == ["moe_gmm", "moe_gmm", "moe_tgmm"], calls


def test_mixture_layer_picks_a_prefix_of_its_buffer_on_the_device(
        one_chip, monkeypatch):
    """One expert layer at `qwen3_next_ep16_s4096`'s shapes (8,192 tokens of
    2,048, ten of 512 experts each, 32 held of width 512: a buffer of 86,016
    rows), value and gradient under `jax.checkpoint` as the decoder calls
    it: TWO `while` loops that hold kernels (the forward and the backward;
    the recomputed forward is dead) and no conditional, each body over one
    chunk of the buffer with the kernels under their names (3 `moe_gmm`
    forward; 6 and 3 `moe_tgmm` backward, which makes its own forward again
    and writes the weight gradients into the buffers the loop carries: no
    copy of one in the body), and the compiler's account of the
    temporaries not above that of the layer over the whole buffer alone."""
    from mxnet_tpu.ops import moe, pallas_stats
    from mxnet_tpu.telemetry import hlo_scopes
    monkeypatch.setattr(pallas_stats, "pallas_on", lambda: True)
    T, d, f, held, n_experts, top_k = 8192, 2048, 512, 32, 512, 10

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, jnp.dtype(dt), sharding=one_chip)

    args = (shape((T, d), "bfloat16"), shape((d, n_experts), "float32"),
            shape((held, d, f), "float32"), shape((held, d, f), "float32"),
            shape((held, f, d), "float32"), shape((T, d), "bfloat16"))

    def whole_buffer(x, router, w_gate, w_up, w_down):
        weights, ids = moe.route_top_k(x, router, top_k)
        plan = moe.plan_dispatch(ids, held)
        return moe._routed_rows(x, weights, w_gate, w_up, w_down, plan,
                                top_k=top_k, row_tile=moe.ROW_TILE, gate=None
                                ).astype(x.dtype)

    def compiled(layer):
        layer = jax.checkpoint(layer)
        return _compiled(jax.value_and_grad(
            lambda *a: jnp.sum((layer(*a[:5]) * a[5]).astype(jnp.float32)),
            argnums=(0, 1, 2, 3, 4)), *args)

    chunked = compiled(lambda *a: moe.moe_routed(*a, top_k))
    text = chunked.as_text()
    assert " conditional(" not in text
    instrs = hlo_scopes.parse(text)
    held_by = {}
    for name, instr in instrs.items():
        held_by.setdefault(instr.computation, []).append(name)
    calls = []
    for instr in instrs.values():
        if instr.opcode != "while":
            continue
        body = [name for computation in instr.calls
                for name in held_by[computation]]
        kernels = sorted(re.match(r"moe_t?gmm", name).group() for name in body
                         if instrs[name].opcode == "custom-call"
                         and name.startswith("moe_"))
        if kernels:
            calls.append(kernels)
            assert not any(instrs[name].opcode == "copy" and re.search(
                r"%%%s = f32\[%d," % (re.escape(name), held), text)
                for name in body)
    forward, backward = ["moe_gmm"] * 3, ["moe_gmm"] * 6 + ["moe_tgmm"] * 3
    assert sorted(calls) == [forward, backward], calls
    assert "rows_%d/" % (moe.CHUNK_TILES * moe.ROW_TILE) in text
    alone = compiled(whole_buffer)
    assert (chunked.memory_analysis().temp_size_in_bytes
            <= alone.memory_analysis().temp_size_in_bytes)


# the cell's 4,096 chunks of 64 a layer; as many positions in chunks of 128
@pytest.mark.parametrize("dims", [(2, 32, 64, 64, 64), (2, 32, 32, 128, 128)])
def test_chunk_inverse_kernels_compile_at_the_cells_shape(one_chip,
                                                          monkeypatch, dims):
    """`gdn_inverse` and `gdn_inverse_bwd` over `qwen3_next_ep16_s4096`'s
    chunks (2 x 32 heads of 4,096 positions, float32 in, bfloat16 out),
    compiled by Mosaic for the described v5e within the 16 MiB of VMEM it
    gives a kernel that asks for none: one named call a direction, and no
    product of XLA's over the blocks."""
    from mxnet_tpu.ops import linear_attention as la, pallas_stats
    monkeypatch.setattr(pallas_stats, "pallas_on", lambda: True)
    args = (jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip))
    text = _compiled_text(jax.grad(
        lambda a, w: jnp.sum((la._unit_lower_inverse(a, jnp.bfloat16) * w
                              ).astype(jnp.float32))), *args)
    calls = re.findall(r"%(\w+?)[.\d]* = [^\n]*? custom-call\(", text)
    assert sorted(calls) == ["gdn_inverse", "gdn_inverse_bwd"], calls
    assert " convolution(" not in text and " dot(" not in text


def _cell_loss(config, batch):
    """(loss function, parameter shapes, batch shapes) of a benchmark
    configuration at its real size, from shapes alone."""
    import importlib
    import json
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    for path in (bench, root):
        if path not in sys.path:
            sys.path.insert(0, path)
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg.pop("rehearse", None)
    leaves = importlib.import_module(
        "reference." + cfg["reference"]).leaves(cfg)
    tree = {}
    for name, dims, _ in leaves:
        node = tree
        *path, last = name.split(".")
        for part in path:
            node = node.setdefault(part, {})
        node[last] = jax.ShapeDtypeStruct(tuple(dims), jnp.float32)
    loss = importlib.import_module("programs." + cfg["program"]).loss_fn(cfg)
    names = (("tokens", "targets", "mask") if cfg["program"] == "bert"
             else ("tokens",))
    return loss, tree, {k: jax.ShapeDtypeStruct(batch, jnp.int32)
                        for k in names}


@functools.lru_cache(maxsize=None)
def _lowered_for_tpu(config, batch):
    """(the jaxpr, the module lowered for the TPU) of a configuration's loss
    and gradient, as text that no address, path or line number is part of:
    a Mosaic call's serialized body carries the kernel's source locations
    (`enable_debug_info` in jax's `tpu_custom_call`) and is cut out of the
    module; the jaxpr holds the same kernel, grid and index maps without
    them."""
    loss, tree, batch = _cell_loss(config, batch)
    traced = jax.jit(jax.value_and_grad(loss)).trace(tree, batch)
    jaxpr = re.sub(r" at \S+:\d+", "", re.sub(r"0x[0-9a-f]+", "0x",
                                              str(traced.jaxpr)))
    module = traced.lower(lowering_platforms=("tpu",)).as_text()
    return jaxpr, re.sub(r'\\22body\\22: \\22[A-Za-z0-9+/=]*\\22', "", module)


def _digest(text):
    import hashlib
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# what PR 34's tree gives (`git checkout c4da908`, this test copied over):
# a PR that means to change BERT's or Qwen3-Next's step replaces the pair it
# changes and says so; one that does not has moved a step it shares. PR 38
# made the expert layer a loop over its buffer's chunks (`ops/moe.py`):
# Qwen3-Next's pair is that tree's, BERT's stay PR 34's
PARENT_STEPS = {
    ("bert_base", (128, 128)): ("191c8e56347535ff", "888006aa458dc3be"),
    ("bert_base", (32, 512)): ("ee54c9a0df876a52", "edf2a4a9d0901870"),
    ("qwen3_next_80b_a3b_ep16", (2, 4096)): ("a21bd5bd56405b7b",
                                             "772e4c0967e40895"),
}


@pytest.mark.parametrize("config,batch", sorted(PARENT_STEPS))
def test_the_older_cells_steps_lower_to_what_the_parent_gave(
        monkeypatch, config, batch):
    """BERT's and Qwen3-Next's loss and gradient at their cells' sizes, with
    the kernels on: the jaxpr (kernel bodies, grids and index maps included)
    and the module lowered for the TPU are, digest for digest, what the tree
    before the windowed kernels and the router's score gave. `window=None`
    and `score="softmax"` change nothing."""
    from mxnet_tpu.ops import pallas_stats
    monkeypatch.setattr(pallas_stats, "pallas_on", lambda: True)
    jaxpr, module = _lowered_for_tpu(config, batch)
    assert module.count("tpu_custom_call") >= 3
    assert (_digest(jaxpr), _digest(module)) == PARENT_STEPS[config, batch]


def test_qwen3_next_step_keeps_its_chunk_inverses_in_the_kernels(monkeypatch):
    """`qwen3_next_ep16_s4096`'s loss and gradient at its real size, lowered
    for the TPU: each kernel of the chunk inverse is lowered once and called
    by the three Gated DeltaNet layers (three times forward, where the
    result is kept for the backward pass and not made again, three times
    backward), and none of the ten float32 products at `highest` over the
    (2, 32, 64, 64, 64) blocks is left in the step."""
    from mxnet_tpu.ops import pallas_stats
    monkeypatch.setattr(pallas_stats, "pallas_on", lambda: True)
    jaxpr, module = _lowered_for_tpu("qwen3_next_80b_a3b_ep16", (2, 4096))
    for kernel, call in (("gdn_inverse", "_inverse_pallas"),
                         ("gdn_inverse_bwd", "_inverse_bwd_pallas")):
        assert module.count('kernel_name = "%s"' % kernel) == 1
        assert len(re.findall(r"call @%s\b" % call, module)) == 3
    assert not re.search(
        r"f32\[2,32,64,64,64\] = dot_general\[\s+dimension_numbers=[^\n]*\s+"
        r"precision=\(Precision\.HIGHEST", jaxpr)


def test_laguna_step_lowers_with_windowed_and_causal_kernels(monkeypatch):
    """`laguna_s_ep32_s4096`'s loss and gradient at its real size, lowered
    for the TPU: the three sliding layers go through `swa_fwd`, `swa_dq`
    and `swa_dkv`, the two full layers through `flash_fwd`, `flash_dq` and
    `flash_dkv` (each lowered once: the layers of a kind share one jitted
    call), and the four expert layers through the grouped products."""
    from mxnet_tpu.ops import pallas_stats
    monkeypatch.setattr(pallas_stats, "pallas_on", lambda: True)
    _, module = _lowered_for_tpu("laguna_s_2.1_ep32", (2, 4096))
    assert set(re.findall(r'kernel_name = "(\w+)"', module)) == {
        "swa_fwd", "swa_dq", "swa_dkv", "flash_fwd", "flash_dq", "flash_dkv",
        "moe_gmm", "moe_tgmm"}


@pytest.mark.parametrize("heads,window", [(18, 512), (12, None)])
def test_laguna_attention_kernels_compile_at_the_cells_shapes(
        one_chip, monkeypatch, heads, window):
    """Forward and backward at `laguna_s_ep32_s4096`'s shapes (2 x 4,096
    positions, 2 key-value heads of 128 with 9 query heads each under the
    window of 512, with 6 each under the causal mask alone), bfloat16,
    compiled by Mosaic for the described v5e within the VMEM they ask for."""
    import sys
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.ops import pallas_stats
    fa = sys.modules["mxnet_tpu.parallel.flash_attention"]
    monkeypatch.setattr(pallas_stats, "pallas_on", lambda: True)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    args = (shape(2, 4096, heads, 128), shape(2, 4096, 2, 128),
            shape(2, 4096, 2, 128))
    text = _compiled_text(jax.grad(
        lambda q, k, v: fa.flash_attention_bshd(
            q, k, v, causal=True, window=window).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), *args)
    prefix = "swa" if window else "flash"
    calls = re.findall(r"%(\w+?)[.\d]* = [^\n]*? custom-call\(", text)
    assert sorted(calls) == [prefix + "_dkv", prefix + "_dq",
                             prefix + "_fwd"], calls
