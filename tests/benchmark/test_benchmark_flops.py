"""The benchmark's own counts of required operations."""
import json
import os

import pytest

from bench_paths import BENCH_DIR, on_path

on_path()
from harness import flops  # noqa: E402


def config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seq,mflop", [(128, 664.4), (512, 706.9)])
def test_bert_base_flops_per_token(seq, mflop):
    cfg = config("bert_base")
    got = flops.bert_train_flops(cfg, {"seq": seq, "batch": 1})
    assert got / 1e6 == pytest.approx(mflop, abs=0.05)
    assert flops.bert_forward_flops(cfg, {"seq": seq, "batch": 1}) * 3 == got


def test_bert_base_matrix_products_alone():
    cfg = config("bert_base")
    d, h, L, V = 768, 3072, 12, 30522
    assert 2 * (L * (4 * d * d + 2 * d * h) + d * V) == 216751104
    assert flops.bert_forward_flops(cfg, {"seq": 0, "batch": 1}) == 216751104


def test_attention_core_work_at_seq_128():
    cfg = dict(config("bert_base"), dtype="bfloat16")
    work, nbytes = flops.attention_core_work(cfg, {"seq": 128, "batch": 128})
    assert work == 16384 * 3 * 12 * 4 * 128 * 768
    # twelve passes over a (B, H, S, D) bf16 tensor a layer: 3.6 GB
    assert nbytes == pytest.approx(3.65e9, rel=0.01)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.roofline_seconds(work, nbytes, peak)
    assert bound == "bytes" and least == pytest.approx(4.46e-3, rel=0.01)
    # in float32, as the configuration runs, the bytes double
    _, wide = flops.attention_core_work(config("bert_base"),
                                        {"seq": 128, "batch": 128})
    assert wide == pytest.approx(2 * nbytes, rel=0.01)


def test_resnet50_table_matches_the_gluon_net():
    """Every convolution and the dense layer of the table has the shape the
    model zoo's net has, found through the plain reference's leaves (which
    are in the net's own order)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo import vision
    from reference import resnet_v1

    cfg = config("resnet50_v1")
    net = vision.resnet50_v1(classes=cfg["classes"])
    net.initialize(ctx=mx.cpu())
    net(nd.zeros((1, 3, 224, 224), ctx=mx.cpu()))
    leaves = resnet_v1.leaves(cfg)
    gluon = list(net.collect_params().values())
    assert len(gluon) == len(leaves)
    for p, (name, shape, _) in zip(gluon, leaves):
        assert tuple(p.shape) == tuple(shape), (p.name, name)
    shapes = {name: shape for name, shape, _ in leaves}
    table = flops.resnet_v1_layers(cfg, 224)
    assert len(table) == 53 + 1
    for name, k, cin, cout, hin, hout in table:
        want = (cout, cin) if name == "fc" else (cout, cin, k, k)
        assert shapes[name + ".weight"] == want, name
    assert {n + ".weight" for n, *_ in table} == {
        n for n, _, kind in leaves if kind == "weight"}
    # spatial sizes: 224 -> 112 (stem) -> 56 (pool) -> 56, 28, 14, 7
    assert [t[5] for t in table if t[0].endswith("b0.c2")] == [56, 28, 14, 7]
    forward = flops.resnet_v1_forward_flops(cfg, {"image_size": 224})
    assert forward == sum(2 * k * k * ci * co * ho * ho
                          for _, k, ci, co, _, ho in table)
    assert 7.6e9 < forward < 8.4e9      # 3.8-4.2 GMAC, as published
    assert flops.resnet_v1_train_flops(cfg, {"image_size": 224}) == 3 * forward
