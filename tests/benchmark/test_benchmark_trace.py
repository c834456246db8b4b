"""The reduction from a trace to numbers: on the trace recorded on the v5e
(four steps of a toy BERT through ShardedTrainStep, flash kernels included;
`benchmark/tools/record_fixture.py`), and on made-up intervals."""
import gzip
import os

import pytest

from bench_paths import FIXTURES, on_path

on_path()
from harness import trace_reduce as tr  # noqa: E402


@pytest.fixture(scope="module")
def reduced():
    with gzip.open(os.path.join(FIXTURES, "toy_bert.xplane.pb.gz")) as f:
        return tr.reduce_trace(tr.loads(f.read()))


def test_recorded_trace_gives_known_numbers(reduced):
    (dev,) = reduced["devices"]
    assert dev["plane"] == "/device:TPU:0"
    assert dev["module"].startswith("jit_step_fn(") and dev["steps"] == 4
    assert dev["busy_s"] == pytest.approx(641.548e-6, rel=1e-6)
    assert dev["window_s"] == pytest.approx(5808.513e-6, rel=1e-6)
    cats = dev["category_s"]
    # 2 layers x (forward, dq, dk/dv) x 4 steps = 24 Mosaic calls
    assert cats["mosaic"] == pytest.approx(190.773e-6, rel=1e-6)
    assert cats["convolution"] == pytest.approx(210.019e-6, rel=1e-6)
    assert cats["collective"] == 0.0
    assert dev["collective_s"] == 0.0 and dev["collective_exposed_s"] == 0.0
    assert sum(cats.values()) == pytest.approx(dev["busy_s"], rel=1e-3)
    # the device waits for the host between the toy's steps
    idle = 1 - dev["busy_s"] / dev["window_s"]
    assert idle == pytest.approx(0.8896, abs=1e-3)


def test_idle_gaps_are_named_by_the_host_span(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) == 5 and gaps[0][1] == pytest.approx(1946.4e-6, rel=1e-3)
    assert gaps[0][0] == "PjitFunction(step_fn)"
    out = tr.breakdown(reduced)
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) <= 10
    assert out["device_ops"][0][0] == "all_convolution"
    assert any(name.startswith("mosaic/") for name, _ in out["device_ops"])


def test_the_hosts_line_is_found_by_the_step_annotations_it_holds():
    """`toy_bert_named.xplane.pb.gz` was recorded from a process started as
    the benchmark's command is, `python3`: the host's line bears that name,
    and the gaps are named all the same."""
    with gzip.open(os.path.join(FIXTURES,
                                "toy_bert_named.xplane.pb.gz")) as f:
        data = tr.loads(f.read())
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    assert "python" not in [line.name for line in host.lines]
    spans = tr._host_spans(data)
    assert sum(name == tr.STEP for _, _, name in spans) == 4
    assert {"train_step", "train_step.launch"} <= {n for _, _, n in spans}
    # nothing of the runtime's own threads
    assert not any(n.startswith("tpu::") for _, _, n in spans)
    gaps = tr.reduce_trace(data)["idle_gaps"]
    assert len(gaps) == 5
    assert all(name != "no_host_span" for name, _ in gaps)
    assert gaps[0][0] == "PjitFunction(step_fn)"


class _Made:
    """A made-up plane, line or event: just the fields the reduction
    reads."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_a_host_without_step_annotations_names_no_gap():
    line = _Made(name="python3", events=[
        _Made(name="PjitFunction(f)", start_ns=0, duration_ns=10)])
    data = _Made(planes=[_Made(name="/host:CPU", lines=[line])])
    assert tr._host_spans(data) == []
    assert tr._host_doing([], 5) == "no_host_span"
    line.events.append(_Made(name=tr.STEP, start_ns=0, duration_ns=20))
    assert sorted(tr._host_spans(data)) == [(0, 10, "PjitFunction(f)"),
                                            (0, 20, tr.STEP)]
    assert tr._host_doing(tr._host_spans(data), 5) == "PjitFunction(f)"
    assert tr._host_doing(tr._host_spans(data), 15) == tr.STEP


@pytest.mark.parametrize("text,want", [
    ('%all-reduce-start.3 = f32[64]{0} all-reduce-start(f32[64]{0} %x), '
     'replica_groups={{0,1,2,3}}', ("all-reduce-start.3", "collective")),
    ('%all-reduce-done.3 = f32[64]{0} all-reduce-done(f32[64]{0} %y)',
     ("all-reduce-done.3", "collective")),
    ('%fusion.248 = bf16[256,64,56,56]{3,2,1,0} fusion(bf16[1]{0} %a), '
     'kind=kOutput, calls=%fused_computation.1', ("fusion.248", "convolution")),
    ('%convolution_add_fusion.15 = f32[8,128]{1,0} fusion(f32[8]{0} %a), '
     'kind=kLoop, calls=%f', ("convolution_add_fusion.15", "convolution")),
    ('%multiply_fusion = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, '
     'calls=%f', ("multiply_fusion", "fusion")),
    ('%jvp__.2 = (f32[8,2,128,64]{3,2,1,0}, f32[8]{0}) custom-call(f32[8]{0} '
     '%q), custom_call_target="tpu_custom_call"', ("jvp__.2", "mosaic")),
    ('%copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %p)',
     ("copy-start.2", "copy")),
    ('%iota.2 = s32[8]{0} iota(), iota_dimension=0', ("iota.2", "other")),
])
def test_categories_from_the_hlo_text(text, want):
    assert tr.op_name_and_category(text) == want


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == [
        (0, 3), (5, 8)]
    assert tr.length([(0, 3), (5, 8)]) == 6
    assert tr.gaps([(0, 3), (5, 8), (10, 11)]) == [(3, 5), (8, 10)]
    # a collective from 0 to 10, compute from 2 to 4 and 6 to 12: exposed
    # while no other operation runs, 0-2 and 4-6
    assert tr.minus([(0, 10)], [(2, 4), (6, 12)]) == [(0, 2), (4, 6)]
    assert tr.minus([(0, 2), (5, 9)], [(1, 6)]) == [(0, 1), (6, 9)]
    assert tr.minus([(0, 2)], []) == [(0, 2)]
