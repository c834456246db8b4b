"""The comparison that decides `correct` can fail: the control (the plain
reference one precision down, put in the program's place) reads far above
the program, and a run with the timed path broken underneath comes out not
correct. Toy sizes on the CPU; the readings that the limits were set from
were taken on the chip at the cells' own sizes (PERF.md)."""
import json

import jax
import pytest

from bench_paths import on_path

on_path()
import run as bench  # noqa: E402
from harness import correct, runners, traffic  # noqa: E402
from harness.spec import Cell  # noqa: E402


def stand_in(**kw):
    return lambda *a: runners.ReferenceRunner(*a, **kw)


def drive(cell_name, factory, capsys):
    """The whole of a run after the look for a chip."""
    cell = Cell(cell_name, rehearse=True)
    result = bench.measure(cell, jax.devices(), 2 ** 31 + 5, 0.5, 0,
                           runner_factory=factory)
    capsys.readouterr()
    json.dumps(result)
    return result


@pytest.mark.parametrize("cell,fault", [
    ("resnet50_b256", "unchanged"), ("resnet50_b256", "half_batch"),
    ("bert_base_s128", "unchanged"), ("bert_base_s128", "half_batch"),
    ("bert_base_s512", "unchanged"), ("bert_base_s512", "half_batch"),
    ("resnet50_dp4_b1024", "no_exchange")])
def test_a_run_with_the_timed_path_broken_is_not_correct(cell, fault,
                                                         capsys):
    result = drive(cell, stand_in(fault=fault), capsys)
    assert result["correct"] is False
    over = [n for n, c in result["compared"].items()
            if c["value"] > c["limit"]]
    assert over, result["compared"]
    if fault == "unchanged":    # a state left unchanged reads 1 at the worst
        # leaf, and at the median large leaf 1 or, where that leaf's
        # gradient is smaller than the median leaf's, its share of it
        worst = [result["compared"][n]["value"] for n in ("grad1", "change3")]
        assert worst == pytest.approx([1.0, 1.0])
        assert all(0.5 < c["value"] <= 1.0 + 1e-6
                   for n, c in result["compared"].items()
                   if n.endswith("_median"))


@pytest.mark.parametrize("cell", ["resnet50_b256", "bert_base_s128"])
def test_the_reference_in_the_programs_place_is_correct(cell, capsys):
    result = drive(cell, stand_in(), capsys)
    assert result["correct"] is True
    assert all(c["value"] <= 1e-5 for c in result["compared"].values())


def test_a_loss_that_is_not_finite_is_a_failed_step(capsys):
    class Poisoned(runners.ReferenceRunner):
        def call(self):
            self.steps = getattr(self, "steps", 0) + 1
            loss = super().call()
            return loss * float("nan") if self.steps == 5 else loss
    result = drive("bert_base_s128", lambda *a: Poisoned(*a), capsys)
    assert result["failed"] == 1 and result["correct"] is False


@pytest.mark.parametrize("cell", ["resnet50_b256", "bert_base_s128",
                                  "bert_base_s512"])
def test_the_control_reads_far_above_the_reference(cell):
    """fp8 operands against float32 at `highest`: at least a hundred times
    what the same reference reads against itself, on the gradient."""
    spec = Cell(cell, rehearse=True)
    ref = spec.reference()

    def readings(**kw):
        start, batch = traffic.make(7, ref, spec.cfg, spec.traffic)
        runner = runners.ReferenceRunner(spec.cfg, spec.traffic, ref, start,
                                         batch, True, **kw)
        return correct.to_host(correct.follow(runner, start))
    plain, leaves = readings(), ref.leaves(spec.cfg)
    again = correct.compare(readings(), plain, leaves)
    control = correct.compare(readings(mode="fp8"), plain, leaves)
    for number in ("grad1", "grad1_median", "change3_median"):
        assert again[number][0] <= 1e-6
        assert control[number][0] >= 1e-3
        assert control[number][0] >= 100 * max(again[number][0], 1e-9)
    # and it fails the cell's own limits
    ok, compared, _ = correct.judge(control, spec.limits, spec.not_compared)
    assert not ok, compared


@pytest.mark.parametrize("cell", ["bert_base_s128", "bert_base_s512"])
def test_float32_state_kept_in_bfloat16_is_not_correct(cell):
    """The control of a configuration stated in float32: weights and AdamW's
    moments kept in bfloat16 fail the cell's limits on the change."""
    spec = Cell(cell, rehearse=True)
    ref = spec.reference()

    def readings(**kw):
        start, batch = traffic.make(7, ref, spec.cfg, spec.traffic)
        runner = runners.ReferenceRunner(spec.cfg, spec.traffic, ref, start,
                                         batch, True, **kw)
        return correct.to_host(correct.follow(runner, start))
    control = correct.compare(readings(mode="bf16", stored="bfloat16"),
                              readings(), ref.leaves(spec.cfg))
    assert control["change3"][0] > 10 * spec.limits["change3"]
    ok, compared, _ = correct.judge(control, spec.limits, spec.not_compared)
    assert not ok, compared


def test_a_leaf_the_stated_precision_does_not_resolve_is_left_out():
    import jax.numpy as jnp
    first = {"a": jnp.ones(8), "b": jnp.ones(8), "c": 1e-4 * jnp.ones(8)}
    stated = {"a": 1.1 * jnp.ones(8), "b": -jnp.ones(8),
              "c": 3e-4 * jnp.ones(8)}
    far, left_out = correct.unresolved(first, stated, 0.5)
    assert left_out == {"b"}            # c is small against the median leaf
    assert far["a"] == pytest.approx(0.1, rel=1e-3)
    assert far["b"] == pytest.approx(2.0)
    ref = {"loss": [1.0] * 3, "grad": {"a": 1.0, "b": 1.0, "c": 1.0},
           "change": {"a": 1.0, "b": 1.0, "c": 1.0}}
    mine = {"loss": [1.0] * 3, "grad": {"a": 1.1, "b": 3.0, "c": 1.0},
            "change": {"a": 1.0, "b": 0.0, "c": 1.2}}
    leaves = [(k, (8,), "beta") for k in "abc"] + [("d", (4096,), "weight")]
    for side in (ref, mine):
        side["grad"]["d"] = side["change"]["d"] = 1.0
    every = correct.compare(mine, ref, leaves)
    kept = correct.compare(mine, ref, leaves, left_out)
    assert every["grad1"] == (pytest.approx(2.0), "b")
    assert kept["grad1"] == (pytest.approx(0.1), "a")
    assert kept["change3"] == (pytest.approx(0.2), "c")


def test_a_number_named_nowhere_in_the_limits_file_is_an_error():
    numbers = {"loss1": (0.0, None), "grad1": (0.5, "a")}
    ok, compared, read = correct.judge(numbers, {"loss1": 1e-3},
                                       {"grad1": {"why": "noise"}})
    assert ok and set(compared) == {"loss1"} and read == {"grad1": 0.5}
    with pytest.raises(SystemExit, match="grad1"):
        correct.judge(numbers, {"loss1": 1e-3})
