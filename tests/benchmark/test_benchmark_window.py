"""The timed window's arithmetic, on a simulated device and clock."""
import json
import os
import statistics

import pytest

from bench_paths import FIXTURES, on_path

on_path()
from harness.window import (IN_FLIGHT, LATE, on_time_end, rate,  # noqa: E402
                            run_window)

STEP = 0.1          # device seconds a step
HOST = 0.001        # host seconds a call


class Sim:
    """A device that runs queued steps one after another, and a host clock
    that only `call` and `wait` move. `pause_at`: the call before which the
    host stalls for `pause` seconds. `late_waits`: {n: seconds} by which the
    host comes back late from its n-th wait (counted from 0; a negative n
    from the window's last wait, which `total` calls make: -1 is the
    last)."""

    def __init__(self, pause_at=None, pause=0.0, nan_at=None,
                 late_waits=None, total=None):
        self.now = 0.0
        self.free_at = 0.0
        self.calls = 0
        self.waits = 0
        self.pause_at, self.pause, self.nan_at = pause_at, pause, nan_at
        self.late_waits = {n if n >= 0 else total + n: s
                           for n, s in (late_waits or {}).items()}

    def clock(self):
        return self.now

    def call(self):
        if self.calls == self.pause_at:
            self.now += self.pause
        self.calls += 1
        self.now += HOST
        self.free_at = max(self.free_at, self.now) + STEP
        return self.free_at

    def wait(self, done_at):
        self.now = max(self.now, done_at) + self.late_waits.get(self.waits,
                                                                0.0)
        self.waits += 1


def drive(seconds=5.0, **kw):
    sim = Sim(**kw)
    return run_window(sim.call, sim.wait, seconds, clock=sim.clock), sim


def test_rate_is_all_steps_over_all_time():
    w, sim = drive()
    assert w["completed"] == w["attempted"] == len(w["done_s"])
    # the window ends on the completion of its last step
    assert w["t1"] == pytest.approx(sim.free_at)
    assert w["elapsed_s"] == pytest.approx(w["done_s"][-1])
    assert rate(w, 256) == pytest.approx(
        w["completed"] * 256 / w["elapsed_s"])
    # the device never waited for the host: one step every STEP seconds
    assert w["elapsed_s"] == pytest.approx(w["completed"] * STEP, rel=0.01)


def test_never_more_in_flight_than_the_bound():
    sim = Sim()
    most = [0]

    def call():
        done_at = sim.call()
        queued = sum(1 for d in pending if d > sim.now)
        most[0] = max(most[0], queued + 1)
        pending.append(done_at)
        return done_at
    pending = []
    run_window(call, sim.wait, 3.0, clock=sim.clock)
    assert most[0] == IN_FLIGHT + 1


@pytest.mark.parametrize("pause,lost", [
    (0.5 * IN_FLIGHT * STEP, 0.0),                       # shorter: absorbed
    (2.0 * IN_FLIGHT * STEP, 2.0 * IN_FLIGHT * STEP - IN_FLIGHT * STEP),
])
def test_a_host_pause_shorter_than_the_queue_is_absorbed(pause, lost):
    base, _ = drive()
    w, _ = drive(pause_at=20, pause=pause)
    # device time lost to the pause: none while the queue covers it, else
    # what of the pause the queue did not cover
    idle = w["elapsed_s"] - w["completed"] * STEP
    base_idle = base["elapsed_s"] - base["completed"] * STEP
    assert idle - base_idle == pytest.approx(lost, abs=1.5 * STEP)
    if lost == 0.0:
        assert rate(w, 1) == pytest.approx(rate(base, 1), rel=0.005)
    else:
        assert rate(w, 1) < 0.9 * rate(base, 1)


def test_a_step_that_raises_ends_the_window_and_is_reported():
    sim = Sim()

    def call():
        if sim.calls == 5:
            raise RuntimeError("planted")
        return sim.call()
    w = run_window(call, sim.wait, 3.0, clock=sim.clock)
    assert isinstance(w["error"], RuntimeError)
    assert w["attempted"] == 6 and w["completed"] == 5


def test_the_memory_is_read_once_with_the_queue_full():
    sim = Sim()
    seen = []
    w = run_window(sim.call, sim.wait, 3.0, clock=sim.clock,
                   dispatched=lambda: seen.append(
                       (sim.calls, sim.free_at - sim.now)))
    # once, after the last dispatch and before the queue is drained
    assert len(seen) == 1 and seen[0][0] == w["attempted"]
    assert seen[0][1] == pytest.approx(IN_FLIGHT * STEP, rel=0.05)


# ------------------------------------------------------ the window's end
def late(seconds=5.0, **late_waits):
    """A window whose waits named from the end (`last=`, `third_last=` ...)
    come back late by so many seconds, beside the same window undisturbed."""
    base, _ = drive(seconds)
    names = {"last": -1, "second_last": -2, "third_last": -3,
             "fourth_last": -4}
    w, _ = drive(seconds, total=base["attempted"], late_waits={
        names.get(k, k): v for k, v in late_waits.items()})
    assert w["attempted"] == base["attempted"]
    return w, base


def test_a_pause_on_the_last_wait_is_not_in_the_window():
    w, base = late(last=0.110)
    assert base["trimmed_steps"] == 0
    assert w["trimmed_steps"] == 1
    assert w["completed"] == base["completed"] - 1
    assert w["last_gap_s"] == pytest.approx(STEP + 0.110)
    # the steps after the end are neither counted nor timed
    assert w["elapsed_s"] == pytest.approx(w["done_s"][-2])
    assert w["t1"] == pytest.approx(w["t0"] + w["elapsed_s"])
    assert rate(w, 256) == pytest.approx(rate(base, 256), rel=1e-3)
    # what the window as it was would have said: 1% less
    old = len(w["done_s"]) * 256 / w["done_s"][-1]
    assert old < 0.99 * rate(base, 256)


def test_a_pause_inside_the_window_is_made_up_by_the_queue_and_stays_in():
    w, base = late(fourth_last=0.110)
    assert w["trimmed_steps"] == 0 and w["completed"] == base["completed"]
    assert w["elapsed_s"] == pytest.approx(base["elapsed_s"])
    assert rate(w, 256) == pytest.approx(rate(base, 256))
    # the late step is seen late and the next one early: both are counted
    gaps = [b - a for a, b in zip(w["done_s"], w["done_s"][1:])]
    assert gaps[-4] == pytest.approx(STEP + 0.110)
    assert gaps[-3] == pytest.approx(0.0, abs=1e-9)


def test_two_late_waits_at_the_end_take_two_steps():
    w, base = late(second_last=0.050, last=0.110)
    # the second to last is seen 50 ms late and the last 110 ms: its gap,
    # 160 ms, is late too
    assert w["trimmed_steps"] == 2
    assert rate(w, 1) == pytest.approx(rate(base, 1), rel=1e-3)
    # what the rule cannot see: a last step that is late by less than the
    # step before it was. Its gap is short, so it counts as on time
    w, base = late(second_last=0.050, last=0.030)
    assert w["trimmed_steps"] == 0
    assert w["elapsed_s"] == pytest.approx(base["elapsed_s"] + 0.030)


def test_a_small_delay_is_no_pause():
    w, base = late(last=0.5 * (LATE - 1) * STEP)
    assert w["trimmed_steps"] == 0
    assert rate(w, 1) == pytest.approx(rate(base, 1), rel=(LATE - 1) * STEP
                                       / base["elapsed_s"])


def test_more_late_steps_at_the_end_than_the_queue_holds_is_no_pause():
    base, _ = drive()
    n = base["attempted"]
    ok, _ = drive(total=n, late_waits={-k: 0.050 * (IN_FLIGHT + 1 - k)
                                       for k in range(1, IN_FLIGHT + 1)})
    assert ok["trimmed_steps"] == IN_FLIGHT and ok["error"] is None
    assert rate(ok, 1) == pytest.approx(rate(base, 1), rel=1e-3)
    # one more: the run has slowed down, and is measured as it always was,
    # every step over all the time to the last
    w, _ = drive(total=n, late_waits={-k: 0.050 * (IN_FLIGHT + 2 - k)
                                      for k in range(1, IN_FLIGHT + 2)})
    assert w["trimmed_steps"] == 0 and w["error"] is None
    assert w["completed"] == n
    assert w["elapsed_s"] == pytest.approx(w["done_s"][-1])
    assert rate(w, 1) < 0.96 * rate(base, 1)


@pytest.mark.parametrize("done,want", [
    ([], (-1, None)),
    ([0.1], (0, None)),
    ([0.1, 0.2, 0.3, 0.4], (3, pytest.approx(0.1))),
    ([0.1, 0.2, 0.3, 0.45], (2, pytest.approx(0.15))),
    ([0.1, 0.2, 0.3, 0.45, 0.5, 0.6], (5, pytest.approx(0.1))),   # made up
    ([0.3, 0.4, 0.5, 0.6, 0.71], (4, pytest.approx(0.11))),  # first: no gap
])
def test_the_end_is_the_last_step_seen_on_time(done, want):
    assert on_time_end(done) == want


def test_only_the_steps_that_were_in_flight_can_go():
    done = [0.1 * i for i in range(1, 11)] + [1.2, 1.4, 1.6]
    assert on_time_end(done, in_flight=3) == (9, pytest.approx(0.2))
    assert on_time_end(done, in_flight=2) == (12, pytest.approx(0.2))


# ------------------------------ completion times as the v5e's host saw them
with open(os.path.join(FIXTURES, "steps_done_ms.json")) as _f:
    RECORDED = json.load(_f)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_the_end_on_recorded_runs(name):
    """`steps_done_ms` of runs of PR 25 to 27 (`origin` says which). A run
    whose last step was seen late loses that step and then reads the steady
    rate, where all steps over all time read 0.2-1% less; every other run
    keeps every step."""
    done = [t / 1e3 for t in RECORDED[name]["steps_done_ms"]]
    gaps = [b - a for a, b in zip(done, done[1:])]
    steady = 1 / statistics.median(gaps)
    k, last_gap = on_time_end(done)
    before = len(done) / done[-1]
    after = (k + 1) / done[k]
    if name.startswith("late_"):
        late_ms = float(name.split("_")[1][:-2])
        assert k == len(done) - 2
        assert 1e3 * (last_gap - gaps[-2]) == pytest.approx(late_ms, abs=1.0)
        assert before < (1 - 0.8 * late_ms / 1e3 / done[-1]) * steady
    else:
        assert k == len(done) - 1 and after == before
    if RECORDED[name]["cell"] == "resnet50_dp4_b1024":
        # the host, held in the step's staging, sees completions a fifth of
        # a step behind time inside the window and catches up once it only
        # waits: the tail's first gaps are short, none is late
        assert max(gaps[-IN_FLIGHT:]) < LATE / steady
        assert min(gaps[-IN_FLIGHT:]) < 0.01 / steady
    else:
        # from the first completion to the window's end the steps kept the
        # steady pace: nothing of a pause is left in the time
        assert done[k] - done[0] == pytest.approx(k / steady, rel=5e-4)
