"""The timed window's arithmetic, on a simulated device and clock."""
import pytest

from bench_paths import on_path

on_path()
from harness.window import IN_FLIGHT, rate, run_window  # noqa: E402

STEP = 0.1          # device seconds a step
HOST = 0.001        # host seconds a call


class Sim:
    """A device that runs queued steps one after another, and a host clock
    that only `call` and `wait` move. `pause_at`: the call before which the
    host stalls for `pause` seconds."""

    def __init__(self, pause_at=None, pause=0.0, nan_at=None):
        self.now = 0.0
        self.free_at = 0.0
        self.calls = 0
        self.pause_at, self.pause, self.nan_at = pause_at, pause, nan_at

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
        self.now = max(self.now, done_at)


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
