"""The timed window, one for every cell.

A bounded number of steps in flight. The rate is every counted step over all
the time from the window's start to its end: no chunks, no medians. Inside
the window the queue makes up a step that the host sees late, and nothing is
looked at there. The end is different: the host's clock is read when a `wait`
returns, and a host that pauses on the last wait (20-110 ms; some wait of
four runs in ten has one on the v5e's host) adds the pause's whole length to
the window. So the window ends at the last completion that was seen on time:
with g the median gap between consecutive completions, at the last step whose
own gap is at most `LATE` x g. Steps after it are neither counted nor timed.
Only the steps that were still in flight when dispatching stopped can go: a
run whose late steps reach further back has slowed down, which is no pause,
and is measured to its last step with every step counted.
"""
import collections
import contextlib
import statistics
import time

# Steps in flight. Eight cover about a second of device time at the shortest
# step of the first cells (113 ms): a training script that reads its loss
# every few batches runs so, and the device queue hides a shorter host pause.
IN_FLIGHT = 8

# A completion is late when its gap to the one before is over LATE times the
# window's median gap. Gaps of steps on time lie within 0.7% of the median on
# one chip and within 3.4% on four (80 runs of PR 25 to 27; 2.2% at most in
# the tail), and the shortest pause seen on a last step was 23 ms of 157.
LATE = 1.1


def on_time_end(done_s, in_flight=IN_FLIGHT):
    """(k, last gap): the index of the step that ends the window, and the
    gap of the very last step, both from the completion times `done_s`.
    A step is on time when its gap to the step before is at most `LATE`
    times the median gap of the window; the first has no gap and is on
    time. k is the last step on time, if at most `in_flight` steps follow
    it, and else the very last step."""
    gaps = [b - a for a, b in zip(done_s, done_s[1:])]
    if not gaps:
        return len(done_s) - 1, None
    limit = LATE * statistics.median(gaps)
    k = len(gaps)
    while k > 0 and gaps[k - 1] > limit:
        k -= 1
    return (k if len(gaps) - k <= in_flight else len(gaps)), gaps[-1]


def run_window(call, wait, seconds, in_flight=IN_FLIGHT,
               clock=time.perf_counter, annotate=None, dispatched=None):
    """Drive `call()` (one step; returns that step's loss, not yet ready)
    for `seconds`, then wait for what is in flight.

    wait(loss) blocks until that step is done. annotate(i), if given, is a
    context manager put around the i-th call (the traced run's host spans).
    dispatched(), if given, is called once when the last step has been
    dispatched, with the queue still full (the memory reading).
    Returns a dict: t0, t1 (the window's end: t0 + elapsed_s), elapsed_s,
    attempted, completed (the steps counted: up to the last one seen on
    time), trimmed_steps (awaited after it: at most `in_flight`),
    last_gap_s, losses (as returned, unread), done_s (host-observed
    completion of each step after t0, the trimmed ones too), dispatch_s
    (host time inside each call), error (the exception that ended the window
    early, or None).
    """
    annotate = annotate or (lambda i: contextlib.nullcontext())
    pending = collections.deque()
    losses, done_s, dispatch_s = [], [], []
    attempted, error = 0, None
    t0 = clock()
    try:
        while True:
            ta = clock()
            attempted += 1
            with annotate(attempted - 1):
                loss = call()
            dispatch_s.append(clock() - ta)
            pending.append(loss)
            losses.append(loss)
            if len(pending) > in_flight:
                wait(pending.popleft())
                done_s.append(clock() - t0)
            if clock() - t0 >= seconds:
                break
    except Exception as e:  # a step that raises ends the window, and counts
        error = e
    try:
        if dispatched is not None:
            dispatched()
        while pending:
            wait(pending.popleft())
            done_s.append(clock() - t0)
    except Exception as e:
        error = error or e
    last, last_gap = on_time_end(done_s, in_flight)
    elapsed = done_s[last] if done_s else clock() - t0
    return {"t0": t0, "t1": t0 + elapsed, "elapsed_s": elapsed,
            "attempted": attempted, "completed": last + 1,
            "trimmed_steps": len(done_s) - 1 - last, "last_gap_s": last_gap,
            "losses": losses, "done_s": done_s, "dispatch_s": dispatch_s,
            "error": error}


def rate(window, samples_per_step):
    """Samples of all steps counted in the window over its whole time."""
    return window["completed"] * samples_per_step / window["elapsed_s"]
