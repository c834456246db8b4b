"""The timed window, one for every cell.

A bounded number of steps in flight; the window ends when its last step is
done. The rate is every completed step over the window's whole time: no
chunks, no medians, no trimmed steps.
"""
import collections
import contextlib
import time

# Steps in flight. Eight cover about a second of device time at the shortest
# step of the first cells (113 ms): a training script that reads its loss
# every few batches runs so, and the device queue hides a shorter host pause.
IN_FLIGHT = 8


def run_window(call, wait, seconds, in_flight=IN_FLIGHT,
               clock=time.perf_counter, annotate=None, dispatched=None):
    """Drive `call()` (one step; returns that step's loss, not yet ready)
    for `seconds`, then wait for what is in flight.

    wait(loss) blocks until that step is done. annotate(i), if given, is a
    context manager put around the i-th call (the traced run's host spans).
    dispatched(), if given, is called once when the last step has been
    dispatched, with the queue still full (the memory reading).
    Returns a dict: t0, t1, elapsed_s, attempted, completed, losses (as
    returned, unread), done_s (host-observed completion of each step after
    t0), dispatch_s (host time inside each call), error (the exception that
    ended the window early, or None).
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
    t1 = clock()
    return {"t0": t0, "t1": t1, "elapsed_s": t1 - t0, "attempted": attempted,
            "completed": len(done_s), "losses": losses, "done_s": done_s,
            "dispatch_s": dispatch_s, "error": error}


def rate(window, samples_per_step):
    """Samples of all steps completed in the window over its whole time."""
    return window["completed"] * samples_per_step / window["elapsed_s"]
