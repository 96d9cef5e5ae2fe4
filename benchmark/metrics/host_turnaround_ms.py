"""Layer: step.  The host's own time per step, not blocked on the device:
mean over the measured window of the interval between the ends of
consecutive ``step.dispatch`` spans, less the ``step.guard_wait`` and
``step.metric_wait`` time inside it.  Where the host blocks on the device
once a step, it is the time the device has nothing queued.  Read from the
program's span recorder (``mxnet_tpu.profiler.spans``), whole window;
nothing to read from a program that keeps no spans."""

WAITS = ("step.guard_wait", "step.metric_wait")


def window_spans(facts):
    """The program's spans that start inside the measured window, or None
    where the program keeps none."""
    from mxnet_tpu import profiler
    if not hasattr(profiler, "spans"):
        return None
    w = facts["window"]
    return profiler.spans(since=w["t_start"],
                          until=w["t_start"] + w["seconds"])


def dispatches(records):
    """The ``step.dispatch`` spans of the thread that made most of them,
    in order: one per step."""
    by_thread = {}
    for r in records or ():
        if r["name"] == "step.dispatch":
            by_thread.setdefault(r["thread"], []).append(r)
    return max(by_thread.values(), key=len) if by_thread else []


def overlap(r, t0, t1):
    return max(0.0, min(r["end"], t1) - max(r["start"], t0))


def read(facts):
    records = window_spans(facts)
    steps = dispatches(records)
    if len(steps) < 2:
        return None
    waits = [r for r in records if r["name"] in WAITS
             and r["thread"] == steps[0]["thread"]]
    own = 0.0
    for a, b in zip(steps, steps[1:]):
        own += b["end"] - a["end"] - sum(
            overlap(r, a["end"], b["end"]) for r in waits)
    return 1e3 * own / (len(steps) - 1)
