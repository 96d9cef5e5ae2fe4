"""Layer: kernels.  How unevenly the routed-expert layers' tokens fall on
the experts held here: over the measured window's steps, the sum of
``moe.load_max`` (pairs on the fullest held expert of the step's worst
layer) over the sum of ``moe.load_mean`` (the mean over that layer's held
experts).  1 is an even load; the grouped expert products take as long as
their groups are uneven.  Read from the program's ``step.counters``
records (the counts a step's graph computed, settled one step late);
nothing to read from a program that keeps none."""
from benchmark.metrics.host_turnaround_ms import window_spans


def step_counters(facts):
    """The window's ``step.counters`` records' ids, one dict a step."""
    return [r["ids"] for r in window_spans(facts) or ()
            if r["name"] == "step.counters"]


def read(facts):
    steps = [s for s in step_counters(facts) if "moe.load_mean" in s]
    mean = sum(s["moe.load_mean"] for s in steps)
    if not mean:
        return None
    return sum(s["moe.load_max"] for s in steps) / mean
