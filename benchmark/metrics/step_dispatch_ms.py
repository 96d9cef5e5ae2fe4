"""Layer: step.  Mean time per step inside ``SPMDTrainer._step_impl``'s
three host phases — ``step.prepare`` (the batch, the key, the schedule's
scalars), ``step.dispatch`` (the call of the compiled step) and
``step.localize`` — over the measured window, from the program's spans."""
from benchmark.metrics.host_turnaround_ms import dispatches, window_spans

PHASES = ("step.prepare", "step.dispatch", "step.localize")


def read(facts):
    records = window_spans(facts)
    steps = dispatches(records)
    if not steps:
        return None
    spent = sum(r["end"] - r["start"] for r in records
                if r["name"] in PHASES and r["thread"] == steps[0]["thread"])
    return 1e3 * spent / len(steps)
