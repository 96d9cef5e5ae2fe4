"""Layer: step.  Share of the measured window's steps that the trainer
dispatched while it had not yet waited for the step before: the
``step.dispatch`` spans that carry ``queued=1`` over those that carry the
note at all.  At 100 % the device goes from step to step; a read of the
guard's counters or of the metric that has crept back into every step
shows here as 0 %, and in ``device_idle_share``.  Nothing to read from a
program whose dispatch spans carry no such note."""
from benchmark.metrics.host_turnaround_ms import dispatches, window_spans


def read(facts):
    noted = [r["ids"]["queued"] for r in dispatches(window_spans(facts))
             if "queued" in r["ids"]]
    if not noted:
        return None
    return 100.0 * sum(1 for q in noted if q) / len(noted)
