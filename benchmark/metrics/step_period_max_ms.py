"""Layer: step.  The longest interval between the ends of consecutive
``step.dispatch`` spans in the measured window.  The spans that fill that
interval (name, thread, milliseconds inside it) go to standard error, so
that a stall names itself in the run in which it happens."""
import sys

from benchmark.metrics.host_turnaround_ms import (dispatches, overlap,
                                                  window_spans)

SHOWN = 16


def read(facts):
    records = window_spans(facts)
    steps = dispatches(records)
    if len(steps) < 2:
        return None
    a, b = max(zip(steps, steps[1:]),
               key=lambda ab: ab[1]["end"] - ab[0]["end"])
    t0, t1 = a["end"], b["end"]
    inside = sorted(((overlap(r, t0, t1), r) for r in records
                     if overlap(r, t0, t1) > 0), key=lambda x: -x[0])
    print("step_period_max_ms: %.3f ms between the dispatches of steps %s "
          "and %s; the spans inside, longest first:"
          % (1e3 * (t1 - t0), a["ids"].get("step"), b["ids"].get("step")),
          file=sys.stderr)
    for ms, r in inside[:SHOWN]:
        print("  %-18s %-24s %9.3f ms  %s"
              % (r["name"], r["thread_name"], 1e3 * ms, r["ids"]),
              file=sys.stderr)
    return 1e3 * (t1 - t0)
