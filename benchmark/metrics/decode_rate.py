"""Layer: input.  Images the native decoder gave per second while it
worked: the ``images`` of the window's ``decode.batch`` spans over their
summed duration — the decoder's rate apart from the step's."""
from benchmark.metrics.host_turnaround_ms import window_spans


def read(facts):
    batches = [r for r in window_spans(facts) or ()
               if r["name"] == "decode.batch"]
    spent = sum(r["end"] - r["start"] for r in batches)
    if not spent:
        return None
    return sum(r["ids"].get("images", 0) for r in batches) / spent
