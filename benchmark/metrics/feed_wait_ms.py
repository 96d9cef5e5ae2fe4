"""Layer: input.  Time per step of the measured window that the consumer
spent blocked on ``DevicePrefetchIter``'s queue (``feed.get_wait`` spans):
the inside of ``input_wait_ms``, which also holds the harness's own
wrapper."""
from benchmark.metrics.host_turnaround_ms import window_spans


def read(facts):
    waits = [r["end"] - r["start"] for r in window_spans(facts) or ()
             if r["name"] == "feed.get_wait"]
    steps = facts["window"]["steps"]
    if not waits or not steps:
        return None
    return 1e3 * sum(waits) / steps
