"""Layer: input.  Share of the measured window that the prefetch worker
spent pulling batches from its source and staging them
(``feed.source_next`` + ``feed.stage`` spans): at 100 the feed sets the
pace, and what is left is the worker blocked on a full queue."""
from benchmark.metrics.host_turnaround_ms import window_spans

WORK = ("feed.source_next", "feed.stage")


def read(facts):
    busy = [r["end"] - r["start"] for r in window_spans(facts) or ()
            if r["name"] in WORK]
    if not busy:
        return None
    return 100.0 * sum(busy) / facts["window"]["seconds"]
