"""Layer: compile cache.  Seconds of tracing and lowering inside set-up
(``compile.trace`` + ``compile.lower`` events of the program's recorder
that end before the window's start): the part of ``setup_compile_s`` that
a warm compile cache does not remove.  Summed as ``setup_compile_s`` sums
them, so a trace nested in another counts in both; the wall time they
cover (their union) goes to standard error beside it, with the program's
own ``setup.*`` spans: what of ``setup_s`` the program owns, piece by piece."""
import sys

KINDS = ("compile.trace", "compile.lower")


def read(facts):
    from mxnet_tpu import profiler
    if not hasattr(profiler, "spans"):
        return None
    t_start = facts["window"]["t_start"]
    before = [r for r in profiler.spans(until=t_start) if r["end"] <= t_start]
    events = [r for r in before if r["name"] in KINDS]
    if not events:
        return None
    for r in before:
        if r["name"].startswith("setup."):
            print("setup_trace_lower_s: %-22s %8.3f s" % (
                r["name"], r["end"] - r["start"]), file=sys.stderr)
    covered, reach = 0.0, -float("inf")
    for r in sorted(events, key=lambda r: r["start"]):
        covered += max(0.0, r["end"] - max(r["start"], reach))
        reach = max(reach, r["end"])
    total = sum(r["end"] - r["start"] for r in events)
    print("setup_trace_lower_s: %d events, %.3f s summed, %.3f s of wall "
          "time covered" % (len(events), total, covered), file=sys.stderr)
    return total
