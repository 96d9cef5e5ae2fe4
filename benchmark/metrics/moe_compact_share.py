"""Layer: kernels.  Share of the routed-expert layers' calls whose held
pairs fit the compact capacity the layer derives from its shapes, so that
the call worked once over that many rows and neither at full width nor a
second time: over the measured window's steps, the sum of
``moe.compact_calls`` over the sum of ``moe.calls``.  Read from the
program's ``step.counters`` records; nothing to read from a program that
counts neither."""
from benchmark.metrics.moe_load_imbalance import step_counters


def read(facts):
    steps = [s for s in step_counters(facts)
             if "moe.calls" in s and "moe.compact_calls" in s]
    calls = sum(s["moe.calls"] for s in steps)
    if not calls:
        return None
    return 100.0 * sum(s["moe.compact_calls"] for s in steps) / calls
