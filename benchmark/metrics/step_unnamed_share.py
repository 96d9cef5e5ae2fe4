"""Layer: step.  Of the traced slice's device self time, the share under no
scope the program wrote: events the compiler made and named by itself —
``hlo:*`` (copies, slices, clones: no ``op_name`` path at all) and the
grouped expert products' ``ragged-dot-*`` (XLA puts its own name in the
path's place).  What a reader of ``scopes_s`` cannot put down to a graph
node, a ``mirror_stage`` or a ``step.*`` scope of the trainer."""
from benchmark.metrics.moe_roofline import OWN_SCOPES


def read(facts):
    trace = facts["trace"]
    if not trace or not trace["scopes_s"]:
        return None
    scopes = trace["scopes_s"]
    total = sum(scopes.values())
    if total <= 0:
        return None
    unnamed = sum(t for scope, t in scopes.items()
                  if scope.startswith("hlo:") or scope in OWN_SCOPES)
    return 100.0 * unnamed / total
