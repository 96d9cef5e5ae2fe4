"""Layer: kernels.  Share of the roofline over the routed-expert stages
(norm, router, top-k, the sort and the gathers, the grouped expert
products, the shared expert, forward and backward with what the step
rematerialises): work from the stages' shapes with the routed part at the
(token, expert) pairs the window's steps really landed on held experts
(``moe.assignments_here`` of the program's ``step.counters`` records; the
expectation where there are none), time from every device event under the
stages' scopes — the grouped products among them, which the compiler names
by itself (``ragged-dot-*``) and not by the graph's scope."""
from benchmark import flops
from benchmark.metrics.moe_load_imbalance import step_counters

#: device events of the grouped products: XLA replaces their op_name
OWN_SCOPES = ("ragged-dot-none", "ragged-dot-metadata")


def read(facts):
    trace, steps = facts["trace"], facts["window"]["traced_steps"]
    if not trace or not steps or facts["peak"] is None:
        return None
    job = facts["job"]
    landed = [s["moe.assignments_here"] for s in step_counters(facts)
              if "moe.assignments_here" in s]
    nodes = job.ref.node_work(
        job.model, job.batch // facts["chips"],
        pairs_here=sum(landed) / len(landed) if landed else None).get("moe")
    if not nodes:
        return None
    scopes = trace["scopes_s"]
    spent = sum(scopes.get(s, 0.0) for s in OWN_SCOPES)
    least = 0.0
    for node in nodes:
        for part, prefix in (("fwd", ""), ("bwd", "_backward_")):
            t = sum(scopes.get(prefix + s, 0.0) for s in node["scopes"])
            if t > 0:
                spent += t
                least += steps * flops.least_seconds(
                    node[part][0], node[part][1], facts["peak"])[0]
    if least == 0:
        return None
    return 100.0 * least / spent
