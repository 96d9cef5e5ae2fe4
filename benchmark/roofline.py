"""A kind of graph node's share of its roofline, shared by the
``<kind>_roofline`` readers: the least time the chip could take for the
work those nodes need (``reference/<config>.py: node_work``, from shapes),
over the device time of every event under those nodes' scopes.  Forward
and backward are taken apart, and a part none of whose scopes shows in the
trace (XLA fused it into a neighbour) is left out on both sides; where
nothing shows, there is nothing to read."""
from benchmark import flops


def kind_share(facts, kind):
    trace, steps = facts["trace"], facts["window"]["traced_steps"]
    if not trace or not steps or facts["peak"] is None:
        return None
    job = facts["job"]
    nodes = job.ref.node_work(job.model, job.batch // facts["chips"]) \
        .get(kind)
    if not nodes:
        return None
    scopes = trace["scopes_s"]
    least = spent = 0.0
    for node in nodes:
        for part, prefix in (("fwd", ""), ("bwd", "_backward_")):
            t = sum(scopes.get(prefix + s, 0.0) for s in node["scopes"])
            if t > 0:
                spent += t
                least += steps * flops.least_seconds(
                    node[part][0], node[part][1], facts["peak"])[0]
    if spent == 0:
        return None
    return 100.0 * least / spent
