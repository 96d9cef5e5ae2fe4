"""Layer: collectives.  Per traced step, device time of collective
operations during which no compute operation ran on that device (the
worst device).  Nothing to read where the step holds no collective."""


def read(facts):
    trace, steps = facts["trace"], facts["window"]["traced_steps"]
    if not trace or not steps or trace["collective_s"] == 0:
        return None
    return 1e3 * trace["collective_exposed_s"] / steps
