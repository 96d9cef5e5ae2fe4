"""Layer: step.  Device time of the step program per traced step: the
``XLA Modules`` events of the busiest program, summed over the traced
slice, over the steps traced (fullest device on several chips)."""


def read(facts):
    trace, steps = facts["trace"], facts["window"]["traced_steps"]
    if not trace or not steps or not trace["step_module_s"]:
        return None
    return 1e3 * trace["step_module_s"] / steps
