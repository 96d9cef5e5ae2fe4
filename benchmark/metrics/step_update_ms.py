"""Layer: step.  Device time a traced step of the optimizer's part of the
fused step: the self time of every device event under the trainer's
``step.update`` scope (the gradient's cast to the master dtype, the update
rule, the guard's selects over parameters, optimizer state and aux) or its
``step.guard`` scope (the all-finite reduction over the gradients, the skip
accounting).  Nothing from a program whose trainer writes no such scope."""

SCOPES = ("step.update", "step.guard")


def read(facts):
    trace, steps = facts["trace"], facts["window"]["traced_steps"]
    if not trace or not steps:
        return None
    spent = [t for scope, t in trace["scopes_s"].items()
             if scope.startswith(SCOPES)]
    if not spent:
        return None
    return 1e3 * sum(spent) / steps
