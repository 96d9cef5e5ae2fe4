"""Layer: kernels.  Share of the roofline over the sliding-window attention
stages (input norm, the q, k, v and gate projections, the heads' norms, the
rotary embedding, grouped-query attention under the window, the sigmoid
gate, the output projection, the output norm and the add, forward and
backward with what the step rematerialises), whichever tier implements
them: work from the stages' shapes — the scores of the window's band only,
not of the causal triangle — time from every device event under the stages'
scopes."""
from benchmark import roofline


def read(facts):
    return roofline.kind_share(facts, "swa")
