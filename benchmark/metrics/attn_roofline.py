"""Layer: kernels.  Share of the roofline over the gated full-attention
stages (norms, projections, rotary embedding, causal grouped-query
attention, gate, forward and backward with what the step rematerialises),
whichever tier implements them: work from the stages' shapes, time from
every device event under the stages' scopes."""
from benchmark import roofline


def read(facts):
    return roofline.kind_share(facts, "attn")
