"""Layer: kernels.  Share of the roofline over the Kimi Delta Attention
stages (norm, the three projections and short convolutions, the low-rank
decay and output gates, the chunked delta rule with a decay per key
channel, the sigmoid-gated output norm, forward and backward with what the
step rematerialises), whichever tier implements them: work from the
stages' shapes, time from every device event under the stages' scopes."""
from benchmark import roofline


def read(facts):
    return roofline.kind_share(facts, "kda")
