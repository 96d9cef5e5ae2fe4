"""Layer: kernels.  Share of the roofline over the Gated DeltaNet stages
(norm, projections, short convolution, the chunked delta rule, gated
output norm, forward and backward with what the step rematerialises),
whichever tier implements them: work from the stages' shapes, time from
every device event under the stages' scopes."""
from benchmark import roofline


def read(facts):
    return roofline.kind_share(facts, "gdn")
