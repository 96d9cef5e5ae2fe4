"""Layer: kernels.  Share of the roofline over Convolution nodes (forward and _backward_): work from the
nodes' shapes, time from every device event under the nodes' scopes."""
from benchmark import roofline


def read(facts):
    return roofline.kind_share(facts, "conv")
