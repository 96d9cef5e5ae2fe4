"""Layer: kernels.  Share of the roofline over BatchNorm(+ReLU) nodes, bytes-bound, whichever tier (Pallas bn_act or lax) runs: work from the
nodes' shapes, time from every device event under the nodes' scopes."""
from benchmark import roofline


def read(facts):
    return roofline.kind_share(facts, "bn")
