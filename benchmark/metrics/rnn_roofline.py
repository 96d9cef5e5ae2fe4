"""Layer: kernels.  Share of the roofline over the fused RNN node (gate contractions and cell math of the scan, forward and backward), whichever lstm_cell tier runs: work from the
nodes' shapes, time from every device event under the nodes' scopes."""
from benchmark import roofline


def read(facts):
    return roofline.kind_share(facts, "rnn")
