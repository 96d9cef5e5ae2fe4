"""Layer: step.  The whole step's share of the chips' bf16 peak: items per
second of the measured window x 3 x the configuration's analytic forward
FLOPs per item, over chips x peak."""
from benchmark import flops


def read(facts):
    if facts["peak"] is None:
        return None
    return flops.mfu_percent(facts["train_throughput"],
                             facts["forward_flops"], facts["chips"],
                             facts["peak"])
