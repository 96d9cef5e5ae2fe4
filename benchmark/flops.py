"""The benchmark's arithmetic: peaks by ``device_kind``, the training rule
(3 x analytic forward FLOPs, 1 MAC = 2 FLOPs; copied from
``bench._train_flops`` / ``contrib/flops.model_flops``), utilisation, and
the least time a piece of work can take on the chip.  Each
configuration's own forward count lives beside its plain reference
(``reference/<config>.py: flops_per_item``)."""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN_FACTOR = 3


def peaks(device_kind):
    """The peaks row of ``device_kind``; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError("no row for device_kind %r in benchmark/peaks.json — "
                       "add the published peaks and their source before "
                       "reporting a share of them" % (device_kind,))
    return table[device_kind]


def train_flops_per_item(forward_flops):
    return TRAIN_FACTOR * forward_flops


def mfu_percent(items_per_s, forward_flops, chips, peak):
    """Model FLOP/s over the chips' bf16 peak, in percent."""
    return 100.0 * items_per_s * train_flops_per_item(forward_flops) \
        / (chips * peak["bf16_flops"])


def least_seconds(flops, bytes_moved, peak):
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s.  Returns (seconds, "flops" | "bytes")."""
    by_flops = flops / peak["bf16_flops"]
    by_bytes = bytes_moved / peak["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes \
        else (by_bytes, "bytes")
