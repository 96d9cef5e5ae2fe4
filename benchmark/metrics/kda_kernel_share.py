"""Layer: kernels.  Share of the ``GatedDeltaRule`` lowerings of the Kimi
Linear step that the program routed to its compiled kernels
(``kernels/delta_rule.py``: the rule with a decay per key channel has a
pair of its own): of set-up's ``kernel.route`` events that name the kernel
``delta_rule``, read as ``gdn_kernel_share`` reads them, those whose tier
is ``pallas``.  A lowering on the lax tier says why on its event
(``reason``: shapes, mesh; ``channel_decay`` from a program that has no
kernels for the vector decay); those go to standard error.  Nothing to
read from a program that records no such event."""
import sys

from benchmark.metrics import gdn_kernel_share


def read(facts):
    found = gdn_kernel_share.routes(facts)
    if not found:
        return None
    other = [ids for ids in found if ids.get("tier") != "pallas"]
    for ids in other:
        print("kda_kernel_share: one lowering on the %s tier (%s)"
              % (ids.get("tier"), ids.get("reason")), file=sys.stderr)
    return 100.0 * (len(found) - len(other)) / len(found)
