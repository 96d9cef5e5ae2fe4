"""Layer: kernels.  Share of the ``GQAttention`` lowerings of the step that
the program routed to its compiled kernels
(``kernels/flash_attention.py``: ``mxtpu_gqa_attention_fwd`` / ``_bwd``):
of the ``kernel.route`` events of the program's recorder that name the
kernel ``gqa_attention`` and end before the window's start — the op is
lowered while the step is traced, inside set-up — those whose tier is
``pallas``.  A lowering on the lax tier says why on its event (``reason``:
shapes, mesh); those go to standard error.  Nothing to read from a program
that records no such event."""
import sys

KERNEL = "gqa_attention"


def routes(facts):
    """The ids of set-up's ``kernel.route`` events of this kernel."""
    from mxnet_tpu import profiler
    t_start = facts["window"]["t_start"]
    return [r["ids"] for r in profiler.spans(until=t_start)
            if r["name"] == "kernel.route" and r["end"] <= t_start
            and r["ids"].get("kernel") == KERNEL]


def read(facts):
    found = routes(facts)
    if not found:
        return None
    other = [ids for ids in found if ids.get("tier") != "pallas"]
    for ids in other:
        print("attn_kernel_share: one lowering on the %s tier (%s)"
              % (ids.get("tier"), ids.get("reason")), file=sys.stderr)
    return 100.0 * (len(found) - len(other)) / len(found)
