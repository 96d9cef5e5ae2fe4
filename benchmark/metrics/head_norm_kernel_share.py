"""Layer: kernels.  Share of the heads' RMSNorm lowerings of the step (an
``RMSNorm`` over (batch, positions, heads, width), gated, turned by the
rotary embedding after it or plain) that the program routed to its compiled
kernels (``kernels/head_norm.py``: ``mxtpu_head_norm_fwd`` / ``_bwd``): of
set-up's ``kernel.route`` events that name the kernel ``head_norm``, read as
``causal_conv_kernel_share`` reads its own, those whose tier is ``pallas``.
A lowering on the lax tier says why on its event (``reason``: shapes, mesh);
those go to standard error.  Nothing to read from a program that records no
such event (a norm of rank 2 or 3 records none)."""
import sys

KERNEL = "head_norm"


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
        print("head_norm_kernel_share: one lowering on the %s tier (%s)"
              % (ids.get("tier"), ids.get("reason")), file=sys.stderr)
    return 100.0 * (len(found) - len(other)) / len(found)
