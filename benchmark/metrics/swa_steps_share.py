"""Layer: kernels.  Of the tiles a causal schedule would visit, the share
that the windowed ``GQAttention`` lowerings of the step do visit: over the
``kernel.route`` events of the program's recorder that name the kernel
``gqa_attention``, carry a ``window`` above 0 and end before the window's
start (the op is lowered while the step is traced, inside set-up), 100 x
the sum of ``steps`` over the sum of ``steps_causal`` (grid steps a (row,
key head) on the compiled tier; (row block, key tile) pairs on the lax
tier).  The band's own share of the triangle is the floor (44 % for a
window of 2,048 at 8,192 positions); 100 means the window is only masked,
not skipped.  Nothing to read from a program that records no such event."""
from benchmark.metrics.attn_kernel_share import routes


def read(facts):
    found = [ids for ids in routes(facts) if ids.get("window", 0) > 0]
    causal = sum(ids.get("steps_causal", 0) for ids in found)
    if not causal:
        return None
    return 100.0 * sum(ids.get("steps", 0) for ids in found) / causal
