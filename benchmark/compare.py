"""The comparison that decides ``correct`` for a training cell.

Both sides give the same readings (``reference.common.follow`` for the
plain reference; ``jobs/train_fit.py`` takes the program's from its own
state): each of the first steps' mean loss, the per-leaf norm of the first
gradient as the optimizer gets it, and the per-leaf norm of the
parameters' change after those steps.  From them come ten numbers; a cell
judges those that its ``benchmark/limits/<cell>.json`` gives a limit, and
shows those it lists with limit null (PERF.md says which and why):

``loss_gap``        widest |program - reference| / |reference| over the steps.
``loss1_gap``       the same for the first step alone: the forward pass
                    from the seeded weights, before any update can amplify
                    a difference.
``grad1_gap``       worst leaf of |‖g_prog‖ - ‖g_ref‖| / max(‖g_ref‖ of that
                    leaf, ‖g_ref‖ of the median leaf) — the gap between the
                    norms, not the norm of the difference.
``grad1_mid_gap``   the median leaf of the same.
``grad1_diff``      worst leaf of ‖g_prog - g_ref‖ over the same measure —
                    the norm of the difference, which rounding moves in
                    the first order where it moves a norm in the second.
``grad1_mid_diff``  the median leaf of that.
``change_gap``, ``change_mid_gap``, ``change_diff``, ``change_mid_diff``
                    the same four over w_k - w_0, leaving out leaves whose
                    raw gradient in the reference is under a thousandth of
                    the median leaf's (they move by decay and round-off
                    alone).
"""
import json
import math
import statistics
import sys

NEGLIGIBLE = 1e-3


def leaf_gaps(prog, ref, leave_out=(), diff=None):
    """[(gap, leaf)] by the measure above, widest first; a leaf the
    program lacks, or a non-finite norm, reads infinity.  With ``diff``
    ({leaf: ‖prog - ref‖}) the numerator is that and not the gap of norms."""
    median = statistics.median(ref.values())
    out = []
    for name, r in ref.items():
        if name in leave_out:
            continue
        p = prog.get(name)
        top = abs(p - r) if diff is None or p is None else diff.get(name)
        if top is None or p is None or not math.isfinite(top) \
                or not math.isfinite(p) or not math.isfinite(r):
            out.append((float("inf"), name))
        else:
            out.append((top / max(r, median, 1e-30), name))
    return sorted(out, reverse=True)


def worst_and_middle(prog, ref, leave_out=(), diff=None):
    """((gap, leaf) of the worst leaf, (gap, leaf) of the median leaf)."""
    gaps = leaf_gaps(prog, ref, leave_out, diff)
    return gaps[0], gaps[len(gaps) // 2]


def negligible_leaves(ref):
    raw = ref["grad1_raw"]
    median = statistics.median(raw.values())
    return sorted(k for k, v in raw.items() if v < NEGLIGIBLE * median)


def training_gaps(prog, ref):
    """{number: (value, where)} of the numbers above (the four ``diff``
    ones where ``prog`` holds ``grad1_diff`` and ``change_diff``)."""
    if len(prog["loss"]) != len(ref["loss"]):
        loss = loss1 = (float("inf"), "steps %d != %d" % (
            len(prog["loss"]), len(ref["loss"])))
    else:
        gaps = [abs(p - r) / abs(r) if math.isfinite(p) else float("inf")
                for p, r in zip(prog["loss"], ref["loss"])]
        loss = (max(gaps), "step %d" % (gaps.index(max(gaps)) + 1))
        loss1 = (gaps[0], "step 1")
    out = {"loss_gap": loss, "loss1_gap": loss1}
    for what, skip in (("grad1", ()), ("change", negligible_leaves(ref))):
        out[what + "_gap"], out[what + "_mid_gap"] = worst_and_middle(
            prog[what], ref[what], skip)
        if what + "_diff" in prog:
            out[what + "_diff"], out[what + "_mid_diff"] = worst_and_middle(
                prog[what], ref[what], skip, prog[what + "_diff"])
    return out


def judge(gaps, limits):
    """(correct, {number: {"value", "limit", "at"}}) over the numbers the
    cell's limits file lists: each that has a limit must lie at or under
    it; one listed with null is shown and not judged."""
    shown, ok = {}, True
    for name, limit in limits.items():
        value, at = gaps[name]
        shown[name] = {"value": value, "limit": limit, "at": at}
        if limit is not None and not value <= limit:
            ok = False
    return ok, shown


def print_compared(shown, extra=None, file=None):
    """The last lines of standard error: each number beside its limit."""
    file = file or sys.stderr
    for name, row in shown.items():
        print("compared %s = %.6g (limit %s) at %s"
              % (name, row["value"], row["limit"], row["at"]), file=file)
    for name, value in (extra or {}).items():
        print("compared %s = %s" % (name, json.dumps(value)), file=file)
    file.flush()
