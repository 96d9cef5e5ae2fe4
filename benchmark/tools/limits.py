#!/usr/bin/env python3
"""Readings that a cell's limits are set from, taken on the chip at the
cell's own size, many seeds in one process:

    python3 benchmark/tools/limits.py --workload <cell> --seeds 12 --controls 3 --out <file.json>

For every seed the program is driven through ``fit`` as a run drives it
(check steps, warm-up, a short window) and compared with the float32
reference: the LOWER readings.  For the first ``--controls`` seeds the
reference is also put in the program's place: in the ``--control``
precisions (the UPPER readings), in bf16 as it is (the stand-in), in bf16
with half of each batch left out and the mean taken over the rest, and in
bf16 with its state handed back unchanged (the fault readings).  ``benchmark/run.py`` never
runs this; ``PERF.md`` holds what it read.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def half_left_out(batches):
    """Each batch with its second half replaced by its first: the mean over
    the rest."""
    import jax.numpy as jnp
    out = []
    for b in batches:
        half = {}
        for k, v in b.items():
            v = jnp.asarray(v)
            n = v.shape[0] // 2
            half[k] = jnp.concatenate([v[:n], v[:n]], axis=0)
        out.append(half)
    return out


def state_unchanged(step):
    """The step with its state handed back as it came: the losses are the
    seeded weights' on every batch, no gradient reaches the optimizer,
    nothing moves."""
    import jax
    import jax.numpy as jnp

    def frozen(params, aux, mom, batch):
        _, _, _, loss, seen, grads = step(params, aux, mom, batch)
        return params, aux, mom, loss, jax.tree.map(jnp.zeros_like, seen), \
            grads
    return frozen


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3000000001)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--control", default="int8",
                    help="precisions of the control, comma-separated")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from benchmark import compare, run
    from benchmark.meter import Meter
    from benchmark.reference import common
    import importlib
    spec, cell, config, traffic, limits = run.load_cell(args.workload)
    run.require_chips(int(cell["chips"]))
    meter = Meter()
    Job = importlib.import_module("benchmark.jobs." + traffic["job"]).Job
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        tic = time.perf_counter()
        job = Job(cell, config, traffic, limits, seed, meter)
        job.setup()
        window = job.run(args.seconds)
        job.release()
        job.compare()
        ref = job.reference

        def numbers(readings):
            return {k: v[0] for k, v in compare.training_gaps(
                readings, ref).items()}

        def lean(readings):
            return {k: v for k, v in readings.items() if k != "full"}

        gaps = compare.training_gaps(job.program, ref)
        row = {"seed": seed, "failed": window["failed"],
               "program": {k: v[0] for k, v in gaps.items()},
               "at": {k: v[1] for k, v in gaps.items()},
               "left_out_of_change": compare.negligible_leaves(ref),
               "readings": {"program": job.program, "reference": lean(ref)}}
        if i < args.controls:
            batches = job.check_batches()
            for name, precision, fed, wrap in [
                    ("control_" + p, p, batches, None)
                    for p in args.control.split(",")] + [
                    ("stand_in_bf16", "bf16", batches, None),
                    ("fault_half_batch", "bf16", half_left_out(batches),
                     None),
                    ("fault_state_unchanged", "bf16", batches,
                     state_unchanged)]:
                got = common.differences(
                    job.compare(precision, fed, wrap), ref)
                row[name] = numbers(got)
                row["readings"][name] = got
        job.reference = ref = None
        row["seconds"] = time.perf_counter() - tic
        run.log("seed %d: %s" % (seed, json.dumps(
            {k: v for k, v in row.items()
             if k not in ("at", "readings")})))
        rows.append(row)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
