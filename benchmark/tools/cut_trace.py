#!/usr/bin/env python3
"""Cut a small recorded trace for ``benchmark/tests/data/`` out of a trace
that ``run.py --keep-trace`` wrote:

    python3 benchmark/tools/cut_trace.py <trace.json.gz> <out.json.gz> [--step 2] [--margin-us 150]

Keeps the events that start between ``margin`` before the end of the
``--step``-th execution of the step program and ``margin`` after the
start of the next (the tail of one step, the idle stretch with the small
programs between, the head of the next), with the op_name paths of the
instructions kept (from ``<trace>.hlo.txt``) under ``"paths"``.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace_reduce as T  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--step", type=int, default=2)
    ap.add_argument("--margin-us", type=float, default=150.0)
    args = ap.parse_args()

    structure = T.load(args.trace)
    reduced = T.reduce(structure)
    plane = [p for p in structure["planes"]
             if p["name"] == reduced["plane"]][0]
    runs = sorted(e for line in plane["lines"]
                  if line["name"] == T.MODULE_LINE for e in line["events"]
                  if e[0].startswith(reduced["step_module"]))
    a, b = runs[args.step - 1], runs[args.step]
    margin = args.margin_us * 1e3
    small = T.cut(structure, a[1] + a[2] - margin, b[1] + margin,
                  max_name=300)
    with open(args.trace + ".hlo.txt") as f:
        paths = T.instruction_scopes(f.read())
    kept = {T.instruction_of(e) for p in small["planes"]
            for line in p["lines"] for e in line["events"]}
    small["paths"] = {k: v for k, v in paths.items() if k in kept}
    T.save(small, args.out)
    print("%d events, %d paths -> %s" % (
        sum(len(line["events"]) for p in small["planes"]
            for line in p["lines"]), len(small["paths"]), args.out))


if __name__ == "__main__":
    main()
