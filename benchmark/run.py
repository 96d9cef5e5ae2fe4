#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell is data found by name: its entry in
``BENCHMARK.json`` (configuration, traffic, chips, which metrics it
reports), ``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<cell>.json``, ``reference/<config>.py``, ``jobs/<kind>.py`` and
one ``metrics/<metric>.py`` per per-layer metric.  The last line of
standard output is the result; see ``benchmark/README.md``.
"""
import time
T0 = time.perf_counter()          # set-up counts from here

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def log(msg):
    print("[bench %7.1fs] %s" % (time.perf_counter() - T0, msg),
          file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name, spec_path=None):
    """(spec, cell, config, traffic, limits) of the cell ``name``.  Traffic
    and limits are found by name beside the harness (tests: beside the
    spec they hand in)."""
    base = os.path.dirname(spec_path) if spec_path else HERE
    with open(spec_path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json (have: %s)"
                         % (name, ", ".join(sorted(cells))))
    cell = cells[name]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        config = json.load(f)
    traffic = load_json(base, "traffic", cell["traffic"] + ".json")
    limits = load_json(base, "limits", name + ".json")["limits"]
    return spec, cell, config, traffic, limits


def metrics_of(spec, cell_name, group):
    """The metrics of ``group`` that this cell reports."""
    return [m for m in spec[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def require_chips(chips):
    """Refuse to run without the accelerator the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit("benchmark: JAX found no TPU (platform %r); a CPU "
                         "timing is never printed under a device metric's "
                         "name" % devices[0].platform)
    if len(devices) < chips:
        raise SystemExit("benchmark: the cell asks for %d chips, JAX has %d"
                         % (chips, len(devices)))
    return devices[:chips]


def memory_peak(devices):
    """Peak bytes on the fullest of the chips used.  The TPU runtime keeps
    an execution's temporaries in a reserved region that
    ``peak_bytes_in_use`` leaves out (PERF.md, Findings PR 23), so the
    peak is live buffers plus that region."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def main(argv=None, devices=None, spec_path=None, faults=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also write the loaded trace (json.gz) here")
    args = ap.parse_args(argv)

    spec, cell, config, traffic, limits = load_cell(args.workload, spec_path)
    if devices is None:                  # tests hand in CPU devices
        # the compile cache: where the machine says, else one fixed
        # directory in the checkout (the program's own default too)
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache"))
        devices = require_chips(int(cell["chips"]))
    from benchmark import compare, flops, trace_reduce
    from benchmark.meter import Meter
    peak = flops.peaks(devices[0].device_kind) if devices[0].platform \
        == "tpu" else None
    meter = Meter()

    job = importlib.import_module("benchmark.jobs." + traffic["job"]).Job(
        cell, config, traffic, limits, args.seed, meter)
    log("cell %s seed %d on %d x %s" % (cell["name"], args.seed,
                                        len(devices), devices[0].device_kind))
    job.faults.update(faults or {})
    job.setup()
    log("set-up built; fit() starts")
    trace_dir = os.path.join(job.work, "trace") if args.trace else None
    window = job.run(args.seconds, trace_dir)
    setup_s = window["t_start"] - T0
    setup_meter = window["meter_at_start"]
    peak_bytes = memory_peak(devices)
    log("window: %d steps in %.3fs, set-up %.1fs, peak %s bytes"
        % (window["steps"], window["seconds"], setup_s, peak_bytes))

    reduced = None
    if args.trace:
        structure = trace_reduce.load_xplane(
            trace_reduce.newest_xplane(trace_dir))
        if args.keep_trace:
            trace_reduce.save(structure, args.keep_trace)
        hlo, step_memory = job.step_program()
        log("step program by XLA's accounting: %r; memory_stats of device "
            "0: %r" % (step_memory, devices[0].memory_stats()))
        if args.keep_trace:
            with open(args.keep_trace + ".hlo.txt", "w") as f:
                f.write(hlo)
        reduced = trace_reduce.reduce(
            structure, len(devices), trace_reduce.instruction_scopes(hlo),
            host_marker=os.path.basename(sys.modules[
                type(job).__module__].__file__) + ":")
    job.release()
    correct, shown = job.compare()
    log("reference followed the check steps")

    facts = {"cell": cell, "config": config, "traffic": traffic,
             "job": job, "window": window, "setup_s": setup_s,
             "setup_meter": setup_meter, "trace": reduced, "peak": peak,
             "chips": len(devices),
             "forward_flops": job.ref.flops_per_item(job.model)}
    values = dict(window["end_to_end"], setup_s=setup_s)
    facts.update(values)
    out = {}
    if args.trace:
        for m in metrics_of(spec, cell["name"], "per_layer"):
            reader = importlib.import_module("benchmark.metrics." + m["name"])
            value = reader.read(facts)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(spec, cell["name"], "end_to_end"):
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    extra = {"steps_failed": window["failed"],
             "window_compiles": window["compile"]["backend_compiles"]}
    correct = bool(correct and window["failed"] == 0
                   and window["compile"]["backend_compiles"] == 0)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": window["steps"],
              "failed": window["failed"], "metrics": out, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = trace_reduce.breakdown(reduced)
    result["compared"] = dict(shown, **{k: {"value": v, "limit": 0}
                                        for k, v in extra.items()})
    compare.print_compared(shown, extra)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
