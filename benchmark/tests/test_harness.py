"""The harness's own checks, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

Tier-1 (``pytest tests/``) does not collect this directory.
"""
import glob
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOY = os.path.join(HERE, "data", "toy_spec.json")
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
NUMBERS = ("loss_gap", "loss1_gap", "grad1_gap", "grad1_mid_gap",
           "grad1_diff", "grad1_mid_diff", "change_gap", "change_mid_gap",
           "change_diff", "change_mid_diff")


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_file_loads_and_cross_references_resolve():
    from benchmark import run
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    configs = {c["name"] for c in s["configs"]}
    for path in glob.glob(os.path.join(BENCH, "*", "*.json")):
        with open(path) as f:
            json.load(f)
    for cell in s["workloads"]:
        assert cell["config"] in configs
        _, _, config, traffic, limits = run.load_cell(cell["name"])
        importlib.import_module("benchmark.jobs." + traffic["job"])
        ref = importlib.import_module(
            "benchmark.reference." + config["reference"])
        for fn in ("init", "loss_fn", "to_program", "from_program",
                   "flops_per_item", "node_work"):
            assert callable(getattr(ref, fn)), (config["name"], fn)
        assert limits and set(limits) <= set(NUMBERS), limits
        assert any(v is not None for v in limits.values()), limits
        assert config["reduced"] == [c["reduced"] for c in s["configs"]
                                     if c["name"] == cell["config"]][0]
    used = {c["config"] for c in s["workloads"]}
    assert used == configs, "a configuration no cell uses"
    for m in s["per_layer"]:
        reader = importlib.import_module("benchmark.metrics." + m["name"])
        assert callable(reader.read)


def test_metrics_move_what_their_cells_report():
    s = spec()
    cells = {w["name"] for w in s["workloads"]}
    e2e = {m["name"]: m for m in s["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in s["per_layer"]:
        assert m["moves"] in e2e, m
        assert set(m["workloads"]) <= cells and m["workloads"], m
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= reporting, m
        assert "bound" not in m
        layers.add(m["layer"])
    for cell in cells:
        mine = [m for m in s["per_layer"] if cell in m["workloads"]]
        assert any("mfu" in m["name"] for m in mine), cell
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert ("**%s**" % layer) in perf, \
            "layer %r is not in PERF.md's list of layers" % layer


def test_names_and_units_use_the_allowed_characters():
    s = spec()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in s[group]]
        assert len(set(names)) == len(names)
        for x in s[group]:
            assert NAME.match(x["name"]), x["name"]
            if "unit" in x:
                assert UNIT.match(x["unit"]), x["unit"]
                assert x["better"] in ("lower", "higher")
            for key in ("why", "layer", "source"):
                if key in x:
                    assert 1 <= len(x[key]) <= 200 and "\n" not in x[key] \
                        and "\t" not in x[key], (x["name"], key)
    for w in s["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in s["workloads"]) <= max(
        1, len(s["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    for path in glob.glob(os.path.join(BENCH, "**", "*"), recursive=True):
        rel = os.path.relpath(path, ROOT)
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_run_refuses_a_machine_without_the_chip():
    s = spec()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable] + s["command"][1:] +
        ["--workload", s["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout
    assert "no TPU" in out.stderr


@pytest.mark.parametrize("cell", ["lstm_toy.train", "resnet_toy.fed",
                                  "resnet_toy.fed_dp4"])
def test_cpu_rehearsal_prints_the_contracts_last_line(cell, capfd):
    """The whole of a run at toy widths, float32, minus the look for a
    chip: the reference agrees with the program, and the last line holds
    exactly the contract's keys."""
    import jax

    from benchmark import run
    rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 12345),
                   "--seconds", "0.5", "--trace", "0"],
                  devices=jax.devices()[:4 if cell.endswith("dp4") else 1],
                  spec_path=TOY)
    assert rc == 0
    out, err = capfd.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) == KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_throughput", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for name in NUMBERS:
        row = line["compared"][name]
        assert row["limit"] is None or row["value"] <= row["limit"]
        assert ("compared %s" % name) in err
