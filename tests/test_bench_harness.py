"""bench.py harness invariants (ROADMAP item 5): per-metric timeout
isolation — one metric hitting its budget costs THAT metric a partial
artifact entry, never the run — and the regression gate that compares a
fresh artifact against the most recent ``BENCH_*.json``.

The isolation regression being pinned: ``subprocess.run(timeout=)``
kills only the direct child; a grandchild (XLA compile worker, decode
pool) holding the inherited stdout pipe then blocks the post-kill
``communicate()`` indefinitely — the BENCH_r05 failure, where one 480s
``inception-bn`` kill turned into rc=1 with no artifact at all.
``_collect`` now runs each metric in its own session and SIGKILLs the
whole process group.
"""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402

pytestmark = pytest.mark.serve


# ---------------------------------------------------------------------------
# per-metric timeout isolation
# ---------------------------------------------------------------------------

def test_collect_timeout_returns_partial_record_fast():
    """A metric that hangs WITH a pipe-holding grandchild (the r05
    shape) must come back as a status record within ~the budget — not
    block until the grandchild's natural exit (600s), not raise."""
    t0 = time.monotonic()
    out = bench._collect("_hang-grandchild", timeout=3)
    elapsed = time.monotonic() - t0
    assert out == {"_hang-grandchild": {"status": "timeout",
                                        "timeout_s": 3}}
    assert elapsed < 25, ("timeout isolation took %.1fs — the group "
                          "kill regressed" % elapsed)


def test_collect_extra_env_overlays_child_env(monkeypatch):
    """``extra_env`` overlays the child's environment — how main()
    points both compile-probe runs at ONE fixed cache directory."""
    import subprocess

    seen = {}

    class _Proc:
        pid = 0

        def communicate(self, timeout=None):
            return "", ""

        def poll(self):
            return 0

        returncode = 1

    def fake_popen(argv, env=None, **kw):
        seen.update(env or {})
        return _Proc()

    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    bench._collect("compile-probe", timeout=5,
                   extra_env={"JAX_COMPILATION_CACHE_DIR": "/x/cache"})
    assert seen["JAX_COMPILATION_CACHE_DIR"] == "/x/cache"
    assert seen["BENCH_MODE"] == "compile-probe"
    # the cache directory the full round hands its probes never moves
    import inspect
    src = inspect.getsource(bench.main)
    assert "mkdtemp" not in src and '".jax_cache"' in src


def test_collect_failed_mode_returns_status_record():
    """A metric whose subprocess dies (unknown mode -> no BENCH_PART
    line) is recorded as failed, not silently dropped."""
    out = bench._collect("_no-such-mode", timeout=120)
    assert out["_no-such-mode"]["status"] == "failed"


def test_timeout_records_land_in_incomplete_not_in_metrics():
    """main() moves status records aside so numeric consumers never see
    them — mirrored here on the exact dict shape _collect returns."""
    parts = {"compute": 100.0,
             "inception-bn": {"status": "timeout", "timeout_s": 480}}
    statuses = {k: v for k, v in parts.items()
                if isinstance(v, dict) and v.get("status")}
    assert set(statuses) == {"inception-bn"}


# ---------------------------------------------------------------------------
# the regression gate
# ---------------------------------------------------------------------------

def _write(path, payload):
    with open(path, "w") as f:
        json.dump(payload, f)
    return str(path)


BASE = {"value": 1000.0, "compute_img_s": 2000.0,
        "inception_bn_img_s": 800.0, "lstm_tok_s": 2.0e6,
        "serve_mlp_c8_qps": 900.0, "pipeline_note": "prose ignored"}


def test_gate_passes_within_tolerance(tmp_path):
    new = dict(BASE, value=950.0)          # -5%: inside the 10% budget
    rep = bench.gate(_write(tmp_path / "new.json", new),
                     against=_write(tmp_path / "old.json", BASE))
    assert rep["pass"], rep
    assert "value" in rep["checked"]


def test_gate_fails_on_drop_beyond_tolerance(tmp_path):
    new = dict(BASE, inception_bn_img_s=700.0)   # -12.5%
    rep = bench.gate(_write(tmp_path / "new.json", new),
                     against=_write(tmp_path / "old.json", BASE))
    assert not rep["pass"]
    (reg,) = rep["regressions"]
    assert reg["key"] == "inception_bn_img_s"
    assert reg["drop"] == pytest.approx(0.125, abs=0.01)


def test_gate_flags_missing_metric_as_regression(tmp_path):
    """The r05 scenario through the gate: the timed-out model's key is
    absent from the (partial) artifact — that IS a failure signal."""
    new = {k: v for k, v in BASE.items() if k != "inception_bn_img_s"}
    new["incomplete"] = {"inception-bn": {"status": "timeout",
                                          "timeout_s": 480}}
    rep = bench.gate(_write(tmp_path / "new.json", new),
                     against=_write(tmp_path / "old.json", BASE))
    assert not rep["pass"]
    (reg,) = rep["regressions"]
    assert reg["key"] == "inception_bn_img_s"
    assert reg["status"] == "missing"
    assert rep["incomplete_modes"] == ["inception-bn"]


def test_gate_serve_prefix_keys_are_guarded(tmp_path):
    new = dict(BASE, serve_mlp_c8_qps=700.0)     # -22%
    rep = bench.gate(_write(tmp_path / "new.json", new),
                     against=_write(tmp_path / "old.json", BASE))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "serve_mlp_c8_qps"


def test_gate_unwraps_driver_artifacts_and_skips_unusable(tmp_path):
    """Baselines come as the driver's {n, cmd, rc, parsed, tail}
    wrapper; a wrapper with parsed=null (the r05 rc=1 file) must be
    skipped in favor of the previous usable round."""
    _write(tmp_path / "BENCH_r04.json",
           {"n": 4, "cmd": "python bench.py", "rc": 0, "tail": "",
            "parsed": BASE})
    _write(tmp_path / "BENCH_r05.json",
           {"n": 5, "cmd": "python bench.py", "rc": 1,
            "tail": "Traceback...", "parsed": None})
    found = bench._latest_artifact(str(tmp_path))
    assert found is not None
    n, path, payload = found
    assert n == 4 and payload == BASE


def test_gate_no_baseline_found_in_empty_dir(tmp_path):
    """A repo with no prior BENCH_*.json has nothing to gate against
    (gate() then passes with a note rather than blocking the first
    run); the discovery itself must return None, not crash."""
    assert bench._latest_artifact(str(tmp_path)) is None


def test_gate_cli_exit_codes(tmp_path):
    old = _write(tmp_path / "old.json", BASE)
    good = _write(tmp_path / "good.json", dict(BASE))
    bad = _write(tmp_path / "bad.json", dict(BASE, value=500.0))
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--gate", good,
         "--against", old], capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    assert json.loads(res.stdout)["pass"] is True
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--gate", bad,
         "--against", old], capture_output=True, text=True, timeout=120)
    assert res.returncode == 1
    report = json.loads(res.stdout)
    assert report["regressions"][0]["key"] == "value"


def test_gate_custom_tolerance(tmp_path):
    old = _write(tmp_path / "old.json", BASE)
    new = _write(tmp_path / "new.json", dict(BASE, value=800.0))  # -20%
    assert not bench.gate(new, against=old, tolerance=0.10)["pass"]
    assert bench.gate(new, against=old, tolerance=0.25)["pass"]


def test_gate_accepts_result_dict_payload(tmp_path):
    """main()'s self-gate passes its own in-memory result instead of a
    path; behavior must match the file route."""
    rep = bench.gate(dict(BASE, value=500.0),
                     against=_write(tmp_path / "old.json", BASE))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "value"
    rep = bench.gate(dict(BASE), against=_write(tmp_path / "o2.json", BASE))
    assert rep["pass"]


def test_gate_data_service_keys_are_guarded(tmp_path):
    base = dict(BASE, data_service_img_s=6000.0,
                data_service_scaling_x=1.8)
    new = dict(base, data_service_img_s=4000.0)   # -33%
    rep = bench.gate(_write(tmp_path / "new.json", new),
                     against=_write(tmp_path / "old.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "data_service_img_s"


def test_gate_keys_cover_model_and_roofline_metrics():
    """Satellite: model-level throughput (lstm_tok_s,
    inception_bn_img_s) and the per-op roofline speedups are guarded —
    a regression in any of them must block like everything else."""
    assert "lstm_tok_s" in bench.GATE_KEYS
    assert "inception_bn_img_s" in bench.GATE_KEYS
    assert "roofline_*_speedup" in bench.GATE_KEYS


def test_gate_roofline_prefix_keys_are_guarded(tmp_path):
    base = dict(BASE, roofline_lstm_cell_speedup=4.0,
                roofline_bn_act_speedup=1.3)
    new = dict(base, roofline_lstm_cell_speedup=2.0)      # -50%
    rep = bench.gate(_write(tmp_path / "new.json", new),
                     against=_write(tmp_path / "old.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "roofline_lstm_cell_speedup"
    # a VANISHED roofline key (kernel dropped from the bench) also blocks
    gone = {k: v for k, v in base.items()
            if k != "roofline_bn_act_speedup"}
    rep = bench.gate(_write(tmp_path / "n2.json", gone),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "roofline_bn_act_speedup"


def test_roofline_bench_small_preset_proves_wins():
    """The roofline mode's self-proof on the small preset: every fused
    kernel (and every mxfuse pass) reports fused/unfused timings, a
    roofline bound with its binding side, and beats its unfused
    composition (the win each kernel must prove in the artifact)."""
    # best of 3 trials: one trial's timing of a 50 us call can read ten
    # times high when the whole suite loads the machine
    out = bench._roofline_bench(preset="small", trials=3)
    for op in ("bn_act", "lstm_cell", "flash_attention",
               "eltwise_chain", "concat_fuse", "pool_act"):
        assert out["roofline_%s_fused_us" % op] > 0
        assert out["roofline_%s_unfused_us" % op] > 0
        assert out["roofline_%s_speedup" % op] > 0
        assert out["roofline_%s_bound" % op] in ("memory", "compute")
        assert out["roofline_%s_bound_us" % op] > 0
        assert isinstance(out["roofline_%s_win" % op], bool)
    assert out["roofline_peak_gflops"] > 0
    assert out["roofline_mem_gbs"] > 0
    # the LSTM cell is the dispatch-bound poster child: the fused pass
    # must actually beat the op-by-op chain, not just tie it
    assert out["roofline_lstm_cell_speedup"] > 1.0
    assert out["roofline_lstm_cell_win"] is True
    # the mxfuse whole-model stanza ships its keys even on the small
    # (trimmed-model) preset, plus the infer_trace trace-time proof
    assert out["roofline_inception_fwd_on_img_s"] > 0
    assert out["roofline_inception_fwd_off_img_s"] > 0
    assert out["roofline_inception_fwd_x"] > 0
    assert isinstance(out["roofline_inception_fwd_win"], bool)
    assert out["roofline_infer_trace_x"] > 0


def test_gate_keys_cover_mxfuse_metrics(tmp_path):
    """Satellite (ISSUE 15): the plan-optimizer headline keys are
    gate-guarded — the whole-model on/off ratio, the trace-time
    ratio, the per-pass speedups (via the roofline_*_speedup prefix)
    and the inception-vs-resnet50 gap fraction all block on a drop OR
    a vanish."""
    for key in ("roofline_inception_fwd_x", "roofline_infer_trace_x",
                "inception_gap_frac"):
        assert key in bench.GATE_KEYS
    base = dict(BASE, roofline_inception_fwd_x=1.25,
                roofline_concat_fuse_speedup=1.3,
                inception_gap_frac=0.55)
    # drop blocks
    rep = bench.gate(_write(tmp_path / "n1.json",
                            dict(base, inception_gap_frac=0.4)),
                     against=_write(tmp_path / "o1.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "inception_gap_frac"
    rep = bench.gate(_write(tmp_path / "n2.json",
                            dict(base, roofline_inception_fwd_x=1.0)),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    # vanish blocks
    gone = {k: v for k, v in base.items()
            if k != "roofline_concat_fuse_speedup"}
    rep = bench.gate(_write(tmp_path / "n3.json", gone),
                     against=_write(tmp_path / "o3.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == \
        "roofline_concat_fuse_speedup"


def test_gate_device_tier_change_skips_only_tier_keys(tmp_path):
    """The device-tier rule (the r04→r06 TPU→CPU transition):
    accelerator-tier throughputs are compared only within one
    ``device_kind``; a tier change records the skip LOUDLY and every
    other key still gates — so the rule can neither mask nor fake a
    regression within a tier."""
    base = dict(BASE, device_kind="TPU v4",
                data_service_img_s=6000.0)
    # same tier: a compute drop still blocks
    rep = bench.gate(
        _write(tmp_path / "n0.json", dict(base, compute_img_s=500.0)),
        against=_write(tmp_path / "o0.json", base))
    assert not rep["pass"]
    # tier change: device-tier keys are skipped (and listed), host
    # keys still gate
    cpu = dict(base, device_kind="cpu", value=10.0, compute_img_s=20.0,
               inception_bn_img_s=12.0, resnet152_img_s=8.0)
    rep = bench.gate(_write(tmp_path / "n1.json", cpu),
                     against=_write(tmp_path / "o1.json", base))
    assert rep["pass"], rep
    skipped = rep["skipped_device_tier_change"]
    assert set(skipped["keys"]) >= {"value", "compute_img_s",
                                    "inception_bn_img_s"}
    assert skipped["baseline_device"] == "TPU v4"
    assert skipped["new_device"] == "cpu"
    # a HOST-side drop on a tier change still blocks
    rep = bench.gate(
        _write(tmp_path / "n2.json",
               dict(cpu, data_service_img_s=3000.0)),
        against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "data_service_img_s"
    # a baseline with NO recorded device_kind (the pre-r06 artifacts)
    # vs a recording one is a tier change too
    legacy = {k: v for k, v in base.items() if k != "device_kind"}
    rep = bench.gate(_write(tmp_path / "n3.json", cpu),
                     against=_write(tmp_path / "o3.json", legacy))
    assert rep["pass"], rep
    assert "skipped_device_tier_change" in rep


def test_gate_skips_scaling_shape_on_1core_hosts(tmp_path):
    """A 1-core host's scaling rows are flat BY CONSTRUCTION: the
    matching note (on either side) exempts the scaling-SHAPE keys, so a
    1-core CI box can neither mask nor fake a scaling regression — but
    the absolute-throughput keys still gate."""
    base = dict(BASE, data_service_img_s=6000.0,
                data_service_scaling_x=1.8,
                pipeline_decode_scaling_x=1.7)
    flat = dict(base, data_service_scaling_x=1.0,
                pipeline_decode_scaling_x=1.0,
                data_service_scaling_note="flat_by_construction_1core",
                decode_scaling_note="flat_by_construction_1core")
    rep = bench.gate(_write(tmp_path / "new.json", flat),
                     against=_write(tmp_path / "old.json", base))
    assert rep["pass"], rep
    assert set(rep["skipped_flat_by_construction"]) == {
        "data_service_scaling_x", "pipeline_decode_scaling_x"}
    # note on the BASELINE side exempts too (flat baseline, multicore new)
    rep = bench.gate(_write(tmp_path / "n2.json",
                            dict(base, data_service_scaling_x=0.9)),
                     against=_write(tmp_path / "o2.json", flat))
    assert rep["pass"], rep
    # without the note a scaling-shape collapse IS a regression
    rep = bench.gate(_write(tmp_path / "n3.json",
                            dict(base, data_service_scaling_x=1.0)),
                     against=_write(tmp_path / "o3.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "data_service_scaling_x"


def test_gate_keys_cover_zero3_metrics(tmp_path):
    """Satellite: the zero3 sweep's throughput, residency leverage and
    wide-model memory leverage are gate-guarded — a drop OR a vanished
    key blocks the run like everything else."""
    for key in ("zero3_steps_s", "zero3_param_shard_x",
                "zero3_wide_mem_x"):
        assert key in bench.GATE_KEYS
    base = dict(BASE, zero3_steps_s=250.0, zero3_param_shard_x=7.8,
                zero3_wide_mem_x=1.7)
    # residency leverage collapsing to ~1 (sharding silently broken)
    new = dict(base, zero3_param_shard_x=1.0)
    rep = bench.gate(_write(tmp_path / "new.json", new),
                     against=_write(tmp_path / "old.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "zero3_param_shard_x"
    # a vanished zero3 key blocks too
    gone = {k: v for k, v in base.items() if k != "zero3_steps_s"}
    rep = bench.gate(_write(tmp_path / "n2.json", gone),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "zero3_steps_s"


def test_zero3_bench_small_preset_self_proof():
    """The zero3 mode's self-proof on the small preset: ~1/world
    per-device parameter residency, a PROVEN collective schedule
    (reduce-scatter present, param-scale gathers — trainer.analyze
    inside the bench), and throughput keys for all three grad_sync
    modes so the gate can watch them round over round."""
    import jax
    out = bench._zero3_bench(preset="small")
    world = len(jax.devices())
    assert out["zero3_world"] == world
    for key in ("zero3_steps_s", "zero3_zero_steps_s",
                "zero3_allreduce_steps_s", "zero3_wide_steps_s"):
        assert out[key] > 0, key
    assert out["zero3_frac_ok"] is True
    assert out["zero3_param_bytes_frac"] <= 1.0 / world + 0.05
    assert out["zero3_param_shard_x"] > world * 0.7
    assert out["zero3_tier"] == "manual"
    assert out["zero3_schedule_ok"] is True
    assert out["zero3_collectives"]["reduce-scatter"]["count"] >= 1
    # wide model: sharded residency exact, compiled peak memory below
    # the replicated baseline (memory_analysis-backed when available)
    assert out["zero3_wide_param_bytes_frac"] <= 1.0 / world + 0.05
    if "zero3_wide_mem_x" in out:
        assert out["zero3_wide_mem_x"] > 1.0


def test_gate_skips_zero3_mem_key_when_unmeasurable(tmp_path):
    """zero3_wide_mem_x needs compiled.memory_analysis(); a backend
    without it marks the key structurally unmeasurable
    (zero3_mem_note=unavailable_*) and the gate SKIPS the comparison
    instead of reporting a vanished metric — but an artifact that
    simply DROPS the key with no note still blocks."""
    base = dict(BASE, zero3_wide_mem_x=1.7)
    gone = {k: v for k, v in base.items() if k != "zero3_wide_mem_x"}
    noted = dict(gone, zero3_mem_note="unavailable_memory_analysis")
    rep = bench.gate(_write(tmp_path / "noted.json", noted),
                     against=_write(tmp_path / "old.json", base))
    assert rep["pass"], rep
    assert "zero3_wide_mem_x" in rep.get(
        "skipped_flat_by_construction", [])
    rep = bench.gate(_write(tmp_path / "gone.json", gone),
                     against=_write(tmp_path / "old2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "zero3_wide_mem_x"


def test_gate_keys_cover_fleet_metrics(tmp_path):
    """PR-11 satellite: the fleet's scale-out ratio, AOT warm-start
    leverage and route efficiency are gate-guarded (all three are
    higher-is-better ratios, per the gate's contract) — a drop OR a
    vanished key blocks the run."""
    for key in ("fleet_qps_x", "fleet_warm_start_x", "fleet_route_eff"):
        assert key in bench.GATE_KEYS
    base = dict(BASE, fleet_qps_x=1.8, fleet_warm_start_x=8.3,
                fleet_route_eff=0.91)
    # warm-start leverage collapsing (the AOT store silently broken)
    new = dict(base, fleet_warm_start_x=1.1)
    rep = bench.gate(_write(tmp_path / "new.json", new),
                     against=_write(tmp_path / "old.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "fleet_warm_start_x"
    # a bloated router hop drops the efficiency ratio
    new = dict(base, fleet_route_eff=0.5)
    rep = bench.gate(_write(tmp_path / "n2.json", new),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "fleet_route_eff"
    # a vanished fleet key blocks too
    gone = {k: v for k, v in base.items() if k != "fleet_qps_x"}
    rep = bench.gate(_write(tmp_path / "n3.json", gone),
                     against=_write(tmp_path / "o3.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "fleet_qps_x"


def test_gate_skips_fleet_scaling_on_small_hosts(tmp_path):
    """fleet_qps_x needs clients + router + 2 replicas running
    concurrently; a host without the cores emits fleet_scaling_note
    and the gate skips the SHAPE key (PR-7 SCALING_SHAPE_KEYS
    machinery) — a note-less collapse still blocks."""
    assert bench.SCALING_SHAPE_KEYS["fleet_qps_x"] == \
        "fleet_scaling_note"
    base = dict(BASE, fleet_qps_x=1.8, fleet_warm_start_x=8.3)
    flat = dict(base, fleet_qps_x=1.0,
                fleet_scaling_note="flat_by_construction_2core")
    rep = bench.gate(_write(tmp_path / "new.json", flat),
                     against=_write(tmp_path / "old.json", base))
    assert rep["pass"], rep
    assert "fleet_qps_x" in rep["skipped_flat_by_construction"]
    # the absolute warm-start key still gates on a noted host
    worse = dict(flat, fleet_warm_start_x=2.0)
    rep = bench.gate(_write(tmp_path / "n2.json", worse),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "fleet_warm_start_x"
    # no note -> a scaling collapse IS a regression
    rep = bench.gate(_write(tmp_path / "n3.json",
                            dict(base, fleet_qps_x=1.0)),
                     against=_write(tmp_path / "o3.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "fleet_qps_x"


def test_gate_keys_cover_data_net_metrics(tmp_path):
    """PR-12 satellite: the network tier's absolute throughput and
    scaling shape are gate-guarded — a drop OR a vanished key blocks
    the run like everything else."""
    for key in ("data_net_img_s", "data_net_scaling_x"):
        assert key in bench.GATE_KEYS
    base = dict(BASE, data_net_img_s=6400.0, data_net_scaling_x=2.5)
    new = dict(base, data_net_img_s=4000.0)        # -37%
    rep = bench.gate(_write(tmp_path / "new.json", new),
                     against=_write(tmp_path / "old.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "data_net_img_s"
    # a vanished key blocks too
    gone = {k: v for k, v in base.items() if k != "data_net_scaling_x"}
    rep = bench.gate(_write(tmp_path / "n2.json", gone),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "data_net_scaling_x"


def test_gate_skips_data_net_scaling_on_small_hosts(tmp_path):
    """data_net_scaling_x needs the consumer + S servers + S decode
    workers running concurrently; a <4-core host emits
    data_net_scaling_note and the gate skips the SHAPE key (the PR-7
    SCALING_SHAPE_KEYS machinery) — absolute throughput still gates,
    and a note-less collapse still blocks."""
    assert bench.SCALING_SHAPE_KEYS["data_net_scaling_x"] == \
        "data_net_scaling_note"
    base = dict(BASE, data_net_img_s=6400.0, data_net_scaling_x=2.5)
    flat = dict(base, data_net_scaling_x=1.0,
                data_net_scaling_note="flat_by_construction_2core")
    rep = bench.gate(_write(tmp_path / "new.json", flat),
                     against=_write(tmp_path / "old.json", base))
    assert rep["pass"], rep
    assert "data_net_scaling_x" in rep["skipped_flat_by_construction"]
    worse = dict(flat, data_net_img_s=3000.0)      # absolute key gates
    rep = bench.gate(_write(tmp_path / "n2.json", worse),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "data_net_img_s"
    rep = bench.gate(_write(tmp_path / "n3.json",
                            dict(base, data_net_scaling_x=1.0)),
                     against=_write(tmp_path / "o3.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "data_net_scaling_x"


def test_data_net_mode_is_known_and_aliases():
    assert "data-net" in bench.KNOWN_MODES
    assert "data_net" in bench.KNOWN_MODES


def test_fleet_mode_is_known_and_in_the_pipeline_set():
    assert "fleet" in bench.KNOWN_MODES


# ---------------------------------------------------------------------------
# overdrive mode (ISSUE 17: the sharded front end)
# ---------------------------------------------------------------------------

def test_gate_keys_cover_overdrive_metrics(tmp_path):
    """The sharded front end's contracts are gate-guarded: absolute
    dispatch QPS and the worker-scaling ratio (higher is better), the
    quiet-tenant p99 under flood (a LATENCY — guarded through
    LOWER_IS_BETTER_KEYS, so a RISE blocks and an improvement passes)
    and the autoscale drop-free flag.  A vanished key blocks like
    everywhere else."""
    for key in ("overdrive_qps", "overdrive_qps_x",
                "overdrive_tenant_p99_ms", "overdrive_drop_free"):
        assert key in bench.GATE_KEYS
    assert "overdrive_tenant_p99_ms" in bench.LOWER_IS_BETTER_KEYS
    base = dict(BASE, overdrive_qps=3200.0, overdrive_qps_x=4.3,
                overdrive_tenant_p99_ms=18.0, overdrive_drop_free=1.0)
    # quiet-tenant p99 BLOWING UP (WFQ isolation broken) blocks...
    worse = dict(base, overdrive_tenant_p99_ms=90.0)
    rep = bench.gate(_write(tmp_path / "worse.json", worse),
                     against=_write(tmp_path / "old.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "overdrive_tenant_p99_ms"
    assert "rise" in rep["regressions"][0]
    # ...while an improvement passes (the lower-is-better contract)
    better = dict(base, overdrive_tenant_p99_ms=5.0)
    rep = bench.gate(_write(tmp_path / "better.json", better),
                     against=_write(tmp_path / "o2.json", base))
    assert rep["pass"], rep
    # a dropped request during the autoscale round trip blocks
    dropped = dict(base, overdrive_drop_free=0.0)
    rep = bench.gate(_write(tmp_path / "drop.json", dropped),
                     against=_write(tmp_path / "o3.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "overdrive_drop_free"
    # a vanished overdrive key IS a regression (the mode timing out
    # cannot silently un-gate the front end)
    gone = {k: v for k, v in base.items() if k != "overdrive_qps"}
    rep = bench.gate(_write(tmp_path / "gone.json", gone),
                     against=_write(tmp_path / "o4.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "overdrive_qps"


def test_gate_skips_overdrive_scaling_on_small_hosts(tmp_path):
    """overdrive_qps_x needs clients + 4 reuseport workers + replica
    running concurrently; a host without the cores emits
    overdrive_note and the gate skips the SHAPE key only — the
    absolute overdrive_qps still gates, and a note-less collapse still
    blocks (the SCALING_SHAPE_KEYS honesty machinery)."""
    assert bench.SCALING_SHAPE_KEYS["overdrive_qps_x"] == \
        "overdrive_note"
    base = dict(BASE, overdrive_qps=3200.0, overdrive_qps_x=4.3,
                overdrive_drop_free=1.0)
    flat = dict(base, overdrive_qps_x=1.0,
                overdrive_note="flat_by_construction_1core")
    rep = bench.gate(_write(tmp_path / "new.json", flat),
                     against=_write(tmp_path / "old.json", base))
    assert rep["pass"], rep
    assert "overdrive_qps_x" in rep["skipped_flat_by_construction"]
    # the absolute QPS key still gates on a noted host
    worse = dict(flat, overdrive_qps=1000.0)
    rep = bench.gate(_write(tmp_path / "n2.json", worse),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "overdrive_qps"
    # no note -> a scaling collapse IS a regression
    rep = bench.gate(_write(tmp_path / "n3.json",
                            dict(base, overdrive_qps_x=1.0)),
                     against=_write(tmp_path / "o3.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "overdrive_qps_x"


def test_overdrive_mode_is_known_and_in_the_pipeline_set():
    assert "overdrive" in bench.KNOWN_MODES


# ---------------------------------------------------------------------------
# hotswap mode (ISSUE 13 satellite)
# ---------------------------------------------------------------------------

def test_gate_keys_cover_hotswap_metrics(tmp_path):
    """The train-to-serve seam's two contracts are gate-guarded: the
    drop-free flag (1.0 -> 0.0 = requests died during a swap) and the
    dispatch-boundary pause (a LATENCY — guarded through
    LOWER_IS_BETTER_KEYS, so a RISE blocks and an improvement passes).
    A vanished key blocks like everywhere else."""
    for key in ("hotswap_drop_free", "hotswap_swap_ms"):
        assert key in bench.GATE_KEYS
    assert "hotswap_swap_ms" in bench.LOWER_IS_BETTER_KEYS
    base = dict(BASE, hotswap_drop_free=1.0, hotswap_swap_ms=6.5)
    # dropped requests during a swap -> the flag collapses -> blocked
    new = dict(base, hotswap_drop_free=0.0)
    rep = bench.gate(_write(tmp_path / "new.json", new),
                     against=_write(tmp_path / "old.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "hotswap_drop_free"
    # a swap pause RISING past tolerance is the latency regression
    new = dict(base, hotswap_swap_ms=20.0)
    rep = bench.gate(_write(tmp_path / "n2.json", new),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    reg = rep["regressions"][0]
    assert reg["key"] == "hotswap_swap_ms" and "rise" in reg
    # ...and an IMPROVEMENT (lower pause) must pass — the raw
    # higher-is-better rule would have flagged exactly this
    new = dict(base, hotswap_swap_ms=2.0)
    rep = bench.gate(_write(tmp_path / "n3.json", new),
                     against=_write(tmp_path / "o3.json", base))
    assert rep["pass"], rep
    # a vanished key blocks too
    for gone_key in ("hotswap_drop_free", "hotswap_swap_ms"):
        gone = {k: v for k, v in base.items() if k != gone_key}
        rep = bench.gate(_write(tmp_path / "g.json", gone),
                         against=_write(tmp_path / "go.json", base))
        assert not rep["pass"]
        assert rep["regressions"][0]["key"] == gone_key


def test_hotswap_mode_is_known_and_in_the_pipeline_set():
    assert "hotswap" in bench.KNOWN_MODES
    # the full-run pipeline collects it (source-level pin, like the
    # data-net/fleet modes): a mode that silently leaves the pipeline
    # set stops minting its gate keys and the artifact goes blind
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert '_collect("hotswap")' in src


def test_gate_keys_cover_plan_metrics(tmp_path):
    """Satellite: mxplan's decision time and planned-grouping step
    time are gate-guarded as LOWER-is-better latencies — a RISE past
    tolerance blocks, an improvement passes, a vanished key blocks."""
    for key in ("plan_decide_ms", "plan_step_ms"):
        assert key in bench.GATE_KEYS
        assert key in bench.LOWER_IS_BETTER_KEYS
    base = dict(BASE, plan_decide_ms=1.2, plan_step_ms=30.0)
    # a 50% faster planner PASSES (higher-is-better logic would fail it)
    rep = bench.gate(_write(tmp_path / "n1.json",
                            dict(base, plan_decide_ms=0.6)),
                     against=_write(tmp_path / "o1.json", base))
    assert rep["pass"], rep
    # a 50% slower planned step BLOCKS
    rep = bench.gate(_write(tmp_path / "n2.json",
                            dict(base, plan_step_ms=45.0)),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "plan_step_ms"
    # a vanished plan key blocks too
    gone = {k: v for k, v in base.items() if k != "plan_decide_ms"}
    rep = bench.gate(_write(tmp_path / "n3.json", gone),
                     against=_write(tmp_path / "o3.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "plan_decide_ms"


def test_plan_mode_is_known_and_in_pipeline():
    assert "plan" in bench.KNOWN_MODES


def test_plan_bench_small_preset_self_proof():
    """The plan mode's self-proof on the small preset: the budget
    ladder walks allreduce -> zero -> zero3, an unfittable budget
    raises at planning time, the serialized plan round-trips to an
    identical digest, and the planned (auto) grouping is measured
    against the retired per-layer default with fewer collectives."""
    out = bench._plan_bench(preset="small")
    assert out["plan_budget_ladder_ok"] is True
    assert out["plan_budget_ladder"] == ["allreduce", "zero", "zero3"]
    assert out["plan_overflow_raises"] is True
    assert out["plan_roundtrip_ok"] is True
    assert out["plan_grad_sync"] == "zero3"
    assert out["plan_decide_ms"] > 0
    assert out["plan_step_ms"] > 0 and out["plan_manual_step_ms"] > 0
    # the planner's bucket merge really produced a different grouping
    assert out["plan_auto_groups"] < out["plan_manual_groups"]


# ---------------------------------------------------------------------------
# region mode (the composed region drill, ISSUE 16)
# ---------------------------------------------------------------------------

def test_gate_keys_cover_region_metrics(tmp_path):
    """The composed drill's three contracts are gate-guarded: the
    storm-grade drop-free flag, the first-try goodput fraction under
    chaos, and the publish->served freshness (a LATENCY — guarded
    through LOWER_IS_BETTER_KEYS).  A vanished key blocks like
    everywhere else: a drill that stops minting a metric must block,
    not go quietly blind."""
    for key in ("region_drop_free", "region_goodput_chaos_frac",
                "region_freshness_ms"):
        assert key in bench.GATE_KEYS
    assert "region_freshness_ms" in bench.LOWER_IS_BETTER_KEYS
    base = dict(BASE, region_drop_free=1.0,
                region_goodput_chaos_frac=0.99,
                region_freshness_ms=250.0)
    # a dropped request during the storm collapses the flag -> blocked
    rep = bench.gate(_write(tmp_path / "n1.json",
                            dict(base, region_drop_free=0.0)),
                     against=_write(tmp_path / "o1.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "region_drop_free"
    # goodput sagging under chaos (more fail-once retries) blocks
    rep = bench.gate(_write(tmp_path / "n2.json",
                            dict(base, region_goodput_chaos_frac=0.5)),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "region_goodput_chaos_frac"
    # freshness RISING past tolerance blocks; an improvement passes
    rep = bench.gate(_write(tmp_path / "n3.json",
                            dict(base, region_freshness_ms=800.0)),
                     against=_write(tmp_path / "o3.json", base))
    assert not rep["pass"]
    reg = rep["regressions"][0]
    assert reg["key"] == "region_freshness_ms" and "rise" in reg
    rep = bench.gate(_write(tmp_path / "n4.json",
                            dict(base, region_freshness_ms=90.0)),
                     against=_write(tmp_path / "o4.json", base))
    assert rep["pass"], rep
    # a vanished region key blocks too
    for gone_key in ("region_drop_free", "region_goodput_chaos_frac",
                     "region_freshness_ms"):
        gone = {k: v for k, v in base.items() if k != gone_key}
        rep = bench.gate(_write(tmp_path / "g.json", gone),
                         against=_write(tmp_path / "go.json", base))
        assert not rep["pass"]
        assert rep["regressions"][0]["key"] == gone_key


def test_region_mode_is_known_and_in_the_pipeline_set():
    assert "region" in bench.KNOWN_MODES
    # source-level pin, like hotswap/fleet: a mode that silently
    # leaves the pipeline set stops minting its gate keys
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert '_collect("region"' in src


# ---------------------------------------------------------------------------
# ckpt mode (ISSUE 18: sharded-native checkpoints)
# ---------------------------------------------------------------------------

def test_gate_keys_cover_sharded_ckpt_metrics(tmp_path):
    """The sharded-checkpoint contract is gate-guarded through two
    LOWER-is-better keys: the sharded save's step-loop cost
    (ckpt_save_ms) and the peak-host fraction (ckpt_peak_host_frac —
    the whole point of the feature; it rises back toward 1.0 if a
    host-side gather sneaks into the save path).  A RISE past
    tolerance blocks, an improvement passes, a vanished key blocks."""
    for key in ("ckpt_save_ms", "ckpt_peak_host_frac"):
        assert key in bench.GATE_KEYS
        assert key in bench.LOWER_IS_BETTER_KEYS
    base = dict(BASE, ckpt_save_ms=40.0, ckpt_peak_host_frac=0.125)
    # peak host residency creeping back toward the full gather BLOCKS
    rep = bench.gate(_write(tmp_path / "n1.json",
                            dict(base, ckpt_peak_host_frac=1.0)),
                     against=_write(tmp_path / "o1.json", base))
    assert not rep["pass"]
    reg = rep["regressions"][0]
    assert reg["key"] == "ckpt_peak_host_frac" and "rise" in reg
    # a slower sharded save BLOCKS
    rep = bench.gate(_write(tmp_path / "n2.json",
                            dict(base, ckpt_save_ms=80.0)),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "ckpt_save_ms"
    # an IMPROVEMENT (smaller peak, faster save) must pass — the raw
    # higher-is-better rule would have flagged exactly this
    rep = bench.gate(_write(tmp_path / "n3.json",
                            dict(base, ckpt_save_ms=20.0,
                                 ckpt_peak_host_frac=0.0625)),
                     against=_write(tmp_path / "o3.json", base))
    assert rep["pass"], rep
    # a vanished key blocks too (the mode silently dying must not
    # look like "nothing regressed")
    for gone_key in ("ckpt_save_ms", "ckpt_peak_host_frac"):
        gone = {k: v for k, v in base.items() if k != gone_key}
        rep = bench.gate(_write(tmp_path / "g.json", gone),
                         against=_write(tmp_path / "go.json", base))
        assert not rep["pass"]
        assert rep["regressions"][0]["key"] == gone_key


def test_ckpt_mode_is_known_and_in_the_pipeline_set():
    assert "ckpt" in bench.KNOWN_MODES
    # source-level pin, like hotswap/fleet/region: a mode that silently
    # leaves the pipeline set stops minting its gate keys
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert '_collect("ckpt")' in src


def test_gate_keys_cover_lint_wall(tmp_path):
    """Satellite: the analyzer's own full-tree wall time is
    gate-guarded as a LOWER-is-better latency — a quadratic blow-up in
    a whole-repo lint pass blocks, a speed-up passes."""
    assert "lint_wall_ms" in bench.GATE_KEYS
    assert "lint_wall_ms" in bench.LOWER_IS_BETTER_KEYS
    base = dict(BASE, lint_wall_ms=4000.0)
    # 50% faster lint PASSES
    rep = bench.gate(_write(tmp_path / "n1.json",
                            dict(base, lint_wall_ms=2000.0)),
                     against=_write(tmp_path / "o1.json", base))
    assert rep["pass"], rep
    # 50% slower lint BLOCKS
    rep = bench.gate(_write(tmp_path / "n2.json",
                            dict(base, lint_wall_ms=6000.0)),
                     against=_write(tmp_path / "o2.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "lint_wall_ms"


# ---------------------------------------------------------------------------
# tail mode (ISSUE 20 satellite: hedged tail latency, measured)
# ---------------------------------------------------------------------------

def test_gate_keys_cover_tail_metrics(tmp_path):
    """The hedging claim is gate-guarded both ways: the hedged p99
    against a gray replica is a LOWER-is-better latency (a RISE past
    tolerance blocks, an improvement passes), and the drop-free flag
    collapses the moment hedging trades correctness for latency.  A
    vanished key blocks like everywhere else."""
    for key in ("tail_p99_ms", "tail_drop_free"):
        assert key in bench.GATE_KEYS
    assert "tail_p99_ms" in bench.LOWER_IS_BETTER_KEYS
    base = dict(BASE, tail_p99_ms=35.0, tail_drop_free=1.0)
    # hedged tail BLOWING UP (back toward the unhedged stall) blocks
    rep = bench.gate(_write(tmp_path / "n1.json",
                            dict(base, tail_p99_ms=250.0)),
                     against=_write(tmp_path / "o1.json", base))
    assert not rep["pass"]
    reg = rep["regressions"][0]
    assert reg["key"] == "tail_p99_ms" and "rise" in reg
    # a FASTER hedged tail passes — the higher-is-better rule would
    # have flagged exactly this improvement
    rep = bench.gate(_write(tmp_path / "n2.json",
                            dict(base, tail_p99_ms=20.0)),
                     against=_write(tmp_path / "o2.json", base))
    assert rep["pass"], rep
    # any non-200 under hedging chaos collapses the flag -> blocked
    rep = bench.gate(_write(tmp_path / "n3.json",
                            dict(base, tail_drop_free=0.0)),
                     against=_write(tmp_path / "o3.json", base))
    assert not rep["pass"]
    assert rep["regressions"][0]["key"] == "tail_drop_free"
    # a vanished key blocks too (the mode silently dying must not
    # look like "nothing regressed")
    for gone_key in ("tail_p99_ms", "tail_drop_free"):
        gone = {k: v for k, v in base.items() if k != gone_key}
        rep = bench.gate(_write(tmp_path / "g.json", gone),
                         against=_write(tmp_path / "go.json", base))
        assert not rep["pass"]
        assert rep["regressions"][0]["key"] == gone_key


def test_tail_mode_is_known_and_in_the_pipeline_set():
    assert "tail" in bench.KNOWN_MODES
    # source-level pin, like hotswap/fleet/ckpt: a mode that silently
    # leaves the pipeline set stops minting its gate keys
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert '_collect("tail"' in src
