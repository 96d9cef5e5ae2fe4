"""The ``qwen3_next`` family through the harness, on the CPU at toy widths:
the whole of a run of the job kind ``train_lm`` (float32, minus the look
for a chip), the int8 control and the planted faults failing it, the
configuration file against the catalog's published keys, and the new
metric readers on hand-made facts and on a trace recorded on the chip."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

TOY = os.path.join(HERE, "data", "toy_spec_lm.json")
CELL = "qwen3_next_toy.train_toy_lm"
REAL = "qwen3_next_80b_a3b.train_8k"
RECORDED = os.path.join(HERE, "data",
                        "trace_qwen3_next_80b_a3b_train_8k.json.gz")

PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "moe_intermediate_size": 512, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "vocab_size": 151936}


def _last_line(capfd):
    out, err = capfd.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def test_cpu_rehearsal_prints_the_contracts_last_line(capfd):
    import jax

    from benchmark import run
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 12345),
                   "--seconds", "0.5", "--trace", "0"],
                  devices=jax.devices()[:1], spec_path=TOY)
    assert rc == 0
    line, err = _last_line(capfd)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_throughput", "setup_s"}
    for name, row in line["compared"].items():
        assert row["limit"] is None or row["value"] <= row["limit"], name
    assert "compared grad1_mid_diff" in err


def _unchanged_state(trainer):
    import jax
    import jax.numpy as jnp
    orig = trainer._step_fn

    def step(*args):
        kept = jax.tree.map(jnp.copy, tuple(args[:3]))
        return kept + tuple(orig(*args)[3:])
    trainer._step_fn = step


def _half_left_out(batch):
    import jax
    import jax.numpy as jnp
    for k, v in batch.staged.items():
        n = v.shape[0] // 2
        batch.staged[k] = jax.device_put(
            jnp.concatenate([v[:n]] * 2, axis=0), v.sharding)
    return batch


@pytest.mark.parametrize("fault", [{"trainer": _unchanged_state},
                                   {"batch": _half_left_out}],
                         ids=["unchanged_state", "half_left_out"])
def test_a_broken_timed_path_reads_not_correct(fault, capfd):
    import jax

    from benchmark import run
    rc = run.main(["--workload", CELL, "--seed", "99", "--seconds", "0.3",
                   "--trace", "0"], devices=jax.devices()[:1],
                  spec_path=TOY, faults=fault)
    assert rc == 0
    line, _ = _last_line(capfd)
    assert line["correct"] is False, line["compared"]
    assert [k for k, v in line["compared"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]]


def _toy_job(seed=11):
    from benchmark import run
    from benchmark.jobs.train_lm import Job
    _, cell, config, traffic, limits = run.load_cell(CELL, TOY)
    return Job(cell, config, traffic, limits, seed, None), limits


def _toy_batches(job, n=3):
    from benchmark.jobs.train_lm import zipf_tokens
    data, label = zipf_tokens(5, n * job.batch, job.model["seq_len"],
                              job.model["vocab_size"], 1.0)
    return [{"data": data[i * job.batch:(i + 1) * job.batch],
             "softmax_label": label[i * job.batch:(i + 1) * job.batch]}
            for i in range(n)]


def test_the_int8_control_and_the_tools_faults_fail_the_limits():
    """The reference put in the program's place: in float32 it passes
    itself; in int8, with half of each batch left out, and with its state
    handed back unchanged it fails — the three readings
    ``tools/limits.py`` takes, through the job's row-by-row follow."""
    from benchmark import compare
    from benchmark.reference import common
    from benchmark.tools.limits import half_left_out, state_unchanged
    job, limits = _toy_job()
    batches = _toy_batches(job)
    ref = job._follow("f32", batches)

    def judged(readings):
        return compare.judge(compare.training_gaps(
            common.differences(readings, ref), ref), limits)
    assert judged(job._follow("f32", batches))[0]
    gaps = {}
    for name, got in (
            ("int8", job.compare("int8", batches)),
            ("half", job.compare("bf16", half_left_out(batches))),
            ("frozen", job.compare("bf16", batches, state_unchanged))):
        ok, shown = judged(got)
        assert not ok, (name, shown)
        gaps[name] = shown
    assert gaps["frozen"]["grad1_mid_gap"]["value"] > 0.9
    bf16 = judged(job.compare("bf16", batches))[1]
    assert gaps["int8"]["grad1_mid_diff"]["value"] > \
        3 * bf16["grad1_mid_diff"]["value"]
    with pytest.raises(ValueError):
        job.compare("bf16", batches, lambda step: step)


def test_the_rows_follow_equals_the_harnesss_follow():
    """``train_lm``'s row-by-row follow gives ``common.follow``'s readings
    of the family's whole-batch ``loss_fn``."""
    import jax
    import numpy as np

    from benchmark import datagen
    from benchmark.reference import common
    job, _ = _toy_job(seed=7)
    batches = _toy_batches(job)
    mine = job._follow("f32", batches)
    params, aux = jax.jit(lambda k: job.ref.init(k, job.model))(
        datagen.jax_key(7, 3))
    theirs = common.follow(common.make_step(
        job.ref.loss_fn(job.model), job.opt, job.batch), params, aux, batches)
    np.testing.assert_allclose(mine["loss"], theirs["loss"], rtol=1e-6)
    for what in ("grad1", "grad1_raw", "change"):
        for k, v in theirs[what].items():
            assert abs(mine[what][k] - v) <= 1e-4 * v + 1e-9, (what, k)
    for what in ("grad1", "change"):
        for k, v in theirs["full"][what].items():
            np.testing.assert_allclose(mine["full"][what][k], v, rtol=2e-3,
                                       atol=1e-8)


def test_zipf_ids_cover_the_slice_and_label_the_next_token():
    import numpy as np

    from benchmark.jobs.train_lm import zipf_tokens
    data, label = zipf_tokens(2 ** 31 + 5, 16, 8192, 18992, 1.0)
    assert data.dtype == np.int32 == label.dtype
    assert data.shape == label.shape == (16, 8192)
    np.testing.assert_array_equal(data[:, 1:], label[:, :-1])
    assert data.min() == 0 and 18000 < data.max() <= 18991
    counts = np.bincount(data.ravel(), minlength=18992)
    share = counts[0] / data.size              # 1 / H(18992) = 0.0959
    assert 0.085 < share < 0.105
    assert counts[0] > 1.7 * counts[1] > 2.2 * counts[3]
    again, _ = zipf_tokens(2 ** 31 + 5, 16, 8192, 18992, 1.0)
    np.testing.assert_array_equal(data, again)


def test_configuration_keeps_every_published_width():
    from benchmark import run
    spec, cell, config, traffic, _ = run.load_cell(REAL)
    entry = [c for c in spec["configs"] if c["name"] == cell["config"]][0]
    reduced = set(entry["reduced"])
    assert reduced == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
        assert config["model"][key] == config[key], key
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["num_experts"] * 16 == config["model"][
        "num_routed_experts"] == PUBLISHED["num_experts"]
    assert config["num_hidden_layers"] == config["full_attention_interval"]
    kwargs = config["program"]["kwargs"]
    assert kwargs["num_experts"] == 512 and kwargs["num_experts_held"] == 32
    for key, value in kwargs.items():
        if key in PUBLISHED and key not in reduced:
            assert value == PUBLISHED[key], key
    assert cell["chips"] == 1 and traffic["job"] == "train_lm"
    assert traffic["batch_per_chip"] * config["model"]["seq_len"] == 16384


def test_the_cell_judges_the_worst_leaf_beside_the_median():
    """A fault confined to a few leaves (a router, ``A_log``, a norm's
    gamma) moves no median: the cell's worst-leaf numbers carry limits."""
    from benchmark import run
    limits = run.load_cell(REAL)[4]
    assert set(limits) == {"loss_gap", "grad1_mid_gap", "grad1_mid_diff",
                           "change_mid_gap", "change_mid_diff", "grad1_gap",
                           "change_gap"}
    assert all(limit is not None and limit > 0 for limit in limits.values())
    assert limits["grad1_gap"] > limits["grad1_mid_gap"]
    assert limits["change_gap"] > limits["change_mid_gap"]


def test_parameters_and_forward_flops_are_the_issues_arithmetic():
    from benchmark import run
    from benchmark.reference import qwen3_next as ref
    config = run.load_cell(REAL)[2]
    model = dict(config["model"], batch=2)
    total = 0
    by_kind = {"gdn": 0, "attn": 0, "moe": 0}
    for name, shape in ref.shapes(model)[0].items():
        n = 1
        for d in shape:
            n *= d
        total += n
        for kind in by_kind:
            if ("_%s_" % kind) in name:
                by_kind[kind] += n
    assert round(total / 1e6, 1) == 625.7
    assert round(by_kind["gdn"] / 3e6, 2) == 33.72
    assert round(by_kind["attn"] / 1e6, 2) == 27.27
    assert round(by_kind["moe"] / 4e6, 2) == 104.86
    flops = ref.flops_per_item(model)
    assert 440e6 < flops < 480e6                  # the issue's ~466 MFLOP
    work = ref.node_work(model, 2)
    assert [n["node"] for n in work["gdn"]] == ["l0_gdn", "l1_gdn", "l2_gdn"]
    assert [n["node"] for n in work["attn"]] == ["l3_attn"]
    assert len(work["moe"]) == 4
    more = ref.node_work(model, 2, pairs_here=4 * 40960)
    assert more["moe"][0]["fwd"][0] > work["moe"][0]["fwd"][0]
    staged = sum(n["fwd"][0] for kind in work.values() for n in kind)
    head = 2 * model["hidden_size"] * model["vocab_size"] * 16384
    assert abs(staged + head - flops * 16384) < 1e-6 * flops * 16384


# -- the new readers ----------------------------------------------------------

class _Job(object):
    batch = 2

    def __init__(self):
        from benchmark import run
        from benchmark.reference import qwen3_next
        self.ref = qwen3_next
        self.model = dict(run.load_cell(REAL)[2]["model"], batch=2)


def _facts(scopes, counters=()):
    from benchmark import flops
    return {"trace": {"scopes_s": scopes}, "chips": 1, "job": _Job(),
            "peak": flops.peaks("TPU v5 lite"),
            "window": {"traced_steps": 3, "t_start": 0.0, "seconds": 1.0,
                       "steps": len(counters)},
            "_counters": list(counters)}


@pytest.fixture
def counters(monkeypatch):
    """``step.counters`` records handed to the readers in place of the
    program's recorder."""
    from benchmark.metrics import host_turnaround_ms
    monkeypatch.setattr(
        host_turnaround_ms, "window_spans",
        lambda facts: [{"name": "step.counters", "ids": ids}
                       for ids in facts["_counters"]])
    from benchmark.metrics import moe_load_imbalance
    monkeypatch.setattr(moe_load_imbalance, "window_spans",
                        host_turnaround_ms.window_spans)


def test_roofline_readers_on_hand_made_scopes(counters):
    from benchmark import flops
    from benchmark.metrics import attn_roofline, gdn_roofline, moe_roofline
    job = _Job()
    peak = flops.peaks("TPU v5 lite")
    work = job.ref.node_work(job.model, 2)

    def least(node, part):
        return flops.least_seconds(node[part][0], node[part][1], peak)[0]
    gdn = work["gdn"][0]
    scopes = {"l0_gdn": 2 * 3 * least(gdn, "fwd"),
              "_backward_l0_gdn": 4 * 3 * least(gdn, "bwd")}
    got = gdn_roofline.read(_facts(scopes))
    want = 100 * (least(gdn, "fwd") + least(gdn, "bwd")) / (
        2 * least(gdn, "fwd") + 4 * least(gdn, "bwd"))
    assert abs(got - want) < 1e-9 and 25 < got < 50
    assert attn_roofline.read(_facts(scopes)) is None
    assert moe_roofline.read(_facts(scopes)) is None
    attn = work["attn"][0]
    assert abs(attn_roofline.read(_facts(
        {"l3_attn": 3 * least(attn, "fwd")})) - 100) < 1e-9
    # the expert layers: the grouped products' own scope counts as spent,
    # and the work follows the pairs the steps really had
    moe = work["moe"][1]
    scopes = {"l1_moe": 3 * least(moe, "fwd"),
              "ragged-dot-none": 3 * least(moe, "fwd")}
    assert abs(moe_roofline.read(_facts(scopes)) - 50) < 1e-9
    heavy = [{"moe.assignments_here": 4 * 81920.0, "moe.load_max": 9.0,
              "moe.load_mean": 3.0}]
    assert moe_roofline.read(_facts(scopes, heavy)) > 50
    assert moe_roofline.read({"trace": None, "window": {"traced_steps": 0},
                              "peak": peak}) is None


def test_moe_load_imbalance_is_the_ratio_of_the_windows_sums(counters):
    from benchmark.metrics import moe_load_imbalance
    steps = [{"moe.load_max": 900.0, "moe.load_mean": 300.0},
             {"moe.load_max": 500.0, "moe.load_mean": 400.0}]
    assert moe_load_imbalance.read(_facts({}, steps)) == 2.0
    assert moe_load_imbalance.read(_facts({}, [])) is None
    assert moe_load_imbalance.read(_facts({}, [{"other": 1.0}])) is None


def test_readers_find_nothing_on_a_program_without_the_counters():
    """The parent commit's program keeps no ``step.counters`` records and
    names no such scopes: every new reader returns None and does not
    raise."""
    from benchmark.metrics import (attn_roofline, gdn_roofline,
                                   moe_load_imbalance, moe_roofline)
    facts = _facts({"conv0": 1.0, "_backward_conv0": 2.0})
    facts["window"]["t_start"] = 1e12             # no span starts there
    for reader in (gdn_roofline, attn_roofline, moe_roofline,
                   moe_load_imbalance):
        assert reader.read(facts) is None


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no trace recorded on the chip in the tree")
def test_readers_on_a_trace_recorded_on_the_chip(counters):
    """A step of the real cell cut from this PR's traced run: the stages'
    scopes are there under their graph names, forward and backward, and
    each share lies in (0, 100]."""
    from benchmark import trace_reduce as T
    from benchmark.metrics import attn_roofline, gdn_roofline, moe_roofline
    structure = T.load(RECORDED)
    reduced = T.reduce(structure, 1, structure.get("paths"))
    scopes = reduced["scopes_s"]
    for name in ("l0_gdn", "_backward_l0_gdn", "l3_attn",
                 "_backward_l3_attn", "l2_moe", "_backward_l2_moe"):
        assert scopes.get(name, 0) > 0, sorted(scopes)[:40]
    facts = _facts(scopes)
    facts["window"]["traced_steps"] = 1
    for reader in (gdn_roofline, attn_roofline, moe_roofline):
        share = reader.read(facts)
        assert 0 < share <= 100, (reader.__name__, share)
