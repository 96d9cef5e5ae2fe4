"""The ``afmoe`` family (Trinity-Mini) through the harness, on the CPU at
toy widths: a whole run of the job kind ``train_lm`` on the toy cell, the
int8 control failing it, ``row_loss`` tied to ``loss_fn``, the
configuration file against the catalog's published keys, the arithmetic
against hand counts, and the readers of ``swa_roofline`` and
``swa_steps_share`` on a hand-written trace and on what the op records."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

TOY = os.path.join(HERE, "data", "toy_spec_trinity.json")
CELL = "trinity_toy.train_toy_lm"
REAL = "trinity_mini.train_8k"

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl:
#: Trinity-Mini), every key
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


def _last_line(capfd):
    out, err = capfd.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def test_cpu_rehearsal_prints_the_contracts_last_line(capfd):
    import jax

    from benchmark import run
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 4321),
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


def test_the_int8_control_fails_the_limits_and_the_stand_in_passes():
    from benchmark import compare
    from benchmark.reference import common
    job, limits = _toy_job()
    batches = _toy_batches(job)
    ref = job._follow("f32", batches)

    def judged(readings):
        return compare.judge(compare.training_gaps(
            common.differences(readings, ref), ref), limits)
    assert judged(job._follow("f32", batches))[0]
    ok, shown = judged(job.compare("int8", batches))
    assert not ok, shown
    bf16 = judged(job.compare("bf16", batches))[1]
    assert shown["grad1_mid_diff"]["value"] > \
        3 * bf16["grad1_mid_diff"]["value"]


def test_row_loss_is_tied_to_loss_fn():
    """``train_lm``'s row-by-row follow gives ``common.follow``'s readings
    of the family's whole-batch ``loss_fn``; the blocked head of
    ``row_loss`` is ``logits`` under the plain cross-entropy."""
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
    # the output norms' weights start where the configuration says (1 here)
    import jax.numpy as jnp
    scaled = jax.jit(lambda k: job.ref.init(k, dict(
        job.model, output_norm_init=0.125)))(datagen.jax_key(7, 3))[0]
    for k, v in params.items():
        want = v * 0.125 if k.endswith("_post_norm_gamma") else v
        assert bool(jnp.all(scaled[k] == want)), k
    assert sum(k.endswith("_post_norm_gamma") for k in params) == 12
    # the selection bias gets no gradient and does not move
    bias = [k for k in theirs["grad1"] if k.endswith("expert_bias")]
    assert len(bias) == 4 and all(theirs["grad1"][k] == 0 for k in bias)
    assert all(theirs["change"][k] == 0 for k in bias)
    data, label = batches[0]["data"][0], batches[0]["softmax_label"][0]
    out = job.ref.logits(params, data, job.model)
    assert out.shape == (job.model["seq_len"], job.model["vocab_size"])
    job.ref.HEAD_BLOCK, whole = 16, job.ref.HEAD_BLOCK
    try:
        blocked = job.ref.row_loss(job.model)(params, data, label)
    finally:
        job.ref.HEAD_BLOCK = whole
    np.testing.assert_allclose(
        blocked, common.softmax_ce_sum(out, label) / out.shape[0], rtol=1e-6)


def test_the_reference_masks_the_window_and_turns_sliding_layers_only():
    """The mask is the band ``p - window < j <= p``; a full layer's output
    does not change when the row's positions shift (no positional
    embedding), a sliding layer's does."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import trinity as ref
    band = np.asarray(ref.seen(4, 3, 10, 3))
    assert band.tolist() == [[j in (2, 3, 4) for j in range(10)],
                             [j in (3, 4, 5) for j in range(10)],
                             [j in (4, 5, 6) for j in range(10)]]
    assert np.asarray(ref.seen(0, 10, 10, None)).sum() == 55
    job, _ = _toy_job()
    cfg = job.model
    p = ref.init(jax.random.PRNGKey(2), cfg)[0]
    x = jax.random.normal(jax.random.PRNGKey(3), (80, cfg["hidden_size"]))
    turned = ref._rope(x.reshape(80, 4, 8), 10000)
    assert not np.allclose(turned[1:], x.reshape(80, 4, 8)[1:])
    np.testing.assert_allclose(turned[0], x.reshape(80, 4, 8)[0])
    # a window of all the positions is the full mask
    wide = dict(cfg, sliding_window=80)
    a = ref.attention(p, "l0_swa_", x, wide, True)
    b = ref.attention(p, "l0_swa_", x, cfg, True)
    assert float(jnp.max(jnp.abs(a[:24] - b[:24]))) < 1e-6
    assert float(jnp.max(jnp.abs(a[24:] - b[24:]))) > 1e-4


def test_configuration_keeps_every_published_key_and_width():
    from benchmark import run
    spec, cell, config, traffic, _ = run.load_cell(REAL)
    entry = [c for c in spec["configs"] if c["name"] == cell["config"]][0]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json")
    assert entry["file"] == "benchmark/configs/trinity_mini.json"
    reduced = set(entry["reduced"])
    assert reduced == set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
        assert config["model"][key] == config[key], key
    assert config["num_hidden_layers"] == 6
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["num_experts"] * 16 == config["model"][
        "num_routed_experts"] == PUBLISHED["num_experts"]
    for said in ("shared by 16 chips", "over 8", "first 6 of the 32"):
        assert said in config["deployment"], said
    # the layers kept: both dense layers and one whole period after them
    kinds = PUBLISHED["layer_types"][:config["num_hidden_layers"]]
    assert kinds == ["sliding_attention"] * 3 + ["full_attention"] \
        + ["sliding_attention"] * 2
    assert kinds[PUBLISHED["num_dense_layers"]:].count("full_attention") == 1
    assert all((t == "full_attention") == ((i + 1) % 4 == 0)
               for i, t in enumerate(PUBLISHED["layer_types"]))
    kwargs = config["program"]["kwargs"]
    assert kwargs["num_experts"] == 128 and kwargs["num_experts_held"] == 8
    for key, value in kwargs.items():
        if key in PUBLISHED and key not in reduced:
            assert value == PUBLISHED[key], key
    assert config["program"]["compute_dtype"] == "bfloat16"
    assert config["reference"] == "trinity"
    assert cell["chips"] == 1 and cell["traffic"] == "train_8k"
    assert traffic["job"] == "train_lm"
    assert traffic["batch_per_chip"] * config["model"]["seq_len"] == 16384
    for key in ("initialisation", "expert_bias", "mup", "attention",
                "router", "unused_keys", "optimizer", "rows", "loss"):
        assert config["assumed"][key]
    # measured departures (PERF.md, Findings PR 37): the output norms'
    # weights start at 1 / sqrt(2 x 32) and the rate is a tenth of the other
    # cells', so that the seeded router's loads hold through a window
    assert config["model"]["output_norm_init"] == 0.125
    assert "0.125" in config["assumed"]["initialisation"]
    assert config["optimizer"] == {"name": "sgd", "params": {
        "learning_rate": 0.001, "momentum": 0.9, "wd": 0.0}}
    assert "0.001" in config["assumed"]["optimizer"]


def test_the_cells_files_are_found_by_name():
    from benchmark import run
    spec, cell, config, traffic, limits = run.load_cell(REAL)
    assert os.path.exists(os.path.join(BENCH, "limits", REAL + ".json"))
    assert os.path.exists(os.path.join(BENCH, "reference",
                                       config["reference"] + ".py"))
    assert os.path.exists(os.path.join(BENCH, "jobs",
                                       traffic["job"] + ".py"))
    assert set(limits) == {"loss_gap", "grad1_mid_gap", "grad1_mid_diff",
                           "change_mid_gap", "change_mid_diff", "grad1_gap",
                           "change_gap"}
    assert all(0 < v < 1 for v in limits.values())


def test_the_cell_reports_the_shared_metrics_and_its_own():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    mine = {m["name"] for m in spec["per_layer"]
            if REAL in m.get("workloads", [])}
    assert mine >= {
        "input_wait_ms", "step_mfu", "step_device_ms", "device_idle_share",
        "setup_compile_s", "host_turnaround_ms", "step_dispatch_ms",
        "step_period_max_ms", "feed_wait_ms", "feed_busy_share",
        "setup_trace_lower_s", "step_overlap_share", "step_update_ms",
        "step_unnamed_share", "attn_roofline", "moe_roofline",
        "moe_load_imbalance", "moe_compact_share", "attn_kernel_share",
        "swa_roofline", "swa_steps_share"}
    assert not mine & {"causal_conv_kernel_share", "gdn_roofline",
                       "gdn_kernel_share", "kda_roofline",
                       "kda_kernel_share", "cca_roofline"}
    for name in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py")), \
            name
    for name, better, source in (
            ("swa_roofline", "higher", "device_trace"),
            ("swa_steps_share", "lower", "program_counter")):
        entry, = [m for m in spec["per_layer"] if m["name"] == name]
        assert REAL in entry["workloads"]
        assert dict(entry, workloads=None) == {
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": "kernels", "moves": "train_throughput",
            "workloads": None}


def _model():
    from benchmark import run
    return dict(run.load_cell(REAL)[2]["model"], batch=2)


def test_parameters_and_forward_flops_against_hand_counts():
    from benchmark import run
    from benchmark.reference import trinity as ref
    model = _model()
    total = 0
    by_kind = {"swa": 0, "attn": 0, "mlp": 0, "moe": 0}
    for name, shape in ref.shapes(model)[0].items():
        n = 1
        for d in shape:
            n *= d
        total += n
        for kind in by_kind:
            if ("_%s_" % kind) in name:
                by_kind[kind] += n
    h, v = 2048, 25024
    # the issue's table: q, gate, o 8,388,608 each; k, v 1,048,576 each; the
    # heads' two norms 256 — it writes the sum as 27,263,488, 256 too many
    mixer = 3 * 8388608 + 2 * 1048576 + 256
    assert mixer == 27263232
    dense, expert = 37748736, 56885376
    assert dense == 3 * h * 6144
    assert expert == 128 * h + 128 + 9 * 3 * h * 1024
    assert by_kind == {"swa": 5 * (mixer + 2 * h), "attn": mixer + 2 * h,
                       "mlp": 2 * (dense + 2 * h),
                       "moe": 4 * (expert + 2 * h)}
    assert total == 6 * (mixer + 4 * h) + 2 * dense + 4 * expert \
        + 2 * v * h + h == 569167872
    # the whole model from ``published``, as the same functions count it
    whole = dict(model, **run.load_cell(REAL)[2]["published"])
    whole["num_routed_experts"] = whole["num_experts"]
    n = 0
    for shape in ref.shapes(whole)[0].values():
        size = 1
        for d in shape:
            size *= d
        n += size
    assert round(n / 1e9, 1) == 26.1               # "26B-A3B"
    # forward FLOPs a token, 2 a MAC: the issue's parts
    t, w = 8192, 2048
    proj = 2 * (mixer - 256)
    band = w * (w + 1) // 2 + (t - w) * w
    assert band == 14681088 and t * (t + 1) // 2 == 33558528
    assert ref.seen_pairs(t, w) == band and ref.seen_pairs(t) == 33558528
    assert round(100 * band / 33558528, 2) == 43.75
    swa = proj + 2 * 2 * 4096 * band / t
    full = proj + 2 * 2 * 4096 * (t + 1) / 2
    moe = 2 * h * (128 + 3 * 1024) + 8 * 8 / 128 * 2 * 3 * h * 1024
    want = 5 * swa + full + 2 * 2 * 3 * h * 6144 + 4 * moe + 2 * h * v
    assert ref.flops_per_item(model) == int(want)
    assert [round(x / 1e6, 1) for x in (
        proj, swa - proj, full - proj, 2 * 3 * h * 6144, moe, 2 * h * v)] \
        == [54.5, 29.4, 67.1, 75.5, 19.4, 102.5]
    assert 870e6 < want < 874e6                    # the issue's ~872 MFLOP
    assert round(100 * (5 * swa + full) / want) == 62
    assert round(100 * 5 * swa / want) == 48
    assert round(100 * 2 * h * v / want) == 12
    # 42.9 TFLOP a step of 16,384 tokens, 218 ms at the chip's peak
    assert round(3 * want * 16384 / 1e12, 1) == 42.9
    assert round(3 * want * 16384 / 197e12 * 1e3) == 218


def test_node_work_files_the_stages_by_kind_and_honours_pairs_here():
    from benchmark.reference import trinity as ref
    model = _model()
    work = ref.node_work(model, 2)
    assert [n["node"] for n in work["swa"]] == [
        "l0_swa", "l1_swa", "l2_swa", "l4_swa", "l5_swa"]
    assert [n["scopes"] for n in work["attn"]] == [["l3_attn"]]
    assert [n["node"] for n in work["mlp"]] == ["l0_mlp", "l1_mlp"]
    assert [n["node"] for n in work["moe"]] == ["l2_moe", "l3_moe", "l4_moe",
                                                "l5_moe"]
    for nodes in work.values():
        for n in nodes:
            assert n["bwd"] == (2 * n["fwd"][0], 2 * n["fwd"][1])
    tokens = 16384
    staged = sum(n["fwd"][0] for kind in work.values() for n in kind)
    head = 2 * model["hidden_size"] * model["vocab_size"] * tokens
    flops = ref.flops_per_item(model)
    assert abs(staged + head - flops * tokens) < 1e-6 * flops * tokens
    # a windowed stage's scores are the band's, not the triangle's
    swa, full = work["swa"][0], work["attn"][0]
    assert full["fwd"][0] - swa["fwd"][0] == 2 * 2 * 4096 * 2 * (
        33558528 - 14681088)
    assert swa["fwd"][1] == full["fwd"][1]
    # the routed part follows the pairs a step really landed: at the
    # expectation 16,384 x 8 x 8 / 128 = 8,192 a layer
    pair = 2 * 3 * 2048 * 1024
    more = ref.node_work(model, 2, pairs_here=4 * 16384)
    assert more["moe"][0]["fwd"][0] - work["moe"][0]["fwd"][0] \
        == (16384 - 8192) * pair
    assert more["swa"] == work["swa"]
    # bytes of an attention stage: weights once and what it writes
    weights = 3 * 8388608 + 2 * 1048576
    assert swa["fwd"][1] == 2 * (weights + tokens * (
        2 * 2048 + 3 * 4096 + 2 * 512))


class _Job(object):
    batch = 2

    def __init__(self):
        from benchmark.reference import trinity
        self.ref, self.model = trinity, _model()


def _facts(scopes):
    from benchmark import flops
    return {"trace": {"scopes_s": scopes}, "chips": 1, "job": _Job(),
            "peak": flops.peaks("TPU v5 lite"),
            "window": {"traced_steps": 3, "t_start": 0.0, "seconds": 1.0,
                       "steps": 0}}


def test_swa_roofline_reader_on_a_hand_written_trace():
    from benchmark import flops
    from benchmark.metrics import (attn_roofline, kda_roofline, moe_roofline,
                                   swa_roofline)
    job = _Job()
    peak = flops.peaks("TPU v5 lite")
    work = job.ref.node_work(job.model, 2)

    def least(node, part):
        return flops.least_seconds(node[part][0], node[part][1], peak)[0]
    swa = work["swa"][0]
    assert flops.least_seconds(*swa["fwd"], peak)[1] == "flops"
    # three traced steps; the first sliding stage ran at half of its
    # roofline forward and a quarter backward, the others left no event
    scopes = {"l0_swa": 2 * 3 * least(swa, "fwd"),
              "_backward_l0_swa": 4 * 3 * least(swa, "bwd")}
    got = swa_roofline.read(_facts(scopes))
    want = 100 * (least(swa, "fwd") + least(swa, "bwd")) / (
        2 * least(swa, "fwd") + 4 * least(swa, "bwd"))
    assert abs(got - want) < 1e-9 and 25 < got < 50
    # the full stage is filed under ``attn``, which the accepted reader
    # reads, and not under ``swa``
    assert attn_roofline.read(_facts(scopes)) is None
    full = work["attn"][0]
    assert abs(attn_roofline.read(_facts(
        {"l3_attn": 3 * least(full, "fwd")})) - 100) < 1e-9
    assert swa_roofline.read(_facts({"l3_attn": 1.0})) is None
    moe = work["moe"][0]
    assert abs(moe_roofline.read(_facts(
        {"l2_moe": 3 * least(moe, "fwd")})) - 100) < 1e-9
    # this family files nothing under ``kda``; a program without the
    # sliding scopes (the parent's) gives the new reader nothing to read
    assert kda_roofline.read(_facts(scopes)) is None
    assert swa_roofline.read(_facts({"l0_kda": 1.0, "conv0": 2.0})) is None
    assert swa_roofline.read({"trace": None, "window": {"traced_steps": 0},
                              "peak": peak}) is None


def _route(window=None, steps=None, causal=None, end=1.0, **ids):
    ids = dict({"kernel": "gqa_attention", "tier": "pallas",
                "reason": "aligned"}, **ids)
    if window is not None:
        ids.update(window=window, steps=steps, steps_causal=causal)
    return {"name": "kernel.route", "start": end, "end": end, "ids": ids}


@pytest.mark.parametrize("records,want", [
    ([_route(2048, 140, 272)] * 5 + [_route()], 100.0 * 140 / 272),
    ([_route(2048, 140, 272), _route(64, 10, 100, tier="lax",
                                     reason="shapes")], 100.0 * 150 / 372),
    ([_route(2048, 272, 272)], 100.0),
    # a full layer's event, another kernel's, and one inside the window
    # are not a windowed lowering of set-up
    ([_route(2048, 140, 272), _route(), _route(kernel="delta_rule"),
      _route(2048, 272, 272, end=11.0)], 100.0 * 140 / 272),
    ([_route()], None), ([], None),
], ids=["the_cells", "two_tiers", "only_masked", "others_left_out",
        "no_window", "empty"])
def test_swa_steps_share_reads_the_windowed_lowerings(monkeypatch, records,
                                                      want):
    from benchmark.metrics import swa_steps_share
    from mxnet_tpu import profiler
    monkeypatch.setattr(profiler, "spans", lambda since=None, until=None: [
        r for r in records if until is None or r["start"] <= until])
    got = swa_steps_share.read({"window": {"t_start": 10.0}})
    assert got == (None if want is None else pytest.approx(want))


def test_swa_steps_share_reads_what_the_op_records():
    """The op's own event, through the real recorder: a windowed lowering
    carries its schedule, an unwindowed one nothing more than it did."""
    import time

    import numpy as np

    from benchmark.metrics import attn_kernel_share, swa_steps_share
    from mxnet_tpu.ops.contrib import gq_attention
    x = np.ones((1, 64, 2, 4), "f")
    gq_attention(x, x, x, block_q=16)
    facts = {"window": {"t_start": time.perf_counter()}}
    assert attn_kernel_share.routes(facts)[-1] == {
        "kernel": "gqa_attention", "tier": "lax", "reason": "shapes"}
    gq_attention(x, x, x, block_q=16, window=16)
    facts = {"window": {"t_start": time.perf_counter()}}
    assert attn_kernel_share.routes(facts)[-1] == {
        "kernel": "gqa_attention", "tier": "lax", "reason": "shapes",
        "window": 16, "steps": 7, "steps_causal": 10}
    assert swa_steps_share.read(facts) is not None
    assert attn_kernel_share.read(facts) is not None
