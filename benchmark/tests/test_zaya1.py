"""The ``zaya`` family through the harness, on the CPU at toy widths: a
whole run of the job kind ``train_lm`` on the toy cell, the int8 control
failing it, ``row_loss`` tied to ``loss_fn``, the configuration file
against the catalog's published keys, the arithmetic against hand counts,
and ``cca_roofline``'s reader on a hand-written trace."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

TOY = os.path.join(HERE, "data", "toy_spec_zaya.json")
CELL = "zaya_toy.train_toy_lm"
REAL = "zaya1_8b.train_8k"

_ROPE = {"partial_rotary_factor": 0.5, "rope_type": "default"}
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl:
#: ZAYA1-8B), every key
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": dict(_ROPE, rope_theta=5000000),
        "hybrid_sliding": dict(_ROPE, rope_theta=10000),
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}


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
    # the load moves the balancing bias, row by row as batch by batch
    bias = [k for k in theirs["grad1"] if k.endswith("balance_bias")]
    assert len(bias) == 3 and all(theirs["grad1"][k] > 0 for k in bias)
    assert all(theirs["change"][k] > 0 for k in bias)
    # the tied leaf is one leaf, and the reference has no other head
    assert "head_weight" not in params and "embed_weight" in params
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


def test_the_balancing_bias_starts_at_zero_and_the_load_moves_it():
    """Seeded zero whatever the key; the gradient the reference hands it is
    ``router_balance_rate`` times the error of each expert's share of the
    row's tokens, over all 16 experts (8 held), and nothing without a
    rate."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import zaya1 as ref
    model = dict(_model(), hidden_size=8, moe_intermediate_size=8,
                 vocab_size=16, head_dim=4, router_hidden_size=4,
                 num_hidden_layers=2, seq_len=64)
    assert model["router_balance_rate"] > 0
    a, b = (ref.init(jax.random.PRNGKey(k), model)[0] for k in (1, 2))
    names = ["l%d_moe_router_balance_bias" % i for i in range(2)]
    for name in names:
        np.testing.assert_array_equal(np.asarray(a[name]), np.zeros(16, "f"))
        np.testing.assert_array_equal(np.asarray(b[name]), 0.0)
    assert not np.array_equal(a["l0_moe_router_fc1_weight"],
                              b["l0_moe_router_fc1_weight"])
    a = {k: (v * 20 if k.endswith("_weight") else v) for k, v in a.items()}
    data = jnp.arange(64, dtype=jnp.int32) % 16
    grads = jax.grad(ref.row_loss(model))(a, data, data)
    for name in names:
        share = np.asarray(grads[name]) / model["router_balance_rate"] \
            + 1.0 / 16
        assert abs(share.sum() - 1) < 1e-5 and share.min() > -1e-6
        assert share.max() > 1.5 / 16, share
        np.testing.assert_allclose(share * 64, np.round(share * 64),
                                   atol=1e-4)
    still = jax.grad(ref.row_loss(dict(model, router_balance_rate=0.0)))(
        a, data, data)
    for name in names:
        np.testing.assert_array_equal(np.asarray(still[name]), 0.0)
    for k in grads:
        if k not in names:
            np.testing.assert_array_equal(np.asarray(grads[k]),
                                          np.asarray(still[k]))


def test_configuration_keeps_every_published_key_and_width():
    from benchmark import run
    spec, cell, config, traffic, _ = run.load_cell(REAL)
    entry = [c for c in spec["configs"] if c["name"] == cell["config"]][0]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json")
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
    assert config["num_hidden_layers"] == 5
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["num_experts"] * 2 == config["model"][
        "num_routed_experts"] == PUBLISHED["num_experts"]
    for said in ("shared by 2 chips", "over 8 chips", "first 5 of the 40"):
        assert said in config["deployment"], said
    assert set(PUBLISHED["layer_types"]) == {"hybrid"}    # the period is 1
    kwargs = config["program"]["kwargs"]
    assert kwargs["num_experts"] == 16 and kwargs["num_experts_held"] == 8
    for key, value in kwargs.items():
        if key in PUBLISHED and key not in reduced:
            assert value == PUBLISHED[key], key
    assert config["program"]["compute_dtype"] == "bfloat16"
    assert cell["chips"] == 1 and cell["traffic"] == "train_8k"
    assert traffic["job"] == "train_lm"
    assert traffic["batch_per_chip"] * config["model"]["seq_len"] == 16384
    for key in ("projections", "normalised_heads", "router", "balance_bias",
                "residual_scaling", "skip_expert", "embedding",
                "initialisation", "unused_keys", "optimizer", "rows"):
        assert config["assumed"][key]
    assert config["optimizer"] == {"name": "sgd", "params": {
        "learning_rate": 0.01, "momentum": 0.9, "wd": 0.0}}


def test_the_cell_reports_the_shared_metrics_and_its_own():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    mine = {m["name"] for m in spec["per_layer"]
            if REAL in m.get("workloads", [])}
    assert mine >= {
        "input_wait_ms", "step_mfu", "step_device_ms", "device_idle_share",
        "setup_compile_s", "host_turnaround_ms", "step_dispatch_ms",
        "step_period_max_ms", "feed_wait_ms", "feed_busy_share",
        "setup_trace_lower_s", "step_overlap_share", "moe_roofline",
        "moe_load_imbalance", "cca_roofline"}
    # the expert layers' counters are read here too: no blocked path at 8
    # of 16 experts held, so the share of calls on one block reads 0
    assert "moe_compact_share" in mine
    for name in mine:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py")), \
            name
    cca = [m for m in spec["per_layer"] if m["name"] == "cca_roofline"][0]
    assert dict(cca, workloads=None) == {
        "name": "cca_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_throughput", "workloads": None}
    assert REAL in cca["workloads"]


def _model():
    from benchmark import run
    return dict(run.load_cell(REAL)[2]["model"], batch=2)


def _count(shapes, want=lambda name: True):
    total = 0
    for name, shape in shapes.items():
        n = 1
        for d in shape:
            n *= d
        total += n if want(name) else 0
    return total


def test_parameters_and_forward_flops_against_hand_counts():
    from benchmark import run
    from benchmark.reference import zaya1 as ref
    model = _model()
    shapes = ref.shapes(model)[0]
    h, v = 2048, 32784
    scaling = 4 * h                        # an add's two scales, two biases
    cca = h * (1024 + 256 + 128 + 128) + 1024 * h \
        + 1280 * 2 + 1280 * 128 * 2 + 2 + h + scaling
    router = h * 256 + 256 + 2 * 256 * 256 + 16 * 256 + 16
    moe = router + 8 * 3 * h * 2048 + h + scaling
    assert _count(shapes, lambda n: "_cca_" in n) == 5 * cca
    # layer 0's router carries nothing: four carry vectors, not five
    assert _count(shapes, lambda n: "_moe_" in n) == 5 * moe + 4 * 256
    total = _count(shapes)
    assert total == 5 * (cca + moe) + 4 * 256 + v * h + h   # tied: once
    # 601,727,834: the issue's ~601.8 M adds up rounded parts
    assert total == 601727834 and abs(total - 601.8e6) < 0.1e6
    # the issue's parts a layer: five projections, convolutions, router,
    # held experts
    assert round((h * 1536 + 1024 * h) / 1e6, 2) == 5.24
    assert round((1280 * 2 + 1280 * 128 * 2) / 1e6, 2) == 0.33
    assert round(router / 1e6, 2) == 0.66
    assert round(8 * 3 * h * 2048 / 1e6, 2) == 100.66
    # the whole model from ``published``: 40 layers, 16 experts, the whole
    # vocabulary, as the same functions count it
    whole = dict(model, **run.load_cell(REAL)[2]["published"])
    whole["num_routed_experts"] = whole["num_experts"]
    assert round(_count(ref.shapes(whole)[0]) / 1e9, 2) == 8.84
    # forward FLOPs a token, 2 a MAC
    t = 8192
    cca_f = 2 * (h * 1536 + 1024 * h) + 2 * (1280 * 2 + 1280 * 128 * 2) \
        + 2 * 8 * 256 * (t + 1) / 2
    moe_f = 2 * (h * 256 + 2 * 256 * 256 + 256 * 16) \
        + 1 * 8 / 16 * 2 * 3 * h * 2048
    want = 5 * (cca_f + moe_f) + 2 * h * v
    assert ref.flops_per_item(model) == int(want)
    assert 340e6 < want < 348e6                    # the issue's ~344 MFLOP
    assert round(cca_f / 1e6) == 28 and round(2 * h * v / 1e6) == 134
    assert round(100 * 5 * cca_f / want) == 41     # CCA's share
    assert round(100 * 2 * h * v / want) == 39     # the head's


def test_node_work_files_the_stages_by_kind_and_honours_pairs_here():
    from benchmark.reference import zaya1 as ref
    model = _model()
    work = ref.node_work(model, 2)
    assert set(work) == {"cca", "moe"}
    assert [n["node"] for n in work["cca"]] == ["l%d_cca" % i
                                                for i in range(5)]
    assert [n["scopes"] for n in work["moe"]] == [["l%d_moe" % i]
                                                  for i in range(5)]
    for nodes in work.values():
        for n in nodes:
            assert n["bwd"] == (2 * n["fwd"][0], 2 * n["fwd"][1])
    tokens = 16384
    staged = sum(n["fwd"][0] for kind in work.values() for n in kind)
    head = 2 * model["hidden_size"] * model["vocab_size"] * tokens
    flops = ref.flops_per_item(model)
    assert abs(staged + head - flops * tokens) < 1e-6 * flops * tokens
    # the routed part follows the pairs a step really landed: at the
    # expectation 16,384 x 1 x 8 / 16 = 8,192 a layer
    pair = 2 * 3 * 2048 * 2048
    more = ref.node_work(model, 2, pairs_here=5 * 16384)
    assert more["moe"][0]["fwd"][0] - work["moe"][0]["fwd"][0] \
        == (16384 - 8192) * pair
    assert more["cca"] == work["cca"]
    none = ref.node_work(model, 2, pairs_here=0)
    assert none["moe"][0]["fwd"][0] == tokens * 2 * (
        2048 * 256 + 2 * 256 * 256 + 256 * 16)
    # bytes of a CCA stage: weights once and the activations it writes
    cca = work["cca"][0]
    weights = 2048 * 1536 + 1024 * 2048 + 1280 * (2 + 128 * 2)
    assert cca["fwd"][1] == 2 * (weights + tokens * (
        2 * 2048 + 2 * 1536 + 2 * 1280 + 2 * 1024))


class _Job(object):
    batch = 2

    def __init__(self):
        from benchmark.reference import zaya1
        self.ref, self.model = zaya1, _model()


def _facts(scopes):
    from benchmark import flops
    return {"trace": {"scopes_s": scopes}, "chips": 1, "job": _Job(),
            "peak": flops.peaks("TPU v5 lite"),
            "window": {"traced_steps": 3, "t_start": 0.0, "seconds": 1.0,
                       "steps": 0}}


def test_cca_roofline_reader_on_a_hand_written_trace():
    from benchmark import flops
    from benchmark.metrics import (attn_roofline, cca_roofline, kda_roofline,
                                   moe_roofline)
    job = _Job()
    peak = flops.peaks("TPU v5 lite")
    work = job.ref.node_work(job.model, 2)

    def least(node, part):
        return flops.least_seconds(node[part][0], node[part][1], peak)[0]
    cca = work["cca"][0]
    assert flops.least_seconds(*cca["fwd"], peak)[1] == "flops"
    # three traced steps; the first CCA stage ran at half of its roofline
    # forward and a quarter backward, the others left no event
    scopes = {"l0_cca": 2 * 3 * least(cca, "fwd"),
              "_backward_l0_cca": 4 * 3 * least(cca, "bwd")}
    got = cca_roofline.read(_facts(scopes))
    want = 100 * (least(cca, "fwd") + least(cca, "bwd")) / (
        2 * least(cca, "fwd") + 4 * least(cca, "bwd"))
    assert abs(got - want) < 1e-9 and 25 < got < 50
    # this family files nothing under ``attn`` or ``kda``; the expert
    # stages are ``moe``, which the accepted reader reads
    assert attn_roofline.read(_facts(scopes)) is None
    assert kda_roofline.read(_facts(scopes)) is None
    moe = work["moe"][0]
    assert abs(moe_roofline.read(_facts(
        {"l0_moe": 3 * least(moe, "fwd")})) - 100) < 1e-9
    # a program without the CCA scopes (the parent's) gives the new reader
    # nothing to read
    assert cca_roofline.read(_facts({"l0_kda": 1.0, "conv0": 2.0})) is None
    assert cca_roofline.read({"trace": None, "window": {"traced_steps": 0},
                              "peak": peak}) is None
