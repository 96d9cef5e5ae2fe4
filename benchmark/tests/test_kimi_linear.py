"""The ``kimi_linear`` family through the harness, on the CPU at toy widths:
a whole run of the job kind ``train_lm`` on the toy cell, the int8 control
failing it, ``row_loss`` tied to ``loss_fn``, the configuration file
against the catalog's published keys, the arithmetic against hand counts,
and ``kda_roofline``'s reader on a hand-written trace."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

TOY = os.path.join(HERE, "data", "toy_spec_kimi.json")
CELL = "kimi_linear_toy.train_toy_lm"
REAL = "kimi_linear_48b_a3b.train_8k"

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl:
#: Kimi-Linear-48B-A3B-Instruct), every key
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


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
    # the selection bias gets no gradient and does not move
    bias = [k for k in theirs["grad1"] if k.endswith("correction_bias")]
    assert len(bias) == 4 and all(theirs["grad1"][k] == 0 for k in bias)
    assert all(theirs["change"][k] == 0 for k in bias)


def test_configuration_keeps_every_published_key_and_width():
    from benchmark import run
    spec, cell, config, traffic, _ = run.load_cell(REAL)
    entry = [c for c in spec["configs"] if c["name"] == cell["config"]][0]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
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
    assert config["num_experts"] * 32 == config["model"][
        "num_routed_experts"] == PUBLISHED["num_experts"]
    assert "32 chips" in config["deployment"]
    # the layers kept: the dense first layer and one whole period, 3 KDA
    # to 1 MLA, as the published lists have them
    lin = PUBLISHED["linear_attn_config"]
    kinds = ["mla" if i in lin["full_attn_layers"] else "kda"
             for i in range(1, config["num_hidden_layers"] + 1)]
    assert kinds == ["kda", "kda", "kda", "mla", "kda"]
    assert all((i in lin["kda_layers"]) != (i in lin["full_attn_layers"])
               for i in range(1, 28))
    kwargs = config["program"]["kwargs"]
    assert kwargs["num_experts"] == 256 and kwargs["num_experts_held"] == 8
    for key, value in kwargs.items():
        if key in PUBLISHED and key not in reduced:
            assert value == PUBLISHED[key], key
    assert cell["chips"] == 1 and cell["traffic"] == "train_8k"
    assert traffic["job"] == "train_lm"
    assert traffic["batch_per_chip"] * config["model"]["seq_len"] == 16384
    for key in ("initialisation", "e_score_correction_bias",
                "low_rank_gates", "optimizer", "rows"):
        assert config["assumed"][key]


def test_the_cell_reports_the_shared_metrics_and_its_own():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    mine = {m["name"] for m in spec["per_layer"]
            if REAL in m.get("workloads", [])}
    assert mine == {
        "input_wait_ms", "step_mfu", "step_device_ms", "device_idle_share",
        "setup_compile_s", "host_turnaround_ms", "step_dispatch_ms",
        "step_period_max_ms", "feed_wait_ms", "feed_busy_share",
        "setup_trace_lower_s", "step_overlap_share", "attn_roofline",
        "moe_roofline", "moe_load_imbalance", "kda_roofline"}
    kda = [m for m in spec["per_layer"] if m["name"] == "kda_roofline"]
    assert kda == [{"name": "kda_roofline", "unit": "%", "better": "higher",
                    "source": "device_trace", "layer": "kernels",
                    "moves": "train_throughput", "workloads": [REAL]}]
    assert os.path.exists(os.path.join(BENCH, "metrics", "kda_roofline.py"))


def _model():
    from benchmark import run
    return dict(run.load_cell(REAL)[2]["model"], batch=2)


def test_parameters_and_forward_flops_against_hand_counts():
    from benchmark.reference import kimi_linear as ref
    model = _model()
    total = 0
    by_kind = {"kda": 0, "mla": 0, "mlp": 0, "moe": 0}
    for name, shape in ref.shapes(model)[0].items():
        n = 1
        for d in shape:
            n *= d
        total += n
        for kind in by_kind:
            if ("_%s_" % kind) in name:
                by_kind[kind] += n
    h = 2304
    kda = 4 * h * 4096 + 3 * 4096 * 4 + 2 * (h * 128 + 128 * 4096) \
        + h * 32 + 32 + 4096 + 128 + h
    mla = h * 32 * 192 + h * 576 + 512 + 512 * 32 * 256 + 4096 * h + h
    moe = 256 * h + 256 + 9 * 3 * h * 1024 + h
    assert by_kind == {"kda": 4 * kda, "mla": mla, "mlp": 3 * h * 9216 + h,
                       "moe": 4 * moe}
    assert total == 4 * kda + mla + 3 * h * 9216 + h + 4 * moe \
        + 2 * 20480 * h + h
    assert round(total / 1e6, 1) == 602.4          # the issue's count
    # the issue's 39.51 / 29.11 M a mixer leave the stage's input norm out
    assert round((kda - h) / 1e6, 2) == 39.51
    assert round((mla - h) / 1e6, 2) == 29.11
    # forward FLOPs a token, 2 a MAC
    t, c = 8192, 64
    kda_f = 2 * (4 * h * 4096 + 2 * (h * 128 + 128 * 4096) + h * 32) \
        + 2 * 32 * (2 * c * 128 + c * c + c * 256 + 4 * 128 * 128 + c * 128)
    mla_f = 2 * (h * 6144 + h * 576 + 512 * 8192 + 4096 * h) \
        + 2 * 32 * (192 + 128) * (t + 1) / 2
    moe_f = 2 * h * (256 + 3 * 1024) + 8 * 8 / 256 * 2 * 3 * h * 1024
    want = 4 * kda_f + mla_f + 2 * 3 * h * 9216 + 4 * moe_f + 2 * h * 20480
    assert ref.flops_per_item(model) == int(want)
    assert 770e6 < want < 790e6                    # the issue's ~778 MFLOP


def test_node_work_files_the_stages_by_kind_and_honours_pairs_here():
    from benchmark.reference import kimi_linear as ref
    model = _model()
    work = ref.node_work(model, 2)
    assert [n["node"] for n in work["kda"]] == ["l0_kda", "l1_kda", "l2_kda",
                                                "l4_kda"]
    assert [n["scopes"] for n in work["attn"]] == [["l3_mla"]]
    assert [n["node"] for n in work["mlp"]] == ["l0_mlp"]
    assert [n["node"] for n in work["moe"]] == ["l1_moe", "l2_moe", "l3_moe",
                                                "l4_moe"]
    for nodes in work.values():
        for n in nodes:
            assert n["bwd"] == (2 * n["fwd"][0], 2 * n["fwd"][1])
    tokens = 16384
    staged = sum(n["fwd"][0] for kind in work.values() for n in kind)
    head = 2 * model["hidden_size"] * model["vocab_size"] * tokens
    flops = ref.flops_per_item(model)
    assert abs(staged + head - flops * tokens) < 1e-6 * flops * tokens
    # the routed part follows the pairs a step really landed: at the
    # expectation 16,384 x 8 x 8 / 256 = 4,096 a layer
    pair = 2 * 3 * 2304 * 1024
    more = ref.node_work(model, 2, pairs_here=4 * 8192)
    assert more["moe"][0]["fwd"][0] - work["moe"][0]["fwd"][0] \
        == (8192 - 4096) * pair
    assert more["kda"] == work["kda"]
    # bytes of a KDA stage: weights once and the activations it writes
    kda = work["kda"][0]
    weights = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096)
    assert kda["fwd"][1] == 2 * (weights + tokens * (2 * 2304 + 10 * 4096))


class _Job(object):
    batch = 2

    def __init__(self):
        from benchmark.reference import kimi_linear
        self.ref, self.model = kimi_linear, _model()


def _facts(scopes):
    from benchmark import flops
    return {"trace": {"scopes_s": scopes}, "chips": 1, "job": _Job(),
            "peak": flops.peaks("TPU v5 lite"),
            "window": {"traced_steps": 3, "t_start": 0.0, "seconds": 1.0,
                       "steps": 0}}


def test_kda_roofline_reader_on_a_hand_written_trace():
    from benchmark import flops
    from benchmark.metrics import attn_roofline, gdn_roofline, kda_roofline
    job = _Job()
    peak = flops.peaks("TPU v5 lite")
    work = job.ref.node_work(job.model, 2)

    def least(node, part):
        return flops.least_seconds(node[part][0], node[part][1], peak)[0]
    kda = work["kda"][0]
    # three traced steps; the first KDA stage ran at half of its roofline
    # forward and a quarter backward, the others left no event
    scopes = {"l0_kda": 2 * 3 * least(kda, "fwd"),
              "_backward_l0_kda": 4 * 3 * least(kda, "bwd")}
    got = kda_roofline.read(_facts(scopes))
    want = 100 * (least(kda, "fwd") + least(kda, "bwd")) / (
        2 * least(kda, "fwd") + 4 * least(kda, "bwd"))
    assert abs(got - want) < 1e-9 and 25 < got < 50
    # the MLA stage is filed under ``attn``, which the accepted reader reads
    assert attn_roofline.read(_facts(scopes)) is None
    mla = work["attn"][0]
    assert abs(attn_roofline.read(_facts(
        {"l3_mla": 3 * least(mla, "fwd")})) - 100) < 1e-9
    # this family files nothing under ``gdn``; a program without the KDA
    # scopes (the parent's) gives the new reader nothing to read
    assert gdn_roofline.read(_facts(scopes)) is None
    assert kda_roofline.read(_facts({"l0_gdn": 1.0, "conv0": 2.0})) is None
    assert kda_roofline.read({"trace": None, "window": {"traced_steps": 0},
                              "peak": peak}) is None
