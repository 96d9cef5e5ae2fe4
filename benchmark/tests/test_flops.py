"""The benchmark's arithmetic against the program's own count."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import flops  # noqa: E402


def model(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet50_is_24_5_gflop_an_image_and_agrees_with_the_program():
    from benchmark.reference import resnet50
    from mxnet_tpu import models
    from mxnet_tpu.contrib.flops import model_flops
    cfg = model("resnet50")
    mine = flops.train_flops_per_item(resnet50.flops_per_item(cfg["model"]))
    sym = models.get_symbol("resnet-50", num_classes=1000)
    theirs = 3 * model_flops(sym, data=(1, 3, 224, 224))
    assert mine == theirs
    assert round(mine / 1e9, 1) == 24.5


def test_lstm_ptb_large_is_306_mflop_a_token_and_agrees_with_the_program():
    from benchmark.reference import lstm_ptb_large
    from mxnet_tpu.contrib.flops import model_flops
    from mxnet_tpu.models.lstm_lm import lstm_lm_sym
    cfg = model("lstm_ptb_large")
    mine = flops.train_flops_per_item(
        lstm_ptb_large.flops_per_item(cfg["model"]))
    sym = lstm_lm_sym(35, 10000, num_embed=1500, num_hidden=1500,
                      num_layers=2)[0]
    theirs = 3 * model_flops(sym, data=(1, 35), softmax_label=(1, 35)) / 35
    assert mine == theirs
    assert round(mine / 1e6) == 306


def test_node_work_adds_up_to_the_models_flops():
    from benchmark.reference import lstm_ptb_large, resnet50
    cfg = model("resnet50")["model"]
    work = resnet50.node_work(cfg, 1)
    conv = sum(n["fwd"][0] for n in work["conv"])
    head = 2 * 2048 * 1000 + 1000
    assert conv + head == resnet50.flops_per_item(cfg)
    assert len(work["conv"]) == 53 and len(work["bn"]) == 51
    cfg = model("lstm_ptb_large")["model"]
    rnn = lstm_ptb_large.node_work(cfg, 1)["rnn"][0]["fwd"][0] / 35
    biases = 2 * 2 * 4 * 1500 * 2
    assert rnn + biases + 2 * 1500 * 10000 + 10000 == \
        lstm_ptb_large.flops_per_item(cfg)


def test_peaks_know_the_v5e_and_refuse_the_rest():
    row = flops.peaks("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")
    with pytest.raises(KeyError):
        flops.peaks("_source")
    assert flops.least_seconds(197e12, 1, row) == (1.0, "flops")
    assert flops.least_seconds(1, 819e9, row) == (1.0, "bytes")
    assert abs(flops.mfu_percent(2000, 24.5e9 / 3, 1, row) - 24.87) < 0.01
