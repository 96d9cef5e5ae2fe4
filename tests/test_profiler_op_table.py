"""``profiler.get_op_stats`` / ``dumps``: the op table of a chip's trace,
by (stage, pass, node), without a chip — on a structure recorded from one
(``tests/data/optable_*.json.gz``) and on hand-made events."""
import gzip
import json
import os

import pytest

from mxnet_tpu import profiler
from mxnet_tpu.base import MXNetError

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "optable_kimi_linear_train_8k.json.gz")


# -- paths --------------------------------------------------------------------

@pytest.mark.parametrize("path,want", [
    ("jit(step)/jvp(l1_kda)/l1_kda_proj/dot_general",
     (["l1_kda", "l1_kda_proj"], "forward", False)),
    ("jit(step)/transpose(jvp(l1_kda))/jvp(l1_kda)/checkpoint/l1_kda_proj/"
     "transpose", (["l1_kda", "l1_kda_proj"], "backward", True)),
    ("jit(step)/transpose(jvp(l1_kda))/jvp(l1_kda)/checkpoint/"
     "rematted_computation/l1_kda_proj/sub",
     (["l1_kda", "l1_kda_proj"], "remat", True)),
    ("jit(step)/jvp(conv1)/conv_general_dilated",
     (["conv1"], "forward", False)),
    ("jit(step)/transpose(jvp(conv1))/conv_general_dilated",
     (["conv1"], "backward", False)),
    # the checkpoint's own call: the stage, no node
    ("jit(step)/transpose(jvp(l1_kda))/jvp(l1_kda)/remat2",
     (["l1_kda"], "backward", False)),
    # scopes inside a node stay behind it
    ("jit(step)/jvp(l0_gdn)/l0_gdn_rule/jit(gated_delta_rule)/while/body/"
     "dot_general", (["l0_gdn", "l0_gdn_rule", "jit(gated_delta_rule)",
                      "while", "body"], "forward", False)),
    ("jit(step)/step.update/jit(_where)/select_n",
     (["step.update", "jit(_where)"], "forward", False)),
    ("jit(step)/jvp(step.cast)/convert_element_type",
     (["step.cast"], "forward", False)),
    # a program without autodiff (a predictor), the tf_op form's colon
    ("jit(forward)/conv2/conv_general_dilated:",
     (["conv2"], "forward", False)),
    # under a whole-loss checkpoint inside shard_map (zero3)
    ("jit(step)/shard_map/transpose(jvp(jvp()))/checkpoint/s1/checkpoint/"
     "s1_fc/dot_general", (["s1", "s1_fc"], "backward", True)),
    # traced under no scope at all: the primitive is all there is
    ("jit(step)/mul", (["mul"], "forward", False)),
], ids=["stage_forward", "stage_backward", "stage_remat", "node_forward",
        "node_backward", "stage_own_call", "inner_scopes", "step_scope",
        "step_cast", "no_autodiff", "zero3", "bare"])
def test_a_path_gives_its_scopes_its_pass_and_whether_it_is_staged(path,
                                                                  want):
    assert profiler._parse_path(path) == want


# -- a recorded structure -----------------------------------------------------

def test_a_recorded_chip_trace_reads_by_stage_pass_and_node():
    """Cut from a traced run of ``kimi_linear_48b_a3b.train_8k`` on a TPU
    v5e (PR 35, seed 3500107920, the slice's second step: the forward of
    the stage ``l1_kda``, the backward pass from the expert stage
    ``l1_moe`` down to the embedding, the step's last 120 events; event
    names clipped, the step's text trimmed to the kept instructions'
    ``op_name``)."""
    rows = profiler.get_op_stats(RECORDED)
    lines = profiler._recorded_lines(RECORDED)
    _, busy = profiler._busiest(lines)
    # self times: the rows sum to the line's busy time
    total = sum(r["total_us"] for r in rows.values())
    assert total == pytest.approx(sum(e - s for s, e in busy) / 1e3,
                                  rel=1e-6)
    kda = {}
    for name, row in rows.items():
        if row.get("stage") == "l1_kda":
            kda.setdefault(row["pass"], {})[row["node"]] = row
            assert name == profiler._PASSES[row["pass"]] + "l1_kda/" \
                + row["node"]
    assert set(kda) == {"forward", "remat", "backward"}
    # the rule's kernels: 13.36 ms forward, again rematerialised, 22.54 back
    assert kda["forward"]["l1_kda_rule"]["max_us"] == pytest.approx(
        13364, rel=1e-3)
    assert kda["remat"]["l1_kda_rule"]["max_us"] == pytest.approx(
        13364, rel=1e-3)
    assert kda["backward"]["l1_kda_rule"]["max_us"] == pytest.approx(
        22539, rel=1e-3)
    sums = {which: sum(r["total_us"] for r in nodes.values())
            for which, nodes in kda.items()}
    assert sums == pytest.approx({"forward": 32903.8, "remat": 31227.3,
                                  "backward": 61940.6}, rel=1e-4)
    # the output projection is not rematerialised: nothing reads it again
    assert "l1_kda_o_proj" in kda["forward"] \
        and "l1_kda_o_proj" not in kda["remat"]
    for conv in ("l1_kda_q_conv", "l1_kda_k_conv", "l1_kda_v_conv"):
        assert all(conv in kda[which] for which in kda)
    # the trainer's own work, a row each
    assert rows["step.update"]["stage"] == "" \
        and rows["step.update"]["node"] == "step.update"
    assert {"step.update", "step.guard", "step.cast"} <= set(rows)
    # the compiler's own: the grouped products run beside the expert stage,
    # the copies beside the mixer's passes
    assert max(rows["ragged-dot-none"]["near"],
               key=rows["ragged-dot-none"]["near"].get) == "_backward_l1_moe"
    assert {"_remat_l1_kda", "_backward_l1_kda"} <= set(
        rows["hlo:copy"]["near"])
    assert sum(rows["hlo:copy"]["near"].values()) == pytest.approx(
        rows["hlo:copy"]["total_us"], abs=0.01)

    table = profiler.dumps(trace_dir=RECORDED)
    for head in ("stage l1_kda: 126071.697 us", "  l1_kda (forward)",
                 "  _remat_l1_kda (remat)", "  _backward_l1_kda (backward)",
                 "    _remat_l1_kda/l1_kda_rule", "  near _backward_l1_moe:"):
        assert "\n" + head in table, head


# -- hand-made events ---------------------------------------------------------

def _event(name, start, dur, path=None):
    return (start, dur, "%%%s = f32[8]{0} fusion(f32[8]{0} %%p)" % name, path)


TEXT = """
HloModule jit_step
%fused_computation.1 { ... }
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/jvp(s1)/s1_fc/dot_general" source_file="a.py"}
  %fusion.2 = f32[8]{0} fusion(%p), metadata={op_name="jit(step)/transpose(jvp(s1))/jvp(s1)/checkpoint/rematted_computation/s1_fc/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%p), metadata={op_name="jit(step)/transpose(jvp(s1))/jvp(s1)/checkpoint/s1_fc/transpose"}
  %while.4 = (f32[8]) while(%t), metadata={op_name="jit(step)/jvp(lstm)/while"}
  %fusion.5 = f32[8]{0} fusion(%p), metadata={op_name="jit(step)/jvp(lstm)/while/body/dot_general"}
  ROOT %fusion.6 = f32[8]{0} fusion(%p), metadata={op_name="jit(step)/step.update/jit(_where)/select_n"}
  %ragged.7 = f32[8]{0} custom-call(%p), metadata={op_name="ragged-dot-none"}
"""


def _hand_made():
    return [
        _event("fusion.1", 0, 100),
        _event("copy.11", 100, 10),                 # near s1
        _event("while.4", 200, 1000),               # spans its body
        _event("fusion.5", 300, 200),
        _event("fusion.5", 600, 300),
        _event("fusion.2", 1300, 50),
        _event("ragged.7", 1350, 30),               # near _remat_s1
        _event("fusion.3", 1400, 70),
        _event("fusion.3", 1500, 30),
        _event("copy.12.clone", 1530, 5),           # near _backward_s1
        _event("fusion.6", 1600, 40),
        _event("slice-done.3", 1640, 2, None),      # near step.update
        _event("fusion.9", 1700, 8, "jit(step)/jvp(late)/add"),
    ]


def test_rows_by_stage_pass_and_node_with_self_times_and_near():
    rows = profiler._op_table(_hand_made(), TEXT)
    assert set(rows) == {
        "s1/s1_fc", "_remat_s1/s1_fc", "_backward_s1/s1_fc", "lstm",
        "step.update", "late", "hlo:copy", "hlo:slice-done",
        "ragged-dot-none"}
    us = {k: v["total_us"] for k, v in rows.items()}
    # the while's 1,000 ns less its body's 500; the body's under the node too
    assert us["lstm"] == pytest.approx(1.0)
    assert rows["lstm"]["count"] == 3
    assert rows["lstm"]["min_us"] == pytest.approx(0.2)
    assert rows["lstm"]["max_us"] == pytest.approx(0.5)
    assert us["_backward_s1/s1_fc"] == pytest.approx(0.1)
    assert rows["_backward_s1/s1_fc"]["avg_us"] == pytest.approx(0.05)
    assert {k: rows["_remat_s1/s1_fc"][k] for k in
            ("stage", "pass", "node")} == {
        "stage": "s1", "pass": "remat", "node": "s1_fc"}
    assert {k: rows["lstm"][k] for k in ("stage", "pass", "node")} == {
        "stage": "", "pass": "forward", "node": "lstm"}
    # an event's own path (stats) goes before the text's
    assert us["late"] == pytest.approx(0.008)
    # the compiler's own: by what ran before them on the line
    assert rows["hlo:copy"]["near"] == {"s1": 0.01, "_backward_s1": 0.005}
    assert rows["hlo:slice-done"]["near"] == {"step.update": 0.002}
    assert rows["ragged-dot-none"]["near"] == {"_remat_s1": 0.03}
    assert "stage" not in rows["hlo:copy"]
    # every nanosecond of the line's busy time is in one row
    assert sum(us.values()) == pytest.approx(
        (100 + 10 + 1000 + 50 + 30 + 70 + 30 + 5 + 40 + 2 + 8) / 1e3)


def test_without_a_text_every_row_is_the_compilers_own():
    rows = profiler._op_table(_hand_made())
    assert set(rows) == {"hlo:fusion", "hlo:copy", "hlo:while",
                         "hlo:ragged", "hlo:slice-done", "late"}
    assert rows["hlo:fusion"]["near"] == {"": pytest.approx(0.79)}
    assert rows["hlo:while"]["total_us"] == pytest.approx(0.5)
    assert sum(r["total_us"] for r in rows.values()) == pytest.approx(1.345)


def _keep(tmp_path, events, text=None):
    """The events as a kept trace (``run.py --keep-trace``'s structure)
    with the step text beside it."""
    path = str(tmp_path / "kept.trace.json.gz")
    lines = [{"name": "XLA Ops", "events": [
        [name, start, dur, {"tf_op": p} if p else {}]
        for start, dur, name, p in events]}]
    idle = [{"name": "XLA Ops", "events": [["%copy.1 = ...", 0, 5, {}]]}]
    with gzip.open(path, "wt") as f:
        json.dump({"planes": [
            {"name": "/device:TPU:1", "lines": idle},
            {"name": "/device:TPU:0", "lines": lines},
            {"name": "/host:CPU", "lines": []}]}, f)
    if text:
        with open(path + ".hlo.txt", "w") as f:
            f.write(text)
    return path


def test_dumps_prints_a_stages_passes_above_its_nodes(tmp_path):
    table = profiler.dumps(trace_dir=_keep(tmp_path, _hand_made(), TEXT))
    lines = table.splitlines()
    assert lines[0].startswith("Profile Statistics")
    assert "1.345 us" in lines[0]
    at = {key: i for i, line in enumerate(lines)
          for key in [line.split()[0] if line.split() else ""]}
    assert lines[at["stage"]].startswith("stage s1: 0.250 us")
    # forward, rematerialised and backward subtotals, each above its nodes
    assert at["stage"] < at["s1"] < at["s1/s1_fc"] < at["_remat_s1"] \
        < at["_remat_s1/s1_fc"] < at["_backward_s1"] \
        < at["_backward_s1/s1_fc"] < at["under"]
    assert "(remat)" in lines[at["_remat_s1"]]
    assert float(lines[at["_backward_s1"]].split()[-1]) == pytest.approx(0.1)
    # one line each for a node under no stage and for the trainer's scope
    assert at["under"] < at["lstm"] < at["step.update"] < at["late"]
    # the compiler's own, grouped by what ran before them
    own = table[table.index("the compiler's own"):]
    assert "adjacency" in own and "0.047 us" in own.splitlines()[0]
    assert own.index("near _remat_s1: 0.030 us") \
        < own.index("near s1: 0.010 us") \
        < own.index("near _backward_s1: 0.005 us") \
        < own.index("near step.update: 0.002 us")
    # the text is an argument too, and goes before the file beside the trace
    assert "late" in profiler.dumps(
        trace_dir=_keep(tmp_path, _hand_made()), hlo_text=TEXT)
    assert "s1/s1_fc" not in profiler.dumps(
        trace_dir=_keep(tmp_path, _hand_made()), hlo_text="")


def test_the_busiest_devices_line_is_read_and_a_trace_without_one_says_so(
        tmp_path):
    rows = profiler.get_op_stats(_keep(tmp_path, _hand_made(), TEXT))
    assert "s1/s1_fc" in rows and rows["hlo:copy"]["count"] == 2
    with gzip.open(tmp_path / "none.json.gz", "wt") as f:
        json.dump({"planes": [{"name": "/host:CPU", "lines": []}]}, f)
    with pytest.raises(MXNetError, match="no device plane"):
        profiler.get_op_stats(str(tmp_path / "none.json.gz"))
    with pytest.raises(MXNetError, match="xplane"):
        profiler.get_op_stats(str(tmp_path / "nowhere"))
