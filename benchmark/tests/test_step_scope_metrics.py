"""``step_update_ms`` and ``step_unnamed_share`` on hand-made ``facts``:
what the readers sum from a traced slice's ``scopes_s`` (the keys
``trace_reduce.scope_of`` gives the trainer's ``step.*`` scopes and the
compiler's own events), nothing where nothing was traced, and nothing of
the optimizer's from a program whose trainer writes no scope (the parent of
the PR that brought them)."""
import json
import os

import pytest

from benchmark import trace_reduce
from benchmark.tests.test_span_metrics import ROOT
from benchmark.metrics import step_unnamed_share, step_update_ms

CELLS = {"resnet50.fed", "lstm_ptb_large.train", "resnet50.fed_dp4",
         "qwen3_next_80b_a3b.train_8k", "kimi_linear_48b_a3b.train_8k",
         "zaya1_8b.train_8k"}

# three traced steps: 1.0 s of device self time in all
SCOPES = {
    "l1_kda": 0.3, "_backward_l1_kda": 0.4, "head": 0.05,
    "step.update": 0.030, "step.update/jit(_where)": 0.012,
    "step.guard": 0.003, "step.guard/jit(_where)": 0.0003,
    "step.cast": 0.02, "_backward_step.cast": 0.0047, "step.metric": 0.01,
    "hlo:copy": 0.06, "hlo:slice-done": 0.02, "ragged-dot-none": 0.08,
    "ragged-dot-metadata": 0.01,
}


def facts(scopes, steps=3):
    return {"trace": scopes and {"scopes_s": scopes},
            "window": {"traced_steps": steps}}


def test_update_and_guard_scopes_are_summed_a_step():
    assert step_update_ms.read(facts(SCOPES)) == pytest.approx(
        1e3 * (0.030 + 0.012 + 0.003 + 0.0003) / 3)


def test_the_compilers_own_events_over_the_whole():
    assert sum(SCOPES.values()) == pytest.approx(1.0)
    assert step_unnamed_share.read(facts(SCOPES)) == pytest.approx(
        100 * (0.06 + 0.02 + 0.08 + 0.01))


@pytest.mark.parametrize("reader,given", [
    (step_update_ms, facts(None)), (step_update_ms, facts(SCOPES, steps=0)),
    (step_update_ms, facts({})), (step_unnamed_share, facts(None)),
    (step_unnamed_share, facts({}))],
    ids=["update_untraced", "update_no_traced_step", "update_no_scope",
         "unnamed_untraced", "unnamed_no_scope"])
def test_nothing_where_nothing_was_traced(reader, given):
    assert reader.read(given) is None


def test_a_program_without_the_scopes_reads_no_update_time():
    """The parent's trace: the update reads ``jit(_where)``, the guard
    ``reduce_and``; the unnamed share needs no scope of the trainer's."""
    old = {"l1_kda": 0.7, "jit(_where)": 0.05, "reduce_and": 0.01,
           "convert_element_type": 0.04, "hlo:copy": 0.2}
    assert step_update_ms.read(facts(old)) is None
    assert step_unnamed_share.read(facts(old)) == pytest.approx(20.0)


@pytest.mark.parametrize("path,want", [
    ("jit(step)/step.update/mul", "step.update"),
    ("jit(step)/step.update/jit(_where)/select_n",
     "step.update/jit(_where)"),
    ("jit(step)/step.guard/reduce_and", "step.guard"),
    ("jit(step)/jvp(step.cast)/convert_element_type", "step.cast"),
    ("jit(step)/transpose(jvp(step.cast))/convert_element_type",
     "_backward_step.cast"),
], ids=["update", "update_select", "guard", "cast", "cast_backward"])
def test_the_keys_the_reduction_gives_the_trainers_scopes(path, want):
    """What ``scopes_s`` calls an instruction traced under a ``step.*``
    scope: the prefix the readers match."""
    event = ["%fusion.1 = f32[8]{0} fusion(...)", 0.0, 1.0, {}]
    assert trace_reduce.scope_of(event, {"fusion.1": path}) == want


@pytest.mark.parametrize("name,unit", [("step_update_ms", "ms"),
                                       ("step_unnamed_share", "%")])
def test_the_metrics_are_in_the_spec_and_the_cells_among_theirs(name, unit):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"] if m["name"] == name]
    assert CELLS <= set(entry.pop("workloads"))
    assert entry == {
        "name": name, "unit": unit, "better": "lower",
        "source": "device_trace", "layer": "step",
        "moves": "train_throughput"}
    assert CELLS <= {w["name"] for w in spec["workloads"]}
