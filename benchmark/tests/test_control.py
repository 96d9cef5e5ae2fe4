"""`correct` has to be able to come out false.  At toy widths on the CPU:
the control (the reference in fp8 in the program's place) fails the limits,
and a run whose timed path is broken underneath — a step that returns its
state unchanged, half of the batch left out, on four (virtual) chips the
exchange left out — ends with ``correct`` false.
The readings the real limits were set from were taken on the chip at the
cells' own sizes (``tools/limits.py``; PERF.md)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TOY = os.path.join(HERE, "data", "toy_spec.json")


def _toy(cell):
    from benchmark import run
    return run.load_cell(cell, TOY)


def _batches(job, n=3):
    import numpy as np
    rs = np.random.RandomState(5)
    m = job.model
    out = []
    for _ in range(n):
        if "image_shape" in m:
            c, h, w = m["image_shape"]
            out.append({"data": rs.randint(0, 256, (job.batch, h, w, c))
                        .astype(np.uint8),
                        "softmax_label": rs.randint(
                            0, m["num_classes"], job.batch).astype("f")})
        else:
            out.append({"data": rs.randint(0, m["vocab_size"],
                                           (job.batch, m["seq_len"])),
                        "softmax_label": rs.randint(
                            0, m["vocab_size"], (job.batch, m["seq_len"]))})
    return out


@pytest.mark.parametrize("cell", ["lstm_toy.train", "resnet_toy.fed"])
def test_the_fp8_control_fails_and_the_reference_passes_itself(cell):
    import jax

    from benchmark import compare, datagen
    from benchmark.jobs.train_fit import Job
    from benchmark.reference import common
    _, c, config, traffic, limits = _toy(cell)
    job = Job(c, config, traffic, limits, 11, None)
    batches = _batches(job)
    params, aux = jax.jit(lambda k: job.ref.init(k, job.model))(
        datagen.jax_key(11, 3))
    ref = common.follow(job._reference_step("f32"), params, aux, batches)
    ok, _ = compare.judge(compare.training_gaps(
        common.differences(ref, ref), ref), limits)
    assert ok
    gaps = {p: compare.training_gaps(
        common.differences(job.compare(p, batches), ref), ref)
        for p in ("bf16", "fp8")}
    ok, shown = compare.judge(gaps["fp8"], limits)
    assert not ok, shown
    # fp8 reads well above bf16 on the number that is steady from seed to
    # seed (the worst leaf's gap saturates in both on a random-init net)
    assert gaps["fp8"]["grad1_mid_gap"][0] > \
        3 * gaps["bf16"]["grad1_mid_gap"][0], gaps


def _unchanged_state(trainer):
    """The step returns the state it was given."""
    import jax
    import jax.numpy as jnp
    orig = trainer._step_fn

    def step(*args):
        kept = jax.tree.map(jnp.copy, tuple(args[:3]))
        out = orig(*args)
        return kept + tuple(out[3:])
    trainer._step_fn = step


def _rows_replaced(keep):
    """Every staged input keeps its first ``1/keep`` of the rows, repeated:
    ``keep=2`` is half of the batch left out and the mean taken over the
    rest; ``keep=4`` on four chips is what each chip would compute from
    its own rows alone, the exchange left out."""
    def fault(batch):
        import jax
        import jax.numpy as jnp
        for k, v in batch.staged.items():
            n = v.shape[0] // keep
            batch.staged[k] = jax.device_put(
                jnp.concatenate([v[:n]] * keep, axis=0), v.sharding)
        return batch
    return fault


FAULTS = {"unchanged_state": {"trainer": _unchanged_state},
          "half_left_out": {"batch": _rows_replaced(2)},
          "exchange_left_out": {"batch": _rows_replaced(4)}}


@pytest.mark.parametrize("cell,fault", [
    ("lstm_toy.train", "unchanged_state"),
    ("lstm_toy.train", "half_left_out"),
    ("resnet_toy.fed", "unchanged_state"),
    ("resnet_toy.fed", "half_left_out"),
    ("resnet_toy.fed_dp4", "unchanged_state"),
    ("resnet_toy.fed_dp4", "exchange_left_out"),
])
def test_a_broken_timed_path_reads_not_correct(cell, fault, capfd):
    import jax

    from benchmark import run
    chips = 4 if cell.endswith("dp4") else 1
    rc = run.main(["--workload", cell, "--seed", "99", "--seconds", "0.3",
                   "--trace", "0"], devices=jax.devices()[:chips],
                  spec_path=TOY, faults=FAULTS[fault])
    assert rc == 0
    out, _ = capfd.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False, line["compared"]
    over = [k for k, v in line["compared"].items()
            if v["limit"] is not None and not v["value"] <= v["limit"]]
    assert over, line["compared"]
