"""mxlint static-analyzer tests (docs/how_to/static_analysis.md).

Three layers of proof:

1. Each graph rule (donation, callback, collective, dtype) is exercised
   BOTH ways — a seeded violation is reported, the clean variant is not.
2. The shipped tree passes: the standard MLP fused step lints clean
   (every carry donated, no callbacks, only the expected dp all-reduces)
   and the whole ``mxnet_tpu/`` package has zero AST findings — the
   regression gate every future PR rides through.
3. The env registry, the code's actual env reads, and the
   ``docs/env_vars.md`` table are asserted to be one set.
"""
import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu.analysis import ast_lint, graph_lint
from mxnet_tpu.analysis.fixtures import (standard_mlp_batch as batch,
                                         standard_mlp_sym as mlp_sym,
                                         standard_mlp_trainer as
                                         make_trainer)
from mxnet_tpu.parallel import SPMDTrainer, local_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "mxnet_tpu")


def rules_of(report):
    return sorted({f.rule for f in report.findings})


# ---------------------------------------------------------------------------
# graph rules, seeded violation vs clean (acceptance criterion)
# ---------------------------------------------------------------------------

def _dp_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))


def _carry_step(params, data):
    w = params["w"]
    out = data @ w
    return {"w": w - 0.01 * out.sum() * w}, out


def _carry_args(mesh):
    w = jax.device_put(jnp.ones((64, 32)), NamedSharding(mesh, P()))
    d = jax.device_put(jnp.ones((8, 64)), NamedSharding(mesh, P("dp")))
    return {"w": w}, d


def test_donation_missing_flagged_and_clean():
    mesh = _dp_mesh()
    params, d = _carry_args(mesh)
    bad = graph_lint.lint_jit(_carry_step, params, d, donate_argnums=(),
                              expect_allgather=False, min_donate_bytes=0)
    assert "graph-donation-missing" in rules_of(bad), bad.format_text()
    good = graph_lint.lint_jit(_carry_step, params, d, donate_argnums=(0,),
                               expect_allgather=False, min_donate_bytes=0)
    assert good.ok, good.format_text()


def test_donation_unused_flagged():
    mesh = _dp_mesh()
    params, d = _carry_args(mesh)
    # donating the DATA batch is wasted: no output has its shape
    rep = graph_lint.lint_jit(_carry_step, params, d,
                              donate_argnums=(0, 1),
                              expect_allgather=False, min_donate_bytes=0)
    assert "graph-donation-unused" in rules_of(rep), rep.format_text()


def test_donation_threshold_respected():
    mesh = _dp_mesh()
    params, d = _carry_args(mesh)
    # the undonated carry is 8 KiB — below a 1 MiB threshold it is not
    # worth a finding (generic jit fns legitimately pass small carries)
    rep = graph_lint.lint_jit(_carry_step, params, d, donate_argnums=(),
                              expect_allgather=False,
                              min_donate_bytes=1 << 20)
    assert "graph-donation-missing" not in rules_of(rep)


def test_callback_flagged_and_clean():
    mesh = _dp_mesh()
    params, d = _carry_args(mesh)

    def leaky(params, data):
        jax.debug.callback(lambda v: None, data.sum())
        return _carry_step(params, data)

    bad = graph_lint.lint_jit(leaky, params, d, donate_argnums=(0,),
                              expect_allgather=False, min_donate_bytes=0)
    assert "graph-callback" in rules_of(bad), bad.format_text()
    good = graph_lint.lint_jit(_carry_step, params, d, donate_argnums=(0,),
                               expect_allgather=False, min_donate_bytes=0)
    assert "graph-callback" not in rules_of(good)


def test_callback_found_in_nested_jaxpr():
    mesh = _dp_mesh()
    params, d = _carry_args(mesh)

    def scanny(params, data):
        def body(c, _):
            jax.debug.callback(lambda v: None, c)
            return c + 1.0, None
        c, _ = jax.lax.scan(body, data.sum(), None, length=3)
        return {"w": params["w"] * c}, data @ params["w"]

    rep = graph_lint.lint_jit(scanny, params, d, donate_argnums=(0,),
                              expect_allgather=False, min_donate_bytes=0)
    assert "graph-callback" in rules_of(rep), rep.format_text()


def test_pallas_no_vjp_flagged_and_clean():
    """graph-pallas-no-vjp (satellite): a raw pallas_call reachable from
    a step fails analysis — Pallas has no reverse-mode transpose, so
    differentiation would die at trace time (rtc.py documents the
    hazard); the same kernel behind a registered custom_vjp (the
    kernels/ pattern) is clean."""
    mesh = _dp_mesh()
    params, d = _carry_args(mesh)
    raw = mx.rtc.elementwise_pallas_kernel(
        lambda in_ref, out_ref: out_ref.__setitem__(..., in_ref[...] * 2.0))

    def bad_step(params, data):
        out = raw(data @ params["w"])
        return {"w": params["w"] * 0.99}, out

    bad = graph_lint.lint_jit(bad_step, params, d, donate_argnums=(0,),
                              expect_allgather=False, min_donate_bytes=0)
    assert "graph-pallas-no-vjp" in rules_of(bad), bad.format_text()

    from mxnet_tpu.kernels.lstm_cell import lstm_cell_pallas

    def good_step(params, data):
        gates = jnp.concatenate([data @ params["w"]] * 4, axis=-1)
        h, c = lstm_cell_pallas(gates, data @ params["w"], interpret=True)
        return {"w": params["w"] * 0.99}, h + c

    good = graph_lint.lint_jit(good_step, params, d, donate_argnums=(0,),
                               expect_allgather=False, min_donate_bytes=0)
    assert "graph-pallas-no-vjp" not in rules_of(good), good.format_text()


def test_pallas_no_vjp_found_in_nested_jaxpr():
    """The rule descends into scan bodies — a raw kernel inside a
    lax.scan (exactly where an RNN cell would live) is still caught."""
    mesh = _dp_mesh()
    params, d = _carry_args(mesh)
    raw = mx.rtc.elementwise_pallas_kernel(
        lambda in_ref, out_ref: out_ref.__setitem__(..., in_ref[...] + 1.0))

    def scanny(params, data):
        def body(c, _):
            return raw(c), None
        c, _ = jax.lax.scan(body, data @ params["w"], None, length=2)
        return {"w": params["w"]}, c

    rep = graph_lint.lint_jit(scanny, params, d, donate_argnums=(0,),
                              expect_allgather=False, min_donate_bytes=0)
    assert "graph-pallas-no-vjp" in rules_of(rep), rep.format_text()


def test_collective_audit_flags_unexpected_allgather():
    mesh = _dp_mesh()
    w = jax.device_put(jnp.ones((64, 32)), NamedSharding(mesh, P("dp")))
    x = jax.device_put(jnp.ones((8, 64)), NamedSharding(mesh, P("dp")))

    def regather(w, x):
        # forcing the dp-sharded weight replicated = a full-param AG
        full = jax.lax.with_sharding_constraint(
            w, NamedSharding(mesh, P()))
        return x @ full

    rep = graph_lint.lint_jit(regather, w, x, expect_allgather=False,
                              param_bytes=64 * 32 * 4,
                              min_donate_bytes=1 << 30)
    assert "graph-collective-allgather" in rules_of(rep), rep.format_text()
    ag = rep.stats["collectives"]["all-gather"]
    assert ag["count"] >= 1 and ag["bytes"] >= 64 * 32 * 4
    # the same traffic under a sharding that EXPECTS gathering is clean
    ok = graph_lint.lint_jit(regather, w, x, expect_allgather=True,
                             min_donate_bytes=1 << 30)
    assert "graph-collective-allgather" not in rules_of(ok)


def test_dtype_drift_flagged_and_clean():
    w = jnp.ones((64, 32), jnp.bfloat16)
    x = jnp.ones((8, 64), jnp.bfloat16)

    def drifty(w, x):
        return (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(
            jnp.bfloat16)

    bad = graph_lint.lint_jit(drifty, w, x, compute_dtype="bfloat16",
                              min_donate_bytes=1 << 30)
    assert "graph-dtype-drift" in rules_of(bad), bad.format_text()

    def clean(w, x):
        return x @ w

    good = graph_lint.lint_jit(clean, w, x, compute_dtype="bfloat16",
                               min_donate_bytes=1 << 30)
    assert "graph-dtype-drift" not in rules_of(good)
    assert good.stats["compute_eqn_dtypes"]["dot_general"] == \
        {"bfloat16": 1}


# ---------------------------------------------------------------------------
# the shipped fused step lints clean (regression guard)
# ---------------------------------------------------------------------------

def test_mlp_fused_step_clean():
    """The standard MLP step: every param/opt-state/guard carry donated,
    no callbacks, only dp all-reduce traffic.  THE gate that keeps
    future PRs from leaking a host sync or an HBM copy into the step."""
    trainer = make_trainer()
    try:
        rep = trainer.analyze(*batch())
        assert rep.ok, rep.format_text()
        stats = rep.stats["collectives"]
        assert "all-gather" not in stats, stats
        assert stats.get("all-reduce", {}).get("count", 0) >= 1, stats
    finally:
        trainer.close()


def test_mlp_step_with_metric_and_momentum_clean():
    """Momentum slots and deferred-metric accumulators join the carry —
    they must all be donated too."""
    trainer = SPMDTrainer(mlp_sym(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9},
                          mesh=local_mesh("dp"))
    trainer.bind([("data", (64, 32))], [("softmax_label", (64,))])
    mx.random.seed(7)
    trainer.init_params(mx.initializer.Xavier())
    metric = mx.metric.Accuracy()
    fn = metric.graph_update(["softmax_label"])
    assert fn is not None
    trainer.install_metric(fn, key="acc-test")
    try:
        rep = trainer.analyze(*batch())
        assert rep.ok, rep.format_text()
    finally:
        trainer.close()


def test_mlp_jaxpr_has_no_callbacks():
    """Direct jaxpr assertion (independent of the report plumbing)."""
    trainer = make_trainer()
    try:
        X, y = batch()
        data = trainer._shard_batch((X, y))
        extras = {"guard": (jnp.zeros((), jnp.int32),) * 3}
        closed = jax.make_jaxpr(trainer._step_raw)(
            trainer.params, trainer.aux, trainer.opt_state, extras, data,
            jax.random.PRNGKey(0), jnp.float32(0.1), jnp.float32(0.0), 1)
        prims = {e.primitive.name for e in graph_lint.iter_eqns(closed)}
        assert not (prims & graph_lint.CALLBACK_PRIMITIVES), prims
    finally:
        trainer.close()


def test_fixture_trainer_donation_violation_flagged():
    """Satellite regression fixture: a trainer that 'forgets' donation
    is caught — params, and the guard accumulators, all flagged."""
    class UndonatedTrainer(SPMDTrainer):
        DONATE_ARGNUMS = ()

    trainer = make_trainer(cls=UndonatedTrainer)
    try:
        rep = trainer.analyze(*batch())
        missing = [f for f in rep.findings
                   if f.rule == "graph-donation-missing"]
        # 4 params (no momentum -> no opt slots) + the stacked i32[3]
        # guard-counter carry (one leaf since the single-fetch change)
        assert len(missing) == 5, rep.format_text()
        text = "\n".join(f.message for f in missing)
        # all four params and the guard counters are individually named
        for name in ("fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias",
                     "guard"):
            assert name in text, text
    finally:
        trainer.close()


def test_fixture_trainer_callback_violation_flagged():
    def leaky(x):
        jax.debug.callback(lambda v: None, x.sum())
        return x

    trainer = SPMDTrainer(mlp_sym(), "sgd", {"learning_rate": 0.1},
                          mesh=local_mesh("dp"),
                          input_transforms={"data": leaky})
    trainer.bind([("data", (64, 32))], [("softmax_label", (64,))])
    mx.random.seed(7)
    trainer.init_params(mx.initializer.Xavier())
    try:
        rep = trainer.analyze(*batch())
        assert "graph-callback" in rules_of(rep), rep.format_text()
    finally:
        trainer.close()


def test_fixture_trainer_dtype_violation_flagged():
    """An input transform that widens to f32 inside a bf16 step."""
    trainer = SPMDTrainer(
        mlp_sym(), "sgd", {"learning_rate": 0.1}, mesh=local_mesh("dp"),
        compute_dtype="bfloat16",
        input_transforms={"data": lambda x: x.astype(jnp.float32)})
    trainer.bind([("data", (64, 32))], [("softmax_label", (64,))])
    mx.random.seed(7)
    trainer.init_params(mx.initializer.Xavier())
    try:
        rep = trainer.analyze(*batch())
        assert "graph-dtype-drift" in rules_of(rep), rep.format_text()
    finally:
        trainer.close()


def test_bf16_trainer_clean():
    trainer = make_trainer(compute_dtype="bfloat16")
    try:
        rep = trainer.analyze(*batch())
        assert "graph-dtype-drift" not in rules_of(rep), rep.format_text()
    finally:
        trainer.close()


def test_autoencoder_shaped_output_not_flagged_as_carry():
    """A model whose OUTPUT shares the data batch's shape/dtype (an
    autoencoder reconstruction): the data arg must not be reported as an
    un-donated carry — the trainer restricts the donation audit to the
    params/aux/opt_state/extras argnums."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="enc")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=32, name="dec")
    net = mx.sym.LinearRegressionOutput(net, name="rec")
    trainer = SPMDTrainer(net, "sgd", {"learning_rate": 0.01},
                          mesh=local_mesh("dp"))
    # label shape == data shape == output shape (64, 32)
    trainer.bind([("data", (64, 32))], [("rec_label", (64, 32))])
    mx.random.seed(7)
    trainer.init_params(mx.initializer.Xavier())
    X = np.random.RandomState(0).randn(64, 32).astype("f")
    try:
        rep = trainer.analyze(X, X)
        assert "graph-donation-missing" not in rules_of(rep), \
            rep.format_text()
    finally:
        trainer.close()
    # the generic API (no carry_argnums) still reports the match — the
    # restriction is the trainer's knowledge, not a weaker rule
    mesh = _dp_mesh()
    params, d = _carry_args(mesh)

    def echoes(params, data):
        return {"w": params["w"] * 0.9}, data * 2.0

    loose = graph_lint.lint_jit(echoes, params, d, donate_argnums=(0,),
                                expect_allgather=False,
                                min_donate_bytes=0)
    assert "graph-donation-missing" in rules_of(loose)


def test_collective_stats_async_start_counts_payload_only():
    """Async '-start' result tuples carry input-alias/context buffers;
    only the payload (largest) shape may count.  Sync tuple results are
    fused multi-tensor collectives and SUM."""
    hlo = "\n".join((
        "%ag = (f32[16,64]{1,0}, f32[128,64]{1,0}) "
        "all-gather-start(f32[16,64]{1,0} %p), dimensions={0}",
        "%agd = f32[128,64]{1,0} all-gather-done((...) %ag)",
        "%ar = (f32[8,8]{1,0}, f32[4]{0}) all-reduce(f32[8,8]{1,0} %a, "
        "f32[4]{0} %b), to_apply=%sum",
    ))
    stats = graph_lint.collective_stats(hlo)
    assert stats["all-gather"] == {"count": 1, "bytes": 128 * 64 * 4}
    assert stats["all-reduce"] == {"count": 1,
                                   "bytes": 8 * 8 * 4 + 4 * 4}
    # reduce-scatter-start: the RESULT is operand/N (second-largest) —
    # max() would report the operand, inflating bytes by the mesh size
    rs = ("%rs = (f32[128,64]{1,0}, f32[16,64]{1,0}, u32[]) "
          "reduce-scatter-start(f32[128,64]{1,0} %g), dimensions={0}")
    stats2 = graph_lint.collective_stats(rs)
    assert stats2["reduce-scatter"] == {"count": 1, "bytes": 16 * 64 * 4}


def test_traced_host_ignores_same_named_method(tmp_path):
    """jax.jit(step, ...) on a closure must not drag a same-named class
    METHOD (referenced as self.step, never a bare Name) into the scan —
    a host clock read in SPMDTrainer.step would be a false positive.  A
    method with its own @jit decorator is still covered."""
    src = """
    import time
    import jax

    def build():
        def step(x):
            return x * 2
        return jax.jit(step, donate_argnums=(0,))

    class Trainer(object):
        def step(self, x):
            t0 = time.monotonic()   # host code: legitimate
            return x, t0

        @jax.jit
        def fused(self, x):
            return bool(x)          # decorated method: still scanned
    """
    rep = _lint_snippet(tmp_path, src)
    traced = [f for f in rep.findings if f.rule == "traced-host-call"]
    assert len(traced) == 1, rep.format_text()
    assert "fused" in traced[0].message


# ---------------------------------------------------------------------------
# MXTPU_ANALYZE wiring
# ---------------------------------------------------------------------------

def test_env_analyze_strict_refuses_violating_step(monkeypatch):
    monkeypatch.setenv("MXTPU_ANALYZE", "strict")

    def leaky(x):
        jax.debug.callback(lambda v: None, x.sum())
        return x

    trainer = SPMDTrainer(mlp_sym(), "sgd", {"learning_rate": 0.1},
                          mesh=local_mesh("dp"),
                          input_transforms={"data": leaky})
    trainer.bind([("data", (64, 32))], [("softmax_label", (64,))])
    mx.random.seed(7)
    trainer.init_params(mx.initializer.Xavier())
    try:
        with pytest.raises(mx.MXNetError, match="graph-callback"):
            trainer.step(*batch())
    finally:
        trainer.close()


def test_env_analyze_strict_covers_retraced_shapes(monkeypatch):
    """A partial final batch retraces a SECOND program — strict mode
    must lint that one too, not just the first compile."""
    monkeypatch.setenv("MXTPU_ANALYZE", "strict")

    def leaky(x):
        # violate only in the retraced (32-row) program: the first
        # (64-row) step must pass, proving the gate is per-signature
        if x.shape[0] == 32:
            jax.debug.callback(lambda v: None, x.sum())
        return x

    trainer = SPMDTrainer(mlp_sym(), "sgd", {"learning_rate": 0.1},
                          mesh=local_mesh("dp"),
                          input_transforms={"data": leaky})
    trainer.bind([("data", (64, 32))], [("softmax_label", (64,))])
    mx.random.seed(7)
    trainer.init_params(mx.initializer.Xavier())
    X, y = batch()
    try:
        trainer.step(X, y)          # full batch: clean, runs
        with pytest.raises(mx.MXNetError, match="graph-callback"):
            trainer.step(X[:32], y[:32])   # retraced variant: refused
    finally:
        trainer.close()


def test_env_analyze_warn_mode_still_trains(monkeypatch, caplog):
    import logging
    monkeypatch.setenv("MXTPU_ANALYZE", "1")
    trainer = make_trainer()
    try:
        with caplog.at_level(logging.INFO,
                             logger="mxnet_tpu.parallel.trainer"):
            outs = trainer.step(*batch())
        assert np.asarray(outs[0]).shape == (64, 10)
        assert any("MXTPU_ANALYZE" in r.message for r in caplog.records)
    finally:
        trainer.close()


# ---------------------------------------------------------------------------
# AST level: the shipped package is clean; each rule proven on fixtures
# ---------------------------------------------------------------------------

def test_package_ast_lint_zero_findings():
    from mxnet_tpu.base import ENV_REGISTRY
    rep = ast_lint.lint_paths([PKG], env_registry=set(ENV_REGISTRY))
    assert rep.files_scanned > 50
    assert rep.ok, rep.format_text()


def _lint_snippet(tmp_path, source, **kwargs):
    path = tmp_path / "snippet.py"
    path.write_text(textwrap.dedent(source))
    return ast_lint.lint_paths([str(path)], **kwargs)


def test_bare_except_flagged_and_suppressed(tmp_path):
    src = """
    def f():
        try:
            return 1
        except:
            return 2
    """
    rep = _lint_snippet(tmp_path, src)
    assert rules_of(rep) == ["bare-except"]
    src_ok = src.replace("except:",
                         "except:  # mxlint: disable=bare-except")
    rep2 = _lint_snippet(tmp_path, src_ok)
    assert rep2.ok, rep2.format_text()


def test_traced_host_calls_flagged(tmp_path):
    src = """
    import time
    import jax

    def step(x):
        y = float(x)
        t = time.time()
        z = x.item()
        return x * y * t * z

    step_fn = jax.jit(step, donate_argnums=(0,))

    def host_only(x):
        return float(x)  # not jitted: fine
    """
    rep = _lint_snippet(tmp_path, src)
    traced = [f for f in rep.findings if f.rule == "traced-host-call"]
    assert len(traced) == 3, rep.format_text()


def test_traced_host_decorator_form(tmp_path):
    src = """
    import jax
    from functools import partial

    @partial(jax.jit, static_argnums=(1,))
    def step(x, n):
        return bool(x) and n

    @jax.jit
    def other(x):
        return x.item()
    """
    rep = _lint_snippet(tmp_path, src)
    assert len([f for f in rep.findings
                if f.rule == "traced-host-call"]) == 2, rep.format_text()


def test_lock_order_cycle_flagged(tmp_path):
    src = """
    import threading

    _a = threading.Lock()
    _b = threading.Lock()

    def forward():
        with _a:
            with _b:
                pass

    def backward():
        with _b:
            with _a:
                pass
    """
    rep = _lint_snippet(tmp_path, src)
    assert "lock-order" in rules_of(rep), rep.format_text()
    # consistent ordering everywhere: no cycle, no finding
    src_ok = src.replace("with _b:\n            with _a:",
                         "with _a:\n            with _b:")
    rep2 = _lint_snippet(tmp_path, src_ok)
    assert rep2.ok, rep2.format_text()


def test_lock_order_multi_item_with(tmp_path):
    """``with a, b:`` acquires sequentially — it must edge a->b so the
    reversed nested form elsewhere closes the cycle."""
    src = """
    import threading

    _a = threading.Lock()
    _b = threading.Lock()

    def forward():
        with _a, _b:
            pass

    def backward():
        with _b:
            with _a:
                pass
    """
    rep = _lint_snippet(tmp_path, src)
    assert "lock-order" in rules_of(rep), rep.format_text()


def test_lock_order_through_method_call(tmp_path):
    src = """
    import threading

    class Pipe(object):
        def __init__(self):
            self._head = threading.Lock()
            self._tail = threading.Lock()

        def push(self):
            with self._head:
                self._drain()

        def _drain(self):
            with self._tail:
                pass

        def steal(self):
            with self._tail:
                with self._head:
                    pass
    """
    rep = _lint_snippet(tmp_path, src)
    assert "lock-order" in rules_of(rep), rep.format_text()


def test_env_rules_flagged(tmp_path):
    src = """
    import os
    from mxnet_tpu.base import get_env

    direct = os.environ.get("MXTPU_SOMETHING_DIRECT")
    typo = get_env("MXTPU_TYPO_KNOB", "1")
    fine = get_env("MXTPU_STEP_GUARD", "1")
    other = os.environ.get("HOME")  # non-framework: not our business
    """
    rep = _lint_snippet(tmp_path, src,
                        env_registry={"MXTPU_STEP_GUARD"})
    assert rules_of(rep) == ["env-direct-read", "env-unregistered"], \
        rep.format_text()


def test_env_constant_resolution(tmp_path):
    """Reads through ENV_* constants (including register_env returns)
    resolve to their string values."""
    src = """
    from mxnet_tpu.base import get_env, register_env

    ENV_GOOD = register_env("MXTPU_GOOD_KNOB")
    ENV_BAD = "MXTPU_NEVER_REGISTERED"

    a = get_env(ENV_GOOD)
    b = get_env(ENV_BAD)
    """
    rep = _lint_snippet(tmp_path, src)
    assert rules_of(rep) == ["env-unregistered"], rep.format_text()
    assert "MXTPU_NEVER_REGISTERED" in rep.findings[0].message


# ---------------------------------------------------------------------------
# env registry <-> docs <-> code three-way sync (satellite)
# ---------------------------------------------------------------------------

def _documented_mxtpu_vars():
    path = os.path.join(REPO, "docs", "env_vars.md")
    with open(path) as f:
        text = f.read()
    # first cell of each table row only — prose mentions don't count
    return set(re.findall(r"^\|\s*`(MXTPU_[A-Z0-9_]+)`", text,
                          flags=re.M))


def test_env_registry_matches_docs():
    from mxnet_tpu.base import ENV_REGISTRY
    registered = {n for n in ENV_REGISTRY if n.startswith("MXTPU_")}
    documented = _documented_mxtpu_vars()
    assert registered == documented, (
        "registry/docs drift: undocumented=%s, unregistered-doc-rows=%s"
        % (sorted(registered - documented),
           sorted(documented - registered)))


def test_every_code_read_is_registered():
    """Every MXTPU_* env var actually read anywhere in the tree (package,
    tools, tests) is a registered knob — the typo'd-knob regression
    gate."""
    from mxnet_tpu.base import ENV_REGISTRY
    reads = ast_lint.collect_env_reads(
        [PKG, os.path.join(REPO, "tools"), os.path.join(REPO, "tests")])
    read_names = {n for n in reads if n.startswith("MXTPU_")}
    unregistered = read_names - set(ENV_REGISTRY)
    assert not unregistered, (
        "env vars read but not registered: %s (sites: %s)"
        % (sorted(unregistered),
           {n: reads[n][:3] for n in sorted(unregistered)}))


# ---------------------------------------------------------------------------
# fault-point registry <-> docs <-> armings three-way sync (satellite)
# ---------------------------------------------------------------------------

def _documented_fault_points():
    path = os.path.join(REPO, "docs", "how_to", "fault_tolerance.md")
    with open(path) as f:
        text = f.read()
    # first cell of each table row, lowercase names only (the same
    # file's env-var table rows start with MXTPU_ and don't match)
    return set(re.findall(r"^\|\s*`([a-z][a-z0-9_]*)`", text,
                          flags=re.M))


def test_fault_point_collector_resolves_every_mechanism():
    """Each static-resolution mechanism proves out on a known site:
    string literal, module-constant first arg, ``fault_point=``
    parameter default, and ``fault_point=`` call-site keyword."""
    sites = ast_lint.collect_fault_points([PKG])
    assert "iter_next" in sites          # plain string literal
    assert "serve_forward" in sites      # SERVE_FORWARD_FAULT constant
    assert "checkpoint_write" in sites   # atomic_path param default
    assert "manifest_write" in sites     # call-site fault_point="..."
    # sites carry usable provenance
    path, line, via = sites["swap_probe"][0]
    assert path.endswith(os.path.join("serving", "deploy.py"))
    assert via == "maybe_fail"


def test_fault_points_match_docs():
    """docs/how_to/fault_tolerance.md's fault table IS the tree: the
    list grew by hand across PRs and nothing checked it until now."""
    sites = ast_lint.collect_fault_points([PKG])
    documented = _documented_fault_points()
    assert set(sites) == documented, (
        "fault-point/docs drift: undocumented=%s, doc-rows-with-no-"
        "site=%s" % (sorted(set(sites) - documented),
                     sorted(documented - set(sites))))


def test_every_static_arming_names_a_real_point():
    """Every ``faults.arm``/``arm_hang`` call with a static point —
    package, tools, tests — arms a point production code actually
    reads; a typo'd arming would never fire and silently pass its
    drill."""
    sites = ast_lint.collect_fault_points([PKG])
    arms = ast_lint.collect_fault_points(
        [PKG, os.path.join(REPO, "tools"), os.path.join(REPO, "tests")],
        arms=True)
    unknown = set(arms) - set(sites)
    assert not unknown, (
        "armed points with no production site: %s (sites: %s)"
        % (sorted(unknown), {n: arms[n][:3] for n in sorted(unknown)}))


def test_mxlint_list_faults_cli():
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         "--list-faults"],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr
    listed = {line.split()[0] for line in res.stdout.splitlines()
              if line and not line.startswith("mxlint:")}
    assert listed == set(ast_lint.collect_fault_points([PKG]))


# ---------------------------------------------------------------------------
# CLI + stable report (satellite)
# ---------------------------------------------------------------------------

def test_mxlint_cli_self_clean(tmp_path):
    out = tmp_path / "report.json"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         "--self", "--json", str(out), "-q"],
        capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items()
             if k != "MXTPU_ANALYZE"})
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(out.read_text())
    assert payload["report_version"] == 1
    assert payload["summary"]["findings"] == 0
    assert payload["files_scanned"] > 50


def test_mxlint_cli_reports_seeded_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    pass\nexcept:\n    pass\n")
    out = tmp_path / "report.json"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         "--json", str(out), str(bad)],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 1, res.stdout + res.stderr
    payload = json.loads(out.read_text())
    assert payload["summary"]["by_rule"] == {"bare-except": 1}
    assert payload["findings"][0]["line"] == 3


def test_mxlint_cli_needs_no_accelerator_runtime(tmp_path):
    """The AST level is stdlib-only BY CONTRACT: the CLI must lint the
    package in a container with no jax at all (and must not import the
    package, whose __init__ would auto-join a launch-configured process
    group).  Simulated by poisoning ``import jax``."""
    (tmp_path / "jax").mkdir()
    (tmp_path / "jax" / "__init__.py").write_text(
        "raise ImportError('no accelerator runtime in this container')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(tmp_path)
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         "--self", "-q"],
        capture_output=True, text=True, timeout=120, env=env)
    assert res.returncode == 0, res.stdout + res.stderr


def test_report_json_is_stable(tmp_path):
    """Two runs over the same tree produce identical reports modulo the
    top-level timing field — the property CI diffing relies on."""
    def run(i):
        out = tmp_path / ("r%d.json" % i)
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
             "--json", str(out), "-q", PKG],
            capture_output=True, text=True, timeout=120)
        assert res.returncode == 0, res.stdout + res.stderr
        payload = json.loads(out.read_text())
        payload.pop("elapsed_s")
        return payload

    assert run(1) == run(2)


# ---------------------------------------------------------------------------
# graph-collective-schedule: the zero3 proof (the rule the tentpole adds)
# ---------------------------------------------------------------------------

def test_degenerate_replica_groups_are_not_traffic():
    """Singleton replica_groups — GSPMD's zero-traffic materialization
    of per-device partials — must not count as collectives; explicit
    and iota group forms both parse, and fixtures without
    replica_groups keep counting (backwards compatible)."""
    hlo = "\n".join((
        "%ar0 = f32[64,16]{1,0} all-reduce(f32[64,16]{1,0} %d), "
        "replica_groups=[8,1]<=[8], to_apply=%add",
        "%ar1 = f32[64,16]{1,0} all-reduce(f32[64,16]{1,0} %d), "
        "replica_groups={{0},{1},{2},{3},{4},{5},{6},{7}}, to_apply=%add",
        "%ar2 = f32[32]{0} all-reduce(f32[32]{0} %d), "
        "replica_groups=[1,8]<=[8], to_apply=%add",
        "%ar3 = f32[16]{0} all-reduce(f32[16]{0} %d), "
        "replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add",
        "%ar4 = f32[8]{0} all-reduce(f32[8]{0} %d), to_apply=%add",
    ))
    stats = graph_lint.collective_stats(hlo)
    # ar0/ar1 are degenerate no-ops; ar2-ar4 are real
    assert stats["all-reduce"]["count"] == 3, stats
    assert stats["all-reduce"]["bytes"] == 32 * 4 + 16 * 4 + 8 * 4


def test_collective_schedule_flags_unsharded_step():
    """An allreduce-shaped step DECLARED as zero3-manual fails all
    three schedule checks: no param-scale gathers, no reduce-scatter,
    and a full-gradient all-reduce."""
    mesh = _dp_mesh()
    w = jax.device_put(jnp.ones((64, 32)), NamedSharding(mesh, P()))
    x = jax.device_put(jnp.ones((64, 64)),
                       NamedSharding(mesh, P("dp", None)))

    def allreduce_step(w, x):
        loss = lambda w: jnp.sum((x @ w) ** 2)  # noqa: E731
        g = jax.grad(loss)(w)
        return w - 0.1 * g

    pb = 64 * 32 * 4
    rep = graph_lint.lint_jit(allreduce_step, w, x,
                              expect_allgather=True,
                              min_donate_bytes=1 << 30)
    # re-lint the same program with the schedule declared
    lowered = jax.jit(allreduce_step).lower(w, x)
    rep = graph_lint.lint_lowered(lowered, schedule="zero3-manual",
                                  expect_gather_bytes=pb,
                                  min_donate_bytes=1 << 30)
    msgs = [f.message for f in rep.findings
            if f.rule == "graph-collective-schedule"]
    assert len(msgs) == 3, rep.format_text()
    assert any("left replicated" in m for m in msgs)
    assert any("all-reduce" in m for m in msgs)
    assert any("no reduce-scatter" in m for m in msgs)
    # the gspmd tier tolerates the backend-placed gradient reduction
    # but still demands the gathers
    rep2 = graph_lint.lint_lowered(lowered, schedule="zero3-gspmd",
                                   expect_gather_bytes=pb,
                                   min_donate_bytes=1 << 30)
    msgs2 = [f.message for f in rep2.findings
             if f.rule == "graph-collective-schedule"]
    assert len(msgs2) == 1 and "left replicated" in msgs2[0]


def test_collective_schedule_clean_zero3_and_unaffected_allreduce():
    """The REAL zero3 step passes the schedule rule; a declared-
    allreduce step is untouched by it (rule keyed on the declaration)."""
    X, y = batch()
    t = make_trainer(grad_sync="zero3")
    try:
        rep = t.analyze(X, y)
        assert rep.ok, rep.format_text()
        assert rep.stats["schedule"]["declared"] == "zero3-manual"
        assert rep.stats["collectives"]["reduce-scatter"]["count"] >= 1
    finally:
        t.close()
    t = make_trainer(grad_sync="allreduce")
    try:
        rep = t.analyze(X, y)
        assert rep.ok, rep.format_text()
        assert "schedule" not in rep.stats
        assert "graph-collective-schedule" not in rules_of(rep)
    finally:
        t.close()


def test_collective_schedule_gspmd_owes_rs_on_rs_platforms():
    """ROADMAP item 2's previously-unverified claim, now asserted: on
    TPU/GPU pipelines XLA's ReduceScatterCreator must give the GSPMD
    tier real reduce-scatter — a gspmd zero3 schedule with gathers but
    no RS (and a param-scale all-reduce) flags on 'tpu', while 'cpu'
    keeps the all-reduce form as the documented tier placement."""
    no_rs = {"all-gather": {"count": 2, "bytes": 1000},
             "all-reduce": {"count": 1, "bytes": 800}}
    fs = graph_lint.audit_collective_schedule(no_rs, "zero3-gspmd",
                                              1000, platform="tpu")
    msgs = [f.message for f in fs]
    assert len(fs) == 2, msgs
    assert any("ReduceScatterCreator" in m for m in msgs)
    assert any("full all-reduce" in m for m in msgs)
    # gpu pipelines run the pass too
    assert len(graph_lint.audit_collective_schedule(
        no_rs, "zero3-gspmd", 1000, platform="gpu")) == 2
    # cpu: documented tier note, not a violation (the gathers still
    # gate — an unsharded step keeps flagging)
    assert graph_lint.audit_collective_schedule(
        no_rs, "zero3-gspmd", 1000, platform="cpu") == []
    assert graph_lint.audit_collective_schedule(
        {}, "zero3-gspmd", 1000, platform="cpu")
    # a clean tpu gspmd schedule passes
    clean = {"all-gather": {"count": 2, "bytes": 1000},
             "reduce-scatter": {"count": 1, "bytes": 125},
             "all-reduce": {"count": 1, "bytes": 12}}
    assert graph_lint.audit_collective_schedule(
        clean, "zero3-gspmd", 1000, platform="tpu") == []
    # the manual tier owes RS on EVERY platform (explicit psum_scatter)
    assert len(graph_lint.audit_collective_schedule(
        no_rs, "zero3-manual", 1000, platform="cpu")) == 2
    # unknown platform (None, the legacy call shape): gspmd tolerates
    assert graph_lint.audit_collective_schedule(
        no_rs, "zero3-gspmd", 1000) == []


def test_collective_schedule_records_platform():
    """trainer.analyze threads the compiled platform into the schedule
    stats — the artifact records WHERE the schedule claim was proven."""
    X, y = batch()
    t = make_trainer(grad_sync="zero3")
    try:
        rep = t.analyze(X, y)
        assert rep.ok, rep.format_text()
        assert rep.stats["schedule"]["platform"] == "cpu"
    finally:
        t.close()


class _UnshardedZero3(SPMDTrainer):
    """Violation fixture: declares zero3 but sabotages the sharding —
    every param resolves replicated, so nothing gathers and gradients
    all-reduce at full size.  The expected-gather-bytes bar comes from
    base rules + shapes, so the override cannot lower it."""

    def _param_spec(self, name, shape):
        return P()


def test_zero3_sabotaged_sharding_flagged():
    X, y = batch()
    t = make_trainer(cls=_UnshardedZero3, grad_sync="zero3")
    try:
        rep = t.analyze(X, y)
        assert "graph-collective-schedule" in rules_of(rep), \
            rep.format_text()
        assert t._zero3_expected_gather_bytes() > 0
    finally:
        t.close()


def test_env_analyze_strict_refuses_unsharded_zero3(monkeypatch):
    """MXTPU_ANALYZE=strict: a zero3 step whose sharding silently
    never happened refuses to train — the declared schedule is
    ENFORCED, not logged."""
    monkeypatch.setenv("MXTPU_ANALYZE", "strict")
    t = make_trainer(cls=_UnshardedZero3, grad_sync="zero3")
    try:
        with pytest.raises(mx.MXNetError,
                           match="graph-collective-schedule"):
            t.step(*batch())
    finally:
        t.close()


def test_env_analyze_strict_accepts_real_zero3(monkeypatch):
    """...and the genuine zero3 step trains under strict."""
    monkeypatch.setenv("MXTPU_ANALYZE", "strict")
    t = make_trainer(grad_sync="zero3")
    try:
        t.step(*batch())
    finally:
        t.close()


# ---------------------------------------------------------------------------
# Level 3 — cross-module lint (race + wire-contract), fixtures + the
# repo-wide zero-findings gate + the PR 18 regression
# ---------------------------------------------------------------------------

from mxnet_tpu.analysis import contract_lint, race_lint
from mxnet_tpu.analysis import fixtures as l3fx


def _default_scope():
    """The CLI's zero-carve-out default: package + tools."""
    return [PKG, os.path.join(REPO, "tools")]


def test_repo_race_lint_zero_findings():
    rep = race_lint.lint_paths(_default_scope())
    assert rep.ok, rep.format_text()


def test_repo_contract_lint_zero_findings():
    rep = contract_lint.lint_paths(_default_scope())
    assert rep.ok, rep.format_text()


def _race_snippet(tmp_path, source):
    p = tmp_path / "snippet.py"
    p.write_text(source)
    return race_lint.lint_paths([str(p)])


def test_race_unguarded_mutation_flagged(tmp_path):
    rep = _race_snippet(tmp_path, l3fx.RACE_UNGUARDED_SRC)
    assert rules_of(rep) == ["repo-shared-mutation"]
    # both sides of the race are findings: the thread's and the main
    # path's
    assert len(rep.findings) == 2, rep.format_text()


def test_race_guarded_mutation_clean(tmp_path):
    rep = _race_snippet(tmp_path, l3fx.RACE_GUARDED_SRC)
    assert rep.ok, rep.format_text()


def test_race_check_then_act_flagged(tmp_path):
    rep = _race_snippet(tmp_path, l3fx.RACE_CHECK_THEN_ACT_SRC)
    assert rules_of(rep) == ["repo-check-then-act"], rep.format_text()


def test_race_suppression_honored(tmp_path):
    rep = _race_snippet(tmp_path, l3fx.RACE_SUPPRESSED_SRC)
    assert rep.ok, rep.format_text()


def test_contract_drift_fixture_both_directions(tmp_path):
    p = tmp_path / "wire.py"
    p.write_text(l3fx.CONTRACT_DRIFT_SRC)
    surface = l3fx.contract_fixture_surface(contract_lint, "wire.py")
    mods, broken = ast_lint.load_modules([str(p)])
    assert not broken
    rep = contract_lint.lint_modules(mods, surfaces=[surface])
    assert rules_of(rep) == ["wire-contract-drift"]
    assert sorted(f.severity for f in rep.findings) == \
        ["error", "warning"], rep.format_text()
    # consumer-read-never-produced (the PR 18 shape) is the ERROR ...
    assert any(f.severity == "error" and "'c'" in f.message
               for f in rep.findings), rep.format_text()
    # ... dead wire weight is the warning
    assert any(f.severity == "warning" and "'b'" in f.message
               for f in rep.findings), rep.format_text()


def test_contract_aligned_fixture_clean(tmp_path):
    p = tmp_path / "wire.py"
    p.write_text(l3fx.CONTRACT_CLEAN_SRC)
    surface = l3fx.contract_fixture_surface(contract_lint, "wire.py")
    mods, _broken = ast_lint.load_modules([str(p)])
    rep = contract_lint.lint_modules(mods, surfaces=[surface])
    assert rep.ok, rep.format_text()


def test_pr18_view_export_regression():
    """THE acceptance criterion: reverting PR 18's view_export
    supervision-fields fix turns wire-contract-drift red (one
    consumer-read-never-produced error per dropped key), while the
    shipped tree stays green."""
    scope = _default_scope()
    clean = contract_lint.lint_paths(scope)
    assert clean.ok, clean.format_text()
    rep = contract_lint.lint_paths(
        scope, overrides=l3fx.pr18_broken_router_source())
    errors = [f for f in rep.findings
              if f.rule == "wire-contract-drift"]
    assert len(errors) == len(l3fx.PR18_SUPERVISION_KEYS), \
        rep.format_text()
    assert all(f.severity == "error" for f in errors)
    assert all(f.file.endswith("router.py") for f in errors)
    for key in l3fx.PR18_SUPERVISION_KEYS:
        assert any("'%s'" % key in f.message for f in errors), key


def test_level3_suppressions_carry_justification():
    """Every inline suppression of a level-3 rule must sit next to a
    real justification comment — a bare directive is a carve-out, not
    an explanation (the escape hatch the tree-wide gate allows)."""
    directive = re.compile(r"mxlint:\s*disable=(repo|wire)-")
    bad = []
    for path in _scope_py_files():
        lines = open(path).read().splitlines()
        for i, line in enumerate(lines):
            if not directive.search(line):
                continue
            context = lines[max(0, i - 6):i] + \
                [line.split("# mxlint:")[0]]
            justified = any(
                "#" in c and "mxlint:" not in c and
                len(c.split("#", 1)[1].split()) >= 3
                for c in context)
            if not justified:
                bad.append("%s:%d" % (os.path.relpath(path, REPO),
                                      i + 1))
    assert not bad, "unjustified level-3 suppressions: %s" % bad


def _scope_py_files():
    for root_dir in _default_scope():
        if os.path.isfile(root_dir):
            yield root_dir
            continue
        for dirpath, _dirs, files in os.walk(root_dir):
            for name in files:
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)


def test_level3_rules_documented():
    doc = open(os.path.join(REPO, "docs", "how_to",
                            "static_analysis.md")).read()
    for rule in tuple(race_lint.RULES) + tuple(contract_lint.RULES):
        assert "`%s`" % rule in doc, \
            "rule %s missing from static_analysis.md" % rule


def test_mxlint_cli_changed_falls_back_on_bad_ref(tmp_path):
    """--changed with an unresolvable ref (the not-a-git-checkout
    shape) falls back to the FULL tree rather than linting nothing."""
    out = tmp_path / "report.json"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         "--changed", "no-such-ref-xyz", "--json", str(out), "-q"],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stdout + res.stderr
    payload = json.loads(out.read_text())
    assert payload["files_scanned"] > 50


def test_mxlint_cli_changed_scopes_to_diff(tmp_path):
    """--changed HEAD lints at most the dirty files (usually far fewer
    than the tree; exit code still reflects findings in them)."""
    out = tmp_path / "report.json"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxlint.py"),
         "--changed", "--json", str(out), "-q"],
        capture_output=True, text=True, timeout=120)
    assert res.returncode in (0, 1), res.stdout + res.stderr
    payload = json.loads(out.read_text())
    full = len(list(_scope_py_files()))
    assert payload["files_scanned"] <= full
