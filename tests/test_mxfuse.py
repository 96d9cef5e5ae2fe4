"""mxfuse plan-optimizer: per-pass parity pins, engagement proofs,
plain-plan contracts (ISSUE 15 / ROADMAP item 5).

Parity matrix (fused vs unfused, forward AND backward):

- ``pool_act`` reorder and ``eltwise_chain`` are BIT-exact by
  construction under the whole-graph jit (same op sequence); pinned
  with the cross-program comparator where two XLA programs may differ
  in final bits.
- ``concat_fuse`` reassociates the conv reduction (a wider GEMM may
  block differently) — documented tolerance, like ``bn_fold``.
- the slice-pooling lowering is bitwise for max and documented-
  tolerance (~1e-7, addition order) for avg/sum.

Plus: ``MXTPU_FUSED_KERNELS=0`` restores the exact unfused plan
object, monitored runs still tap every plain-plan node, each pass has
a provably-engaged assert (its kernel body must be reached), and the
``plan-fusion-parity`` lint holds the rewrite contract.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import mxfuse
from mxnet_tpu.executor import _fuse_bn_plan, _node_plan
from mxnet_tpu.kernels import (concat_fuse as CF, eltwise_chain as EC,
                               pool_act as PA)
from mxnet_tpu.models.inception_bn import (ConvFactory,
                                           InceptionFactoryA,
                                           InceptionFactoryB)

#: the pre-mxfuse kernel set — "new passes off" with bn_act/bn_fold
#: (PR 8) still on
PRE = "bn_act,bn_fold,lstm_cell,flash_attention,augment"


def _xprog_close(a, b, msg="", rtol=2e-6, atol=1e-7):
    np.testing.assert_allclose(a, b, rtol=rtol, atol=atol, err_msg=msg)


def _inception_net():
    """Stem + one A tower + one B tower: every pattern the pipeline
    matches (merge trio, grouped 3x3 siblings, act→max-pool stem,
    avg-pool branch, concat)."""
    data = mx.sym.Variable("data")
    c1 = ConvFactory(data, 16, (3, 3), pad=(1, 1), name="c1")
    p1 = mx.sym.Pooling(c1, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                        pool_type="max", name="p1")
    a = InceptionFactoryA(p1, 8, 8, 12, 8, 12, "avg", 8, "3a")
    b = InceptionFactoryB(a, 8, 12, 8, 12, "3c")
    flat = mx.sym.Flatten(mx.sym.Pooling(
        b, global_pool=True, kernel=(1, 1), pool_type="avg"))
    fc = mx.sym.FullyConnected(flat, num_hidden=10, name="fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def _resnet_block_net():
    """conv→bn→relu stacks + a shortcut add + relu tail and a scalar
    chain — the eltwise/bn patterns resnets exercise."""
    data = mx.sym.Variable("data")
    body = ConvFactory(data, 8, (3, 3), pad=(1, 1), name="rb1")
    body = mx.sym.Convolution(body, num_filter=8, kernel=(3, 3),
                              pad=(1, 1), name="rb2")
    body = mx.sym.BatchNorm(body, fix_gamma=False, name="rb2_bn")
    short = mx.sym.Convolution(data, num_filter=8, kernel=(1, 1),
                               name="sc")
    fused = mx.sym.Activation(body + short, act_type="relu",
                              name="sum_relu")
    tail = mx.sym.tanh(fused * 0.5 + 1.0)
    flat = mx.sym.Flatten(tail)
    fc = mx.sym.FullyConnected(flat, num_hidden=10, name="fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def _mlp_net():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _run(sym_fn, shape, train, env, monkeypatch, label=True):
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", env)
    rs = np.random.RandomState(0)
    sym = sym_fn()
    ex = sym.simple_bind(mx.cpu(), data=shape)
    for name in sorted(ex.arg_dict):
        if name in ("data", "softmax_label"):
            continue
        r = np.random.RandomState(abs(hash(name)) % (2 ** 31))
        ex.arg_dict[name][:] = \
            (r.rand(*ex.arg_dict[name].shape).astype("f") - 0.5) * 0.4
    for name in ex.aux_dict:
        ex.aux_dict[name][:] = 1.0 if name.endswith("var") else 0.0
    ex.arg_dict["data"][:] = rs.rand(*shape).astype("f")
    if label:
        ex.arg_dict["softmax_label"][:] = \
            rs.randint(0, 10, shape[0]).astype("f")
    out = ex.forward(is_train=train)[0].asnumpy()
    grads, aux = {}, {}
    if train:
        ex.backward()
        grads = {k: v.asnumpy() for k, v in ex.grad_dict.items()
                 if v is not None}
        aux = {k: v.asnumpy() for k, v in ex.aux_dict.items()}
    ex.close()
    return out, grads, aux


# ---------------------------------------------------------------------------
# parity pins: fused vs unfused, forward AND backward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("train", [False, True])
def test_mlp_parity_all_passes(train, monkeypatch):
    o1, g1, _ = _run(_mlp_net, (4, 12), train, "1", monkeypatch)
    o0, g0, _ = _run(_mlp_net, (4, 12), train, "0", monkeypatch)
    _xprog_close(o1, o0, "forward")
    for k in g0:
        _xprog_close(g1[k], g0[k], k)


@pytest.mark.parametrize("train", [False, True])
def test_resnet_block_parity_all_passes(train, monkeypatch):
    shape = (2, 3, 8, 8)
    o1, g1, a1 = _run(_resnet_block_net, shape, train, "1", monkeypatch)
    o0, g0, a0 = _run(_resnet_block_net, shape, train, "0", monkeypatch)
    np.testing.assert_allclose(o1, o0, rtol=1e-5, atol=1e-6)
    for k in g0:
        np.testing.assert_allclose(g1[k], g0[k], rtol=5e-4, atol=5e-6,
                                   err_msg=k)
    for k in a0:
        np.testing.assert_allclose(a1[k], a0[k], rtol=5e-4, atol=5e-6,
                                   err_msg=k)


@pytest.mark.parametrize("train", [False, True])
def test_inception_parity_all_passes(train, monkeypatch):
    """The headline model: A+B towers, fused vs unfused, forward AND
    backward AND aux (moving stats) — within the documented
    reassociation tolerance (conv merge + fold + avg-pool order)."""
    shape = (2, 3, 16, 16)
    o1, g1, a1 = _run(_inception_net, shape, train, "1", monkeypatch)
    o0, g0, a0 = _run(_inception_net, shape, train, "0", monkeypatch)
    np.testing.assert_allclose(o1, o0, rtol=1e-5, atol=1e-6)
    for k in g0:
        np.testing.assert_allclose(g1[k], g0[k], rtol=5e-4, atol=5e-6,
                                   err_msg=k)
    for k in a0:
        np.testing.assert_allclose(a1[k], a0[k], rtol=5e-4, atol=5e-6,
                                   err_msg=k)


def test_inception_eval_stays_in_bn_fold_contract(monkeypatch):
    """New passes on vs the pre-mxfuse set: the serving-facing eval
    output moves by no more than the existing bn_fold tolerance
    contract (rtol 1e-5) — the concat merge and pooling lowering add
    no NEW numerics class."""
    shape = (2, 3, 16, 16)
    o_all, _, _ = _run(_inception_net, shape, False, "1", monkeypatch)
    o_pre, _, _ = _run(_inception_net, shape, False, PRE, monkeypatch)
    np.testing.assert_allclose(o_all, o_pre, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# =0 restores the plain plans; plan structure per pass
# ---------------------------------------------------------------------------

def test_off_restores_exact_plain_plan(monkeypatch):
    sym = _inception_net()
    plan = _node_plan(sym)
    refs = [(id(n), i) for n, i in sym._outputs]
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "0")
    assert _fuse_bn_plan(plan, refs) is plan
    # and the pipeline never mutates the plain plan it was given
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    fused = _fuse_bn_plan(plan, refs)
    assert fused is not plan
    assert all(e[5] is None for e in plan)


def test_concat_fuse_plan_structure(monkeypatch):
    """The A-tower's three 1x1 stacks merge into one shared-input
    group (every member BN carries the group's refs: 1 shared input +
    per-member weight/bias + 4 BN vectors), and the fused plan is a
    PERMUTATION of the plain entries with slots 0-4 intact."""
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "concat_fuse")
    sym = _inception_net()
    plan = _node_plan(sym)
    refs = [(id(n), i) for n, i in sym._outputs]
    fused = _fuse_bn_plan(plan, refs)
    by_name = {e[0].name: e for e in fused}
    trio = ["bn_3a_1x1", "bn_3a_3x3_reduce", "bn_3a_double_3x3_reduce"]
    for name in trio:
        ov = by_name[name][5]
        assert ov is not None, name
        # 1 shared x + 3 members x (w, b, gamma, beta, mm, mv)
        assert len(ov[1]) == 1 + 3 * 6
    # permutation with per-entry slots intact (rng fold constants ride
    # IN the entries, so order is free; identity/slots are not)
    assert {id(e[0]) for e in fused} == {id(e[0]) for e in plan}
    plain_of = {id(e[0]): e for e in plan}
    for e in fused:
        assert e[:5] == plain_of[id(e[0])][:5]


def test_concat_fuse_grouped_siblings(monkeypatch):
    """Equal-width sibling 3x3 convs with DIFFERENT inputs (inception's
    parallel 3x3 towers) merge via the grouped-conv shape: member BNs
    carry one x ref PER member."""
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "concat_fuse")
    data = mx.sym.Variable("data")
    l = ConvFactory(data, 8, (1, 1), name="la")
    r = ConvFactory(data, 8, (1, 1), name="ra")
    lb = ConvFactory(l, 12, (3, 3), pad=(1, 1), name="lb")
    rb = ConvFactory(r, 12, (3, 3), pad=(1, 1), name="rb")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(mx.sym.Flatten(
        mx.sym.Concat(lb, rb)), num_hidden=4), name="softmax")
    plan = _node_plan(net)
    refs = [(id(n), i) for n, i in net._outputs]
    fused = _fuse_bn_plan(plan, refs)
    by_name = {e[0].name: e for e in fused}
    for name in ("bn_lb", "bn_rb"):
        ov = by_name[name][5]
        assert ov is not None, name
        # 2 member inputs + 2 members x (w, b, gamma, beta, mm, mv)
        assert len(ov[1]) == 2 + 2 * 6
    # the 1x1 pair over `data` merges as a shared-input group
    assert by_name["bn_la"][5] is not None
    assert len(by_name["bn_la"][5][1]) == 1 + 2 * 6


def test_concat_fuse_dependent_siblings_not_merged(monkeypatch):
    """Two same-geometry stacks where one's input derives from the
    other's output must NOT merge (the chain case) — the independence
    check splits them."""
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "concat_fuse")
    data = mx.sym.Variable("data")
    a = ConvFactory(data, 8, (3, 3), pad=(1, 1), name="s1")
    b = ConvFactory(a, 8, (3, 3), pad=(1, 1), name="s2")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(mx.sym.Flatten(b),
                                                     num_hidden=4),
                               name="softmax")
    plan = _node_plan(net)
    refs = [(id(n), i) for n, i in net._outputs]
    assert _fuse_bn_plan(plan, refs) is plan


def test_pool_act_reorder_is_bitwise(monkeypatch):
    """act→max-pool reorder: bit-identical forward (monotone act
    commutes with max) on a conv→relu→maxpool net."""
    def net():
        data = mx.sym.Variable("data")
        c = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3),
                               pad=(1, 1), name="c")
        r = mx.sym.Activation(c, act_type="relu", name="r")
        p = mx.sym.Pooling(r, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                           pool_type="max", name="p")
        fc = mx.sym.FullyConnected(mx.sym.Flatten(p), num_hidden=4,
                                   name="fc")
        return mx.sym.SoftmaxOutput(fc, name="softmax")

    shape = (2, 3, 10, 10)
    o1, g1, _ = _run(net, shape, True, "pool_act", monkeypatch)
    o0, g0, _ = _run(net, shape, True, "0", monkeypatch)
    _xprog_close(o1, o0, "forward")
    for k in g0:
        _xprog_close(g1[k], g0[k], k)
    # plan: relu passthrough + pool override
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "pool_act")
    sym = net()
    plan = _node_plan(sym)
    refs = [(id(n), i) for n, i in sym._outputs]
    fused = _fuse_bn_plan(plan, refs)
    names = sorted(e[0].name for e in fused if e[5] is not None)
    assert names == ["p", "r"]


def test_pool_slice_lowering_matches_reduce_window():
    """The shifted-slice pooling lowering vs the registered op: max is
    BITWISE, avg within the documented addition-order tolerance, and
    oversized maps fall back to the op itself."""
    from mxnet_tpu.ops import nn as NN
    rs = np.random.RandomState(0)
    import jax.numpy as jnp
    x = jnp.asarray(rs.randn(2, 6, 10, 10).astype("f"))
    for pool_type, kw in (("max", {}), ("avg", {}), ("sum", {})):
        attrs = dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                     pool_type=pool_type, **kw)
        ref = NN.pooling(x, **attrs)
        got = PA.pooling_opt(x, attrs, is_train=False)
        if pool_type == "max":
            assert np.array_equal(np.asarray(ref), np.asarray(got))
        else:
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-6, atol=1e-6)
    # max at TRAIN keeps the reduce_window lowering (tie-breaking in
    # the backward differs between lowerings)
    attrs = dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                 pool_type="max")
    t = PA.pooling_opt(x, attrs, is_train=True)
    assert np.array_equal(np.asarray(t),
                          np.asarray(NN.pooling(x, **attrs)))
    # oversized spatial falls back (still correct)
    big = jnp.asarray(rs.randn(1, 2, 80, 80).astype("f"))
    got = PA.pooling_opt(big, attrs, is_train=False)
    assert np.array_equal(np.asarray(got),
                          np.asarray(NN.pooling(big, **attrs)))


def test_eltwise_chain_plan_and_parity(monkeypatch):
    """A relu→scale→add→tanh run collapses into ONE override at the
    chain tail (intermediates passthrough; the side operand rides as
    an extra ref) and stays bit-identical under the whole-graph jit."""
    def net():
        data = mx.sym.Variable("data")
        side = mx.sym.Variable("side")
        v = mx.sym.Activation(data, act_type="relu", name="n1")
        v = v * 0.5
        v = mx.sym.broadcast_add(v, side, name="n3")
        v = mx.sym.tanh(v, name="n4")
        fc = mx.sym.FullyConnected(mx.sym.Flatten(v), num_hidden=4,
                                   name="fc")
        return mx.sym.SoftmaxOutput(fc, name="softmax")

    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "eltwise_chain")
    sym = net()
    plan = _node_plan(sym)
    refs = [(id(n), i) for n, i in sym._outputs]
    fused = _fuse_bn_plan(plan, refs)
    overridden = {e[0].name: e[5] for e in fused if e[5] is not None}
    assert "n4" in overridden
    tail = overridden["n4"]
    assert len(tail[1]) == 1          # the broadcast side operand
    assert len(overridden) == 4       # 3 passthroughs + tail

    def run(env, train):
        monkeypatch.setenv("MXTPU_FUSED_KERNELS", env)
        rs = np.random.RandomState(0)
        s = net()
        ex = s.simple_bind(mx.cpu(), data=(2, 3, 4, 4),
                           side=(2, 3, 4, 4))
        for name in sorted(ex.arg_dict):
            r = np.random.RandomState(abs(hash(name)) % (2 ** 31))
            ex.arg_dict[name][:] = r.rand(
                *ex.arg_dict[name].shape).astype("f")
        out = ex.forward(is_train=train)[0].asnumpy()
        ex.backward()
        grads = {k: v.asnumpy() for k, v in ex.grad_dict.items()
                 if v is not None}
        ex.close()
        return out, grads

    o1, g1 = run("eltwise_chain", True)
    o0, g0 = run("0", True)
    _xprog_close(o1, o0, "forward")
    for k in g0:
        _xprog_close(g1[k], g0[k], k)


# ---------------------------------------------------------------------------
# provably engaged: each pass's kernel body must be reached
# ---------------------------------------------------------------------------

def test_passes_provably_engaged(monkeypatch):
    """Each pass's kernel factory is invoked for the inception net AND
    its produced bodies actually run in the forward — patched counters,
    not inference from timings."""
    calls = {"concat": 0, "pool": 0, "chain": 0}
    real_group = CF.make_group_member
    real_pool = PA.pooling_opt
    real_chain = EC.make_chain_fn

    def count_group(*a, **kw):
        calls["concat"] += 1
        return real_group(*a, **kw)

    def count_pool(*a, **kw):
        calls["pool"] += 1
        return real_pool(*a, **kw)

    def count_chain(*a, **kw):
        calls["chain"] += 1
        return real_chain(*a, **kw)

    monkeypatch.setattr(CF, "make_group_member", count_group)
    monkeypatch.setattr(PA, "pooling_opt", count_pool)
    monkeypatch.setattr(EC, "make_chain_fn", count_chain)
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    shape = (2, 3, 16, 16)
    sym = _inception_net()
    ex = sym.simple_bind(mx.cpu(), data=shape)
    ex.arg_dict["data"][:] = np.random.RandomState(0).rand(
        *shape).astype("f")
    ex.forward()[0].asnumpy()
    ex.close()
    assert calls["concat"] >= 3       # the A-tower trio at least
    assert calls["pool"] >= 1         # stem/branch pooling routed
    # no eltwise chain exists in this net — assert via the resnet block
    sym2 = _resnet_block_net()
    ex2 = sym2.simple_bind(mx.cpu(), data=(2, 3, 8, 8))
    ex2.close()
    assert calls["chain"] >= 1


def test_infer_trace_prunes_dead_convs(monkeypatch):
    """DCE: with the folds installed, the eval interpretation skips
    the original per-branch convs (and their weights stay live via the
    override's extra refs) — and the pruned plan computes the same
    outputs bitwise as the unpruned fused plan."""
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    sym = _inception_net()
    plan = _node_plan(sym)
    refs = [(id(n), i) for n, i in sym._outputs]
    fused = _fuse_bn_plan(plan, refs)
    live = mxfuse.live_entries(fused, refs)
    dropped = {e[0].name for e in fused} - {e[0].name for e in live}
    assert any(name.startswith("conv_") for name in dropped)
    # every override extra ref stays interpretable
    live_ids = {id(e[0]) for e in live}
    for e in live:
        if e[5] is None:
            continue
        for src, _idx in e[5][1]:
            assert src.op is None or id(src) in live_ids
    # value identity: infer_trace on vs off (both fully fused)
    shape = (2, 3, 16, 16)
    o_on, _, _ = _run(_inception_net, shape, False, "1", monkeypatch)
    no_prune = ",".join(k for k in
                        __import__("mxnet_tpu").kernels.KNOWN_KERNELS
                        if k != "infer_trace")
    o_off, _, _ = _run(_inception_net, shape, False, no_prune,
                       monkeypatch)
    assert np.array_equal(o_on, o_off)


def test_fold_constants_unit():
    """Bind-time constant folding over a hand-built plan: a zero-input
    generator op folds, its consumer folds transitively, and anything
    touching runtime args stays."""
    class FakeOp(object):
        def __init__(self, fn, n_in):
            self.fn = fn
            self.name = fn.__name__
            self.needs_rng = False
            self.needs_is_train = False
            self.no_jit = False
            self.variable_inputs = False
            self._n_in = n_in

        def get_input_names(self, attrs):
            return tuple("in%d" % i for i in range(self._n_in))

    class FakeNode(object):
        def __init__(self, name, op, inputs):
            self.name = name
            self.op = op
            self.inputs = inputs
            self.is_variable = op is None

    def three():
        return np.float32(3.0)

    def double(x):
        return x * 2

    var = FakeNode("w", None, [])
    gen = FakeNode("gen", FakeOp(three, 0), [])
    dbl = FakeNode("dbl", FakeOp(double, 1), [(gen, 0)])
    dep = FakeNode("dep", FakeOp(double, 1), [(var, 0)])
    entries = [
        (var, None, None, None, 0, None),
        (gen, {}, 1, [], 1, None),
        (dbl, {}, 1, [], 2, None),
        (dep, {}, 1, [], 3, None),
    ]
    const_env, remaining = mxfuse.fold_constants(entries)
    assert const_env[id(gen)][0] == np.float32(3.0)
    assert const_env[id(dbl)][0] == np.float32(6.0)
    kept = [e[0].name for e in remaining]
    assert kept == ["w", "dep"]


# ---------------------------------------------------------------------------
# the monitored (plain-plan) contract + the lint
# ---------------------------------------------------------------------------

def test_monitored_runs_tap_every_plain_node(monkeypatch):
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    sym = _inception_net()
    shape = (2, 3, 16, 16)
    ex = sym.simple_bind(mx.cpu(), data=shape)
    ex.arg_dict["data"][:] = np.random.RandomState(0).rand(
        *shape).astype("f")
    taps = []
    ex.set_monitor_callback(lambda name, arr: taps.append(name))
    ex.forward(is_train=False)
    n_ops = sum(1 for n in sym._nodes() if n.op is not None)
    assert len(taps) >= n_ops
    # the taps carry the UNFUSED per-node outputs: the original conv
    # results exist even though the fused program never computes them
    assert any(t.startswith("conv_3a_1x1") for t in taps)
    ex.close()


def test_plan_fusion_parity_lint_clean(monkeypatch):
    from mxnet_tpu.analysis import graph_lint
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    rep = graph_lint.audit_plan_fusion(_inception_net())
    assert rep.ok, rep.format_text()
    assert rep.stats["plan_fusion"]["overrides"] > 10
    assert rep.stats["plan_fusion"]["eval_live"] \
        < rep.stats["plan_fusion"]["entries"]
    # off: nothing to audit, still clean
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "0")
    rep = graph_lint.audit_plan_fusion(_inception_net())
    assert rep.ok
    assert rep.stats["plan_fusion"]["overrides"] == 0


def test_plan_fusion_parity_lint_flags_broken_pass(monkeypatch):
    """Seeded violations: a pass that drops an entry from the plain
    plan, and one whose override reads a value-rewriting passthrough —
    both must surface as plan-fusion-parity findings, not silent
    corruption."""
    from mxnet_tpu.analysis import graph_lint

    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")

    def drops_an_entry(view):
        view.plan.pop()

    monkeypatch.setattr(mxfuse, "PASSES",
                        ((frozenset(("bn_act",)), drops_an_entry),))
    rep = graph_lint.audit_plan_fusion(_mlp_net())
    assert not rep.ok
    assert rep.findings[0].rule == "plan-fusion-parity"

    def reads_passthrough(view):
        # claim the relu as a value-rewriting passthrough, then read it
        # from another override's extra refs
        act = next(e[0] for e in view.plan
                   if e[0].op is not None
                   and e[0].op.name == "Activation")
        fc2 = next(e[0] for e in view.plan
                   if e[0].name == "fc2")
        view.passthrough(act)
        view.override(fc2, lambda *a, **k: a[0], [(act, 0)])

    monkeypatch.setattr(mxfuse, "PASSES",
                        ((frozenset(("bn_act",)), reads_passthrough),))
    rep = graph_lint.audit_plan_fusion(_mlp_net())
    assert not rep.ok
    assert any("passthrough" in f.message or "raised" in f.message
               for f in rep.findings)


def test_trainer_analyze_carries_plan_fusion_stats(monkeypatch):
    """The plan-fusion-parity rule rides every trainer.analyze() —
    the fixtures path ``mxlint --graph`` lints."""
    from mxnet_tpu.analysis import fixtures
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    trainer = fixtures.standard_mlp_trainer()
    try:
        rep = trainer.analyze(*fixtures.standard_mlp_batch())
        assert rep.ok, rep.format_text()
        assert "plan_fusion" in rep.stats
    finally:
        trainer.close()


def test_topo_sort_raises_on_cycle():
    class N(object):
        def __init__(self, name):
            self.name = name
            self.op = object()
            self.inputs = []

    a, b = N("a"), N("b")
    ea = (a, {}, 1, [], 0, (lambda *x, **k: x[0], [(b, 0)],
                            frozenset()))
    eb = (b, {}, 1, [], 1, (lambda *x, **k: x[0], [(a, 0)],
                            frozenset()))
    with pytest.raises(mx.base.MXNetError):
        mxfuse._topo_sort([ea, eb])
