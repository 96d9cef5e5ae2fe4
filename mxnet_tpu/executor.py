"""Executor — compiled graph execution.

Re-design of the reference GraphExecutor (src/executor/graph_executor.cc,
1,126 LoC).  Where the reference builds an NNVM fwd+bwd graph, plans memory,
and pushes per-op engine tasks, this executor traces the Symbol DAG into one
pure JAX function and jits it:

- graph building + gradient: ``jax.vjp`` over the traced function
  (nnvm::pass::Gradient analog; mirroring/remat is ``jax.checkpoint`` at the
  model level).
- memory planning / pooled reuse: XLA's buffer assignment.
- bulk segments & cached ops (InitCachedOps/InitOpSegs,
  graph_executor.cc:556,690): the whole graph IS one fused XLA program.

Training dispatch is a single fused fwd+bwd+aux-update XLA call per batch:
``forward(is_train=True)`` computes outputs, gradients (w.r.t. args whose
grad_req != 'null', with ones head-gradients — the loss-layer convention) and
BatchNorm-style aux updates in one compiled program; ``backward()`` then just
writes the cached gradients into the grad arrays (honoring write/add).
``backward(out_grads)`` with explicit head gradients re-runs the same
compiled function with those heads.
"""
from __future__ import annotations

from contextlib import nullcontext

import numpy as np

import jax
import jax.numpy as jnp

from . import random as _random
from .base import MXNetError, register_env

ENV_BACKWARD_DO_MIRROR = register_env(
    "MXNET_BACKWARD_DO_MIRROR", default=0,
    doc="1 = memory mirror mode: the backward rematerializes activations "
        "per checkpoint segment instead of storing them")
ENV_MIRROR_SEGMENTS = register_env(
    "MXNET_MIRROR_SEGMENTS",
    doc="Segment count for mirror mode (default sqrt of op count)")
from .context import Context, current_context
from .ndarray import NDArray, zeros as nd_zeros
from .ops.registry import OpDef

__all__ = ["Executor"]


def _filter_attrs(op, attrs):
    """Keep only attrs the op function accepts (graph nodes also carry
    framework attrs like ctx_group / lr_mult).  Unknown USER attrs were
    already rejected at symbol-creation time (OpDef.validate_attrs)."""
    from .ops.registry import fn_signature_info
    names, has_var_kw = fn_signature_info(op.fn)
    if has_var_kw:
        return dict(attrs)
    return {k: v for k, v in attrs.items() if k in names}


def _node_plan(symbol):
    """Precompute the per-node execution plan for the trace.  Slot 5 is
    the node's position in this graph's topological order — the
    per-node RNG fold constant.  It must be a pure function of the GRAPH
    (never of process history): folding the old process-global Symbol
    uid meant the same seeded program drew different Dropout masks
    depending on how many symbols the process had ever created, so a
    test suite's earlier tests silently changed later seeded runs.

    Slot 6 is an optional fusion override, ``None`` or ``(fn,
    extra_refs, eval_dead_ins)``: the interpreter then calls ``fn``
    instead of the node's op, appending the values of ``extra_refs``
    ((src_node, idx) pairs) to the node's own inputs — how the mxfuse
    plan-optimizer passes (:mod:`mxnet_tpu.mxfuse`) rewrite node groups
    without renumbering the plan (RNG fold constants stay put);
    ``eval_dead_ins`` feeds the inference-trace dead-node
    elimination."""
    plan = []
    for ix, node in enumerate(symbol._nodes()):
        if node.is_variable:
            plan.append((node, None, None, None, ix, None))
            continue
        attrs = node.op.normalize_attrs(node.op_attrs())
        call_attrs = _filter_attrs(node.op, attrs)
        n_out = node.op.get_num_outputs(attrs)
        n_in = len(node.op.get_input_names(attrs))
        aux_names = node.op.get_aux_names(attrs)
        aux_var_names = []
        for k in range(len(aux_names)):
            if n_in + k < len(node.inputs):
                src, _ = node.inputs[n_in + k]
                aux_var_names.append(src.name if src.is_variable else None)
        plan.append((node, call_attrs, n_out, aux_var_names, ix, None))
    return plan


def _fuse_bn_plan(plan, out_refs):
    """Run the mxfuse plan-optimizer pipeline (docs/how_to/
    performance.md "The plan optimizer") — kept under the historical
    name as the executor's rewrite entry point.  Entries keep their
    positions (only the override slot changes), so RNG fold constants
    are unchanged and ``MXTPU_FUSED_KERNELS=0`` (which skips the
    pipeline entirely) restores the exact pre-fusion program."""
    from . import mxfuse
    return mxfuse.optimize_plan(plan, out_refs)


def _build_eval(symbol, placement=None, mirror_segments=0):
    """Return eval_fn(args_dict, aux_dict, rng, is_train) ->
    (outputs_list, aux_updates_dict).  Pure — jit/vjp-able.

    ``placement`` (id(node) -> jax device) activates group2ctx model
    parallelism: every node's inputs are committed to its group's device
    before dispatch — the reference's PlaceDevice pass inserting
    _CrossDeviceCopy at group boundaries (graph_executor.cc:242-331),
    expressed as jax.device_put (whose vjp transposes to a device_put of
    the cotangent back across the same boundary).  Placement-active graphs
    run eagerly per-op, the reference's own dispatch model.

    ``mirror_segments`` > 1 wraps the trace in that many jax.checkpoint
    segments: the backward rematerializes each segment's activations
    instead of storing them (the reference's MXNET_BACKWARD_DO_MIRROR
    memory mode, graph_executor.cc InitFullGraph mirror option)."""
    plan = _node_plan(symbol)
    out_refs = [(id(n), i) for n, i in symbol._outputs]
    placement = placement or {}
    # mxfuse plan-optimizer passes (MXTPU_FUSED_KERNELS): fused
    # dispatch only — the placement (eager per-op) path and monitored
    # runs keep the plain plan, so per-node taps still see the unfused
    # node outputs
    fused_plan = plan if placement else _fuse_bn_plan(plan, out_refs)
    # the inference-trace pass set (infer_trace): dead-node elimination
    # + bind-time constant folding over the EVAL interpretation only —
    # entries are skipped, never changed, so positions (RNG folds,
    # monitor coordinates) are untouched and values are bit-identical
    # (dead entries were unread; folded values are computed once here
    # instead of per trace)
    infer_plan, const_env = None, {}
    if not placement:
        from .kernels import fused_enabled
        if fused_enabled("infer_trace"):
            from . import mxfuse
            const_env, infer_plan = mxfuse.fold_constants(
                mxfuse.live_entries(fused_plan, out_refs))
    staged = any(_mirror_stage(entry[0]) for entry in fused_plan)
    if staged or (mirror_segments and mirror_segments > 1):
        if placement:
            import logging
            logging.warning(
                "MXNET_BACKWARD_DO_MIRROR ignored: group2ctx placement "
                "runs per-op eagerly, which jax.checkpoint cannot wrap")
        else:
            return _build_eval_segmented(plan, fused_plan, out_refs,
                                         0 if staged
                                         else int(mirror_segments))

    if not placement:
        def eval_fn(args, aux, rng, is_train, monitor=None):
            if monitor is not None:
                chunk = plan              # plain: every node tapped
            elif not is_train and infer_plan is not None:
                chunk = infer_plan        # pruned + const-folded eval
            else:
                chunk = fused_plan
            env = dict(const_env) if chunk is infer_plan else {}
            aux_updates = {}
            _run_plan_nodes(chunk, env, args, aux, rng, is_train,
                            aux_updates, monitor)
            return [env[nid][i] for nid, i in out_refs], aux_updates
        return eval_fn

    def eval_fn(args, aux, rng, is_train, monitor=None):
        env = {}
        aux_updates = {}
        for node, call_attrs, n_out, aux_var_names, rng_ix, _ov in plan:
            dev = placement.get(id(node))
            if node.op is None:
                if node.name in args:
                    val = args[node.name]
                elif node.name in aux:
                    val = aux[node.name]
                else:
                    raise MXNetError("unbound variable %r" % node.name)
                if dev is not None:
                    val = jax.device_put(val, dev)
                env[id(node)] = (val,)
                continue
            ins = [env[id(src)][idx] for src, idx in node.inputs]
            if dev is not None:
                ins = [jax.device_put(x, dev) for x in ins]
            kw = {}
            if node.op.needs_is_train:
                kw["is_train"] = is_train
            if node.op.needs_rng:
                kw["rng"] = jax.random.fold_in(rng, rng_ix)
            with jax.named_scope(node.name):
                out = node.op.fn(*ins, **call_attrs, **kw)
            if not isinstance(out, (tuple, list)):
                out = (out,)
            env[id(node)] = tuple(out[:n_out])
            for name, arr in zip(aux_var_names, out[n_out:]):
                if name is not None:
                    aux_updates[name] = arr
            if monitor is not None:
                monitor(node, env[id(node)])
        outputs = [env[nid][i] for nid, i in out_refs]
        return outputs, aux_updates

    return eval_fn


def mirror_segments_for(symbol, force=False):
    """Segment count for the memory-mirror mode (0 = off).  Engages when
    MXNET_BACKWARD_DO_MIRROR=1 (or ``force``, the SPMDTrainer remat
    param); MXNET_MIRROR_SEGMENTS overrides the sqrt-of-op-count
    default."""
    from .base import get_env
    if not force and str(get_env(ENV_BACKWARD_DO_MIRROR, "0")) != "1":
        return 0
    n_ops = sum(1 for nd_ in symbol._nodes() if nd_.op is not None)
    return max(2, int(get_env(ENV_MIRROR_SEGMENTS,
                              int(np.sqrt(max(1, n_ops))))))


def _run_plan_nodes(chunk, env, args, aux, rng, is_train, aux_updates,
                    monitor=None):
    """Interpret a slice of the node plan against ``env`` (id -> outputs
    tuple).  Shared by the plain and segmented eval builders."""
    for node, call_attrs, n_out, aux_var_names, rng_ix, override in chunk:
        if node.op is None:
            if node.name in args:
                val = args[node.name]
            elif node.name in aux:
                val = aux[node.name]
            else:
                raise MXNetError("unbound variable %r" % node.name)
            env[id(node)] = (val,)
            continue
        kw = {}
        if node.op.needs_is_train or override is not None:
            # override bodies ALWAYS receive is_train (train/eval
            # lowering choices are theirs to make), whatever the
            # underlying op declares
            kw["is_train"] = is_train
        if node.op.needs_rng:
            kw["rng"] = jax.random.fold_in(rng, rng_ix)
        if override is not None:
            # fusion override (mxfuse passes): fn replaces the op, with
            # the referenced extra inputs appended (conv data/weights).
            # Inputs the override declared dead on the inference path
            # ride as None — their producers may have been pruned from
            # the eval trace by infer_trace (the fn ignores them there)
            fn, extra_refs = override[0], override[1]
            dead = override[2] if len(override) > 2 and not is_train \
                else ()
            ins = [None if pos in dead else env[id(src)][idx]
                   for pos, (src, idx) in enumerate(node.inputs)]
            for src, idx in extra_refs:
                if id(src) not in env and src.op is None:
                    # variable extras may sit LATER in plan order than
                    # this entry (a merged group references every
                    # sibling's weights) — bind them on first touch
                    if src.name in args:
                        env[id(src)] = (args[src.name],)
                    elif src.name in aux:
                        env[id(src)] = (aux[src.name],)
                    else:
                        raise MXNetError("unbound variable %r"
                                         % src.name)
                ins.append(env[id(src)][idx])
        else:
            fn = node.op.fn
            ins = [env[id(src)][idx] for src, idx in node.inputs]
        # named_scope stamps the symbol node name into HLO op_name
        # metadata, so device profiles attribute fused-program time back
        # to graph nodes (reference per-op profiler semantics,
        # src/engine/profiler.cc AddOprStat with opr_name)
        with jax.named_scope(node.name):
            out = fn(*ins, **call_attrs, **kw)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        env[id(node)] = tuple(out[:n_out])
        for name, arr in zip(aux_var_names, out[n_out:]):
            if name is not None:
                aux_updates[name] = arr
        if monitor is not None:
            monitor(node, env[id(node)])


def _mirror_stage(node):
    """The ``mirror_stage`` a graph node was built under (``with
    mx.AttrScope(mirror_stage="l0_attn")``), or None."""
    return None if node.op is None else node.attrs.get("mirror_stage")


def _stage_chunks(fused_plan):
    """[(stage or None, entries)]: maximal runs of plan entries built under
    one ``mirror_stage``.  A variable belongs to the run it falls in."""
    chunks, current = [], object()
    for entry in fused_plan:
        node = entry[0]
        stage = current if node.op is None and chunks \
            else _mirror_stage(node)
        if not chunks or stage != current:
            chunks.append((stage, []))
            current = stage
        chunks[-1][1].append(entry)
    return chunks


def _build_eval_segmented(plan, fused_plan, out_refs, n_segments):
    """Segmented-remat eval: the plan is split into chunks, each wrapped
    in jax.checkpoint.  Residuals between segments are only the live
    boundary values, so activation memory scales with the segment size
    while the backward recomputes within each segment.  With
    ``n_segments`` the chunks are that many equal runs of entries
    (MXNET_BACKWARD_DO_MIRROR); with 0 they are the graph's own
    ``mirror_stage`` runs: each stage is one checkpoint under a
    ``jax.named_scope`` of the stage's name — so a device trace still
    tells the stages apart, forward and backward, which a bare
    checkpoint's op names do not — and entries built under no stage (an
    embedding, a head) are not rematerialised.  Monitored (per-op tap)
    runs interpret the plain ``plan``; everything else runs the (possibly
    BN-fused) ``fused_plan`` — same node positions, so the liveness
    analysis below serves both."""
    if n_segments:
        n = len(fused_plan)
        seg_size = max(1, -(-n // n_segments))
        stages = [""] * -(-n // seg_size)
        chunks = [fused_plan[i:i + seg_size] for i in range(0, n, seg_size)]
    else:
        stages, chunks = zip(*_stage_chunks(fused_plan))

    # liveness: which node outputs cross each boundary
    produced_in = {}
    for ci, chunk in enumerate(chunks):
        for node, *_ in chunk:
            produced_in[id(node)] = ci
    consumers = {}   # id -> last chunk index that reads it
    for ci, chunk in enumerate(chunks):
        for entry in chunk:
            node, override = entry[0], entry[5]
            if node.op is not None:
                refs = list(node.inputs)
                if override is not None:
                    refs += list(override[1])   # fusion extra inputs
                for src, _idx in refs:
                    consumers[id(src)] = max(consumers.get(id(src), -1), ci)
    for nid, _ in out_refs:
        consumers[nid] = len(chunks)
    live_out = []   # per chunk: ids leaving that boundary, ordered
    for ci in range(len(chunks)):
        ids = [nid for nid, pc in produced_in.items()
               if pc <= ci and consumers.get(nid, -1) > ci]
        live_out.append(ids)

    def eval_fn(args, aux, rng, is_train, monitor=None):
        if monitor is not None:
            # monitored (per-op tap) runs use the plain interpretation
            env, aux_updates = {}, {}
            _run_plan_nodes(plan, env, args, aux, rng, is_train,
                            aux_updates, monitor)
            return [env[nid][i] for nid, i in out_refs], aux_updates

        aux_updates = {}
        carry_ids = []
        carry_vals = ()

        for ci, chunk in enumerate(chunks):
            ids_in = list(carry_ids)
            ids_out = live_out[ci]

            def seg(vals_in, args, aux, rng, _chunk=chunk, _in=ids_in,
                    _out=ids_out):
                env = dict(zip(_in, vals_in))
                seg_aux = {}
                _run_plan_nodes(_chunk, env, args, aux, rng, is_train,
                                seg_aux)
                return tuple(env[i] for i in _out), seg_aux

            stage = stages[ci]
            run = seg if stage is None else jax.checkpoint(seg)
            with jax.named_scope(stage) if stage else nullcontext():
                out_vals, seg_aux = run(carry_vals, args, aux, rng)
            aux_updates.update(seg_aux)
            carry_ids, carry_vals = ids_out, out_vals

        env = dict(zip(carry_ids, carry_vals))
        outputs = [env[nid][i] for nid, i in out_refs]
        return outputs, aux_updates

    return eval_fn


class Executor(object):
    """Bound, compiled executor (parity: python/mxnet/executor.py)."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None):
        self._symbol = symbol
        self._ctx = Context(ctx) if not isinstance(ctx, Context) else ctx
        self._group2ctx = group2ctx or {}
        self._monitor_callback = None
        self._monitor_all = False

        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        self.arg_dict = _to_dict("args", args, arg_names, self._ctx)
        self.aux_dict = _to_dict("aux_states", aux_states, aux_names,
                                 self._ctx, allow_missing=not aux_names)
        self.grad_req = _req_dict(grad_req, arg_names)
        if args_grad is None:
            self.grad_dict = {}
        else:
            self.grad_dict = _to_dict("args_grad", args_grad, arg_names,
                                      self._ctx, allow_missing=True)
        self._diff_names = tuple(
            n for n in arg_names
            if self.grad_req.get(n, "null") != "null" and n in self.grad_dict)

        # group2ctx model parallelism: resolve each node's ctx_group to a
        # device; active only when ≥2 distinct devices result (a single
        # device degenerates to the normal fused path)
        placement = {}
        if self._group2ctx:
            for node in symbol._nodes():
                grp = node.attrs.get("ctx_group")
                c = self._group2ctx.get(grp) if grp else None
                placement[id(node)] = (c if c is not None
                                       else self._ctx).jax_device
            if len(set(placement.values())) <= 1:
                placement = {}
        self._placement = placement

        self._eval = _build_eval(symbol, placement=placement or None,
                                 mirror_segments=mirror_segments_for(symbol))
        # graphs holding host-callback ops (Custom) can only be whole-graph
        # jitted if the backend supports callbacks under jit; otherwise run
        # eagerly — the reference likewise executes CustomOp host-side
        # between kernel launches (src/operator/custom/custom-inl.h).
        # Multi-device group2ctx placement also runs eagerly: one XLA
        # program compiles for one device, while eager ops dispatch on
        # their (committed) input devices.
        has_no_jit = any(n.op is not None and getattr(n.op, "no_jit", False)
                         for n in symbol._nodes())
        from .ops.registry import callbacks_under_jit_supported
        use_jit = (not has_no_jit or callbacks_under_jit_supported()) \
            and not placement
        _maybe_jit = jax.jit if use_jit else (lambda f: f)
        self._jit_fwd = _maybe_jit(
            lambda a, x, r: self._eval(a, x, r, False)[0])
        self._jit_fwd_train = _maybe_jit(
            lambda a, x, r: self._eval(a, x, r, True))
        diff_names = self._diff_names

        # memory mirror mode lives inside self._eval (segmented
        # jax.checkpoint, see _build_eval_segmented)

        def train_fn(args, aux, rng, heads):
            diff = {k: args[k] for k in diff_names}
            rest = {k: v for k, v in args.items() if k not in diff}

            def f(d):
                merged = dict(rest)
                merged.update(d)
                outs, auxu = self._eval(merged, aux, rng, True)
                return tuple(outs), auxu

            outs, vjp_fn, auxu = jax.vjp(f, diff, has_aux=True)
            grads, = vjp_fn(tuple(heads))
            return list(outs), grads, auxu

        self._jit_train = _maybe_jit(train_fn)

        self._outputs = None      # list[NDArray]
        self._grads = None        # dict name -> jax array
        self._head_cache = {}     # arg-shape signature -> ones head grads

    # -- construction helpers --------------------------------------------
    @staticmethod
    def _simple_bind(symbol, ctx, grad_req="write", type_dict=None,
                     group2ctx=None, shared_exec=None, shapes=None):
        arg_shapes, _, aux_shapes = symbol.infer_shape(**(shapes or {}))
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        arg_types, _, aux_types = symbol.infer_type(
            **{k: v for k, v in (type_dict or {}).items()})
        args = {}
        for name, shape, typ in zip(arg_names, arg_shapes, arg_types):
            args[name] = nd_zeros(shape, ctx=ctx, dtype=np.dtype(typ))
        aux = {}
        for name, shape, typ in zip(aux_names, aux_shapes, aux_types):
            aux[name] = nd_zeros(shape, ctx=ctx, dtype=np.dtype(typ))
        req = _req_dict(grad_req, arg_names)
        grads = {name: nd_zeros(shape, ctx=ctx)
                 for name, shape in zip(arg_names, arg_shapes)
                 if req.get(name, "null") != "null"}
        return Executor(symbol, ctx, args, args_grad=grads, grad_req=grad_req,
                        aux_states=aux, group2ctx=group2ctx,
                        shared_exec=shared_exec)

    # -- dict/list views ---------------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._symbol.list_arguments()]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n)
                for n in self._symbol.list_arguments()]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._symbol.list_auxiliary_states()]

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    # -- execution ---------------------------------------------------------
    def _raw(self, d):
        return {k: v._data for k, v in d.items()}

    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown forward argument %r" % k)
            if isinstance(v, NDArray):
                self.arg_dict[k]._data = v._data
            else:
                self.arg_dict[k][:] = v
        rng = _random.next_key()
        self._last_rng = rng
        args, aux = self._raw(self.arg_dict), self._raw(self.aux_dict)

        if self._monitor_callback is not None:
            return self._forward_monitored(args, aux, rng, is_train)

        if is_train and self._diff_names:
            heads = self._ones_heads()
            outs, grads, auxu = self._jit_train(args, aux, rng, heads)
            self._grads = grads
        elif is_train:
            outs, auxu = self._jit_fwd_train(args, aux, rng)
            self._grads = None
        else:
            outs = self._jit_fwd(args, aux, rng)
            auxu = {}
            self._grads = None
        self._outputs = [NDArray._from_jax(o) for o in outs]
        if is_train:
            self._apply_aux(auxu)
        return self._outputs

    def _ones_heads(self):
        sig = tuple(sorted((k, v.shape) for k, v in self.arg_dict.items()))
        heads = self._head_cache.get(sig)
        if heads is None:
            _, out_shapes, _ = self._symbol.infer_shape_partial(
                **{k: v.shape for k, v in self.arg_dict.items()})
            heads = [jnp.ones(s if s is not None else (), dtype=jnp.float32)
                     for s in out_shapes]
            self._head_cache[sig] = heads
        return heads

    def _apply_aux(self, auxu):
        for name, arr in auxu.items():
            if name in self.aux_dict:
                self.aux_dict[name]._data = arr

    #: ops whose backward supplies its OWN head gradient (their custom
    #: vjp ignores the incoming cotangent) — the reference's loss layers,
    #: which need no entry in a user-passed out_grads list
    _SELF_GRAD_OPS = frozenset((
        "MakeLoss", "make_loss", "SoftmaxOutput", "softmax_output",
        "LinearRegressionOutput", "MAERegressionOutput",
        "LogisticRegressionOutput", "SVMOutput", "BlockGrad", "stop_gradient",
    ))

    def _pad_out_grads(self, heads):
        """Match user heads to outputs the way the reference does: loss
        outputs (self-gradient ops, incl. need_top_grad=False Customs)
        are skipped; the given heads fill the remaining outputs in
        order; anything left unmatched gets zeros
        (reference graph_executor head_grad binding for the
        Module.backward(out_grads) contract, e.g. the
        parallel_actor_critic example's [log_policy, value] heads next
        to a MakeLoss entropy term and a BlockGrad output)."""
        n_out = len(self._symbol._outputs)
        if len(heads) == n_out:
            return heads
        # zero cotangents must match each output's exact aval: prefer the
        # freshest forward outputs (shape AND dtype); fall back to
        # inferred shapes at float32
        if self._outputs is not None and len(self._outputs) == n_out:
            out_avals = [(o._data.shape, o._data.dtype)
                         for o in self._outputs]
        else:
            _, out_shapes, _ = self._symbol.infer_shape_partial(
                **{k: v.shape for k, v in self.arg_dict.items()})
            out_avals = [(s or (), jnp.float32) for s in out_shapes]
        it = iter(heads)
        full = []
        for (node, _idx), (shape, dtype) in zip(self._symbol._outputs,
                                                out_avals):
            op_name = getattr(node.op, "name", None) if node.op else None
            self_grad = op_name in self._SELF_GRAD_OPS
            if op_name == "Custom":
                from .operator import _prop_for
                try:
                    self_grad = not _prop_for(node.attrs).need_top_grad_
                except Exception:  # noqa: BLE001 — unknown op_type
                    self_grad = False
            if self_grad:
                full.append(jnp.zeros(shape, dtype))
            else:
                g = next(it, None)
                full.append(jnp.zeros(shape, dtype) if g is None else g)
        leftover = list(it)
        if leftover:
            raise MXNetError(
                "backward: %d out_grads given but only %d outputs "
                "accept head gradients" % (len(heads),
                                           len(heads) - len(leftover)))
        return full

    def backward(self, out_grads=None):
        """Write gradients into grad arrays.  Uses the cached fused-step
        gradients when called without explicit head gradients."""
        if not self._diff_names:
            return
        if out_grads is not None:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            heads = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                     for g in out_grads]
            heads = self._pad_out_grads(heads)
            args, aux = self._raw(self.arg_dict), self._raw(self.aux_dict)
            # reuse the forward pass's RNG key so stochastic ops (Dropout,
            # rrelu) see the same masks the observed outputs were computed
            # with — otherwise the gradients would belong to a different
            # sampled forward
            rng = getattr(self, "_last_rng", None)
            if rng is None:
                rng = _random.next_key()
                self._last_rng = rng
            outs, grads, _auxu = self._jit_train(args, aux, rng, heads)
            self._outputs = [NDArray._from_jax(o) for o in outs]
            self._grads = grads
        if self._grads is None:
            # forward(is_train=True) was not called — run the fused step now
            args, aux = self._raw(self.arg_dict), self._raw(self.aux_dict)
            rng = _random.next_key()
            self._last_rng = rng
            outs, grads, auxu = self._jit_train(args, aux, rng,
                                                self._ones_heads())
            self._outputs = [NDArray._from_jax(o) for o in outs]
            self._grads = grads
            self._apply_aux(auxu)
        for name in self._diff_names:
            garr = self.grad_dict[name]
            g = self._grads[name].astype(garr._data.dtype)
            if self.grad_req[name] == "add":
                garr._data = garr._data + g
            else:
                garr._data = g

    @property
    def outputs(self):
        if self._outputs is None:
            self.forward()
        return self._outputs

    # -- monitored (eager) execution for mx.mon.Monitor --------------------
    def set_monitor_callback(self, callback, monitor_all=False):
        """Install a per-node output tap (reference
        MXExecutorSetMonitorCallback / graph_executor.cc:69-72).  Runs the
        graph eagerly (unfused) while installed."""
        self._monitor_callback = callback
        self._monitor_all = monitor_all

    def _forward_monitored(self, args, aux, rng, is_train):
        taps = []

        monitor_all = self._monitor_all

        def monitor(node, outs):
            names = ([node.name + "_output"] if len(outs) == 1 else
                     ["%s_output%d" % (node.name, i) for i in range(len(outs))])
            for nm, arr in zip(names, outs):
                taps.append((nm, arr))

        if monitor_all:
            for name, arr in {**aux, **args}.items():
                taps.append((name, arr))

        outs, auxu = self._eval(args, aux, rng, is_train, monitor=monitor)
        self._outputs = [NDArray._from_jax(o) for o in outs]
        if is_train:
            self._apply_aux(auxu)
        self._grads = None
        for nm, arr in taps:
            self._monitor_callback(nm, NDArray._from_jax(arr))
        return self._outputs

    # -- misc ---------------------------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        from .ndarray import _to_device
        for name, arr in arg_params.items():
            if name in self.arg_dict:
                dst = self.arg_dict[name]
                dst._data = _to_device(arr._data.astype(dst._data.dtype),
                                       dst._ctx)
            elif not allow_extra_params:
                raise MXNetError("unknown argument %r" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    dst = self.aux_dict[name]
                    dst._data = _to_device(arr._data.astype(dst._data.dtype),
                                           dst._ctx)
                elif not allow_extra_params:
                    raise MXNetError("unknown aux state %r" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **new_shapes):
        """Return a new executor for new input shapes, sharing parameter
        arrays (executor.py:reshape).  Recompilation is handled by jit."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**new_shapes)
        arg_names = self._symbol.list_arguments()
        new_args, new_grads = {}, {}
        for name, shape in zip(arg_names, arg_shapes):
            cur = self.arg_dict[name]
            if cur.shape == tuple(shape):
                new_args[name] = cur
                if name in self.grad_dict:
                    new_grads[name] = self.grad_dict[name]
            else:
                if name not in new_shapes and not partial_shaping:
                    raise MXNetError(
                        "reshape changes the shape of parameter %r from %s to "
                        "%s; pass partial_shaping=True to allow reallocating "
                        "it (contents are NOT preserved)"
                        % (name, cur.shape, tuple(shape)))
                new_args[name] = nd_zeros(shape, ctx=self._ctx)
                if name in self.grad_dict:
                    new_grads[name] = nd_zeros(shape, ctx=self._ctx)
        return Executor(self._symbol, self._ctx, new_args,
                        args_grad=new_grads or None, grad_req=self.grad_req,
                        aux_states=self.aux_dict, group2ctx=self._group2ctx)

    def debug_str(self):
        lines = ["Symbol Outputs:"]
        for name in self._symbol.list_outputs():
            lines.append("\toutput[%s]" % name)
        for node in self._symbol._nodes():
            if node.is_variable:
                lines.append("Variable:%s" % node.name)
            else:
                ins = ", ".join(s.name for s, _ in node.inputs)
                lines.append("Op:%s, Name=%s\n\tInputs:\n\t\t%s"
                             % (node.op.name, node.name, ins))
        return "\n".join(lines)


def _to_dict(what, values, names, ctx, allow_missing=False):
    if values is None:
        if allow_missing:
            return {}
        raise MXNetError("%s must be provided" % what)
    if isinstance(values, dict):
        out = {}
        for name in names:
            if name in values:
                v = values[name]
                out[name] = v if isinstance(v, NDArray) else NDArray(v, ctx=ctx)
            elif not allow_missing:
                raise MXNetError("%s: missing entry %r" % (what, name))
        return out
    values = list(values)
    if len(values) != len(names):
        raise MXNetError("%s: length mismatch (%d given, %d needed: %s)"
                         % (what, len(values), len(names), names))
    return {n: (v if isinstance(v, NDArray) else NDArray(v, ctx=ctx))
            for n, v in zip(names, values) if v is not None}


def _req_dict(grad_req, arg_names):
    if isinstance(grad_req, str):
        return {n: grad_req for n in arg_names}
    if isinstance(grad_req, (list, tuple)):
        return dict(zip(arg_names, grad_req))
    if isinstance(grad_req, dict):
        return {n: grad_req.get(n, "null") for n in arg_names}
    raise MXNetError("invalid grad_req %r" % (grad_req,))


def _executor_close(self):
    """Release this executor's compiled programs and the buffers it owns
    (its outputs), and drop its references to the bound arrays (reference
    ~GraphExecutor frees its memory pool; jax buffers otherwise wait for
    GC and retained jit wrappers pin executables).  The bound
    arg/grad/aux arrays are CALLER-owned — they may be shared with other
    executors (shared_exec bucketing) or still be the caller's parameter
    NDArrays — so close() must not delete them, only unpin them.  The
    executor is unusable afterwards; safe to call twice."""
    # On the eager (non-jit) path a passthrough graph output can BE one of
    # the caller's bound arrays (identity, not a copy) — deleting it would
    # invalidate a caller-owned buffer, so collect bound identities first.
    bound = set()
    for d in (self.arg_dict, self.aux_dict, self.grad_dict):
        for arr in (d or {}).values():
            data = getattr(arr, "_data", None)
            if isinstance(data, jax.Array):
                bound.add(id(data))
    for o in (self._outputs or []):
        data = getattr(o, "_data", None)
        if isinstance(data, jax.Array) and id(data) not in bound:
            try:
                data.delete()
            except Exception:  # noqa: BLE001
                pass
    self._outputs = None
    self.arg_dict = {}
    self.aux_dict = {}
    self.grad_dict = {}
    for attr in ("_jit_fwd", "_jit_fwd_train", "_jit_train"):
        fn = getattr(self, attr, None)
        if fn is not None and hasattr(fn, "clear_cache"):
            try:
                fn.clear_cache()
            except Exception:  # noqa: BLE001
                pass
        setattr(self, attr, None)
    self._eval = None
    import gc
    gc.collect()


Executor.close = _executor_close
Executor.__enter__ = lambda self: self
Executor.__exit__ = (
    lambda self, exc_type, exc_val, exc_tb: (self.close(), False)[1])
