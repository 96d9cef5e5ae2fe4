"""Operator registry — the single source of truth for all ops.

Re-designs the reference's dual registries (NNVM FCompute ops,
include/mxnet/op_attr_types.h:33-63, and legacy OperatorProperty,
include/mxnet/operator.h:77-155) as ONE registry of pure JAX functions.
Each op is a pure function over jax.Arrays; the imperative layer (ndarray.py)
jit-caches it per attr-set, and the symbolic layer (symbol.py/executor.py)
traces it into a whole-graph jit — which is how the reference's cached-op /
bulk-segment machinery (src/executor/graph_executor.cc:556,690) collapses
into XLA's own fusion.

Op conventions
--------------
``fn(*inputs, **attrs)`` -> jax.Array | tuple of jax.Arrays
  - inputs are the op's data+parameter inputs, in ``input_names`` order,
    followed by aux states in ``aux_names`` order (BatchNorm moving stats —
    the reference's auxiliary states, include/mxnet/operator.h aux_states).
  - if ``needs_is_train``: fn must accept keyword ``is_train`` (bool, static).
  - if ``needs_rng``: fn must accept keyword ``rng`` (jax PRNG key).
  - ops with aux states return outputs + updated aux concatenated in one flat
    tuple; the executor splits on ``num_outputs``.
"""
from __future__ import annotations

import functools

from ..base import MXNetError, parse_attr_value, register_env

ENV_CUSTOM_UNDER_JIT = register_env(
    "MXNET_CUSTOM_UNDER_JIT", default=0,
    doc="1 lets graphs with Custom (host-callback) ops be whole-graph "
        "jitted; default runs them eagerly per-op")

__all__ = ["OpDef", "register", "get_op", "list_ops", "OP_REGISTRY", "apply_op"]

OP_REGISTRY = {}


# attrs the framework itself attaches to nodes (AttrScope / optimizer
# multipliers / graph plumbing) — always allowed alongside op params
FRAMEWORK_ATTRS = frozenset({
    "ctx_group", "lr_mult", "wd_mult", "force_mirroring", "mirror_stage",
    "num_args",
})


@functools.lru_cache(maxsize=2048)
def fn_signature_info(fn):
    """(keyword-accepting param names, has **kwargs) of a lowering fn —
    shared by attr validation here and executor._filter_attrs."""
    import inspect
    params = inspect.signature(fn).parameters
    has_var_kw = any(p.kind == inspect.Parameter.VAR_KEYWORD
                     for p in params.values())
    names = frozenset(p.name for p in params.values()
                      if p.kind in (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                                    inspect.Parameter.KEYWORD_ONLY))
    return names, has_var_kw


class OpDef(object):
    __slots__ = (
        "name", "fn", "input_names", "aux_names", "num_outputs",
        "infer_shape", "needs_is_train", "needs_rng", "variable_inputs",
        "aliases", "output_names", "hidden", "param_indices", "doc",
        "no_jit", "extra_attrs", "dynamic_attrs", "_accepted",
    )

    def __init__(self, name, fn, input_names=("data",), aux_names=(),
                 num_outputs=1, infer_shape=None, needs_is_train=False,
                 needs_rng=False, variable_inputs=False, aliases=(),
                 output_names=None, hidden=False, no_jit=False,
                 extra_attrs=(), dynamic_attrs=()):
        self.name = name
        self.fn = fn
        self.input_names = input_names          # tuple | callable(attrs)->tuple
        self.aux_names = aux_names              # tuple | callable(attrs)->tuple
        self.num_outputs = num_outputs          # int | callable(attrs)->int
        self.infer_shape = infer_shape          # optional custom shape inference
        self.needs_is_train = needs_is_train
        self.needs_rng = needs_rng
        self.variable_inputs = variable_inputs  # Concat/add_n style variadic
        self.aliases = tuple(aliases)
        self.output_names = output_names        # tuple | callable(attrs)->tuple
        self.hidden = hidden
        self.no_jit = no_jit    # host-callback ops: run eagerly, never jit
        self.extra_attrs = tuple(extra_attrs)  # attrs consumed outside fn
        # scalar attrs passed as TRACED args, not compile-time constants:
        # the imperative jit cache stays one entry per op+shape even when
        # the value changes every call (optimizer lr schedules/bias
        # correction — the reference likewise passes lr at call time,
        # src/operator/optimizer_op-inl.h SGDParam fields are runtime
        # kwargs, not compile specializations)
        self.dynamic_attrs = tuple(dynamic_attrs)
        self._accepted = None   # lazy cache for accepted_attrs()
        self.doc = fn.__doc__

    # -- resolved-per-attrs accessors ------------------------------------
    def get_input_names(self, attrs):
        names = self.input_names
        return tuple(names(attrs)) if callable(names) else tuple(names)

    def get_aux_names(self, attrs):
        names = self.aux_names
        return tuple(names(attrs)) if callable(names) else tuple(names)

    def get_num_outputs(self, attrs):
        n = self.num_outputs
        return n(attrs) if callable(n) else n

    def get_output_names(self, attrs):
        if self.output_names is None:
            n = self.get_num_outputs(attrs)
            if n == 1:
                return ("output",)
            return tuple("output%d" % i for i in range(n))
        names = self.output_names
        return tuple(names(attrs)) if callable(names) else tuple(names)

    def normalize_attrs(self, attrs):
        """Parse string attr values into typed python values."""
        return {k: parse_attr_value(v) for k, v in attrs.items()}

    def accepted_attrs(self):
        """The op's declared parameter surface (the dmlc::Parameter schema
        analog: kwargs of the lowering function plus declared extra_attrs,
        minus tensor inputs/aux and the is_train/rng specials), or None
        when the function takes **kwargs."""
        if self._accepted is None:
            names, has_var_kw = fn_signature_info(self.fn)
            if has_var_kw:
                self._accepted = "any"
            else:
                drop = {"is_train", "rng"}
                try:
                    drop |= set(self.get_input_names({}))
                    drop |= set(self.get_aux_names({}))
                except Exception:  # noqa: BLE001 — attr-dependent callables
                    pass
                self._accepted = frozenset(
                    (names | set(self.extra_attrs)) - drop)
        return None if self._accepted == "any" else self._accepted

    def validate_attrs(self, attrs, where="op call"):
        """Reject unknown parameters instead of silently dropping them —
        dmlc::Parameter semantics (the reference errors on a typo'd
        ``kernal=(3,3)``; src/operator/optimizer_op-inl.h:25-45).
        Framework attrs and ``__dunder__`` user attrs always pass."""
        accepted = self.accepted_attrs()
        if accepted is None:
            return
        bad = [k for k in attrs
               if k not in accepted and k not in FRAMEWORK_ATTRS
               and not (k.startswith("__") and k.endswith("__"))]
        if bad:
            import difflib
            hints = []
            for k in bad:
                close = difflib.get_close_matches(k, sorted(accepted), n=1)
                hints.append("%r%s" % (k, (" (did you mean %r?)" % close[0])
                                       if close else ""))
            raise MXNetError(
                "%s %s: unknown parameter(s) %s; accepted parameters: %s"
                % (self.name, where, ", ".join(hints),
                   ", ".join(sorted(accepted))))

    def __repr__(self):
        return "OpDef(%s)" % self.name


def register(name, **kwargs):
    """Decorator registering a JAX function as an op.

    Example::

        @register("broadcast_add", input_names=("lhs", "rhs"),
                  aliases=("broadcast_plus",))
        def broadcast_add(lhs, rhs):
            return jnp.add(lhs, rhs)
    """
    def _reg(fn):
        opdef = OpDef(name, fn, **kwargs)
        if name in OP_REGISTRY:
            raise MXNetError("op %r registered twice" % name)
        OP_REGISTRY[name] = opdef
        for alias in opdef.aliases:
            OP_REGISTRY[alias] = opdef
        return fn
    return _reg


def get_op(name):
    try:
        return OP_REGISTRY[name]
    except KeyError:
        raise MXNetError("operator %r is not registered" % (name,)) from None


def list_ops():
    """Distinct canonical op names (MXListAllOpNames analog)."""
    return sorted({op.name for op in OP_REGISTRY.values()})


# ---------------------------------------------------------------------------
# jit-cached imperative application
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=8192)
def _jitted(op_name, attr_items, dyn_names, is_train, with_rng):
    """One compiled callable per (op, static attrs, is_train) — the TPU
    analog of the reference's cached engine ops (graph_executor.cc:556).
    ``dyn_names`` attrs arrive as traced scalars (first positional arg, a
    tuple) so their values don't key the cache."""
    import jax
    op = get_op(op_name)
    attrs = dict(attr_items)
    kw = {}
    if op.needs_is_train:
        kw["is_train"] = is_train

    if with_rng:
        def call(rng, dyn_vals, *arrays):
            return op.fn(*arrays, rng=rng, **attrs,
                         **dict(zip(dyn_names, dyn_vals)), **kw)
    else:
        def call(dyn_vals, *arrays):
            return op.fn(*arrays, **attrs,
                         **dict(zip(dyn_names, dyn_vals)), **kw)
    return jax.jit(call)


def callbacks_under_jit_supported():
    """Whether graphs containing host-callback ops (Custom) may be
    whole-graph jitted.  Default: NO — callbacks then run inside the
    compiled program on a runtime callback thread, and a concurrent
    device_get on the main thread (metric pulls, async dispatch) can
    deadlock against the callback's own host transfers (observed:
    CustomOp inside Module.fit hangs intermittently).  Eager per-op
    execution mirrors the reference, where CustomOp is always a
    host-side engine callback between kernel launches
    (src/operator/custom/custom-inl.h), and makes stateful callback RNG
    deterministic (pure_callback gives no execution-count guarantee).
    Set MXNET_CUSTOM_UNDER_JIT=1 to opt into fused custom-op graphs
    (host callbacks run under jit on the CPU and TPU backends alike).
    The env var is read per call, so toggling it mid-process takes
    effect at the next bind."""
    from ..base import get_env
    return str(get_env(ENV_CUSTOM_UNDER_JIT, "0")) == "1"


def _hashable(v):
    if isinstance(v, list):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


def apply_op(op, arrays, attrs, is_train=False, rng=None):
    """Run an op imperatively on jax.Arrays, via the per-attr jit cache.

    Returns a tuple of jax.Arrays (outputs, then updated aux if any).
    """
    op.validate_attrs(attrs, where="imperative call")
    attrs = op.normalize_attrs(attrs)
    accepted = op.accepted_attrs()
    if accepted is not None:
        # framework attrs (ctx_group/lr_mult/...) validated above but not
        # consumed by the lowering fn
        attrs = {k: v for k, v in attrs.items() if k in accepted}
    with_rng = op.needs_rng
    # is_train only keys the cache for ops whose behavior depends on it —
    # otherwise autograd's train-mode default would double-compile every op
    is_train = bool(is_train) and op.needs_is_train
    if op.no_jit:
        kw = {}
        if op.needs_is_train:
            kw["is_train"] = is_train
        if with_rng:
            if rng is None:
                from .. import random as _random
                rng = _random.next_key()
            kw["rng"] = rng
        out = op.fn(*arrays, **attrs, **kw)
        if isinstance(out, (tuple, list)):
            return tuple(out)
        return (out,)
    dyn_names = tuple(k for k in op.dynamic_attrs if k in attrs)
    dyn_vals = tuple(float(attrs[k]) for k in dyn_names)
    items = tuple(sorted((k, _hashable(v)) for k, v in attrs.items()
                         if k not in dyn_names))
    fn = _jitted(op.name, items, dyn_names, is_train, with_rng)
    if with_rng:
        if rng is None:
            from .. import random as _random
            rng = _random.next_key()
        out = fn(rng, dyn_vals, *arrays)
    else:
        out = fn(dyn_vals, *arrays)
    if isinstance(out, (tuple, list)):
        return tuple(out)
    return (out,)
