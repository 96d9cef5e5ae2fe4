"""Custom operators written in Python (reference python/mxnet/operator.py).

The reference routes Python callbacks through the C API (`MXCustomOpRegister`,
src/operator/custom/custom.cc) so they run engine-safely inside the threaded
executor.  Here the same surface — ``CustomOp``/``CustomOpProp`` +
``mx.operator.register`` and the legacy ``NumpyOp``/``NDArrayOp`` — lowers to
``jax.pure_callback`` (host callback with declared result shapes, the XLA
analog of the engine-safe callback) wrapped in ``jax.custom_vjp`` so the
user's ``backward`` defines the gradient.  Custom ops therefore work in BOTH
the imperative path and inside jit-compiled executor graphs.

Usage (identical to the reference)::

    @mx.operator.register("mysigmoid")
    class MySigmoidProp(mx.operator.CustomOpProp):
        def __init__(self):
            super().__init__(need_top_grad=True)
        def list_arguments(self): return ['data']
        def list_outputs(self): return ['output']
        def infer_shape(self, in_shape): return in_shape, [in_shape[0]], []
        def create_operator(self, ctx, shapes, dtypes): return MySigmoid()

    y = mx.sym.Custom(x, op_type='mysigmoid')
    y = mx.nd.Custom(x_nd, op_type='mysigmoid')
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ops.registry import register as _register_op

__all__ = ["CustomOp", "CustomOpProp", "register", "get_prop",
           "NumpyOp", "NDArrayOp"]


def _assign(dst, req, src):
    """Write src into dst honoring the grad_req (reference operator.py
    CustomOp.assign semantics, shared by all op base classes)."""
    if req == "null":
        return
    if not isinstance(src, np.ndarray) and hasattr(src, "asnumpy"):
        # an NDArray built inside the callback: pull it host-side once
        # here rather than letting numpy's setitem trigger __array__
        src = src.asnumpy()
    if req in ("write", "inplace"):
        dst[:] = src
    elif req == "add":
        dst[:] += src

# op_type -> CustomOpProp subclass (reference CustomOpProp registry,
# src/operator/custom/custom.cc CustomOpPropRegistry)
_PROP_REGISTRY = {}


class CustomOp(object):
    """Base class for custom operator implementations (reference
    operator.py:CustomOp)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError()

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError()

    def assign(self, dst, req, src):
        """Write src to dst honoring the grad_req (operator.py:assign)."""
        _assign(dst, req, src)


class CustomOpProp(object):
    """Operator properties: shapes, types, and operator creation (reference
    operator.py:CustomOpProp)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]] * len(self.list_outputs()), []

    def infer_type(self, in_type):
        return in_type, [in_type[0]] * len(self.list_outputs()), \
            [in_type[0]] * len(self.list_auxiliary_states())

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def list_auxiliary_states(self):
        return []

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad_:
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return CustomOp()


def register(reg_name):
    """Register a CustomOpProp subclass under ``op_type=reg_name``
    (reference operator.py:register / MXCustomOpRegister)."""
    def do_register(prop_cls):
        if not issubclass(prop_cls, CustomOpProp):
            raise MXNetError("can only register subclass of CustomOpProp")
        _PROP_REGISTRY[reg_name] = prop_cls
        _cached_prop.cache_clear()  # re-registration must not serve stale props
        return prop_cls
    return do_register


def get_prop(op_type):
    try:
        return _PROP_REGISTRY[op_type]
    except KeyError:
        raise MXNetError("custom op type %r is not registered "
                         "(use mx.operator.register)" % (op_type,)) from None


def _user_attrs(attrs):
    """kwargs forwarded to the user's prop ctor, as strings (the reference
    passes all op kwargs through the C API as char**)."""
    return {k: str(v) for k, v in attrs.items()
            if k != "op_type" and not k.startswith("__")}


@functools.lru_cache(maxsize=512)
def _cached_prop(op_type, attr_items):
    return get_prop(op_type)(**dict(attr_items))


def _prop_for(attrs):
    op_type = attrs.get("op_type")
    if op_type is None:
        raise MXNetError("Custom op requires op_type=...")
    items = tuple(sorted(_user_attrs(attrs).items()))
    return _cached_prop(op_type, items)


def _create_operator(op_type, attr_items, shapes, dtypes):
    """A fresh stateful operator per Custom-node instantiation: under a
    per-executor jit trace this yields one instance per bound executor
    (matching the reference, custom-inl.h CreateOperator); imperatively the
    forward/backward pair still shares the instance via the vjp closures."""
    prop = _cached_prop(op_type, attr_items)
    return prop.create_operator("tpu(0)", [list(s) for s in shapes],
                                [np.dtype(d).name for d in dtypes])


class _HostArray(np.ndarray):
    """What custom-op callbacks receive: a numpy view with the NDArray
    conveniences (.asnumpy/.wait_to_read/.copyto/.context).

    Callbacks run on a runtime callback thread while the compiled
    program that invoked them is still executing — creating device
    arrays there (the old path device_put every input) can deadlock
    against the main thread's device_get (observed with a CustomOp
    inside a fit loop).  The reference hands CPU NDArrays; a numpy view
    is the TPU-native equivalent: zero-copy, full numpy operator
    surface, and no device traffic from inside a callback."""

    def asnumpy(self):
        return np.asarray(self)

    def wait_to_read(self):
        pass

    wait_to_write = wait_to_read

    def copyto(self, other):
        other[:] = self
        return other

    @property
    def context(self):
        from .context import cpu
        return cpu()


def _wrap_nd(arrays):
    out = []
    for a in arrays:
        a = np.ascontiguousarray(a)
        if not a.flags.writeable:
            # np.asarray(jax.Array) aliases jax's read-only host cache;
            # callbacks write in-place (out/aux/in-grad buffers, and some
            # user ops scribble on inputs) — give them their own copy,
            # which is what the old device-NDArray path did implicitly
            a = a.copy()
        out.append(a.view(_HostArray))
    return out


def _custom_input_names(attrs):
    prop = _prop_for(attrs)
    return tuple(prop.list_arguments())


def _custom_aux_names(attrs):
    prop = _prop_for(attrs)
    return tuple(prop.list_auxiliary_states())


def _custom_num_outputs(attrs):
    return len(_prop_for(attrs).list_outputs())


def _custom_output_names(attrs):
    return tuple(_prop_for(attrs).list_outputs())


def _custom_infer_shape(attrs, in_shapes):
    prop = _prop_for(attrs)
    n_out = len(prop.list_outputs())
    if all(s is None for s in in_shapes):
        return list(in_shapes), [None] * n_out, []
    if any(s is None for s in in_shapes):
        # partial knowledge: the prop may be able to fill the rest (e.g.
        # weight shapes derived from data, the reference's standard
        # simple_bind flow); props that need every input just bail
        try:
            ret = prop.infer_shape([list(s) if s is not None else None
                                    for s in in_shapes])
        except (TypeError, IndexError, AttributeError):
            return list(in_shapes), [None] * n_out, []
        if len(ret) == 2:
            in_sh, out_sh = ret
            aux_sh = []
        else:
            in_sh, out_sh, aux_sh = ret
        return ([tuple(s) if s is not None else None for s in in_sh],
                [tuple(s) if s is not None else None for s in out_sh],
                [tuple(s) for s in aux_sh])
    ret = prop.infer_shape([list(s) for s in in_shapes])
    if len(ret) == 2:
        in_sh, out_sh = ret
        aux_sh = []
    else:
        in_sh, out_sh, aux_sh = ret
    return ([tuple(s) for s in in_sh], [tuple(s) for s in out_sh],
            [tuple(s) for s in aux_sh])


@_register_op("Custom", input_names=_custom_input_names,
              aux_names=_custom_aux_names, num_outputs=_custom_num_outputs,
              output_names=_custom_output_names,
              infer_shape=_custom_infer_shape, needs_is_train=True,
              no_jit=True)
def _custom(*inputs, is_train=False, **attrs):
    """Python CustomOp node (reference src/operator/custom/custom.cc) —
    host callback via jax.pure_callback, gradient via jax.custom_vjp."""
    prop = _prop_for(attrs)
    arg_names = prop.list_arguments()
    aux_names = prop.list_auxiliary_states()
    n_in, n_aux = len(arg_names), len(aux_names)
    n_out = len(prop.list_outputs())
    data_in, aux_in = inputs[:n_in], inputs[n_in:n_in + n_aux]

    in_shapes = tuple(tuple(x.shape) for x in data_in)
    _, out_shapes, _ = _custom_infer_shape(attrs, in_shapes)
    in_types = [np.dtype(x.dtype) for x in data_in]
    _, out_types, _ = prop.infer_type(in_types)
    op = _create_operator(attrs["op_type"],
                          tuple(sorted(_user_attrs(attrs).items())),
                          in_shapes, tuple(in_types))

    out_structs = tuple(jax.ShapeDtypeStruct(s, np.dtype(t))
                        for s, t in zip(out_shapes, out_types))
    aux_structs = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                        for a in aux_in)

    def host_forward(*arrs):
        ins = _wrap_nd(arrs[:n_in])
        auxs = _wrap_nd(arrs[n_in:])
        outs = _wrap_nd([np.zeros(s, t) for s, t in
                         zip(out_shapes, out_types)])
        op.forward(is_train=is_train, req=["write"] * n_out,
                   in_data=ins, out_data=outs, aux=auxs)
        return tuple(o.asnumpy() for o in outs) + \
            tuple(a.asnumpy() for a in auxs)

    def host_backward(*arrs):
        k = 0
        ograds = _wrap_nd(arrs[k:k + n_out]); k += n_out
        ins = _wrap_nd(arrs[k:k + n_in]); k += n_in
        outs = _wrap_nd(arrs[k:k + n_out]); k += n_out
        auxs = _wrap_nd(arrs[k:])
        igrads = _wrap_nd([np.zeros(s, t) for s, t in
                           zip(in_shapes, in_types)])
        op.backward(req=["write"] * n_in, out_grad=ograds, in_data=ins,
                    out_data=outs, in_grad=igrads, aux=auxs)
        return tuple(g.asnumpy() for g in igrads)

    in_structs = tuple(jax.ShapeDtypeStruct(s, t)
                       for s, t in zip(in_shapes, in_types))

    @jax.custom_vjp
    def run(data_in, aux_in):
        res = jax.pure_callback(host_forward, out_structs + aux_structs,
                                *data_in, *aux_in)
        return tuple(res)

    def run_fwd(data_in, aux_in):
        res = run(data_in, aux_in)
        return res, (data_in, aux_in, res[:n_out])

    def run_bwd(saved, cts):
        data_in_, aux_in_, outs = saved
        ograds = cts[:n_out]
        igrads = tuple(jax.pure_callback(
            host_backward, in_structs, *ograds, *data_in_, *outs, *aux_in_))
        # integer/bool primals take symbolic-zero (float0) cotangents
        fixed = []
        for g, x in zip(igrads, data_in_):
            if jnp.issubdtype(x.dtype, jnp.floating) or \
                    jnp.issubdtype(x.dtype, jnp.complexfloating):
                fixed.append(g)
            else:
                fixed.append(np.zeros(x.shape, dtype=jax.dtypes.float0))
        aux_zero = tuple(
            np.zeros(a.shape, dtype=jax.dtypes.float0)
            if not jnp.issubdtype(a.dtype, jnp.floating)
            else jnp.zeros_like(a) for a in aux_in_)
        return tuple(fixed), aux_zero

    run.defvjp(run_fwd, run_bwd)
    results = run(tuple(data_in), tuple(aux_in))
    return tuple(results)


# ---------------------------------------------------------------------------
# Legacy NumpyOp / NDArrayOp (reference operator.py:126-372) — instances are
# callable on symbols; internally adapted onto the Custom machinery.
# ---------------------------------------------------------------------------

class _LegacyOpAdapter(CustomOp):
    """NDArrayOp-style dispatch: the instance's fwd/bwd take NDArrays."""

    def __init__(self, inst):
        self._inst = inst

    def forward(self, is_train, req, in_data, out_data, aux):
        self._inst.forward(in_data=in_data, out_data=out_data)

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        self._inst.backward(out_grad=out_grad, in_data=in_data,
                            out_data=out_data, in_grad=in_grad)


def _np_copy(arrays):
    # writable host copies (asnumpy may alias a read-only device buffer)
    return [np.array(a.asnumpy()) for a in arrays]


class _NumpyOpAdapter(_LegacyOpAdapter):
    """NumpyOp-style dispatch: the instance's fwd/bwd take numpy arrays."""

    def forward(self, is_train, req, in_data, out_data, aux):
        ins = _np_copy(in_data)
        outs = _np_copy(out_data)
        self._inst.forward(in_data=ins, out_data=outs)
        for dst, src in zip(out_data, outs):
            dst[:] = src

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        ograds = _np_copy(out_grad)
        ins = _np_copy(in_data)
        outs = _np_copy(out_data)
        igrads = _np_copy(in_grad)
        self._inst.backward(out_grad=ograds, in_data=ins, out_data=outs,
                            in_grad=igrads)
        for dst, src in zip(in_grad, igrads):
            dst[:] = src


class _LegacyProp(CustomOpProp):
    """Adapter exposing a PythonOp instance through CustomOpProp."""

    def __init__(self, instance):
        super().__init__(need_top_grad=instance.need_top_grad_)
        self._inst = instance

    def list_arguments(self):
        return self._inst.list_arguments()

    def list_outputs(self):
        return self._inst.list_outputs()

    def infer_shape(self, in_shape):
        return self._inst.infer_shape(in_shape)

    def create_operator(self, ctx, in_shapes, in_dtypes):
        if isinstance(self._inst, NumpyOp):
            return _NumpyOpAdapter(self._inst)
        return _LegacyOpAdapter(self._inst)


class PythonOp(object):
    """Base of legacy python ops (reference operator.py:PythonOp)."""

    _legacy_count = [0]

    def __init__(self, need_top_grad=True):
        self.info_ = None
        self.need_top_grad_ = need_top_grad
        self._op_type = None

    def __call__(self, *args, **kwargs):
        return self.get_symbol(*args, **kwargs)

    def get_symbol(self, *args, **kwargs):
        from . import symbol as sym_mod
        if self._op_type is None:    # one registry entry per instance
            self._op_type = "_legacy_python_op_%d" % PythonOp._legacy_count[0]
            PythonOp._legacy_count[0] += 1
            inst = self
            _PROP_REGISTRY[self._op_type] = lambda **kw: _LegacyProp(inst)
        kwargs["op_type"] = self._op_type
        return sym_mod.Custom(*args, **kwargs)

    def forward(self, in_data, out_data):
        raise NotImplementedError()

    def backward(self, out_grad, in_data, out_data, in_grad):
        raise NotImplementedError()

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]]

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def assign(self, dst, req, src):
        _assign(dst, req, src)


class NumpyOp(PythonOp):
    """Legacy numpy custom op (reference operator.py:NumpyOp): forward/
    backward receive numpy arrays."""

    def __init__(self, need_top_grad=True):
        super().__init__(need_top_grad)


class NDArrayOp(PythonOp):
    """Legacy NDArray custom op (reference operator.py:NDArrayOp)."""

    def __init__(self, need_top_grad=True):
        super().__init__(need_top_grad)
