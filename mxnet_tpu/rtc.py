"""Runtime kernel escape hatch — the TPU-native analog of MXRtc.

The reference lets users hand-write CUDA at runtime and push it through
NVRTC (include/mxnet/mxrtc.h:16-89, python/mxnet/rtc.py: ``MXRtc(name,
inputs, outputs, kernel_src).push(...)``).  On TPU the corresponding
escape hatch is a **Pallas kernel**: a Python function lowered to a
Mosaic/TPU kernel by ``jax.experimental.pallas``.  This module makes such
kernels first-class framework ops:

- :func:`register_kernel` — register any JAX/Pallas callable as an op; it
  immediately becomes available as ``mx.nd.<name>`` and ``mx.sym.<name>``
  and participates in executor fusion, autograd (via jax.vjp, or a custom
  ``vjp``), and the Module stack.
- :func:`elementwise_pallas_kernel` — wrap a Pallas kernel *body*
  (``kernel(in_ref, out_ref)``) into a callable with sane VMEM block specs,
  interpreted when lowered for anything but a TPU so kernels are testable
  on the virtual CPU mesh.
- :class:`MXRtc` — the reference's class shape (name/inputs/outputs +
  ``push``); the kernel is a Python/Pallas function instead of a CUDA
  source string (documented divergence: there is no NVRTC on TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ops.registry import OP_REGISTRY, register

__all__ = ["register_kernel", "elementwise_pallas_kernel", "MXRtc",
           "on_tpu"]


def _inject(reg_name):
    """Make a freshly registered op callable as mx.nd/<name> and
    mx.sym.<name> (the autogen modules are populated at import; late
    registrations self-inject)."""
    from . import ndarray as nd_mod
    from . import symbol as sym_mod
    opdef = OP_REGISTRY[reg_name]
    if reg_name not in vars(sym_mod):
        vars(sym_mod)[reg_name] = sym_mod._make_symbol_function(opdef,
                                                                reg_name)
    if reg_name not in vars(nd_mod):
        vars(nd_mod)[reg_name] = nd_mod._make_ndarray_function(opdef,
                                                               reg_name)


def register_kernel(name, fn=None, *, input_names=("data",), num_outputs=1,
                    infer_shape=None, needs_rng=False, vjp=None, **opdef_kw):
    """Register a JAX/Pallas callable as a framework op.

    Usable as a decorator::

        @mx.rtc.register_kernel("my_scale")
        def my_scale(data, scalar=2.0):
            return my_pallas_scale(data, scalar)

        y = mx.nd.my_scale(x, scalar=3.0)
        s = mx.sym.my_scale(mx.sym.Variable("data"), scalar=3.0)

    ``vjp``: optional ``vjp(primals..., cotangents...) -> grads``.
    Plain-JAX kernels differentiate automatically; **pallas_call kernels
    need an explicit vjp** (Pallas has no reverse-mode transpose — pair
    the forward kernel with a backward kernel, pallas_guide.md "Patterns:
    Custom VJP"), otherwise the op is forward-only.
    """
    def _do(f):
        import inspect

        if name in OP_REGISTRY:
            raise MXNetError("kernel/op %r already registered" % name)
        wrapped = f
        if vjp is not None:
            def wrapped(*arrays, **attrs):
                # jax.custom_vjp can't bind kwargs, so close over the
                # (static) attrs per call; traced values all ride in
                # ``arrays``.  Under jit this traces once per attr-set.
                @jax.custom_vjp
                def _core(*arr):
                    return f(*arr, **attrs)

                def _fwd(*arr):
                    return f(*arr, **attrs), arr

                def _bwd(res, g):
                    gs = g if isinstance(g, (tuple, list)) else (g,)
                    grads = vjp(*res, *gs, **attrs)
                    if not isinstance(grads, (tuple, list)):
                        grads = (grads,)
                    return tuple(grads)

                _core.defvjp(_fwd, _bwd)
                return _core(*arrays)

            wrapped.__doc__ = f.__doc__
            # keep f's declared parameter surface for attr validation and
            # the executor's framework-attr filtering
            wrapped.__signature__ = inspect.signature(f)
        register(name, input_names=input_names, num_outputs=num_outputs,
                 infer_shape=infer_shape, needs_rng=needs_rng,
                 **opdef_kw)(wrapped)
        _inject(name)
        return f
    if fn is not None:
        return _do(fn)
    return _do


def on_tpu():
    """Whether the default JAX backend is a TPU.  A backend that fails
    to initialize raises here — it is never read as "no TPU".  Kernels
    do not route on this: they pick their tier from the platform the
    program is lowered for (``kernels.by_platform``)."""
    return jax.default_backend() == "tpu"


def elementwise_pallas_kernel(kernel_body, interpret=None):
    """Wrap an elementwise Pallas kernel body ``kernel(in_ref, out_ref)``
    into ``fn(x) -> y`` with whole-array VMEM blocks.

    ``interpret=None`` picks by the platform the call is lowered for:
    compiled in a TPU program, the Pallas interpreter anywhere else (so
    the same kernel runs on the virtual CPU mesh in tests — the MXRtc
    story never had that).
    """
    from jax.experimental import pallas as pl

    def call(interp, x):
        return pl.pallas_call(
            kernel_body,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interp,
        )(x)

    def fn(x):
        if interpret is not None:
            return call(interpret, x)
        import functools
        return jax.lax.platform_dependent(
            x, tpu=functools.partial(call, False),
            default=functools.partial(call, True))
    return fn


class MXRtc(object):
    """Reference-API-shaped runtime kernel (python/mxnet/rtc.py MXRtc).

    The reference compiles ``kernel`` as CUDA source via NVRTC; here
    ``kernel`` is a Python function over jax arrays (typically a
    pallas_call wrapper).  ``push`` mirrors the reference call shape; the
    grid/block dims are accepted for signature parity and passed through
    to kernels that want them.
    """

    def __init__(self, name, inputs, outputs, kernel):
        if isinstance(kernel, str):
            raise MXNetError(
                "MXRtc on TPU takes a Python/Pallas kernel function, not "
                "CUDA source (no NVRTC on TPU; see mxnet_tpu/rtc.py)")
        self.name = name
        self.input_names = [n for n, _ in inputs]
        self.output_names = [n for n, _ in outputs]
        self.kernel = kernel

    def push(self, inputs, outputs, grid_dims=None, block_dims=None):
        """Run the kernel: reads ``inputs`` NDArrays, writes ``outputs``."""
        from .ndarray import NDArray
        from .ops.registry import fn_signature_info
        arrays = [x._data if isinstance(x, NDArray) else jnp.asarray(x)
                  for x in inputs]
        names, has_var_kw = fn_signature_info(self.kernel)
        if has_var_kw or {"grid_dims", "block_dims"} & set(names):
            res = self.kernel(*arrays, grid_dims=grid_dims,
                              block_dims=block_dims)
        else:
            res = self.kernel(*arrays)
        if not isinstance(res, (tuple, list)):
            res = (res,)
        if len(res) != len(outputs):
            raise MXNetError("kernel %s returned %d outputs, expected %d"
                             % (self.name, len(res), len(outputs)))
        for out, r in zip(outputs, res):
            out._data = r.astype(out._data.dtype)
