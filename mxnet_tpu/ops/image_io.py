"""Image I/O ops — the reference's in-engine OpenCV NDArray ops
(reference src/io/image_io.cc:269 registers _cvimdecode/_cvimresize/
_cvcopyMakeBorder; python mx.image rides them).

TPU-first split: `imdecode` is a host op (JPEG entropy decode is inherently
serial — it runs on the native libjpeg decoder, cv2 fallback) marked
no_jit, while `imresize` and `copyMakeBorder` are ordinary XLA lowerings
(jax.image.resize / lax.pad) that run on-device and fuse like any other op.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from .registry import register

# cv2 interp codes -> jax.image methods (2=bicubic like the reference's
# OpenCV default; 3=INTER_AREA has no jax analog, mapped to linear)
_INTERP = {0: "nearest", 1: "linear", 2: "cubic", 3: "linear", 4: "lanczos3"}


def _decode_host(buf, flag, to_rgb):
    """bytes -> HWC uint8 numpy (BGR when to_rgb=0, reference default)."""
    from .. import native as _native

    raw = bytes(buf)
    lib = _native.get_lib()
    # the native JPEG path always yields 3 channels; flags other than
    # 0 (gray) and 1 (color) — e.g. IMREAD_UNCHANGED=-1, which must return
    # 2-D for grayscale sources like the reference _cvimdecode — go to cv2
    if lib is not None and int(flag) in (0, 1):
        import ctypes as ct
        h = ct.c_int()
        w = ct.c_int()
        cbuf = ct.cast(ct.c_char_p(raw), ct.c_void_p)
        if lib.MXTPUImgDecodeDims(cbuf, len(raw), ct.byref(h),
                                  ct.byref(w)) == 0:
            out = np.empty((h.value, w.value, 3), np.uint8)
            if lib.MXTPUImgDecode(cbuf, len(raw), out.ctypes.data_as(
                    ct.c_void_p), 1 if to_rgb else 0) == 0:
                if flag == 0:  # grayscale requested
                    coef = (np.array([0.299, 0.587, 0.114])
                            if to_rgb else np.array([0.114, 0.587, 0.299]))
                    g = (out.astype(np.float32) * coef).sum(-1)
                    return np.clip(g + 0.5, 0,
                                   255).astype(np.uint8)[:, :, None]
                return out
        # non-JPEG payloads (png, ...) fall through to cv2
    import cv2
    img = cv2.imdecode(np.frombuffer(raw, np.uint8), int(flag))
    if img is None:
        raise MXNetError("imdecode: cannot decode image")
    if img.ndim == 2:
        img = img[:, :, None]
    elif to_rgb:
        img = np.ascontiguousarray(img[..., ::-1])
    return img


@register("imdecode", input_names=("buf",), aliases=("_cvimdecode",),
          no_jit=True)
def imdecode_op(buf, flag=1, to_rgb=1):
    """Decode an image byte buffer into an HWC uint8 array (reference
    src/io/image_io.cc Imdecode; _cvimdecode defaults: flag=1 color,
    to_rgb=1).  Host op: output shape depends on the image content, so it
    is imperative-only (the reference likewise executes it eagerly on the
    engine's CPU queue)."""
    import jax.numpy as jnp
    host = np.asarray(buf)
    if host.dtype != np.uint8 or host.ndim != 1:
        raise MXNetError("imdecode expects a 1-D uint8 buffer NDArray")
    return jnp.asarray(_decode_host(host.tobytes(), int(flag), int(to_rgb)))


@register("imresize", input_names=("src",), aliases=("_cvimresize",))
def imresize_op(src, w=0, h=0, interp=1):
    """Resize HWC image to (h, w) — reference _cvimresize, as an XLA
    lowering (jax.image.resize) so it runs on-device."""
    import jax.image
    import jax.numpy as jnp
    method = _INTERP.get(int(interp), "linear")
    out_shape = (int(h), int(w)) + tuple(src.shape[2:])
    out = jax.image.resize(src.astype(jnp.float32), out_shape, method=method)
    if src.dtype == jnp.uint8:
        out = jnp.clip(jnp.round(out), 0, 255)
    return out.astype(src.dtype)


@register("copyMakeBorder", input_names=("src",),
          aliases=("_cvcopyMakeBorder",))
def copy_make_border_op(src, top=0, bot=0, left=0, right=0, type=0,
                        value=0.0):
    """Pad an HWC image with a constant border — reference
    _cvcopyMakeBorder (only BORDER_CONSTANT, type=0, like the reference's
    default use in mx.image)."""
    import jax.numpy as jnp
    if int(type) != 0:
        raise MXNetError("copyMakeBorder: only type=0 (constant) supported")
    pads = [(int(top), int(bot)), (int(left), int(right))] + \
        [(0, 0)] * (src.ndim - 2)
    return jnp.pad(src, pads, constant_values=jnp.asarray(
        value, dtype=src.dtype))
