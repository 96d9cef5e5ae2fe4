"""Loader for the native host-runtime library (libmxtpu.so).

The native layer provides the host-side dependency engine, the RecordIO
codec and the libjpeg image pipeline (engine.cc / recordio.cc /
imagedec.cc / im2rec.cc).  It is built on first use from the sources
beside this file; the library is git-ignored, so a fresh checkout always
builds its own.  A build that fails raises with the compiler's output —
it never degrades to a slower pure-Python or cv2 path behind the
caller's back.  ``MXNET_NO_NATIVE=1`` is the one way to run without it.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

from ..base import MXNetError

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libmxtpu.so")
#: sidecar naming the sources the library was built from (see _stale)
_DIGEST_PATH = _LIB_PATH + ".src"
_SRCS = ("engine.cc", "recordio.cc", "imagedec.cc", "im2rec.cc")
_CMD = ("g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-pthread")
_LIBS = ("-ljpeg",)

_lock = threading.Lock()
_lib = None
_tried = False


def _src_digest():
    """Digest of everything the build reads: sources, header, command."""
    h = hashlib.sha256(" ".join(_CMD + _LIBS).encode())
    for name in _SRCS + ("mxtpu.h",):
        with open(os.path.join(_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()


def _build(digest):
    """Compile libmxtpu.so in-place; raises MXNetError with the
    compiler's stderr on failure.

    Compiles to a per-pid temp name then renames atomically so concurrent
    first-use from multiple processes cannot dlopen a half-written file;
    the digest sidecar is published last, so a library without a matching
    sidecar is rebuilt, never trusted.
    """
    tmp = _LIB_PATH + ".%d.tmp" % os.getpid()
    cmd = list(_CMD) + ["-o", tmp] + \
        [os.path.join(_DIR, s) for s in _SRCS] + list(_LIBS)
    try:
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=300)
        except (OSError, subprocess.TimeoutExpired) as e:
            raise MXNetError("native build could not run (%s): %s"
                             % (" ".join(cmd), e))
        if proc.returncode != 0 or not os.path.exists(tmp):
            raise MXNetError("native build failed (rc %d): %s\n%s"
                             % (proc.returncode, " ".join(cmd),
                                proc.stderr))
        os.replace(tmp, _LIB_PATH)
        with open(tmp, "w") as f:
            f.write(digest)
        os.replace(tmp, _DIGEST_PATH)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _stale(digest):
    """True unless the library on disk was built by :func:`_build` from
    exactly the sources beside it.  Content, not mtimes: a copy or an
    unpacked archive scrambles mtimes, and a library that came along from
    another tree must not be loaded."""
    try:
        with open(_DIGEST_PATH) as f:
            return f.read().strip() != digest \
                or not os.path.exists(_LIB_PATH)
    except OSError:
        return True


def _configure(lib):
    u64 = ctypes.c_uint64
    p = ctypes.c_void_p
    lib.MXTPUEngineCreate.restype = p
    lib.MXTPUEngineCreate.argtypes = [ctypes.c_int, ctypes.c_int]
    lib.MXTPUEngineShutdown.argtypes = [p]
    lib.MXTPUEngineNewVar.restype = u64
    lib.MXTPUEngineNewVar.argtypes = [p]
    lib.MXTPUEngineDeleteVar.argtypes = [p, u64]
    lib.MXTPUEnginePushAsync.restype = ctypes.c_int
    lib.MXTPUEnginePushAsync.argtypes = [
        p, ENGINE_CB, p, ctypes.POINTER(u64), ctypes.c_int,
        ctypes.POINTER(u64), ctypes.c_int, ctypes.c_int, ctypes.c_char_p]
    lib.MXTPUEngineWaitForVar.argtypes = [p, u64]
    lib.MXTPUEngineWaitForAll.argtypes = [p]
    lib.MXTPUEngineNumPending.restype = ctypes.c_int
    lib.MXTPUEngineNumPending.argtypes = [p]
    lib.MXTPUEngineLastError.restype = ctypes.c_char_p
    lib.MXTPUEngineLastError.argtypes = [p]
    lib.MXTPUProfilerSetState.argtypes = [p, ctypes.c_int]
    lib.MXTPUProfilerDump.restype = p  # manually decoded + freed
    lib.MXTPUProfilerDump.argtypes = [p]

    lib.MXTPURecordIOWriterCreate.restype = p
    lib.MXTPURecordIOWriterCreate.argtypes = [ctypes.c_char_p]
    lib.MXTPURecordIOWriterWrite.restype = ctypes.c_int
    lib.MXTPURecordIOWriterWrite.argtypes = [p, ctypes.c_char_p, u64]
    lib.MXTPURecordIOWriterTell.restype = u64
    lib.MXTPURecordIOWriterTell.argtypes = [p]
    lib.MXTPURecordIOWriterClose.argtypes = [p]
    lib.MXTPURecordIOReaderCreate.restype = p
    lib.MXTPURecordIOReaderCreate.argtypes = [ctypes.c_char_p]
    lib.MXTPURecordIOReaderRead.restype = ctypes.c_int
    lib.MXTPURecordIOReaderRead.argtypes = [
        p, ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(u64)]
    lib.MXTPURecordIOReaderSeek.argtypes = [p, u64]
    lib.MXTPURecordIOReaderTell.restype = u64
    lib.MXTPURecordIOReaderTell.argtypes = [p]
    lib.MXTPURecordIOReaderClose.argtypes = [p]
    lib.MXTPUFree.argtypes = [p]

    fp = ctypes.POINTER(ctypes.c_float)
    pp = ctypes.POINTER(ctypes.c_void_p)
    lib.MXTPUImgPipeCreate.restype = p
    lib.MXTPUImgPipeCreate.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int, fp, fp,
        ctypes.c_int]
    lib.MXTPUImgPipeDecodeBatch.restype = ctypes.c_int
    lib.MXTPUImgPipeDecodeBatch.argtypes = [
        p, pp, ctypes.POINTER(u64), ctypes.c_int, p,
        ctypes.POINTER(ctypes.c_uint8), u64]
    lib.MXTPUImgPipeDestroy.argtypes = [p]
    lib.MXTPUImgDecodeDims.restype = ctypes.c_int
    lib.MXTPUImgDecodeDims.argtypes = [
        p, u64, ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
    lib.MXTPUImgDecode.restype = ctypes.c_int
    lib.MXTPUImgDecode.argtypes = [p, u64, p, ctypes.c_int]
    lib.MXTPUIm2Rec.restype = ctypes.c_int
    lib.MXTPUIm2Rec.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(u64), ctypes.POINTER(u64)]
    return lib


ENGINE_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p)

from ..base import get_env, register_env  # noqa: E402 — after ctypes setup

ENV_NO_NATIVE = register_env(
    "MXNET_NO_NATIVE", default=0,
    doc="1 disables the native C runtime entirely (pure-Python fallbacks)")


def get_lib():
    """Return the configured ctypes library, building it first when the
    one on disk is missing or stale.  None only under MXNET_NO_NATIVE=1;
    a failed build or load raises."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        if str(get_env(ENV_NO_NATIVE, "0")) == "1":
            _tried = True
            return None
        digest = _src_digest()
        if _stale(digest):
            _build(digest)
        _lib = _configure(ctypes.CDLL(_LIB_PATH))
        _tried = True
    return _lib
