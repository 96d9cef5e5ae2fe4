"""Base utilities: errors, env-config, generic registries, attr parsing.

TPU-native re-design of the reference's dmlc-core surface:
- ``MXNetError`` mirrors python/mxnet/base.py:35 in the reference.
- ``get_env`` mirrors dmlc::GetEnv runtime config (reference docs/how_to/env_var.md).
- ``Registry`` mirrors dmlc registry used for initializers/optimizers/iterators
  (reference include/dmlc usage via MXNET_REGISTER_* macros).

No ctypes / C-ABI plumbing: the compute substrate is JAX/XLA, so the Python
layer talks to it directly.  A native C runtime exists for the IO/runtime
components (see mxnet_tpu/native/).
"""
from __future__ import annotations

import ast
import logging
import os
import threading
from collections import namedtuple

__all__ = [
    "MXNetError", "MXTPUError", "get_env", "Registry", "parse_attr_value",
    "string_types", "numeric_types", "classproperty",
    "EnvSpec", "ENV_REGISTRY", "register_env", "registered_env_names",
]

_LOG = logging.getLogger(__name__)

string_types = (str,)
numeric_types = (int, float)


class MXNetError(Exception):
    """Framework error type (name kept for API parity with the reference,
    python/mxnet/base.py:35)."""


# Idiomatic alias.
MXTPUError = MXNetError


_TRUE_STRINGS = frozenset(("1", "true", "yes", "on"))
_FALSE_STRINGS = frozenset(("0", "false", "no", "off"))


class EnvSpec(namedtuple("EnvSpec", ["name", "default", "doc", "scope"])):
    """One registered runtime knob.  ``scope`` records who reads it:
    ``runtime`` (the package), ``test`` (the test harness), ``tools``
    (launch/supervise/mxlint CLIs) — documentation metadata, not an
    access control."""


#: The single catalog of every ``MXTPU_*``/``MXNET_*`` knob this codebase
#: reads.  All env access goes through :func:`get_env` (enforced by
#: ``tools/mxlint.py``'s ``env-unregistered``/``env-direct-read`` rules),
#: and every registered MXTPU_* name must have a row in
#: ``docs/env_vars.md`` (asserted by tests/test_analysis.py) — so a knob
#: cannot be added, typo'd, or dropped without the analyzer noticing.
ENV_REGISTRY = {}


def register_env(name, default=None, doc="", scope="runtime"):
    """Register one env knob; returns ``name`` so call sites can do
    ``ENV_FOO = register_env("MXTPU_FOO", ...)``.

    Default precedence: a ``get_env`` call that passes its own default
    wins (sites do this deliberately — a STRING default keeps garbage
    values like ``MXTPU_STEP_GUARD=maybe`` readable instead of raising
    in ``int()``); the default registered here applies only when the
    site passes none, and otherwise serves as the documented value the
    docs table mirrors."""
    ENV_REGISTRY[name] = EnvSpec(name, default, doc, scope)
    return name


def registered_env_names(prefix=None, scope=None):
    """Registered knob names, optionally filtered by prefix/scope."""
    return sorted(
        n for n, s in ENV_REGISTRY.items()
        if (prefix is None or n.startswith(prefix))
        and (scope is None or s.scope == scope))


_WARNED_UNREGISTERED = set()


def get_env(name, default=None, typ=None):
    """Read a runtime config env var (dmlc::GetEnv analog).

    Supported vars follow the reference's catalog (docs/how_to/env_var.md)
    with an ``MXNET_`` prefix, e.g. ``MXNET_ENGINE_TYPE``,
    ``MXNET_EXEC_BULK_EXEC_TRAIN``; TPU-era knobs use ``MXTPU_``.  Every
    framework-prefixed name must be in :data:`ENV_REGISTRY` — an
    unregistered read warns once (and is a static-analysis finding, see
    tools/mxlint.py), because a typo'd knob silently reading its default
    is exactly the failure mode the registry exists to catch.
    """
    if name.startswith(("MXTPU_", "MXNET_")) and name not in ENV_REGISTRY \
            and name not in _WARNED_UNREGISTERED:
        _WARNED_UNREGISTERED.add(name)
        _LOG.warning("env var %s is not registered in base.ENV_REGISTRY — "
                     "typo, or a knob missing from the catalog "
                     "(docs/env_vars.md)?", name)
    if default is None and name in ENV_REGISTRY:
        # the registered default is authoritative when the call site
        # doesn't override it — one place to change a knob's default
        default = ENV_REGISTRY[name].default
    raw = os.environ.get(name)
    if raw is None:
        return default
    if typ is None and default is not None:
        typ = type(default)
    if typ is bool:
        low = raw.strip().lower()
        if low in _TRUE_STRINGS:
            return True
        if low in _FALSE_STRINGS:
            return False
        raise MXNetError("Invalid boolean env var %s=%r" % (name, raw))
    if typ is not None:
        return typ(raw)
    return raw


def parse_attr_value(value):
    """Parse a string attribute into a Python value.

    The reference serializes op kwargs as strings through dmlc::Parameter
    (src/operator/optimizer_op-inl.h:25-45); symbols store attrs as strings in
    JSON.  We accept both typed python values and their string forms:
    ``"(2, 2)"`` -> (2, 2), ``"1"`` -> 1, ``"True"`` -> True, ``"relu"`` -> "relu".
    """
    if not isinstance(value, str):
        return value
    s = value.strip()
    try:
        return ast.literal_eval(s)
    except (ValueError, SyntaxError):
        low = s.lower()
        if low in _TRUE_STRINGS and s.isalpha():
            return True
        if low in _FALSE_STRINGS and s.isalpha():
            return False
        return s


def attr_to_string(value):
    """Serialize an attr value to the string form used in symbol JSON."""
    if isinstance(value, str):
        return value
    if isinstance(value, bool):
        return "True" if value else "False"
    if isinstance(value, (list, tuple)):
        return "(" + ", ".join(attr_to_string(v) for v in value) + (",)" if len(value) == 1 else ")")
    return str(value)


class Registry(object):
    """Generic name->object registry (dmlc registry analog).

    Used for optimizers, initializers, metrics, data iterators, kvstores.
    """

    def __init__(self, kind):
        self._kind = kind
        self._entries = {}

    def register(self, obj=None, name=None, aliases=()):
        def _do(o):
            key = (name or o.__name__).lower()
            self._entries[key] = o
            for a in aliases:
                self._entries[a.lower()] = o
            return o
        if obj is None:
            return _do
        return _do(obj)

    def get(self, name):
        key = name.lower()
        if key not in self._entries:
            raise MXNetError(
                "Cannot find %s %r. Registered: %s"
                % (self._kind, name, sorted(self._entries)))
        return self._entries[key]

    def find(self, name):
        return self._entries.get(name.lower())

    def list(self):
        return sorted(self._entries)

    def create(self, name, *args, **kwargs):
        return self.get(name)(*args, **kwargs)


class classproperty(object):
    def __init__(self, fget):
        self.fget = fget

    def __get__(self, obj, owner):
        return self.fget(owner)


class _ThreadLocalStack(threading.local):
    """Thread-local scope stack (used by Context / AttrScope / NameManager)."""

    def __init__(self):
        self.stack = []


def check_call(ret):  # pragma: no cover - API-parity shim
    """No-op kept for source compatibility with reference-style code."""
    return ret


# -- knobs owned by the package root / the test harness (modules register
# their own next to the code that reads them; see ENV_REGISTRY)
ENV_TEST_PLATFORM = register_env(
    "MXTPU_TEST_PLATFORM", default="cpu", scope="test",
    doc="Test-suite platform: cpu = 8-device virtual mesh, tpu = real "
        "chip (read by tests/conftest.py)")
# Registered here (not in data_service/) because it is read across
# modules: image.py routes ImageRecordIter through the data service when
# it is set, and data_service.service sizes the worker fleet from it.
ENV_DATA_WORKERS = register_env(
    "MXTPU_DATA_WORKERS", default=0,
    doc="N>0 routes ImageRecordIter through the multi-process "
        "shared-memory data service with N decode worker processes "
        "(same as data_service=True; docs/how_to/performance.md)")
# Registered here for the same cross-module reason: image.py routes
# through the NETWORK tier when it is set.
ENV_DATA_SERVERS = register_env(
    "MXTPU_DATA_SERVERS", default="",
    doc="Comma list of host:port data servers (tools/data_server.py): "
        "routes every eligible ImageRecordIter through the "
        "network-tier data service (same as "
        "data_service='host:port,...'); unset falls back to the local "
        "service / in-process pipelines (docs/how_to/performance.md)")
# Registered here (not in kernels/) because it is read across modules:
# ops/nn.py's RNN scan, rnn/rnn_cell.py's LSTMCell, executor.py's
# BN+activation fusion pass and parallel/ring_attention.py all consult it
# at trace/bind time (docs/how_to/kernels.md).
ENV_FUSED_KERNELS = register_env(
    "MXTPU_FUSED_KERNELS", default="1",
    doc="Fused-kernel + plan-optimizer routing (mxnet_tpu/kernels/, "
        "mxnet_tpu/mxfuse.py): 1 = everything on (default), 0 = exact "
        "pre-fusion graphs, or a comma list from {bn_act, bn_fold, "
        "lstm_cell, flash_attention, augment, concat_fuse, pool_act, "
        "eltwise_chain, infer_trace} to enable individually "
        "(docs/how_to/kernels.md)")
