"""AOT executable store: serialized COMPILED (model, bucket) forwards.

The persistent compile cache (``JAX_COMPILATION_CACHE_DIR``) removes XLA
compilation from a replica's bring-up — but the dominant remaining cost
on re-trace is Python: binding the symbol graph and tracing one jitted
forward per bucket shape (seconds for a deep net, per process).  This
store removes THAT too: the fleet's warmup builder compiles each
(model, bucket) forward ONCE, serializes the compiled executable
(``jax.experimental.serialize_executable`` — the true AOT artifact:
no trace, no lower, no compile at load), and a fresh or respawned
replica ``deserialize_and_load``\\ s it.

Artifacts are WEIGHT-FREE: the compiled program takes the parameters as
call arguments (the pool keeps the single device-resident copy), so a
store is a few hundred KB per program regardless of model size, and
reloading never duplicates weights.

Store layout (``<jax_compilation_cache_dir>/aot/``)::

    <model>.json            meta: sample shapes, dtype, param/aux names,
                            platform, buckets — verified before loading
    <model>-b<bucket>.exec  the serialized executable
    <model>-b<bucket>.tree  its pickled (in_tree, out_tree)

A meta mismatch (different shapes/dtype/platform/param set) or a
deserialization failure falls back to the classic trace-and-compile
warmup with a warning — the store can go stale, serving must not.
Trust model: the store directory is operator-owned exactly like a
checkpoint directory (the ``.tree`` files are pickles, as checkpoint
state already is).

Executables are platform-specific by nature: a store built under the
replica device env (``fleet warmup`` builds under replica 0's) loads on
every replica of that fleet; it will refuse (and fall back) anywhere
else.  Bit-exactness: every replica of a fleet loads the SAME compiled
bytes, so the (bucket-shape) bit-stability contract holds fleet-wide by
construction — stronger than N independent compiles.
"""
from __future__ import annotations

import json
import os
import pickle

import numpy as np

from ..base import MXNetError

__all__ = ["AotStore", "aot_dir"]

_META_VERSION = 1


def aot_dir():
    """The store's location: inside the compile-cache directory this
    process runs with (``JAX_COMPILATION_CACHE_DIR``, or the fixed
    in-checkout default the package sets at import)."""
    import jax
    return os.path.join(jax.config.jax_compilation_cache_dir, "aot")


def _log():
    import logging
    return logging.getLogger(__name__)


def dev_array(v):
    """NDArray -> its device buffer; anything else -> jnp.asarray.
    The ONE unwrap rule every serving forward builder shares."""
    import jax.numpy as jnp
    from ..ndarray import NDArray
    return v._data if isinstance(v, NDArray) else jnp.asarray(v)


def eval_closure(eval_fn, fills, aux_fills, input_names):
    """The shared body of every serving forward: merge params +
    zero-filled args, zero-filled missing aux, run the eval graph in
    inference mode with the fixed PRNG convention.  ``run(params_dict,
    aux_dict, inputs_tuple) -> tuple(outputs)``.  Lives in ONE place so
    the int8 path and the AOT exporter cannot drift on the rng/train
    flag or the fill dtype."""
    import jax
    import jax.numpy as jnp

    def run(params, auxs, inputs):
        merged = dict(params)
        merged.update({n: jnp.zeros(s, jnp.float32)
                       for n, s in fills.items()})
        merged.update(dict(zip(input_names, inputs)))
        full_aux = dict(auxs)
        full_aux.update({n: jnp.zeros(s, jnp.float32)
                         for n, s in aux_fills.items()})
        outs, _ = eval_fn(merged, full_aux, jax.random.PRNGKey(0),
                          False)
        return tuple(outs)

    return run


def graph_fills(symbol, shapes, known_args, known_auxs):
    """The Predictor.reshape allocation rule, shared by every serving
    forward builder (Predictor itself, the int8 path, the AOT export):
    args absent from the blob AND the inputs (loss labels at
    inference) and missing aux states are zero-filled at their
    inferred shapes.  Returns ``(fills, aux_fills)`` as
    ``{name: shape}`` dicts.  Lives in ONE place so the int8 and AOT
    forwards can never drift from each other on what gets filled."""
    arg_shapes, _, aux_shapes = symbol.infer_shape(**shapes)
    known = set(known_args) | set(shapes)
    fills = {n: tuple(s)
             for n, s in zip(symbol.list_arguments(), arg_shapes)
             if n not in known}
    aux_fills = {n: tuple(s)
                 for n, s in zip(symbol.list_auxiliary_states(),
                                 aux_shapes)
                 if n not in known_auxs}
    return fills, aux_fills


class AotStore(object):
    """One directory of serialized compiled forwards."""

    def __init__(self, directory):
        self.dir = directory

    def _base(self, model, bucket):
        return os.path.join(self.dir, "%s-b%d" % (model, int(bucket)))

    def _meta_path(self, model):
        return os.path.join(self.dir, "%s.json" % model)

    @staticmethod
    def _platform():
        import jax
        return jax.default_backend()

    def meta(self, model):
        try:
            with open(self._meta_path(model)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def buckets(self, model):
        doc = self.meta(model)
        return sorted(int(b) for b in (doc or {}).get("buckets", []))

    # -- build side --------------------------------------------------------
    def save(self, model, bucket, compiled, meta):
        """Serialize one compiled executable + record/refresh the
        model's meta doc (``meta``: sample_shapes/dtype/param_names/
        aux_names/input_names)."""
        from jax.experimental import serialize_executable as se
        from ..resilience import atomic_write
        os.makedirs(self.dir, exist_ok=True)
        payload, in_tree, out_tree = se.serialize(compiled)
        base = self._base(model, bucket)
        atomic_write(base + ".exec", bytes(payload))
        atomic_write(base + ".tree",
                     pickle.dumps((in_tree, out_tree), protocol=4))
        doc = self.meta(model) or {}
        doc.update(meta)
        doc["meta_version"] = _META_VERSION
        doc["platform"] = self._platform()
        buckets = set(int(b) for b in doc.get("buckets", []))
        buckets.add(int(bucket))
        doc["buckets"] = sorted(buckets)
        atomic_write(self._meta_path(model),
                     json.dumps(doc, indent=2, sort_keys=True))
        return base

    # -- load side ---------------------------------------------------------
    def verify(self, model, meta):
        """Does the store's meta match this pool entry?  Returns the
        meta doc on match, None (with a warning) otherwise — stale
        artifacts must fall back, never serve wrong math."""
        doc = self.meta(model)
        if doc is None:
            return None
        checks = dict(meta)
        checks["platform"] = self._platform()
        checks["meta_version"] = _META_VERSION
        for key, want in checks.items():
            got = doc.get(key)
            # JSON roundtrips tuples as lists
            norm = lambda v: json.loads(json.dumps(v))  # noqa: E731
            if norm(got) != norm(want):
                _log().warning(
                    "AOT store %s: meta mismatch for %r on %r "
                    "(store %r != pool %r) — falling back to "
                    "trace warmup", self.dir, model, key, got, want)
                return None
        return doc

    def load(self, model, bucket):
        """One executable -> callable, or None (missing/corrupt —
        caller falls back)."""
        from jax.experimental import serialize_executable as se
        base = self._base(model, bucket)
        try:
            with open(base + ".exec", "rb") as f:
                payload = f.read()
            with open(base + ".tree", "rb") as f:
                in_tree, out_tree = pickle.load(f)
            # the store holds single-device forwards; without the
            # device list jax loads onto EVERY local device and the call
            # then wants one shard per device
            import jax
            return se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=jax.local_devices()[:1])
        except Exception as e:  # noqa: BLE001 — stale/foreign artifact
            _log().warning("AOT store %s: cannot load %s-b%d (%s: %s) — "
                           "falling back to trace warmup",
                           self.dir, model, bucket, type(e).__name__, e)
            return None


def build_forward(symbol, arg_params, aux_params, sample_shapes,
                  bucket):
    """The canonical AOT forward for one bucket: a compiled function of
    ``(param_list, aux_list, *inputs)`` (params in sorted-name order —
    weight-free executable, the pool passes its device-resident copy).
    Returns ``(compiled, input_names)``.  The math is the same
    ``executor._build_eval`` program the Predictor runs — the bit-parity
    tests pin the two paths against each other."""
    import jax
    import jax.numpy as jnp
    from ..executor import _build_eval

    eval_fn = _build_eval(symbol)
    pnames = sorted(arg_params)
    anames = sorted(aux_params)
    pv = [dev_array(arg_params[n]) for n in pnames]
    av = [dev_array(aux_params[n]) for n in anames]
    input_names = sorted(sample_shapes)
    shapes = {k: (int(bucket),) + tuple(s)
              for k, s in sample_shapes.items()}
    fills, aux_fills = graph_fills(symbol, shapes, arg_params,
                                   aux_params)
    run = eval_closure(eval_fn, fills, aux_fills, input_names)

    def infer(params, auxv, *inputs):
        return run(dict(zip(pnames, params)),
                   dict(zip(anames, auxv)), inputs)

    xs = [jax.ShapeDtypeStruct(shapes[n], jnp.float32)
          for n in input_names]
    compiled = jax.jit(infer).lower(pv, av, *xs).compile()
    return compiled, (pv, av)


def entry_meta(entry):
    """The verification meta for one pool entry (shape/dtype/param-set
    identity — what must match for a stored executable to be THIS
    model's forward)."""
    if entry.sample_shapes is None:
        raise MXNetError("model %r has no declared sample_shapes — the "
                         "AOT store needs them" % entry.name)
    return {"sample_shapes": {k: list(v)
                              for k, v in sorted(
                                  entry.sample_shapes.items())},
            "dtype": entry.dtype or "float32",
            "param_names": sorted(entry.arg_params),
            "aux_names": sorted(entry.aux_params),
            "param_digest": params_digest(entry.arg_params,
                                          entry.aux_params)}


def params_digest(arg_params, aux_params):
    """Cheap shape/dtype digest of the parameter set (NOT a content
    hash — weights ride at call time, only the program signature must
    match)."""
    import hashlib
    h = hashlib.sha256()
    for prefix, d in (("arg", arg_params), ("aux", aux_params)):
        for k in sorted(d):
            v = d[k]
            h.update(("%s:%s:%s:%s;" % (
                prefix, k, tuple(getattr(v, "shape", ())),
                np.dtype(getattr(v, "dtype", np.float32)).name))
                .encode())
    return h.hexdigest()[:16]
