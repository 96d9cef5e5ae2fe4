"""Compile seconds and persistent-cache hits/misses of this process, read
off ``jax.monitoring`` (copied from ``chip_smoke.Meter``): the compile
share of ``setup_s`` and the proof that nothing compiles inside the
measured window."""
import threading

_COMPILE = ("/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/jaxpr_to_mlir_module_duration",
            "/jax/core/compile/backend_compile_duration")


class Meter(object):
    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.backend_compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in _COMPILE:
            with self._lock:
                self.compile_s += secs
                if event == _COMPILE[2]:
                    self.backend_compiles += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self):
        """{compile_s, backend_compiles, cache_hits, cache_misses} so far.
        ``backend_compiles`` counts programs handed to the backend, whether
        the persistent cache then served them or not."""
        with self._lock:
            return {"compile_s": self.compile_s,
                    "backend_compiles": self.backend_compiles,
                    "cache_hits": self.hits, "cache_misses": self.misses}

    @staticmethod
    def delta(after, before):
        return {k: after[k] - before[k] for k in after}
