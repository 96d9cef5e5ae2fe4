"""Layer: compile cache.  Seconds of tracing + lowering + backend compile
(a cache hit's retrieval included) inside set-up, from ``jax.monitoring``;
hits and misses go to the log beside it."""


def read(facts):
    return facts["setup_meter"]["compile_s"]
