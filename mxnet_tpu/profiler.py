"""Profiler control (reference python/mxnet/profiler.py over MXSetProfilerConfig/
MXSetProfilerState/MXDumpProfile, src/engine/profiler.{h,cc}).

Three collectors, one Chrome ``traceEvents`` dump in the reference's format
(profiler.cc:134-216):
- the host dependency engine's per-op timings (data pipeline, engine ops) via
  the native profiler (mxnet_tpu/native/engine.cc);
- the program's own spans and counters (:func:`span`, :func:`count`,
  :func:`event`): where the host's time goes in the training path — ``fit``,
  the fused step, the feed, the decoder, compilation, set-up;
- XLA device traces via ``jax.profiler`` when a trace_dir is configured
  (mode='all_xla', or ``MXTPU_PROFILE_DIR`` under ``fit``) — viewable in
  TensorBoard/Perfetto, the TPU analog of the reference's per-kernel GPU
  stats.  :func:`idle_gaps` lays the spans over such a trace and says what
  the host was doing while the device sat idle; :func:`get_op_stats` /
  :func:`dumps` put the device's busy time down to graph nodes,
  ``mirror_stage``s (forward, rematerialised, backward) and the fused
  step's own ``step.*`` scopes.

The span recorder is always on and bounded: the last ``RING`` spans stay in
memory, so a live job can be asked for its last minute.  A step of ``fit``
over a ``DevicePrefetchIter`` over the native image pipeline makes 17
records: 9 spans on the loop's thread (``fit.next``, ``feed.get_wait``,
``fit.step``, ``step.prepare``, ``step.dispatch``, ``step.localize``,
``fit.metric``, one ``step.guard_wait`` (of the step before, inside
``fit.step`` after ``step.localize``), ``fit.callback``), 3 on the feed's
worker, 4 on the decoder's threads, and one ``compile.trace`` event (the
image iterator re-traces its device transform's shape once a batch);
in-memory batches make 12.  ``RING`` holds over 3,800 such steps: about
eight minutes of ResNet-50 at 121 ms a step.  Every stamp
is ``time.perf_counter_ns()``; :func:`spans` adds the Unix time of each
start, from an anchor pair of both clocks taken at the read, and a
``jax.profiler`` trace's events count from its ``profile_start_time`` (Unix
ns, plane ``Task Environment``), so the two share a timeline.

Env parity: MXNET_PROFILER_AUTOSTART=1 starts profiling at import
(docs/how_to/env_var.md:66-73).
"""
from __future__ import annotations

import collections
import gzip
import itertools
import json
import os
import re
import threading
import time

import jax

from .base import MXNetError, get_env, register_env

__all__ = ["profiler_set_config", "profiler_set_state", "dump_profile",
           "dumps", "get_op_stats", "State", "Mode", "StepTraceCapture",
           "ENV_PROFILE_DIR", "span", "count", "event", "spans", "counters",
           "idle_gaps", "RING"]

#: when set, fit() captures a jax.profiler trace of steps 10-15 of the
#: first epoch into this directory (viewable in TensorBoard/Perfetto)
ENV_PROFILE_DIR = register_env(
    "MXTPU_PROFILE_DIR",
    doc="fit() captures a jax.profiler trace of steps 10-15 of the first "
        "epoch into this directory")
ENV_PROFILER_AUTOSTART = register_env(
    "MXNET_PROFILER_AUTOSTART", default=0,
    doc="1 starts the host profiler at import (reference parity)")


# -- the program's spans and counters ---------------------------------------

#: how many finished spans stay in memory (the oldest fall out)
RING = 65536

_ring = collections.deque(maxlen=RING)
_serial = itertools.count()
_counters = collections.Counter()
_counters_lock = threading.Lock()
_local = threading.local()
_now = time.perf_counter_ns


def _thread():
    """(open spans of this thread, innermost last; ident; name)."""
    try:
        return _local.state
    except AttributeError:
        t = threading.current_thread()
        _local.state = ([], t.ident, t.name)
        return _local.state


class span(object):
    """``with span("feed.stage", batch=7):`` — times the block and, at its
    end, appends itself to the ring: name, start, end (perf-counter ns),
    thread, the span of this thread that was open around it, and ``ids``.
    A span inherits its parent's ids (a step's spans all carry ``step``);
    :meth:`note` adds what is only known inside the block."""

    __slots__ = ("name", "ids", "serial", "parent", "thread", "start", "end")

    def __init__(self, name, **ids):
        self.name = name
        self.ids = ids

    def _adopt(self):
        """Take a serial, this thread and, from the span open on it, the
        parent's serial and ids; returns the thread's open spans."""
        stack, ident, tname = _thread()
        self.serial = next(_serial)
        self.thread = (ident, tname)
        self.parent = None
        if stack:
            parent = stack[-1]
            self.parent = parent.serial
            if parent.ids:
                self.ids = dict(parent.ids, **self.ids) if self.ids \
                    else parent.ids
        return stack

    def __enter__(self):
        self._adopt().append(self)
        self.start = _now()
        return self

    def __exit__(self, *exc):
        self.end = _now()
        _local.state[0].pop()
        _ring.append(self)
        return False

    def note(self, **ids):
        self.ids = dict(self.ids, **ids)


def event(name, start, end, **ids):
    """Append a finished span whose times (perf-counter ns) someone else
    measured; its parent is the span open on the calling thread."""
    s = span(name, **ids)
    s._adopt()
    s.start, s.end = int(start), int(end)
    _ring.append(s)


def count(name, n=1):
    """Add ``n`` to the cumulative counter ``name``."""
    with _counters_lock:
        _counters[name] += n


def counters():
    """{name: value} of every counter, cumulative since import."""
    with _counters_lock:
        return dict(_counters)


def spans(since=None, until=None):
    """The ring's spans that start in [since, until] (perf-counter
    seconds, what ``time.perf_counter()`` gives; None is open), sorted by
    start.  Each is a dict: ``name``, ``start`` and ``end`` (perf-counter
    seconds), ``unix_ns`` (its start in Unix nanoseconds), ``thread``
    (ident), ``thread_name``, ``serial``, ``parent`` (the enclosing span's
    serial, or None) and ``ids``."""
    anchor = time.time_ns() - _now()
    lo = -float("inf") if since is None else since * 1e9
    hi = float("inf") if until is None else until * 1e9
    # list(deque) is one C call: no append of another thread lands inside
    held = sorted((s for s in list(_ring) if lo <= s.start <= hi),
                  key=lambda s: s.start)
    return [{"name": s.name, "start": s.start / 1e9, "end": s.end / 1e9,
             "unix_ns": s.start + anchor, "thread": s.thread[0],
             "thread_name": s.thread[1], "serial": s.serial,
             "parent": s.parent, "ids": dict(s.ids)} for s in held]


def _chrome_events(records, clock):
    """Chrome ``X`` events of span records, one ``tid`` per thread;
    ``clock`` gives a record's start in microseconds."""
    out = []
    for tid, name in sorted({(r["thread"], r["thread_name"])
                             for r in records}):
        out.append({"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                    "args": {"name": name}})
    for r in records:
        out.append({"name": r["name"], "cat": "span", "ph": "X",
                    "ts": clock(r), "dur": (r["end"] - r["start"]) * 1e6,
                    "pid": 0, "tid": r["thread"], "args": r["ids"]})
    return out


_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}


def _on_duration(name, secs, **_):
    if name in _COMPILE_EVENTS:
        end = _now()
        event(_COMPILE_EVENTS[name], end - int(secs * 1e9), end)


def _on_event(name, **_):
    if name in _CACHE_EVENTS:
        count(_CACHE_EVENTS[name])


# JAX reports each trace, lowering and backend compile (a cache hit's load
# included) as it ends, on the thread that asked for it: inside the span
# that caused it (a first ``step.dispatch``, a ``setup.*``)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


class StepTraceCapture(object):
    """Window-bounded ``jax.profiler`` trace for a training loop.

    Captures steps ``[start_step, stop_step]`` (default 10-15) of the
    epoch it is driven through: the caller invokes :meth:`on_batch` with
    the 0-based batch index before each step and :meth:`stop` at epoch
    end (closing a window the epoch cut short).  A steady-state window —
    not step 0 — so the trace shows the pipeline, not compilation."""

    #: the spans of the traced window, written beside the trace by stop()
    SPANS_FILE = "mxnet_tpu_spans.trace.json"
    #: the compiled step's text, written there too when ``trainer`` is
    #: given: where :func:`get_op_stats` finds each device event's scope
    STEP_FILE = "mxnet_tpu_step.hlo.txt"

    def __init__(self, directory, start_step=10, stop_step=15, trainer=None):
        self.directory = os.fspath(directory)
        self.start_step = int(start_step)
        self.stop_step = int(stop_step)
        self.trainer = trainer      # an SPMDTrainer (its ``step_text``)
        self._active = False
        self._done = False
        self._since = None

    @classmethod
    def from_env(cls, trainer=None):
        """A capture configured from MXTPU_PROFILE_DIR, or None."""
        directory = get_env(ENV_PROFILE_DIR)
        return cls(directory, trainer=trainer) if directory else None

    def on_batch(self, nbatch):
        if self._done:
            return
        if not self._active and nbatch >= self.start_step:
            os.makedirs(self.directory, exist_ok=True)
            # the device's planes only.  Host TraceMe events make the
            # transfer threads write ~0.9 M events for one batch and stall
            # the feed they record (PERF.md, PR 23), and the Python tracer
            # slows the loop it watches; what the host did is in the
            # program's own spans, written beside the trace on stop()
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 0
            options.python_tracer_level = 0
            self._since = time.perf_counter()
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            self._active = True
        elif self._active and nbatch > self.stop_step:
            self.stop()

    def stop(self):
        if not self._active:
            return
        jax.profiler.stop_trace()
        self._active = False
        self._done = True
        # Unix microseconds: the trace's own events count from its
        # profile_start_time, which is Unix time too
        events = _chrome_events(spans(since=self._since),
                                lambda r: r["unix_ns"] / 1e3)
        with open(os.path.join(self.directory, self.SPANS_FILE), "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        text = self.trainer.step_text() if self.trainer is not None else None
        if text:
            with open(os.path.join(self.directory, self.STEP_FILE), "w") as f:
                f.write(text)
        import logging
        logging.getLogger(__name__).info(
            "StepTraceCapture: wrote steps %d-%d trace to %s",
            self.start_step, self.stop_step, self.directory)


class Mode(object):
    SYMBOLIC = "symbolic"       # kOnlySymbolic
    ALL = "all"                 # kAllOperator
    ALL_XLA = "all_xla"         # + device-side XLA trace via jax.profiler


class State(object):
    STOP = "stop"               # kNotRunning
    RUN = "run"                 # kRunning


_config = {"mode": Mode.ALL, "filename": "profile.json", "trace_dir": None}
_state = [State.STOP]
_xla_tracing = [False]


def profiler_set_config(mode="symbolic", filename="profile.json",
                        trace_dir=None):
    """Set profiler mode and output file (reference profiler.py:
    profiler_set_config / MXSetProfilerConfig)."""
    if mode not in (Mode.SYMBOLIC, Mode.ALL, Mode.ALL_XLA):
        raise MXNetError("invalid profiler mode %r" % (mode,))
    _config["mode"] = mode
    _config["filename"] = filename
    _config["trace_dir"] = trace_dir


def profiler_set_state(state="stop"):
    """Start/stop profiling (reference profiler.py:profiler_set_state /
    MXSetProfilerState)."""
    from . import engine
    if state not in (State.RUN, State.STOP):
        raise MXNetError("invalid profiler state %r" % (state,))
    running = state == State.RUN
    engine.get().set_profiler_state(running)
    if _config["mode"] == Mode.ALL_XLA:
        trace_dir = _config["trace_dir"] or \
            os.path.splitext(_config["filename"])[0] + "_xla"
        if running and not _xla_tracing[0]:
            jax.profiler.start_trace(trace_dir)
            _xla_tracing[0] = True
        elif not running and _xla_tracing[0]:
            jax.profiler.stop_trace()
            _xla_tracing[0] = False
    _state[0] = state


def dump_profile(finished=True):
    """Write the host engine's operations and the program's spans as
    Chrome traceEvents JSON to the configured filename (reference
    profiler.py:dump_profile / MXDumpProfile).  Both are stamped in
    microseconds of the monotonic clock; a span's ``tid`` is its thread."""
    from . import engine
    data = json.loads(engine.get().dump_profile())
    data["traceEvents"] = list(data.get("traceEvents") or []) + \
        _chrome_events(spans(), lambda r: r["start"] * 1e6)
    with open(_config["filename"], "w") as f:
        json.dump(data, f)
    return _config["filename"]


def _newest_profile_file(trace_dir, suffix):
    """Newest <trace_dir>/plugins/profile/*/*<suffix> that jax.profiler
    wrote."""
    import glob
    cands = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*" + suffix))
    if not cands:
        raise MXNetError("no *%s of a jax.profiler trace under %r"
                         % (suffix, trace_dir))
    return max(cands, key=os.path.getmtime)


def _recorded_path(stats):
    """The scope path XLA recorded on a device event, or None (a TPU trace
    of this jax records none: the path is in the compiled step's text)."""
    return stats.get("tf_op") or stats.get("op_name") or None


def _device_lines(trace_dir):
    """(profile_start_time in Unix ns, {device plane: {line name:
    [(start_ns, duration_ns, name, recorded path)]}}) of the newest
    ``.xplane.pb`` under ``trace_dir``; event starts count from
    profile_start_time."""
    data = jax.profiler.ProfileData.from_file(
        _newest_profile_file(trace_dir, ".xplane.pb"))
    start, devices = None, {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
        elif plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {
                line.name: [(e.start_ns, e.duration_ns, e.name,
                             _recorded_path(dict(e.stats)))
                            for e in line.events] for line in plane.lines}
    return start, devices


def _busiest(devices):
    """(plane name, merged busy intervals [[start, end]]) of the device
    whose ``XLA Ops`` cover the most time."""
    busy = {}
    for name, lines in devices.items():
        merged = []
        for s, d in sorted(e[:2] for e in lines.get("XLA Ops", ())):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], s + d)
            else:
                merged.append([s, s + d])
        if merged:
            busy[name] = merged
    if not busy:
        raise MXNetError("the trace holds no device plane with XLA Ops: %r"
                         % sorted(devices))
    return max(busy.items(), key=lambda kv: sum(e - s for s, e in kv[1]))


def _attribute_gaps(start_ns, devices, records, top=10):
    """:func:`idle_gaps` on plain data: ``devices`` as
    :func:`_device_lines` gives them, ``records`` as :func:`spans`."""
    if start_ns is None:
        raise MXNetError("the trace has no profile_start_time: its events "
                         "cannot be put on the spans' clock")
    device, merged = _busiest(devices)
    # whole nanoseconds: a float holds Unix nanoseconds to 256 of them
    gaps = [(start_ns + round(a[1]), start_ns + round(b[0]))
            for a, b in zip(merged, merged[1:])]
    # the thread that hands the device its work says why the device waits
    feeders = {r["thread"] for r in records if r["name"] == "step.dispatch"}
    mine = [(r["unix_ns"], r["unix_ns"] + round((r["end"] - r["start"]) * 1e9),
             r["name"]) for r in records
            if not feeders or r["thread"] in feeders]
    by_span, rows = collections.Counter(), []
    for g0, g1 in gaps:
        over = [(max(s, g0), min(e, g1), s, n) for s, e, n in mine
                if s < g1 and e > g0]
        cuts = sorted({g0, g1} | {t for o in over for t in o[:2]})
        row = collections.Counter()
        for a, b in zip(cuts, cuts[1:]):
            # spans of one thread nest: the one that started last is innermost
            inner = max((o for o in over if o[0] <= a and o[1] >= b),
                        key=lambda o: o[2], default=None)
            row[inner[3] if inner else "unattributed"] += (b - a) / 1e9
        by_span.update(row)
        rows.append({"start_unix_ns": g0, "seconds": (g1 - g0) / 1e9,
                     "by_span": dict(row.most_common())})
    rows.sort(key=lambda r: -r["seconds"])
    return {"device": device,
            "window_s": (merged[-1][1] - merged[0][0]) / 1e9,
            "idle_s": sum(r["seconds"] for r in rows),
            "by_span": dict(by_span.most_common()), "gaps": rows[:top]}


def idle_gaps(trace_dir, top=10):
    """Why the device sat idle: the gaps between the ``XLA Ops`` of the
    busiest device in the newest ``jax.profiler`` trace under
    ``trace_dir``, each put down to the program's spans that overlap it.

    Returns ``{"device", "window_s" (first operation to last), "idle_s",
    "by_span": {span name: idle seconds under it, over all gaps}, "gaps":
    the ``top`` longest, each {"start_unix_ns", "seconds", "by_span"}}``.
    A gap's time goes to the innermost span open over it on the thread
    that dispatches the steps (every thread where nothing dispatched), and
    to ``unattributed`` where none was.  The spans are those
    ``StepTraceCapture`` wrote beside the trace, else this process's ring.
    Raises if the trace has no ``profile_start_time``: without it the two
    clocks cannot be laid over each other."""
    start_ns, devices = _device_lines(trace_dir)
    path = os.path.join(trace_dir, StepTraceCapture.SPANS_FILE)
    if os.path.exists(path):
        with open(path) as f:
            records = [{"name": e["name"], "thread": e["tid"],
                        "unix_ns": int(e["ts"] * 1e3), "start": 0.0,
                        "end": e["dur"] / 1e6}
                       for e in json.load(f)["traceEvents"]
                       if e["ph"] == "X"]
    else:
        records = spans()
    return _attribute_gaps(start_ns, devices, records, top)


# -- the op table ------------------------------------------------------------

_PASSES = {"forward": "", "remat": "_remat_", "backward": "_backward_"}
# parts of an op_name path that jax writes for its own transformations
_JAX_PARTS = ("", "checkpoint", "rematted_computation", "shard_map")
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]+)"', re.M)
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")


def _parse_path(path):
    """``(names, pass, staged)`` of one ``op_name`` path.

    The executor wraps every graph node in ``jax.named_scope(node.name)``,
    every ``mirror_stage`` in one of the stage's name around its
    checkpoint, and the trainer its own work in ``step.*``; autodiff wraps
    the outermost scope: ``jit(step)/jvp(l1_kda)/l1_kda_proj/dot_general``
    is a stage's forward, ``.../transpose(jvp(l1_kda))/jvp(l1_kda)/
    checkpoint/l1_kda_proj/transpose`` its backward and ``.../checkpoint/
    rematted_computation/l1_kda_proj/sub`` its rematerialised forward;
    ``jvp(conv1)`` / ``transpose(jvp(conv1))`` are an unstaged node's.
    ``names`` are the scopes somebody wrote, outermost first: the parts
    without the first (``jit(...)``), jax's own and the primitive — which
    stands in where there is no scope; ``staged`` says that a checkpoint
    sits directly under the first of them."""
    parts = [p for p in path.rstrip(":").split("/") if p][1:]
    names, backward, remat, staged = [], False, False, False
    for part in parts[:-1]:
        while part.endswith(")") and part.startswith(("transpose(", "jvp(")):
            backward = backward or part.startswith("transpose(")
            part = part[part.index("(") + 1:-1]
        remat = remat or part == "rematted_computation"
        staged = staged or (part == "checkpoint" and len(names) == 1)
        if part not in _JAX_PARTS and part not in names[-1:]:
            names.append(part)
    return (names or parts[-1:],
            "remat" if remat else "backward" if backward else "forward",
            staged)


def _instruction_paths(hlo_text):
    """{instruction name: op_name path} of a compiled module's text (a
    fusion's line carries its root's path)."""
    paths = {}
    for name, path in _HLO_LINE.findall(hlo_text or ""):
        paths.setdefault(name, path)
    return paths


def _self_times(events):
    """[(event, self ns)] in order of start: an event's duration less that
    of the events nested directly inside it (a ``while`` spans its body's
    operations; events of one line nest or are disjoint)."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and e[0] >= stack[-1][0][0] + stack[-1][0][1]:
            stack.pop()
        if stack:
            stack[-1][1] -= e[1]
        stack.append([e, e[1]])
        out.append(stack[-1])
    return [(e, max(t, 0)) for e, t in out]


def _op_table(events, hlo_text=None):
    """:func:`get_op_stats` on plain data: the ``XLA Ops`` events of one
    device as :func:`_device_lines` gives them."""
    paths = _instruction_paths(hlo_text)
    timed = []
    for event, self_ns in _self_times(events):
        instruction = _INSTRUCTION.match(event[2])
        instruction = instruction.group(1) if instruction else "?"
        path = event[3] or paths.get(instruction)
        timed.append((instruction, path, self_ns / 1e3))
    # a path the program wrote starts with the jitted function; XLA puts
    # names of its own in a path's place (ragged-dot-none, a parameter's)
    parsed = {p: _parse_path(p) for _, p, _ in timed
              if p and p.startswith("jit(")}
    stages = {names[0] for names, _, staged in parsed.values() if staged}

    rows, near = {}, ""
    for instruction, path, us in timed:
        if path in parsed:
            names, which, _ = parsed[path]
            stage = names[0] if names[0] in stages else ""
            node = names[0] if not stage else \
                names[1] if len(names) > 1 else ""
            near = _PASSES[which] + (stage or node)
            name = near + ("/" + node if stage and node else "")
            fields = {"stage": stage, "pass": which, "node": node}
        else:
            # the compiler's own: a copy, a slice, a clone, or a name it
            # put in the path's place (ragged-dot-none)
            name = path or "hlo:" + re.sub(r"(\.\d+|\.clone)+$", "",
                                           instruction)
            fields = {"near": {}}
        row = rows.setdefault(name, dict(
            fields, count=0, total_us=0.0, min_us=float("inf"), max_us=0.0))
        row["count"] += 1
        row["total_us"] += us
        row["min_us"] = min(row["min_us"], us)
        row["max_us"] = max(row["max_us"], us)
        if "near" in row:
            row["near"][near] = row["near"].get(near, 0.0) + us
    for row in rows.values():
        for key in ("total_us", "min_us", "max_us"):
            row[key] = round(row[key], 3)
        row["avg_us"] = round(row["total_us"] / row["count"], 3)
        if "near" in row:
            row["near"] = {k: round(v, 3) for k, v in row["near"].items()}
    return rows


def _recorded_lines(path):
    """A trace kept as a plain structure (what ``benchmark/run.py
    --keep-trace`` writes, and the tests' recorded cuts): ``{"planes":
    [{"name", "lines": [{"name", "events": [[name, start_ns, duration_ns,
    {stat: value}]]}]}]}``, gzipped JSON -> what :func:`_device_lines`
    gives."""
    with gzip.open(path, "rt") as f:
        planes = json.load(f)["planes"]
    return {plane["name"]: {
        line["name"]: [(e[1], e[2], e[0], _recorded_path(e[3]))
                       for e in line["events"]] for line in plane["lines"]}
        for plane in planes if plane["name"].startswith("/device:TPU:")}


def get_op_stats(trace_dir=None, hlo_text=None):
    """Device time by graph node from a ``jax.profiler`` trace of the chip:
    ``{name: {"count", "total_us", "avg_us", "min_us", "max_us", ...}}``
    (the reference's per-op profile, src/engine/profiler.cc:134-216, over
    a FUSED program).

    Read: the newest ``.xplane.pb`` under ``trace_dir`` (default: where
    ``mode='all_xla'`` traces; a file is taken as a trace kept by
    ``benchmark/run.py --keep-trace``), the busiest device's ``XLA Ops``
    line, each event's SELF time.  An event's scope path is the one XLA
    recorded on it, else — a TPU trace of this jax names events by HLO
    instruction and records none — the instruction's ``op_name`` in
    ``hlo_text``, the compiled step's text (``SPMDTrainer.step_text()``;
    default: ``mxnet_tpu_step.hlo.txt`` in the directory, which
    ``StepTraceCapture`` writes, or ``<file>.hlo.txt``).  Without either
    every row is the compiler's own.

    Rows are by (stage, pass, node), each also a field of the row: a
    ``mirror_stage``'s node is ``l1_kda/l1_kda_conv_q``, rematerialised
    ``_remat_l1_kda/l1_kda_conv_q``, backward ``_backward_l1_kda/...``; a
    node under no stage ``conv1`` / ``_backward_conv1``; the trainer's own
    work ``step.update``, ``step.guard``, ...  Events whose path the
    program did not write — ``hlo:copy``, ``hlo:slice-done``,
    ``ragged-dot-none``, clones — have no stage; their ``near`` is
    ``{stage and pass of the last event WITH a path before them on the
    line: us}``.  That is adjacency on the device's timeline, not the
    compiler's word: such an event may serve a later stage or none."""
    trace = trace_dir or _config["trace_dir"] \
        or os.path.splitext(_config["filename"])[0] + "_xla"
    if os.path.isdir(trace) or not os.path.exists(trace):
        devices = _device_lines(trace)[1]
        text = os.path.join(trace, StepTraceCapture.STEP_FILE)
    else:
        devices, text = _recorded_lines(trace), trace + ".hlo.txt"
    if hlo_text is None and os.path.exists(text):
        with open(text) as f:
            hlo_text = f.read()
    return _op_table(devices[_busiest(devices)[0]]["XLA Ops"], hlo_text)


def dumps(reset=False, trace_dir=None, hlo_text=None):
    """:func:`get_op_stats` as a table (reference mx.profiler.dumps):
    every stage with its three passes' subtotals above its nodes, the
    nodes under no stage and the ``step.*`` scopes a line each, then the
    compiler's own events grouped by ``near`` (of a group, the names that
    hold at least a hundredth of it).  ``reset`` is accepted for API parity
    (traces are per-start_trace already)."""
    del reset
    stats = get_op_stats(trace_dir, hlo_text)
    width = max([len("Name")] + [len(k) + 2 for k in stats]) + 2
    lines = ["Profile Statistics (device self time, fused program): "
             "%.3f us in %d rows" % (
                 sum(s["total_us"] for s in stats.values()), len(stats)),
             "%-*s %10s %12s %12s %12s %12s" % (
                 width, "Name", "Count", "Total-us", "Min-us", "Max-us",
                 "Avg-us")]

    def line(name, s, indent=""):
        lines.append("%-*s %10d %12.3f %12.3f %12.3f %12.3f" % (
            width, indent + name, s["count"], s["total_us"], s["min_us"],
            s["max_us"], s["avg_us"]))

    def total(rows):
        return sum(s["total_us"] for _, s in rows)

    def by_time(rows):
        return sorted(rows, key=lambda kv: -kv[1]["total_us"])

    pathed = [(k, s) for k, s in stats.items() if "near" not in s]
    stages = {}
    for k, s in pathed:
        if s["stage"]:
            stages.setdefault(s["stage"], []).append((k, s))
    for stage, rows in sorted(stages.items(), key=lambda kv: -total(kv[1])):
        lines.append("stage %s: %.3f us" % (stage, total(rows)))
        for which, prefix in _PASSES.items():
            part = [(k, s) for k, s in rows if s["pass"] == which]
            if part:
                lines.append("  %-*s %10d %12.3f" % (
                    width - 2, prefix + stage + " (%s)" % which,
                    sum(s["count"] for _, s in part), total(part)))
                for k, s in by_time(part):
                    line(k, s, "    ")
    lines.append("under no stage")
    for k, s in by_time((k, s) for k, s in pathed if not s["stage"]):
        line(k, s, "  ")
    own = [(k, s) for k, s in stats.items() if "near" in s]
    if own:
        lines.append("the compiler's own, by what ran before them on the "
                     "device (adjacency, not attribution): %.3f us"
                     % total(own))
        groups = {}
        for k, s in own:
            for where, us in s["near"].items():
                groups.setdefault(where, {})[k] = us
        for where, members in sorted(groups.items(),
                                     key=lambda kv: -sum(kv[1].values())):
            lines.append("  near %s: %.3f us" % (where or "(nothing)",
                                                 sum(members.values())))
            for k, us in sorted(members.items(), key=lambda kv: -kv[1]):
                if us >= 0.01 * sum(members.values()):
                    lines.append("    %-*s %23.3f" % (width - 4, k, us))
    return "\n".join(lines) + "\n"


if str(get_env(ENV_PROFILER_AUTOSTART, "0")) == "1":
    profiler_set_state(State.RUN)
