"""From a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
a plain structure (``jax.profiler.ProfileData``, nothing else):

    {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [[name, start_ns, dur_ns, {stat: v}], ...]}]}]}

``reduce`` works on that structure alone, so that a small recorded one
(``benchmark/tests/data/``) checks it without a chip.  Per device it gives
the busy union of operation intervals, the time of each graph-node scope,
the step program's time, collective time and its exposed part, and the
longest idle gaps; ``breakdown`` puts the gaps beside what the host was
doing in them.

The scope rule is borrowed from ``mxnet_tpu.profiler._scope_of``: the
executor wraps every symbol node in ``jax.named_scope(node.name)``, XLA
carries the path in each instruction's ``op_name``
(``jit(step)/jvp(conv0)/conv_general_dilated``), ``jvp(x)`` is node ``x``'s
forward and ``transpose(jvp(x))`` its backward (``_backward_x``).  On this
JAX the TPU trace's events are named by the HLO instruction's text and
carry no ``op_name`` (Findings, PR 23), so the path comes from the compiled
step's own HLO text (``instruction_scopes``), keyed by instruction name; a
fusion carries the path of its root.  Events nest (a ``while`` spans its
body's operations): a scope is given each event's SELF time.
"""
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINES = ("XLA Ops",)
ASYNC_LINES = ("Async XLA Ops",)
MODULE_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\b")
_OP_NAME = re.compile(r'op_name="([^"]+)"')
_INSTR = re.compile(r"^%?([A-Za-z0-9_.\-]+)")
_SCOPE_STATS = ("tf_op", "op_name", "long_name")


def load_xplane(path):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                stats = {}
                for k, v in e.stats:
                    stats[str(k)] = v if isinstance(v, (int, float)) \
                        else str(v)
                events.append([e.name, float(e.start_ns),
                               float(e.duration_ns), stats])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def newest_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return max(paths, key=os.path.getmtime)


def save(structure, path):
    with gzip.open(path, "wt") as f:
        json.dump(structure, f)


def load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def cut(structure, t0_ns, t1_ns, max_name=4000):
    """The events that start inside [t0, t1), names clipped: what a test
    keeps of a real trace."""
    planes = []
    for plane in structure["planes"]:
        lines = []
        for line in plane["lines"]:
            events = [[e[0][:max_name], e[1], e[2], e[3]]
                      for e in line["events"] if t0_ns <= e[1] < t1_ns]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


# -- scopes -----------------------------------------------------------------

_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=.*?op_name="([^"]+)"')


def instruction_scopes(hlo_text):
    """{instruction name: op_name path} from a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        if "op_name=" not in line:
            continue
        m = _HLO_LINE.match(line)
        if m:
            out.setdefault(m.group(1), m.group(2))
    return out


def instruction_of(event):
    m = _INSTR.match(event[0])
    return m.group(1) if m else None


def op_path(event, paths=None):
    """The named-scope path XLA recorded for a device event, or None."""
    name, stats = event[0], event[3]
    for key in _SCOPE_STATS:
        if stats.get(key):
            return str(stats[key])
    m = _OP_NAME.search(name)
    if m:
        return m.group(1)
    return (paths or {}).get(instruction_of(event))


def scope_of(event, paths=None):
    """Graph-node name of a device event (``_backward_<node>`` for its
    backward), or the HLO instruction's own name where XLA recorded no
    path (copies, infeed)."""
    path = op_path(event, paths)
    if path:
        parts = [p for p in path.rstrip(":").split("/") if p]
        if parts and (parts[0].startswith("jit(")
                      or parts[0].startswith("pjit(")):
            parts = parts[1:]
        for part in parts:
            m = re.fullmatch(r"transpose\(jvp\((.+)\)\)", part)
            if m:
                return "_backward_" + m.group(1)
            m = re.fullmatch(r"jvp\((.+)\)", part)
            if m:
                return m.group(1)
        if len(parts) >= 2:
            return "/".join(parts[:-1])
        if parts:
            return parts[0]
    m = _INSTR.match(event[0])
    return "hlo:" + (re.sub(r"[.\d]+$", "", m.group(1)) if m else "?")


def is_collective(event):
    m = _INSTR.match(event[0])
    return bool(m and COLLECTIVE.search(m.group(1)))


# -- intervals --------------------------------------------------------------

def union(intervals):
    """Merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(merged):
    return sum(e - s for s, e in merged)


def subtract(merged_a, merged_b):
    """Length of merged_a not covered by merged_b."""
    out, j = 0.0, 0
    for s, e in merged_a:
        cur = s
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < e:
            bs, be = merged_b[k]
            if bs > cur:
                out += bs - cur
            cur = max(cur, be)
            k += 1
        if cur < e:
            out += e - cur
    return out


def gaps(merged, t0, t1):
    """[(start, end)] of the idle stretches of [t0, t1]."""
    out, cur = [], t0
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


# -- the reduction ----------------------------------------------------------

def _lines(plane, names):
    return [l for l in plane["lines"] if l["name"] in names]


def self_times(events):
    """[(event, self_ns)]: each event's duration less that of the events
    nested directly inside it (same line, so they nest or are disjoint)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for e in order:
        while stack and e[1] >= stack[-1][0][1] + stack[-1][0][2]:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= e[2]
        stack.append([e, e[2]])
    out.extend(tuple(x) for x in stack)
    return [(e, max(t, 0.0)) for e, t in out]


def reduce_device(plane, paths=None):
    """One device plane -> its numbers (seconds)."""
    ops = [e for l in _lines(plane, OPS_LINES) for e in l["events"]]
    asyncs = [e for l in _lines(plane, ASYNC_LINES) for e in l["events"]]
    modules = [e for l in _lines(plane, (MODULE_LINE,)) for e in l["events"]]
    if not ops and not modules:
        return None
    spans = ops or modules
    t0 = min(e[1] for e in spans)
    t1 = max(e[1] + e[2] for e in spans)
    busy = union([(e[1], e[1] + e[2]) for e in spans])

    scopes = {}
    for e, self_ns in self_times(ops):
        s = scope_of(e, paths)
        scopes[s] = scopes.get(s, 0.0) + self_ns
    by_module = {}
    for e in modules:
        name = re.sub(r"\(\d+\)$", "", e[0])
        by_module[name] = by_module.get(name, 0.0) + e[2]

    coll = [e for e in ops + asyncs if is_collective(e)]
    compute = union([(e[1], e[1] + e[2]) for e in ops
                     if not is_collective(e)])
    coll_union = union([(e[1], e[1] + e[2]) for e in coll])
    step = max(by_module.items(), key=lambda kv: kv[1]) if by_module \
        else (None, 0.0)
    return {
        "t0_ns": t0, "t1_ns": t1, "window_s": (t1 - t0) / 1e9,
        "busy_s": total(busy) / 1e9,
        "scopes_s": {k: v / 1e9 for k, v in scopes.items()},
        "modules_s": {k: v / 1e9 for k, v in by_module.items()},
        "step_module": step[0], "step_module_s": step[1] / 1e9,
        "collective_s": total(coll_union) / 1e9,
        "collective_exposed_s": subtract(coll_union, compute) / 1e9,
        "idle_gaps_ns": sorted(gaps(busy, t0, t1),
                               key=lambda g: g[0] - g[1])[:10],
    }


def host_lines(structure, marker=None):
    """The host threads' lines; with ``marker``, only those holding an
    event whose name contains it (the thread that runs the harness's own
    loop), if any does."""
    lines = [line for plane in structure["planes"]
             if plane["name"].startswith("/host:")
             for line in plane["lines"]]
    if marker:
        mine = [line for line in lines
                if any(marker in e[0] for e in line["events"])]
        lines = mine or lines
    return lines


def host_activity(lines, t0, t1, horizon, limit=3):
    """What the host was doing over [t0, t1] (ns): of the events that
    cover at least half of it and last under half of ``horizon`` (the
    traced window: the loop around the whole run says nothing), the
    ``limit`` longest — the outermost frames that are about this stretch —
    outermost first."""
    found = []
    for line in lines:
        for e in line["events"]:
            lo, hi = max(e[1], t0), min(e[1] + e[2], t1)
            if hi - lo >= 0.5 * (t1 - t0) and e[2] < 0.5 * horizon:
                found.append((-e[2], e[1], e[0]))
    names = []
    for _, _, name in sorted(found):
        name = re.sub(r"^\$", "", name)[:60]
        if name not in names:
            names.append(name)
        if len(names) == limit:
            break
    return names


def reduce(structure, chips=1, paths=None, host_marker=None):
    """The whole trace -> numbers.  ``paths`` maps HLO instruction names
    to op_name paths (``instruction_scopes``); ``host_marker`` picks the
    host thread whose frames label the idle gaps (``host_lines``).  Busy
    and window are averaged over the device planes used; the per-scope,
    module and collective figures are the fullest (busiest) device's."""
    devices = []
    for plane in structure["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            r = reduce_device(plane, paths)
            if r is not None:
                r["plane"] = plane["name"]
                devices.append(r)
    if not devices:
        raise ValueError("the trace holds no device plane with events: %r"
                         % [p["name"] for p in structure["planes"]])
    devices = devices[:chips] if len(devices) > chips else devices
    fullest = max(devices, key=lambda d: d["busy_s"])
    out = dict(fullest)
    out["busy_s_fullest"] = fullest["busy_s"]
    out["busy_s"] = sum(d["busy_s"] for d in devices) / len(devices)
    out["window_s"] = sum(d["window_s"] for d in devices) / len(devices)
    out["devices"] = len(devices)
    worst = max(devices, key=lambda d: d["collective_exposed_s"])
    out["collective_s"] = worst["collective_s"]
    out["collective_exposed_s"] = worst["collective_exposed_s"]
    lines = host_lines(structure, host_marker)
    horizon = fullest["t1_ns"] - fullest["t0_ns"]
    out["idle_gaps"] = [
        [" > ".join(host_activity(lines, s, e, horizon)) or "host idle",
         (e - s) / 1e9] for s, e in fullest["idle_gaps_ns"]]
    return out


def breakdown(reduced):
    """The result line's ``breakdown``: at most ten device scopes by time
    and ten idle gaps by what the host was doing."""
    ops = sorted(reduced["scopes_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": reduced["idle_gaps"][:10]}
