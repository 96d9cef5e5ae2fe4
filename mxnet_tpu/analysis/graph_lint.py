"""Level-1 static analysis: lint one jitted step program.

The reference got its graph-level guarantees from NNVM passes
(infer_shape, plan_memory); the TPU-native analog inspects the three
artifacts every jitted step already produces — the jaxpr (host-callback
and dtype rules), the lowering's arg/out metadata (donation rules) and
the compiled HLO module (the collective audit) — and reports violations
of the invariants the runtime relies on:

- ``graph-donation-missing``: a large array argument whose shape/dtype
  matches an output (a carry: params, optimizer state, metric/guard
  accumulators) is not covered by ``donate_argnums`` — each step then
  pays an extra HBM copy and doubles the buffer's footprint.
- ``graph-donation-unused``: a donated argument matches NO output, so
  XLA cannot alias it anywhere — the donation is silently wasted and the
  caller's array is still invalidated (a likely bug at the call site).
- ``graph-callback``: a ``pure_callback``/``io_callback``/
  ``debug_callback`` equation inside the step — a host sync point that
  serializes the device pipeline every single step.
- ``graph-collective-allgather``: all-gather traffic in a step whose
  declared sharding should not need it (replicated params under plain dp
  'allreduce'), at or above a meaningful fraction of the parameter
  bytes — the GSPMD signature of an accidental full-parameter regather.
- ``graph-collective-schedule``: the inverse direction — a step that
  DECLARED fully-sharded training (grad_sync='zero3') must actually
  all-gather ~param bytes and reduce-scatter its gradients; missing
  gathers or a param-scale all-reduce mean the sharding silently never
  happened.  The reduce-scatter requirement covers the manual tier on
  every backend AND the gspmd tier on TPU/GPU pipelines (where XLA's
  ReduceScatterCreator must rewrite all-reduce+slice; CPU keeps the
  all-reduce form as a documented tier note).  ``trainer.analyze()``
  under zero3 is thereby the PROOF the collective schedule matches the
  declared strategy.
- ``graph-dtype-drift``: dot/conv equations computing in a wider float
  than the declared ``compute_dtype`` — silent f32 math inside a bf16
  step costs ~2x FLOP time on the MXU.
- ``graph-pallas-no-vjp``: a ``pallas_call`` not protected by a
  registered ``custom_vjp``/``custom_jvp`` — Pallas has no reverse-mode
  transpose, so a differentiated step reaching it dies at trace time
  (or the op is silently forward-only); rtc.py documents the contract.
- ``plan-fusion-parity``: the mxfuse plan-optimizer rewrite for a
  symbol must keep the plain-plan monitored path intact — every pass
  may only FILL override slots: entry count, node identity/order and
  slots 0-4 (attrs, output counts, aux names, RNG fold positions) must
  be byte-identical to the unoptimized plan, no extra ref may read a
  value-rewritten passthrough, and the original plan object must be
  left untouched (monitored runs interpret it verbatim).
  ``audit_plan_fusion(symbol)`` is the check; ``trainer.analyze()``
  and ``PooledModel.analyze()`` run it on their bound symbols.

All jax imports are function-local so importing this module costs
nothing in host-only contexts (the AST level and the CLI).
"""
from __future__ import annotations

import re

from .report import Finding, Report

__all__ = ["iter_eqns", "find_callbacks", "audit_dtype", "audit_donation",
           "collective_stats", "audit_collectives",
           "audit_collective_schedule", "find_unprotected_pallas",
           "audit_plan_fusion", "lint_lowered", "lint_jit",
           "CALLBACK_PRIMITIVES", "COLLECTIVE_OPS", "PALLAS_PRIMITIVES",
           "RS_PLATFORMS"]

#: jaxpr primitives that re-enter the host mid-step
CALLBACK_PRIMITIVES = frozenset((
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback_call", "outside_call",
))

#: Pallas kernel-call primitives — no reverse-mode transpose exists for
#: these (rtc.py's documented contract), so one reachable from a
#: differentiated step MUST sit under a registered custom_vjp
PALLAS_PRIMITIVES = frozenset(("pallas_call",))

#: primitives whose body is differentiation-protected: jax never
#: transposes THROUGH these (the registered rules apply instead), so a
#: pallas_call inside them is safe and the walk does not descend
_CUSTOM_DIFF_WRAPPERS = frozenset((
    "custom_vjp_call", "custom_vjp_call_jaxpr", "custom_jvp_call",
    "custom_jvp_call_jaxpr", "custom_jvp_generic_call",
))

#: primitives whose dtype decides where the MXU/VPU math happens
_COMPUTE_PRIMITIVES = frozenset(("dot_general", "conv_general_dilated"))

#: HLO instruction names of cross-device traffic (the ``-start`` async
#: forms count once; ``-done`` carries no payload of its own)
COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

_WIDER_THAN = {
    "bfloat16": ("float32", "float64"),
    "float16": ("float32", "float64"),
    "float32": ("float64",),
}

# f32[128,64]{1,0} / bf16[8]{0} / pred[] ... inside an HLO result type
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# `%name = <result type> <collective>(` — the result type is everything
# between '= ' and the op name; matching on the instruction form keeps
# op_name metadata strings from false-matching
_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<type>(?:\([^)]*\)|[a-z][a-z0-9]*\[[^\]]*\][^\s]*))\s*"
    r"(?P<op>" + "|".join(re.escape(o) for o in COLLECTIVE_OPS) + r")"
    r"(?P<suffix>-start|-done)?\(")

# replica_groups={{0,1},{2,3}} (explicit) or [2,4]<=[8] (iota v2:
# num_groups, devices_per_group) on the same instruction line
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(?:\{\{(?P<first>[0-9, ]*)\}|"
    r"\[(?P<groups>\d+),(?P<size>\d+)\]<=)")


def _is_degenerate_groups(line):
    """True when the instruction's replica_groups are singletons (each
    device alone) — the partitioner's representation of a NO-OP
    collective that moves zero bytes across devices.  GSPMD emits these
    to materialize per-device partial values; counting them as traffic
    would make the schedule audit see phantom all-reduces.  Lines with
    no replica_groups at all (hand-written fixtures) count as real."""
    m = _REPLICA_GROUPS_RE.search(line)
    if m is None:
        return False
    if m.group("size") is not None:
        return int(m.group("size")) <= 1
    return "," not in (m.group("first") or "")


def _eqn_location(eqn):
    """(file, line) of the traced user code for one equation, best
    effort (source info is jax-internal; absent on synthesized eqns)."""
    try:
        frame = eqn.source_info.traceback.frames[0]
        return frame.file_name, frame.start_line
    except Exception:  # noqa: BLE001 — diagnostics only
        return None, None


def iter_eqns(jaxpr, prune=frozenset()):
    """Yield every equation in ``jaxpr`` including nested sub-jaxprs
    (pjit bodies, scan/while bodies, cond branches, remat, custom_vjp).

    ``prune``: primitive names whose equations are yielded but whose
    sub-jaxprs are NOT descended into (the pallas rule prunes at
    custom-vjp wrappers — their bodies are differentiation-protected)."""
    from jax.extend import core as jex_core

    def _walk(jxp):
        for eqn in jxp.eqns:
            yield eqn
            if eqn.primitive.name in prune:
                continue
            for v in eqn.params.values():
                items = v if isinstance(v, (list, tuple)) else (v,)
                for item in items:
                    if isinstance(item, jex_core.ClosedJaxpr):
                        yield from _walk(item.jaxpr)
                    elif isinstance(item, jex_core.Jaxpr):
                        yield from _walk(item)
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    return _walk(inner)


def find_callbacks(closed_jaxpr):
    """``graph-callback`` findings for every host-callback equation."""
    out = []
    for eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMITIVES:
            fname, line = _eqn_location(eqn)
            out.append(Finding(
                "graph-callback",
                "host callback %r inside the jitted step — a per-step "
                "host sync point (move it out of the step or behind a "
                "deferred metric/guard carry)" % name,
                file=fname, line=line))
    return out


def find_unprotected_pallas(closed_jaxpr):
    """``graph-pallas-no-vjp``: a ``pallas_call`` NOT wrapped in a
    ``custom_vjp``/``custom_jvp`` rule.  Pallas has no reverse-mode
    transpose, so differentiating through such a kernel is a trace-time
    error at best — and in a step assembled from many ops the failure
    surfaces far from the kernel that caused it (rtc.py documents the
    hazard; kernels/ pairs every Pallas forward with a backward kernel
    behind ``jax.custom_vjp``).  The walk descends into ordinary
    sub-jaxprs (pjit/scan/while/cond/remat) but NOT into custom-vjp
    wrappers, whose bodies are differentiation-protected by the
    registered rule."""
    out = []
    for eqn in iter_eqns(closed_jaxpr, prune=_CUSTOM_DIFF_WRAPPERS):
        if eqn.primitive.name not in PALLAS_PRIMITIVES:
            continue
        fname, line = _eqn_location(eqn)
        out.append(Finding(
            "graph-pallas-no-vjp",
            "pallas_call without a registered custom_vjp is "
            "reachable from this step — Pallas kernels have no "
            "reverse-mode transpose, so differentiation fails "
            "at trace time (or silently degrades); pair the "
            "forward kernel with a backward kernel via "
            "jax.custom_vjp (rtc.register_kernel(vjp=...), "
            "kernels/ pattern)",
            file=fname, line=line))
    return out


def audit_dtype(closed_jaxpr, compute_dtype):
    """``graph-dtype-drift``: dot/conv eqns whose inputs are wider floats
    than the declared compute dtype.  Returns (findings, tally) where
    tally maps primitive name -> {dtype_name: count} for reporting."""
    import numpy as np
    tally = {}
    offenders = []
    compute_dtype = np.dtype(compute_dtype) if compute_dtype else None
    wider = _WIDER_THAN.get(compute_dtype.name, ()) if compute_dtype \
        else ()
    for eqn in iter_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name not in _COMPUTE_PRIMITIVES:
            continue
        in_dtypes = sorted({str(v.aval.dtype) for v in eqn.invars
                            if hasattr(v, "aval")
                            and hasattr(v.aval, "dtype")})
        slot = tally.setdefault(name, {})
        for d in in_dtypes:
            slot[d] = slot.get(d, 0) + 1
        if wider and any(d in wider for d in in_dtypes):
            offenders.append((eqn, in_dtypes))
    findings = []
    if offenders:
        fname, line = _eqn_location(offenders[0][0])
        findings.append(Finding(
            "graph-dtype-drift",
            "%d dot/conv equation(s) compute in %s inside a "
            "compute_dtype=%s step (first at the reported location) — "
            "a widening cast upstream is defeating the mixed-precision "
            "path" % (len(offenders),
                      "/".join(sorted({d for _, ds in offenders
                                       for d in ds if d in wider})),
                      compute_dtype.name),
            file=fname, line=line,
            data={"offending_eqns": len(offenders)}))
    return findings, tally


def _leaf_bytes(shape, dtype):
    import numpy as np
    n = 1
    for d in shape:
        n *= int(d)
    return n * np.dtype(dtype).itemsize


def _leading_argnum(path):
    """Positional index of the top-level argument a leaf path belongs
    to.  ``args_info`` is the ``(args, kwargs)`` pair, so a positional
    leaf's path is ``[0][argnum]...`` — the argnum is the SECOND key;
    kwargs leaves (path ``[1][name]...``) have no argnum."""
    try:
        if getattr(path[0], "idx", None) != 0:
            return None
        return getattr(path[1], "idx", None)
    except Exception:  # noqa: BLE001 — unexpected path shape
        return None


def audit_donation(lowered, min_bytes=1 << 20, carry_argnums=None):
    """Donation findings from a ``jax.stages.Lowered``'s arg/out info.

    An argument leaf is a *carry* when some output leaf has its exact
    (shape, dtype) — params vs updated params, accumulators vs updated
    accumulators.  Carries at or above ``min_bytes`` must be donated
    (``graph-donation-missing``); donated leaves that match no output
    cannot alias anywhere and are flagged ``graph-donation-unused``.
    Output slots are consumed greedily by donated args first, so a
    non-donated copy of an already-claimed output does not double-count.

    ``carry_argnums``: when the caller knows which positional arguments
    hold the step's carries (SPMDTrainer: params/aux/opt_state/extras),
    the missing-donation check is restricted to leaves under them — a
    DATA batch that happens to share an output's shape/dtype (an
    autoencoder's reconstruction, a per-example loss matching the label
    vector) must not be flagged as an un-donated carry.
    """
    import jax.tree_util as jtu

    arg_leaves = [(jtu.keystr(path), _leading_argnum(path), info)
                  for path, info in
                  jtu.tree_flatten_with_path(lowered.args_info)[0]]
    out_slots = {}
    for info in jtu.tree_leaves(lowered.out_info):
        key = (tuple(info.shape), str(info.dtype))
        out_slots[key] = out_slots.get(key, 0) + 1

    findings = []
    donated = [(p, i) for p, n, i in arg_leaves if i.donated]
    undonated = [(p, n, i) for p, n, i in arg_leaves if not i.donated]
    for path, info in donated:
        key = (tuple(info.shape), str(info.dtype))
        if out_slots.get(key, 0) > 0:
            out_slots[key] -= 1
        else:
            findings.append(Finding(
                "graph-donation-unused",
                "argument %s (%s%s, %d bytes) is donated but matches no "
                "output — XLA cannot alias it, the donation is wasted "
                "and the caller's buffer is invalidated anyway"
                % (path, info.dtype, list(info.shape),
                   _leaf_bytes(info.shape, info.dtype))))
    for path, argnum, info in undonated:
        if carry_argnums is not None and argnum not in carry_argnums:
            continue
        nbytes = _leaf_bytes(info.shape, info.dtype)
        if nbytes < min_bytes:
            continue
        key = (tuple(info.shape), str(info.dtype))
        if out_slots.get(key, 0) > 0:
            out_slots[key] -= 1
            findings.append(Finding(
                "graph-donation-missing",
                "argument %s (%s%s, %d bytes) looks like a carry (an "
                "output has the same shape/dtype) but is not donated — "
                "the step pays an avoidable HBM copy and holds two "
                "copies live" % (path, info.dtype, list(info.shape),
                                 nbytes)))
    return findings


def collective_stats(hlo_text):
    """Tally cross-device traffic in compiled (post-SPMD) HLO text.

    Returns ``{op: {"count": n, "bytes": b}}`` where ``bytes`` sums each
    instruction's per-device OUTPUT bytes (the shard this device
    materializes; async ``-start`` forms count once, ``-done`` not at
    all).  A sync instruction with a tuple result is a fused multi-tensor
    collective, so its shapes SUM.  An async ``-start`` result tuple is
    ``(operand-alias, result, context...)``: the payload is the RESULT —
    the largest shape for gathers (result = N x operand), the
    second-largest for reduce-scatter (result = operand / N; the tiny
    context buffers rank below both), and either of the two for the
    size-preserving ops.  A byte figure of 0 with nonzero count means
    shapes were unparseable (report still useful for counts).

    Degenerate instructions — ``replica_groups`` of singletons, the
    partitioner's zero-traffic way of materializing per-device partial
    values — are skipped entirely: they move no bytes between devices,
    and the schedule audit must not mistake them for real traffic.
    """
    stats = {op: {"count": 0, "bytes": 0} for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None or m.group("suffix") == "-done":
            continue
        if _is_degenerate_groups(line):
            continue
        op = m.group("op")
        stats[op]["count"] += 1
        sizes = []
        for dtype, dims in _SHAPE_RE.findall(m.group("type")):
            if dtype not in _DTYPE_BYTES:
                continue
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            sizes.append(n * _DTYPE_BYTES[dtype])
        if sizes:
            if m.group("suffix") != "-start":
                nbytes = sum(sizes)
            else:
                ranked = sorted(sizes, reverse=True)
                if op == "reduce-scatter" and len(ranked) > 1:
                    nbytes = ranked[1]
                else:
                    nbytes = ranked[0]
            stats[op]["bytes"] += nbytes
    return {op: s for op, s in stats.items() if s["count"]}


def audit_collectives(stats, param_bytes=None, expect_allgather=False,
                      allgather_fraction=0.5):
    """``graph-collective-allgather``: all-gather traffic in a step that
    declared replicated parameters (plain dp 'allreduce') — GSPMD only
    emits one when something un-replicated sneaks into the param path.
    With ``param_bytes`` given, only traffic >= ``allgather_fraction`` of
    it flags (an incidental small gather is not a regather storm);
    without it, any all-gather flags."""
    if expect_allgather:
        return []
    ag = stats.get("all-gather", {"count": 0, "bytes": 0})
    if not ag["count"]:
        return []
    if param_bytes and ag["bytes"] < allgather_fraction * param_bytes:
        return []
    detail = "%d all-gather(s), %d bytes/step per device" \
        % (ag["count"], ag["bytes"])
    if param_bytes:
        detail += " (params total %d bytes)" % param_bytes
    return [Finding(
        "graph-collective-allgather",
        "unexpected all-gather under a sharding that declares replicated "
        "parameters: %s — a full-parameter regather erases the point of "
        "dp sharding (check param_shardings / with_sharding_constraint "
        "placement)" % detail,
        data={"all_gather": ag, "param_bytes": param_bytes})]


#: platforms whose XLA pipeline runs ReduceScatterCreator — on these
#: the GSPMD tier's gradient reduction MUST compile to reduce-scatter
#: (ROADMAP item 2's previously-unverified claim, now a lint assertion);
#: CPU keeps the all-reduce+slice form and stays a documented tier note
RS_PLATFORMS = frozenset(("tpu", "gpu", "cuda", "rocm"))


def audit_collective_schedule(stats, schedule, expect_gather_bytes,
                              tolerance=0.25, platform=None):
    """``graph-collective-schedule``: under a DECLARED fully-sharded
    strategy the compiled schedule must actually be sharded.

    ``schedule`` is ``'zero3-manual'`` or ``'zero3-gspmd'`` (None
    disables the rule); ``expect_gather_bytes`` is the per-step forward
    gather traffic a correct step must move (the full-size comm-dtype
    bytes of every dp-sharded parameter — the trainer computes it from
    base sharding rules and shapes, so a broken override cannot lower
    the bar).  ``platform`` is the compiled backend (``'cpu'``/
    ``'tpu'``/``'gpu'``...; None = unknown).  Checks:

    - all-gather traffic >= (1 - tolerance) x expected — a zero3 step
      that moves less is NOT gathering its parameters, i.e. they were
      silently left replicated and the sharding never happened;
    - a stray full all-reduce: all-reduce traffic at or above HALF the
      expected gather bytes means gradients left the backward as a
      full all-reduce instead of reduce-scatter.  The manual tier owes
      this on EVERY backend (its psum_scatter is explicit); the gspmd
      tier owes it on :data:`RS_PLATFORMS`, where ReduceScatterCreator
      rewrites all-reduce+slice — on CPU the all-reduce form is the
      documented backend placement, reported in ``stats`` not flagged;
    - at least one real reduce-scatter instruction: always for the
      manual tier (it emits one per gather bucket by construction),
      and for the gspmd tier on :data:`RS_PLATFORMS` — the
      ReduceScatterCreator claim is thereby PROVEN per compile instead
      of assumed from XLA documentation.
    """
    if not schedule:
        return []
    findings = []
    ag = stats.get("all-gather", {"count": 0, "bytes": 0})
    rs = stats.get("reduce-scatter", {"count": 0, "bytes": 0})
    ar = stats.get("all-reduce", {"count": 0, "bytes": 0})
    expect = int(expect_gather_bytes or 0)
    # the gspmd tier's gradient reduction is backend-placed; only on
    # RS-pipeline platforms is its shape an assertable contract
    owes_rs = schedule == "zero3-manual" or (
        schedule == "zero3-gspmd" and platform in RS_PLATFORMS)
    if expect and ag["bytes"] < (1.0 - tolerance) * expect:
        findings.append(Finding(
            "graph-collective-schedule",
            "declared %s but the compiled step all-gathers only %d "
            "bytes/step of the >= %d expected for its sharded "
            "parameters — the params were left replicated; the "
            "sharding silently never happened" %
            (schedule, ag["bytes"], expect),
            data={"all_gather": ag, "expect_gather_bytes": expect}))
    if expect and ar["bytes"] >= 0.5 * expect and owes_rs:
        findings.append(Finding(
            "graph-collective-schedule",
            "declared %s%s but a param-scale all-reduce (%d bytes/step) "
            "is in the compiled schedule — gradients are leaving the "
            "backward as a full all-reduce instead of reduce-scatter" %
            (schedule,
             (" on %s" % platform) if schedule == "zero3-gspmd" else "",
             ar["bytes"]),
            data={"all_reduce": ar, "expect_gather_bytes": expect,
                  "platform": platform}))
    if owes_rs and expect and not rs["count"]:
        if schedule == "zero3-manual":
            why = ("the manual tier emits one per gather bucket by "
                   "construction, so the step was not built from the "
                   "declared formulation")
        else:
            why = ("on %s XLA's ReduceScatterCreator must rewrite the "
                   "gradient all-reduce+slice into reduce-scatter — "
                   "its absence means the pass did not engage and the "
                   "backward pays full all-reduce bandwidth"
                   % platform)
        findings.append(Finding(
            "graph-collective-schedule",
            "declared %s but the compiled step contains no "
            "reduce-scatter — %s" % (schedule, why),
            data={"reduce_scatter": rs, "platform": platform}))
    return findings


def audit_plan_fusion(symbol):
    """The ``plan-fusion-parity`` rule: run the mxfuse pipeline over
    ``symbol``'s node plan (under the CURRENT ``MXTPU_FUSED_KERNELS``)
    and verify every override kept the plain-plan monitored contract.

    Checks (docs/how_to/performance.md "The plan optimizer"):

    1. the pipeline neither raises nor mutates the plain plan — the
       monitored path interprets that exact object;
    2. the rewritten plan is a PERMUTATION of the plain entries (none
       added or dropped) with byte-identical slots 0-4 — per-node RNG
       fold constants and monitor coordinates ride IN the entries, so
       identity must hold while interpretation order may be re-sorted
       — and the order is topologically valid for the post-override
       dependency graph (op-node values exist before an entry reads
       them; variables bind lazily);
    3. every override is ``(callable, [(plan-node, int)], dead-ins)``
       and no extra ref reads a value-rewriting passthrough (its env
       value is not that node's output);
    4. inference-trace pruning (``live_entries``) keeps every graph
       output and every extra-ref producer interpretable.

    Returns a :class:`Report`; violations are rule
    ``plan-fusion-parity``.
    """
    import copy

    from .. import mxfuse
    from ..executor import _node_plan

    rep = Report(tool="mxlint.graph")

    def flag(msg):
        rep.add("plan-fusion-parity", msg)

    plan = _node_plan(symbol)
    out_refs = [(id(n), i) for n, i in symbol._outputs]
    before = [(id(e[0]),) + tuple(copy.deepcopy(e[1:5])) for e in plan]
    try:
        fused = mxfuse.optimize_plan(plan, out_refs)
    except Exception as e:  # noqa: BLE001 — a broken pass IS the finding
        flag("pass pipeline raised %s: %s" % (type(e).__name__, e))
        return rep
    after = [(id(e[0]),) + tuple(e[1:5]) for e in plan]
    if before != after:
        flag("pass pipeline MUTATED the plain plan — monitored runs "
             "interpret that object verbatim")
    if fused is plan:
        rep.stats["plan_fusion"] = {"overrides": 0,
                                    "entries": len(plan)}
        return rep
    if len(fused) != len(plan):
        flag("rewritten plan has %d entries, plain plan %d — passes "
             "must never add or drop entries (per-node RNG fold "
             "constants travel with them)" % (len(fused), len(plan)))
        return rep
    plain_of = {id(e[0]): e for e in plan}
    if {id(e[0]) for e in fused} != set(plain_of):
        flag("rewritten plan is not a permutation of the plain "
             "entries — nodes were substituted")
        return rep
    n_overrides = 0
    seen = set()
    for fe in fused:
        pe = plain_of[id(fe[0])]
        if tuple(fe[1:5]) != tuple(pe[1:5]):
            flag("entry %r changed outside the override slot"
                 % fe[0].name)
        ov = fe[5]
        if ov is None:
            continue
        n_overrides += 1
        if not callable(ov[0]) or not isinstance(ov[1], (list, tuple)):
            flag("override at %r is not (callable, refs, ...)"
                 % fe[0].name)
            continue
        for ref in ov[1]:
            if id(ref[0]) not in plain_of:
                flag("override at %r references a node outside the "
                     "plan" % fe[0].name)
    # interpretation-order validity: an entry's op-node dependencies
    # (inputs + override extra refs) must already be interpreted when
    # it runs; variables bind lazily
    for fe in fused:
        node, ov = fe[0], fe[5]
        refs = list(node.inputs or ())
        if ov is not None:
            refs += list(ov[1])
        for src, _idx in refs:
            if id(src) in plain_of and src.op is not None \
                    and id(src) not in seen:
                flag("entry %r runs before its dependency %r — the "
                     "rewritten order is not topologically valid"
                     % (node.name, src.name))
                return rep
        seen.add(id(node))
    live = mxfuse.live_entries(fused, out_refs)
    live_ids = {id(e[0]) for e in live}
    for nid, _i in out_refs:
        if nid not in live_ids:
            flag("inference-trace pruning dropped a graph output")
    for e in live:
        ov = e[5]
        if ov is None:
            continue
        for src, _idx in ov[1]:
            if id(src) not in live_ids and src.op is not None:
                flag("pruned eval plan drops op node %r that an "
                     "override's extra refs read" % src.name)
    rep.stats["plan_fusion"] = {"overrides": n_overrides,
                                "entries": len(plan),
                                "eval_live": len(live)}
    return rep


def lint_lowered(lowered, closed_jaxpr=None, compute_dtype=None,
                 param_bytes=None, expect_allgather=True,
                 schedule=None, expect_gather_bytes=None,
                 min_donate_bytes=1 << 20, carry_argnums=None,
                 compiled_text=None, platform=None):
    """Run every graph rule against one lowered step.

    ``lowered`` is a ``jax.stages.Lowered``;  ``closed_jaxpr`` enables
    the callback/dtype rules (pass ``jax.make_jaxpr(fn)(*args)``);
    ``compiled_text`` skips the internal ``lowered.compile()`` when the
    caller already has the executable.  Returns a :class:`Report` whose
    ``stats["collectives"]`` always carries the audit tally, even when
    nothing flags.
    """
    rep = Report(tool="mxlint.graph")
    rep.extend(audit_donation(lowered, min_bytes=min_donate_bytes,
                              carry_argnums=carry_argnums))
    if closed_jaxpr is not None:
        rep.extend(find_callbacks(closed_jaxpr))
        rep.extend(find_unprotected_pallas(closed_jaxpr))
        if compute_dtype is not None:
            findings, tally = audit_dtype(closed_jaxpr, compute_dtype)
            rep.extend(findings)
            rep.stats["compute_eqn_dtypes"] = tally
    if compiled_text is None:
        compiled = lowered.compile()
        compiled_text = compiled.as_text()
        mem = compiled.memory_analysis()
        if mem is not None:
            # XLA's own accounting of one execution, per device
            rep.stats["memory"] = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes)}
    stats = collective_stats(compiled_text)
    rep.stats["collectives"] = stats
    # which of the package's Pallas kernels the COMPILED program holds —
    # the tier a step took is read off the program (chip_smoke.py)
    from ..kernels import compiled_kernels
    rep.stats["pallas_kernels"] = compiled_kernels(compiled_text)
    rep.extend(audit_collectives(stats, param_bytes=param_bytes,
                                 expect_allgather=expect_allgather))
    rep.extend(audit_collective_schedule(
        stats, schedule, expect_gather_bytes, platform=platform))
    if schedule:
        rep.stats["schedule"] = {
            "declared": schedule,
            "expect_gather_bytes": int(expect_gather_bytes or 0),
            "platform": platform}
    return rep


def lint_jit(fn, *args, donate_argnums=(), compute_dtype=None,
             param_bytes=None, expect_allgather=True,
             min_donate_bytes=1 << 20, **kwargs):
    """Convenience wrapper: jit + lower + trace ``fn`` and lint it.

    ``fn`` may already be jitted (then ``donate_argnums`` is ignored —
    the jit's own settings win).  Example::

        report = lint_jit(step, params, batch, donate_argnums=(0,),
                          expect_allgather=False)
        assert report.ok, report.format_text()
    """
    import jax
    jf = fn if hasattr(fn, "lower") else \
        jax.jit(fn, donate_argnums=donate_argnums)
    lowered = jf.lower(*args, **kwargs)
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return lint_lowered(lowered, closed_jaxpr=closed,
                        compute_dtype=compute_dtype,
                        param_bytes=param_bytes,
                        expect_allgather=expect_allgather,
                        min_donate_bytes=min_donate_bytes)
