"""mxfuse — the plan-level graph optimizer (ROADMAP item 5).

The executor's node plan (:func:`executor._node_plan`) is a topological
list of ``(node, call_attrs, n_out, aux_var_names, rng_ix, override)``
entries — exactly the dataflow IR a TASO/XLA-style rewrite pipeline
needs.  This module grows the one-off conv→BN→act rewrite
(``_fuse_bn_plan``, PR 8) into a reusable **match-and-rewrite
framework** plus a pipeline of composable passes, all behind the same
``MXTPU_FUSED_KERNELS`` routing the kernel catalog uses
(docs/how_to/performance.md "The plan optimizer").

The ONE invariant every pass must keep (the ``plan-fusion-parity``
lint, :func:`analysis.graph_lint.audit_plan_fusion`): **entries are
never added, removed or reordered** — a rewrite only fills the
``override`` slot.  Node positions are the per-node RNG fold constants
(seeded Dropout masks) and the coordinates monitored runs tap, so the
plain plan must stay interpretable unchanged; ``MXTPU_FUSED_KERNELS=0``
(or per-pass opt-out) restores the exact pre-fusion program.

An override is ``(fn, extra_refs, eval_dead_ins)``:

- ``fn`` replaces the node's op; the interpreter appends the values of
  ``extra_refs`` (``(src_node, idx)`` pairs) to the node's own inputs.
- ``eval_dead_ins`` names input POSITIONS the override ignores on the
  inference path — what the ``infer_trace`` dead-node elimination
  (:func:`live_entries`) uses to drop dead producers (e.g. the original
  conv under a BN fold) from the eval trace instead of tracing them
  for XLA to DCE.

A **passthrough** override (identity on input 0) marks a node whose
work was absorbed by another override.  Its env value may be
semantically WRONG (an elementwise-chain intermediate carries the
chain INPUT, not its own output), so the framework enforces — and the
lint re-checks — that no extra_ref ever reads a passthrough entry.

Pass pipeline (first match wins; order is the documented priority):

1. ``concat_fuse`` — sibling conv→BN(→act) tower heads sharing one
   input and one geometry (inception's 1x1 branches) merge into ONE
   conv over concatenated filters (+ merged BN / fold), each member
   slicing its channel range; XLA CSE dedups the shared body.
2. ``pool_act`` — act→max-pool reorders to pool-first (monotone
   activations commute with max BITWISE; the activation then touches
   stride²-fewer elements), and pool→act pairs collapse to one entry.
3. ``bn_act`` / ``bn_fold`` — the PR-8 BN+activation fusion and
   inference conv-BN folding, now a pass like any other.
4. ``eltwise_chain`` — runs of private elementwise ops collapse into
   one override at the chain tail (one dispatch instead of N on the
   eager/unjittable paths; bit-identical under whole-graph jit).

``infer_trace`` (dead-node elimination + bind-time constant folding
for the inference trace) is not a rewrite pass: it runs after the
pipeline in ``_build_eval`` and only SKIPS entries, never changes one.
"""
from __future__ import annotations

from .base import MXNetError

__all__ = ["PlanView", "optimize_plan", "live_entries", "fold_constants",
           "PASSES", "MONOTONE_ACTS", "FUSABLE_ACTS"]

#: activation types the BN+activation fusion accepts (the fused kernel's
#: lax tier covers every registered act_type; the Pallas tier narrows
#: further internally and falls back to lax for the rest)
FUSABLE_ACTS = ("relu", "sigmoid", "tanh", "softrelu", "softsign")

#: monotone NON-DECREASING activations — exactly the set that commutes
#: with max-pooling bitwise (``f(max(a,b)) == max(f(a), f(b))``: the
#: pooled maximum is one of the inputs, and a non-decreasing f keeps
#: the argmax).  Every registered Activation type qualifies.
MONOTONE_ACTS = frozenset(FUSABLE_ACTS)


class PlanView(object):
    """Mutable match-and-rewrite view over one node plan.

    Passes query structure (consumers, outputs, claims) and record
    overrides; :meth:`apply` emits the rewritten plan with every entry
    at its original position (slot 5 is the only slot that changes).
    """

    def __init__(self, plan, out_refs):
        self.plan = plan
        self.entry_of = {id(e[0]): e for e in plan}
        self.consumers = {}      # (id(src), idx) -> [(consumer, pos)]
        for e in plan:
            node = e[0]
            if node.op is None:
                continue
            for pos, (src, idx) in enumerate(node.inputs):
                self.consumers.setdefault((id(src), idx), []) \
                    .append((node, pos))
        self.out_ids = {(nid, i) for nid, i in out_refs}
        self.pos = {id(e[0]): i for i, e in enumerate(plan)}
        self.overrides = {}      # id(node) -> (fn, extras, eval_dead)
        self.passthroughs = set()
        #: passthroughs whose env value is NOT the node's true output
        #: (an eltwise-chain intermediate forwards the chain INPUT);
        #: readers of these must all be overrides that know it
        self.wrong_valued = set()
        self.extra_targets = set()

    # -- queries -----------------------------------------------------------
    def users(self, node, idx=0):
        return self.consumers.get((id(node), idx), [])

    def is_output(self, node, idx=0):
        return (id(node), idx) in self.out_ids

    def claimed(self, node):
        return id(node) in self.overrides

    def sole_user(self, node, idx=0):
        """The one (consumer, pos) reading this output — or None when
        it has several readers or is a graph output (a rewrite that
        absorbs the node would then change observable values)."""
        if self.is_output(node, idx):
            return None
        users = self.users(node, idx)
        return users[0] if len(users) == 1 else None

    # -- rewrites ----------------------------------------------------------
    def override(self, node, fn, extra_refs=(), eval_dead_ins=()):
        if id(node) in self.overrides:
            raise MXNetError("mxfuse: node %r rewritten twice" % node.name)
        self.overrides[id(node)] = (fn, list(extra_refs),
                                    frozenset(eval_dead_ins))
        self.extra_targets.update(id(src) for src, _ in extra_refs)

    def passthrough(self, node, value_preserving=False):
        """Mark ``node`` as absorbed: its entry becomes identity on
        input 0.  ``value_preserving=True`` says the forwarded value IS
        the node's true output (a bn_act Activation forwards the fused
        post-activation value); otherwise every reader must be an
        override that was rewritten to not depend on the node's value
        (enforced at :meth:`apply`)."""
        self.override(node, _identity, ())
        self.passthroughs.add(id(node))
        if not value_preserving:
            if id(node) in self.extra_targets:
                raise MXNetError(
                    "mxfuse: node %r is read by an override's extra "
                    "refs and cannot become a value-rewriting "
                    "passthrough" % node.name)
            self.wrong_valued.add(id(node))

    def locked(self, node):
        """Is this node pinned by an existing override's extra refs
        (so a pass must not turn it into a value-rewriting
        passthrough)?"""
        return id(node) in self.extra_targets

    def apply(self):
        """The rewritten plan (the ORIGINAL list object when no pass
        matched — callers key "untouched" off identity).

        Overrides may reference values produced LATER in symbol order
        (a merged sibling group reads every member's input), so the
        rewritten plan is re-sorted into a stable topological order of
        the POST-override dependency graph.  Entries are never added,
        dropped or changed beyond slot 5 — and each entry carries its
        own RNG fold constant (slot 4), so the per-node numbering the
        seeded-RNG and monitor contracts rely on is independent of
        interpretation order (monitored runs interpret the untouched
        plain plan anyway)."""
        if not self.overrides:
            return self.plan
        for nid, (fn, extras, _dead) in self.overrides.items():
            for src, _idx in extras:
                if id(src) in self.wrong_valued:
                    raise MXNetError(
                        "mxfuse: override extra ref reads passthrough "
                        "node %r — its env value is not the node's "
                        "output" % src.name)
        for nid in self.wrong_valued:
            node = self.entry_of[nid][0]
            for i in range(self.entry_of[nid][2] or 1):
                for user, _pos in self.users(node, i):
                    if id(user) not in self.overrides:
                        raise MXNetError(
                            "mxfuse: plain node %r reads rewritten "
                            "passthrough %r" % (user.name, node.name))
        entries = [e if id(e[0]) not in self.overrides
                   else e[:5] + (self.overrides[id(e[0])],)
                   for e in self.plan]
        return _topo_sort(entries)


def _topo_sort(entries):
    """Stable topological re-sort of a rewritten plan: dependency =
    the node's own inputs plus its override's extra refs.  When the
    original order is already valid (the common case) this returns it
    verbatim; a dependency cycle (a pass merged two mutually dependent
    stacks) raises rather than producing an uninterpretable plan."""
    import heapq
    index = {id(e[0]): i for i, e in enumerate(entries)}
    deps = [set() for _ in entries]
    rdeps = [[] for _ in entries]
    for i, e in enumerate(entries):
        node, override = e[0], e[5]
        refs = list(node.inputs or ())
        if override is not None:
            refs += list(override[1])
        for src, _idx in refs:
            j = index.get(id(src))
            if j is not None and j != i:
                deps[i].add(j)
    for i, dd in enumerate(deps):
        for j in dd:
            rdeps[j].append(i)
    ready = [i for i, dd in enumerate(deps) if not dd]
    heapq.heapify(ready)
    order = []
    remaining = [len(dd) for dd in deps]
    while ready:
        i = heapq.heappop(ready)
        order.append(i)
        for k in rdeps[i]:
            remaining[k] -= 1
            if remaining[k] == 0:
                heapq.heappush(ready, k)
    if len(order) != len(entries):
        raise MXNetError("mxfuse: rewritten plan has a dependency "
                         "cycle — a pass merged mutually dependent "
                         "nodes")
    if order == list(range(len(entries))):
        return entries
    return [entries[i] for i in order]


def _identity(*vals, **_kw):
    return vals[0]


# ---------------------------------------------------------------------------
# pass 1: concat_fuse — merge sibling conv→BN(→act) tower heads
# ---------------------------------------------------------------------------

def _conv_geometry(attrs):
    """The merge key: everything about a Convolution EXCEPT how many
    filters it has.  Two convs sharing input + geometry compute slices
    of one wider conv."""
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, tuple))
                         else v)
                        for k, v in attrs.items() if k != "num_filter"))


def _bn_sig(attrs):
    return tuple(sorted((k, v) for k, v in attrs.items()
                        if k != "output_mean_var"))


def _collect_conv_bn_stacks(view):
    """Every unclaimed private conv→BN(→act) stack in the plan, as
    ``(conv, conv_entry, bn, bn_entry, act_node, act_type)``."""
    stacks = []
    for e in view.plan:
        conv = e[0]
        if conv.op is None or conv.op.name != "Convolution" \
                or e[2] != 1 or view.claimed(conv):
            continue
        conv_attrs = e[1] or {}
        if int(conv_attrs.get("num_group", 1)) != 1 \
                or "num_filter" not in conv_attrs:
            continue
        user = view.sole_user(conv)
        if user is None:
            continue
        bn, pos = user
        if bn.op is None or bn.op.name != "BatchNorm" or pos != 0 \
                or view.claimed(bn) or view.is_output(bn):
            continue
        bn_entry = view.entry_of[id(bn)]
        if bn_entry[2] != 1 or len(bn.inputs) != 5 \
                or len(bn_entry[3] or ()) != 2 \
                or None in (bn_entry[3] or ()):
            continue
        # an optional private Activation to bake into the merged body
        act_node, act_type = None, None
        act_user = view.sole_user(bn)
        if act_user is not None:
            u, upos = act_user
            if u.op is not None and u.op.name == "Activation" \
                    and upos == 0 and len(u.inputs) == 1 \
                    and not view.claimed(u):
                at = str((view.entry_of[id(u)][1] or {})
                         .get("act_type", "relu"))
                if at in FUSABLE_ACTS:
                    act_node, act_type = u, at
        stacks.append((conv, e, bn, bn_entry, act_node, act_type))
    return stacks


def _rewrite_group(view, members, grouped, do_fold):
    """Install the merged-body overrides for one sibling group.

    ``grouped=False``: every member shares ONE input — merge into one
    wider conv (concatenated filters).  ``grouped=True``: inputs
    differ — channel-concatenate them and merge as a grouped conv
    (``num_group=len(members)``), which is BITWISE the per-member
    convs; requires equal ``num_filter`` (enforced by the caller's
    group key) and equal input channels (checked at trace time by the
    override, which falls back to the member's own conv otherwise).
    """
    from .kernels import concat_fuse as CF
    acts = {m[5] for m in members}
    bake_act = acts.pop() if len(acts) == 1 else None
    widths = [int(m[1][1]["num_filter"]) for m in members]
    offsets = [0]
    for w in widths:
        offsets.append(offsets[-1] + w)
    has_bias = not bool(members[0][1][1].get("no_bias", False))
    if grouped:
        refs = [m[0].inputs[0] for m in members]
    else:
        refs = [members[0][0].inputs[0]]
    for conv, _e, bn, _bne, _a, _t in members:
        refs.extend(conv.inputs[1:])      # weight (+ bias)
        refs.extend(bn.inputs[1:])        # gamma, beta, mm, mv
    conv_attrs = dict(members[0][1][1])
    for ix, (conv, _e, bn, _bne, act_node, _t) in enumerate(members):
        fn = CF.make_group_member(
            ix, len(members), conv_attrs, bake_act, offsets,
            has_bias, do_fold, grouped=grouped)
        # the override consumes ONLY the extra refs: the original
        # per-branch conv (input 0) and the per-member BN vectors
        # (inputs 1-4, re-read through extras) go dead on the eval
        # trace
        view.override(bn, fn, refs,
                      eval_dead_ins=range(len(bn.inputs)))
        if bake_act is not None and act_node is not None:
            # the forwarded value IS the true post-activation slice
            view.passthrough(act_node, value_preserving=True)


def _ancestors_of(start_refs):
    """Transitive producer set (node ids) above ``start_refs``."""
    out = set()
    stack = [src for src, _idx in start_refs]
    while stack:
        node = stack.pop()
        nid = id(node)
        if nid in out:
            continue
        out.add(nid)
        stack.extend(src for src, _idx in (node.inputs or ()))
    return out


def pass_concat_fuse(view):
    """Merge sibling conv→BN(→act) tower heads (inception's parallel
    branches) so the machine runs ONE wide GEMM instead of N narrow
    ones — each member's override computes the shared merged body and
    slices its channel range (XLA CSE collapses the per-member copies
    into one).  Two shapes:

    - **shared input** (the 1x1 branch + reduce layers over one
      tensor): one conv over concatenated filters.
    - **sibling inputs** (the parallel 3x3 convs, whose inputs are
      different tensors — often adjacent slices of an already-merged
      body): channel-concatenate the inputs and merge as a GROUPED
      conv (``feature_group_count`` = member count), bitwise the
      per-member math.  Members must be dependency-independent (one's
      input must not derive from another's output) — checked here;
      the rewritten plan is topologically re-sorted at apply().

    Per-member aux updates (moving stats) are slices of the merged
    statistics — BN stats are per-channel, so the merged math is the
    member math up to conv reassociation (the documented tolerance).
    """
    from .kernels import fused_enabled
    do_fold = fused_enabled("bn_fold")
    stacks = _collect_conv_bn_stacks(view)

    # phase 1: shared-input groups (no width constraint)
    shared = {}
    for s in stacks:
        conv, e = s[0], s[1]
        src, idx = conv.inputs[0]
        key = ((id(src), idx), _conv_geometry(e[1]),
               _bn_sig(s[3][1] or {}),
               bool(e[1].get("no_bias", False)), s[5])
        shared.setdefault(key, []).append(s)
    merged_ids = set()
    for key, members in shared.items():
        if len(members) >= 2:
            _rewrite_group(view, members, grouped=False, do_fold=do_fold)
            merged_ids.update(id(m[0]) for m in members)

    # phase 2: equal-width sibling groups with DIFFERENT inputs ->
    # grouped conv (num_filter joins the key: grouped outputs must
    # split evenly across members)
    siblings = {}
    for s in stacks:
        if id(s[0]) in merged_ids:
            continue
        e = s[1]
        key = (_conv_geometry(e[1]), int(e[1]["num_filter"]),
               _bn_sig(s[3][1] or {}),
               bool(e[1].get("no_bias", False)), s[5])
        siblings.setdefault(key, []).append(s)
    for key, cands in siblings.items():
        if len(cands) < 2:
            continue
        # greedy independence partition: a member may not (transitively)
        # feed another member's input
        groups = []
        for s in cands:
            own = {id(s[0]), id(s[2])} | \
                ({id(s[4])} if s[4] is not None else set())
            anc = _ancestors_of([s[0].inputs[0]])
            placed = False
            for g in groups:
                # s's input must not derive from any group member's
                # stack, and no member's input from s's stack
                if any(nid in anc for _s in g for nid in _s[6]) or \
                        any(nid in _s[7] for _s in g for nid in own):
                    continue
                g.append(s + (own, anc))
                placed = True
                break
            if not placed:
                groups.append([s + (own, anc)])
        for g in groups:
            if len(g) >= 2:
                _rewrite_group(view, [m[:6] for m in g], grouped=True,
                               do_fold=do_fold)


# ---------------------------------------------------------------------------
# pass 2: pool_act — act→max-pool reorder and pool→act collapse
# ---------------------------------------------------------------------------

def pass_pool_act(view):
    """Three shapes (docs/how_to/kernels.md):

    - ``act → Pooling(max)``: reorder to pool-first.  Monotone
      non-decreasing activations commute with max BITWISE, and the
      activation then runs on the pooled (stride²-smaller) tensor —
      the real win (inception/resnet stems: relu on 112² vs 56²).
      Restricted to the default ``valid`` pooling convention: ``full``
      can manufacture all-padding windows where -inf padding and the
      activation no longer commute.
    - ``Pooling → act``: collapse to one entry at the act node (same
      composition, one dispatch on the eager paths; bit-identical).
    - every remaining Pooling entry routes through the shifted-slice
      lowering (:func:`kernels.pool_act.pooling_opt`) — same math,
      vectorized instead of ``reduce_window``'s scalar window walk;
      trace-time shape gates decide per site.
    """
    from .kernels import pool_act as PA
    for e in view.plan:
        node = e[0]
        if node.op is None or view.claimed(node):
            continue
        if node.op.name == "Activation" and e[2] == 1 \
                and len(node.inputs) == 1:
            act_type = str((e[1] or {}).get("act_type", "relu"))
            if act_type not in MONOTONE_ACTS:
                continue
            user = view.sole_user(node)
            if user is None:
                continue
            pool, pos = user
            if pool.op is None or pool.op.name != "Pooling" or pos != 0 \
                    or view.claimed(pool) or len(pool.inputs) != 1:
                continue
            pool_entry = view.entry_of[id(pool)]
            pool_attrs = pool_entry[1] or {}
            if str(pool_attrs.get("pool_type", "max")) != "max" \
                    or str(pool_attrs.get("pooling_convention",
                                          "valid")) != "valid" \
                    or view.locked(node):
                continue
            view.passthrough(node)
            view.override(pool, PA.make_act_then_maxpool(act_type))
        elif node.op.name == "Pooling" and e[2] == 1 \
                and len(node.inputs) == 1:
            user = view.sole_user(node)
            if user is None:
                continue
            act, pos = user
            if act.op is None or act.op.name != "Activation" \
                    or pos != 0 or view.claimed(act) \
                    or len(act.inputs) != 1 or view.locked(node):
                continue
            view.passthrough(node)
            view.override(act, PA.make_pool_then_act(dict(e[1] or {})))
    # remaining standalone Pooling entries: routed lowering only
    for e in view.plan:
        node = e[0]
        if node.op is None or node.op.name != "Pooling" \
                or e[2] != 1 or view.claimed(node) \
                or len(node.inputs) != 1:
            continue
        view.override(node, PA.make_pool_opt())


# ---------------------------------------------------------------------------
# pass 3: bn_act / bn_fold — the PR-8 BatchNorm fusions as a pass
# ---------------------------------------------------------------------------

def _make_fused_bn_fn(act_type, conv_attrs):
    """The override body for one fused BatchNorm site.

    Training: fused normalize+scale/shift+activate in one kernel pass
    (kernels/bn_act.py; Pallas on TPU, fused-lax elsewhere — bit-equal
    to the unfused graph on the lax tier).  Inference with a private
    Conv producer: BN folds into the conv weights and the original conv
    result goes dead (pruned from the eval trace by ``infer_trace``,
    DCE'd by XLA otherwise); parity is tolerance-bound there (float
    reassociation), the documented exception in tests/test_kernels.py.
    """
    def fused(data, gamma, beta, moving_mean, moving_var, *conv_ins,
              is_train=False, **bn_attrs):
        from .kernels import bn_act as _ba
        bn_attrs.pop("output_mean_var", None)   # fusion requires False
        if conv_ins and not is_train:
            cdata, w = conv_ins[0], conv_ins[1]
            cbias = conv_ins[2] if len(conv_ins) > 2 else None
            from .ops.nn import activation, convolution
            w2, b2 = _ba.fold_bn_into_conv(
                w, cbias, gamma, beta, moving_mean, moving_var,
                eps=bn_attrs.get("eps", 0.001),
                fix_gamma=bn_attrs.get("fix_gamma", True))
            out = convolution(cdata, w2, b2,
                              **{k: v for k, v in conv_attrs.items()
                                 if k != "no_bias"})
            if act_type:
                out = activation(out, act_type=act_type)
            return out, moving_mean, moving_var
        return _ba.fused_bn_act(data, gamma, beta, moving_mean,
                                moving_var, act_type=act_type,
                                is_train=is_train, **bn_attrs)
    return fused


def pass_bn(view):
    """The BatchNorm fusions (``bn_act``/``bn_fold``):

    - a BatchNorm whose single consumer is an Activation gets the fused
      one-pass kernel; the Activation entry becomes a passthrough.
    - a BatchNorm whose data producer is a private Convolution
      additionally folds into the conv weights on the inference trace.

    Aux updates are untouched: the overridden entry still returns
    ``(out, new_mm, new_mv)`` at the BatchNorm node, where the executor
    already writes them back.
    """
    from .kernels import fused_enabled
    do_act = fused_enabled("bn_act")
    do_fold = fused_enabled("bn_fold")
    for e in view.plan:
        node, call_attrs, n_out = e[0], e[1], e[2]
        if node.op is None or node.op.name != "BatchNorm" \
                or n_out != 1 or view.claimed(node):
            continue
        act_node, act_type = None, None
        if do_act:
            user = view.sole_user(node)
            if user is not None:
                u, pos = user
                if u.op is not None and u.op.name == "Activation" \
                        and pos == 0 and len(u.inputs) == 1 \
                        and not view.claimed(u):
                    at = str((view.entry_of[id(u)][1] or {})
                             .get("act_type", "relu"))
                    if at in FUSABLE_ACTS:
                        act_node, act_type = u, at
        conv_node = None
        if do_fold and node.inputs:
            src, idx = node.inputs[0]
            if src.op is not None and src.op.name == "Convolution" \
                    and idx == 0 and not view.claimed(src) \
                    and view.sole_user(src) is not None:
                conv_node = src
        if act_node is None and conv_node is None:
            continue
        conv_attrs = dict(view.entry_of[id(conv_node)][1]) if conv_node \
            else {}
        extra = list(conv_node.inputs) if conv_node is not None else []
        view.override(node, _make_fused_bn_fn(act_type, conv_attrs),
                      extra,
                      # the fold path ignores the conv result at eval
                      eval_dead_ins=(0,) if conv_node is not None else ())
        if act_node is not None:
            # the BN override bakes the activation in, so the act entry
            # forwards the TRUE post-activation value — downstream plain
            # nodes (and later folds' extra refs) may read it
            view.passthrough(act_node, value_preserving=True)


# ---------------------------------------------------------------------------
# pass 4: eltwise_chain — collapse private elementwise runs
# ---------------------------------------------------------------------------

def pass_eltwise_chain(view):
    """Maximal runs of ≥2 private elementwise ops (the catalog in
    :data:`kernels.eltwise_chain.ELTWISE_OPS`) linked through input 0
    collapse into ONE override at the chain tail; intermediates become
    passthroughs.  Side inputs (the other operand of a binary op) ride
    as extra refs.  The composed function applies the identical op
    sequence, so the whole-graph jit program is bit-identical — the win
    is dispatch count on the eager/no-jit paths and one compiled region
    instead of N at dispatch granularity."""
    from .kernels import eltwise_chain as EC

    def chainable(node):
        if node.op is None or node.op.name not in EC.ELTWISE_OPS:
            return False
        e = view.entry_of[id(node)]
        if e[2] != 1 or e[3]:
            return False
        op = node.op
        return not (op.needs_rng or op.needs_is_train
                    or getattr(op, "no_jit", False)) \
            and not view.claimed(node) and not view.locked(node)

    in_chain = set()
    for e in view.plan:
        head = e[0]
        if id(head) in in_chain or not chainable(head):
            continue
        # only start at a true head: the producer of input 0 must not
        # itself extend the chain backwards
        src0 = head.inputs[0][0] if head.inputs else None
        if src0 is not None and chainable(src0) \
                and id(src0) not in in_chain \
                and view.sole_user(src0) == (head, 0):
            continue
        chain = [head]
        while True:
            user = view.sole_user(chain[-1])
            if user is None:
                break
            nxt, pos = user
            if pos != 0 or not chainable(nxt) or id(nxt) in in_chain:
                break
            chain.append(nxt)
        if len(chain) < 2:
            continue
        in_chain.update(id(n) for n in chain)
        stages = []
        extra_refs = []
        for n in chain:
            ne = view.entry_of[id(n)]
            stages.append((n.op.fn, dict(ne[1] or {}),
                           len(n.inputs) - 1))
            if n is not chain[-1]:
                extra_refs.extend(n.inputs[1:])
        tail = chain[-1]
        fn = EC.make_chain_fn(stages)
        view.override(tail, fn, extra_refs)
        for n in chain[:-1]:
            view.passthrough(n)


#: the pipeline, in priority order; each entry is (enabling kernel
#: names, pass fn) — a pass runs when ANY of its names is enabled
PASSES = (
    (frozenset(("concat_fuse",)), pass_concat_fuse),
    (frozenset(("pool_act",)), pass_pool_act),
    (frozenset(("bn_act", "bn_fold")), pass_bn),
    (frozenset(("eltwise_chain",)), pass_eltwise_chain),
)


def optimize_plan(plan, out_refs):
    """Run every enabled pass over ``plan`` and return the rewritten
    plan — or ``plan`` itself (same object) when nothing matched or
    nothing is enabled, so ``MXTPU_FUSED_KERNELS=0`` restores the
    exact pre-fusion program."""
    from .kernels import enabled_kernels
    enabled = enabled_kernels()
    active = [fn for names, fn in PASSES if names & enabled]
    if not active:
        return plan
    view = PlanView(plan, out_refs)
    for fn in active:
        fn(view)
    return view.apply()


# ---------------------------------------------------------------------------
# infer_trace: dead-node elimination + constant folding for eval traces
# ---------------------------------------------------------------------------

def live_entries(plan, out_refs):
    """The subset of ``plan`` reachable from the graph outputs on the
    INFERENCE path (override ``eval_dead_ins`` edges excluded, extra
    refs included).  Entries keep their order and contents — dead ones
    are simply not interpreted, so the eval trace skips e.g. the
    original convs a BN fold replaced instead of tracing them for XLA
    to DCE (measured as ``roofline_infer_trace_x``)."""
    entry_of = {id(e[0]): e for e in plan}
    live = set()
    stack = [nid for nid, _i in out_refs]
    while stack:
        nid = stack.pop()
        if nid in live or nid not in entry_of:
            continue
        live.add(nid)
        e = entry_of[nid]
        node, override = e[0], e[5]
        dead = override[2] if override is not None \
            and len(override) > 2 else frozenset()
        for pos, (src, _idx) in enumerate(node.inputs or ()):
            if pos not in dead:
                stack.append(id(src))
        if override is not None:
            for src, _idx in override[1]:
                stack.append(id(src))
    return [e for e in plan if id(e[0]) in live]


def fold_constants(entries):
    """Bind-time constant folding over an (already pruned) entry list:
    deterministic ops whose transitive inputs are all themselves
    foldable — seeded by zero-input generator ops — are evaluated ONCE
    here and served from a constant env, so every bucket trace (and
    recompile) starts past them.  Returns ``(const_env, remaining)``.
    Ops with RNG, train-mode branches, aux updates or host callbacks
    never fold."""
    const_env = {}
    remaining = []
    for e in entries:
        node, call_attrs, n_out, aux_names, _rng_ix, override = e
        op = node.op
        if op is None:
            remaining.append(e)
            continue
        if override is not None or aux_names or op.needs_rng \
                or op.needs_is_train or getattr(op, "no_jit", False):
            remaining.append(e)
            continue
        if node.inputs and not all(id(src) in const_env
                                   for src, _ in node.inputs):
            remaining.append(e)
            continue
        if not node.inputs and not getattr(op, "variable_inputs", False) \
                and len(op.get_input_names(call_attrs or {})) > 0:
            # an op that EXPECTS inputs but the node has none recorded —
            # malformed; leave it to fail loudly at run time
            remaining.append(e)
            continue
        try:
            ins = [const_env[id(src)][idx] for src, idx in node.inputs]
            out = op.fn(*ins, **(call_attrs or {}))
        except Exception:  # noqa: BLE001 — fold is best-effort
            remaining.append(e)
            continue
        if not isinstance(out, (tuple, list)):
            out = (out,)
        const_env[id(node)] = tuple(out[:n_out])
    # only the values that survive as inputs of live entries (or were
    # folded outputs) matter; keeping all folded values is harmless
    return const_env, remaining
