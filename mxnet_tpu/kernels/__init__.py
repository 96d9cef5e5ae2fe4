"""mxkern — fused Pallas/lax kernels for the graphs XLA leaves on the table.

BatchNorm/concat-heavy (inception-bn) and gate-heavy (LSTM) graphs spend
their time in memory-bound elementwise chains that benefit from being ONE
kernel pass instead of a dispatch-granularity composition (whether each
kernel wins on the chip is not measured yet — PERF.md).  Following the
FlashAttention discipline (Dao et al., 2022 — materialize nothing you can
recompute in-tile), every kernel here ships at two tiers:

- **Pallas tier** (TPU): a ``pl.pallas_call`` kernel with a registered
  ``jax.custom_vjp`` backward, per the :mod:`~mxnet_tpu.rtc` contract
  (Pallas has no reverse-mode transpose; an unprotected kernel in a
  differentiated step is a trace-time error — mxlint's
  ``graph-pallas-no-vjp`` rule polices this).  A program lowered for a
  TPU gets the compiled kernel or fails to compile; interpret mode
  exists only for tests that ask for it by argument.
- **fused-lax reference** (every other platform, and the numeric
  oracle): the same math as the unfused op composition, in one traced
  function, written so the per-element operation sequence is IDENTICAL
  to the unfused graph — bit-comparable where float reassociation
  permits (asserted in tests/test_kernels.py), and faster than the
  op-by-op composition because it compiles to one program instead of a
  dispatch chain.

Routing is per-kernel via ``MXTPU_FUSED_KERNELS`` (registered in
``base.py``): ``1`` (default) enables everything, ``0`` restores the
exact pre-fusion graphs, a comma list enables individual kernels.  The
env is consulted at trace/bind time (symbol build, executor bind, jit
trace), so toggling it affects the NEXT graph built, never a compiled
program (docs/how_to/kernels.md).

Kernel catalog (``KNOWN_KERNELS``):

- ``bn_act``   — fused BatchNorm+activation (training one-pass), wired
  into the executor's BN aux-update path (:mod:`.bn_act`).
- ``bn_fold``  — fold BN scale/shift into conv weights for inference
  (:func:`.bn_act.fold_bn_into_conv`; executor eval trace).
- ``lstm_cell`` — one-kernel LSTM gate math consumed by the fused RNN
  op's ``lax.scan`` and by ``rnn_cell.LSTMCell`` (:mod:`.lstm_cell`).
- ``flash_attention`` — tiled online-softmax attention that
  ``parallel/ring_attention.py`` composes with (:mod:`.flash_attention`).
- ``augment``   — in-graph image augmentation (resize/crop/mirror/
  normalize as traced ops, per-image RNG folded from the data
  service's ``chunk_seed``) so the input pipeline ships raw-decoded
  uint8 and augments on-device (:mod:`.augment`; consumed by
  ``ImageRecordIter(device_augment=...)``).
- ``concat_fuse`` — mxfuse plan pass: sibling conv→BN(→act) tower
  heads sharing one input merge into ONE conv over concatenated
  filters (inception's 1x1 branches; :mod:`.concat_fuse`).
- ``pool_act``  — mxfuse plan pass: act→max-pool reorders to
  pool-first (bitwise; the activation touches stride²-fewer elements)
  and pool→act pairs collapse to one entry (:mod:`.pool_act`).
- ``eltwise_chain`` — mxfuse plan pass: private elementwise runs
  collapse into one fused region (:mod:`.eltwise_chain`).
- ``infer_trace`` — inference-trace pass set: dead-node elimination +
  bind-time constant folding over the executor's EVAL interpretation
  (``mxnet_tpu.mxfuse.live_entries``/``fold_constants``) — composes
  with the ``bn_fold`` serving default; values are bit-identical, the
  win is trace/bind time per serving bucket.

Outside the registry, decided by platform, mesh and shapes alone (a
kernel that wins its cell is unconditional, ``ROADMAP.md`` Design 2):

- ``delta_rule`` — the chunked gated delta rule of ``GatedDeltaRule``
  (:mod:`.delta_rule`), two compiled rules.  A decay a head:
  ``mxtpu_delta_rule_fwd`` / ``mxtpu_delta_rule_bwd`` walk a row's chunks
  with the state in VMEM, one key head and the value heads it serves a
  grid step; q, k and v are read as the graph has them.  Compiled in a
  program lowered for a TPU when the head sizes are multiples of 128, the
  value heads a multiple of the key heads and the row whole chunks (of a
  multiple of 16); the lax tier otherwise and under
  :func:`auto_partitioned`.  A decay per key channel (Kimi Delta
  Attention): ``mxtpu_delta_rule_channel_fwd`` / ``_bwd``, two heads a
  grid step at chunks of 64, when besides there is one key head a value
  head and the chunk is 16, 32, 64 or 128 positions.  Each lowering
  records a ``kernel.route`` event (kernel, tier, reason, and ``decay`` =
  ``channel`` for the vector rule) in the program's recorder; the
  benchmark's ``gdn_kernel_share`` and ``kda_kernel_share`` read them.
  On the v5e at the benchmark's shapes (2 x 8,192 positions, chunk 64,
  heads of 128; forward / forward + backward): 16 key / 32 value heads
  under a scalar decay, lax tier 25.4 / 59.3 ms, kernels 9.6 / 17.9
  (PERF.md, PR 30); 32 heads under a vector decay, lax tier 32.4 / 109.9
  ms, kernels 16.0 / 41.1 (PERF.md, PR 32).
- ``gqa_attention`` — the causal grouped-query attention of ``GQAttention``
  (:mod:`.flash_attention`): ``mxtpu_gqa_attention_fwd`` /
  ``mxtpu_gqa_attention_bwd``, a key / value head and the query heads it
  serves a grid step on (keys x stacked query rows) tiles, the score tile,
  the running softmax and all three gradients' accumulators in VMEM, blocks
  above the diagonal neither fetched nor computed.  Compiled in a program
  lowered for a TPU when the positions are whole blocks of 128, the value
  heads whole lane tiles, the query / key heads whole lane tiles or padded
  to one by a third at most (192 -> 256) and a (row, key head)'s dk and dv
  fit VMEM (reason ``aligned``); the lax tier otherwise (``shapes``) and
  under :func:`auto_partitioned` (``mesh``).  Each lowering records a
  ``kernel.route`` event (kernel ``gqa_attention``); the benchmark's
  ``attn_kernel_share`` reads them.  On the v5e at the benchmark's shapes
  (2 x 8,192 positions, bfloat16; forward / forward + backward): 32 heads
  of 192 / 128, lax tier 45.9 / 149.5 ms, kernels 17.5 / 50.0; 16 query /
  2 key heads of 256, 22.3 / 49.9 and 8.9 / 27.7; 8 / 2 of 128, 8.9 / 22.2
  and 2.75 / 7.43 (PERF.md, PR 34).

- ``causal_conv`` — the depthwise causal convolution of ``CausalConv1D``
  (:mod:`.causal_conv`): ``mxtpu_causal_conv_fwd`` / ``mxtpu_causal_conv_bwd``,
  a (positions x channels) tile of one row a grid step with the 16 positions
  beside it as a second block of the same operand, widened in VMEM; taps,
  float32 sum and ``silu`` in the lax tier's order, ``dx`` the flipped
  convolution over the recomputed pre-activation, ``dw`` added up in a
  float32 block that never leaves VMEM; residuals the inputs.  Compiled in a
  program lowered for a TPU when the channels are whole lane tiles, the
  positions whole blocks of 32, the taps 2 to 8, ``act_type`` None or
  ``silu`` and the data bfloat16 or float32 (reason ``aligned``); the lax
  tier otherwise (``shapes``) and under :func:`auto_partitioned` (``mesh``).
  Each lowering records a ``kernel.route`` event (kernel ``causal_conv``);
  the benchmark's ``causal_conv_kernel_share`` reads them.  On the v5e at
  the benchmark's shapes (2 x 8,192 positions, bfloat16; forward / forward
  + backward): 4,096 channels at 4 taps with ``silu``, lax tier 2.31 / 7.55
  ms, kernels 0.50 / 1.40; 8,192 channels, 4.97 / 14.92 and 0.96 / 2.72;
  1,280 channels at 2 taps, plain, 0.24 / 0.80 and 0.22 / 0.48 (PERF.md,
  PR 36).

The plan-level passes live in :mod:`mxnet_tpu.mxfuse` (the
match-and-rewrite framework over the executor's node plan); this
registry routes them exactly like the kernel bodies.
"""
from __future__ import annotations

import contextlib
import logging
import threading

from ..base import ENV_FUSED_KERNELS, get_env, register_env

__all__ = ["KNOWN_KERNELS", "fused_enabled", "enabled_kernels",
           "by_platform", "auto_partitioned", "partitioned",
           "compiled_kernels",
           "ENV_FLASH_BLOCK", "bn_act",
           "lstm_cell", "flash_attention", "augment",
           "concat_fuse", "pool_act", "eltwise_chain"]

_LOG = logging.getLogger(__name__)

#: every kernel name the router understands (docs/how_to/kernels.md);
#: the last four are mxfuse plan-optimizer passes, routed identically
KNOWN_KERNELS = ("bn_act", "bn_fold", "lstm_cell", "flash_attention",
                 "augment", "concat_fuse", "pool_act", "eltwise_chain",
                 "infer_trace")

# registered EAGERLY at package import (a lazy registration inside the
# flash module failed the three-way registry==docs==reads sync for the
# data-service knobs — same lesson here)
ENV_FLASH_BLOCK = register_env(
    "MXTPU_FLASH_BLOCK", default=128,
    doc="Tile size (query and key block length) for the flash-attention "
        "kernel; sequences at or below one block use plain attention")

_ON = frozenset(("1", "on", "true", "yes", "all"))
_OFF = frozenset(("", "0", "off", "false", "no", "none"))

_warned_unknown = set()


def enabled_kernels():
    """The set of fused kernels the env currently enables.  Read per
    call — callers consult it at trace/bind time, so the cost is paid
    once per graph build, not per step."""
    raw = str(get_env(ENV_FUSED_KERNELS, "1")).strip().lower()
    if raw in _ON:
        return frozenset(KNOWN_KERNELS)
    if raw in _OFF:
        return frozenset()
    names = set()
    for part in raw.replace(";", ",").split(","):
        part = part.strip()
        if not part:
            continue
        if part in KNOWN_KERNELS:
            names.add(part)
        elif part not in _warned_unknown:
            _warned_unknown.add(part)
            _LOG.warning(
                "MXTPU_FUSED_KERNELS names unknown kernel %r "
                "(known: %s) — ignored", part, ", ".join(KNOWN_KERNELS))
    return frozenset(names)


def fused_enabled(name):
    """Whether the named fused kernel should be used for graphs built
    NOW (``MXTPU_FUSED_KERNELS``; see module docstring for the catalog)."""
    return name in enabled_kernels()


_auto_partitioned = threading.local()


@contextlib.contextmanager
def auto_partitioned():
    """Mark what is traced inside as part of a program the SPMD
    partitioner will split over several devices (``SPMDTrainer``'s GSPMD
    tiers on a multi-device mesh).  jax cannot partition a Mosaic kernel
    automatically and refuses to lower one there, so :func:`by_platform`
    keeps such programs on the fused-lax tier; single-device programs and
    ``shard_map`` bodies (the zero3 manual tier) take the compiled one."""
    prev = getattr(_auto_partitioned, "on", False)
    _auto_partitioned.on = True
    try:
        yield
    finally:
        _auto_partitioned.on = prev


def partitioned():
    """Whether the trace is inside :func:`auto_partitioned`."""
    return getattr(_auto_partitioned, "on", False)


def by_platform(pallas_fn, lax_fn, *args):
    """Tier selection by the platform the computation is LOWERED for
    (``lax.platform_dependent``): the compiled Pallas kernel in a TPU
    program, the fused-lax reference anywhere else — so a CPU-placed
    program on a TPU host takes the lax tier and a TPU program never
    quietly drops to it.  The one exception is decided by the caller's
    placement, not by failure: see :func:`auto_partitioned`.  The Pallas
    outputs are cast to the lax tier's dtypes: both branches must present
    one signature."""
    import jax
    if partitioned():
        return lax_fn(*args)
    want = jax.eval_shape(lax_fn, *args)

    def tpu(*a):
        return jax.tree.map(lambda o, w: o.astype(w.dtype),
                            pallas_fn(*a), want)
    return jax.lax.platform_dependent(*args, tpu=tpu, default=lax_fn)


def compiled_kernels(hlo_text):
    """{kernel name: count} of this package's compiled Pallas kernels
    in a compiled program's HLO text — which tier a step actually took
    is read off the program, not off the routing code.  Every
    pallas_call here is given a ``name="mxtpu_..."``, which the Mosaic
    custom call carries."""
    import re
    counts = {}
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = re.search(r"mxtpu_[a-z0-9_]+", line)
        name = m.group(0) if m else "unnamed"
        counts[name] = counts.get(name, 0) + 1
    return counts


from . import bn_act              # noqa: E402
from . import lstm_cell           # noqa: E402
from . import flash_attention     # noqa: E402
from . import augment             # noqa: E402
from . import concat_fuse         # noqa: E402
from . import pool_act            # noqa: E402
from . import eltwise_chain       # noqa: E402
