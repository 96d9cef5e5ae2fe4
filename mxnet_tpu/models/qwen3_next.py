"""Qwen3-Next language model (hybrid Gated DeltaNet / gated attention with a
routed-expert feed-forward in every layer), built from the published
``config.json`` keys of the ``qwen3_next`` model type.

Layer ``i`` is ``h = h + mixer_i(N(h)); h = h + moe(N(h))`` with ``N`` the
zero-centred RMSNorm; ``mixer_i`` is gated full attention where
``(i + 1) % full_attention_interval == 0`` and Gated DeltaNet elsewhere.
The routed-expert layer is told which experts it holds
(``num_experts_held`` from ``expert_offset`` on): the router stays
``num_experts`` wide and the layer computes the held experts' part of the
result, which is what one chip of an expert-parallel group does.

The residual stream is (batch * positions, hidden), batch-major; the
sequence ops see it reshaped to (batch, positions, ...).  Every mixer and
every expert layer is built under its own ``mirror_stage`` (``l<i>_gdn``,
``l<i>_attn``, ``l<i>_moe``): a training step keeps the stream between
stages and rematerialises inside them, and a device trace names its
events by those stages.  The head emits time-major rows, like
``lstm_lm``: ``SoftmaxOutput`` over (positions * batch, vocab) against
``softmax_label`` (batch, positions), its gradient scaled by 1 / positions
so that with the optimizer's 1 / batch it is the mean over the tokens.
"""
from __future__ import annotations

from .. import initializer, symbol as sym
from ..attribute import AttrScope

#: counters of the routed-expert layers, in the order RoutedExpertsStats
#: gives them; a trainer adds them to ``profiler.count`` one step late
MOE_COUNTERS = ("moe.assignments", "moe.assignments_here", "moe.load_max",
                "moe.load_mean", "moe.calls", "moe.compact_calls")


def _norm(x, name, width, zero_centered=True, gate=None, eps=1e-6,
          gate_act="silu"):
    gamma = sym.Variable(name + "_gamma", shape=(width,),
                         init=initializer.Zero() if zero_centered
                         else initializer.One())
    kw = {"gate": gate, "gated": True} if gate is not None else {}
    if gate_act != "silu":
        kw["gate_act"] = gate_act
    return sym.RMSNorm(data=x, gamma=gamma, eps=eps,
                       zero_centered=zero_centered, name=name, **kw)


def _linear(x, name, width, weight=None):
    """``weight``: a variable the graph already has (a tied head's), else
    the layer's own ``<name>_weight``."""
    kw = {} if weight is None else {"weight": weight}
    return sym.FullyConnected(data=x, num_hidden=width, no_bias=True,
                              name=name, **kw)


def _cut(x, axis, begin, end):
    return sym.slice_axis(x, axis=axis, begin=begin, end=end)


def _gated_delta_net(x, p, seq_len, c):
    """x (tokens, hidden) -> (tokens, hidden)."""
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    kdim, vdim = hk * dk, hv * dv
    taps = c["linear_conv_kernel_dim"]
    qkvz = _linear(x, p + "_in_proj_qkvz", 2 * kdim + 2 * vdim)
    ba = _linear(x, p + "_in_proj_ba", 2 * hv)
    qkv = sym.Reshape(_cut(qkvz, 1, 0, 2 * kdim + vdim),
                      shape=(-1, seq_len, 2 * kdim + vdim))
    qkv = sym.CausalConv1D(
        data=qkv, kernel=taps, act_type="silu", name=p + "_conv",
        weight=sym.Variable(p + "_conv_weight",
                            shape=(2 * kdim + vdim, taps)))
    z = sym.Reshape(_cut(qkvz, 1, 2 * kdim + vdim, 2 * kdim + 2 * vdim),
                    shape=(-1, seq_len, hv, dv))
    q = sym.Reshape(_cut(qkv, 2, 0, kdim), shape=(-1, seq_len, hk, dk))
    k = sym.Reshape(_cut(qkv, 2, kdim, 2 * kdim),
                    shape=(-1, seq_len, hk, dk))
    v = sym.Reshape(_cut(qkv, 2, 2 * kdim, 2 * kdim + vdim),
                    shape=(-1, seq_len, hv, dv))
    b = sym.Reshape(_cut(ba, 1, 0, hv), shape=(-1, seq_len, hv))
    a = sym.Reshape(_cut(ba, 1, hv, 2 * hv), shape=(-1, seq_len, hv))
    o = sym.GatedDeltaRule(
        query=q, key=k, value=v, a=a, b=b, chunk=64, name=p + "_rule",
        A_log=sym.Variable(p + "_A_log", shape=(hv,),
                           init=initializer.LogUniform(1e-3, 16.0)),
        dt_bias=sym.Variable(p + "_dt_bias", shape=(hv,),
                             init=initializer.One()))
    o = _norm(o, p + "_out_norm", dv, zero_centered=False, gate=z,
              eps=c["rms_norm_eps"])
    return _linear(sym.Reshape(o, shape=(-1, vdim)), p + "_out_proj",
                   c["hidden_size"])


def _gated_attention(x, p, seq_len, c):
    """x (tokens, hidden) -> (tokens, hidden)."""
    hq, hkv, d = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    eps = c["rms_norm_eps"]
    rotary = int(d * c["partial_rotary_factor"])
    qg = sym.Reshape(_linear(x, p + "_q_proj", hq * 2 * d),
                     shape=(-1, seq_len, hq, 2 * d))
    q, gate = _cut(qg, 3, 0, d), _cut(qg, 3, d, 2 * d)
    k = sym.Reshape(_linear(x, p + "_k_proj", hkv * d),
                    shape=(-1, seq_len, hkv, d))
    v = sym.Reshape(_linear(x, p + "_v_proj", hkv * d),
                    shape=(-1, seq_len, hkv, d))
    q = sym.RotaryEmbedding(_norm(q, p + "_q_norm", d, eps=eps),
                            rotary_dim=rotary, base=c["rope_theta"])
    k = sym.RotaryEmbedding(_norm(k, p + "_k_norm", d, eps=eps),
                            rotary_dim=rotary, base=c["rope_theta"])
    o = sym.GQAttention(query=q, key=k, value=v, gate=gate, gated=True,
                        name=p + "_core")
    return _linear(sym.Reshape(o, shape=(-1, hq * d)), p + "_o_proj",
                   c["hidden_size"])


def _experts(x, p, c, held, offset):
    """x (tokens, hidden) -> ((tokens, hidden), stats)."""
    hidden, width = c["hidden_size"], c["moe_intermediate_size"]
    routed = sym.RoutedExperts(
        data=x, top_k=c["num_experts_per_tok"], expert_offset=offset,
        norm_topk_prob=bool(c["norm_topk_prob"]), name=p + "_routed",
        router_weight=sym.Variable(p + "_router_weight",
                                   shape=(c["num_experts"], hidden)),
        gate_up_weight=sym.Variable(p + "_experts_gate_up_weight",
                                    shape=(held, hidden, 2 * width)),
        down_weight=sym.Variable(p + "_experts_down_weight",
                                 shape=(held, width, hidden)))
    shared = _linear(sym.SwiGLU(_linear(
        x, p + "_shared_gate_up", 2 * c["shared_expert_intermediate_size"])),
        p + "_shared_down", hidden)
    share = sym.Activation(_linear(x, p + "_shared_gate", 1),
                           act_type="sigmoid")
    return routed[0] + sym.broadcast_mul(shared, share), routed[1]


def _head(h, label, stats, seq_len, hidden, vocab, eps, zero_centered=True,
          weight=None):
    """The residual stream (batch * T, hidden) -> (symbol, data names, label
    names): final norm, the head over time-major rows — untied, or tied to
    the (vocab, hidden) variable ``weight`` —, ``SoftmaxOutput`` against
    ``label`` (batch, T) with its gradient scaled by 1 / T, and the expert
    layers' counters as the second head."""
    # (batch * T, H) -> time-major rows (T * batch, H), as the label's
    h = sym.SwapAxis(sym.Reshape(h, shape=(-1, seq_len, hidden)),
                     dim1=0, dim2=1)
    h = _norm(sym.Reshape(h, shape=(-1, hidden)), "head_norm", hidden,
              zero_centered=zero_centered, eps=eps)
    logits = _linear(h, "head", vocab, weight)
    lab = sym.Reshape(sym.SwapAxis(label, dim1=0, dim2=1), shape=(-1,))
    out = sym.SoftmaxOutput(data=logits, label=lab,
                            grad_scale=1.0 / seq_len, name="softmax")
    counters = sym.RoutedExpertsStats(
        *stats, name="moe_counters",
        attr={"__step_counters__": ",".join(MOE_COUNTERS)})
    return sym.Group([out, counters]), ("data",), ("softmax_label",)


def qwen3_next_sym(seq_len, num_experts_held=None, expert_offset=0,
                   **config):
    """The training symbol for rows of ``seq_len`` tokens: data (batch,
    seq_len) token ids, ``softmax_label`` (batch, seq_len) next tokens.
    ``config`` holds the published keys (``hidden_size``,
    ``num_hidden_layers``, ``full_attention_interval``, the attention and
    ``linear_*`` head counts and sizes, ``num_experts`` — the router's
    width —, ``num_experts_per_tok``, ``moe_intermediate_size``,
    ``shared_expert_intermediate_size``, ``norm_topk_prob``,
    ``partial_rotary_factor``, ``rope_theta``, ``rms_norm_eps``,
    ``vocab_size``); keys it does not use are ignored.
    ``num_experts_held`` (default: all) from ``expert_offset`` on are the
    experts whose weights live here.  Returns (symbol, data names, label
    names); the symbol's second head is the experts' counters
    (:data:`MOE_COUNTERS`), which a trainer takes out of the outputs."""
    c = config
    hidden, eps = c["hidden_size"], c["rms_norm_eps"]
    held = int(num_experts_held or c["num_experts"])
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Embedding(data=data, input_dim=c["vocab_size"],
                      output_dim=hidden, name="embed")
    h = sym.Reshape(h, shape=(-1, hidden))
    stats = []
    for i in range(c["num_hidden_layers"]):
        full = (i + 1) % c["full_attention_interval"] == 0
        p = "l%d_%s" % (i, "attn" if full else "gdn")
        with AttrScope(mirror_stage=p):
            x = _norm(h, p + "_norm", hidden, eps=eps)
            mixer = _gated_attention if full else _gated_delta_net
            h = h + mixer(x, p, seq_len, c)
        p = "l%d_moe" % i
        with AttrScope(mirror_stage=p):
            out, stat = _experts(_norm(h, p + "_norm", hidden, eps=eps), p,
                                 c, held, int(expert_offset))
            h = h + out
            stats.append(stat)
    return _head(h, label, stats, seq_len, hidden, c["vocab_size"], eps)
