"""Kimi Linear language model (hybrid Kimi Delta Attention / latent
attention without positional embedding, a dense first layer and routed
experts behind a sigmoid router after it), built from the published
``config.json`` keys of the ``kimi_linear`` model type
(arXiv:2510.26692).

Layer ``i`` (0-based here; the config counts from 1) is ``h = h +
mixer_i(N(h)); h = h + ffn_i(N(h))`` with ``N`` the plain RMSNorm (weight
initialised 1).  ``mixer_i`` is latent attention (MLA) where ``i + 1`` is in
``linear_attn_config["full_attn_layers"]`` and Kimi Delta Attention (KDA)
elsewhere; ``ffn_i`` is a dense SwiGLU for the first
``first_k_dense_replace`` layers and the expert layer after them.

KDA is the gated delta rule with a decay per key channel
(``GatedDeltaRule`` with a rank-4 ``a``): q, k and v each from its own
projection, depthwise causal convolution and ``silu``; the decay and the
output gate through low-rank projections (inner width the head size);
the output norm gated by ``sigmoid``.  MLA with ``mla_use_nope`` has no
rotary embedding: the 64-wide ``k_pe`` part of the key, one for all heads,
is broadcast to the heads and concatenated behind ``k_nope``
(``GQAttention`` with 192-wide queries and keys and 128-wide values).  The
expert layer scores by ``sigmoid``, chooses by score plus
``e_score_correction_bias``, renormalises, scales by
``routed_scaling_factor`` and adds an ungated shared expert; it is told
which experts it holds (``num_experts_held`` from ``expert_offset`` on),
as ``qwen3_next``'s is.

Stream, stages (``l<i>_kda``, ``l<i>_mla``, ``l<i>_mlp``, ``l<i>_moe``),
head and counters are ``qwen3_next``'s: see that module.
"""
from __future__ import annotations

from .. import initializer, symbol as sym
from ..attribute import AttrScope
from .qwen3_next import MOE_COUNTERS, _cut, _head, _linear, _norm

__all__ = ["kimi_linear_sym", "MOE_COUNTERS"]


def _low_rank(x, p, inner, width):
    """``W_b (W_a x)``, no activation between."""
    return _linear(_linear(x, p + "_a_proj", inner), p + "_b_proj", width)


def _kda(x, p, seq_len, c):
    """x (tokens, hidden) -> (tokens, hidden)."""
    lin = c["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    width, taps = heads * d, lin["short_conv_kernel_size"]

    def mixed(name):
        y = sym.Reshape(_linear(x, "%s_%s_proj" % (p, name), width),
                        shape=(-1, seq_len, width))
        y = sym.CausalConv1D(
            data=y, kernel=taps, act_type="silu",
            name="%s_%s_conv" % (p, name),
            weight=sym.Variable("%s_%s_conv_weight" % (p, name),
                                shape=(width, taps)))
        return sym.Reshape(y, shape=(-1, seq_len, heads, d))
    a = sym.Reshape(_low_rank(x, p + "_f", d, width),
                    shape=(-1, seq_len, heads, d))
    b = sym.Reshape(_linear(x, p + "_b_proj", heads),
                    shape=(-1, seq_len, heads))
    o = sym.GatedDeltaRule(
        query=mixed("q"), key=mixed("k"), value=mixed("v"), a=a, b=b,
        chunk=64, name=p + "_rule",
        A_log=sym.Variable(p + "_A_log", shape=(heads,),
                           init=initializer.LogUniform(1.0, 16.0)),
        dt_bias=sym.Variable(p + "_dt_bias", shape=(width,),
                             init=initializer.StepSizeBias()))
    gate = sym.Reshape(_low_rank(x, p + "_g", d, width),
                       shape=(-1, seq_len, heads, d))
    o = _norm(o, p + "_o_norm", d, zero_centered=False, gate=gate,
              gate_act="sigmoid", eps=c["rms_norm_eps"])
    return _linear(sym.Reshape(o, shape=(-1, width)), p + "_o_proj",
                   c["hidden_size"])


def _mla(x, p, seq_len, c):
    """x (tokens, hidden) -> (tokens, hidden).  No query compression
    (``q_lora_rank`` null) and no rotary embedding (``mla_use_nope``)."""
    heads, rank = c["num_attention_heads"], c["kv_lora_rank"]
    nope, pe, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    q = sym.Reshape(_linear(x, p + "_q_proj", heads * (nope + pe)),
                    shape=(-1, seq_len, heads, nope + pe))
    kva = _linear(x, p + "_kv_a_proj", rank + pe)
    latent = _norm(_cut(kva, 1, 0, rank), p + "_kv_a_norm", rank,
                   zero_centered=False, eps=c["rms_norm_eps"])
    kv = sym.Reshape(_linear(latent, p + "_kv_b_proj", heads * (nope + dv)),
                     shape=(-1, seq_len, heads, nope + dv))
    # the one k_pe of a position serves every head
    k_pe = sym.broadcast_axis(
        sym.Reshape(_cut(kva, 1, rank, rank + pe),
                    shape=(-1, seq_len, 1, pe)), axis=2, size=heads)
    k = sym.Concat(_cut(kv, 3, 0, nope), k_pe, dim=3)
    # 256 query rows a block: at 32 heads a block's float32 score tile
    # is what 512 rows are at 16
    o = sym.GQAttention(query=q, key=k, value=_cut(kv, 3, nope, nope + dv),
                        block_q=256, name=p + "_core")
    return _linear(sym.Reshape(o, shape=(-1, heads * dv)), p + "_o_proj",
                   c["hidden_size"])


def _swiglu(x, p, width, hidden):
    return _linear(sym.SwiGLU(_linear(x, p + "_gate_up", 2 * width)),
                   p + "_down", hidden)


def _experts(x, p, c, held, offset):
    """x (tokens, hidden) -> ((tokens, hidden), stats)."""
    hidden, width = c["hidden_size"], c["moe_intermediate_size"]
    routed = sym.RoutedExperts(
        data=x, top_k=c["num_experts_per_token"], expert_offset=offset,
        norm_topk_prob=bool(c["moe_renormalize"]),
        score_func=c["moe_router_activation_func"],
        routed_scaling_factor=c["routed_scaling_factor"],
        use_select_bias=True, name=p + "_routed",
        router_weight=sym.Variable(p + "_router_weight",
                                   shape=(c["num_experts"], hidden)),
        select_bias=sym.Variable(p + "_e_score_correction_bias",
                                 shape=(c["num_experts"],),
                                 init=initializer.Zero()),
        gate_up_weight=sym.Variable(p + "_experts_gate_up_weight",
                                    shape=(held, hidden, 2 * width)),
        down_weight=sym.Variable(p + "_experts_down_weight",
                                 shape=(held, width, hidden)))
    shared = _swiglu(x, p + "_shared", width * c["num_shared_experts"],
                     hidden)
    return routed[0] + shared, routed[1]


def kimi_linear_sym(seq_len, num_experts_held=None, expert_offset=0,
                    **config):
    """The training symbol for rows of ``seq_len`` tokens: data (batch,
    seq_len) token ids, ``softmax_label`` (batch, seq_len) next tokens.
    ``config`` holds the published keys (``hidden_size``,
    ``num_hidden_layers``, ``linear_attn_config`` — ``full_attn_layers``
    counted from 1, ``num_heads``, ``head_dim``,
    ``short_conv_kernel_size`` —, ``num_attention_heads``,
    ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``, ``first_k_dense_replace``, ``intermediate_size``,
    ``num_experts`` — the router's width —, ``num_experts_per_token``,
    ``moe_intermediate_size``, ``num_shared_experts``,
    ``moe_router_activation_func``, ``moe_renormalize``,
    ``routed_scaling_factor``, ``rms_norm_eps``, ``vocab_size``); keys it
    does not use are ignored, and so are the listed layers past
    ``num_hidden_layers``.  ``num_experts_held`` (default: all) from
    ``expert_offset`` on are the experts whose weights live here.  Returns
    (symbol, data names, label names); the symbol's second head is the
    expert layers' counters (:data:`MOE_COUNTERS`), which a trainer takes
    out of the outputs."""
    c = config
    hidden, eps = c["hidden_size"], c["rms_norm_eps"]
    held = int(num_experts_held or c["num_experts"])
    full = set(c["linear_attn_config"]["full_attn_layers"])
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Embedding(data=data, input_dim=c["vocab_size"],
                      output_dim=hidden, name="embed")
    h = sym.Reshape(h, shape=(-1, hidden))
    stats = []

    def norm(x, p):
        return _norm(x, p + "_norm", hidden, zero_centered=False, eps=eps)
    for i in range(c["num_hidden_layers"]):
        p = "l%d_%s" % (i, "mla" if i + 1 in full else "kda")
        with AttrScope(mirror_stage=p):
            mixer = _mla if i + 1 in full else _kda
            h = h + mixer(norm(h, p), p, seq_len, c)
        if i < c["first_k_dense_replace"]:
            p = "l%d_mlp" % i
            with AttrScope(mirror_stage=p):
                h = h + _swiglu(norm(h, p), p, c["intermediate_size"], hidden)
            continue
        p = "l%d_moe" % i
        with AttrScope(mirror_stage=p):
            out, stat = _experts(norm(h, p), p, c, held, int(expert_offset))
            h = h + out
            stats.append(stat)
    return _head(h, label, stats, seq_len, hidden, c["vocab_size"], eps,
                 zero_centered=False)
