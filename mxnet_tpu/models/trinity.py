"""Trinity language model (sliding-window attention with a rotary
embedding beside periodic full attention without any positional embedding,
a sigmoid gate on every attention output, four norms a layer, leading
dense layers and routed experts behind a sigmoid router after them), built
from the published ``config.json`` keys of the ``afmoe`` model type
(Trinity-Mini / Trinity-Nano).

Layer ``i`` is ``h = h + N2(attention_i(N1(h))); h = h + N4(ffn_i(N3(h)))``
with every ``N`` a plain RMSNorm (weight initialised 1): the branch's
OUTPUT is normed before the add, as well as its input.  The embedding's
output is multiplied by ``sqrt(hidden_size)`` before layer 0
(``mup_enabled``).

Attention, every layer: ``q = N_d(W_q x)``, ``k = N_d(W_k x)`` per head,
``v = W_v x``, a gate ``W_gate x`` as wide as the queries from its own
projection; grouped-query causal softmax attention at ``head_dim^-0.5``,
times ``sigmoid(gate)``, then ``W_o``.  Where ``layer_types[i]`` is
``sliding_attention`` q and k are turned by a rotary embedding over the
whole head (theta ``rope_theta``) and position ``p`` sees the
``sliding_window`` keys up to its own (``GQAttention(window=)``); where it
is ``full_attention`` there is NO rotary embedding and the whole causal
prefix is seen.

``ffn_i`` is a dense SwiGLU of ``intermediate_size`` for the first
``num_dense_layers`` layers and the expert layer after them: scores
``sigmoid(W_r x)`` over all ``num_experts`` experts, the
``num_experts_per_tok`` with the largest score plus ``expert_bias`` (which
enters the choice alone), weighed by their scores over their sum
(``route_norm``) times ``route_scale``, plus one ungated shared SwiGLU of
``moe_intermediate_size x num_shared_experts``.  The expert layer is told
which experts it holds (``num_experts_held`` from ``expert_offset`` on),
as ``qwen3_next``'s is.

Stream, stages (``l<i>_swa``, ``l<i>_attn``, ``l<i>_mlp``, ``l<i>_moe``:
each holds its branch's input norm, the branch, its output norm and the
add), head and counters are ``qwen3_next``'s: see that module.
"""
from __future__ import annotations

import math

from .. import initializer, symbol as sym
from ..attribute import AttrScope
from .kimi_linear import _swiglu
from .qwen3_next import MOE_COUNTERS, _head, _linear, _norm

__all__ = ["trinity_sym", "MOE_COUNTERS"]


def _attention(x, p, seq_len, c, sliding):
    """x (tokens, hidden) -> (tokens, hidden)."""
    hq, hkv, d = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]

    def heads(name, n, normed):
        y = sym.Reshape(_linear(x, "%s_%s_proj" % (p, name), n * d),
                        shape=(-1, seq_len, n, d))
        if normed:
            y = _norm(y, "%s_%s_norm" % (p, name), d, zero_centered=False,
                      eps=c["rms_norm_eps"])
            if sliding:
                y = sym.RotaryEmbedding(y, base=c["rope_theta"],
                                        name="%s_%s_rope" % (p, name))
        return y
    o = sym.GQAttention(
        query=heads("q", hq, True), key=heads("k", hkv, True),
        value=heads("v", hkv, False), gate=heads("gate", hq, False),
        gated=True, window=int(c["sliding_window"]) if sliding else 0,
        name=p + "_core")
    return _linear(sym.Reshape(o, shape=(-1, hq * d)), p + "_o_proj",
                   c["hidden_size"])


def _experts(x, p, c, held, offset):
    """x (tokens, hidden) -> ((tokens, hidden), stats)."""
    hidden, width = c["hidden_size"], c["moe_intermediate_size"]
    routed = sym.RoutedExperts(
        data=x, top_k=c["num_experts_per_tok"], expert_offset=offset,
        norm_topk_prob=bool(c["route_norm"]), score_func=c["score_func"],
        routed_scaling_factor=c["route_scale"], use_select_bias=True,
        name=p + "_routed",
        router_weight=sym.Variable(p + "_router_weight",
                                   shape=(c["num_experts"], hidden)),
        select_bias=sym.Variable(p + "_expert_bias",
                                 shape=(c["num_experts"],),
                                 init=initializer.Zero()),
        gate_up_weight=sym.Variable(p + "_experts_gate_up_weight",
                                    shape=(held, hidden, 2 * width)),
        down_weight=sym.Variable(p + "_experts_down_weight",
                                 shape=(held, width, hidden)))
    shared = _swiglu(x, p + "_shared", width * c["num_shared_experts"],
                     hidden)
    return routed[0] + shared, routed[1]


def trinity_sym(seq_len, num_experts_held=None, expert_offset=0, **config):
    """The training symbol for rows of ``seq_len`` tokens: data (batch,
    seq_len) token ids, ``softmax_label`` (batch, seq_len) next tokens.
    ``config`` holds the published keys (``hidden_size``,
    ``num_hidden_layers``, ``layer_types``, ``sliding_window``,
    ``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
    ``rope_theta``, ``num_dense_layers``, ``intermediate_size``,
    ``num_experts`` — the router's width —, ``num_experts_per_tok``,
    ``moe_intermediate_size``, ``num_shared_experts``, ``score_func``,
    ``route_norm``, ``route_scale``, ``mup_enabled``, ``rms_norm_eps``,
    ``vocab_size``); keys it does not use are ignored, and so are the
    listed layers past ``num_hidden_layers``.  ``num_experts_held``
    (default: all) from ``expert_offset`` on are the experts whose weights
    live here.  Returns (symbol, data names, label names); the symbol's
    second head is the expert layers' counters (:data:`MOE_COUNTERS`),
    which a trainer takes out of the outputs."""
    c = config
    hidden, eps = c["hidden_size"], c["rms_norm_eps"]
    held = int(num_experts_held or c["num_experts"])
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    h = sym.Embedding(data=data, input_dim=c["vocab_size"],
                      output_dim=hidden, name="embed")
    h = sym.Reshape(h, shape=(-1, hidden))
    if c["mup_enabled"]:
        h = h * math.sqrt(hidden)
    stats = []

    def norm(x, name):
        return _norm(x, name, hidden, zero_centered=False, eps=eps)
    for i in range(c["num_hidden_layers"]):
        kind = c["layer_types"][i]
        if kind not in ("sliding_attention", "full_attention"):
            raise ValueError("trinity_sym: layer_types[%d] is %r" % (i, kind))
        sliding = kind == "sliding_attention"
        p = "l%d_%s" % (i, "swa" if sliding else "attn")
        with AttrScope(mirror_stage=p):
            out = _attention(norm(h, p + "_norm"), p, seq_len, c, sliding)
            h = h + norm(out, p + "_post_norm")
        if i < c["num_dense_layers"]:
            p = "l%d_mlp" % i
            with AttrScope(mirror_stage=p):
                out = _swiglu(norm(h, p + "_norm"), p,
                              c["intermediate_size"], hidden)
                h = h + norm(out, p + "_post_norm")
            continue
        p = "l%d_moe" % i
        with AttrScope(mirror_stage=p):
            out, stat = _experts(norm(h, p + "_norm"), p, c, held,
                                 int(expert_offset))
            h = h + norm(out, p + "_post_norm")
            stats.append(stat)
    return _head(h, label, stats, seq_len, hidden, c["vocab_size"], eps,
                 zero_centered=False)
