"""ZAYA1 language model (Compressed Convolutional Attention and a top-1
expert layer behind a router that carries its state from layer to layer),
built from the published ``config.json`` keys of the ``zaya`` model type
(arXiv:2510.04476 for the mixer, arXiv:2511.17127 for the model).

Every layer is ``h = h (+) CCA(N(h)); h = h (+) MoE(N(h))`` with ``N`` the
plain RMSNorm (weight initialised 1) and ``(+)`` a residual add with a
learned scale and bias on the stream and on the branch: ``(a_s * h + b_s) +
(a_o * f + b_o)``, ``a`` initialised 1 and ``b`` 0.

CCA attends inside a latent: one projection of the normed stream gives a
``Hq x d`` wide query latent, a ``Hkv x d`` wide key latent and the values;
the value heads' first half is this position's, the second half the
position before's (value shift).  The concatenated query and key latents
pass two causal convolutions (``CausalConv1D``: depthwise over
``cca_time0`` taps, then grouped by head over ``cca_time1``), and the mean
of the pre-convolution latents over each key head's group is added back
(to a query head, half of itself plus its key head; to a key head, half of
its group's mean query plus itself).  Queries and keys are brought to
length ``sqrt(d)`` per head (``HeadL2Norm``, the keys times a learned
``exp(temp)`` per head), turned by a rotary embedding on
``partial_rotary_factor`` of each head, and meet in causal grouped-query
attention (``GQAttention``); one projection takes the ``Hq x d`` result up
to the hidden size.

The router (``DepthRouter``, float32) projects the normed stream down to
``router_hidden_size``, adds the previous layer's router state times a
learned vector, hands that state on to the next layer, and scores the
``num_experts`` experts by a three-layer MLP and a softmax; the expert
layer (``RoutedExperts`` with ``scores_given``) takes each token's one
expert by score plus a balancing bias and weighs it by the score as it is.
The bias starts at zero and the load moves it: with ``router_balance_rate``
in the config its gradient is that rate times the error of each expert's
share of the step's tokens (``RoutedExperts``' ``balance_rate``, a token's
worth: the rate over ``seq_len``, since the loss is a mean over a row), and
the optimizer's rule does the rest.
It is told which experts it holds (``num_experts_held`` from
``expert_offset`` on), as ``qwen3_next``'s is.  The head is tied to the
embedding: one variable, ``embed_weight``, read by both.

Stream, stages (``l<i>_cca``, ``l<i>_moe``; the router state is a second
tensor that crosses every stage boundary from ``l0_moe`` on), head and
counters are ``qwen3_next``'s: see that module.
"""
from __future__ import annotations

from .. import initializer, symbol as sym
from ..attribute import AttrScope
from .qwen3_next import MOE_COUNTERS, _cut, _head, _linear, _norm

__all__ = ["zaya_sym", "MOE_COUNTERS"]


def _vector(name, width, value):
    return sym.Variable(name, shape=(width,),
                        init=initializer.Constant(value))


def _residual(h, branch, p, hidden):
    """``(a_s * h + b_s) + (a_o * branch + b_o)``."""
    def scaled(x, which):
        return sym.broadcast_add(
            sym.broadcast_mul(x, _vector("%s_%s_scale" % (p, which), hidden,
                                         1.0)),
            _vector("%s_%s_bias" % (p, which), hidden, 0.0))
    return scaled(h, "res") + scaled(branch, "out")


def _shifted(x, seq_len):
    """x (batch, positions, width) one position later, zeros first."""
    return sym.Concat(sym.zeros_like(_cut(x, 1, 0, 1)),
                      _cut(x, 1, 0, seq_len - 1), dim=1)


def _cca(x, p, seq_len, c):
    """x (tokens, hidden) -> (tokens, hidden)."""
    hq, hkv, d = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    ql, kl, group = hq * d, hkv * d, hq // hkv
    rotary = int(d * c["partial_rotary_factor"])
    theta = c["rope_parameters"]["hybrid"]["rope_theta"]
    qkv = sym.Reshape(_linear(x, p + "_in_proj", ql + 2 * kl),
                      shape=(-1, seq_len, ql + 2 * kl))
    latent = _cut(qkv, 2, 0, ql + kl)
    # value shift: the second half of the value heads is the position
    # before's (the projection has no bias, so shifting its result is
    # projecting the shifted stream)
    v = sym.Concat(_cut(qkv, 2, ql + kl, ql + kl + kl // 2),
                   _shifted(_cut(qkv, 2, ql + kl + kl // 2, ql + 2 * kl),
                            seq_len), dim=2)
    mixed = sym.CausalConv1D(
        data=latent, kernel=c["cca_time0"], name=p + "_conv0",
        weight=sym.Variable(p + "_conv0_weight",
                            shape=(ql + kl, c["cca_time0"])))
    mixed = sym.CausalConv1D(
        data=mixed, kernel=c["cca_time1"], num_group=hq + hkv,
        name=p + "_conv1",
        weight=sym.Variable(p + "_conv1_weight",
                            shape=(ql + kl, d, c["cca_time1"])))

    def heads(y, begin, n):
        """The ``n`` heads of ``y`` from channel ``begin`` on, filed under
        their key head: (batch, positions, hkv, n / hkv, d)."""
        return sym.Reshape(_cut(y, 2, begin, begin + n * d),
                           shape=(-1, seq_len, hkv, n // hkv, d))
    q0, k0 = heads(latent, 0, hq), heads(latent, ql, hkv)
    q = heads(mixed, 0, hq) + sym.broadcast_add(q0, k0) * 0.5
    k = heads(mixed, ql, hkv) + (sym.mean(q0, axis=3, keepdims=True)
                                 + k0) * 0.5
    q = sym.HeadL2Norm(sym.Reshape(q, shape=(-1, seq_len, hq, d)),
                       name=p + "_q_unit")
    k = sym.HeadL2Norm(
        sym.Reshape(k, shape=(-1, seq_len, hkv, d)), scaled=True,
        log_scale=sym.Variable(p + "_temp", shape=(hkv,),
                               init=initializer.Zero()),
        name=p + "_k_unit")
    q = sym.RotaryEmbedding(q, rotary_dim=rotary, base=theta)
    k = sym.RotaryEmbedding(k, rotary_dim=rotary, base=theta)
    o = sym.GQAttention(query=q, key=k,
                        value=sym.Reshape(v, shape=(-1, seq_len, hkv, d)),
                        name=p + "_core")
    return _linear(sym.Reshape(o, shape=(-1, ql)), p + "_o_proj",
                   c["hidden_size"])


def _experts(x, state, p, c, held, offset, balance_rate=0.0):
    """x (tokens, hidden), the previous layer's router state or None ->
    ((tokens, hidden), stats, this layer's router state)."""
    hidden, width = c["hidden_size"], c["moe_intermediate_size"]
    experts, r = c["num_experts"], c["router_hidden_size"]

    def matrix(name, rows, cols):
        return sym.Variable("%s_router_%s_weight" % (p, name),
                            shape=(rows, cols))
    carried = {} if state is None else {
        "carried": True, "state": state,
        "carry": _vector(p + "_router_carry", r, 0.5)}
    router = sym.DepthRouter(
        data=x, down_weight=matrix("down", r, hidden),
        norm_gamma=_vector(p + "_router_norm_gamma", r, 1.0),
        fc1_weight=matrix("fc1", r, r), fc2_weight=matrix("fc2", r, r),
        fc3_weight=matrix("fc3", experts, r), eps=c["rms_norm_eps"],
        name=p + "_router", **carried)
    routed = sym.RoutedExperts(
        data=x, scores=router[1], scores_given=True,
        top_k=c["num_experts_per_tok"], expert_offset=offset,
        norm_topk_prob=False, use_select_bias=True, name=p + "_routed",
        balance_rate=balance_rate,
        select_bias=sym.Variable(p + "_router_balance_bias",
                                 shape=(experts,), init=initializer.Zero()),
        gate_up_weight=sym.Variable(p + "_experts_gate_up_weight",
                                    shape=(held, hidden, 2 * width)),
        down_weight=sym.Variable(p + "_experts_down_weight",
                                 shape=(held, width, hidden)))
    return routed[0], routed[1], router[0]


def zaya_sym(seq_len, num_experts_held=None, expert_offset=0, **config):
    """The training symbol for rows of ``seq_len`` tokens: data (batch,
    seq_len) token ids, ``softmax_label`` (batch, seq_len) next tokens.
    ``config`` holds the published keys (``hidden_size``,
    ``num_hidden_layers``, ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``cca_time0``, ``cca_time1``,
    ``partial_rotary_factor``, ``rope_parameters`` — the ``hybrid`` layer
    type's ``rope_theta`` —, ``router_hidden_size``, ``num_experts`` — the
    router's width —, ``num_experts_per_tok``, ``moe_intermediate_size``,
    ``rms_norm_eps``, ``vocab_size``, and ``router_balance_rate``, which is
    not published: default 0, the balancing bias stays as it is); keys it
    does not use are ignored.
    ``num_experts_held`` (default: all) from ``expert_offset`` on are the
    experts whose weights live here.  Returns (symbol, data names, label
    names); the symbol's second head is the expert layers' counters
    (:data:`MOE_COUNTERS`), which a trainer takes out of the outputs."""
    c = config
    hidden, eps = c["hidden_size"], c["rms_norm_eps"]
    held = int(num_experts_held or c["num_experts"])
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed = sym.Variable("embed_weight", shape=(c["vocab_size"], hidden))
    h = sym.Embedding(data=data, weight=embed, input_dim=c["vocab_size"],
                      output_dim=hidden, name="embed")
    h = sym.Reshape(h, shape=(-1, hidden))
    stats, state = [], None

    def norm(x, p):
        return _norm(x, p + "_norm", hidden, zero_centered=False, eps=eps)
    for i in range(c["num_hidden_layers"]):
        p = "l%d_cca" % i
        with AttrScope(mirror_stage=p):
            h = _residual(h, _cca(norm(h, p), p, seq_len, c), p, hidden)
        p = "l%d_moe" % i
        with AttrScope(mirror_stage=p):
            out, stat, state = _experts(
                norm(h, p), state, p, c, held, int(expert_offset),
                c.get("router_balance_rate", 0.0) / seq_len)
            h = _residual(h, out, p, hidden)
            stats.append(stat)
    return _head(h, label, stats, seq_len, hidden, c["vocab_size"], eps,
                 zero_centered=False, weight=embed)
