"""Plain reference of the ``qwen3_next`` family (Qwen3-Next-80B-A3B:
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json):
a straight float32 ``jax.numpy`` program, contractions under ``highest``
precision, independent of ``mxnet_tpu``.  One ROW (one sequence) at a time:
``row_loss`` is the loss of one sequence, and a job sums rows itself so
that float32 weights, momentum and gradients (7.5 GB at the published
widths) leave room for one row's activations.

Layer i (0-based): ``h = h + mixer_i(N(h))``, ``h = h + moe(N(h))`` with
``N(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)``; ``mixer_i`` is gated
full attention where ``(i + 1) % full_attention_interval == 0``, else Gated
DeltaNet.  The delta rule runs position by position, the experts are a
plain loop over the held experts with masks, attention is a softmax over
explicit scores (a block of query rows at a time, so that the scores of
8,192 positions fit).  The expert layer routes over all
``num_routed_experts`` and adds only what the ``num_experts`` experts held
here (from ``expert_offset``) give: the chip's share of an expert-parallel
deployment; the vocabulary is the chip's slice.

Departures from the published model, also under ``assumed`` in the
configuration: no multi-token-prediction module (the published ``config``
has no key for it); the columns of ``in_proj_qkvz`` / ``in_proj_ba`` are
contiguous ([q, k, v, z] and [b, a]), not the checkpoint's per-head
interleave (random weights: the same distribution); initialisation
normal(0, 0.02) for every matrix and the convolution, norm weights 0
(zero-centred) or 1 (the DeltaNet output norm), ``A_log = log(U(0, 16))``,
``dt_bias = 1``, as the family's code does; a row is one sequence with no
document boundary inside it; the loss is the mean over the step's tokens
(SoftmaxOutput's sum scaled by 1 / seq_len, the optimizer's 1 / rows).

Leaves carry the program's own argument names (``l0_gdn_in_proj_qkvz_weight``
...), so ``to_program`` / ``from_program`` only pass them on.
"""
import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

ATTENTION_BLOCK = 512      # query rows whose scores are held at once
RULE_BLOCK = 64            # positions between two saved states


def _is_full(i, cfg):
    return (i + 1) % cfg["full_attention_interval"] == 0


def shapes(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    shared = cfg["shared_expert_intermediate_size"]
    p = {"embed_weight": (v, h), "head_norm_gamma": (h,),
         "head_weight": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        if _is_full(i, cfg):
            a = "l%d_attn_" % i
            p.update({a + "norm_gamma": (h,),
                      a + "q_proj_weight": (hq * 2 * d, h),
                      a + "k_proj_weight": (hkv * d, h),
                      a + "v_proj_weight": (hkv * d, h),
                      a + "q_norm_gamma": (d,), a + "k_norm_gamma": (d,),
                      a + "o_proj_weight": (h, hq * d)})
        else:
            g = "l%d_gdn_" % i
            p.update({g + "norm_gamma": (h,),
                      g + "in_proj_qkvz_weight": (2 * hk * dk + 2 * hv * dv,
                                                  h),
                      g + "in_proj_ba_weight": (2 * hv, h),
                      g + "conv_weight": (2 * hk * dk + hv * dv,
                                          cfg["linear_conv_kernel_dim"]),
                      g + "A_log": (hv,), g + "dt_bias": (hv,),
                      g + "out_norm_gamma": (dv,),
                      g + "out_proj_weight": (h, hv * dv)})
        m = "l%d_moe_" % i
        p.update({m + "norm_gamma": (h,),
                  m + "router_weight": (cfg["num_routed_experts"], h),
                  m + "experts_gate_up_weight": (held, h, 2 * width),
                  m + "experts_down_weight": (held, width, h),
                  m + "shared_gate_up_weight": (2 * shared, h),
                  m + "shared_down_weight": (h, shared),
                  m + "shared_gate_weight": (1, h)})
    return p, {}


def init(key, cfg):
    """Seeded weights in one traceable call."""
    pshapes, _ = shapes(cfg)
    params = {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_out_norm_gamma") or name.endswith("_dt_bias"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_gamma"):
            params[name] = jnp.zeros(shape, jnp.float32)
        elif name.endswith("_A_log"):
            params[name] = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1e-3, 16.0))
        else:
            params[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
    return params, {}


# -- the layers, one row (T, ...) at a time -----------------------------------

def _norm(x, w, eps, zero_centred=True):
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centred else w)


def _linear(x, w, precision):
    """x (T, in) @ w (out, in)^T."""
    return C.matmul(x, w.T, precision)


def _rope(x, cfg):
    """x (T, heads, head_dim): rotate-half rotary embedding on the first
    ``partial_rotary_factor`` of the features."""
    d = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    half = d // 2
    inv = cfg["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32)
                                * 2.0 / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:d]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., d:]], axis=-1)


def _attention(p, a, x, cfg, precision):
    t = x.shape[0]
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    qg = _linear(x, p[a + "q_proj_weight"], precision).reshape(t, hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = _linear(x, p[a + "k_proj_weight"], precision).reshape(t, hkv, d)
    v = _linear(x, p[a + "v_proj_weight"], precision).reshape(t, hkv, d)
    q = _rope(_norm(q, p[a + "q_norm_gamma"], eps), cfg)
    k = _rope(_norm(k, p[a + "k_norm_gamma"], eps), cfg)
    group = hq // hkv
    kt = jnp.transpose(k, (1, 2, 0))                    # (hkv, d, T)
    vt = jnp.transpose(v, (1, 0, 2))                    # (hkv, T, d)

    @jax.checkpoint
    def block(q_blk, first):
        """q_blk (n, hq, d) at positions first.. -> (n, hq, d)."""
        n = q_blk.shape[0]
        qh = jnp.transpose(q_blk.reshape(n, hkv, group, d), (1, 2, 0, 3))
        s = C.matmul(qh.reshape(hkv, group * n, d), kt, precision) \
            .reshape(hkv, group, n, t) * d ** -0.5
        seen = (first + jnp.arange(n))[:, None] >= jnp.arange(t)[None, :]
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = C.matmul(prob.reshape(hkv, group * n, t), vt, precision)
        return jnp.transpose(o.reshape(hkv, group, n, d), (2, 0, 1, 3)) \
            .reshape(n, hq, d)

    n = min(ATTENTION_BLOCK, t)
    out = jnp.concatenate([block(q[i:i + n], i) for i in range(0, t, n)])
    out = out * jax.nn.sigmoid(gate)
    return _linear(out.reshape(t, hq * d), p[a + "o_proj_weight"], precision)


def _delta_rule(q, k, v, g, beta):
    """Position by position.  q, k (T, H, dk); v (T, H, dv); g, beta
    (T, H) -> o (T, H, dv)."""
    t, h, dk = q.shape
    dv = v.shape[-1]

    def position(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, None, None] * s
        u = (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                              precision=lax.Precision.HIGHEST)) * b_t[:, None]
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t,
                             precision=lax.Precision.HIGHEST)

    blk = RULE_BLOCK if t % RULE_BLOCK == 0 else t

    @jax.checkpoint
    def run(s, xs):
        return lax.scan(position, s, xs)

    xs = tuple(x.reshape((t // blk, blk) + x.shape[1:])
               for x in (q, k, v, g, beta))
    _, o = lax.scan(run, jnp.zeros((h, dk, dv), jnp.float32), xs)
    return o.reshape(t, h, dv)


def _gated_delta_net(p, g_, x, cfg, precision):
    t = x.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kdim, vdim = hk * dk, hv * dv
    qkvz = _linear(x, p[g_ + "in_proj_qkvz_weight"], precision)
    ba = _linear(x, p[g_ + "in_proj_ba_weight"], precision)
    qkv, z = qkvz[:, :2 * kdim + vdim], qkvz[:, 2 * kdim + vdim:]
    w = p[g_ + "conv_weight"]
    taps = w.shape[1]
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j:j + t] * w[:, j] for j in range(taps)))
    q = qkv[:, :kdim].reshape(t, hk, dk)
    k = qkv[:, kdim:2 * kdim].reshape(t, hk, dk)
    v = qkv[:, 2 * kdim:].reshape(t, hv, dv)

    def unit(y):
        y = y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
        return jnp.repeat(y, hv // hk, axis=1)

    beta = jax.nn.sigmoid(ba[:, :hv])
    decay = -jnp.exp(p[g_ + "A_log"]) * jax.nn.softplus(
        ba[:, hv:] + p[g_ + "dt_bias"])
    o = _delta_rule(unit(q) * dk ** -0.5, unit(k), v, decay, beta)
    o = _norm(o, p[g_ + "out_norm_gamma"], cfg["rms_norm_eps"],
              zero_centred=False) * jax.nn.silu(z.reshape(t, hv, dv))
    return _linear(o.reshape(t, vdim), p[g_ + "out_proj_weight"], precision)


def _gated_ffn(x, gate_up, down, precision):
    """gate_up (hidden, 2 width), down (width, hidden)."""
    gate, up = jnp.split(C.matmul(x, gate_up, precision), 2, axis=-1)
    return C.matmul(jax.nn.silu(gate) * up, down, precision)


def expert_layer(p, m, x, cfg, precision="f32"):
    """The expert layer on x (T, hidden): the held experts' part of the
    routed sum, plus the shared expert."""
    k = cfg["num_experts_per_tok"]
    prob = jax.nn.softmax(_linear(x, p[m + "router_weight"], precision),
                          axis=-1)
    weight, chosen = lax.top_k(prob, k)
    if cfg["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    @jax.checkpoint
    def one(e, gate_up, down):
        mine = jnp.sum(jnp.where(chosen == e + cfg["expert_offset"], weight,
                                 0.0), axis=-1)
        return mine[:, None] * _gated_ffn(x, gate_up, down, precision)

    def add(total, ew):
        return total + one(*ew), None

    held = p[m + "experts_gate_up_weight"].shape[0]
    routed, _ = lax.scan(add, jnp.zeros_like(x), (
        jnp.arange(held), p[m + "experts_gate_up_weight"],
        p[m + "experts_down_weight"]))
    shared = _gated_ffn(x, p[m + "shared_gate_up_weight"].T,
                        p[m + "shared_down_weight"].T, precision)
    share = jax.nn.sigmoid(_linear(x, p[m + "shared_gate_weight"],
                                   precision))
    return routed + share * shared


def logits(params, data, cfg, precision="f32"):
    """data (T,) token ids -> logits (T, vocab)."""
    eps = cfg["rms_norm_eps"]
    h = jnp.take(params["embed_weight"], data.astype(jnp.int32), axis=0)
    for i in range(cfg["num_hidden_layers"]):
        full = _is_full(i, cfg)
        a = "l%d_%s_" % (i, "attn" if full else "gdn")
        m = "l%d_moe_" % i

        @jax.checkpoint
        def layer(h, p, a=a, m=m, full=full):
            x = _norm(h, p[a + "norm_gamma"], eps)
            mixer = _attention if full else _gated_delta_net
            h = h + mixer(p, a, x, cfg, precision)
            return h + expert_layer(p, m, _norm(h, p[m + "norm_gamma"], eps),
                                    cfg, precision)

        h = layer(h, {k: v for k, v in params.items()
                      if k.startswith(a) or k.startswith(m)})
    h = _norm(h, params["head_norm_gamma"], eps)
    return _linear(h, params["head_weight"], precision)


def row_loss(cfg, precision="f32"):
    """``f(params, data (T,), label (T,)) -> sum of the row's cross-entropy
    / T``: summed over a step's rows and divided by their number it is the
    mean over the step's tokens."""
    def f(params, data, label):
        out = logits(params, data, cfg, precision)
        return C.softmax_ce_sum(out, label) / out.shape[0]
    return f


def loss_fn(cfg, precision="f32"):
    """The harness's form: ``f(params, aux, batch) -> (loss_sum, (aux,
    rows))`` for ``batch = {"data": (B, T), "softmax_label": (B, T)}``;
    ``loss_sum / rows`` is the mean cross-entropy of the step's tokens and
    the gradient of ``loss_sum`` is what the optimizer rescales by 1/rows."""
    row = row_loss(cfg, precision)

    def f(params, aux, batch):
        rows = batch["data"].shape[0]
        total = sum(row(params, batch["data"][r], batch["softmax_label"][r])
                    for r in range(rows))
        return total, (aux, rows)
    return f


def to_program(params, aux, cfg):
    return dict(params), dict(aux)


def from_program(arg_params, cfg):
    pshapes, _ = shapes(cfg)
    return {k: arg_params[k] for k in pshapes}


# -- the arithmetic -----------------------------------------------------------

def _layer_flops(cfg):
    """Forward FLOPs a token of one layer of each kind (2 a MAC), with the
    routed experts at the pairs that land on held experts in expectation."""
    h, t = cfg["hidden_size"], cfg["seq_len"]
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width, shared = cfg["moe_intermediate_size"], \
        cfg["shared_expert_intermediate_size"]
    attn_proj = 2 * h * (hq * 2 * d + 2 * hkv * d + hq * d)
    attn_core = 2 * 2 * hq * d * (t + 1) / 2           # causal: half of T^2
    gdn_proj = 2 * h * (2 * hk * dk + 2 * hv * dv + 2 * hv + hv * dv)
    # chunked delta rule per value head and token, chunk C: k k^T and q k^T
    # (2 C dk), the solve's products (~C^2 + C (dk + dv)), state in and out
    # (3 dk dv), q S and the intra-chunk product (dk dv + C dv)
    c = RULE_BLOCK
    gdn_core = 2 * hv * (2 * c * dk + c * c + c * (dk + dv) + 4 * dk * dv
                         + c * dv)
    landed = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_routed_experts"]
    moe_fixed = 2 * h * (cfg["num_routed_experts"] + 3 * shared + 1)
    pair = 2 * 3 * h * width
    return {"attn": attn_proj + attn_core, "gdn": gdn_proj + gdn_core,
            "moe": moe_fixed + landed * pair, "pair": pair,
            "moe_fixed": moe_fixed}


def flops_per_item(cfg):
    """Model FLOPs of one token, forward: every projection, the causal
    attention at ``seq_len`` positions, the chunked delta rule, the router,
    the shared expert, the routed experts at their expected share of pairs
    (top-k x held / routed), and the head over the vocabulary slice."""
    per = _layer_flops(cfg)
    total = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    for i in range(cfg["num_hidden_layers"]):
        total += per["attn" if _is_full(i, cfg) else "gdn"] + per["moe"]
    return int(total)


def node_work(cfg, rows, itemsize=2, pairs_here=None):
    """Per stage of the graph (``l<i>_gdn``, ``l<i>_attn``, ``l<i>_moe``:
    the program's ``mirror_stage`` scopes), the work one step of ``rows``
    sequences needs: {kind: [{"node", "scopes", "fwd": (flops, bytes),
    "bwd": (flops, bytes)}]}, from shapes alone.  Backward is twice the
    forward's contractions; the forward a rematerialising step runs again
    is not work the model needs.  Bytes: the stage's weights once, its
    input and output and the widest activation it has to write and read.
    ``pairs_here``: (token, expert) pairs a step really landed on held
    experts, all layers together (default: the expectation)."""
    per = _layer_flops(cfg)
    h, t = cfg["hidden_size"], cfg["seq_len"]
    tokens = rows * t
    layers = cfg["num_hidden_layers"]
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = cfg["moe_intermediate_size"]
    if pairs_here is None:
        pairs_here = layers * tokens * cfg["num_experts_per_tok"] \
            * cfg["num_experts"] / cfg["num_routed_experts"]
    out = {"gdn": [], "attn": [], "moe": []}

    def add(kind, name, flops, weights, acts, scopes=None):
        nbytes = itemsize * (weights + acts)
        out[kind].append({"node": name, "scopes": scopes or [name],
                          "fwd": (flops, nbytes),
                          "bwd": (2 * flops, 2 * nbytes)})

    for i in range(layers):
        if _is_full(i, cfg):
            add("attn", "l%d_attn" % i, tokens * per["attn"],
                h * (hq * 3 * d + 2 * hkv * d),
                tokens * (2 * h + hq * 3 * d + 2 * hkv * d))
        else:
            add("gdn", "l%d_gdn" % i, tokens * per["gdn"],
                h * (2 * hk * dk + 3 * hv * dv),
                tokens * (2 * h + 2 * (2 * hk * dk + 2 * hv * dv)))
        pairs = pairs_here / layers
        add("moe", "l%d_moe" % i,
            tokens * per["moe_fixed"] + pairs * per["pair"],
            h * (cfg["num_routed_experts"] + 3 * width * cfg["num_experts"]
                 + 3 * cfg["shared_expert_intermediate_size"]),
            tokens * 2 * h + pairs * (2 * h + 3 * width))
    return out
