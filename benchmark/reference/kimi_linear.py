"""Plain reference of the ``kimi_linear`` family (Kimi-Linear-48B-A3B:
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json,
the layer equations of arXiv:2510.26692): a straight float32 ``jax.numpy``
program, contractions under ``highest`` precision, independent of
``mxnet_tpu``.  One ROW (one sequence) at a time, as
``reference/qwen3_next.py``: ``row_loss`` is the loss of one sequence and a
job sums rows itself.

Layer i (0-based; the config counts from 1): ``h = h + mixer_i(N(h))``,
``h = h + ffn_i(N(h))`` with ``N(x; w) = x * rsqrt(mean(x^2) + eps) * w``;
``mixer_i`` is latent attention (MLA) where ``i + 1`` is in
``linear_attn_config.full_attn_layers``, else Kimi Delta Attention (KDA);
``ffn_i`` is a dense SwiGLU for ``i < first_k_dense_replace`` and the
expert layer after.

KDA: q, k, v each from its own projection, 4-tap depthwise causal
convolution and ``silu``; ``g = -exp(A_log[head]) * softplus(W_fb W_fa x +
dt_bias)``, one log-decay a key channel; ``beta = sigmoid(W_b x)``; q, k
L2-normalised per head, q scaled by dk^-0.5; per head from a zero state,
position by position, ``S = diag(exp(g_t)) S; u = beta_t (v_t - S^T k_t);
S = S + k_t u^T; o_t = S^T q_t``; output ``W_o (N_128(o) * sigmoid(W_gb
W_ga x))``.  MLA (``mla_use_nope``: no rotary embedding; ``q_lora_rank``
null): ``q = W_q x`` -> (heads, 128 + 64); ``[c, k_pe] = W_kva x``;
``[k_nope, v] = W_kvb N_512(c)``; ``k = [k_nope, k_pe]`` with the one
``k_pe`` shared by the heads; causal ``softmax(q k^T 192^-0.5) v`` over
explicit scores, a block of query rows at a time.  Expert layer: ``s =
sigmoid(W_r x)`` over all ``num_routed_experts``; the top-k of ``s +
e_score_correction_bias`` are chosen (one group: the grouped top-k is the
plain one), weighed by ``s`` over their sum times
``routed_scaling_factor``; a plain loop over the ``num_experts`` experts
held here (from ``expert_offset``) with masks; plus the ungated shared
expert.  The vocabulary is the chip's slice.

Departures from the published model, also under ``assumed`` in the
configuration: initialisation normal(0, 0.02) for every matrix and
convolution, norm weights 1, ``A_log = log(U(1, 16))``, ``dt_bias =
softplus^-1(dt)`` with ``dt`` log-uniform in [0.001, 0.1] as the family's
code draws it, ``e_score_correction_bias`` normal(0, 0.01) (the checkpoint's
is learnt by the balancing rule; SGD leaves it as seeded: it enters the
choice alone); the low-rank gates' inner width is the linear head size;
no bias on any projection; a row is one sequence with no document boundary;
the loss is the mean over the step's tokens.

Leaves carry the program's own argument names, so ``to_program`` /
``from_program`` only pass them on.
"""
import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

ATTENTION_BLOCK = 512      # query rows whose scores are held at once
RULE_BLOCK = 64            # positions between two saved states
_HIGHEST = lax.Precision.HIGHEST


def _is_full(i, cfg):
    return i + 1 in cfg["linear_attn_config"]["full_attn_layers"]


def _is_dense(i, cfg):
    return i < cfg["first_k_dense_replace"]


def _sizes(cfg):
    lin = cfg["linear_attn_config"]
    return dict(
        h=cfg["hidden_size"], heads=lin["num_heads"], d=lin["head_dim"],
        taps=lin["short_conv_kernel_size"], hq=cfg["num_attention_heads"],
        rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        pe=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        dense=cfg["intermediate_size"], width=cfg["moe_intermediate_size"],
        shared=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        held=cfg["num_experts"], routed=cfg["num_routed_experts"],
        k=cfg["num_experts_per_token"])


def shapes(cfg):
    z = _sizes(cfg)
    h, v, kw = z["h"], cfg["vocab_size"], z["heads"] * z["d"]
    p = {"embed_weight": (v, h), "head_norm_gamma": (h,),
         "head_weight": (v, h)}
    for i in range(cfg["num_hidden_layers"]):
        if _is_full(i, cfg):
            a = "l%d_mla_" % i
            p.update({a + "norm_gamma": (h,),
                      a + "q_proj_weight": (z["hq"] * (z["nope"] + z["pe"]),
                                            h),
                      a + "kv_a_proj_weight": (z["rank"] + z["pe"], h),
                      a + "kv_a_norm_gamma": (z["rank"],),
                      a + "kv_b_proj_weight": (z["hq"] * (z["nope"]
                                                          + z["dv"]),
                                               z["rank"]),
                      a + "o_proj_weight": (h, z["hq"] * z["dv"])})
        else:
            a = "l%d_kda_" % i
            p.update({a + "norm_gamma": (h,), a + "A_log": (z["heads"],),
                      a + "dt_bias": (kw,), a + "o_norm_gamma": (z["d"],),
                      a + "f_a_proj_weight": (z["d"], h),
                      a + "f_b_proj_weight": (kw, z["d"]),
                      a + "g_a_proj_weight": (z["d"], h),
                      a + "g_b_proj_weight": (kw, z["d"]),
                      a + "b_proj_weight": (z["heads"], h),
                      a + "o_proj_weight": (h, kw)})
            for name in "qkv":
                p[a + name + "_proj_weight"] = (kw, h)
                p[a + name + "_conv_weight"] = (kw, z["taps"])
        if _is_dense(i, cfg):
            m = "l%d_mlp_" % i
            p.update({m + "norm_gamma": (h,),
                      m + "gate_up_weight": (2 * z["dense"], h),
                      m + "down_weight": (h, z["dense"])})
        else:
            m = "l%d_moe_" % i
            p.update({m + "norm_gamma": (h,),
                      m + "router_weight": (z["routed"], h),
                      m + "e_score_correction_bias": (z["routed"],),
                      m + "experts_gate_up_weight": (z["held"], h,
                                                     2 * z["width"]),
                      m + "experts_down_weight": (z["held"], z["width"], h),
                      m + "shared_gate_up_weight": (2 * z["shared"], h),
                      m + "shared_down_weight": (h, z["shared"])})
    return p, {}


def init(key, cfg):
    """Seeded weights in one traceable call."""
    pshapes, _ = shapes(cfg)
    params = {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_A_log"):
            params[name] = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0))
        elif name.endswith("_dt_bias"):
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, jnp.log(1e-3), jnp.log(0.1)))
            params[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif name.endswith("_e_score_correction_bias"):
            params[name] = 0.01 * jax.random.normal(k, shape, jnp.float32)
        else:
            params[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
    return params, {}


# -- the layers, one row (T, ...) at a time -----------------------------------

def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _linear(x, w, precision):
    """x (T, in) @ w (out, in)^T."""
    return C.matmul(x, w.T, precision)


def _attention(p, a, x, cfg, precision):
    z = _sizes(cfg)
    t, hq, dv = x.shape[0], z["hq"], z["dv"]
    d = z["nope"] + z["pe"]
    q = _linear(x, p[a + "q_proj_weight"], precision).reshape(t, hq, d)
    kva = _linear(x, p[a + "kv_a_proj_weight"], precision)
    latent = _norm(kva[:, :z["rank"]], p[a + "kv_a_norm_gamma"],
                   cfg["rms_norm_eps"])
    kv = _linear(latent, p[a + "kv_b_proj_weight"], precision) \
        .reshape(t, hq, z["nope"] + dv)
    k_pe = jnp.broadcast_to(kva[:, None, z["rank"]:], (t, hq, z["pe"]))
    k = jnp.concatenate([kv[..., :z["nope"]], k_pe], axis=-1)
    kt = jnp.transpose(k, (1, 2, 0))                    # (hq, d, T)
    vt = jnp.transpose(kv[..., z["nope"]:], (1, 0, 2))  # (hq, T, dv)

    @jax.checkpoint
    def block(q_blk, first):
        """q_blk (n, hq, d) at positions first.. -> (n, hq, dv)."""
        n = q_blk.shape[0]
        s = C.matmul(jnp.transpose(q_blk, (1, 0, 2)), kt, precision) \
            * d ** -0.5                                 # (hq, n, T)
        seen = (first + jnp.arange(n))[:, None] >= jnp.arange(t)[None, :]
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.transpose(C.matmul(prob, vt, precision), (1, 0, 2))

    # one block after another (lax.map): 32 key heads make every block's
    # rounded keys and values 335 MB, which an unrolled loop holds at once
    n = ATTENTION_BLOCK if t % ATTENTION_BLOCK == 0 else t
    out = lax.map(lambda x: block(*x), (q.reshape(t // n, n, hq, d),
                                        jnp.arange(0, t, n)))
    return _linear(out.reshape(t, hq * dv), p[a + "o_proj_weight"],
                   precision)


def _delta_rule(q, k, v, g, beta):
    """Position by position.  q, k, g (T, H, dk); v (T, H, dv); beta
    (T, H) -> o (T, H, dv)."""
    t, h, dk = q.shape
    dv = v.shape[-1]

    def position(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[:, :, None] * s
        u = (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                              precision=_HIGHEST)) * b_t[:, None]
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=_HIGHEST)

    blk = RULE_BLOCK if t % RULE_BLOCK == 0 else t

    @jax.checkpoint
    def run(s, xs):
        return lax.scan(position, s, xs)

    xs = tuple(x.reshape((t // blk, blk) + x.shape[1:])
               for x in (q, k, v, g, beta))
    _, o = lax.scan(run, jnp.zeros((h, dk, dv), jnp.float32), xs)
    return o.reshape(t, h, dv)


def _kda(p, a, x, cfg, precision):
    z = _sizes(cfg)
    t, heads, d = x.shape[0], z["heads"], z["d"]

    def mixed(name):
        y = _linear(x, p[a + name + "_proj_weight"], precision)
        w = p[a + name + "_conv_weight"]
        taps = w.shape[1]
        padded = jnp.pad(y, ((taps - 1, 0), (0, 0)))
        y = jax.nn.silu(sum(padded[j:j + t] * w[:, j] for j in range(taps)))
        return y.reshape(t, heads, d)

    def low_rank(name):
        return _linear(_linear(x, p[a + name + "_a_proj_weight"], precision),
                       p[a + name + "_b_proj_weight"], precision)

    def unit(y):
        return y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

    decay = -jnp.exp(p[a + "A_log"])[None, :, None] * jax.nn.softplus(
        (low_rank("f") + p[a + "dt_bias"]).reshape(t, heads, d))
    beta = jax.nn.sigmoid(_linear(x, p[a + "b_proj_weight"], precision))
    o = _delta_rule(unit(mixed("q")) * d ** -0.5, unit(mixed("k")),
                    mixed("v"), decay, beta)
    o = _norm(o, p[a + "o_norm_gamma"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(low_rank("g").reshape(t, heads, d))
    return _linear(o.reshape(t, heads * d), p[a + "o_proj_weight"],
                   precision)


def _gated_ffn(x, gate_up, down, precision):
    """gate_up (hidden, 2 width), down (width, hidden)."""
    gate, up = jnp.split(C.matmul(x, gate_up, precision), 2, axis=-1)
    return C.matmul(jax.nn.silu(gate) * up, down, precision)


def route(p, m, x, cfg, precision="f32"):
    """(weight, chosen) (T, k): the experts chosen by score plus bias, and
    their scores renormalised and scaled."""
    score = jax.nn.sigmoid(_linear(x, p[m + "router_weight"], precision))
    _, chosen = lax.top_k(score + p[m + "e_score_correction_bias"],
                          cfg["num_experts_per_token"])
    weight = jnp.take_along_axis(score, chosen, axis=-1)
    if cfg["moe_renormalize"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return weight * cfg["routed_scaling_factor"], chosen


def expert_layer(p, m, x, cfg, precision="f32"):
    """The expert layer on x (T, hidden): the held experts' part of the
    routed sum, plus the shared expert."""
    weight, chosen = route(p, m, x, cfg, precision)

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
    return routed + _gated_ffn(x, p[m + "shared_gate_up_weight"].T,
                               p[m + "shared_down_weight"].T, precision)


def logits(params, data, cfg, precision="f32"):
    """data (T,) token ids -> logits (T, vocab)."""
    eps = cfg["rms_norm_eps"]
    h = jnp.take(params["embed_weight"], data.astype(jnp.int32), axis=0)
    for i in range(cfg["num_hidden_layers"]):
        full, dense = _is_full(i, cfg), _is_dense(i, cfg)
        a = "l%d_%s_" % (i, "mla" if full else "kda")
        m = "l%d_%s_" % (i, "mlp" if dense else "moe")

        @jax.checkpoint
        def layer(h, p, a=a, m=m, full=full, dense=dense):
            x = _norm(h, p[a + "norm_gamma"], eps)
            h = h + (_attention if full else _kda)(p, a, x, cfg, precision)
            x = _norm(h, p[m + "norm_gamma"], eps)
            if dense:
                return h + _gated_ffn(x, p[m + "gate_up_weight"].T,
                                      p[m + "down_weight"].T, precision)
            return h + expert_layer(p, m, x, cfg, precision)

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
    """Forward FLOPs a token of one stage of each kind (2 a MAC), with the
    routed experts at the pairs that land on held experts in expectation."""
    z = _sizes(cfg)
    h, t, d, kw = z["h"], cfg["seq_len"], z["d"], z["heads"] * z["d"]
    # q, k, v and output projections, the two low-rank gates, beta
    kda_proj = 2 * (4 * h * kw + 2 * (h * d + d * kw) + h * z["heads"])
    # the chunked rule per head and token, chunk C: k k^T and q k^T (2 C
    # dk), the solve's products (~C^2 + C (dk + dv)), state in and out (3
    # dk dv), q S and the intra-chunk product (dk dv + C dv): the same
    # count whichever rank the decay has
    c = RULE_BLOCK
    kda_core = 2 * z["heads"] * (2 * c * d + c * c + c * 2 * d + 4 * d * d
                                 + c * d)
    qk = z["nope"] + z["pe"]
    mla_proj = 2 * (h * z["hq"] * qk + h * (z["rank"] + z["pe"])
                    + z["rank"] * z["hq"] * (z["nope"] + z["dv"])
                    + z["hq"] * z["dv"] * h)
    mla_core = 2 * z["hq"] * (qk + z["dv"]) * (t + 1) / 2  # causal: half T^2
    landed = z["k"] * z["held"] / z["routed"]
    moe_fixed = 2 * h * (z["routed"] + 3 * z["shared"])
    pair = 2 * 3 * h * z["width"]
    return {"kda": kda_proj + kda_core, "attn": mla_proj + mla_core,
            "mlp": 2 * 3 * h * z["dense"], "moe": moe_fixed + landed * pair,
            "pair": pair, "moe_fixed": moe_fixed}


def _stages(cfg):
    """[(kind, stage name)] in the graph's order."""
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out.append(("attn", "l%d_mla" % i) if _is_full(i, cfg)
                   else ("kda", "l%d_kda" % i))
        out.append(("mlp", "l%d_mlp" % i) if _is_dense(i, cfg)
                   else ("moe", "l%d_moe" % i))
    return out


def flops_per_item(cfg):
    """Model FLOPs of one token, forward: every projection, the causal
    attention at ``seq_len`` positions, the chunked delta rule, the dense
    feed-forward, the router, the shared expert, the routed experts at
    their expected share of pairs (top-k x held / routed), and the head
    over the vocabulary slice."""
    per = _layer_flops(cfg)
    return int(2 * cfg["hidden_size"] * cfg["vocab_size"]
               + sum(per[kind] for kind, _ in _stages(cfg)))


def node_work(cfg, rows, itemsize=2, pairs_here=None):
    """Per stage of the graph (``l<i>_kda``, ``l<i>_mla``, ``l<i>_mlp``,
    ``l<i>_moe``: the program's ``mirror_stage`` scopes), the work one step
    of ``rows`` sequences needs: {kind: [{"node", "scopes", "fwd": (flops,
    bytes), "bwd": (flops, bytes)}]} for the kinds ``kda``, ``attn`` (the
    MLA stages), ``mlp`` and ``moe``, from shapes alone.  Backward is twice
    the forward's contractions; the forward a rematerialising step runs
    again is not work the model needs.  Bytes: the stage's weights once,
    its input and output and the widest activation it has to write and
    read.  ``pairs_here``: (token, expert) pairs a step really landed on
    held experts, all expert layers together (default: the expectation)."""
    per = _layer_flops(cfg)
    z = _sizes(cfg)
    h, kw = z["h"], z["heads"] * z["d"]
    tokens = rows * cfg["seq_len"]
    stages = _stages(cfg)
    layers = sum(kind == "moe" for kind, _ in stages)
    if pairs_here is None:
        pairs_here = layers * tokens * z["k"] * z["held"] / z["routed"]
    pairs = pairs_here / max(layers, 1)
    qkv = z["hq"] * (2 * (z["nope"] + z["pe"]) + z["dv"])
    weights = {
        "kda": 4 * h * kw + 2 * (h * z["d"] + z["d"] * kw),
        "attn": h * z["hq"] * (z["nope"] + z["pe"]) + h * (z["rank"]
                                                           + z["pe"])
        + z["rank"] * z["hq"] * (z["nope"] + z["dv"]) + z["hq"] * z["dv"] * h,
        "mlp": 3 * h * z["dense"],
        "moe": h * (z["routed"] + 3 * z["width"] * z["held"]
                    + 3 * z["shared"])}
    acts = {"kda": tokens * (2 * h + 2 * 5 * kw),      # q, k, v, g, gate
            "attn": tokens * (2 * h + qkv + z["hq"] * z["dv"]),
            "mlp": tokens * (2 * h + 3 * z["dense"]),
            "moe": tokens * 2 * h + pairs * (2 * h + 3 * z["width"])}
    flops = {"kda": tokens * per["kda"], "attn": tokens * per["attn"],
             "mlp": tokens * per["mlp"],
             "moe": tokens * per["moe_fixed"] + pairs * per["pair"]}
    out = {"kda": [], "attn": [], "mlp": [], "moe": []}
    for kind, name in stages:
        nbytes = itemsize * (weights[kind] + acts[kind])
        out[kind].append({"node": name, "scopes": [name],
                          "fwd": (flops[kind], nbytes),
                          "bwd": (2 * flops[kind], 2 * nbytes)})
    return out
