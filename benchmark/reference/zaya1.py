"""Plain reference of the ``zaya`` family (ZAYA1-8B:
https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json, the mixer of
"Compressed Convolutional Attention", arXiv:2510.04476, the router and the
residual scaling of the ZAYA1 report, arXiv:2511.17127): a straight float32
``jax.numpy`` program, contractions under ``highest`` precision,
independent of ``mxnet_tpu``.  One ROW (one sequence) at a time, as
``reference/qwen3_next.py``: ``row_loss`` is the loss of one sequence and a
job sums rows itself.

Every layer: ``h = R(h, CCA(N(h))); h = R(h, MoE(N(h)))`` with ``N(x; w) =
x * rsqrt(mean(x^2) + eps) * w`` and ``R(h, f) = (a_s * h + b_s) + (a_o * f
+ b_o)``, four learned (hidden,) vectors an add.

CCA, ``x = N(h)``, positions ``t``, zeros before the row's start, ``Hq``
query heads, ``Hkv`` key-value heads of ``d``, ``G = Hq / Hkv``:
``[q~ ; k~ ; v1 ; v2] = W_in x`` (``Hq d``, ``Hkv d``, ``Hkv d / 2`` twice);
``v_t = [v1_t ; v2_{t-1}]`` read as (Hkv, d): the first half of the value
heads is this position's, the second half the one before's (value shift);
``c = conv1(conv0([q~ ; k~]))``, ``conv0`` depthwise causal over
``cca_time0`` taps (weight (channels, taps)), ``conv1`` causal over
``cca_time1`` taps and grouped by head (weight (channels, d, taps): ``y_t[o]
= sum_j sum_{i in head(o)} W[o, i, j] x_{t-(taps-1)+j}[i]``); as heads, ``q
= c_q + (q~ + repeat(k~, G)) / 2``, ``k = c_k + (mean over each group's G
query heads of q~ + k~) / 2``; per head ``q = sqrt(d) q / |q|``, ``k =
sqrt(d) k / |k| * exp(temp[head])``; rotary embedding (rotate-half) on the
first ``partial_rotary_factor d`` features of every head, base the
``hybrid`` layer type's ``rope_theta``; causal ``softmax(q k^T d^-0.5) v``
over explicit scores, a block of query rows at a time, key-value head ``j``
serving query heads ``G j .. G j + G - 1``; ``CCA(x) = W_o o``.

Router and experts, ``x = N(h)``, ``r_prev`` the previous layer's router
state (none in layer 0): ``r = W_d x + carry * r_prev`` (``r`` is what the
next layer gets); ``s = W_3 gelu(W_2 gelu(W_1 N(r; w_r)))``; ``p =
softmax(s)`` over all ``num_routed_experts``; the token's expert is
``argmax(p + b)`` and its weight ``p`` of that expert, not renormalised;
``MoE(x) = p_e (silu(x Wg_e) * (x Wu_e)) Wdn_e``, as a plain loop over the
``num_experts`` experts held here (from ``expert_offset``) with masks: a
token whose expert is not held gets zeros.  The head is the embedding
(``embed_weight``, one leaf), over the chip's slice of the vocabulary.

Assumed, where the published config says nothing (also under ``assumed`` in
the configuration): no bias on any projection or convolution; the constant
``sqrt(d)``, the ``exp`` form of the temperature and 1e-6 under the root of
the heads' lengths; a vector ``carry`` (exponential depth averaging) and an
RMSNorm with weight before the router's MLP; the exact (tanh-free)
``gelu``; scale before bias in ``R``; no skip ("depth") expert in the 8B
(the catalog gives that to the 74B); no scaling of the embedding; the
balancing bias ``b`` seeded zero and moved by the load, the one rule the
ZAYA1 report's bias balancing is known by here: going backward ``b`` is
handed ``router_balance_rate x (share of the row's tokens that chose expert
e - 1 / num_routed_experts)`` as its gradient (a token's worth is the rate
over ``seq_len``, as the loss is a mean over the row), over all the experts,
held here or not, and the optimizer's own rule — momentum SGD here — moves
it as it moves every leaf: the load's error in the place of its sign in
arXiv:2408.15664's rule, since the seeded router's probabilities differ by
some 1e-4 and a fixed step of 1e-3 would throw every token from one expert
to the next; the rate (0.05) is this configuration's, not the report's;
initialisation normal(0, 0.02) for every matrix and convolution, norms and
scales 1, biases of ``R`` 0, ``temp`` 0, ``carry`` 0.5; a row is one sequence with no
document boundary; the loss is the mean over the step's tokens.
``rope_parameters.hybrid_sliding``, ``sliding_window`` (null) and
``attention_bias`` are published and unused.

Leaves carry the program's own argument names, so ``to_program`` /
``from_program`` only pass them on.
"""
import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

ATTENTION_BLOCK = 512      # query rows whose scores are held at once
HEAD_BLOCK = 2048          # rows whose logits are held at once


def _sizes(cfg):
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    return dict(
        h=cfg["hidden_size"], hq=hq, hkv=hkv, d=d, ql=hq * d, kl=hkv * d,
        t0=cfg["cca_time0"], t1=cfg["cca_time1"],
        rotary=int(d * cfg["partial_rotary_factor"]),
        theta=cfg["rope_parameters"]["hybrid"]["rope_theta"],
        r=cfg["router_hidden_size"], width=cfg["moe_intermediate_size"],
        held=cfg["num_experts"], routed=cfg["num_routed_experts"],
        k=cfg["num_experts_per_tok"])


def shapes(cfg):
    z = _sizes(cfg)
    h = z["h"]
    p = {"embed_weight": (cfg["vocab_size"], h), "head_norm_gamma": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        a, m = "l%d_cca_" % i, "l%d_moe_" % i
        p.update({a + "norm_gamma": (h,),
                  a + "in_proj_weight": (z["ql"] + 2 * z["kl"], h),
                  a + "conv0_weight": (z["ql"] + z["kl"], z["t0"]),
                  a + "conv1_weight": (z["ql"] + z["kl"], z["d"], z["t1"]),
                  a + "temp": (z["hkv"],),
                  a + "o_proj_weight": (h, z["ql"]),
                  m + "norm_gamma": (h,),
                  m + "router_down_weight": (z["r"], h),
                  m + "router_norm_gamma": (z["r"],),
                  m + "router_fc1_weight": (z["r"], z["r"]),
                  m + "router_fc2_weight": (z["r"], z["r"]),
                  m + "router_fc3_weight": (z["routed"], z["r"]),
                  m + "router_balance_bias": (z["routed"],),
                  m + "experts_gate_up_weight": (z["held"], h,
                                                 2 * z["width"]),
                  m + "experts_down_weight": (z["held"], z["width"], h)})
        if i:
            p[m + "router_carry"] = (z["r"],)
        for stage in (a, m):
            for name in ("res_scale", "res_bias", "out_scale", "out_bias"):
                p[stage + name] = (h,)
    return p, {}


def init(key, cfg):
    """Seeded weights in one traceable call."""
    pshapes, _ = shapes(cfg)
    params = {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith(("_gamma", "_scale")):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith(("_bias", "_temp")):
            params[name] = jnp.zeros(shape, jnp.float32)
        elif name.endswith("_router_carry"):
            params[name] = jnp.full(shape, 0.5, jnp.float32)
        else:
            params[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
    return params, {}


# -- the layers, one row (T, ...) at a time -----------------------------------

def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _linear(x, w, precision):
    """x (T, in) @ w (out, in)^T."""
    return C.matmul(x, w.T, precision)


def _residual(p, stage, h, f):
    return (p[stage + "res_scale"] * h + p[stage + "res_bias"]) \
        + (p[stage + "out_scale"] * f + p[stage + "out_bias"])


def _before(x, n):
    """x (T, C) ``n`` positions later, zeros first."""
    return jnp.pad(x, ((n, 0), (0, 0)))[:x.shape[0]]


def conv_depthwise(x, w):
    """x (T, C), w (C, taps): ``y_t = sum_j w[:, j] x_{t-(taps-1)+j}``."""
    taps = w.shape[1]
    return sum(_before(x, taps - 1 - j) * w[:, j] for j in range(taps))


def conv_grouped(x, w, groups, precision="f32"):
    """x (T, C), w (C, C / groups, taps): ``y_t[o] = sum_j sum_{i in
    group(o)} w[o, i, j] x_{t-(taps-1)+j}[i]``."""
    t, c = x.shape
    n, taps = c // groups, w.shape[2]
    y = 0.0
    for j in range(taps):
        xs = jnp.transpose(_before(x, taps - 1 - j).reshape(t, groups, n),
                           (1, 0, 2))                   # (g, T, in)
        wj = jnp.transpose(w[:, :, j].reshape(groups, n, n), (0, 2, 1))
        y = y + C.matmul(xs, wj, precision)             # (g, T, out)
    return jnp.transpose(y, (1, 0, 2)).reshape(t, c)


def unit_heads(x, log_scale=None):
    """x (T, heads, d) -> every head at length sqrt(d), times
    ``exp(log_scale[head])``."""
    y = x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6) \
        * x.shape[-1] ** 0.5
    return y if log_scale is None else y * jnp.exp(log_scale)[:, None]


def _rope(x, rotary, theta):
    """Rotate-half on the first ``rotary`` features of x (T, heads, d)."""
    half = rotary // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary:]], axis=-1)


def attention(q, k, v, precision="f32"):
    """Causal softmax attention over explicit scores: q (T, Hq, d), k and
    v (T, Hkv, d), key-value head j serving query heads G j .. G j + G - 1
    -> (T, Hq, d)."""
    t, hq, d = q.shape
    group = hq // k.shape[1]
    kt = jnp.transpose(jnp.repeat(k, group, axis=1), (1, 2, 0))  # (hq, d, T)
    vt = jnp.transpose(jnp.repeat(v, group, axis=1), (1, 0, 2))  # (hq, T, d)

    @jax.checkpoint
    def block(q_blk, first):
        """q_blk (n, hq, d) at positions first.. -> (n, hq, d)."""
        n = q_blk.shape[0]
        s = C.matmul(jnp.transpose(q_blk, (1, 0, 2)), kt, precision) \
            * d ** -0.5                                 # (hq, n, T)
        seen = (first + jnp.arange(n))[:, None] >= jnp.arange(t)[None, :]
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.transpose(C.matmul(prob, vt, precision), (1, 0, 2))

    n = ATTENTION_BLOCK if t % ATTENTION_BLOCK == 0 else t
    out = lax.map(lambda x: block(*x), (q.reshape(t // n, n, hq, d),
                                        jnp.arange(0, t, n)))
    return out.reshape(t, hq, d)


def qk_mean(latent, cfg):
    """The pre-convolution latents' mean, as heads filed under their key
    head: latent (T, Hq d + Hkv d) -> (to the queries (T, Hkv, G, d), to
    the keys (T, Hkv, 1, d))."""
    z = _sizes(cfg)
    t = latent.shape[0]
    q0 = latent[:, :z["ql"]].reshape(t, z["hkv"], z["hq"] // z["hkv"],
                                     z["d"])
    k0 = latent[:, z["ql"]:].reshape(t, z["hkv"], 1, z["d"])
    return (q0 + k0) / 2, (jnp.mean(q0, axis=2, keepdims=True) + k0) / 2


def values(v12):
    """v12 (T, Hkv d) = [v1 ; v2] -> [v1_t ; v2_{t-1}]."""
    half = v12.shape[1] // 2
    return jnp.concatenate([v12[:, :half], _before(v12[:, half:], 1)],
                           axis=-1)


def cca(p, a, x, cfg, precision="f32"):
    """The mixer on x (T, hidden)."""
    z = _sizes(cfg)
    t, hq, hkv, d, ql = x.shape[0], z["hq"], z["hkv"], z["d"], z["ql"]
    qkv = _linear(x, p[a + "in_proj_weight"], precision)
    latent = qkv[:, :ql + z["kl"]]
    v = values(qkv[:, ql + z["kl"]:]).reshape(t, hkv, d)
    c = conv_grouped(conv_depthwise(latent, p[a + "conv0_weight"]),
                     p[a + "conv1_weight"], hq + hkv, precision)
    mq, mk = qk_mean(latent, cfg)
    q = (c[:, :ql].reshape(mq.shape) + mq).reshape(t, hq, d)
    k = (c[:, ql:].reshape(mk.shape) + mk).reshape(t, hkv, d)
    q = _rope(unit_heads(q), z["rotary"], z["theta"])
    k = _rope(unit_heads(k, p[a + "temp"]), z["rotary"], z["theta"])
    o = attention(q, k, v, precision)
    return _linear(o.reshape(t, ql), p[a + "o_proj_weight"], precision)


def _gated_ffn(x, gate_up, down, precision):
    """gate_up (hidden, 2 width), down (width, hidden)."""
    gate, up = jnp.split(C.matmul(x, gate_up, precision), 2, axis=-1)
    return C.matmul(jax.nn.silu(gate) * up, down, precision)


def route(p, m, x, state, cfg, precision="f32"):
    """(weight, chosen) (T, k) and the router state (T, width): the experts
    chosen by probability plus bias, weighed by their probability."""
    r = _linear(x, p[m + "router_down_weight"], precision)
    if state is not None:
        r = r + p[m + "router_carry"] * state
    y = _norm(r, p[m + "router_norm_gamma"], cfg["rms_norm_eps"])
    for name in ("fc1", "fc2"):
        y = jax.nn.gelu(_linear(y, p[m + "router_%s_weight" % name],
                                precision), approximate=False)
    prob = jax.nn.softmax(_linear(y, p[m + "router_fc3_weight"], precision),
                          axis=-1)
    _, chosen = lax.top_k(prob + p[m + "router_balance_bias"],
                          cfg["num_experts_per_tok"])
    return jnp.take_along_axis(prob, chosen, axis=-1), chosen, r


@jax.custom_vjp
def pushing(out, bias, push):
    """``out`` as it is; going backward ``bias`` is handed ``push`` as its
    gradient."""
    return out


pushing.defvjp(lambda out, bias, push: (out, push),
               lambda push, g: (g, push, jnp.zeros_like(push)))


def expert_layer(p, m, x, state, cfg, precision="f32"):
    """The expert layer on x (T, hidden): (the held experts' part of the
    routed sum, the router state).  The load moves the balancing bias: see
    the module's docstring."""
    weight, chosen, r = route(p, m, x, state, cfg, precision)

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
    rate = cfg.get("router_balance_rate", 0.0) / cfg["seq_len"]
    if rate:
        experts = cfg["num_routed_experts"]
        load = jnp.sum(jax.nn.one_hot(chosen.reshape(-1), experts), axis=0)
        routed = pushing(routed, p[m + "router_balance_bias"],
                         rate * (load - chosen.size / experts))
    return routed, r


def _hidden(params, data, cfg, precision):
    """data (T,) token ids -> the normed stream the head reads (T, hidden)."""
    eps = cfg["rms_norm_eps"]
    h = jnp.take(params["embed_weight"], data.astype(jnp.int32), axis=0)
    state = None
    for i in range(cfg["num_hidden_layers"]):
        a, m = "l%d_cca_" % i, "l%d_moe_" % i

        @jax.checkpoint
        def layer(h, state, p, a=a, m=m):
            h = _residual(p, a, h, cca(p, a, _norm(h, p[a + "norm_gamma"],
                                                   eps), cfg, precision))
            out, state = expert_layer(
                p, m, _norm(h, p[m + "norm_gamma"], eps), state, cfg,
                precision)
            return _residual(p, m, h, out), state

        h, state = layer(h, state, {k: v for k, v in params.items()
                                    if k.startswith(a) or k.startswith(m)})
    return _norm(h, params["head_norm_gamma"], eps)


def logits(params, data, cfg, precision="f32"):
    """data (T,) token ids -> logits (T, vocab), the head tied to the
    embedding."""
    return _linear(_hidden(params, data, cfg, precision),
                   params["embed_weight"], precision)


def row_loss(cfg, precision="f32"):
    """``f(params, data (T,), label (T,)) -> sum of the row's cross-entropy
    / T``: summed over a step's rows and divided by their number it is the
    mean over the step's tokens.  The logits are taken ``HEAD_BLOCK`` rows
    at a time."""
    def f(params, data, label):
        h = _hidden(params, data, cfg, precision)
        t = h.shape[0]
        n = HEAD_BLOCK if t % HEAD_BLOCK == 0 else t

        @jax.checkpoint
        def block(total, x):
            rows, lab = x
            return total + C.softmax_ce_sum(
                _linear(rows, params["embed_weight"], precision), lab), None
        total, _ = lax.scan(block, jnp.zeros((), jnp.float32), (
            h.reshape(t // n, n, -1), label.reshape(t // n, n)))
        return total / t
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
    h, t, d, r = z["h"], cfg["seq_len"], z["d"], z["r"]
    latent = z["ql"] + z["kl"]
    proj = 2 * (h * (z["ql"] + 2 * z["kl"]) + z["ql"] * h)
    conv = 2 * (latent * z["t0"] + latent * d * z["t1"])
    core = 2 * z["hq"] * 2 * d * (t + 1) / 2            # causal: half T^2
    moe_fixed = 2 * (h * r + 2 * r * r + r * z["routed"])
    pair = 2 * 3 * h * z["width"]
    landed = z["k"] * z["held"] / z["routed"]
    return {"cca": proj + conv + core, "moe": moe_fixed + landed * pair,
            "pair": pair, "moe_fixed": moe_fixed}


def _stages(cfg):
    """[(kind, stage name)] in the graph's order."""
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out += [("cca", "l%d_cca" % i), ("moe", "l%d_moe" % i)]
    return out


def flops_per_item(cfg):
    """Model FLOPs of one token, forward: CCA's projections, its two
    convolutions and the latent's causal attention at ``seq_len``
    positions, the router, the routed experts at their expected share of
    pairs (top-k x held / routed), and the tied head over the vocabulary
    slice."""
    per = _layer_flops(cfg)
    return int(2 * cfg["hidden_size"] * cfg["vocab_size"]
               + sum(per[kind] for kind, _ in _stages(cfg)))


def node_work(cfg, rows, itemsize=2, pairs_here=None):
    """Per stage of the graph (``l<i>_cca``, ``l<i>_moe``: the program's
    ``mirror_stage`` scopes), the work one step of ``rows`` sequences
    needs: {kind: [{"node", "scopes", "fwd": (flops, bytes), "bwd": (flops,
    bytes)}]} for the kinds ``cca`` and ``moe``, from shapes alone.
    Backward is twice the forward's contractions; the forward a
    rematerialising step runs again is not work the model needs.  Bytes:
    the stage's weights once, its input and output and the activations it
    has to write and read (CCA: the projection's result, the convolved
    latent and the attention's result).  ``pairs_here``: (token, expert)
    pairs a step really landed on held experts, all expert layers together
    (default: the expectation)."""
    per = _layer_flops(cfg)
    z = _sizes(cfg)
    h, r, latent = z["h"], z["r"], z["ql"] + z["kl"]
    tokens = rows * cfg["seq_len"]
    stages = _stages(cfg)
    layers = cfg["num_hidden_layers"]
    if pairs_here is None:
        pairs_here = layers * tokens * z["k"] * z["held"] / z["routed"]
    pairs = pairs_here / max(layers, 1)
    weights = {
        "cca": h * (z["ql"] + 2 * z["kl"]) + z["ql"] * h
        + latent * (z["t0"] + z["d"] * z["t1"]),
        "moe": h * r + 2 * r * r + r * z["routed"]
        + 3 * h * z["width"] * z["held"]}
    acts = {"cca": tokens * (2 * h + 2 * (z["ql"] + 2 * z["kl"])
                             + 2 * latent + 2 * z["ql"]),
            "moe": tokens * 2 * h + pairs * (2 * h + 3 * z["width"])}
    flops = {"cca": tokens * per["cca"],
             "moe": tokens * per["moe_fixed"] + pairs * per["pair"]}
    out = {"cca": [], "moe": []}
    for kind, name in stages:
        nbytes = itemsize * (weights[kind] + acts[kind])
        out[kind].append({"node": name, "scopes": [name],
                          "fwd": (flops[kind], nbytes),
                          "bwd": (2 * flops[kind], 2 * nbytes)})
    return out
