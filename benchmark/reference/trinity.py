"""Plain reference of the ``afmoe`` family (Trinity-Mini:
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json, the
layer equations of the family's public modelling code): a straight float32
``jax.numpy`` program, contractions under ``highest`` precision,
independent of ``mxnet_tpu``.  One ROW (one sequence) at a time, as
``reference/qwen3_next.py``: ``row_loss`` is the loss of one sequence and a
job sums rows itself.

The stream starts as ``embed[id] * sqrt(hidden_size)`` (``mup_enabled``).
Layer i: ``h = h + N2(attention_i(N1(h)))``, ``h = h + N4(ffn_i(N3(h)))``
with ``N(x; w) = x * rsqrt(mean(x^2) + eps) * w`` — four norms a layer
(``input_layernorm``, ``post_attention_layernorm``, ``pre_mlp_layernorm``,
``post_mlp_layernorm``): the branch's output is normed before the add.

Attention, every layer, ``Hq`` query heads over ``Hkv`` key-value heads of
``d``: ``q = N_d(W_q x)``, ``k = N_d(W_k x)`` per head, ``v = W_v x``, ``g =
W_gate x`` (its own projection, as wide as q).  Where ``layer_types[i]`` is
``sliding_attention``: rotary embedding (rotate-half over all ``d``
features, theta ``rope_theta``) on q and k, and position ``p`` sees the keys
``p - sliding_window < j <= p``; where it is ``full_attention``: NO rotary
embedding, the whole causal prefix.  Both: ``softmax(q k^T d^-0.5)`` over
explicit masked scores, a block of query rows at a time against ALL the
keys, key-value head ``j`` serving query heads ``G j .. G j + G - 1``;
``attention(x) = W_o (o * sigmoid(g))``.

``ffn_i``: a dense SwiGLU of ``intermediate_size`` for ``i <
num_dense_layers``; after them ``shared(x) + routed(x)``: one ungated SwiGLU
of ``moe_intermediate_size x num_shared_experts`` and the routed experts:
``s = sigmoid(W_r x)`` over all ``num_routed_experts``; the
``num_experts_per_tok`` with the largest ``s + expert_bias`` are chosen (one
group: the grouped top-k is the plain one; the bias enters the choice
alone), weighed by ``s_e / (sum of the chosen + 1e-20) * route_scale``
(``route_norm``); a plain loop over the ``num_experts`` experts held here
(from ``expert_offset``) with masks.  Final RMSNorm, untied head over the
chip's slice of the vocabulary.

Departures from the family's code, also under ``assumed`` in the
configuration: initialisation normal(0, 0.02) for every matrix, norm weights
1 but for the two OUTPUT norms of a layer, whose weights start at
``output_norm_init`` (the cell's 0.125 = 1 / sqrt(2 x 32 layers): at 1 the
normed mean of a window's values is as large as a token's own embedding and
every token ranks the experts alike from the first step); ``expert_bias``
normal(0, 0.01) from the seed and untrained (the family's
buffer starts at zero and a rule outside the gradient moves it with the
load, rate ``load_balance_coeff``: neither is in the config's shape keys,
and a zero bias would leave the choice to the scores alone; it enters the
choice alone, so its gradient is zero and SGD leaves it as seeded); no bias
on any projection; the router and its scores float32 (as the family's code
casts them); a row is one sequence with no document boundary and positions
from 0; the loss is the mean over the step's tokens.  ``rope_scaling``
(null), ``max_position_embeddings``, ``use_grouped_mm`` and
``load_balance_coeff`` are published and unused.

Leaves carry the program's own argument names, so ``to_program`` /
``from_program`` only pass them on.
"""
import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

ATTENTION_BLOCK = 512      # query rows whose scores are held at once
HEAD_BLOCK = 2048          # rows whose logits are held at once


def _is_sliding(i, cfg):
    kind = cfg["layer_types"][i]
    if kind not in ("sliding_attention", "full_attention"):
        raise ValueError("layer_types[%d] is %r" % (i, kind))
    return kind == "sliding_attention"


def _is_dense(i, cfg):
    return i < cfg["num_dense_layers"]


def _sizes(cfg):
    return dict(
        h=cfg["hidden_size"], hq=cfg["num_attention_heads"],
        hkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
        dense=cfg["intermediate_size"], width=cfg["moe_intermediate_size"],
        shared=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        held=cfg["num_experts"], routed=cfg["num_routed_experts"],
        k=cfg["num_experts_per_tok"])


def _stages(cfg):
    """[(kind, stage name)] in the graph's order."""
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out.append(("swa", "l%d_swa" % i) if _is_sliding(i, cfg)
                   else ("attn", "l%d_attn" % i))
        out.append(("mlp", "l%d_mlp" % i) if _is_dense(i, cfg)
                   else ("moe", "l%d_moe" % i))
    return out


def shapes(cfg):
    z = _sizes(cfg)
    h, v, d = z["h"], cfg["vocab_size"], z["d"]
    p = {"embed_weight": (v, h), "head_norm_gamma": (h,),
         "head_weight": (v, h)}
    for kind, stage in _stages(cfg):
        s = stage + "_"
        p.update({s + "norm_gamma": (h,), s + "post_norm_gamma": (h,)})
        if kind in ("swa", "attn"):
            p.update({s + "q_proj_weight": (z["hq"] * d, h),
                      s + "k_proj_weight": (z["hkv"] * d, h),
                      s + "v_proj_weight": (z["hkv"] * d, h),
                      s + "gate_proj_weight": (z["hq"] * d, h),
                      s + "o_proj_weight": (h, z["hq"] * d),
                      s + "q_norm_gamma": (d,), s + "k_norm_gamma": (d,)})
        elif kind == "mlp":
            p.update({s + "gate_up_weight": (2 * z["dense"], h),
                      s + "down_weight": (h, z["dense"])})
        else:
            p.update({s + "router_weight": (z["routed"], h),
                      s + "expert_bias": (z["routed"],),
                      s + "experts_gate_up_weight": (z["held"], h,
                                                     2 * z["width"]),
                      s + "experts_down_weight": (z["held"], z["width"], h),
                      s + "shared_gate_up_weight": (2 * z["shared"], h),
                      s + "shared_down_weight": (h, z["shared"])})
    return p, {}


def init(key, cfg):
    """Seeded weights in one traceable call."""
    pshapes, _ = shapes(cfg)
    params = {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_post_norm_gamma"):
            params[name] = jnp.full(shape, cfg.get("output_norm_init", 1.0),
                                    jnp.float32)
        elif name.endswith("_gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_expert_bias"):
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


def _rope(x, theta):
    """Rotate-half over every feature of x (T, heads, d)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def seen(first, n, t, window):
    """(n, t) mask: which of ``t`` keys the ``n`` positions from ``first``
    on see — their causal prefix, the last ``window`` keys of it where a
    window is given."""
    p = (first + jnp.arange(n))[:, None]
    j = jnp.arange(t)[None, :]
    return (j <= p) if window is None else (j <= p) & (j > p - window)


def softmax_attention(q, k, v, window=None, precision="f32"):
    """Masked softmax attention over explicit scores: q (T, Hq, d), k and
    v (T, Hkv, d), key-value head j serving query heads G j .. G j + G - 1
    -> (T, Hq, d)."""
    t, hq, d = q.shape
    group = hq // k.shape[1]
    kt = jnp.transpose(jnp.repeat(k, group, axis=1), (1, 2, 0))  # (hq, d, T)
    vt = jnp.transpose(jnp.repeat(v, group, axis=1), (1, 0, 2))  # (hq, T, d)

    @jax.checkpoint
    def block(q_blk, first):
        """q_blk (n, hq, d) at positions first.. -> (n, hq, d)."""
        s = C.matmul(jnp.transpose(q_blk, (1, 0, 2)), kt, precision) \
            * d ** -0.5                                 # (hq, n, T)
        prob = jax.nn.softmax(jnp.where(
            seen(first, q_blk.shape[0], t, window), s, -jnp.inf), axis=-1)
        return jnp.transpose(C.matmul(prob, vt, precision), (1, 0, 2))

    n = ATTENTION_BLOCK if t % ATTENTION_BLOCK == 0 else t
    out = lax.map(lambda x: block(*x), (q.reshape(t // n, n, hq, d),
                                        jnp.arange(0, t, n)))
    return out.reshape(t, hq, d)


def attention(p, a, x, cfg, sliding, precision="f32"):
    """The mixer on x (T, hidden)."""
    z = _sizes(cfg)
    t, hq, hkv, d = x.shape[0], z["hq"], z["hkv"], z["d"]
    eps = cfg["rms_norm_eps"]
    q = _norm(_linear(x, p[a + "q_proj_weight"], precision)
              .reshape(t, hq, d), p[a + "q_norm_gamma"], eps)
    k = _norm(_linear(x, p[a + "k_proj_weight"], precision)
              .reshape(t, hkv, d), p[a + "k_norm_gamma"], eps)
    v = _linear(x, p[a + "v_proj_weight"], precision).reshape(t, hkv, d)
    gate = _linear(x, p[a + "gate_proj_weight"], precision)
    if sliding:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    o = softmax_attention(q, k, v, cfg["sliding_window"] if sliding
                          else None, precision)
    return _linear(o.reshape(t, hq * d) * jax.nn.sigmoid(gate),
                   p[a + "o_proj_weight"], precision)


def _gated_ffn(x, gate_up, down, precision):
    """gate_up (hidden, 2 width), down (width, hidden)."""
    gate, up = jnp.split(C.matmul(x, gate_up, precision), 2, axis=-1)
    return C.matmul(jax.nn.silu(gate) * up, down, precision)


def route(p, m, x, cfg, precision="f32"):
    """(weight, chosen) (T, k): the experts chosen by score plus bias, and
    their scores renormalised and scaled."""
    score = jax.nn.sigmoid(_linear(x, p[m + "router_weight"], precision))
    _, chosen = lax.top_k(score + p[m + "expert_bias"],
                          cfg["num_experts_per_tok"])
    weight = jnp.take_along_axis(score, chosen, axis=-1)
    if cfg["route_norm"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return weight * cfg["route_scale"], chosen


def routed_part(p, m, x, cfg, precision="f32"):
    """What the experts held here give of the routed sum on x (T,
    hidden)."""
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
    return routed


def shared_part(p, m, x, precision="f32"):
    return _gated_ffn(x, p[m + "shared_gate_up_weight"].T,
                      p[m + "shared_down_weight"].T, precision)


def expert_layer(p, m, x, cfg, precision="f32"):
    """The expert layer on x (T, hidden): the shared expert plus the held
    experts' part of the routed sum."""
    return shared_part(p, m, x, precision) \
        + routed_part(p, m, x, cfg, precision)


def _hidden(params, data, cfg, precision):
    """data (T,) token ids -> the normed stream the head reads (T, hidden)."""
    eps = cfg["rms_norm_eps"]
    h = jnp.take(params["embed_weight"], data.astype(jnp.int32), axis=0)
    if cfg["mup_enabled"]:
        h = h * cfg["hidden_size"] ** 0.5
    stages = _stages(cfg)
    for (akind, a), (mkind, m) in zip(stages[::2], stages[1::2]):
        a, m = a + "_", m + "_"

        @jax.checkpoint
        def layer(h, p, a=a, m=m, akind=akind, mkind=mkind):
            out = attention(p, a, _norm(h, p[a + "norm_gamma"], eps), cfg,
                            akind == "swa", precision)
            h = h + _norm(out, p[a + "post_norm_gamma"], eps)
            x = _norm(h, p[m + "norm_gamma"], eps)
            if mkind == "mlp":
                out = _gated_ffn(x, p[m + "gate_up_weight"].T,
                                 p[m + "down_weight"].T, precision)
            else:
                out = expert_layer(p, m, x, cfg, precision)
            return h + _norm(out, p[m + "post_norm_gamma"], eps)

        h = layer(h, {k: v for k, v in params.items()
                      if k.startswith(a) or k.startswith(m)})
    return _norm(h, params["head_norm_gamma"], eps)


def logits(params, data, cfg, precision="f32"):
    """data (T,) token ids -> logits (T, vocab)."""
    return _linear(_hidden(params, data, cfg, precision),
                   params["head_weight"], precision)


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
                _linear(rows, params["head_weight"], precision), lab), None
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

def seen_pairs(t, window=None):
    """(query, key) pairs of ``t`` positions that see each other: the
    causal triangle, or the band of the last ``window`` keys of it."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _layer_flops(cfg):
    """Forward FLOPs a token of one stage of each kind (2 a MAC): a
    windowed stage's scores from the window's band, not from the triangle;
    the routed experts at the pairs that land on held experts in
    expectation."""
    z = _sizes(cfg)
    h, t, d = z["h"], cfg["seq_len"], z["d"]
    proj = 2 * h * d * (3 * z["hq"] + 2 * z["hkv"])     # q, gate, o; k, v
    # q k^T and p v, per query head and pair that see each other
    core = {kind: 2 * 2 * z["hq"] * d * seen_pairs(t, window) / t
            for kind, window in (("swa", cfg["sliding_window"]),
                                 ("attn", None))}
    landed = z["k"] * z["held"] / z["routed"]
    moe_fixed = 2 * h * (z["routed"] + 3 * z["shared"])
    pair = 2 * 3 * h * z["width"]
    return {"swa": proj + core["swa"], "attn": proj + core["attn"],
            "mlp": 2 * 3 * h * z["dense"], "moe": moe_fixed + landed * pair,
            "pair": pair, "moe_fixed": moe_fixed}


def flops_per_item(cfg):
    """Model FLOPs of one token, forward: every projection, the sliding
    stages' scores over the window's band and the full stages' over the
    causal triangle at ``seq_len`` positions, the dense feed-forwards, the
    router, the shared expert, the routed experts at their expected share
    of pairs (top-k x held / routed), and the head over the vocabulary
    slice."""
    per = _layer_flops(cfg)
    return int(2 * cfg["hidden_size"] * cfg["vocab_size"]
               + sum(per[kind] for kind, _ in _stages(cfg)))


def node_work(cfg, rows, itemsize=2, pairs_here=None):
    """Per stage of the graph (``l<i>_swa``, ``l<i>_attn``, ``l<i>_mlp``,
    ``l<i>_moe``: the program's ``mirror_stage`` scopes), the work one step
    of ``rows`` sequences needs: {kind: [{"node", "scopes", "fwd": (flops,
    bytes), "bwd": (flops, bytes)}]} for the kinds ``swa`` (the
    sliding-window stages: their scores are the band's), ``attn`` (the full
    stages), ``mlp`` and ``moe``, from shapes alone.  Backward is twice the
    forward's contractions; the forward a rematerialising step runs again
    is not work the model needs.  Bytes: the stage's weights once, its
    input and output and the activations it has to write and read (q, k,
    v, the gate and the attention's result).  ``pairs_here``: (token,
    expert) pairs a step really landed on held experts, all expert layers
    together (default: the expectation)."""
    per = _layer_flops(cfg)
    z = _sizes(cfg)
    h, d = z["h"], z["d"]
    tokens = rows * cfg["seq_len"]
    stages = _stages(cfg)
    layers = sum(kind == "moe" for kind, _ in stages)
    if pairs_here is None:
        pairs_here = layers * tokens * z["k"] * z["held"] / z["routed"]
    pairs = pairs_here / max(layers, 1)
    mixer = h * d * (3 * z["hq"] + 2 * z["hkv"])
    mixed = tokens * (2 * h + d * (3 * z["hq"] + 2 * z["hkv"]))
    weights = {"swa": mixer, "attn": mixer, "mlp": 3 * h * z["dense"],
               "moe": h * (z["routed"] + 3 * z["width"] * z["held"]
                           + 3 * z["shared"])}
    acts = {"swa": mixed, "attn": mixed,
            "mlp": tokens * (2 * h + 3 * z["dense"]),
            "moe": tokens * 2 * h + pairs * (2 * h + 3 * z["width"])}
    flops = {"swa": tokens * per["swa"], "attn": tokens * per["attn"],
             "mlp": tokens * per["mlp"],
             "moe": tokens * per["moe_fixed"] + pairs * per["pair"]}
    out = {"swa": [], "attn": [], "mlp": [], "moe": []}
    for kind, name in stages:
        nbytes = itemsize * (weights[kind] + acts[kind])
        out[kind].append({"node": name, "scopes": [name],
                          "fwd": (flops[kind], nbytes),
                          "bwd": (2 * flops[kind], 2 * nbytes)})
    return out
