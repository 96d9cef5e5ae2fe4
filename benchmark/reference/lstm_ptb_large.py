"""Plain reference of the ``lstm_ptb_large`` configuration: the "large"
regularised LSTM of Zaremba, Sutskever, Vinyals, arXiv:1409.2329 (2 layers
x 1500 units, embedding 1500, vocabulary 10,000, 35 unrolled steps), as a
straight ``jax.numpy`` float32 program independent of ``mxnet_tpu``.

Leaves are the published model's own matrices (``l0_i2h_weight`` ...).
The program keeps every LSTM matrix in one flat vector in cuDNN's order
(all layers' [W_i2h, W_h2h], then all layers' [b_i2h, b_h2h]; gates i, f,
g, o): ``to_program`` / ``from_program`` pack and unpack that order, which
is the documented layout of the fused RNN operator, not code of the
program.

Departures from the paper, listed in the configuration under ``assumed``:
dropout 0 (the program draws its masks from its own key stream, which a
reference cannot share), the initial states are learnable leaves of the
batch's shape (as ``lstm_lm_sym`` declares them) and are not carried from
batch to batch, and the loss is SoftmaxOutput's sum over all T x B rows
rescaled by 1/B.
"""
import jax
import jax.numpy as jnp
from jax import lax

from . import common as C


def shapes(cfg):
    h, e, v, n = cfg["num_hidden"], cfg["num_embed"], cfg["vocab_size"], \
        cfg["num_layers"]
    b = cfg["batch"]
    p = {"embed_weight": (v, e), "pred_weight": (v, h), "pred_bias": (v,),
         "init_h": (n, b, h), "init_c": (n, b, h)}
    for layer in range(n):
        p["l%d_i2h_weight" % layer] = (4 * h, e if layer == 0 else h)
        p["l%d_h2h_weight" % layer] = (4 * h, h)
        p["l%d_i2h_bias" % layer] = (4 * h,)
        p["l%d_h2h_bias" % layer] = (4 * h,)
    return p, {}


def init(key, cfg):
    """Seeded weights in one traceable call: uniform(-s, s) with the
    paper's s = 0.04 for every matrix and bias, initial states small."""
    pshapes, _ = shapes(cfg)
    params = {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        s = 0.01 if name.startswith("init_") else cfg["init_scale"]
        params[name] = jax.random.uniform(jax.random.fold_in(key, i), shape,
                                          jnp.float32, -s, s)
    return params, {}


def _layer(x, h0, c0, w_i2h, w_h2h, b_i2h, b_h2h, precision):
    """x (T, B, I) -> hs (T, B, H)."""
    t, b, _ = x.shape
    xp = C.matmul(x.reshape(t * b, -1), w_i2h.T, precision) \
        .reshape(t, b, -1) + b_i2h

    def cell(carry, xp_t):
        h, c = carry
        gates = xp_t + C.matmul(h, w_h2h.T, precision) + b_h2h
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    _, hs = lax.scan(cell, (h0, c0), xp)
    return hs


def logits(params, data, cfg, precision="f32"):
    """data (B, T) int tokens -> logits (T*B, V), time-major rows."""
    x = jnp.take(params["embed_weight"], data.astype(jnp.int32), axis=0)
    x = jnp.swapaxes(x, 0, 1)
    for layer in range(cfg["num_layers"]):
        x = _layer(x, params["init_h"][layer], params["init_c"][layer],
                   params["l%d_i2h_weight" % layer],
                   params["l%d_h2h_weight" % layer],
                   params["l%d_i2h_bias" % layer],
                   params["l%d_h2h_bias" % layer], precision)
    x = x.reshape(-1, cfg["num_hidden"])
    return C.matmul(x, params["pred_weight"].T, precision) \
        + params["pred_bias"]


def loss_fn(cfg, precision="f32"):
    """``f(params, aux, batch) -> (sum CE over T*B rows, (aux, T*B))`` for
    ``batch = {"data": (B, T) tokens, "softmax_label": (B, T) tokens}``."""
    def f(params, aux, batch):
        out = logits(params, batch["data"], cfg, precision)
        labels = jnp.swapaxes(batch["softmax_label"], 0, 1).reshape(-1)
        return C.softmax_ce_sum(out, labels), (aux, out.shape[0])
    return f


def _order(cfg):
    n = cfg["num_layers"]
    return [("l%d_%s_weight" % (l, k)) for l in range(n)
            for k in ("i2h", "h2h")] + \
        [("l%d_%s_bias" % (l, k)) for l in range(n) for k in ("i2h", "h2h")]


def to_program(params, aux, cfg):
    """The program's arguments from the reference's leaves."""
    order = _order(cfg)
    flat = jnp.concatenate([params[k].reshape(-1) for k in order])
    out = {k: params[k] for k in ("embed_weight", "pred_weight", "pred_bias")}
    out["lstm_parameters"] = flat
    out["lstm_init_h"], out["lstm_init_c"] = params["init_h"], \
        params["init_c"]
    return out, dict(aux)


def from_program(arg_params, cfg):
    """The reference's leaves from the program's arguments."""
    pshapes, _ = shapes(cfg)
    out = {k: v for k, v in arg_params.items()
           if k in ("embed_weight", "pred_weight", "pred_bias")}
    out["init_h"], out["init_c"] = arg_params["lstm_init_h"], \
        arg_params["lstm_init_c"]
    flat, off = arg_params["lstm_parameters"], 0
    for name in _order(cfg):
        size = 1
        for d in pshapes[name]:
            size *= d
        out[name] = flat[off:off + size].reshape(pshapes[name])
        off += size
    assert off == flat.shape[0], (off, flat.shape)
    return out


def flops_per_item(cfg):
    """Model FLOPs of one token, forward: 2 x every LSTM parameter (each
    is applied once a token; biases billed as the operator's count does)
    plus the 10,000-wide head; the embedding is a lookup."""
    h, e, v = cfg["num_hidden"], cfg["num_embed"], cfg["vocab_size"]
    total = 0
    for layer in range(cfg["num_layers"]):
        total += 2 * 4 * h * ((e if layer == 0 else h) + h + 2)
    return total + 2 * h * v + v


def node_work(cfg, rows, itemsize=2):
    """The fused RNN node's work for one step of ``rows`` sequences (see
    ``resnet50.node_work``): gate contractions forward, twice that
    backward; bytes are the weights, the layer inputs and outputs and the
    saved gates."""
    h, e, t = cfg["num_hidden"], cfg["num_embed"], cfg["seq_len"]
    flops = nbytes = 0
    for layer in range(cfg["num_layers"]):
        i = e if layer == 0 else h
        flops += 2 * t * rows * 4 * h * (i + h)
        nbytes += itemsize * (4 * h * (i + h) + t * rows * (i + h + 4 * h))
    return {"rnn": [{"node": "lstm", "scopes": ["lstm"],
                     "fwd": (flops, nbytes),
                     "bwd": (2 * flops, 2 * nbytes)}]}
