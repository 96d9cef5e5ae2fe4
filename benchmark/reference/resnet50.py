"""Plain reference of the ``resnet50`` configuration: ResNet v2
(pre-activation, bottleneck) as He et al. arXiv:1512.03385 Table 1 /
arXiv:1603.05027 give it and MXNet's
``example/image-classification/symbols/resnet.py --num-layers 50`` builds
it.  Straight ``jax.numpy``, float32, no kernels; independent of
``mxnet_tpu``.  Leaves carry MXNet's argument names (``stage1_unit1_conv1_weight``
...), a convention of the published symbol, so no table maps them.

Departures from the paper, all the published symbol's own: a BatchNorm with
fixed gamma on the input (``bn_data``), BatchNorm eps 2e-5, the loss is
SoftmaxOutput (sum of cross-entropies; the optimizer rescales by 1/batch).
Each residual unit is rematerialised in the backward pass
(``jax.checkpoint``) so that batch 256 in float32 fits one chip; that
changes memory, not values.
"""
import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

EPS = 2e-5
BN_MOMENTUM = 0.9


def _units(cfg):
    """[(name, filters, stride, dim_match)] in the published order."""
    out = []
    for i, n in enumerate(cfg["units"]):
        f = cfg["filter_list"][i + 1]
        for j in range(n):
            out.append(("stage%d_unit%d" % (i + 1, j + 1), f,
                        (1 if i == 0 else 2) if j == 0 else 1, j > 0))
    return out


def shapes(cfg):
    """({param: shape}, {aux: shape}) of the whole net."""
    p, a = {}, {}

    def bn(name, c):
        p[name + "_gamma"], p[name + "_beta"] = (c,), (c,)
        a[name + "_moving_mean"], a[name + "_moving_var"] = (c,), (c,)

    f0 = cfg["filter_list"][0]
    bn("bn_data", cfg["image_shape"][0])
    p["conv0_weight"] = (f0, cfg["image_shape"][0], 7, 7)
    bn("bn0", f0)
    cin = f0
    for name, f, _, match in _units(cfg):
        mid = f // 4
        bn(name + "_bn1", cin)
        p[name + "_conv1_weight"] = (mid, cin, 1, 1)
        bn(name + "_bn2", mid)
        p[name + "_conv2_weight"] = (mid, mid, 3, 3)
        bn(name + "_bn3", mid)
        p[name + "_conv3_weight"] = (f, mid, 1, 1)
        if not match:
            p[name + "_sc_weight"] = (f, cin, 1, 1)
        cin = f
    bn("bn1", cin)
    p["fc1_weight"], p["fc1_bias"] = (cfg["num_classes"], cin), \
        (cfg["num_classes"],)
    return p, a


def init(key, cfg):
    """Seeded weights in one traceable call: He-normal fan-in for
    convolutions and the head (MXNet's Xavier(gaussian, in, 2)), gamma 1
    (jittered so that no two leaves agree by construction), beta and bias
    small, moving mean 0 / variance 1.  ``residual_init_scale`` (default 1)
    scales the last convolution of every residual branch: the softened
    zero-init-residual of Goyal et al., arXiv:1706.02677 — at 1 a sixteen
    unit random net is so ill-conditioned that bfloat16 rounding alone
    turns the median leaf's first gradient by 0.6 of its norm (PERF.md)."""
    pshapes, ashapes = shapes(cfg)
    branch = float(cfg.get("residual_init_scale", 1.0))
    params, aux = {}, {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_weight"):
            fan_in = 1
            for d in shape[1:]:
                fan_in *= d
            params[name] = jax.random.normal(k, shape, jnp.float32) \
                * (2.0 / fan_in) ** 0.5 \
                * (branch if name.endswith("_conv3_weight") else 1.0)
        elif name.endswith("_gamma"):
            params[name] = 1.0 + 0.1 * jax.random.uniform(
                k, shape, jnp.float32, -1.0, 1.0)
        else:
            params[name] = 0.1 * jax.random.uniform(
                k, shape, jnp.float32, -1.0, 1.0)
    for name, shape in ashapes.items():
        aux[name] = (jnp.ones if name.endswith("_var") else jnp.zeros)(
            shape, jnp.float32)
    return params, aux


def _bn(x, p, aux, new_aux, name, fix_gamma=False):
    mean = jnp.mean(x, axis=(0, 2, 3))
    var = jnp.var(x, axis=(0, 2, 3))
    gamma = jnp.ones_like(p[name + "_gamma"]) if fix_gamma \
        else p[name + "_gamma"]
    new_aux[name + "_moving_mean"] = aux[name + "_moving_mean"] \
        * BN_MOMENTUM + lax.stop_gradient(mean) * (1 - BN_MOMENTUM)
    new_aux[name + "_moving_var"] = aux[name + "_moving_var"] \
        * BN_MOMENTUM + lax.stop_gradient(var) * (1 - BN_MOMENTUM)
    b = (1, -1, 1, 1)
    return (x - mean.reshape(b)) * lax.rsqrt(var + EPS).reshape(b) \
        * gamma.reshape(b) + p[name + "_beta"].reshape(b)


def _unit(x, p, aux, name, stride, match, precision):
    new_aux = {}
    s = (stride, stride)
    act1 = jax.nn.relu(_bn(x, p, aux, new_aux, name + "_bn1"))
    y = C.conv2d(act1, p[name + "_conv1_weight"], (1, 1), (0, 0), precision)
    y = jax.nn.relu(_bn(y, p, aux, new_aux, name + "_bn2"))
    y = C.conv2d(y, p[name + "_conv2_weight"], s, (1, 1), precision)
    y = jax.nn.relu(_bn(y, p, aux, new_aux, name + "_bn3"))
    y = C.conv2d(y, p[name + "_conv3_weight"], (1, 1), (0, 0), precision)
    short = x if match else C.conv2d(act1, p[name + "_sc_weight"], s,
                                     (0, 0), precision)
    return y + short, new_aux


def input_transform(raw, cfg):
    """uint8 NHWC as the decoder delivers it -> normalised float32 NCHW."""
    mean = jnp.asarray(cfg["pixel_mean"], jnp.float32)
    std = jnp.asarray(cfg["pixel_std"], jnp.float32)
    return jnp.transpose((raw.astype(jnp.float32) - mean) / std, (0, 3, 1, 2))


def logits(params, aux, data, cfg, precision="f32"):
    """Training-mode forward from the raw uint8 NHWC batch.
    Returns (logits, new_aux)."""
    new_aux = {}
    x = input_transform(data, cfg)
    x = _bn(x, params, aux, new_aux, "bn_data", fix_gamma=True)
    x = C.conv2d(x, params["conv0_weight"], (2, 2), (3, 3), precision)
    x = jax.nn.relu(_bn(x, params, aux, new_aux, "bn0"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          ((0, 0), (0, 0), (1, 1), (1, 1)))
    for name, _, stride, match in _units(cfg):
        keys = [k for k in params if k.startswith(name + "_")]
        akeys = [k for k in aux if k.startswith(name + "_")]
        unit = jax.checkpoint(
            lambda x_, p_, a_, _n=name, _s=stride, _m=match:
            _unit(x_, p_, a_, _n, _s, _m, precision))
        x, ua = unit(x, {k: params[k] for k in keys},
                     {k: aux[k] for k in akeys})
        new_aux.update(ua)
    x = jax.nn.relu(_bn(x, params, aux, new_aux, "bn1"))
    x = jnp.mean(x, axis=(2, 3))
    out = C.matmul(x, params["fc1_weight"].T, precision) + params["fc1_bias"]
    return out, new_aux


def loss_fn(cfg, precision="f32"):
    """``f(params, aux, batch) -> (sum CE, (new_aux, rows))`` for
    ``batch = {"data": uint8 NHWC, "softmax_label": class ids}``."""
    def f(params, aux, batch):
        out, new_aux = logits(params, aux, batch["data"], cfg, precision)
        return C.softmax_ce_sum(out, batch["softmax_label"]), \
            (new_aux, out.shape[0])
    return f


def to_program(params, aux, cfg):
    """The program's arguments from the reference's leaves: identical."""
    return dict(params), dict(aux)


def from_program(arg_params, cfg):
    """The reference's leaves from the program's arguments: identical."""
    return dict(arg_params)


def flops_per_item(cfg):
    """Model FLOPs of one image, forward (2 x MACs of every convolution and
    the head; elementwise work is not billed)."""
    h = cfg["image_shape"][1]
    total = 0

    def conv(cout, cin, k, hout):
        return 2 * cout * cin * k * k * hout * hout

    f0 = cfg["filter_list"][0]
    h = (h + 6 - 7) // 2 + 1
    total += conv(f0, cfg["image_shape"][0], 7, h)
    h = (h + 2 - 3) // 2 + 1
    cin = f0
    for _, f, stride, match in _units(cfg):
        mid = f // 4
        hout = (h - 1) // stride + 1
        total += conv(mid, cin, 1, h) + conv(mid, mid, 3, hout) \
            + conv(f, mid, 1, hout)
        if not match:
            total += conv(f, cin, 1, hout)
        h, cin = hout, f
    total += 2 * cin * cfg["num_classes"] + cfg["num_classes"]
    return total


def node_work(cfg, rows, itemsize=2):
    """Per graph node, the work one step needs of it for ``rows`` images at
    ``itemsize`` bytes an activation: {kind: [{"node", "scopes", "fwd":
    (flops, bytes), "bwd": (flops, bytes)}]}.  Counted from the node's
    shapes alone — the same whatever kernel or fusion implements it.
    A convolution's backward is two contractions of the forward's size; a
    BatchNorm(+ReLU) is bound by bytes: at the least it reads its input
    and writes its output forward, and reads dy and x and writes dx
    backward."""
    conv, bn = [], []
    n = rows

    def add_conv(name, cout, cin, k, hin, hout):
        x, y, w = n * cin * hin * hin, n * cout * hout * hout, \
            cout * cin * k * k
        f = 2 * n * cout * cin * k * k * hout * hout
        conv.append({"node": name, "scopes": [name],
                     "fwd": (f, itemsize * (x + y + w)),
                     "bwd": (2 * f, itemsize * 2 * (x + y + w))})

    def add_bn(name, relu, c, h):
        x = n * c * h * h
        bn.append({"node": name, "scopes": [name] + ([relu] if relu else []),
                   "fwd": (0, itemsize * 2 * x), "bwd": (0, itemsize * 3 * x)})

    f0, c0, h = cfg["filter_list"][0], cfg["image_shape"][0], \
        cfg["image_shape"][1]
    add_bn("bn_data", None, c0, h)
    h1 = (h + 6 - 7) // 2 + 1
    add_conv("conv0", f0, c0, 7, h, h1)
    add_bn("bn0", "relu0", f0, h1)
    h, cin = (h1 + 2 - 3) // 2 + 1, f0
    for name, f, stride, match in _units(cfg):
        mid, hout = f // 4, (h - 1) // stride + 1
        add_bn(name + "_bn1", name + "_relu1", cin, h)
        add_conv(name + "_conv1", mid, cin, 1, h, h)
        add_bn(name + "_bn2", name + "_relu2", mid, h)
        add_conv(name + "_conv2", mid, mid, 3, h, hout)
        add_bn(name + "_bn3", name + "_relu3", mid, hout)
        add_conv(name + "_conv3", f, mid, 1, hout, hout)
        if not match:
            add_conv(name + "_sc", f, cin, 1, h, hout)
        h, cin = hout, f
    add_bn("bn1", "relu1", cin, h)
    return {"conv": conv, "bn": bn}
