"""Plain reference of DeepCAM training, beside ``deepcam.json``.

DeepCAM (Kurth et al., arXiv:1810.01993; the MLPerf-HPC DeepCAM
benchmark) is a DeepLabv3+ segmentation network over 16-channel climate
fields: a ResNet-50 encoder whose last stage is dilated, an ASPP pyramid,
and a decoder with two skip connections.  Everything here is written from
that description and the sizes in ``deepcam.json``, in float32 at the
``highest`` matmul precision, with no kernels and no code of the program.

The parameter tree uses the program's key names, so that one set of
weights made here from the seed is handed to the program and kept for
this reference; :func:`param_shapes` is checked against the program's
own tree before a run.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _conv(k: int, cin: int, cout: int) -> dict:
    return {"w": (k, k, cin, cout), "b": (cout,)}


def _bn(c: int) -> dict:
    return {"scale": (c,), "bias": (c,), "mean": (c,), "var": (c,)}


def param_shapes(cfg: dict) -> dict:
    """The tree of parameter shapes (tuples) for ``cfg``."""
    w = cfg["stem_width"]
    stages, cin = [], w
    for blocks, cout, _stride, _dil in cfg["stages"]:
        stage = []
        for i in range(blocks):
            c0 = cin if i == 0 else cout
            mid = cout // 4
            b = {"c1": _conv(1, c0, mid), "n1": _bn(mid),
                 "c2": _conv(3, mid, mid), "n2": _bn(mid),
                 "c3": _conv(1, mid, cout), "n3": _bn(cout)}
            if c0 != cout:
                b["proj"] = _conv(1, c0, cout)
                b["projn"] = _bn(cout)
            stage.append(b)
        stages.append(stage)
        cin = cout
    enc, a = cfg["stages"][-1][1], cfg["aspp_width"]
    skip, mid_c = cfg["stages"][0][1], cfg["stages"][1][1]
    sk, mk = cfg["skip_width"], cfg["mid_width"]
    rates = cfg["aspp_rates"]
    aspp = {f"r{r}": _conv(1 if r == 1 else 3, enc, a) for r in rates}
    aspp["pool"] = _conv(1, enc, a)
    aspp["proj"] = _conv(1, a * (len(rates) + 1), a)
    aspp["proj_n"] = _bn(a)
    dec = {"skip_proj": _conv(1, skip, sk), "mid_proj": _conv(1, mid_c, mk),
           "d1": _conv(3, a + sk, a), "d1n": _bn(a),
           "d2": _conv(3, a, a), "d2n": _bn(a),
           "d3": _conv(3, a + mk, a), "d3n": _bn(a),
           "d4": _conv(3, a, a // 2), "d4n": _bn(a // 2),
           "d5": _conv(3, a // 2, a // 2), "d5n": _bn(a // 2),
           "head": _conv(1, a // 2, cfg["classes"])}
    return {"stem": _conv(7, cfg["in_channels"], w), "stem_n": _bn(w),
            "stages": stages, "aspp": aspp, "dec": dec}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Weights from one key, float32 (jit this: it runs on the device).

    Convolution weights are normal with variance 1/fan-in; biases and the
    norm's learned statistics are drawn too, so that every term of the
    norm is exercised: scale 1 + 0.1 N, bias 0.1 N, mean 0.1 N,
    var 1 + 0.1 |N|.
    """
    shapes = param_shapes(cfg)
    leaves, tdef = jax.tree.flatten(shapes, is_leaf=_is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes,
                                                  is_leaf=_is_shape)[0]]
    keys = jax.random.split(key, len(leaves))
    out = []
    for path, shape, k in zip(paths, leaves, keys):
        z = jax.random.normal(k, shape, jnp.float32)
        last = path.rsplit("'", 2)[-2]
        if last == "w":
            out.append(z / math.sqrt(math.prod(shape[:-1])))
        elif last == "b":
            out.append(0.01 * z)
        elif last == "scale":
            out.append(1.0 + 0.1 * z)
        elif last in ("bias", "mean"):
            out.append(0.1 * z)
        elif last == "var":
            out.append(1.0 + 0.1 * jnp.abs(z))
        else:
            raise ValueError(f"no initialiser for {path}")
    return tdef.unflatten(out)


# --------------------------------------------------------------------------
# forward and loss
# --------------------------------------------------------------------------

def _plain_conv(f):
    return f


def _to_fp8(t):
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 448.0
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def fp8_conv(f):
    """The control's convolution ``f(x, w)``: its inputs, and in the
    backward pass the incoming gradient, each scaled to the range of
    float8 e4m3 by its largest magnitude and rounded to it."""
    @jax.custom_vjp
    def g(x, w):
        return f(_to_fp8(x), _to_fp8(w))

    def fwd(x, w):
        xq, wq = _to_fp8(x), _to_fp8(w)
        return f(xq, wq), (xq, wq)

    def bwd(res, ct):
        return jax.vjp(f, *res)[1](_to_fp8(ct))

    g.defvjp(fwd, bwd)
    return g


def forward(params: dict, images: jax.Array, cfg: dict,
            quant=_plain_conv) -> jax.Array:
    """images (B, H, W, C) float32 -> logits (B, H, W, classes) float32.

    ``quant`` wraps each convolution: the identity for the reference,
    :func:`fp8_conv` for the control.
    """
    eps = cfg["bn_eps"]

    def conv(x, p, stride=1, dil=1):
        def f(a, w):
            return jax.lax.conv_general_dilated(
                a, w, (stride, stride), "SAME", rhs_dilation=(dil, dil),
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
        return quant(f)(x, p["w"]) + p["b"]

    def bn(x, p):
        return ((x - p["mean"]) * jax.lax.rsqrt(p["var"] + eps)
                * p["scale"] + p["bias"])

    relu = jax.nn.relu

    @functools.partial(jax.checkpoint, static_argnums=(2, 3))
    def bottleneck(x, p, stride, dil):
        h = relu(bn(conv(x, p["c1"]), p["n1"]))
        h = relu(bn(conv(h, p["c2"], stride, dil), p["n2"]))
        h = bn(conv(h, p["c3"]), p["n3"])
        if "proj" in p:
            x = bn(conv(x, p["proj"], stride), p["projn"])
        elif stride != 1:
            x = x[:, ::stride, ::stride]
        return relu(x + h)

    def resize(x, hw):
        return jax.image.resize(x, (x.shape[0], *hw, x.shape[-1]),
                                "bilinear", precision=HI)

    H, W = images.shape[1], images.shape[2]
    x = relu(bn(conv(images, params["stem"], 2), params["stem_n"]))
    feats = []
    for stage_p, (_n, _c, stride, dil) in zip(params["stages"],
                                              cfg["stages"]):
        for i, bp in enumerate(stage_p):
            x = bottleneck(x, bp, stride if i == 0 else 1, dil)
        feats.append(x)
    skip, mid = feats[0], feats[1]

    ap = params["aspp"]
    branches = [relu(conv(x, ap[f"r{r}"], 1, r)) for r in cfg["aspp_rates"]]
    pooled = relu(conv(jnp.mean(x, axis=(1, 2), keepdims=True), ap["pool"]))
    branches.append(jnp.broadcast_to(pooled, (*x.shape[:3],
                                              pooled.shape[-1])))
    x = relu(bn(conv(jnp.concatenate(branches, -1), ap["proj"]),
                ap["proj_n"]))

    dp = params["dec"]

    @jax.checkpoint
    def decode_low(x, skip, mid):
        x = resize(x, skip.shape[1:3])
        x = jnp.concatenate([x, conv(skip, dp["skip_proj"])], -1)
        x = relu(bn(conv(x, dp["d1"]), dp["d1n"]))
        x = relu(bn(conv(x, dp["d2"]), dp["d2n"]))
        m = resize(conv(mid, dp["mid_proj"]), x.shape[1:3])
        x = jnp.concatenate([x, m], -1)
        return relu(bn(conv(x, dp["d3"]), dp["d3n"]))

    @jax.checkpoint
    def decode_high(x):
        x = resize(x, (H, W))
        x = relu(bn(conv(x, dp["d4"]), dp["d4n"]))
        x = relu(bn(conv(x, dp["d5"]), dp["d5n"]))
        return conv(x, dp["head"])

    return decode_high(decode_low(x, skip, mid))


def loss(params: dict, batch: dict, cfg: dict, quant=_plain_conv):
    """Mean over pixels and images of the cross-entropy of the labels."""
    logits = forward(params, batch["images"], cfg, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(batch["labels"], cfg["classes"],
                            dtype=jnp.float32)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


# --------------------------------------------------------------------------
# model FLOPs, from the shapes of the forward's convolutions and dots
# --------------------------------------------------------------------------

def _valid_taps(in_size, out_size, window, stride, pad_lo, rhs_dil):
    pos = (np.arange(out_size)[:, None] * stride
           + np.arange(window)[None, :] * rhs_dil - pad_lo)
    return int(((pos >= 0) & (pos < in_size)).sum())


def _conv_flops(eqn) -> float:
    lhs, rhs = (v.aval for v in eqn.invars[:2])
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    lhs_spec, rhs_spec, out_spec = dn.lhs_spec, dn.rhs_spec, dn.out_spec
    if any(d != 1 for d in eqn.params["lhs_dilation"]):
        raise ValueError("a forward convolution with lhs dilation")
    taps = 1
    for i, (stride, (lo, _hi), rd) in enumerate(zip(
            eqn.params["window_strides"], eqn.params["padding"],
            eqn.params["rhs_dilation"])):
        taps *= _valid_taps(lhs.shape[lhs_spec[2 + i]],
                            out.shape[out_spec[2 + i]],
                            rhs.shape[rhs_spec[2 + i]], stride, lo, rd)
    batch, cout = out.shape[out_spec[0]], out.shape[out_spec[1]]
    cin = rhs.shape[rhs_spec[1]]
    return 2.0 * batch * cout * cin * taps


def _dot_flops(eqn) -> float:
    (lc, _rc), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    return 2.0 * math.prod(eqn.outvars[0].aval.shape) * math.prod(
        lhs.shape[d] for d in lc)


def _walk(jaxpr, live: list[bool], total: list[float]) -> list[bool]:
    """Add the training FLOPs of ``jaxpr``'s convolutions and dots to
    ``total``; ``live`` says which inputs depend on the parameters.  A
    convolution or dot costs its forward once, plus once more for each
    operand that depends on the parameters (the gradient to that operand
    is one more product of the same size).  Returns which outputs depend
    on the parameters."""
    from jax.extend import core as jcore
    dep = {}
    for v, l in zip(jaxpr.invars, live):
        dep[v] = l

    def is_live(v):
        return not isinstance(v, jcore.Literal) and dep.get(v, False)

    for eqn in jaxpr.eqns:
        ins = [is_live(v) for v in eqn.invars]
        name = eqn.primitive.name
        if name in ("conv_general_dilated", "dot_general"):
            f = (_conv_flops(eqn) if name == "conv_general_dilated"
                 else _dot_flops(eqn))
            total[0] += f * (1 + ins[0] + ins[1])
            outs = [ins[0] or ins[1]] * len(eqn.outvars)
        else:
            sub = [p for p in eqn.params.values()
                   if isinstance(p, (jcore.ClosedJaxpr, jcore.Jaxpr))]
            if sub:
                inner = sub[0].jaxpr if isinstance(
                    sub[0], jcore.ClosedJaxpr) else sub[0]
                outs = _walk(inner, ins[-len(inner.invars):]
                             if len(inner.invars) <= len(ins)
                             else ins + [False] * (len(inner.invars)
                                                   - len(ins)),
                             total)
            else:
                outs = [any(ins)] * len(eqn.outvars)
        for v, l in zip(eqn.outvars, outs):
            dep[v] = l
    return [is_live(v) for v in jaxpr.outvars]


def train_flops_per_example(cfg: dict) -> float:
    """FLOPs of one image's forward and backward, counted from the shapes
    of the forward's convolutions and dots (taps that land on padding
    left out), with no recomputation: nothing is run."""
    H, W = cfg["image_hw"]
    shapes = param_shapes(cfg)
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
                          shapes, is_leaf=_is_shape)
    img = jax.ShapeDtypeStruct((1, H, W, cfg["in_channels"]), jnp.float32)
    closed = jax.make_jaxpr(lambda p, x: forward(p, x, cfg))(params, img)
    n = len(jax.tree.leaves(params))
    total = [0.0]
    _walk(closed.jaxpr, [True] * n + [False], total)
    return total[0]
