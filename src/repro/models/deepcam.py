"""DeepCAM: the paper's case-study network (§III-B), in two JAX lowerings.

DeepLabv3+-style semantic segmentation [paper refs 21, 36]:
encoder = ResNet-50 with atrous (dilated) stage-4 + ASPP pyramid pooling,
decoder = 9 conv/deconv layers with two skip connections (input + encoder
middle).  Input: climate images (B, H, W, 16 channels); output: per-pixel
3-class logits (background / tropical cyclone / atmospheric river).

The paper's point in comparing TensorFlow vs PyTorch DeepCAM is that two
*implementations* of the same math produce different kernel mixes.  We
reproduce that with two lowerings selected by ``impl``:

* ``reference`` — straight-line NHWC convs, batch norm as separate ops
  (TensorFlow-ish: many small kernels, more zero-AI data movement);
* ``fused``     — conv+bias+norm+activation fused by construction
  (single expression per block), scan over the repeated residual
  bottleneck blocks (PyTorch/AMP-ish: fewer, fatter kernels).

Both produce identical math (tests assert allclose); their HLO kernel
censuses differ — that is benchmark ``deepcam_roofline`` / ``zero_ai``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import RunConfig
from repro.models.params import P

Params = Any

IN_CHANNELS = 16
N_CLASSES = 3

# ResNet-50 stage plan: (blocks, out_channels, stride, dilation)
_STAGES = ((3, 256, 1, 1), (4, 512, 2, 1), (6, 1024, 2, 1), (3, 2048, 1, 2))
_ASPP_RATES = (1, 6, 12, 18)


# --------------------------------------------------------------------------
# Specs
# --------------------------------------------------------------------------

def _conv_spec(cin: int, cout: int, k: int = 3) -> Params:
    return {"w": P((k, k, cin, cout), (None, None, None, "ffn")),
            "b": P((cout,), ("ffn",), "zeros")}


def _bn_spec(c: int) -> Params:
    return {"scale": P((c,), ("ffn",), "ones"),
            "bias": P((c,), ("ffn",), "zeros"),
            "mean": P((c,), ("ffn",), "zeros"),
            "var": P((c,), ("ffn",), "ones")}


def _bottleneck_spec(cin: int, cout: int) -> Params:
    mid = cout // 4
    spec = {
        "c1": _conv_spec(cin, mid, 1), "n1": _bn_spec(mid),
        "c2": _conv_spec(mid, mid, 3), "n2": _bn_spec(mid),
        "c3": _conv_spec(mid, cout, 1), "n3": _bn_spec(cout),
    }
    if cin != cout:
        spec["proj"] = _conv_spec(cin, cout, 1)
        spec["projn"] = _bn_spec(cout)
    return spec


def deepcam_spec(width: int = 64) -> Params:
    """width=64 is real DeepCAM; smoke tests pass width=8."""
    w = width
    stages = []
    cin = w
    for blocks, cout_base, _s, _d in _STAGES:
        cout = cout_base * w // 64
        stage = [_bottleneck_spec(cin if i == 0 else cout, cout)
                 for i in range(blocks)]
        cin = cout
        stages.append(stage)
    c_enc = _STAGES[-1][1] * w // 64
    c_aspp = 256 * w // 64
    c_skip = _STAGES[0][1] * w // 64
    return {
        "stem": _conv_spec(IN_CHANNELS, w, 7), "stem_n": _bn_spec(w),
        "stages": stages,
        "aspp": {f"r{r}": _conv_spec(c_enc, c_aspp, 1 if r == 1 else 3)
                 for r in _ASPP_RATES}
                | {"pool": _conv_spec(c_enc, c_aspp, 1),
                   "proj": _conv_spec(c_aspp * (len(_ASPP_RATES) + 1),
                                      c_aspp, 1),
                   "proj_n": _bn_spec(c_aspp)},
        "dec": {
            "skip_proj": _conv_spec(c_skip, 48 * w // 64, 1),
            "mid_proj": _conv_spec(_STAGES[1][1] * w // 64, 32 * w // 64, 1),
            "d1": _conv_spec(c_aspp + 48 * w // 64, c_aspp, 3),
            "d1n": _bn_spec(c_aspp),
            "d2": _conv_spec(c_aspp, c_aspp, 3), "d2n": _bn_spec(c_aspp),
            "d3": _conv_spec(c_aspp + 32 * w // 64, c_aspp, 3),
            "d3n": _bn_spec(c_aspp),
            "d4": _conv_spec(c_aspp, c_aspp // 2, 3),
            "d4n": _bn_spec(c_aspp // 2),
            "d5": _conv_spec(c_aspp // 2, c_aspp // 2, 3),
            "d5n": _bn_spec(c_aspp // 2),
            "head": _conv_spec(c_aspp // 2, N_CLASSES, 1),
        },
    }


# --------------------------------------------------------------------------
# Ops (both impls share these primitives; `fused` composes them differently)
# --------------------------------------------------------------------------

def _conv(x, p, stride=1, dilation=1, cd=jnp.float32):
    return jax.lax.conv_general_dilated(
        x.astype(cd), p["w"].astype(cd), (stride, stride), "SAME",
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"].astype(cd)


def _bn(x, p, eps=1e-5, upcast=False):
    """Inference-style norm with learned stats (deterministic, §III-B).

    ``upcast=True`` is the *reference* lowering: the norm round-trips through
    fp32 like TF's AMP graph — under O1/O2 this inserts convert (zero-AI)
    kernels around every norm, reproducing the paper's Table III phenomenon.
    The *fused* lowering stays in the compute dtype.
    """
    dt = x.dtype
    if upcast:
        x = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(p["var"].astype(x.dtype) + eps)
    y = (x - p["mean"].astype(x.dtype)) * inv * p["scale"].astype(x.dtype) \
        + p["bias"].astype(x.dtype)
    return y.astype(dt)


def _bottleneck(x, p, stride, dilation, cd, fused: bool):
    mid_dil = dilation
    up = not fused

    def cbr(h, cp, np_, s=1, d=1, act=True):
        h = _conv(h, cp, s, d, cd)
        h = _bn(h, np_, upcast=up)
        return jax.nn.relu(h) if act else h

    h = cbr(cbr(cbr(x, p["c1"], p["n1"]),
                p["c2"], p["n2"], stride, mid_dil),
            p["c3"], p["n3"], act=False)
    if "proj" in p:
        x = _bn(_conv(x, p["proj"], stride, 1, cd), p["projn"], upcast=up)
    elif stride != 1:
        x = x[:, ::stride, ::stride]
    return jax.nn.relu(x + h)


def _resize(x, hw):
    return jax.image.resize(x, (x.shape[0], *hw, x.shape[-1]), "bilinear")


def resolve_impl(run: RunConfig, impl: str | None = None) -> str:
    """Which lowering a run selects: an explicit ``impl`` wins, then the
    ``RunConfig.impl`` knob, and ``fusion="auto"`` upgrades the default
    reference lowering to the fused one (the same roofline move the LM
    path makes through ``repro.kernels.fused``)."""
    chosen = impl if impl is not None else run.impl
    if chosen == "reference" and getattr(run, "fusion", "off") == "auto" \
            and impl is None:
        return "fused"
    return chosen


def deepcam_forward(params: Params, images: jax.Array, run: RunConfig,
                    impl: str = "reference") -> jax.Array:
    """images (B, H, W, 16) → logits (B, H, W, 3)."""
    from repro.distributed.sharding import constrain
    fused = impl == "fused"
    cd = run.compute_dtype
    x = images.astype(cd)
    x = constrain(x, run, "batch", None, None, None)
    H, W = x.shape[1], x.shape[2]

    up = not fused
    # part scopes: each op's HLO ``op_name`` names the part it belongs to
    with jax.named_scope("deepcam.stem"):
        x = jax.nn.relu(_bn(_conv(x, params["stem"], 2, 1, cd),
                            params["stem_n"], upcast=up))
    skip = None
    mid = None
    for si, (stage_p, (_blocks, _c, stride, dil)) in enumerate(
            zip(params["stages"], _STAGES)):
        with jax.named_scope(f"deepcam.stage{si + 1}"):
            for bi, bp in enumerate(stage_p):
                x = _bottleneck(x, bp, stride if bi == 0 else 1, dil, cd,
                                fused)
        if si == 0:
            skip = x
        if si == 1:
            mid = x

    with jax.named_scope("deepcam.aspp"):
        hw = (x.shape[1], x.shape[2])
        branches = [jax.nn.relu(_conv(x, params["aspp"][f"r{r}"], 1,
                                      1 if r == 1 else r, cd))
                    for r in _ASPP_RATES]
        pooled = jnp.mean(x, axis=(1, 2), keepdims=True)
        pooled = jax.nn.relu(_conv(pooled, params["aspp"]["pool"], cd=cd))
        branches.append(jnp.broadcast_to(
            pooled, (x.shape[0], *hw, pooled.shape[-1])))
        x = jnp.concatenate(branches, axis=-1)
        x = jax.nn.relu(_bn(_conv(x, params["aspp"]["proj"], cd=cd),
                            params["aspp"]["proj_n"], upcast=up))

    # decoder: upsample to skip resolution, two skip connections
    dp = params["dec"]
    with jax.named_scope("deepcam.decoder"):
        x = _resize(x, (skip.shape[1], skip.shape[2]))
        sk = _conv(skip, dp["skip_proj"], cd=cd)
        x = jnp.concatenate([x, sk], axis=-1)
        x = jax.nn.relu(_bn(_conv(x, dp["d1"], cd=cd), dp["d1n"], upcast=up))
        x = jax.nn.relu(_bn(_conv(x, dp["d2"], cd=cd), dp["d2n"], upcast=up))
        # second skip: encoder-middle features, projected + upsampled
        # (paper §III-B)
        mk = _resize(_conv(mid, dp["mid_proj"], cd=cd),
                     (x.shape[1], x.shape[2]))
        x = jnp.concatenate([x, mk], axis=-1)
        x = jax.nn.relu(_bn(_conv(x, dp["d3"], cd=cd), dp["d3n"], upcast=up))
        x = _resize(x, (H, W))
        x = jax.nn.relu(_bn(_conv(x, dp["d4"], cd=cd), dp["d4n"], upcast=up))
        x = jax.nn.relu(_bn(_conv(x, dp["d5"], cd=cd), dp["d5n"], upcast=up))
    with jax.named_scope("deepcam.head"):
        return _conv(x, dp["head"], cd=cd).astype(jnp.float32)


def deepcam_loss(params: Params, images: jax.Array, labels: jax.Array,
                 run: RunConfig, impl: str = "reference") -> jax.Array:
    """Per-pixel weighted cross-entropy (paper's segmentation objective)."""
    logits = deepcam_forward(params, images, run, impl)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels, N_CLASSES, dtype=jnp.float32)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))
