"""Plain reference of minitron-4b's forward pass, beside ``minitron-4b.json``.

Minitron-4B (arXiv:2407.14679; hf:nvidia/Minitron-4B-Base) is a
Nemotron-family dense decoder pruned from Nemotron-4 15B: grouped-query
attention (24 query heads over 8 key/value heads of 128) and a
squared-ReLU MLP of width 9,216 around a residual stream of 3,072, 32
layers, a vocabulary of 256,000.  Written here from that description and
the sizes in ``minitron-4b.json``, with the program's departures from
the source that the file lists (RMSNorm, rotary over the whole head, a
tied output head), in float32 at the ``highest`` matmul precision, with
no cache, no chunking, no batching and no code of the program.

It runs layer by layer, each layer one jitted call over the whole padded
sequence, and the unembedding in blocks of the vocabulary, so that it
fits on the chip beside a set of bfloat16 weights.  ``quant`` turns the
float32 reference into the control: every matrix multiplication takes
float8 e4m3 inputs, each scaled to the format's range by its largest
magnitude.

The parameter tree uses the program's key names and dtype, so that one
set of weights made here from the seed is handed to the program;
:func:`param_shapes` is checked against the program's own tree first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def vocab_rows(cfg: dict) -> int:
    """Rows of the embedding table: the vocabulary padded to 128."""
    return -(-cfg["vocab_size"] // 128) * 128


def param_shapes(cfg: dict) -> dict:
    """The tree of parameter shapes (tuples) for ``cfg``; layers stacked
    on a leading axis."""
    L, D, F = cfg["n_layers"], cfg["d_model"], cfg["d_ff"]
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    if cfg["act"] != "relu2" or not cfg["tie_embeddings"]:
        raise ValueError("the reference is written for a squared-ReLU MLP "
                         "and a tied output head")
    return {
        "embed": {"tokens": (vocab_rows(cfg), D)},
        "blocks": {"ln_attn": {"scale": (L, D)},
                   "attn": {"wq": (L, D, H, hd), "wk": (L, D, K, hd),
                            "wv": (L, D, K, hd), "wo": (L, H, hd, D)},
                   "ln_mlp": {"scale": (L, D)},
                   "mlp": {"w_up": (L, D, F), "w_down": (L, F, D)}},
        "ln_f": {"scale": (D,)},
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) for s in jax.tree.leaves(
        param_shapes(cfg), is_leaf=_is_shape))


def init_params(cfg: dict, key: jax.Array) -> dict:
    """Weights from one key, in ``cfg["param_dtype"]`` (jit this: it runs
    on the device and makes each leaf in that type, with no float32 copy).

    Matrices are normal with variance 1/fan-in, fan-in being the width
    they contract (the residual stream for q, k, v and the MLP's up
    projection; the heads for o; the MLP's width for its down
    projection); the tied embedding is normal with variance 1/d_model,
    so that its rows and the logits it gives are of order one; norm
    scales are 1 + 0.1 N, so that every scale is exercised."""
    dt = jnp.dtype(cfg["param_dtype"])
    D, F = cfg["d_model"], cfg["d_ff"]
    fan_in = {"tokens": D, "wq": D, "wk": D, "wv": D, "w_up": D,
              "wo": cfg["n_heads"] * cfg["head_dim"], "w_down": F}
    shapes = param_shapes(cfg)
    flat = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)[0]
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, shape), k in zip(flat, keys):
        name = path[-1].key
        z = jax.random.normal(k, shape, dt)
        if name == "scale":
            out.append(1 + jnp.asarray(0.1, dt) * z)
        else:
            out.append(z * jnp.asarray(1 / math.sqrt(fan_in[name]), dt))
    return jax.tree.unflatten(jax.tree.structure(shapes, is_leaf=_is_shape),
                              out)


# --------------------------------------------------------------------------
# the forward pass
# --------------------------------------------------------------------------

def _to_fp8(t):
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 448.0
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(quant: bool):
    """Every matrix multiplication: float32 at the highest precision, or
    the control's, with float8 inputs."""
    def mm(spec, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if quant:
            a, b = _to_fp8(a), _to_fp8(b)
        return jnp.einsum(spec, a, b, precision=HI)
    return mm


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """Rotary embedding over the whole head (S, heads, hd), rotate-half
    convention."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, blocks, i, *, cfg, quant):
    """Layer ``i`` of the stacked ``blocks`` over the residual stream
    ``h`` (S, D), causal over its S positions."""
    mm = _mm(quant)
    p = jax.tree.map(lambda w: w[i], blocks)
    S = h.shape[0]
    K, hd = cfg["n_kv_heads"], cfg["head_dim"]
    G = cfg["n_heads"] // K
    pos = jnp.arange(S)
    x = _rmsnorm(h, p["ln_attn"]["scale"], cfg["norm_eps"])
    q = _rope(mm("sd,dhk->shk", x, p["attn"]["wq"]), pos, cfg["rope_theta"])
    k = _rope(mm("sd,dhk->shk", x, p["attn"]["wk"]), pos, cfg["rope_theta"])
    v = mm("sd,dhk->shk", x, p["attn"]["wv"])
    q = q.reshape(S, K, G, hd)
    s = mm("qkgh,skh->kgqs", q, k) / math.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    a = mm("kgqs,skh->qkgh", w, v).reshape(S, K * G, hd)
    h = h + mm("shk,hkd->sd", a, p["attn"]["wo"])
    x = _rmsnorm(h, p["ln_mlp"]["scale"], cfg["norm_eps"])
    u = jax.nn.relu(mm("sd,df->sf", x, p["mlp"]["w_up"]))
    return h + mm("sf,fd->sd", u * u, p["mlp"]["w_down"])


def _statics(cfg: dict) -> tuple:
    keys = ("n_kv_heads", "head_dim", "n_heads", "norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


@functools.lru_cache(maxsize=None)
def _jitted_layer(statics: tuple, quant: bool):
    return jax.jit(functools.partial(_layer, cfg=dict(statics), quant=quant))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(h, scale, rows, *, eps):
    return _rmsnorm(h, scale, eps)[rows]


def hidden(params: dict, tokens: jax.Array, rows: jax.Array, cfg: dict,
           quant: bool = False) -> jax.Array:
    """The final normed residual stream (len(rows), D), float32, at the
    positions ``rows`` of the causal forward pass over ``tokens`` (S,),
    one layer at a time."""
    layer = _jitted_layer(_statics(cfg), quant)
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"]["tokens"], tokens)
        for i in range(cfg["n_layers"]):
            h = layer(h, params["blocks"], jnp.int32(i))
        return _final(h, params["ln_f"]["scale"], rows,
                      eps=float(cfg["norm_eps"]))


@functools.partial(jax.jit, static_argnames=("size", "quant"))
def logits_block(table: jax.Array, x: jax.Array, start: jax.Array, *,
                 size: int, quant: bool = False) -> jax.Array:
    """Logits (n, size) of the hidden rows ``x`` (n, D) over the
    vocabulary rows ``start .. start + size`` of the tied table."""
    w = jax.lax.dynamic_slice_in_dim(table, start, size, axis=0)
    with jax.default_matmul_precision("highest"):
        return _mm(quant)("nd,vd->nv", x, w)
