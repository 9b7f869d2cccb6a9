"""Search spaces + candidate builders for the kernel autotuner.

Two backends, mirroring ``repro.kernels.ert.ops``:

* ``pallas`` — the tile/block spaces of the Pallas kernels themselves
  (block_m/n/k for the ERT GEMM, block + double_buffer for triad, block
  for the FMA chain, block_q/block_k for flash attention, chunk for the
  SSD scan).  On TPU hardware this is real tile tuning; on the interpret
  host the ordering is still meaningful (grid-step overhead dominates) and
  the winners are what the smoke/CI loop exercises.
* ``xla`` — the jnp-oracle spaces that feed machine characterization: the
  FMA chain's (n_iters, ilp) ladder (the paper's §II-A tuning ladder —
  15.4 → 29.2 TFLOP/s on V100 came exactly from this kind of knob), and
  single-candidate ceiling measurements for the GEMM / triad oracles so
  ``empirical_cpu_spec`` ceilings are persisted best-of-tuned numbers.

Every space includes the hardcoded-default candidate, so a search always
produces an honest before (default) / after (tuned) pair.

The objective is always *maximize metric*:

* fixed-work kernels → ``flops_per_s`` / ``bytes_per_s`` (work / wall);
* the SSD scan's FLOPs vary with ``chunk`` (algorithmic), so its metric
  is ``calls_per_s`` — same problem solved, fastest wall wins.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

from repro.kernels.config import KernelConfig, default_config

PALLAS_KERNELS = ("triad", "fma_chain", "ert_gemm", "flash_attention",
                  "ssd_scan", "fused_norm", "fused_swiglu", "fused_adamw")
XLA_KERNELS = ("triad", "fma_chain", "ert_gemm")

# oracle-path defaults (what ops.measure_flops has always used)
XLA_FMA_DEFAULT = {"n_iters": 256, "ilp": 8}


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of a search space, ready to compile and time."""

    params: tuple[tuple[str, Any], ...]
    build: Callable[[], tuple[Callable, tuple]]    # () -> (fn, args)
    work: float                                    # per-call work units
    metric_name: str

    @property
    def dict(self) -> dict[str, Any]:
        return dict(self.params)

    def label(self) -> str:
        return ",".join(f"{k}={v}" for k, v in self.params)


def _cand(params: dict[str, Any], build, work: float,
          metric_name: str) -> Candidate:
    return Candidate(tuple(sorted(params.items())), build, work, metric_name)


def default_shape(kernel: str, smoke: bool = False) -> tuple[int, ...]:
    """The shape a bare ``repro.tune search --kernel X`` tunes at."""
    full = {
        "triad": (1 << 20,),
        "fma_chain": (1 << 18,),
        "ert_gemm": (512, 512, 512),
        "flash_attention": (4, 1024, 1024, 64),
        "ssd_scan": (1, 2, 512, 32, 32),
        "fused_norm": (4096, 512),
        "fused_swiglu": (4096, 1024),
        "fused_adamw": (1 << 20,),
    }
    tiny = {
        "triad": (1 << 16,),
        "fma_chain": (1 << 14,),
        "ert_gemm": (256, 256, 256),
        "flash_attention": (2, 256, 256, 64),
        "ssd_scan": (1, 2, 128, 16, 16),
        "fused_norm": (256, 64),
        "fused_swiglu": (256, 128),
        "fused_adamw": (1 << 14,),
    }
    table = tiny if smoke else full
    if kernel not in table:
        raise KeyError(f"unknown kernel {kernel!r}; "
                       f"known: {sorted(table)}")
    return table[kernel]


def default_params(kernel: str, backend: str = "pallas") -> dict[str, Any]:
    """The hardcoded-default candidate's params (the "before" config)."""
    if backend == "xla":
        return dict(XLA_FMA_DEFAULT) if kernel == "fma_chain" else {}
    return default_config(kernel).dict


def _dtype(name: str):
    import jax.numpy as jnp
    return jnp.dtype(name)


def fit_block(block: int, dim: int) -> int:
    """Largest halving of ``block`` that divides ``dim`` (min 1).

    The divisibility-constrained kernels (GEMM, flash attention, SSD)
    cannot run their clamped default on shapes the default doesn't tile —
    this is how the space keeps a feasible "default" baseline anyway
    (e.g. GEMM 384³: 256 → 128), so odd shapes still get an honest
    before/after pair instead of an error.
    """
    block = min(block, dim)
    while block > 1 and dim % block:
        block //= 2
    return max(block, 1)


# --------------------------------------------------------------------------
# Per-kernel spaces
# --------------------------------------------------------------------------

def _triad_pallas(shape, dtype, smoke):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ert import bandwidth
    (n,) = shape
    dt = _dtype(dtype)
    work = bandwidth.triad_bytes(n, np.dtype(dt).itemsize)
    blocks = (16384, 65536) if smoke else (8192, 16384, 32768, 65536)
    dflt = default_config("triad").dict
    out = []
    for blk in blocks:
        for db in (False, True):
            params = {"block": blk, "double_buffer": db}
            # a candidate whose grid step exceeds N only measures padding
            # — skip it, except the default, which must always be present
            # (the kernel supports it via the padded final block)
            if blk * (2 if db else 1) > n and params != dflt:
                continue

            def build(blk=blk, db=db):
                a = jnp.ones((n,), dt)
                b = jnp.full((n,), 0.5, dt)
                cfg = default_config("triad").replace(block=blk,
                                                      double_buffer=db)
                fn = lambda a_, b_: bandwidth.triad(a_, b_, config=cfg)
                return fn, (a, b)

            out.append(_cand(params, build, work, "bytes_per_s"))
    return out


def _fma_pallas(shape, dtype, smoke):
    import jax.numpy as jnp

    from repro.kernels.ert import flops as fl
    (n,) = shape
    dt = _dtype(dtype)
    n_iters, ilp = 64, 4
    work = fl.fma_flops(n, n_iters, ilp)
    blocks = (4096, 16384) if smoke else (2048, 4096, 8192, 16384, 65536)
    dflt_blk = default_config("fma_chain").get("block")
    out = []
    for blk in blocks:
        if blk > n and blk != dflt_blk:     # default always present (pads)
            continue

        def build(blk=blk):
            x = jnp.ones((n,), dt)
            cfg = default_config("fma_chain").replace(block=blk)
            fn = lambda x_: fl.fma_chain(x_, n_iters, ilp, config=cfg)
            return fn, (x,)

        out.append(_cand({"block": blk}, build, work, "flops_per_s"))
    return out


def _gemm_pallas(shape, dtype, smoke):
    import jax

    from repro.kernels.ert import gemm
    m, n, k = shape
    dt = _dtype(dtype)
    work = gemm.gemm_flops(m, n, k)
    if smoke:
        combos = [(128, 128, 128), (256, 256, 256)]
    else:
        combos = [(b, b, bk) for b in (128, 256, 512)
                  for bk in (128, 256, 512)]
    combos.append((256, 256, 256))                  # the hardcoded default
    out = []
    seen = set()
    for bm, bn, bk in combos:
        # clamp to the shape, then halve to the nearest divisor so odd
        # shapes keep a feasible variant of each combo (incl. the default)
        bm, bn, bk = fit_block(bm, m), fit_block(bn, n), fit_block(bk, k)
        if (bm, bn, bk) in seen:
            continue
        seen.add((bm, bn, bk))

        def build(bm=bm, bn=bn, bk=bk):
            key = jax.random.PRNGKey(0)
            a = jax.random.normal(key, (m, k)).astype(dt)
            b = jax.random.normal(key, (k, n)).astype(dt)
            cfg = default_config("ert_gemm").replace(
                block_m=bm, block_n=bn, block_k=bk)
            fn = lambda a_, b_: gemm.matmul(a_, b_, config=cfg)
            return fn, (a, b)

        out.append(_cand({"block_m": bm, "block_n": bn, "block_k": bk},
                         build, work, "flops_per_s"))
    return out


def _flash_pallas(shape, dtype, smoke):
    import jax

    from repro.kernels.flash_attention import kernel as fa
    bh, sq, sk, hd = shape
    dt = _dtype(dtype)
    work = fa.flops(bh, sq, sk, hd, causal=True)
    pairs = ([(128, 128), (256, 256), (128, 256)] if smoke else
             [(bq, bk) for bq in (128, 256, 512) for bk in (128, 256, 512)])
    out = []
    seen = set()
    for bq, bk in pairs + [(512, 512)]:             # incl. the default
        bq, bk = fit_block(bq, sq), fit_block(bk, sk)
        if (bq, bk) in seen:
            continue
        seen.add((bq, bk))

        def build(bq=bq, bk=bk):
            key = jax.random.PRNGKey(0)
            q = jax.random.normal(key, (bh, sq, hd)).astype(dt)
            k = jax.random.normal(key, (bh, sk, hd)).astype(dt)
            v = jax.random.normal(key, (bh, sk, hd)).astype(dt)
            cfg = default_config("flash_attention").replace(
                block_q=bq, block_k=bk)
            fn = lambda q_, k_, v_: fa.flash_attention(q_, k_, v_,
                                                       config=cfg)
            return fn, (q, k, v)

        out.append(_cand({"block_q": bq, "block_k": bk}, build, work,
                         "flops_per_s"))
    return out


def _ssd_pallas(shape, dtype, smoke):
    import jax
    import jax.numpy as jnp

    from repro.kernels.ssd_scan import kernel as ssd
    b, h, s, p, nstate = shape
    dt = _dtype(dtype)
    chunks = (32, 64, 128) if smoke else (32, 64, 128, 256)  # 128 = default
    out = []
    for chunk in chunks:
        chunk = fit_block(chunk, s)

        def build(chunk=chunk):
            key = jax.random.PRNGKey(0)
            xdt = jax.random.normal(key, (b, h, s, p)).astype(dt) * 0.1
            a = -jnp.abs(jax.random.normal(key, (b, h, s))).astype(dt) * 0.1
            B_ = jax.random.normal(key, (b, s, nstate)).astype(dt) * 0.1
            C_ = jax.random.normal(key, (b, s, nstate)).astype(dt) * 0.1
            cfg = default_config("ssd_scan").replace(chunk=chunk)
            fn = lambda x_, a_, bb, cc: ssd.ssd_scan(x_, a_, bb, cc,
                                                     config=cfg)
            return fn, (xdt, a, B_, C_)

        out.append(_cand({"chunk": chunk}, build, 1.0, "calls_per_s"))
    # dedupe clamped chunks (min(chunk, s) collisions)
    uniq: dict[tuple, Candidate] = {}
    for c in out:
        uniq.setdefault(c.params, c)
    return list(uniq.values())


# -- fused epilogue kernels (repro.kernels.fused) --------------------------
#
# All three are memory-bound streaming kernels with shape-fixed traffic, so
# the objective is bytes_per_s over the analytic fused byte count; the row
# (or element) block is the only knob.  Oversized blocks only measure
# padding and are skipped — except the hardcoded default, which must stay
# in every space for the honest before/after pair.

def _row_cap(kernel: str, d: int, dtype: str) -> int:
    """The VMEM bound ``row_blocked_call`` puts on a row block of width
    ``d``: the norm+residual kernel the tuner times streams x and h in and
    r and y out, SwiGLU gate and up in and the product out, all in
    ``dtype``."""
    from repro.kernels.fused.common import vmem_rows
    streams = 4 if kernel == "fused_norm" else 3
    return vmem_rows(d, [_dtype(dtype)] * streams)


def _row_blocks(rows: int, dflt: int, smoke: bool, cap: int) -> list[int]:
    """Row-block candidates, each capped at ``cap`` (:func:`_row_cap`) as
    the kernel caps it, so a stored winner names the block that ran."""
    blocks = (128, 1024) if smoke else (128, 256, 1024, 4096)
    dflt = min(dflt, cap)
    out = []
    for blk in dict.fromkeys(min(b, cap) for b in (*blocks, dflt)):
        if blk > rows and blk != dflt:
            continue
        out.append(blk)
    return out


def _fused_norm_pallas(shape, dtype, smoke):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.fused import norm as nk
    rows, d = shape
    dt = _dtype(dtype)
    work = nk.hbm_bytes(rows, d, np.dtype(dt).itemsize, residual=True)
    dflt = default_config("fused_norm").get("block_rows")
    out = []
    for blk in _row_blocks(rows, dflt, smoke,
                           _row_cap("fused_norm", d, dtype)):

        def build(blk=blk):
            import jax
            key = jax.random.PRNGKey(0)
            x = jax.random.normal(key, (rows, d)).astype(dt)
            h = jax.random.normal(key, (rows, d)).astype(dt)
            s = jnp.ones((d,), jnp.float32)
            cfg = default_config("fused_norm").replace(block_rows=blk)
            fn = lambda x_, h_, s_: nk.fused_rmsnorm_residual(
                x_, h_, s_, config=cfg)
            return fn, (x, h, s)

        out.append(_cand({"block_rows": blk}, build, work, "bytes_per_s"))
    return out


def _fused_swiglu_pallas(shape, dtype, smoke):
    import numpy as np

    from repro.kernels.fused import swiglu as sk
    rows, d = shape
    dt = _dtype(dtype)
    work = sk.hbm_bytes(rows, d, np.dtype(dt).itemsize)
    dflt = default_config("fused_swiglu").get("block_rows")
    out = []
    for blk in _row_blocks(rows, dflt, smoke,
                           _row_cap("fused_swiglu", d, dtype)):

        def build(blk=blk):
            import jax
            key = jax.random.PRNGKey(0)
            g = jax.random.normal(key, (rows, d)).astype(dt)
            u = jax.random.normal(key, (rows, d)).astype(dt)
            cfg = default_config("fused_swiglu").replace(block_rows=blk)
            fn = lambda g_, u_: sk.fused_swiglu(g_, u_, config=cfg)
            return fn, (g, u)

        out.append(_cand({"block_rows": blk}, build, work, "bytes_per_s"))
    return out


def _fused_adamw_pallas(shape, dtype, smoke):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.fused import adamw as ak
    (n,) = shape
    dt = _dtype(dtype)
    work = ak.hbm_bytes(n, np.dtype(dt).itemsize)
    blocks = (4096, 65536) if smoke else (4096, 16384, 65536, 262144)
    dflt = default_config("fused_adamw").get("block")
    out = []
    for blk in dict.fromkeys((*blocks, dflt)):
        if blk > n and blk != dflt:
            continue

        def build(blk=blk):
            import jax
            key = jax.random.PRNGKey(0)
            g = jax.random.normal(key, (n,)).astype(dt)
            m = jnp.zeros((n,), dt)
            v = jnp.zeros((n,), dt)
            p = jax.random.normal(key, (n,)).astype(dt)
            bc = jnp.asarray(0.1, jnp.float32)
            cfg = default_config("fused_adamw").replace(block=blk)
            fn = lambda g_, m_, v_, p_, b_: ak.fused_adamw(
                g_, m_, v_, p_, b_, b_, config=cfg)
            return fn, (g, m, v, p, bc)

        out.append(_cand({"block": blk}, build, work, "bytes_per_s"))
    return out


# -- xla (oracle) spaces: machine-characterization ceilings ----------------

def _fma_xla(shape, dtype, smoke):
    import jax.numpy as jnp

    from repro.kernels.ert import flops as fl
    from repro.kernels.ert import ref
    (n,) = shape
    dt = _dtype(dtype)
    if smoke:
        grid = [(64, 4), (64, 8)]
    else:
        grid = [(ni, il) for ni in (64, 256) for il in (4, 8, 16)]
    grid.append((XLA_FMA_DEFAULT["n_iters"], XLA_FMA_DEFAULT["ilp"]))
    out = []
    seen = set()
    for n_iters, ilp in grid:
        if (n_iters, ilp) in seen:
            continue
        seen.add((n_iters, ilp))

        def build(n_iters=n_iters, ilp=ilp):
            x = jnp.ones((n,), dt)
            fn = lambda x_: ref.fma_chain_ref(x_, n_iters, ilp)
            return fn, (x,)

        out.append(_cand({"n_iters": n_iters, "ilp": ilp}, build,
                         fl.fma_flops(n, n_iters, ilp), "flops_per_s"))
    return out


def _triad_xla(shape, dtype, smoke):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ert import bandwidth, ref
    (n,) = shape
    dt = _dtype(dtype)

    def build():
        return ref.triad_ref, (jnp.ones((n,), dt), jnp.full((n,), 0.5, dt))

    return [_cand({}, build, bandwidth.triad_bytes(n, np.dtype(dt).itemsize),
                  "bytes_per_s")]


def _gemm_xla(shape, dtype, smoke):
    import jax

    from repro.kernels.ert import gemm, ref
    m, n, k = shape
    dt = _dtype(dtype)

    def build():
        key = jax.random.PRNGKey(0)
        a = jax.random.normal(key, (m, k)).astype(dt)
        b = jax.random.normal(key, (k, n)).astype(dt)
        return ref.matmul_ref, (a, b)

    return [_cand({}, build, gemm.gemm_flops(m, n, k), "flops_per_s")]


_SPACES = {
    ("triad", "pallas"): _triad_pallas,
    ("fma_chain", "pallas"): _fma_pallas,
    ("ert_gemm", "pallas"): _gemm_pallas,
    ("flash_attention", "pallas"): _flash_pallas,
    ("ssd_scan", "pallas"): _ssd_pallas,
    ("fused_norm", "pallas"): _fused_norm_pallas,
    ("fused_swiglu", "pallas"): _fused_swiglu_pallas,
    ("fused_adamw", "pallas"): _fused_adamw_pallas,
    ("triad", "xla"): _triad_xla,
    ("fma_chain", "xla"): _fma_xla,
    ("ert_gemm", "xla"): _gemm_xla,
}


def candidates(kernel: str, shape: Sequence[int], dtype: str = "float32",
               backend: str = "pallas",
               smoke: bool = False) -> list[Candidate]:
    """The search space for one (kernel, shape, dtype, backend) point.

    Always contains the hardcoded-default candidate (possibly clamped to
    the shape); raises ``KeyError`` for unknown kernels/backends.
    """
    try:
        fn = _SPACES[(kernel, backend)]
    except KeyError:
        raise KeyError(f"no search space for kernel={kernel!r} "
                       f"backend={backend!r}; known: "
                       f"{sorted(set(k for k, _ in _SPACES))}")
    cands = fn(tuple(shape), dtype, smoke)
    if not cands:
        raise ValueError(f"{kernel}: no feasible candidate for shape "
                         f"{tuple(shape)} — every block choice was "
                         "incompatible")
    dflt = _clamped_default(kernel, backend, shape, dtype)
    if not any(c.dict == dflt for c in cands):
        raise AssertionError(
            f"{kernel}/{backend} space must contain the default {dflt}")
    return cands


def _clamped_default(kernel: str, backend: str, shape: Sequence[int],
                     dtype: str = "float32") -> dict[str, Any]:
    """Default params fitted to ``shape``: min-clamped (flash block_q=512
    on sq=256 runs as 256) and, for the divisibility-constrained kernels,
    halved to the nearest divisor (GEMM 384³ → 128 tiles) — the feasible
    stand-in for the hardcoded default on shapes it cannot tile.  The
    fused row blocks are capped by VMEM (:func:`_row_cap`)."""
    p = default_params(kernel, backend)
    if backend != "pallas":
        return p
    if kernel == "ert_gemm":
        m, n, k = shape
        p["block_m"] = fit_block(p["block_m"], m)
        p["block_n"] = fit_block(p["block_n"], n)
        p["block_k"] = fit_block(p["block_k"], k)
    elif kernel == "flash_attention":
        _, sq, sk, _ = shape
        p["block_q"] = fit_block(p["block_q"], sq)
        p["block_k"] = fit_block(p["block_k"], sk)
    elif kernel == "ssd_scan":
        s = shape[2]
        p["chunk"] = fit_block(p["chunk"], s)
    elif kernel in ("fused_norm", "fused_swiglu"):
        p["block_rows"] = min(p["block_rows"],
                              _row_cap(kernel, shape[1], dtype))
    return p


def is_default(kernel: str, backend: str, shape: Sequence[int],
               params: dict[str, Any], dtype: str = "float32") -> bool:
    return params == _clamped_default(kernel, backend, shape, dtype)
