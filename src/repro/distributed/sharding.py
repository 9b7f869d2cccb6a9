"""Logical-axis sharding rules → NamedSharding (DP / FSDP / TP / SP / EP).

Every parameter spec (:class:`repro.models.params.P`) names its axes with a
logical vocabulary; this module maps logical → mesh axes under a
:class:`repro.configs.base.RunConfig` policy:

* **TP** (Megatron): ``heads / kv_heads / ffn / expert_ffn / ssm_inner /
  vocab`` → ``"model"``.
* **EP**: ``experts`` → ``"model"`` (expert weights live on their expert-
  parallel rank; the MoE combine's expert reduction becomes the TP
  all-reduce).
* **DP**: the batch dim of inputs → ``("pod", "data")``.
* **FSDP** (ZeRO-3): additionally shard each parameter's first *unsharded,
  divisible* axis over ``"data"`` — XLA inserts the all-gather before use
  and reduce-scatters the grads.
* **SP**: activation sequence dim → ``"model"`` between blocks (norms/
  elementwise run sequence-sharded; attention/mlp gather via TP collectives).

Divisibility guard: a logical rule only applies if the dim size divides the
mesh-axis size; otherwise that tensor axis is replicated (e.g. glm4's
kv_heads=2 on a 16-way model axis — query heads shard, KV replicate, which
is exactly how GQA TP is deployed in practice).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import RunConfig
from repro.models.params import P, tree_map_specs

# logical axis → mesh axis under TP/EP
_TP_RULES = {
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "expert_ffn": "model",
    "ssm_inner": "model",
    "experts": "model",
}
# never sharded (small / must be local)
_REPLICATED = {"head_dim", "layers", "conv", "ssm_state", "embed", None}


def _axis_size(mesh: Mesh, name: str | tuple[str, ...]) -> int:
    if isinstance(name, tuple):
        return int(np.prod([mesh.shape[n] for n in name]))
    return mesh.shape[name]


def logical_to_spec(p: P, mesh: Mesh, run: RunConfig) -> PartitionSpec:
    """Map one parameter spec's logical axes to a PartitionSpec."""
    out: list[Any] = []
    used: set[str] = set()
    for dim, ax in zip(p.shape, p.axes):
        assign = None
        if run.tp and ax in _TP_RULES:
            m = _TP_RULES[ax]
            if m not in used and dim % _axis_size(mesh, m) == 0:
                assign = m
                used.add(m)
        out.append(assign)
    # row-parallel fallback: if no dim took the model axis (e.g. minitron's
    # 24 heads on a 16-way TP axis), shard the embed (contracting) dim —
    # XLA lowers this as a local partial matmul + all-reduce (Megatron row
    # parallelism), keeping the weight sharded instead of replicated.
    # EXCEPT embedding tables (first axis "vocab"): they are gathered by
    # token id, and a gather from an embed-dim-sharded table trips the SPMD
    # partitioner (observed verifier failure); when vocab doesn't divide the
    # mesh they stay replicated.
    if (run.tp and "model" not in used and len(p.shape) > 1
            and p.axes[0] != "vocab"):
        for i, (dim, ax) in enumerate(zip(p.shape, p.axes)):
            if ax == "embed" and dim % _axis_size(mesh, "model") == 0:
                out[i] = "model"
                used.add("model")
                break
    if run.fsdp and "data" in mesh.shape:
        daxes = _data_axes(mesh)            # ("pod","data") on multi-pod
        dsize = _axis_size(mesh, daxes)
        for i, (dim, ax) in enumerate(zip(p.shape, p.axes)):
            if out[i] is None and ax not in ("layers",) and dim % dsize == 0 \
                    and dim >= dsize:
                out[i] = daxes if len(daxes) > 1 else daxes[0]
                break
    # trim trailing Nones (canonical PartitionSpec form)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def param_shardings(specs: Any, mesh: Mesh, run: RunConfig) -> Any:
    """NamedSharding tree matching a parameter spec tree."""
    return tree_map_specs(
        lambda p: NamedSharding(mesh, logical_to_spec(p, mesh, run)), specs)


def _data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_spec(mesh: Mesh, run: RunConfig, rank: int = 2,
               batch_size: int | None = None) -> PartitionSpec:
    """Inputs (B, S, ...): B over DP axes, S over model iff SP."""
    b = _data_axes(mesh)
    if b and batch_size is not None and batch_size % _axis_size(mesh, b):
        b = ()
    s = "model" if run.sp else None
    extra = [None] * (rank - 2)
    return PartitionSpec(b if b else None, s, *extra)


def batch_sharding(mesh: Mesh, run: RunConfig, rank: int = 2) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(mesh, run, rank))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def kv_cache_sharding(mesh: Mesh, run: RunConfig,
                      n_kv_heads: int) -> NamedSharding:
    """KV cache (L, B, S, K, hd): B over DP, K over model if divisible."""
    b = _data_axes(mesh)
    k = "model" if (run.tp and n_kv_heads % _axis_size(mesh, "model") == 0) \
        else None
    return NamedSharding(mesh, PartitionSpec(None, b if b else None,
                                             None, k, None))


def shard_batch_dim(tree: Any, mesh: Mesh, run: RunConfig,
                    batch_axis: int = 0) -> Any:
    """Sharding tree for an arbitrary pytree of batched arrays/structs."""
    def one(x):
        rank = len(x.shape)
        spec = [None] * rank
        b = _data_axes(mesh)
        if (rank > batch_axis and b
                and x.shape[batch_axis] % _axis_size(mesh, b) == 0):
            spec[batch_axis] = b
        return NamedSharding(mesh, PartitionSpec(*spec))
    return jax.tree.map(one, tree)


# --------------------------------------------------------------------------
# Derived shardings: optimizer state, decode state, whole train state
# --------------------------------------------------------------------------

def _pad_spec(spec: PartitionSpec, rank: int) -> list:
    out = list(spec)
    return out + [None] * (rank - len(out))


def reduced_spec(param_spec: PartitionSpec, param_rank: int,
                 dropped_dim: int) -> PartitionSpec:
    """Sharding of a rank-reduced moment (Adafactor vr/vc) from its param."""
    full = _pad_spec(param_spec, param_rank)
    del full[dropped_dim % param_rank]
    while full and full[-1] is None:
        full.pop()
    return PartitionSpec(*full)


def opt_state_shardings(opt_state_abstract: Any, param_shardings_tree: Any,
                        mesh: Mesh) -> Any:
    """Shardings for an optimizer-state pytree.

    AdamW moments mirror the parameter tree exactly; Adafactor's factored
    moments drop one trailing dim (matched by shape).  Anything that matches
    no parameter (counts, scalars) is replicated.
    """
    flat_params = [s.spec for s in jax.tree.leaves(param_shardings_tree)]
    # shape of each param leaf comes along with its sharding via id order —
    # so instead match by structure: state trees are built with
    # jax.tree.map over params, so each moment *tree* has the params treedef.
    rep = NamedSharding(mesh, PartitionSpec())

    def assign(state_tree):
        leaves, treedef = jax.tree.flatten(state_tree)
        if len(leaves) == len(flat_params):
            out = []
            for leaf, pspec in zip(leaves, flat_params):
                rank = len(leaf.shape)
                spec = _pad_spec(pspec, max(rank, len(pspec)))[:rank]
                # drop mesh axes that no longer divide (factored moments)
                spec = [a if a is not None and leaf.shape[i] %
                        _axis_size(mesh, a) == 0 else None
                        for i, a in enumerate(spec)]
                while spec and spec[-1] is None:
                    spec.pop()
                out.append(NamedSharding(mesh, PartitionSpec(*spec)))
            return treedef.unflatten(out)
        return jax.tree.map(lambda _: rep, state_tree)

    # optimizer states are NamedTuples of (trees | scalars)
    return type(opt_state_abstract)(*[
        assign(field) for field in opt_state_abstract])


def decode_state_shardings(state_abstract: Any, mesh: Mesh, run: RunConfig
                           ) -> Any:
    """Decode caches: batch over DP axes; heads/channels over model (TP).

    Works for transformer DecodeState (L,B,S,K,hd), SSMState conv
    (L,B,W,C) / ssd (L,B,H,P,N) and HybridState — by dimension heuristics:
    dim 1 is batch (dim 0 the stacked layer/site axis), and the largest
    remaining dim divisible by the model axis takes it (channels/heads).
    """
    b = _data_axes(mesh)
    msize = _axis_size(mesh, "model") if "model" in mesh.shape else 0

    def one(x):
        rank = len(x.shape)
        # newer jax canonicalizes 1-tuples in PartitionSpec; do it ourselves
        # so specs compare equal across versions
        bspec = b if len(b) > 1 else (b[0] if b else None)
        if rank <= 1:                          # lengths / scalars
            spec = [None] * rank
            if rank == 1 and b and x.shape[0] % _axis_size(mesh, b) == 0:
                spec[0] = bspec
            return NamedSharding(mesh, PartitionSpec(*spec))
        spec: list = [None] * rank
        if b and x.shape[1] % _axis_size(mesh, b) == 0:
            spec[1] = bspec
        if run.tp and msize:
            # prefer the kv-heads/channel dim (dim 3 of (L,B,S,K,hd) caches,
            # dim 3 of (L,B,H,P,N) ssd states): an in-place cache update at
            # a dynamic position on a SHARDED seq dim costs a partitioner
            # select over the whole cache — heads-sharding avoids it.  The
            # seq dim (dim 2) is the fallback when heads don't divide
            # (GQA kv < mesh), trading that select for 16× less cache/dev.
            cands = ([3, 2] + list(range(4, rank)) if rank >= 4
                     else list(range(2, rank)))
            for i in cands:
                if x.shape[i] % msize == 0 and x.shape[i] >= msize:
                    spec[i] = "model"
                    break
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree.map(one, state_abstract)


def with_sharding(abstract_tree: Any, sharding_tree: Any) -> Any:
    """Attach shardings to ShapeDtypeStructs (dry-run input specs)."""
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract_tree, sharding_tree)


# --------------------------------------------------------------------------
# Activation constraints (logical axes, applied inside traced model code)
# --------------------------------------------------------------------------
#
# Without these the SPMD partitioner is free to re-shard activations in the
# backward pass (we observed batch-replicated gradients with full cross-data
# all-reduces).  Every production JAX LLM stack pins activation shardings at
# block boundaries; models call ``constrain(x, run, "batch", "seq", None)``.

_ACT_RULES = {
    "batch": ("pod", "data"),     # intersected with the ambient mesh
    "seq": "model",               # only under run.sp (sequence parallelism)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "vocab": "model",
    "experts": "model",
    "ssm_inner": "model",
    None: None,
}


def constrain(x: jax.Array, run: RunConfig, *logical: str | None) -> jax.Array:
    """with_sharding_constraint by logical axis names, mesh-aware + safe.

    No-op when there is no ambient mesh (plain CPU tests) or when a dim does
    not divide its mesh axes (falls back to unconstrained for that dim).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.shape or int(np.prod(list(
            mesh.shape.values()))) == 1:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    spec: list[Any] = []
    used: set[str] = set()
    for dim, name in zip(x.shape, logical):
        tgt = _ACT_RULES.get(name)
        if name == "seq" and not run.sp:
            tgt = None
        if isinstance(tgt, tuple):
            tgt = tuple(a for a in tgt if a in mesh.shape and a not in used)
            tgt = tgt if tgt else None
        elif tgt is not None and (tgt not in mesh.shape or tgt in used):
            tgt = None
        if tgt is not None:
            size = (int(np.prod([mesh.shape[a] for a in tgt]))
                    if isinstance(tgt, tuple) else mesh.shape.get(tgt, 1))
            if size <= 1 or dim % size != 0:
                tgt = None
        if tgt is not None:
            used.update(tgt if isinstance(tgt, tuple) else (tgt,))
        spec.append(tgt)
    return jax.lax.with_sharding_constraint(
        x, PartitionSpec(*spec))
