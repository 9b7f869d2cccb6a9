"""Sharding-rule unit tests (no multi-device needed) + an 8-device
subprocess integration test that lowers/compiles a real sharded train step
and checks the collective analysis (the mini dry-run).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec

from repro.configs.base import RunConfig
from repro.distributed import sharding as shd
from repro.models.params import P


def _fake_mesh(shape=(4, 4), axes=("data", "model")) -> Mesh:
    """A Mesh over a device grid for *spec* computation only (no compile)."""
    import numpy as np
    devs = np.asarray([jax.devices()[0]] * int(np.prod(shape))).reshape(shape)
    return Mesh(devs, axes)


MESH = _fake_mesh()
RUN = RunConfig()


class TestLogicalRules:
    def test_tp_shards_heads(self):
        p = P((64, 8, 16), ("embed", "heads", "head_dim"))
        spec = shd.logical_to_spec(p, MESH, RUN)
        assert spec == PartitionSpec(None, "model")

    def test_divisibility_guard_replicates(self):
        p = P((64, 6, 16), ("embed", "heads", "head_dim"))  # 6 % 4 != 0
        spec = shd.logical_to_spec(p, MESH, RUN)
        # row-parallel fallback takes the embed dim instead
        assert spec == PartitionSpec("model")

    def test_embedding_table_never_row_sharded(self):
        p = P((50277, 64), ("vocab", "embed"))   # vocab doesn't divide
        spec = shd.logical_to_spec(p, MESH, RUN)
        assert spec == PartitionSpec()

    def test_fsdp_takes_first_free_dim(self):
        p = P((64, 8, 16), ("embed", "heads", "head_dim"))
        spec = shd.logical_to_spec(p, MESH, RunConfig(fsdp=True))
        assert spec == PartitionSpec("data", "model")

    def test_experts_to_model(self):
        p = P((8, 64, 32), ("experts", "embed", "expert_ffn"))
        spec = shd.logical_to_spec(p, MESH, RUN)
        assert spec[0] == "model"

    def test_layers_axis_never_sharded(self):
        p = P((12, 64, 8, 16), ("layers", "embed", "heads", "head_dim"))
        spec = shd.logical_to_spec(p, MESH, RunConfig(fsdp=True))
        assert spec[0] is None

    def test_tp_off_replicates(self):
        p = P((64, 8, 16), ("embed", "heads", "head_dim"))
        assert shd.logical_to_spec(p, MESH, RunConfig(tp=False)) == \
            PartitionSpec()


class TestBatchSpecs:
    def test_batch_over_data(self):
        assert shd.batch_spec(MESH, RUN) == PartitionSpec(("data",), None)

    def test_indivisible_batch_replicates(self):
        assert shd.batch_spec(MESH, RUN, batch_size=1) == \
            PartitionSpec(None, None)

    def test_sp_shards_seq(self):
        assert shd.batch_spec(MESH, RunConfig(sp=True)) == \
            PartitionSpec(("data",), "model")

    def test_multi_pod_axes(self):
        mesh = _fake_mesh((2, 2, 2), ("pod", "data", "model"))
        assert shd.batch_spec(mesh, RUN) == \
            PartitionSpec(("pod", "data"), None)


class TestDecodeStateShardings:
    def test_kv_heads_preferred_over_seq(self):
        cache = jax.ShapeDtypeStruct((4, 8, 64, 8, 16), jnp.bfloat16)
        sh = shd.decode_state_shardings(cache, MESH, RUN)
        assert sh.spec == PartitionSpec(None, "data", None, "model")

    def test_seq_fallback_when_heads_indivisible(self):
        cache = jax.ShapeDtypeStruct((4, 8, 64, 2, 16), jnp.bfloat16)
        sh = shd.decode_state_shardings(cache, MESH, RUN)
        assert sh.spec == PartitionSpec(None, "data", "model")

    def test_scalar_length_replicated(self):
        ln = jax.ShapeDtypeStruct((), jnp.int32)
        sh = shd.decode_state_shardings(ln, MESH, RUN)
        assert sh.spec == PartitionSpec()


class TestConstrain:
    def test_noop_without_mesh(self):
        x = jnp.ones((4, 8))
        y = shd.constrain(x, RUN, "batch", None)
        assert y is x   # identity outside any mesh


_SPAWN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs.base import RunConfig, SHAPES, ShapeSpec
    from repro.configs.registry import get_smoke
    from repro.core import analyze_compiled, get_machine, roofline_terms
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.models import api as M
    from repro.train import step as TS

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_smoke("granite-8b")
    run = RunConfig(amp="O1")
    model = M.build(cfg)
    shape = ShapeSpec("t", 32, 4, "train")

    state_abs = TS.abstract_state(model, run)
    pshard = shd.param_shardings(model.spec, mesh, run)
    oshard = shd.opt_state_shardings(state_abs.opt, pshard, mesh)
    rep = shd.replicated(mesh)
    state_sh = TS.TrainState(
        params=pshard, opt=oshard,
        loss_scale=jax.tree.map(lambda _: rep, state_abs.loss_scale),
        step=rep)
    state_specs = shd.with_sharding(state_abs, state_sh)
    batch_abs = M.input_specs(cfg, shape)
    batch_specs = shd.with_sharding(
        batch_abs, shd.shard_batch_dim(batch_abs, mesh, run))

    step = TS.make_train_step(model, run)
    with jax.set_mesh(mesh):
        compiled = jax.jit(step, donate_argnums=0).lower(
            state_specs, batch_specs).compile()
    an = analyze_compiled(compiled, devices_per_pod=8)
    terms = roofline_terms(an, get_machine("tpu-v5e"))

    # elastic re-mesh: save sharded state from the (2,4) mesh, restore it
    # onto a (4,2) mesh with different shardings — values must survive
    import tempfile, numpy as np
    from repro.checkpoint import checkpointer as ckpt
    from repro.train.step import init_state
    with jax.set_mesh(mesh):
        state = init_state(model, run, jax.random.PRNGKey(0))
        state = jax.device_put(state, state_sh)
    mesh2 = make_mesh((4, 2), ("data", "model"))
    pshard2 = shd.param_shardings(model.spec, mesh2, run)
    oshard2 = shd.opt_state_shardings(state_abs.opt, pshard2, mesh2)
    rep2 = shd.replicated(mesh2)
    sh2 = TS.TrainState(
        params=pshard2, opt=oshard2,
        loss_scale=jax.tree.map(lambda _: rep2, state_abs.loss_scale),
        step=rep2)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, state)
        restored, _ = ckpt.restore(d, state_abs, shardings=sh2)
    leaf0 = jax.tree.leaves(state.params)[0]
    leaf1 = jax.tree.leaves(restored.params)[0]
    elastic_ok = bool(np.allclose(np.asarray(leaf0), np.asarray(leaf1)))
    resharded = jax.tree.leaves(restored.params)[0].sharding.mesh.shape \
        == {"data": 4, "model": 2}

    print(json.dumps({
        "kernels": len(an.kernels),
        "collectives": len(an.collectives),
        "has_all_reduce": any(c.opcode == "all-reduce"
                              for c in an.collectives),
        "flops": an.total_flops,
        "compute_s": terms.compute_s,
        "elastic_ok": elastic_ok,
        "resharded": bool(resharded),
    }))
""")


class TestMiniDryRun:
    """Real 8-device SPMD compile in a subprocess (device count is locked
    per-process, so the 1-device test process spawns a fresh one)."""

    def test_sharded_train_step_compiles_with_collectives(self):
        env = dict(os.environ,
                   PYTHONPATH=os.path.abspath(
                       os.path.join(os.path.dirname(__file__), "..", "src")))
        out = subprocess.run([sys.executable, "-c", _SPAWN], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        assert rec["kernels"] > 10
        assert rec["collectives"] > 0
        assert rec["has_all_reduce"]          # TP/DP reductions present
        assert rec["flops"] > 0
        assert rec["compute_s"] > 0
        assert rec["elastic_ok"]              # checkpoint survives re-mesh
        assert rec["resharded"]
