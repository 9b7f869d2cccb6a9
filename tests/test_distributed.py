"""Multi-device tests for gradient compression and pipeline parallelism
(shard_map features need real devices → 8-device subprocess)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.compression import (ErrorFeedback, compress,
                                           decompress,
                                           compress_with_feedback)
from repro.distributed.pipeline import bubble_fraction


class TestCompressionLocal:
    def test_roundtrip(self):
        g = jnp.asarray([0.5, -1.25, 3.0], jnp.float32)
        q, s = compress(g)
        assert q.dtype == jnp.int8
        np.testing.assert_allclose(np.asarray(decompress(q, s)),
                                   np.asarray(g), atol=float(s) / 2 + 1e-6)

    def test_error_feedback_init(self):
        ef = ErrorFeedback.init({"w": jnp.ones((2, 3))})
        assert ef.residual["w"].shape == (2, 3)
        assert float(jnp.sum(jnp.abs(ef.residual["w"]))) == 0.0

    def test_feedback_captures_residual(self):
        g = jnp.asarray([0.3], jnp.float32)
        q, s, r = compress_with_feedback(g, jnp.zeros(1))
        np.testing.assert_allclose(
            np.asarray(decompress(q, s) + r), np.asarray(g), rtol=1e-6)


class TestPipelineLocal:
    def test_bubble_fraction(self):
        assert bubble_fraction(4, 12) == 3 / 15
        assert bubble_fraction(1, 8) == 0.0


_SPAWN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import functools
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((8,), ("pod",))

    # --- compressed psum vs exact psum (distributed.compression) -----------
    from repro.distributed.compression import psum_compressed
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
    r0 = jnp.zeros((8, 64))

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("pod"), P("pod")),
                       out_specs=(P("pod"), P("pod")))
    def mean_compressed(gs, rs):
        s, nr = psum_compressed(gs[0], rs[0], "pod")
        return s[None], nr[None]

    approx, _ = mean_compressed(g, r0)
    exact = jnp.mean(g, axis=0)
    err = float(jnp.max(jnp.abs(approx[0] - exact)))
    scale = float(jnp.max(jnp.abs(g))) / 127
    comp_ok = err <= scale + 1e-5

    # --- gpipe forward == direct stacked forward (distributed.pipeline) ----
    from repro.distributed.pipeline import gpipe
    L, M, b, s, d = 8, 4, 2, 4, 16
    ws = jax.random.normal(jax.random.PRNGKey(1), (L, d, d)) * 0.2
    xs = jax.random.normal(jax.random.PRNGKey(2), (M, b, s, d))

    def stage_fn(wstack, x):                     # 1 layer per device
        return jnp.tanh(x @ wstack[0])

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("pod"), P()),
                       out_specs=P("pod"))
    def run_pipe(local_w, x_mbs):
        # results are only valid on the last stage; stack per-stage buffers
        return gpipe(stage_fn, local_w, x_mbs, axis="pod")[None]

    out = run_pipe(ws.reshape(8, 1, d, d), xs)[-1]   # last stage's buffer
    ref = xs
    for i in range(L):
        ref = jnp.tanh(ref @ ws[i])
    pipe_err = float(jnp.max(jnp.abs(out - ref)))

    print(json.dumps({"comp_ok": bool(comp_ok), "comp_err": err,
                      "pipe_err": pipe_err}))
""")


class TestMultiDevice:
    def test_compression_and_pipeline_on_8_devices(self):
        env = dict(os.environ,
                   PYTHONPATH=os.path.abspath(
                       os.path.join(os.path.dirname(__file__), "..", "src")))
        out = subprocess.run([sys.executable, "-c", _SPAWN], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        assert rec["comp_ok"], rec
        assert rec["pipe_err"] < 1e-4, rec
