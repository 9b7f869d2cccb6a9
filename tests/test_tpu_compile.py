"""Main-path Pallas kernels compile for a TPU v5e chip at published widths.

The TPU compiler ships with jaxlib, so a chip that is described rather
than attached (``v5e:2x2``) still refuses what the real one would: block
shapes off the (8, 128) tiling, kernels that overrun VMEM, primitives
Mosaic cannot lower.  Interpret-mode tests see none of that.  Each case
lowers a kernel with ``interpret=False`` for one chip of the described
topology and asserts the compiled program holds the Mosaic custom call.

The topology is described only inside the module fixture: the TPU
library admits one loading process at a time, so describing it while a
module is imported would break every other test worker.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ert import bandwidth, flops, gemm
from repro.kernels.flash_attention.ops import flash_attention_gqa
from repro.kernels.fused import adamw, norm, swiglu
from repro.kernels.ssd_scan.ops import ssd_scan_model_layout

BF16, F32 = jnp.bfloat16, jnp.float32

# minitron-4b attention: 8 KV heads x 3 query heads each, head_dim 128
_Q, _KV = (1, 8, 3, 128), (1, 8, 128)
# mamba2-1.3b SSD: 64 heads x 64, state 128, one 4k sequence
_SSD_S = 4096
# minitron-4b widths over an 8192-token batch; AdamW over one MLP matrix
_ROWS, _D, _FF = 8192, 3072, 9216

#: name -> (kernel called with interpret=False, [(shape, dtype), ...])
CASES = {
    "triad": (lambda a, b: bandwidth.triad(a, b, interpret=False),
              [((1 << 26,), F32)] * 2),
    "fma_chain": (lambda x: flops.fma_chain(x, interpret=False),
                  [((1 << 24,), F32)]),
    "gemm": (lambda a, b: gemm.matmul(a, b, interpret=False),
             [((4096, 4096), BF16)] * 2),
    "flash_gqa_s2048": (
        lambda q, k, v: flash_attention_gqa(q, k, v, interpret=False),
        [((1, 2048) + _Q[1:], BF16)] + [((1, 2048) + _KV[1:], BF16)] * 2),
    "flash_gqa_s32": (
        lambda q, k, v: flash_attention_gqa(q, k, v, interpret=False),
        [((1, 32) + _Q[1:], BF16)] + [((1, 32) + _KV[1:], BF16)] * 2),
    "ssd_scan": (
        lambda x, a, b, c: ssd_scan_model_layout(x, a, b, c,
                                                 interpret=False),
        [((1, _SSD_S, 64, 64), F32), ((1, _SSD_S, 64), F32),
         ((1, _SSD_S, 128), F32), ((1, _SSD_S, 128), F32)]),
    "fused_rmsnorm": (
        lambda x, s: norm.fused_rmsnorm(x, s, interpret=False),
        [((_ROWS, _D), BF16), ((_D,), F32)]),
    "fused_rmsnorm_residual": (
        lambda x, h, s: norm.fused_rmsnorm_residual(x, h, s,
                                                    interpret=False),
        [((_ROWS, _D), BF16)] * 2 + [((_D,), F32)]),
    "fused_swiglu": (
        lambda g, u: swiglu.fused_swiglu(g, u, interpret=False),
        [((_ROWS, _FF), BF16)] * 2),
    "fused_adamw": (
        lambda g, m, v, p, b1, b2: adamw.fused_adamw(
            g, m, v, p, b1, b2, interpret=False),
        [((_D, _FF), F32)] * 4 + [((), F32)] * 2),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A program compiled for a described chip can be written to the
    persistent cache but never read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
