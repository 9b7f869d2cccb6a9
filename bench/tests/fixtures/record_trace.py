#!/usr/bin/env python3
"""Records ``small.xplane.pb``, the trace the reduction's tests read.

    python3 bench/tests/fixtures/record_trace.py <out_dir>

Run on a TPU: a convolution and a matmul, each twice, between host spans
named as the benchmark names its own, with a 5 ms host sleep between them
so that the device idles under a span.  The ``.xplane.pb`` lands under
``<out_dir>/plugins/profile/<time>/``.
"""

import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    k = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(k[0], (1, 256, 256, 64), jnp.bfloat16)
    w = jax.random.normal(k[1], (3, 3, 64, 128), jnp.bfloat16)
    m = jax.random.normal(k[2], (2048, 2048), jnp.bfloat16)
    conv = jax.jit(lambda x, w: jax.nn.relu(jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))))
    mm = jax.jit(lambda a: jnp.tanh(a @ a))
    conv(x, w).block_until_ready()
    mm(m).block_until_ready()
    jax.profiler.start_trace(out_dir)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.conv"):
            conv(x, w).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host_wait"):
            time.sleep(0.005)
        with jax.profiler.TraceAnnotation("bench.matmul"):
            mm(m).block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
