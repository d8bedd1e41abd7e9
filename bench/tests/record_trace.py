#!/usr/bin/env python3
"""Record the small device trace that ``test_devtrace.py`` reads.

    python3 bench/tests/record_trace.py     # on a TPU; rewrites bench/tests/data/

Traces three calls of one jitted program that holds what the benchmark's
readers look for: the program's ``cross_gram`` Pallas kernel, a
convolution and its gradient, with a host sleep between calls so the
trace has idle gaps.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax
import jax.numpy as jnp

from repro.kernels import ops


def step(u, v, x, w):
    g = ops.cross_gram(u, v)
    conv = lambda w: jnp.sum(jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")) ** 2)
    return g, jax.grad(conv)(w)


def main():
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    key = jax.random.PRNGKey(0)
    ku, kv, kx, kw = jax.random.split(key, 4)
    u = jax.random.normal(ku, (10, 8192), jnp.float32)
    v = jax.random.normal(kv, (100, 8192), jnp.float32)
    x = jax.random.normal(kx, (32, 32, 32, 3), jnp.float32)
    w = jax.random.normal(kw, (5, 5, 3, 32), jnp.float32)
    f = jax.jit(step)
    jax.block_until_ready(f(u, v, x, w))
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for _ in range(3):
        jax.block_until_ready(f(u, v, x, w))
        time.sleep(0.01)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.join(HERE, "data"), exist_ok=True)
    shutil.copy(src, os.path.join(HERE, "data", "small.xplane.pb"))
    print("recorded", os.path.getsize(src), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
