#!/usr/bin/env python3
"""How ``small.xplane.pb`` was made (on one TPU v5e chip, through the
builder's chip tool): six runs of a small jitted program (two matrix
products and a sort), traced with the Python tracer off.

    python3 perfbench/tests/recorded/record.py <out-dir>
"""

import glob
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def small_step(x, w):
    y = jnp.tanh(x @ w)
    return (jnp.sort(y, axis=-1) @ w.T) / 64.0


def main(out_dir: str) -> None:
    x = jnp.ones((256, 512), jnp.float32)
    w = jnp.asarray(np.random.default_rng(0).normal(size=(512, 512)),
                    jnp.float32)
    small_step(x, w).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = os.path.join(out_dir, "_trace")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(6):
        x = small_step(x, w)
        np.asarray(x[0, :4])
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))[0]
    shutil.copy(found, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(sys.argv[1])
