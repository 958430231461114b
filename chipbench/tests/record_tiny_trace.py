"""Record ``data/tiny_v5e.xplane.pb``, the trace the reduction is tested on.
Run on the chip from the root of the repo (``chiprun -- python3
chipbench/tests/record_tiny_trace.py``); the trace comes back under
``chiprun_out/tiny/`` and is copied beside this file by hand."""
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402
from jax.experimental import pallas as pl                  # noqa: E402

from chipbench.trace import reduce                         # noqa: E402


def add_kernel(x_ref, y_ref, o_ref):
    o_ref[...] = x_ref[...] + y_ref[...]


@jax.jit
def tiny_step(x):
    y = x @ x
    z = pl.pallas_call(add_kernel, out_shape=jax.ShapeDtypeStruct(
        x.shape, x.dtype))(y, x)
    return jnp.tanh(z).sum()


def main():
    x = jnp.ones((512, 512), jnp.float32)
    tiny_step(x).block_until_ready()
    out = "chiprun_out/tiny"
    with reduce.capture(out + "/trace"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                tiny_step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                time.sleep(0.01)
    path = reduce.find_xplane(out + "/trace")
    shutil.copy(path, out + "/tiny_v5e.xplane.pb")
    t = reduce.Trace(path)
    print(os.path.getsize(path), t.window_s, t.busy_s(), t.device_ops(),
          t.idle_gaps())


if __name__ == "__main__":
    main()
