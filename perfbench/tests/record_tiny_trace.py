"""Record the small xplane file the trace-reduction test reads
(`perfbench/tests/data/tiny.xplane.pb`), on the chip:
`python -m perfbench.tests.record_tiny_trace <out dir>`.

Two jitted programs, a few runs each, with host sleeps between them so that
the device has idle gaps, inside a `perfbench.window` span and with
`perfbench.sleep` spans over the sleeps."""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir):
    @jax.jit
    def tiny_matmul(a, b):
        return jnp.tanh(a @ b)

    @jax.jit
    def tiny_loop(a):
        return jax.lax.fori_loop(0, 4, lambda i, x: jnp.sin(x) * 1.01, a)

    a = jnp.ones((1024, 1024), jnp.bfloat16)
    tiny_matmul(a, a).block_until_ready()
    tiny_loop(a).block_until_ready()
    tmp = os.path.join(out_dir, "tiny_trace")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("perfbench.window"):
        for _ in range(3):
            tiny_matmul(a, a).block_until_ready()
            with jax.profiler.TraceAnnotation("perfbench.sleep"):
                time.sleep(0.002)
            tiny_loop(a).block_until_ready()
    jax.profiler.stop_trace()
    src = max(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True),
              key=os.path.getmtime)
    shutil.copy(src, os.path.join(out_dir, "tiny.xplane.pb"))
    print(os.path.getsize(src), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
