"""Record the small device trace ``tpu_small.xplane.pb`` that the trace
reduction's test reads: two short jitted programs inside a
``bench.window`` span, each dispatched under a ``bench.dispatch`` span,
with host sleeps between them that leave the device idle. Run on a TPU:

  python3 benchmarks/chip/tests/data/record_trace.py <out_dir>

Copies the trace to ``<out_dir>/tpu_small.xplane.pb`` and prints the
planes and lines it holds.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation


def main(out_dir):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    mm = jax.jit(lambda a: jnp.tanh(a @ a))
    red = jax.jit(lambda a: jnp.sum(a * a, axis=0))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready((mm(x), red(x)))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench.window"):
        for _ in range(3):
            with TraceAnnotation("bench.dispatch"):
                y = mm(x)
            jax.block_until_ready(y)
            with TraceAnnotation("bench.verify"):
                time.sleep(0.002)
            with TraceAnnotation("bench.dispatch"):
                z = red(y)
            jax.block_until_ready(z)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "tpu_small.xplane.pb")
    shutil.copy(src, dst)
    with open(dst, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    for plane in pd.planes:
        lines = [(ln.name, len(list(ln.events))) for ln in plane.lines]
        print(plane.name, lines[:12])
    print("bytes", os.path.getsize(dst))


if __name__ == "__main__":
    main(sys.argv[1])
