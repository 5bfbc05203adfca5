"""Record the small TPU trace the trace-reduction test reads.

    python3 tests/bench/data/record_trace.py   # on a machine with a TPU

Traces two jitted programs (a matmul and a Pallas kernel) with a host
sleep between them, then writes ``tpu_small.xplane.pb`` beside this file
and prints the device events the reduction will see.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HERE = os.path.dirname(os.path.abspath(__file__))


def _add_one(x_ref, o_ref):
    o_ref[...] = x_ref[...] + 1.0


@jax.jit
def matmul(a):
    return a @ a


@jax.jit
def add_one(x):
    return pl.pallas_call(_add_one,
                          out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


def main():
    assert jax.devices()[0].platform == "tpu", "needs a TPU"
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    x = jnp.ones((512, 1024), jnp.float32)
    jax.block_until_ready((matmul(a), add_one(x)))       # compile first
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for _ in range(3):
        jax.block_until_ready(matmul(a))
        time.sleep(0.01)
        jax.block_until_ready(add_one(x))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(HERE, "tpu_small.xplane.pb"))
    import sys
    sys.path[:0] = [os.path.join(HERE, "..", "..", "..")]
    from bench import xplane
    for chip in xplane.load(src):
        print(chip.name, "ops", chip.ops[:20])
        print(chip.name, "modules", chip.modules[:20])
    from jax.profiler import ProfileData
    for p in ProfileData.from_file(src).planes:
        print("plane", p.name, [ln.name for ln in p.lines])
        if p.name.startswith("/device:TPU:0"):
            for ln in p.lines:
                for e in list(ln.events)[:4]:
                    print("  ", ln.name, "|", e.name, e.start_ns,
                          e.duration_ns, [tuple(s) for s in e.stats][:8])
    print("bytes", os.path.getsize(src))


if __name__ == "__main__":
    main()
