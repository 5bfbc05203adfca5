"""Record the small TPU trace the span-reduction test reads.

    python3 tests/bench/data/record_span_trace.py   # on a machine with a TPU

Three rounds, each inside a ``round`` span: the matmul of
``record_trace.py`` launched inside ``launch`` and waited for inside
``wait``, a 10 ms host sleep inside ``sleep``, then its Pallas kernel
launched and waited for inside ``kernel``.  Writes ``tpu_spans.xplane.pb``
beside this file and prints what ``bench/spans.py`` reads from it.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = ("round", "launch", "wait", "sleep", "kernel")


def main():
    sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "..")]
    from record_trace import add_one, matmul
    assert jax.devices()[0].platform == "tpu", "needs a TPU"
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    x = jnp.ones((512, 1024), jnp.float32)
    jax.block_until_ready((matmul(a), add_one(x)))       # compile first
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    for i in range(3):
        with TraceAnnotation("round", i=i):
            with TraceAnnotation("launch"):
                y = matmul(a)
            with TraceAnnotation("wait"):
                jax.block_until_ready(y)
            with TraceAnnotation("sleep"):
                time.sleep(0.01)
            with TraceAnnotation("kernel"):
                jax.block_until_ready(add_one(x))
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(HERE, "tpu_spans.xplane.pb")
    shutil.copy(src, dst)
    from bench import spans, xplane
    host = spans.load(dst, NAMES)
    print("launches", host.launches)
    print("spans", [(s.name, s.start_ns, s.end_ns) for s in host.spans])
    s = spans.reduce(host, xplane.load(dst))
    print("offset_ns", s.offset_ns, "runs", s.runs)
    print("device_s", s.device_s)
    print("idle_s", s.idle_s)
    print("bytes", os.path.getsize(dst))


if __name__ == "__main__":
    main()
