#!/usr/bin/env python3
"""Record the small profiler trace that ``test_tracing.py`` reads.

    python3 bench/tests/record_trace.py bench/tests/data/small.xplane.pb

Run it on a TPU. Inside a ``bench.window`` span it runs four rounds of two
small programs, a matrix product (``bench.step``) and an elementwise pass
(``bench.scale``), waiting for each, with a 2 ms host sleep
(``bench.sleep``) between them: the device is idle in the sleeps and in
the dispatches.
"""
import os
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import glob
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    g = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.ones((512, 512), jnp.float32)
    g(f(x)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    try:
        with jax.profiler.trace(tmp, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(4):
                    with jax.profiler.TraceAnnotation("bench.step"):
                        y = f(x).block_until_ready()
                    with jax.profiler.TraceAnnotation("bench.sleep"):
                        time.sleep(0.002)
                    with jax.profiler.TraceAnnotation("bench.scale"):
                        g(y).block_until_ready()
        src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)[0]
        shutil.copy(src, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"recorded {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
