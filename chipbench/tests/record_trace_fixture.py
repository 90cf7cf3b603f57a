"""Record ``fixtures/tiny.xplane.pb``, the trace ``test_trace.py`` reduces.

Run once on a TPU from the root of a checkout:

    python3 chipbench/tests/record_trace_fixture.py

Three executions of a small jitted program inside one benchmark span,
with 50 ms of host sleep before the second (inside a nested span) and
the third, so the trace holds known busy stretches and idle gaps.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


@jax.jit
def toy(x):
    for _ in range(8):
        x = jnp.tanh(x @ x) * 0.5
    return x


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    x = jnp.ones((512, 512), jnp.float32)
    toy(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(tmp, profiler_options=opts):
        with jax.profiler.TraceAnnotation("chipbench.request"):
            toy(x).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.pause"):
                time.sleep(0.05)
            toy(x).block_until_ready()
            time.sleep(0.05)
            toy(x).block_until_ready()
    (src,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    dst = os.path.join(HERE, "fixtures", "tiny.xplane.pb")
    os.makedirs(os.path.dirname(dst), exist_ok=True)
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print(dst, os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
