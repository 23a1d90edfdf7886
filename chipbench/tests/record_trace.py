"""Record the small trace that ``test_chipbench_trace.py`` reduces: on a
TPU, a jitted matmul-sum, a jitted sin and the matmul-sum again, 10 ms
apart, under the harness's ``chipbench.window`` span, with the Python
tracer off so that the file stays small.

    python3 chipbench/tests/record_trace.py OUT.xplane.pb
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import trace

    f = jax.jit(lambda x: (x @ x).sum())
    g = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir, profiler_options=opts)
    time.sleep(0.005)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        f(x).block_until_ready()
        time.sleep(0.01)
        g(x).block_until_ready()
        time.sleep(0.01)
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(trace.find_xplane(logdir), out)
    shutil.rmtree(logdir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
