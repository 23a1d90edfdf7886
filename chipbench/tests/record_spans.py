"""Record the small trace that ``test_chipbench_spans.py`` reduces: on a
TPU, under the harness's ``chipbench.window`` span, two flushes whose
``kfed.*`` spans are opened by hand around jitted calls and known
sleeps, the way ``repro/fed/telemetry.py`` nests them, with the Python
tracer off so that the file stays small:

* 5 ms outside any span;
* ``kfed.flush`` 1: ``kfed.prep`` (4 ms sleep); ``kfed.step`` (the
  jitted ``step`` run to completion, so the span brackets its device
  module: the clock check); ``kfed.fold`` (a jitted sin dispatched, then
  ``kfed.refresh``: an 8 ms sleep and ``step`` run to completion);
  ``kfed.deliver`` (the sin's result fetched);
* 3 ms outside;
* ``kfed.flush`` 2: ``kfed.prep`` (2 ms), ``kfed.step`` (``step``
  dispatched, not waited for), ``kfed.deliver`` (its result fetched);
* 2 ms outside.

    python3 chipbench/tests/record_spans.py OUT.xplane.pb
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
    import numpy as np

    from chipbench import trace

    def step(x):
        return (x @ x).sum()

    f = jax.jit(step)
    g = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    g(x).block_until_ready()
    span = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir, profiler_options=opts)
    time.sleep(0.005)
    with span(trace.WINDOW_SPAN):
        time.sleep(0.005)
        with span("kfed.flush", flush=1):
            with span("kfed.prep", flush=1):
                time.sleep(0.004)
            with span("kfed.step", flush=1, rung=1024, rows=1):
                f(x).block_until_ready()
            with span("kfed.fold", flush=1):
                y = g(x)
                with span("kfed.refresh", flush=1):
                    time.sleep(0.008)
                    f(x).block_until_ready()
            with span("kfed.deliver", flush=1):
                np.asarray(y)
        time.sleep(0.003)
        with span("kfed.flush", flush=2):
            with span("kfed.prep", flush=2):
                time.sleep(0.002)
            with span("kfed.step", flush=2, rung=1024, rows=1):
                z = f(x)
            with span("kfed.deliver", flush=2):
                np.asarray(z)
        time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(trace.find_xplane(logdir), out)
    shutil.rmtree(logdir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
