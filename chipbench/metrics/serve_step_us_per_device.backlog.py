"""Device time of the serve plane's step (``fed/plane.py``
``ServePlane.step``: Algorithm 1 steps 1-3 and the fused solve+attach)
per device delivered in the traced window, from the profiler trace.
``MODULES`` are the step's XLA module names as the trace shows them."""
from chipbench.trace import module_seconds

SOURCE = "device_trace"
MODULES = ["jit_step"]


def read(rec):
    done = len(rec.delivered())
    t = module_seconds(rec.trace, MODULES) if rec.trace else None
    return t * 1e6 / done if t and done else None
