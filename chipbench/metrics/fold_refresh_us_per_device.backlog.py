"""Device time of everything the window runs besides the serve step, per
device delivered in the traced window, from the profiler trace: the fold
scatter (``core/server.py`` ``aggregate_incremental``), the re-finalize
of Algorithm 2 at each refresh, and the request keys' derivation, all of
which the program runs as eager operations. ``FOLD`` must be among the
trace's modules and ``SERVE_STEP`` too, so that a renamed program reads
as missing rather than as a wrong sum."""
from chipbench.trace import module_seconds

SOURCE = "device_trace"
SERVE_STEP = ["jit_step"]
FOLD = ["jit_scatter"]


def read(rec):
    done = len(rec.delivered())
    if not rec.trace or not done:
        return None
    if module_seconds(rec.trace, SERVE_STEP) is None \
            or module_seconds(rec.trace, FOLD) is None:
        return None
    rest = sum(t for name, t in rec.trace["modules"].items()
               if name not in SERVE_STEP)
    return rest * 1e6 / done
