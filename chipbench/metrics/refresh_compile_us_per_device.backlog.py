"""Host time spent building programs inside the window, per device
delivered: the durations JAX reports for tracing, lowering to MLIR and
compiling (``/jax/core/compile/...``). Every shape the harness drives is
built in set-up, so what is left is the program's own: each sync refresh
runs Algorithm 2's max-min growth (``core/lloyd.py`` ``maxmin_grow``) as
an eager loop, which is traced and compiled anew with the refresh's
arrays in it."""
SOURCE = "program_span"


def read(rec):
    done = len(rec.delivered())
    if rec.compile_s is None or not done:
        return None
    return rec.compile_s * 1e6 / done
