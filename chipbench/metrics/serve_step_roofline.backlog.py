"""The serve step's share of its roofline: the least time the chip could
take for the solve+attach mathematics of every request delivered in the
traced window (``work.py``: real points only, bytes bind), over the
device time of the serve step's modules, in %."""
from chipbench.trace import module_seconds
from chipbench.work import least_seconds

SOURCE = "device_trace"
MODULES = ["jit_step"]


def read(rec):
    t = module_seconds(rec.trace, MODULES) if rec.trace else None
    done = rec.delivered()
    if not t or not done:
        return None
    p = rec.config["plan"]
    least = least_seconds([r["n"] for r in done], len(rec.flushes), p["d"],
                          p["k"], p["k_prime"], rec.peaks)
    return 100.0 * least / t
