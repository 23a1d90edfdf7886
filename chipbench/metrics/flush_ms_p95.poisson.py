"""95th percentile of the host time of one ``Session.flush_versioned``
call, over the window's flushes (host clock)."""
from chipbench.stat import percentile

SOURCE = "host_clock"


def read(rec):
    return percentile(((f["end"] - f["start"]) * 1e3
                       for f in rec.flushes), 95)
