"""95th percentile of the time a request waits in the host queue: from
its due time to the start of the flush that served it (host clock)."""
from chipbench.stat import percentile

SOURCE = "host_clock"


def read(rec):
    return percentile(((r["flush"] - r["due"]) * 1e3
                       for r in rec.requests if r["flush"] is not None), 95)
