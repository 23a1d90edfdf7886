"""Median time from a request's due time to its labels in the caller's
hands, over all requests due in the window (host clock)."""
from chipbench.stat import latencies_ms, percentile

SOURCE = "host_clock"


def read(rec):
    return percentile(latencies_ms(rec), 50)
