"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals / traced window)."""
from chipbench.stat import idle_share

SOURCE = "device_trace"


def read(rec):
    return idle_share(rec)
