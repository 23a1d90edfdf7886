"""Share of the traced window of ``cifar100-backlog`` in which no
operation ran on the device: the reader of
``device_idle_share.backlog``."""
from chipbench.harness import metric_reader

_READER = metric_reader("device_idle_share.backlog")
SOURCE = _READER.SOURCE
read = _READER.read
