"""Device time of the serve plane's step per device delivered in the
traced window of ``cifar100-backlog``: the reader of
``serve_step_us_per_device.backlog``, which the configuration's widths
do not change."""
from chipbench.harness import metric_reader

_READER = metric_reader("serve_step_us_per_device.backlog")
SOURCE = _READER.SOURCE
read = _READER.read
