"""The serve step's share of its roofline in ``cifar100-backlog``: the
reader of ``serve_step_roofline.backlog``, whose least time
(``work.py``) takes d, k and k' from the run's configuration (here
3,072, 100 and 10)."""
from chipbench.harness import metric_reader

_READER = metric_reader("serve_step_roofline.backlog")
SOURCE = _READER.SOURCE
read = _READER.read
