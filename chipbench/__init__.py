"""The chip benchmark of the k-FED attach service: one process per run,
``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``. See ``run.py``."""
