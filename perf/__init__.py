"""Cell benchmark of the serve path: one run of one cell per process.

``python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
"""
