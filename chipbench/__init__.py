"""On-chip benchmark of the served ParIS+ search path and the index build.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` and prints one JSON
line. Everything about a cell lives in data files found by name:
``configs/<config>.json`` (the deployment), ``traffic/<mix>.json`` (the
traffic, naming a driver in ``drivers/``) and ``metrics/<metric>.json``
(a per-layer metric, naming a reader in ``reducers/``).
"""
