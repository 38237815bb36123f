"""Chip benchmark of the serving path: one cell, one run, one result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it: ``configs/<config>.json``,
``traffic/<mix>.json`` (read by ``generators/<generator>.py``),
``cells/<workload>.json`` (the cell's fixed rate and its correctness
limit) and ``metrics/<metric>.py``. The yardstick lives here too: the peak
table (``peaks.json``), the work counts (``work/``), the plain reference
(``reference.py``) and the comparison that decides ``correct``
(``check.py``).
"""
