"""The chip benchmark of the CFD memory-architecture flow.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chip
this process finds.  Everything a cell needs is found by name:
``configs/<config>.json``, ``traffic/<mix>.json`` (data) and the code of
its kind, ``kinds/<kind>.py``, ``programs/<program>.py`` and
``metrics/<metric>.py``.
"""
