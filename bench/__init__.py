"""The chip benchmark: TPC-H query streams served by Weld on one TPU.

``python bench/run.py --workload <config>.<mix> --seed N --seconds S
--trace 0|1`` runs one cell of ``BENCHMARK.json``.  Everything a cell
needs is found by name: ``configs/<config>.json``, ``mixes/<mix>.json``,
``queries/<query>.py`` and ``metrics/<metric>.py``.
"""
