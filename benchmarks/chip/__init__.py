"""Chip benchmark of the served path: one process per run, driven by data.

``run.py`` is the entry point. A cell of ``BENCHMARK.json`` names a
configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); each metric is a reader of its own
(``metrics/<name>.py``). A configuration names its architecture
(``arch/<name>.py``: its weight tree, its plain reference and the work a
token does in each layer). Of the run's modules only ``engine_cell.py``
imports the program, to drive the system under test; the architectures and
their references import nothing of it. The tools beside them
(``probe.py``, ``sweep.py``, ``readings.py``, ``compile_v5e.py``) size the
cells and set the limits.
"""
