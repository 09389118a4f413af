"""The chip benchmark of ompi_tpu: cells driven by data.

``run.py`` runs one cell once. Everything that belongs to one
configuration, traffic mix, entry driver, reference or metric lives in
a file of its own under this directory and is found by its name in
``BENCHMARK.json``; see ``harness.py``.
"""
