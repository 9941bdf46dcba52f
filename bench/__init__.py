"""The benchmark: a data-driven harness that serves each cell of
``BENCHMARK.json`` on the chip and reports its metrics (see ``run.py``)."""
