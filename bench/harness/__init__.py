"""Benchmark harness for packed-codebook serving on a TPU.

``bench/run.py`` runs one cell of ``BENCHMARK.json`` once.  Everything
that belongs to one configuration, traffic mix or per-layer metric is a
file of its own under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, found by the name ``BENCHMARK.json`` gives it.
"""
