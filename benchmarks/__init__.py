"""Benchmark harnesses for the BASELINE.md configs, run by the ``make bench-*``
targets; the tests share their service and request builders."""
