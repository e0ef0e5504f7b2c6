"""The benchmark's own code: everything the yardstick is made of lives
under ``benchmarks/`` so that a later PR cannot change it. Whatever
belongs to ONE configuration, traffic mix, generator kind, metric,
step-program count or reference sits in a file of its own, found by the
name ``BENCHMARK.json`` gives (``harness/spec.py``)."""
