"""On-chip serving benchmark: a data-driven harness around ``repro.serving``.

``run.py`` is the entry point. Each configuration, traffic mix, per-layer
metric reader, plain reference and correctness limit is a file of its own,
found by the name that ``BENCHMARK.json`` gives it (see ``spec.py``).
"""
