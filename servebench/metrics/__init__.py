"""One reader per per-layer metric, ``<metric name>.py``, each with
``read(run) -> float | None`` (``run`` is ``run.RunData``). A reader that
finds nothing to read returns None and the metric is left out."""
