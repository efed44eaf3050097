"""One small reader per per-layer metric, found by the ``reader`` key of
``benchmark/metrics/<metric>.json``.  ``read(run)`` returns the number,
or ``None`` where it finds nothing to read (the metric is then left out
of the line; a share of a roofline or of a peak is never reported as 0)."""
