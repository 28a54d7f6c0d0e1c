"""Per-layer metric readers: ``<metric>.py`` with ``read(ctx)``, which
returns the metric's value, or None where the run has nothing to read.
``ctx`` is :class:`bench.trace.Context`."""
