"""Per-layer metric readers, one file per metric of ``BENCHMARK.json``'s
``per_layer`` list: ``read(ctx)`` takes a ``harness.Reading`` and returns
the metric's value, or ``None`` when the traced stretch holds nothing to
read (the harness then leaves the metric out of the result line)."""
