"""Per-layer metrics, one reader each: ``read(ctx)`` returns the value,
or None where there is nothing to read. Name, unit, layer and ``moves``
are ``BENCHMARK.json``'s alone."""
