"""End-to-end metrics, one reader each: ``read(ctx)`` -> value or None."""
