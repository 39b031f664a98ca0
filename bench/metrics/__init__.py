"""One per-layer metric reader per file: ``read(ctx)`` returns the
number, or None where the run holds nothing to read."""
