"""One reader per metric: ``read(run)`` returns the number, or None where
the run holds nothing to read. Found by name from ``BENCHMARK.json``."""
