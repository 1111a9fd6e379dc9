"""End-to-end metrics: one reader per metric, found by its name in
BENCHMARK.json. ``read(run)`` takes the value from a finished run."""
