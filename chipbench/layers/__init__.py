"""Per-layer metrics: one reader per metric, found by its name in
BENCHMARK.json. ``read(run)`` takes the value from the run's spans, counters
or reduced trace, and returns None where it finds nothing to read (the driver
refuses a line that lacks a listed metric: list a metric only for cells where
it always reads something). A reader
may set ``RANKS`` ("mean", "sum" or "max") to say how a cell across chips
combines its ranks' values; the mean is the default."""
