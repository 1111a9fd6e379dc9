"""Graph drivers: one module per task graph, found by a configuration's
``graph`` key. See ``chipbench/README.md`` for what a driver provides."""
