"""ready/sched: the part of a pool's life the lane's manager thread spent
outside the pool's callbacks, milliseconds a pool, the median over the
accounts the program filed (``away_ns`` = ``life`` - dispatch - poll -
retire; ``chipbench/layers/pool_account.py``): the engine's release walk
between a poll that reports nodes and the dispatch of their successors,
the C lane's wake-ups and timed waits, and the tail between the last call
and the pass that sees the last program complete."""

from chipbench.layers.pool_account import median_ms


def read(run):
    return median_ms("away_ns")
