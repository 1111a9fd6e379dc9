"""device issue: what a pool's ``dispatch`` callbacks spent outside their
push phase and their calls, milliseconds a pool, the median over the
accounts the program filed (``own_ns`` = ``ptdev.dispatch`` - ``ptdev.push``
- ``ptdev.call``; ``chipbench/layers/pool_account.py``): operand lists,
slot landings, donation clearing, the write-backs of programs released at
dispatch, the in-flight entry. The host's own price of issuing, which
``dispatch_per_program`` stopped being once a call could block on the
device's queue."""

from chipbench.layers.pool_account import median_ms


def read(run):
    return median_ms("own_ns")
