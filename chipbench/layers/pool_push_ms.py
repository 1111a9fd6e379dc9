"""residency: the ``ptdev.push`` spans of a pool, milliseconds a pool, the
median over the accounts the program filed (``push_ns``;
``chipbench/layers/pool_account.py``). One span a ``dispatch`` callback
around the push phase: every distinct memory operand of the batch staged
in (a miss is a ``device_put``, the nested ``dev.stage_in``; a hit is the
table call, the data's lock, the LRU touch and the pin) before any program
of the batch is called."""

from chipbench.layers.pool_account import median_ms


def read(run):
    return median_ms("push_ns")
