"""device issue: from a pool's ``bind`` on the ``ptdev`` lane to the first
``ptdev.call`` it entered, milliseconds, the median over the accounts the
program filed (``head_ns``; ``chipbench/layers/pool_account.py``). Before
that moment the chip can hold no work of this pool: the head holds the
first ``dispatch`` callback's push phase (stage-in misses and hits of the
first batch's operands), its operand lists, and the manager's wake-up.
Lowering (``lower_per_solve``) comes before ``bind`` and is not in it."""

from chipbench.layers.pool_account import median_ms


def read(run):
    return median_ms("head_ns")
