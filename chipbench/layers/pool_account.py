"""What the ``pool_*_ms`` readers share: the median of one field over the
accounts the program filed (``parsec_tpu.utils.xla_trace.POOL_ACCOUNTS``:
one plain dict a pool that ended on the ``ptdev`` lane with the spans on,
the last 64, written on the lane's manager thread). Both PTG cells make a
pool a solve, so a run's accounts are its warm-up solve and its window's
solves (the traced first seconds among them); the median drops the warm-up
pool, whose calls load the executables. A program without the account, or
a run in which no pool ended on the lane, gives nothing to read."""

import statistics


def median_ms(field):
    try:
        from parsec_tpu.utils.xla_trace import POOL_ACCOUNTS
    except ImportError:         # a program from before the account
        return None
    values = [a[field] for a in POOL_ACCOUNTS if field in a]
    return statistics.median(values) / 1e6 if values else None
