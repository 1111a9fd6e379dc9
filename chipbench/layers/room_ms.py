"""residency: the ``ptdev.room`` spans of a pool, milliseconds a pool, the
median over the accounts the program filed (``room_ns``;
``chipbench/layers/pool_account.py``). One span a miss of the lane's push
phase: the room the table reserves for it, its victims chosen and applied,
a dirty victim's write-back waited for; a part of ``pool_push_ms``. A
program without the field gives nothing to read."""

from chipbench.layers.pool_account import median_ms


def read(run):
    return median_ms("room_ns")
