"""device issue: what share of the device programs the ``ptdev`` lane
called went to the chip while a later program of their dispatch round was
still to be pushed (its output room made, its operands staged): 100 x
``PTDEV_STATS["called_in_push"]`` over ``PTDEV_STATS["programs"]``. A
round pushes and calls program by program, so every program of a round but
the last that pushes counts; a round of one program counts none. In an
out-of-core pool it is the head round's packs, which the chip starts on
while the manager still makes room for the rest. Process-lifetime totals,
read after the run, like the readers beside it. A program without the
counter (it pushes the whole round before its first call), or a run in
which no program ran, gives nothing to read."""


def read(run):
    from parsec_tpu.device.native import PTDEV_STATS

    programs = PTDEV_STATS.get("programs")
    if not programs or "called_in_push" not in PTDEV_STATS:
        return None
    return 100.0 * PTDEV_STATS["called_in_push"] / programs
