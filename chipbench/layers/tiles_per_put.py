"""residency: how many tiles one ``jax.device_put`` of the ``ptdev`` lane's
push phase moves: ``PTDEV_STATS["staged_tiles"]`` (the tiles the push
phases' stage-ins moved onto the device: misses; a hit or an adoption moves
nothing) over ``PTDEV_STATS["stage_in_puts"]`` (the ``device_put`` calls
that moved them: one a ``dispatch`` callback whose batch had a miss). The
TPU client's price is per call, so the more tiles a call carries, the less
of the manager thread a stage-in costs (``ptdev_stage_in_per_tile``).
Process-lifetime totals, read after the run, like the readers beside it. A
program without the counters (one ``device_put`` a tile), or a run in
which the lane moved no tile, gives nothing to read."""


def read(run):
    from parsec_tpu.device.native import PTDEV_STATS

    puts = PTDEV_STATS.get("stage_in_puts")
    if not puts or "staged_tiles" not in PTDEV_STATS:
        return None
    return PTDEV_STATS["staged_tiles"] / puts
