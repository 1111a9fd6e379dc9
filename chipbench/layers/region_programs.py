"""PTG lowering: region executables the process built
(``PTEXEC_STATS["region_programs"]``: one per shape of region, all of them
in the warm-up solve; the window may build none). Each is traced, compiled
or loaded from the cache in set-up. A program without the count gives
nothing to read."""


def read(run):
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS

    return PTEXEC_STATS.get("region_programs")
