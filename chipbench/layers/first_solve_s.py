"""entry: host clock around the warm-up solve (executable loads from the
persistent cache, first stage-in of the operands)."""

RANKS = "max"


def read(run):
    return run.first_solve_s
