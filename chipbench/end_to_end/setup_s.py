"""Process start to the first timed solve: start-up, context, operands,
the warm-up solve with its executable loads and first stage-in."""


def read(run):
    return run.setup_s
