"""device: 1 - union of device-busy intervals over the traced part of the
window, in percent."""


def read(run):
    if not run.trace or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
