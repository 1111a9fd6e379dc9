"""device: seconds of a solve in which nothing ran on the device (traced
part of the window, per solve): what stage-in and the host leave exposed."""


def read(run):
    if not run.trace or not run.trace["solves"]:
        return None
    return (run.trace["window_s"] - run.trace["busy_s"]) / run.trace["solves"]
