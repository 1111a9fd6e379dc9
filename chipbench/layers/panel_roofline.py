"""kernels: share of the chip's bf16 peak that the Householder panels reach
in their own kernel time: their FLOP from shapes (the graph driver's
``panel_flops``: the QR, V^T V and the triangular inverse that forms T)
over the peak of ``peaks.json`` over their device seconds. A latency-bound
kernel, f32 at ``HIGHEST``: it reads far under ``kernel_roofline``."""


def read(run):
    panel = getattr(run.graph, "panel_flops", None)
    if panel is None or not run.trace or not run.trace["solves"] \
            or not run.peaks:
        return None
    flop = secs = 0.0
    for name, per_solve in panel(run.traffic).items():
        module = run.trace["modules"].get(name)
        if module is None:
            return None
        flop += per_solve * run.trace["solves"]
        secs += module["seconds"]
    return 100.0 * flop / run.peaks["bf16_flops_per_s"] / secs if secs \
        else None
