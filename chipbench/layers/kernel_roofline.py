"""kernels: share of the chip's bf16 peak that the dot-bearing task classes
reach in their own kernel time: their FLOP from shapes (the graph driver's
``dot_flops``) over the peak of ``peaks.json`` over their device seconds.
Compute-bound. These cells run f32 at ``HIGHEST``, several bf16 MXU passes
for each product, so their ceiling is about a sixth of that peak."""


def read(run):
    if not run.trace or not run.trace["solves"] or not run.peaks:
        return None
    flop = secs = 0.0
    for name, per_solve in run.graph.dot_flops(run.traffic).items():
        module = run.trace["modules"].get(name)
        if module is None:
            return None
        flop += per_solve * run.trace["solves"]
        secs += module["seconds"]
    return 100.0 * flop / run.peaks["bf16_flops_per_s"] / secs if secs \
        else None
