"""kernels: share of the chip's bf16 peak that the products of the
factorization's region programs reach in those programs' device time: the
FLOP of the trailing GEMMs and SYRKs of a traced solve (the graph driver's
``dot_flops_total``) over the peak of ``peaks.json`` over the device seconds
of every module whose name starts ``jit_ptg_region_``. Those seconds also
hold the ``cholesky`` and ``triangular_solve`` members of the mixed regions,
which do latency-bound work and count no FLOP here, so it reads under
``kernel_roofline``'s 14.6 % of the same tiles through DTD and far under 100;
f32 at ``HIGHEST`` is several bf16 passes, a ceiling of about a sixth. A
graph without ``dot_flops_total``, or a trace without such a module, gives
nothing to read."""


def read(run):
    total = getattr(run.graph, "dot_flops_total", None)
    if total is None or not run.trace or not run.trace["solves"] \
            or not run.peaks:
        return None
    secs = sum(m["seconds"] for name, m in run.trace["modules"].items()
               if name.startswith(run.graph.REGION_PREFIX))
    if not secs:
        return None
    return 100.0 * total(run.traffic) * run.trace["solves"] \
        / run.peaks["bf16_flops_per_s"] / secs
