"""Graph driver: DPLASMA dpotrf (lower, right-looking) as a PTG: the
program's ``ops/potrf.py:POTRF_JDF`` (``src/zpotrf_L.jdf``'s four classes
over their triangular task space), compiled once and instantiated per solve
(as DPLASMA creates a taskpool per call), through the native execution lane
``ptexec``, region fusion and the native device lane ``ptdev``, all at the
program's defaults.

The PTG twin of ``graphs/potrf.py``: the same tile bodies, operands, restore
(the host tiles handed back before every solve, outside the timer, so every
solve stages its lower triangle in), task and FLOP counts and residual; the
two differ in the path only. The lane counters and the failed-solve rule are
``graphs/ptg_gemm.py``'s.
"""

import sys
import types

from chipbench.graphs import potrf as twin
from chipbench.graphs.ptg_gemm import _lane_stats as gemm_lane_stats
from chipbench.reference import ptg_potrf as ref

tasks = twin.tasks
flops = twin.flops
#: the original host tiles again before every solve, outside the timer, so
#: that every solve stages its lower triangle in: the twin's, to the letter
restore = twin.restore

#: every fused region's XLA module starts so; a mixed region's is named
#: after its classes in order of appearance (``jit_ptg_region_GEMM_SYRK``)
REGION_PREFIX = "jit_ptg_region_"


#: no module of this graph has a name of its own to sum kernel seconds by
KERNEL_MODULES = ()


def dot_flops(traffic):
    """The contract's FLOP by XLA module name: this graph has none to give,
    a region's module being named after whatever classes the plan put in
    it. The readers keyed by module name (``kernel_roofline``,
    ``region_roofline``) find nothing here; ``factor_region_roofline``
    reads :func:`dot_flops_total` over every module under
    :data:`REGION_PREFIX`."""
    return {}


def dot_flops_total(traffic):
    """FLOP of the products the region programs of a solve hold: 2 TS^3 for
    each trailing GEMM and each SYRK (``graphs/potrf.py:dot_flops``,
    summed). The ``cholesky`` and ``triangular_solve`` members are not
    counted."""
    return float(sum(twin.dot_flops(traffic).values()))


def dot_bytes(traffic):
    """Bytes those products move at the least: a GEMM reads A, B and C and
    writes C, a SYRK reads A and T and writes T, each tile once."""
    nt, ts = traffic["n"] // traffic["ts"], traffic["ts"]
    gemms, syrks = nt * (nt - 1) * (nt - 2) // 6, nt * (nt - 1) // 2
    return float((4 * gemms + 3 * syrks) * ts * ts * 4)


def _lane_stats():
    from parsec_tpu.dsl.fusion import CAPTURE_CACHE_STATS
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS

    out = gemm_lane_stats()
    out["ptexec.mixed_regions"] = int(PTEXEC_STATS["mixed_regions"])
    out["capture.cache_hits"] = int(CAPTURE_CACHE_STATS["cache_hits"])
    return out


def build(run):
    from parsec_tpu.ops import potrf as ops

    if not hasattr(ops, "potrf_taskpool"):
        print("chipbench: this program has no Cholesky written as a PTG "
              "(parsec_tpu/ops/potrf.py has no POTRF_JDF): ptg_potrf_f32 "
              "is not supported", file=sys.stderr)
        raise SystemExit(1)

    import parsec_tpu as pt
    from parsec_tpu.data.matrix import SymTwoDimBlockCyclic
    from parsec_tpu.utils.counters import install_native_counters

    install_native_counters()   # the ptdev lane's C-side counts, by name

    st = types.SimpleNamespace()
    n, ts = run.traffic["n"], run.traffic["ts"]
    st.ctx = pt.Context(nb_cores=1)
    # only the lower triangle is made and held, as the twin does
    st.A = SymTwoDimBlockCyclic("A", n, n, ts, ts)
    st.mine = [(m, k) for m in range(st.A.mt) for k in range(m + 1)]
    st.host = run.make_tiles(
        st.mine, lambda mk: ref.spd_tile(n, ts, mk[0], mk[1], run.seed))
    st.solves = 0
    restore(st, run)
    return st


def solve(st, run):
    from parsec_tpu.ops.potrf import potrf_taskpool

    before = _lane_stats()
    with run.span("insert"):
        tp = potrf_taskpool(st.ctx, st.A)
        st.ctx.add_taskpool(tp)
    with run.span("wait"):
        st.ctx.wait(timeout=run.timeout)
        for m, k in st.mine:
            run.block(st.A.data_of(m, k).newest_copy().payload)
    after = _lane_stats()
    d = {k: after[k] - before[k] for k in after}
    n = run.tasks_per_solve
    ok = tp.completed and all(d[k] == 0 for k in (
        "ptexec.pools_fallback", "ptexec.pools_ineligible",
        "ptdev.pools_fallback", "ptdev.pools_ineligible", "ptdev.cb_errors"))
    if not ok or d["ptexec.tasks_engaged"] != n \
            or d["ptdev.tasks_engaged"] != n:
        raise RuntimeError(f"PTG pool: completed={tp.completed}, a lane "
                           f"declined or lost tasks of {n}: {d}")
    st.solves += 1
    return {"local_tasks": n, "window_stalls": 0}


def counters(st, run):
    return {**run.device_counters(st.ctx), **_lane_stats()}


def check(st, run):
    n, ts = run.traffic["n"], run.traffic["ts"]
    return twin.check_factor(
        lambda m, j: st.A.data_of(m, j).newest_copy().payload,
        n, ts, run.seed, run.config["tolerance"]["value"])


def close(st, run):
    st.ctx.fini()
