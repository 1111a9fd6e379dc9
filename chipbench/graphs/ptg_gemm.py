"""Graph driver: tiled GEMM as a PTG (``BASELINE.json`` config 2 as it is
written): the JDF of ``examples/ex06_gemm_ptg.py``, ``GEMM(m, n, k)`` with one
k-chain per C tile, compiled once and instantiated per solve (as DPLASMA
creates a taskpool per call), through the native execution lane ``ptexec``,
region fusion and the native device lane ``ptdev``, all at the program's
defaults.

C is set to zero on the device between solves, outside the timer, so every
solve computes C = A B from zero and the check is of one product.
"""

import os
import sys
import types

import numpy as np

from chipbench.reference import ptg_gemm as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the XLA module of the fused k-chain: one program for every region
REGION_MODULE = "jit_ptg_region_GEMM"
KERNEL_MODULES = (REGION_MODULE,)


def tasks(traffic):
    """PTG task instances of a solve, however many programs carry them."""
    return (traffic["n"] // traffic["ts"]) ** 3


def flops(traffic):
    return 2.0 * traffic["n"] ** 3


def dot_flops(traffic):
    return {REGION_MODULE: flops(traffic)}


def dot_bytes(traffic):
    """Bytes the fused k-chains of a solve move at the least: a region reads
    its row of A tiles, its column of B tiles and C, and writes C."""
    nt = traffic["n"] // traffic["ts"]
    return {REGION_MODULE: float(nt * nt * (2 * nt + 2)
                                 * traffic["ts"] ** 2 * 4)}


def _lane_stats():
    from parsec_tpu.device.native import PTDEV_STATS
    from parsec_tpu.dsl.fusion import CAPTURE_CACHE_STATS
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS
    from parsec_tpu.utils.counters import counters

    out = {"ptexec." + k: int(PTEXEC_STATS[k]) for k in (
        "pools_engaged", "tasks_engaged", "pools_fallback",
        "pools_ineligible", "tasks_device", "fused_regions",
        "region_programs")}
    out.update({"ptdev." + k: int(PTDEV_STATS[k]) for k in (
        "pools_engaged", "tasks_engaged", "pools_fallback",
        "pools_ineligible")})
    out.update({"ptdev." + k: int(counters.read("ptdev." + k))
                for k in ("dispatched", "cb_errors")})
    out["capture.cache_evictions"] = int(
        CAPTURE_CACHE_STATS["cache_evictions"])
    return out


def build(run):
    from parsec_tpu.dsl.ptg import compiler

    if "region_programs" not in compiler.PTEXEC_STATS:
        # one executable per fused region: 1,024 keys walk a 128-entry
        # cache, every solve builds them all anew, inside the window
        print("chipbench: this program builds a fused PTG region's "
              "executable once per region, not once per shape of region: "
              "ptg_gemm_f32 is not supported", file=sys.stderr)
        raise SystemExit(1)

    import jax

    import parsec_tpu as pt
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.utils.counters import install_native_counters

    install_native_counters()   # the ptdev lane's C-side counts, by name

    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import ex06_gemm_ptg

    st = types.SimpleNamespace()
    n, ts = run.traffic["n"], run.traffic["ts"]
    st.nt = nt = n // ts
    st.ctx = pt.Context(nb_cores=1)
    st.A, st.B, st.C = (TwoDimBlockCyclic(name, n, n, ts, ts)
                        for name in ("A", "B", "C"))
    grid = [(m, k) for m in range(nt) for k in range(nt)]
    st.a_host = run.make_tiles(
        grid, lambda mk: ref.operand_tile(0, ts, mk[0], mk[1], run.seed))
    st.b_host = run.make_tiles(
        grid, lambda mk: ref.operand_tile(1, ts, mk[0], mk[1], run.seed))
    st.A.fill(lambda m, k: st.a_host[m, k])
    st.B.fill(lambda k, j: st.b_host[k, j])
    zeros = np.zeros((ts, ts), np.float32)
    st.C.fill(lambda m, j: zeros)
    st.zero = jax.device_put(zeros, jax.devices()[0])
    st.program = compiler.compile_ptg(ex06_gemm_ptg.SRC, "gemm")
    st.solves = 0
    return st


def restore(st, run):
    """C back to zero, on the device: its newest copy becomes one shared
    device tile of zeros (the first solve finds the zeros it was filled
    with)."""
    if not st.solves:
        return
    for m in range(st.nt):
        for j in range(st.nt):
            data = st.C.data_of(m, j)
            data.get_copy(0).payload = st.zero
            data.bump_version(0)


def solve(st, run):
    before = _lane_stats()
    with run.span("insert"):
        tp = st.program.instantiate(
            st.ctx, globals={"MT": st.nt, "NT": st.nt, "KT": st.nt},
            collections={"descA": st.A, "descB": st.B, "descC": st.C})
        st.ctx.add_taskpool(tp)
    with run.span("wait"):
        st.ctx.wait(timeout=run.timeout)
        for m in range(st.nt):
            for j in range(st.nt):
                run.block(st.C.data_of(m, j).newest_copy().payload)
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
    n = run.traffic["n"]
    rows = ref.sample_rows(st.nt, run.seed)

    def c_tile(m, j):
        return st.C.data_of(m, j).newest_copy().payload
    err = ref.max_abs_err(c_tile, st.a_host, st.b_host, st.nt, rows)
    # beside it, the same check with the reference one precision down: on
    # the chip it reads several times the bound, or the bound would let a
    # cheaper dot pass for the stated one (the CHECK detail, every run)
    err_high = ref.max_abs_err(c_tile, st.a_host, st.b_host, st.nt, rows,
                               precision="high")
    tol = ref.tolerance(n, run.config["tolerance"]["value"])
    return bool(err < tol), {
        "max_abs_err": err, "tolerance": tol,
        "max_abs_err_against_precision_high": err_high,
        "tiles_checked": len(rows) * st.nt}


def close(st, run):
    st.ctx.fini()
