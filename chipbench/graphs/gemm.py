"""Graph driver: tiled GEMM, one task per (m, n, k), inserted through
``DTDTaskpool`` on one chip (the upstream harness
``tests/dsl/dtd/dtd_test_simple_gemm.c``).

Operands stay resident after the warm-up solve and C accumulates: after K
solves C = K A B, and the tolerance scales by K.
"""

import types

import numpy as np

from chipbench.reference import gemm as ref


def tasks(traffic):
    return (traffic["n"] // traffic["ts"]) ** 3


def flops(traffic):
    """2 M N K, the upstream harness's ``gflops = 2MNK/1e9/t``."""
    return 2.0 * traffic["n"] ** 3


def dot_flops(traffic):
    return {"jit_tile_gemm": flops(traffic)}


KERNEL_MODULES = ("jit_tile_gemm",)


def build(run):
    import parsec_tpu as pt
    from parsec_tpu.data.matrix import TwoDimBlockCyclic

    st = types.SimpleNamespace()
    n, ts = run.traffic["n"], run.traffic["ts"]
    nt = n // ts
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
    st.C.fill(lambda m, j: np.zeros((ts, ts), np.float32))
    st.solves = 0
    return st


def restore(st, run):
    """Nothing: A and B stay resident, C accumulates."""


def solve(st, run):
    from parsec_tpu.dsl.dtd import DTDTaskpool
    from parsec_tpu.ops.gemm import insert_gemm_tasks

    tp = DTDTaskpool(st.ctx, "chipbench-gemm")
    with run.span("insert"):
        inserted = insert_gemm_tasks(tp, st.A, st.B, st.C)
    with run.span("wait"):
        drained = tp.wait(timeout=run.timeout)
        tp.close()
        st.ctx.wait(timeout=run.timeout)
        for m in range(st.C.mt):
            for j in range(st.C.nt):
                run.block(st.C.data_of(m, j).newest_copy().payload)
    if not drained or inserted != run.tasks_per_solve:
        raise RuntimeError(f"GEMM pool: drained={drained}, inserted "
                           f"{inserted} of {run.tasks_per_solve} tasks")
    st.solves += 1
    return {"local_tasks": tp.local_inserted,
            "window_stalls": tp.window_stalls,
            "native_engine": getattr(tp, "_neng", None) is not None}


def counters(st, run):
    return run.device_counters(st.ctx)


def check(st, run):
    n, ts = run.traffic["n"], run.traffic["ts"]
    nt = n // ts
    rows = ref.sample_rows(nt, run.seed)
    err = ref.max_abs_err(
        lambda m, j: st.C.data_of(m, j).newest_copy().payload,
        st.a_host, st.b_host, nt, rows, float(st.solves))
    tol = ref.tolerance(n, st.solves, run.config["tolerance"]["value"])
    return bool(err < tol), {"max_abs_err": err, "tolerance": tol,
                             "solves_accumulated": st.solves,
                             "tiles_checked": len(rows) * nt}


def close(st, run):
    st.ctx.fini()
