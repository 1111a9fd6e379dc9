"""Graph driver: DPLASMA dgeqrf (the flat-tree tile QR, compact-WY storage:
V in A below the diagonal, T in a second tiled matrix), inserted through
``DTDTaskpool`` on one chip.

The DAG and the tile bodies are the program's (``ops/geqrf.py``); operands,
task and FLOP counts and the check are the benchmark's own. A is handed its
original host tiles before every solve, outside the timer, so every solve
stages A in. T is zeroed on the host once, at build (the caller's workspace,
as DPLASMA's testing_zgeqrf zeroes it with zlaset before it factors), and
never restored: GEQRT and TSQRT write it without reading it, so the warm-up
solve gives its tiles room on the device and moves no byte, and every later
solve finds the copies the last one wrote current there.
"""

import inspect
import sys
import types

import numpy as np

from chipbench.reference import geqrf as ref

#: what a program whose QR keeps no T is told
UNSUPPORTED = ("chipbench: this program's insert_geqrf_tasks takes no T "
               "collection (ops/geqrf.py), so it cannot store the factor as "
               "dtd_geqrf_f32 states it (V in A, T beside): the configuration "
               "is not supported here")


def _nt(traffic):
    return traffic["n"] // traffic["ts"]


def tasks(traffic):
    """NT GEQRT + NT(NT-1)/2 UNMQR + NT(NT-1)/2 TSQRT + sum_{j<NT} j^2
    TSMQR."""
    nt = _nt(traffic)
    return nt + nt * (nt - 1) + (nt - 1) * nt * (2 * nt - 1) // 6


def flops(traffic):
    """The dgeqrf count of LAWN 41 (PLASMA's flops.h FLOPS_DGEQRF) at
    M = N: 4N^3/3 + 2N^2 + 14N/3."""
    n = traffic["n"]
    return 4.0 * n ** 3 / 3.0 + 2.0 * n ** 2 + 14.0 * n / 3.0


def dot_flops(traffic):
    """FLOP of the update classes of one solve, by XLA module name: three
    dense TS x TS x TS products, 6 TS^3, in each UNMQR and each TSMQR (one
    level of blocking: the inner-blocked algorithm does 4 TS^3)."""
    nt, ts = _nt(traffic), traffic["ts"]
    return {"jit_tile_unmqr": nt * (nt - 1) // 2 * 6.0 * ts ** 3,
            "jit_tile_tsmqr": (nt - 1) * nt * (2 * nt - 1) // 6
            * 6.0 * ts ** 3}


def panel_flops(traffic):
    """FLOP of the panel classes of one solve, by XLA module name: the
    Householder QR (2 m n^2 - 2 n^3 / 3; m = TS for GEQRT, 2 TS for TSQRT's
    stack), V^T V (2 TS^3, a whole product) and T as the inverse of a
    TS x TS triangle (TS^3)."""
    nt, ts = _nt(traffic), traffic["ts"]
    tail = 2.0 * ts ** 3 + ts ** 3
    return {"jit_tile_geqrt": nt * (4.0 / 3.0 * ts ** 3 + tail),
            "jit_tile_tsqrt": nt * (nt - 1) // 2 * (10.0 / 3.0 * ts ** 3
                                                    + tail)}


KERNEL_MODULES = ("jit_tile_geqrt", "jit_tile_unmqr", "jit_tile_tsqrt",
                  "jit_tile_tsmqr")
PANEL_MODULES = ("jit_tile_geqrt", "jit_tile_tsqrt")


def build(run):
    """Context, A and T, A's host tiles from the seed. A program whose QR
    takes no T cannot run the configuration: the run ends here, before a
    tile is made, with the reason on stderr and exit code 1."""
    import parsec_tpu as pt
    from parsec_tpu.data.matrix import SymTwoDimBlockCyclic, TwoDimBlockCyclic
    from parsec_tpu.ops.geqrf import insert_geqrf_tasks

    if len(inspect.signature(insert_geqrf_tasks).parameters) < 3:
        sys.exit(UNSUPPORTED)
    st = types.SimpleNamespace()
    n, ts = run.traffic["n"], run.traffic["ts"]
    st.ctx = pt.Context(nb_cores=1)
    st.A = TwoDimBlockCyclic("A", n, n, ts, ts, P=1, Q=1)
    # T(k, k) and T(m, k), m > k: the lower triangle of tiles
    st.T = SymTwoDimBlockCyclic("T", n, n, ts, ts, P=1, Q=1)
    nt = _nt(run.traffic)
    st.a_keys = [(m, k) for m in range(nt) for k in range(nt)]
    st.t_keys = [(m, k) for m in range(nt) for k in range(m + 1)]
    st.host = run.make_tiles(
        st.a_keys, lambda mk: ref.operand_tile(n, ts, mk[0], mk[1], run.seed))
    zero = np.zeros((ts, ts), np.float32)
    st.T.fill(lambda m, k: zero)
    st.solves = 0
    restore(st, run)
    return st


def restore(st, run):
    """QR overwrites A: the original host tiles again, outside the solve's
    timer. T is left as the last solve wrote it."""
    st.A.fill(lambda m, k: st.host[m, k])


def solve(st, run):
    from parsec_tpu.dsl.dtd import DTDTaskpool
    from parsec_tpu.ops.geqrf import insert_geqrf_tasks

    tp = DTDTaskpool(st.ctx, "chipbench-geqrf")
    with run.span("insert"):
        inserted = insert_geqrf_tasks(tp, st.A, st.T)
    with run.span("wait"):
        drained = tp.wait(timeout=run.timeout)
        tp.close()
        st.ctx.wait(timeout=run.timeout)
        for m, k in st.a_keys:
            run.block(st.A.data_of(m, k).newest_copy().payload)
        for m, k in st.t_keys:
            run.block(st.T.data_of(m, k).newest_copy().payload)
    if not drained or inserted != run.tasks_per_solve:
        raise RuntimeError(f"QR pool: drained={drained}, inserted "
                           f"{inserted} of {run.tasks_per_solve} tasks")
    st.solves += 1
    return {"local_tasks": tp.local_inserted,
            "window_stalls": tp.window_stalls,
            "native_engine": getattr(tp, "_neng", None) is not None}


def counters(st, run):
    """``run.device_counters``, what the residency layer allocated for the
    flows written without being read (0 where the program has no such
    count) and wrote back, and the tasks issued in groups."""
    stats = st.ctx.devices.statistics()
    out = run.device_counters(st.ctx)
    for key in ("write_alloc_bytes", "write_allocs", "transfer_out_bytes",
                "batched_tasks"):
        out[key] = sum(int(s.get(key, 0)) for s in stats.values())
    return out


def check(st, run):
    n, ts = run.traffic["n"], run.traffic["ts"]
    tol = run.config["tolerance"]
    a_tile = lambda m, k: st.A.data_of(m, k).newest_copy().payload
    t_tile = lambda m, k: st.T.data_of(m, k).newest_copy().payload
    orig = lambda m, k: st.host[m, k]
    c = min(tol["r_tiles"], _nt(run.traffic))
    backward = ref.backward_errors(a_tile, t_tile, orig, n, ts)
    r_err = ref.leading_r_error(a_tile, orig, n, ts, c)
    ok = max(backward) < tol["backward"] and r_err < tol["leading_r"]
    return bool(ok), {"backward_error": max(backward),
                      "backward_by_block": backward,
                      "leading_r_error": r_err, "r_tiles": c,
                      "tolerance": {"backward": tol["backward"],
                                    "leading_r": tol["leading_r"]}}


def close(st, run):
    st.ctx.fini()
