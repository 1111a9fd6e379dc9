"""Graph driver: DPLASMA dpotrf (lower, right-looking), inserted through
``DTDTaskpool`` — on one chip, or on a P x Q block-cyclic grid of ranks.

The DAG and the tile bodies are the program's (``ops/potrf.py``); operands,
task and FLOP counts and the check are the benchmark's own.
"""

import os
import types

import numpy as np

from chipbench.reference import potrf as ref


def tasks(traffic):
    """Tasks of the DAG: NT potrf + NT(NT-1)/2 trsm + NT(NT-1)/2 syrk +
    NT(NT-1)(NT-2)/6 gemm = NT(NT+1)(NT+2)/6."""
    nt = traffic["n"] // traffic["ts"]
    return nt * (nt + 1) * (nt + 2) // 6


def flops(traffic):
    """The standard dpotrf count, N^3/3 + N^2/2 (lower order kept as the
    upstream harness does)."""
    n = traffic["n"]
    return n ** 3 / 3.0 + n ** 2 / 2.0


def dot_flops(traffic):
    """FLOP of the dot-bearing task classes of one solve, by XLA module
    name: 2 TS^3 for each trailing GEMM, and the same for each SYRK (the
    body computes the whole tile product, not a triangle)."""
    nt, ts = traffic["n"] // traffic["ts"], traffic["ts"]
    return {"jit_tile_gemm_update": nt * (nt - 1) * (nt - 2) // 6 * 2.0 * ts ** 3,
            "jit_tile_syrk": nt * (nt - 1) // 2 * 2.0 * ts ** 3}


KERNEL_MODULES = ("jit_tile_potrf", "jit_tile_trsm", "jit_tile_syrk",
                  "jit_tile_gemm_update")


def build(run):
    import parsec_tpu as pt
    from parsec_tpu.data.matrix import SymTwoDimBlockCyclic

    st = types.SimpleNamespace()
    n, ts = run.traffic["n"], run.traffic["ts"]
    p, q = run.config["distribution"]["P"], run.config["distribution"]["Q"]
    if run.comm is not None:
        from parsec_tpu.comm.remote_dep import RemoteDepEngine
        st.ctx = pt.Context(nb_cores=1, my_rank=run.rank,
                            nb_ranks=run.nranks)
        RemoteDepEngine(st.ctx, run.comm)
    else:
        st.ctx = pt.Context(nb_cores=1)
    # only the lower triangle is made and held: the square at N = 49152
    # would be 9.7 GB of host memory for nothing
    st.A = SymTwoDimBlockCyclic("A", n, n, ts, ts, P=p, Q=q,
                                nodes=run.nranks, myrank=run.rank)
    st.mine = [(m, k) for m in range(st.A.mt) for k in range(m + 1)
               if st.A.rank_of(m, k) == run.rank]
    st.host = run.make_tiles(
        st.mine, lambda mk: ref.spd_tile(n, ts, mk[0], mk[1], run.seed))
    st.solves = 0
    restore(st, run)
    return st


def restore(st, run):
    """POTRF overwrites its matrix: hand the collection the original host
    tiles again (outside the solve's timer), so that every solve stages
    its lower triangle in, as a PaRSEC GPU run does."""
    st.A.fill(lambda m, k: st.host[m, k])


def solve(st, run):
    from parsec_tpu.dsl.dtd import DTDTaskpool
    from parsec_tpu.ops.potrf import insert_potrf_tasks

    tp = DTDTaskpool(st.ctx, "chipbench-potrf")
    with run.span("insert"):
        inserted = insert_potrf_tasks(tp, st.A)
    with run.span("wait"):
        drained = tp.wait(timeout=run.timeout)
        tp.close()
        st.ctx.wait(timeout=run.timeout)
        for m, k in st.mine:
            run.block(st.A.data_of(m, k).newest_copy().payload)
    if not drained or inserted != run.tasks_per_solve:
        raise RuntimeError(f"POTRF pool: drained={drained}, inserted "
                           f"{inserted} of {run.tasks_per_solve} tasks")
    st.solves += 1
    return {"local_tasks": tp.local_inserted,
            "window_stalls": tp.window_stalls,
            "native_engine": getattr(tp, "_neng", None) is not None}


def counters(st, run):
    return run.device_counters(st.ctx)


def dump(st, run, directory):
    """A rank's tiles of the last factor, for the parent's checker."""
    os.makedirs(directory, exist_ok=True)
    for m, k in st.mine:
        np.save(os.path.join(directory, f"L_{m}_{k}.npy"),
                np.asarray(st.A.data_of(m, k).newest_copy().payload))


def check(st, run):
    n, ts = run.traffic["n"], run.traffic["ts"]
    return check_factor(lambda m, j: st.A.data_of(m, j).newest_copy().payload,
                        n, ts, run.seed, run.config["tolerance"]["value"])


def check_dumped(directory, traffic, seed, config):
    """The parent's checker child: the same residual over dumped tiles."""
    return check_factor(
        lambda m, j: np.load(os.path.join(directory, f"L_{m}_{j}.npy")),
        traffic["n"], traffic["ts"], seed, config["tolerance"]["value"])


def check_factor(factor_tile, n, ts, seed, tol):
    sample = ref.sample_tiles(n // ts, seed)
    rel = ref.residual(factor_tile, n, ts, seed, sample)
    return bool(rel < tol), {"rel_residual": rel, "tolerance": tol,
                             "tiles_checked": len(sample)}


def close(st, run):
    st.ctx.fini()
