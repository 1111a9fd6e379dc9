"""Ex09: real multi-process launch — run with

    python -m parsec_tpu.launch -n 2 --cpu examples/ex09_tcp_launch.py
    python -m parsec_tpu.launch -n 4 --bind-devices \\
        examples/ex09_tcp_launch.py --n 16384 --ts 512 --grid 2x2

Each process joins the TCP mesh (init_from_env = the MPI_Init moment),
builds its rank's tiles of a block-cyclic SPD matrix from a seed, and runs a
distributed DTD Cholesky with cross-process activate/put dataflow — the same
program that runs on in-process ranks in Ex07, now with a real process
boundary (ref workflow: mpiexec -n N over parsec_mpi_funnelled). With
``--bind-devices`` every rank owns exactly one chip (BASELINE config 3: the
2x2 block-cyclic dpotrf). Each rank checks its own tiles, on its own device,
against XLA's Cholesky of the same matrix, and prints one ``EX09 {json}``
line.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from _common import setup  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--ts", type=int, default=16)
    ap.add_argument("--grid", default="", help="PxQ (default: ranks x 1)")
    ap.add_argument("--seed", type=int, default=7)
    opts = ap.parse_args()
    setup()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.comm.tcp import init_from_env
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.dsl.dtd import DTDTaskpool
    from parsec_tpu.ops.potrf import insert_potrf_tasks, spd_tile

    ce = init_from_env()
    ctx = Context(nb_cores=1, my_rank=ce.my_rank, nb_ranks=ce.nb_ranks)
    RemoteDepEngine(ctx, ce)
    devs = jax.devices()

    n, ts, seed = opts.n, opts.ts, opts.seed
    T = n // ts
    P, Q = (int(x) for x in opts.grid.split("x")) if opts.grid \
        else (ce.nb_ranks, 1)
    A = TwoDimBlockCyclic("A", n, n, ts, ts, P=P, Q=Q,
                          nodes=ce.nb_ranks, myrank=ce.my_rank)
    A.fill(lambda m, k: spd_tile(n, ts, m, k, seed))

    tp = DTDTaskpool(ctx, "ex09-potrf")
    insert_potrf_tasks(tp, A)
    drained = tp.wait(timeout=600)
    tp.close()
    ctx.wait(timeout=600)
    executed = {name: int(s["executed_tasks"])
                for name, s in ctx.devices.statistics().items()}
    ctx.fini()

    # every rank checks its own tiles against XLA's factor of the same
    # matrix (the Cholesky factor is unique), on its own device: worst
    # per-tile relative Frobenius error
    mine = [(m, k) for m in range(T) for k in range(m + 1)
            if A.rank_of(m, k) == ce.my_rank]
    full = jnp.asarray(np.block([[spd_tile(n, ts, m, k, seed)
                                  for k in range(T)] for m in range(T)]))
    with jax.default_matmul_precision("highest"):
        ref = jnp.linalg.cholesky(full).reshape(T, ts, T, ts)
    got = jnp.stack([jnp.asarray(A.data_of(m, k).newest_copy().payload)
                     for m, k in mine])
    want = jnp.stack([ref[m, :, k, :] for m, k in mine])
    err = float(jnp.max(jnp.linalg.norm(got - want, axis=(1, 2))
                        / jnp.linalg.norm(want, axis=(1, 2))))
    ok = bool(drained) and err < 1e-3
    print("EX09 " + json.dumps({
        "rank": ce.my_rank, "nranks": ce.nb_ranks, "grid": [P, Q],
        "n": n, "ts": ts, "platform": devs[0].platform,
        "device_kind": devs[0].device_kind, "device_count": len(devs),
        "chip": os.environ.get("TPU_VISIBLE_CHIPS"),
        "tasks_local": tp.local_inserted, "executed": executed,
        "rel_err": err, "ok": ok,
        "error": None if ok else f"drained={drained} rel_err={err}"}),
        flush=True)
    ce.sync()
    ce.fini()
    assert ok, f"rank {ce.my_rank}: drained={drained} rel_err={err}"


if __name__ == "__main__":
    main()
