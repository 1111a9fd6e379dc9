"""Ex18: tiled Cholesky as a PTG: DPLASMA's dpotrf JDF (BASELINE config 3's
DAG, written as its users write it), beside ex07's insert_task spelling."""
from _common import setup

def main():
    setup()
    import numpy as np
    import parsec_tpu as pt
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.ops.potrf import make_spd, potrf_taskpool

    NT, TS = 4, 64
    n = NT * TS
    spd = make_spd(n, seed=1)
    ctx = pt.init(nb_cores=1)
    A = TiledMatrix("A", n, n, TS, TS)
    A.fill(lambda m, k: spd[m*TS:(m+1)*TS, k*TS:(k+1)*TS])
    # POTRF_JDF is compiled once a process; every call is a new taskpool.
    # Its bodies call tile_potrf / tile_trsm / tile_syrk / tile_gemm_update
    # by name: potrf_taskpool hands them in as globals
    tp = potrf_taskpool(ctx, A)
    ctx.add_taskpool(tp)
    ctx.wait()
    L = np.tril(A.to_dense())
    err = np.abs(L @ L.T - spd).max()
    print(f"ex18 PTG POTRF: {NT*(NT+1)*(NT+2)//6} tasks, residual {err:.2e}")
    assert tp.completed and err < 1e-4
    pt.fini()

if __name__ == "__main__":
    main()
