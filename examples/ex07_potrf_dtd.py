"""Ex07: tiled Cholesky through the dynamic interface (BASELINE config 3)."""
from _common import setup

def main():
    setup()
    import numpy as np
    import parsec_tpu as pt
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.dsl.dtd import DTDTaskpool
    from parsec_tpu.ops.potrf import insert_potrf_tasks, make_spd

    n, ts = 256, 64
    spd = make_spd(n, seed=1)
    ctx = pt.init(nb_cores=1)
    A = TiledMatrix("A", n, n, ts, ts)
    A.fill(lambda m, k: spd[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    tp = DTDTaskpool(ctx, "potrf")
    ntasks = insert_potrf_tasks(tp, A)
    tp.wait(); tp.close(); ctx.wait()
    L = np.tril(A.to_dense())
    err = np.abs(L @ L.T - spd).max()
    print(f"ex07 DTD POTRF: {ntasks} tasks, residual {err:.2e}")
    pt.fini()

if __name__ == "__main__":
    main()
