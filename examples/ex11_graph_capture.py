"""Ex11: whole-DAG graph capture — one XLA executable per taskpool.

The same tiled Cholesky as Ex07, but the taskpool is CAPTURED: the
insert_task sequence records instead of scheduling, and wait() compiles the
entire DAG into a single jitted program (dsl/capture.py). On a real chip
this amortizes per-task dispatch to one launch and lets XLA fuse across
task boundaries; re-running the same DAG shape reuses the compiled
executable (watch the second run's time).

    python examples/ex11_graph_capture.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from _common import setup  # noqa: E402


def main():
    setup()
    import numpy as np

    import parsec_tpu as pt
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.dsl.dtd import DTDTaskpool
    from parsec_tpu.ops.potrf import insert_potrf_tasks, make_spd

    n, ts = 256, 64
    spd = make_spd(n, seed=4)
    ctx = pt.Context(nb_cores=1)
    A = TwoDimBlockCyclic("A", n, n, ts, ts, P=1, Q=1)

    def factorize() -> float:
        A.fill(lambda m, k: spd[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
        tp = DTDTaskpool(ctx, "potrf-cap", capture=True)
        t0 = time.perf_counter()
        insert_potrf_tasks(tp, A)
        tp.wait()           # trace (first time) + execute as ONE program
        tp.close()
        dt = time.perf_counter() - t0
        print(f"  {tp.inserted} tasks as one executable: {dt*1e3:.1f} ms "
              f"(cache {'hit' if tp._capture.cache_hit else 'miss'})")
        return dt

    print("first run (compiles the whole DAG):")
    factorize()
    print("second run (compiled program cached):")
    factorize()
    ctx.wait()

    L = np.tril(A.to_dense().astype(np.float64))
    err = float(np.abs(L @ L.T - spd).max())
    print(f"||L L^T - A||_max = {err:.2e}")
    ctx.fini()
    assert err < 1e-2


if __name__ == "__main__":
    main()
