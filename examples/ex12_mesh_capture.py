"""Ex12: multi-chip in one launch — mesh capture and the data bridge.

Runs on an 8-device virtual mesh (works anywhere):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/ex12_mesh_capture.py

1. A tiled GEMM taskpool is captured and compiled into ONE GSPMD program
   over a 2x4 device mesh (`tp.wait_mesh`): tiles become slices of sharded
   globals, XLA partitions the ops and inserts the ICI transfers.
2. The result hands off to the SPMD world through the mesh data bridge
   (`to_global` / `from_global`) for a jitted sharded post-step, then back
   to a regular taskpool — both worlds on the same matrices.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from _common import setup  # noqa: E402


def main():
    setup()
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import parsec_tpu as pt
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.data.mesh_bridge import from_global, to_global
    from parsec_tpu.dsl.dtd import DTDTaskpool, RW
    from parsec_tpu.ops.gemm import insert_gemm_tasks

    devs = jax.devices()
    if len(devs) < 8:
        print(f"only {len(devs)} device(s); set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return
    mesh = Mesh(np.array(devs[:8]).reshape(2, 4), ("x", "y"))
    print(f"mesh: {mesh.devices.shape} over {len(devs)} devices")

    n, ts = 64, 16
    rng = np.random.default_rng(7)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)

    ctx = pt.Context(nb_cores=1)
    A = TwoDimBlockCyclic("A", n, n, ts, ts)
    B = TwoDimBlockCyclic("B", n, n, ts, ts)
    C = TwoDimBlockCyclic("C", n, n, ts, ts)
    A.fill(lambda m, k: a[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    B.fill(lambda m, k: b[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    C.fill(lambda m, k: np.zeros((ts, ts), np.float32))

    # 1. whole DAG -> one GSPMD program over the mesh
    tp = DTDTaskpool(ctx, "mesh-gemm", capture=True)
    insert_gemm_tasks(tp, A, B, C, batch_k=True)
    tp.wait_mesh(mesh)
    tp.close()
    err = float(np.abs(C.to_dense() - a @ b).max())
    print(f"mesh-captured GEMM ({tp.inserted} tasks, one launch): "
          f"max err {err:.2e}")

    # 2. hand the result to the SPMD world and back
    g = to_global(C, mesh)
    sym = jax.jit(lambda x: 0.5 * (x + x.T),
                  in_shardings=g.sharding, out_shardings=g.sharding)
    from_global(C, sym(g))

    tp2 = DTDTaskpool(ctx, "post")
    for m in range(C.mt):
        tp2.insert_task(lambda x: x * 2.0, (tp2.tile_of(C, m, m), RW))
    tp2.wait()
    tp2.close()
    ctx.wait()
    ref = 0.5 * (a @ b + (a @ b).T)
    for m in range(C.mt):
        ref[m*ts:(m+1)*ts, m*ts:(m+1)*ts] *= 2.0
    err2 = float(np.abs(C.to_dense() - ref).max())
    print(f"SPMD handoff + second taskpool: max err {err2:.2e}")
    ctx.fini()
    assert err < 1e-3 and err2 < 1e-3


if __name__ == "__main__":
    main()
