"""Child script for the launcher --virtual-devices test: joins the TCP mesh,
reports which device the TPU module bound, and runs a tiny DTD GEMM through
it. Launched by tests/test_tcp_distributed.py via

    python -m parsec_tpu.launch -n 2 --virtual-devices 2 tests/_launch_device_probe.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    import numpy as np

    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.comm.tcp import init_from_env
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.device.tpu import TPUDevice
    from parsec_tpu.dsl.dtd import DTDTaskpool
    from parsec_tpu.ops.gemm import insert_gemm_tasks

    ce = init_from_env()
    ctx = Context(nb_cores=1, my_rank=ce.my_rank, nb_ranks=ce.nb_ranks)
    RemoteDepEngine(ctx, ce)
    tpus = [d for d in ctx.devices.devices if isinstance(d, TPUDevice)]

    n, ts = 32, 16
    rng = np.random.default_rng(2)
    a = rng.standard_normal((n, n)).astype(np.float32)
    kw = dict(nodes=ce.nb_ranks, myrank=ce.my_rank, P=ce.nb_ranks, Q=1)
    A = TwoDimBlockCyclic("A", n, n, ts, ts, **kw)
    B = TwoDimBlockCyclic("B", n, n, ts, ts, **kw)
    C = TwoDimBlockCyclic("C", n, n, ts, ts, **kw)
    A.fill(lambda m, k: a[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    B.fill(lambda m, k: np.eye(ts, dtype=np.float32) if m == k
           else np.zeros((ts, ts), np.float32))
    C.fill(lambda m, k: np.zeros((ts, ts), np.float32))
    tp = DTDTaskpool(ctx, "probe-gemm")
    insert_gemm_tasks(tp, A, B, C)
    tp.wait(timeout=60)
    tp.close()
    ctx.wait(timeout=60)
    ctx.fini()

    err = max((float(np.abs(np.asarray(C.data_of(m, k).newest_copy().payload)
                            - a[m*ts:(m+1)*ts, k*ts:(k+1)*ts]).max())
               for m in range(n//ts) for k in range(n//ts)
               if C.rank_of(m, k) == ce.my_rank), default=0.0)
    executed = sum(d.executed_tasks for d in tpus)

    # cross-host device-payload leg: a DEVICE-resident array crosses the OS
    # ranks through the PJRT transfer server (comm/xhost.py) — rendezvous
    # descriptor in the AM frame, buffer pulled device-to-device, pin
    # retired by the transport ACK
    import time

    import jax
    import jax.numpy as jnp

    from parsec_tpu.comm.engine import CAP_ACCELERATOR_MEM, TAG_DSL_BASE
    from parsec_tpu.comm.xhost import XHostTransfer
    from parsec_tpu.utils.counters import counters

    xgot = []
    ce.tag_register(TAG_DSL_BASE, lambda _c, src, hdr, pl: xgot.append(pl))
    ce.sync()
    ce._xhost = ce._xpull = XHostTransfer()
    ce.capabilities |= CAP_ACCELERATOR_MEM
    dev_payload = jnp.full((8, 8), float(ce.my_rank + 1))
    ce.send_am(TAG_DSL_BASE, (ce.my_rank + 1) % ce.nb_ranks, {}, dev_payload)
    t0 = time.time()
    while (not xgot or ce._xhost.pending()) and time.time() - t0 < 30:
        ce.progress()
        time.sleep(0.001)
    peer = (ce.my_rank - 1) % ce.nb_ranks
    assert xgot and isinstance(xgot[0], jax.Array), xgot
    assert float(np.asarray(xgot[0])[0, 0]) == float(peer + 1)
    assert ce._xhost.pending() == 0          # ACK retired the pin
    xd2d = int(counters.read("comm.xhost_d2d_msgs"))

    print(f"PROBE rank={ce.my_rank} devices={[d.jax_device.id for d in tpus]} "
          f"executed={executed} err={err:.2e} xhost_d2d={xd2d}", flush=True)
    ce.sync()
    ce.fini()
    assert err < 1e-3
    assert len(tpus) == 1 and executed > 0
    assert xd2d == 1


if __name__ == "__main__":
    main()
