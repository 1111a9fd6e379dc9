"""Ex14: cross-host device-native payloads — run with

    python -m parsec_tpu.launch -n 2 --cpu --mca comm_device_mem 1 \\
        examples/ex14_device_mem_comms.py

With ``comm_device_mem`` on (the reference's
``parsec_mpi_allow_gpu_memory_communications`` gate,
parsec/parsec_internal.h:504), a device-resident array crossing OS ranks
never enters the host wire frame: the producer registers it with its
per-rank PJRT transfer server (comm/xhost.py) and ships only a rendezvous
descriptor; the consumer pulls the buffer over the transfer transport
straight into its own device memory, and the transport-level ACK retires
the producer's pin. Counters tell the story: ``comm.xhost_d2d_msgs`` moves,
``comm.host_materialized_msgs`` stays zero.

Each rank here computes a tile ON DEVICE, sends it to its neighbor, and
verifies what arrived is device-resident with zero host materializations.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from _common import setup  # noqa: E402


def main():
    setup()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parsec_tpu.comm.engine import TAG_DSL_BASE
    from parsec_tpu.comm.tcp import init_from_env
    from parsec_tpu.utils.counters import counters

    ce = init_from_env()
    got = []
    ce.tag_register(TAG_DSL_BASE, lambda _c, src, hdr, pl: got.append(pl))
    ce.sync()

    # a device-resident payload: computed by the chip, never fetched
    payload = jnp.linalg.cholesky(
        jnp.eye(64) * (4.0 + ce.my_rank)) * jnp.float32(ce.my_rank + 1)

    ce.send_am(TAG_DSL_BASE, (ce.my_rank + 1) % ce.nb_ranks,
               {"from": ce.my_rank}, payload)
    deadline = time.time() + 30
    while (not got or (ce._xhost is not None and ce._xhost.pending())) \
            and time.time() < deadline:
        ce.progress()
        time.sleep(0.001)

    peer = (ce.my_rank - 1) % ce.nb_ranks
    assert got, "no payload arrived"
    arrived = got[0]
    expect = float(np.sqrt(4.0 + peer) * (peer + 1))
    assert abs(float(np.asarray(arrived)[0, 0]) - expect) < 1e-5
    d2d = int(counters.read("comm.xhost_d2d_msgs"))
    bounced = int(counters.read("comm.host_materialized_msgs"))
    device_resident = isinstance(arrived, jax.Array)
    print(f"rank {ce.my_rank}: got peer {peer}'s tile "
          f"(device_resident={device_resident}, xhost_d2d={d2d}, "
          f"host_bounces={bounced})", flush=True)
    if os.environ.get("PARSEC_MCA_comm_device_mem") == "1":
        assert device_resident and d2d == 1 and bounced == 0
    ce.sync()
    ce.fini()


if __name__ == "__main__":
    main()
