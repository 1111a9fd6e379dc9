"""Ex03: the chain across ranks — remote deps carry the tile between ranks.

(Reference analogue: examples/Ex03_ChainMPI.c; ranks here are in-process,
the same CE vtable backs a multi-host transport on a pod.)
"""
from _common import setup

def main():
    setup()
    import numpy as np
    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.comm.threads import ThreadsCE, run_distributed
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.dsl.dtd import DTDTaskpool, RW, AFFINITY

    NB_RANKS, NT = 2, 16

    def program(rank, fabric):
        ctx = Context(nb_cores=1, my_rank=rank, nb_ranks=NB_RANKS)
        RemoteDepEngine(ctx, ThreadsCE(fabric, rank))
        A = TwoDimBlockCyclic("A", NT * 4, 4, 4, 4, P=NB_RANKS, Q=1,
                              nodes=NB_RANKS, myrank=rank)
        A.fill(lambda m, n: np.zeros((4, 4), np.float32))
        tp = DTDTaskpool(ctx, "chain")
        # each step owns a different tile -> the chain hops between ranks
        prev = None
        for k in range(NT):
            t = tp.tile_of(A, k, 0)
            if prev is None:
                tp.insert_task(lambda x: x + 1.0, (t, RW | AFFINITY))
            else:
                tp.insert_task(lambda x, p: p + 1.0, (t, RW | AFFINITY),
                               (prev, 0x1))  # READ previous tile
            prev = t
        tp.wait(); tp.close(); ctx.wait(); ctx.fini()
        if A.rank_of(NT - 1, 0) == rank:
            return float(np.asarray(A.data_of(NT - 1, 0).newest_copy().payload)[0, 0])
        return None

    results = run_distributed(NB_RANKS, program)
    print("ex03 distributed chain result (expect 16):",
          [r for r in results if r is not None][0])

if __name__ == "__main__":
    main()
