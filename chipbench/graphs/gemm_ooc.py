"""Graph driver: the tiled GEMM of ``graphs/gemm.py`` at a size the chip
cannot hold (the upstream harness ``tests/dsl/dtd/dtd_test_simple_gemm.c``
over upstream's device layer: an LRU tile heap, write-back of dirty tiles,
out-of-memory -> evict -> retry).

The same graph, the same inserts, the same kernels as the in-core driver;
what differs is where a C tile is when a solve ends. The residency layer
evicts and writes back as it sees fit: this driver moves no tile, fetches
nothing inside the timer, and takes a tile's newest copy where it is. C
accumulates over solves and is never flushed home.
"""

import resource
import sys
import types

import numpy as np

from chipbench.graphs.gemm import (KERNEL_MODULES, close,  # noqa: F401
                                   dot_flops, flops, restore, tasks)
from chipbench.reference import gemm_ooc as ref


#: what a program that cannot be held to the configuration is told
UNSUPPORTED = ("chipbench: this program does not count the evictions of "
               "copies the device owned (TPUDevice.owned_evictions), so a "
               "run of dtd_gemm_f32_ooc cannot be held to \"an evicted dirty "
               "tile is never dropped without its write-back\": the "
               "configuration is not supported here")


def _tpu(st):
    from parsec_tpu.device.tpu import TPUDevice
    return next(d for d in st.ctx.devices.devices if isinstance(d, TPUDevice))


def build(run):
    """``graphs/gemm.py``'s build behind one question to the program: the
    context, then the question, then the collections and the host tiles from
    the seed. The budget is the program's default; only the rehearsal's
    traffic carries a ``budget_bytes``, so that the CPU walk evicts too.

    The configuration promises that no dirty tile leaves the device without
    its write-back, and the check can hold a run to that only by the
    program's own count of the evictions that owed one
    (``TPUDevice.owned_evictions``). A program that keeps no such count
    cannot be held to the configuration: the run ends here, before a tile
    is made, with the reason on stderr and exit code 1."""
    import parsec_tpu as pt
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.utils import mca

    st = types.SimpleNamespace()
    n, ts = run.traffic["n"], run.traffic["ts"]
    nt = n // ts
    budget = run.traffic.get("budget_bytes")
    if budget:
        mca.set("device_tpu_max_bytes", int(budget))
    try:
        st.ctx = pt.Context(nb_cores=1)
    finally:
        if budget:
            mca.params.unset("device_tpu_max_bytes")
    if not hasattr(_tpu(st), "owned_evictions"):
        st.ctx.fini()
        sys.exit(UNSUPPORTED)
    st.A, st.B, st.C = (TwoDimBlockCyclic(name, n, n, ts, ts)
                        for name in ("A", "B", "C"))
    grid = [(m, k) for m in range(nt) for k in range(nt)]
    st.a_host = run.make_tiles(
        grid, lambda mk: ref.operand_tile(0, ts, mk[0], mk[1], run.seed))
    st.b_host = run.make_tiles(
        grid, lambda mk: ref.operand_tile(1, ts, mk[0], mk[1], run.seed))
    st.A.fill(lambda m, k: st.a_host[m, k])
    st.B.fill(lambda k, j: st.b_host[k, j])
    st.C.fill(lambda m, j: np.zeros((ts, ts), np.float32))
    st.solves = 0
    # a C tile's version counts the writes to it: what filling left, plus
    # one for each task of its k-chain in each solve
    st.kt, st.version0 = st.A.nt, st.C.data_of(0, 0).version
    return st


def newest_valid(data):
    """The copies of ``data`` that hold its newest version with a payload."""
    from parsec_tpu.data.data import COHERENCY_INVALID
    return [c for c in list(data.copies.values())
            if c.coherency_state != COHERENCY_INVALID
            and c.version == data.version and c.payload is not None]


def settle(st, run, solves):
    """Wait for every C tile where it is: a device copy through
    ``run.block``, a written-back one by seeing that it is a finished numpy
    array. Each tile must have exactly one newest valid copy, at the version
    the last write of ``solves`` k-chains gave it. Returns (on the device,
    on the host)."""
    want = st.version0 + st.kt * solves
    on_device = on_host = 0
    for m in range(st.C.mt):
        for j in range(st.C.nt):
            data = st.C.data_of(m, j)
            held = newest_valid(data)
            if len(held) != 1 or data.version != want:
                raise RuntimeError(
                    f"C({m},{j}) has {len(held)} newest valid copies at "
                    f"version {data.version}, wanted one at {want}: "
                    f"{sorted(data.copies.items())}")
            copy = held[0]
            if copy.device_index == 0:
                if not isinstance(copy.payload, np.ndarray):
                    raise RuntimeError(
                        f"C({m},{j})'s written-back copy is a "
                        f"{type(copy.payload).__name__}, not a numpy array")
                on_host += 1
            else:
                run.block(copy.payload)
                on_device += 1
    return on_device, on_host


def solve(st, run):
    from parsec_tpu.dsl.dtd import DTDTaskpool
    from parsec_tpu.ops.gemm import insert_gemm_tasks

    tp = DTDTaskpool(st.ctx, "chipbench-gemm-ooc")
    with run.span("insert"):
        inserted = insert_gemm_tasks(tp, st.A, st.B, st.C)
    with run.span("wait"):
        drained = tp.wait(timeout=run.timeout)
        tp.close()
        st.ctx.wait(timeout=run.timeout)
        if not drained or inserted != run.tasks_per_solve:
            raise RuntimeError(f"GEMM pool: drained={drained}, inserted "
                               f"{inserted} of {run.tasks_per_solve} tasks")
        on_device, on_host = st.settled = settle(st, run, st.solves + 1)
    st.solves += 1
    with open("/proc/self/statm") as f:
        rss = int(f.read().split()[1]) * resource.getpagesize()
    print(f"gemm_ooc: solve {st.solves}: C tiles {on_device} on the device, "
          f"{on_host} written back; host RSS {rss / 2 ** 30:.2f} GiB",
          file=sys.stderr, flush=True)
    return {"local_tasks": tp.local_inserted,
            "window_stalls": tp.window_stalls,
            "c_on_device": on_device, "c_on_host": on_host,
            "native_engine": getattr(tp, "_neng", None) is not None}


def counters(st, run):
    """``run.device_counters`` and what the residency layer did under
    pressure."""
    dev = _tpu(st)
    out = run.device_counters(st.ctx)
    out["transfer_out_bytes"] = int(dev.transfer_out_bytes)
    out["batched_tasks"] = int(dev.batched_tasks)
    out["pinned_skips"] = int(dev.pinned_skips)
    out["owned_evictions"] = int(dev.owned_evictions)
    coh = dev.coh_stats()
    if coh is not None:
        out["coh_stage_out_bytes"] = int(coh["stage_out_bytes"])
        out["coh_evictions"] = int(coh["evictions"])
        out["coh_pinned_skips"] = int(coh["pinned_skips"])
    return out


def guarantees(st, run):
    """What the configuration promises beyond the numbers, over the whole
    process: a line for each promise that broke, none when all held."""
    dev = _tpu(st)
    tile = st.C.mb * st.C.nb * 4
    broken = []
    if run.failed:
        broken.append(f"{run.failed} failed solves")
    owned = dev.owned_evictions
    if dev.transfer_out_bytes < tile * owned:
        broken.append(f"{owned} evictions of a copy the device owned, "
                      f"{dev.transfer_out_bytes} bytes written back: a dirty "
                      f"tile left without its write-back")
    coh = dev.coh_stats()
    if coh is not None and coh["hwm_bytes"] > coh["budget"]:
        broken.append(f"tracked resident bytes reached {coh['hwm_bytes']}, "
                      f"the budget is {coh['budget']}")
    try:
        settle(st, run, st.solves)
    except RuntimeError as e:
        broken.append(str(e))
    return broken


def check(st, run):
    n, ts = run.traffic["n"], run.traffic["ts"]
    nt = n // ts
    dev = _tpu(st)
    rows = ref.sample_rows(nt, run.seed)
    where = {"device": 0, "host": 0}

    def c_tile(m, j):
        copy = st.C.data_of(m, j).newest_copy()
        where["host" if copy.device_index == 0 else "device"] += 1
        return copy.payload

    err, err_high = ref.max_abs_err(c_tile, st.a_host, st.b_host, nt, rows,
                                    float(st.solves))
    tol = ref.tolerance(n, st.solves, run.config["tolerance"]["value"])
    broken = guarantees(st, run)
    coh = dev.coh_stats() or {}
    detail = {"max_abs_err": err, "tolerance": tol,
              "max_abs_err_reference_high": err_high,
              "solves_accumulated": st.solves,
              "tiles_checked": len(rows) * nt, "checked_from": where,
              "budget_bytes": int(dev._budget),
              "resident_hwm_bytes": coh.get("hwm_bytes"),
              "resident_bytes": int(dev._resident_bytes),
              "c_on_device_c_on_host_last_solve": list(st.settled),
              "host_rss_peak_bytes": resource.getrusage(
                  resource.RUSAGE_SELF).ru_maxrss * 1024,
              "guarantees_broken": broken}
    return bool(err < tol and not broken), detail
