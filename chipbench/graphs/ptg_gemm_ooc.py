"""Graph driver: the PTG tiled GEMM of ``graphs/ptg_gemm.py`` at a size the
chip cannot hold (``BASELINE.json`` config 2's JDF, ``examples/ex06_gemm_ptg.py``
``GEMM(m, n, k)``, over upstream's device layer: an LRU tile heap, write-back
of dirty tiles, a batch that waits for room).

The JDF, the packing rule, ``ptexec`` and the ``ptdev`` lane are the in-core
cell's; the operands, the accumulation and the reference are
``graphs/gemm_ooc.py``'s. The residency layer evicts and writes back as it
sees fit: this driver moves no tile, fetches nothing inside the timer, and
takes a tile's newest copy where it is. C accumulates over solves and is
never restored.

The JDF's dot runs at the configuration's precision (``HIGHEST``, as the JDF
is written), so a scratch copy of the configuration one precision down
measures the program one precision down: the tolerance's second reading.

A solve waits in short slices and looks at the lane and the residency table
between them: it fails as soon as a callback of the lane has raised or the
tracked resident bytes have passed the budget, so a program that cannot hold
the configuration fails its first solve within seconds.
"""

import os
import resource
import sys
import time
import types

import numpy as np

from chipbench.graphs.gemm_ooc import _tpu, settle
from chipbench.graphs.ptg_gemm import (KERNEL_MODULES,  # noqa: F401
                                       _lane_stats, dot_bytes, dot_flops,
                                       flops, tasks)
from chipbench.reference import ptg_gemm_ooc as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: seconds a solve waits at a time before it looks at the lane and the table
SLICE = 1.0


def build(run):
    """Context, collections and host tiles from the seed, and the JDF
    compiled once. The budget is the program's default; only the
    rehearsal's traffic carries a ``budget_bytes``, so that the CPU walk
    holds programs back and evicts too."""
    import parsec_tpu as pt
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.dsl.ptg import compiler
    from parsec_tpu.utils import mca
    from parsec_tpu.utils.counters import install_native_counters

    install_native_counters()   # the ptdev lane's C-side counts, by name
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import ex06_gemm_ptg

    st = types.SimpleNamespace()
    n, ts = run.traffic["n"], run.traffic["ts"]
    st.nt = nt = n // ts
    budget = run.traffic.get("budget_bytes")
    if budget:
        mca.set("device_tpu_max_bytes", int(budget))
    try:
        st.ctx = pt.Context(nb_cores=1)
    finally:
        if budget:
            mca.params.unset("device_tpu_max_bytes")
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
    src = ex06_gemm_ptg.SRC.replace(
        "lax.Precision.HIGHEST",
        "lax.Precision." + run.config["precision"].upper())
    st.program = compiler.compile_ptg(src, "gemm")
    st.solves = 0
    # a C tile's version counts the writes to it: what filling left, plus
    # one write-back of its k-chain in each solve (settle's ``kt``)
    st.kt, st.version0 = 1, st.C.data_of(0, 0).version
    return st


def restore(st, run):
    """Nothing: C accumulates, and every C tile that leaves is dirty."""


def _broken_now(st, before):
    """What has already broken inside a solve that is still running."""
    from parsec_tpu.utils.counters import counters

    errors = int(counters.read("ptdev.cb_errors")) - before["ptdev.cb_errors"]
    if errors:
        return f"{errors} callbacks of the ptdev lane raised"
    coh = _tpu(st).coh_stats()
    if coh is not None and coh["hwm_bytes"] > coh["budget"]:
        return (f"tracked resident bytes reached {coh['hwm_bytes']}, the "
                f"budget is {coh['budget']}")
    return None


def solve(st, run):
    before = _lane_stats()
    with run.span("insert"):
        tp = st.program.instantiate(
            st.ctx, globals={"MT": st.nt, "NT": st.nt, "KT": st.nt},
            collections={"descA": st.A, "descB": st.B, "descC": st.C})
        st.ctx.add_taskpool(tp)
    with run.span("wait"):
        # a wait that runs out returns: the pool may still be running
        deadline = time.perf_counter() + run.timeout
        while True:
            st.ctx.wait(timeout=SLICE)
            broken = _broken_now(st, before)
            if broken:
                raise RuntimeError(f"PTG pool: {broken}")
            if tp.completed:
                break
            if time.perf_counter() > deadline:
                raise TimeoutError(f"PTG pool: not done in {run.timeout} s")
        on_device, on_host = st.settled = settle(st, run, st.solves + 1)
    after = _lane_stats()
    d = {k: after[k] - before[k] for k in after}
    n = run.tasks_per_solve
    if not tp.completed or any(d[k] for k in (
            "ptexec.pools_fallback", "ptexec.pools_ineligible",
            "ptdev.pools_fallback", "ptdev.pools_ineligible",
            "ptdev.cb_errors")) or d["ptexec.tasks_engaged"] != n \
            or d["ptdev.tasks_engaged"] != n:
        raise RuntimeError(f"PTG pool: completed={tp.completed}, a lane "
                           f"declined or lost tasks of {n}: {d}")
    st.solves += 1
    with open("/proc/self/statm") as f:
        rss = int(f.read().split()[1]) * resource.getpagesize()
    print(f"ptg_gemm_ooc: solve {st.solves}: C tiles {on_device} on the "
          f"device, {on_host} written back; host RSS {rss / 2 ** 30:.2f} GiB",
          file=sys.stderr, flush=True)
    return {"local_tasks": n, "window_stalls": 0,
            "c_on_device": on_device, "c_on_host": on_host}


def counters(st, run):
    """``run.device_counters``, the lanes' counts, what the residency layer
    did under pressure, and the programs that waited for room."""
    from parsec_tpu.device.native import PTDEV_STATS

    dev = _tpu(st)
    out = {**run.device_counters(st.ctx), **_lane_stats()}
    out["transfer_out_bytes"] = int(dev.transfer_out_bytes)
    out["pinned_skips"] = int(dev.pinned_skips)
    out["owned_evictions"] = int(dev.owned_evictions)
    if "held_back" in PTDEV_STATS:
        out["ptdev.held_back"] = int(PTDEV_STATS["held_back"])
    coh = dev.coh_stats()
    if coh is not None:
        out["coh_stage_out_bytes"] = int(coh["stage_out_bytes"])
        out["coh_evictions"] = int(coh["evictions"])
    return out


def guarantees(st, run):
    """What the configuration promises beyond the numbers, over the whole
    process: a line for each promise that broke, none when all held."""
    dev = _tpu(st)
    tile = st.C.mb * st.C.nb * 4
    broken = []
    if run.failed:
        broken.append(f"{run.failed} failed solves")
    owned = getattr(dev, "owned_evictions", 0)
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
    n = run.traffic["n"]
    dev = _tpu(st)
    rows = ref.sample_rows(st.nt, run.seed)
    where = {"device": 0, "host": 0}

    def c_tile(m, j):
        copy = st.C.data_of(m, j).newest_copy()
        where["host" if copy.device_index == 0 else "device"] += 1
        return copy.payload

    err, err_high = ref.max_abs_err(c_tile, st.a_host, st.b_host, st.nt,
                                    rows, float(st.solves))
    tol = ref.tolerance(n, st.solves, run.config["tolerance"]["value"])
    broken = guarantees(st, run)
    coh = dev.coh_stats() or {}
    detail = {"max_abs_err": err, "tolerance": tol,
              "max_abs_err_reference_high": err_high,
              "solves_accumulated": st.solves,
              "tiles_checked": len(rows) * st.nt, "checked_from": where,
              "budget_bytes": int(dev._budget),
              "resident_hwm_bytes": coh.get("hwm_bytes"),
              "resident_bytes": int(dev._resident_bytes),
              "c_on_device_c_on_host_last_solve": list(st.settled),
              "host_rss_peak_bytes": resource.getrusage(
                  resource.RUSAGE_SELF).ru_maxrss * 1024,
              "guarantees_broken": broken}
    return bool(err < tol and not broken), detail


def close(st, run):
    st.ctx.fini()
