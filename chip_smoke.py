#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the runtime still starts on the chip.

Drives the main path once through the entry points a user calls — Context,
DTD ``insert_task`` pools, a PTG ``BODY [type=TPU]`` pool, the Pallas tile
kernels and (on a four-chip host) the one-process-per-chip launcher — at
N = 16384, TS = 512, f32 at ``tile_dot_precision=highest``, and checks every
result on the device against raw XLA on the same operands.

The parent process never imports JAX: a process that has touched JAX holds
the chip, and a child that needs it would then fail or hang. Each phase is
one child that owns the chip while it runs; phases run one after another.

A phase fails — and the exit code is non-zero — if the platform is not
``tpu``, a native artifact did not build or load, a task of a
device-capable class ran on the CPU device, a lane fell back, or a result
is out of tolerance. ``--rehearsal`` (tiny size, CPU backend, Pallas in
interpret mode, ``"rehearsal": true`` in the output) is the only way it
runs without a chip; it exists to debug this script before spending chip
time. Speeds are not measured here: the wall seconds printed are set-up
and data checks included.

Last line of stdout, once a child has reported its device — these keys and
no others; sizes, versions, per-phase counts and seconds are on the
``SUMMARY`` line before it:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

SINGLE_CHIP_PHASES = ("kernels", "dtd-gemm", "dtd-potrf", "ptg-gemm")
ALL_PHASES = SINGLE_CHIP_PHASES + ("launch-4",)
#: child exit code for "JAX found no accelerator": the parent stops at once
RC_NO_ACCELERATOR = 3


# --------------------------------------------------------------------------
# child side: one phase, one process, one chip
# --------------------------------------------------------------------------

class PhaseFailure(Exception):
    pass


def require(cond, msg):
    if not cond:
        raise PhaseFailure(msg)


def _start_child(args, report):
    """Backend, compile accounting and the identity every phase reports."""
    import jax
    import jaxlib

    from parsec_tpu import native
    from parsec_tpu.utils import compile_cache, mca

    compile_cache.enable()
    report.update(compile_s=0.0, compiles=0, cache_hits=0)

    def on_duration(event, secs, **_kw):
        # the backend compile, or the cache read that stood in for it
        if event == "/jax/core/compile/backend_compile_duration":
            report["compile_s"] += secs
            report["compiles"] += 1

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            report["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    devs = jax.devices()
    platform = devs[0].platform
    if not args.rehearsal and platform != "tpu":
        print(f"chip_smoke: JAX found no accelerator (platform={platform!r})",
              file=sys.stderr)
        sys.exit(RC_NO_ACCELERATOR)
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    native.require_all()
    mca.set("tile_dot_precision", "highest")
    if args.rehearsal:
        # register the device module over a host device, so the rehearsal
        # walks the same device path (and the same placement checks)
        mca.set("device_tpu_over_cpu", True)
    report.update(platform=platform, device_kind=devs[0].device_kind,
                  device_count=len(devs), jax=jax.__version__,
                  jaxlib=jaxlib.__version__, libtpu=libtpu_version,
                  n=args.n, ts=args.ts)
    require(args.rehearsal or len(devs) == 1,
            f"a phase owns exactly one chip, jax.devices() has {len(devs)}")
    return jax


def misplaced(executed, ntasks):
    """Why ``executed`` ({device name: tasks run}) is not "all ``ntasks``
    tasks of the device-capable classes ran on the accelerator", or None."""
    on_tpu = sum(n for name, n in executed.items() if name.startswith("tpu"))
    if executed.get("cpu", 0) or on_tpu != ntasks:
        return (f"{on_tpu} of {ntasks} tasks ran on the accelerator, "
                f"{executed.get('cpu', 0)} on the CPU device: {executed}")
    return None


def _placement(ctx, ntasks, report):
    report["executed"] = {name: int(s["executed_tasks"]) for name, s
                          in ctx.devices.statistics().items()}
    problem = misplaced(report["executed"], ntasks)
    require(problem is None, problem)


def _device_tile(dc, m, n):
    import jax.numpy as jnp
    return jnp.asarray(dc.data_of(m, n).newest_copy().payload)


def _gemm_max_err(jax, C, ref, scale):
    """max |C - scale * ref| over the tiles of ``C``, one tile row per
    dispatch, everything on the device."""
    import jax.numpy as jnp

    @jax.jit
    def row_err(tiles, slab):
        return jnp.max(jnp.abs(jnp.concatenate(tiles, axis=1) - scale * slab))

    ts = C.mb
    errs = [row_err([_device_tile(C, m, n) for n in range(C.nt)],
                    ref[m * ts:(m + 1) * ts])
            for m in range(C.mt)]
    return float(jnp.max(jnp.stack(errs)))


def _gemm_operands(args):
    import numpy as np
    rng = np.random.default_rng(args.seed)
    return (rng.standard_normal((args.n, args.n), dtype=np.float32),
            rng.standard_normal((args.n, args.n), dtype=np.float32))


def _gemm_collections(args, a, b, cls):
    import numpy as np
    n, ts = args.n, args.ts
    A, B, C = (cls(name, n, n, ts, ts) for name in ("A", "B", "C"))
    A.fill(lambda m, k: a[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    B.fill(lambda k, j: b[k*ts:(k+1)*ts, j*ts:(j+1)*ts])
    C.fill(lambda m, j: np.zeros((ts, ts), np.float32))
    return A, B, C


def _gemm_tolerance(args):
    # f32 sums of K products of unit normals, taken in two different
    # orders: sqrt(K) * 1e-4 is ~800 ulp of the result's magnitude, and far
    # below the sqrt(TS) a single missing tile product would cost
    return 1e-4 * args.n ** 0.5


def phase_kernels(args, jax, report):
    from parsec_tpu.ops import pallas_kernels as pk
    require(args.rehearsal or not pk._interpret(),
            "Pallas is in interpret mode on an accelerator backend")
    ts = args.ts
    shapes = ((128, 128, 128),) if args.rehearsal else \
        ((256, 256, 256), (ts, ts, ts))
    results = pk.verify_lowering(shapes=shapes, kt=args.n // ts,
                                 dtypes=("float32", "bfloat16"))
    report["kernels"] = sorted(results)
    report["tasks"] = 0


def phase_dtd_gemm(args, jax, report):
    import jax.numpy as jnp

    import parsec_tpu as pt
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.dsl.dtd import DTDTaskpool
    from parsec_tpu.ops import pallas_kernels as pk
    from parsec_tpu.ops.gemm import insert_gemm_tasks

    kt = args.n // args.ts
    require(kt > 16, f"kt={kt}: the Pallas gemm_chain is on the path only "
                     f"for k-chains longer than 16")
    a, b = _gemm_operands(args)
    ctx = pt.Context(nb_cores=1)
    A, B, C = _gemm_collections(args, a, b, TwoDimBlockCyclic)
    chains0 = pk._gemm_chain_call.cache_info().misses

    tp = DTDTaskpool(ctx, "smoke-gemm-k")
    n_k = insert_gemm_tasks(tp, A, B, C, batch_k=True)
    require(tp.wait(timeout=args.timeout), "batch_k pool did not drain")
    tp.close()
    ctx.wait(timeout=args.timeout)
    require(pk._gemm_chain_call.cache_info().misses > chains0,
            "the Pallas gemm_chain kernel was never built")
    _placement(ctx, n_k, report)

    # the reference: raw XLA on the same operands, on the same device
    ref = jnp.dot(jnp.asarray(a), jnp.asarray(b),
                  precision=jax.lax.Precision.HIGHEST)
    err_k = _gemm_max_err(jax, C, ref, 1.0)

    tp = DTDTaskpool(ctx, "smoke-gemm")
    n_t = insert_gemm_tasks(tp, A, B, C, batch_k=False)
    require(tp.wait(timeout=args.timeout), "per-tile pool did not drain")
    tp.close()
    ctx.wait(timeout=args.timeout)
    _placement(ctx, n_k + n_t, report)
    err_t = _gemm_max_err(jax, C, ref, 2.0)     # C accumulated both runs
    ctx.fini()

    tol = _gemm_tolerance(args)
    report.update(tasks=n_k + n_t, tasks_batch_k=n_k, tasks_per_tile=n_t,
                  max_abs_err_batch_k=err_k, max_abs_err_per_tile=err_t,
                  tolerance=tol)
    require(n_k == kt * kt and n_t == kt ** 3, f"task counts {n_k}, {n_t}")
    require(err_k < tol and err_t < tol,
            f"GEMM out of tolerance: {err_k}, {err_t} vs {tol}")


def phase_dtd_potrf(args, jax, report):
    import jax.numpy as jnp
    import numpy as np

    import parsec_tpu as pt
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.dsl.dtd import DTDTaskpool
    from parsec_tpu.ops.potrf import insert_potrf_tasks, spd_tile

    n, ts = args.n, args.ts
    T = n // ts
    spd = np.block([[spd_tile(n, ts, m, k, args.seed) for k in range(T)]
                    for m in range(T)])
    ctx = pt.Context(nb_cores=1)
    A = TwoDimBlockCyclic("A", n, n, ts, ts)
    A.fill(lambda m, k: spd[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    tp = DTDTaskpool(ctx, "smoke-potrf")
    ntasks = insert_potrf_tasks(tp, A)
    require(tp.wait(timeout=args.timeout), "POTRF pool did not drain")
    tp.close()
    ctx.wait(timeout=args.timeout)
    _placement(ctx, ntasks, report)

    # ||L L^T - A||_F / ||A||_F on the device, L from the lower tiles
    rows = [jnp.concatenate(
        [_device_tile(A, m, k) for k in range(m + 1)] +
        [jnp.zeros((ts, (T - 1 - m) * ts), jnp.float32)] * (m < T - 1),
        axis=1) for m in range(T)]
    L = jnp.concatenate(rows, axis=0)
    A_dev = jnp.asarray(spd)
    resid = jnp.dot(L, L.T, precision=jax.lax.Precision.HIGHEST) - A_dev
    rel = float(jnp.linalg.norm(resid) / jnp.linalg.norm(A_dev))
    ctx.fini()

    report.update(tasks=ntasks, rel_residual=rel, tolerance=1e-4)
    require(ntasks == T * (T + 1) * (T + 2) // 6, f"task count {ntasks}")
    # backward error of an f32 Cholesky grows like sqrt(N) * eps (2e-5 at
    # N = 16384); one wrong tile update would cost ~5e-4
    require(rel < 1e-4, f"POTRF residual {rel} out of tolerance")


def phase_ptg_gemm(args, jax, report):
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(REPO, "examples"))
    import ex06_gemm_ptg

    import parsec_tpu as pt
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.device.native import PTDEV_STATS
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS, compile_ptg
    from parsec_tpu.utils.counters import counters, install_native_counters

    install_native_counters()       # ptdev.cb_errors reads the lane's count
    nt = args.n // args.ts
    a, b = _gemm_operands(args)
    ctx = pt.Context(nb_cores=1)
    A, B, C = _gemm_collections(args, a, b, TiledMatrix)
    snap_x, snap_d = PTEXEC_STATS.snapshot(), PTDEV_STATS.snapshot()
    tp = compile_ptg(ex06_gemm_ptg.SRC, "gemm").instantiate(
        ctx, globals={"MT": nt, "NT": nt, "KT": nt},
        collections={"descA": A, "descB": B, "descC": C})
    ctx.add_taskpool(tp)
    ctx.wait(timeout=args.timeout)
    require(tp.completed, "PTG pool did not complete")
    dx, dd = PTEXEC_STATS.delta(snap_x), PTDEV_STATS.delta(snap_d)
    cb_errors = int(counters.read("ptdev.cb_errors"))
    report.update(tasks=nt ** 3, ptexec=dx, ptdev=dd,
                  ptdev_cb_errors=cb_errors)
    require(dx["pools_engaged"] >= 1 and dx["pools_fallback"] == 0,
            f"ptexec lane did not carry the pool: {dx}")
    require(dd["pools_engaged"] >= 1 and dd["pools_fallback"] == 0,
            f"ptdev lane did not carry the pool: {dd}")
    require(dd["tasks_engaged"] == nt ** 3,
            f"ptdev carried {dd['tasks_engaged']} of {nt ** 3} tasks")
    require(cb_errors == 0, f"ptdev.cb_errors = {cb_errors}")
    # one executable per SHAPE of fused region: the nt * nt k-chains are one
    report["region_programs"] = dx["region_programs"]
    require(dx["region_programs"] <= 8,
            f"{dx['region_programs']} region programs for {nt * nt} "
            f"structurally equal regions")
    _placement(ctx, nt ** 3, report)

    ref = jnp.dot(jnp.asarray(a), jnp.asarray(b),
                  precision=jax.lax.Precision.HIGHEST)
    err = _gemm_max_err(jax, C, ref, 1.0)
    ctx.fini()
    tol = _gemm_tolerance(args)
    report.update(max_abs_err=err, tolerance=tol)
    require(err < tol, f"PTG GEMM out of tolerance: {err} vs {tol}")


PHASE_FNS = {"kernels": phase_kernels, "dtd-gemm": phase_dtd_gemm,
             "dtd-potrf": phase_dtd_potrf, "ptg-gemm": phase_ptg_gemm}


def run_phase(args) -> int:
    t0 = time.perf_counter()
    report = {"phase": args.phase}
    try:
        jax = _start_child(args, report)
        PHASE_FNS[args.phase](args, jax, report)
        report["ok"] = True
    except (PhaseFailure, TimeoutError) as e:
        report.update(ok=False, error=f"{type(e).__name__}: {e}")
    report["compile_s"] = round(report["compile_s"], 2)
    report["wall_s"] = round(time.perf_counter() - t0, 2)
    print("PHASE " + json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


# --------------------------------------------------------------------------
# parent side: never imports JAX
# --------------------------------------------------------------------------

def _run(cmd, env, timeout):
    """Run a child in its own process group; on timeout or interrupt the
    whole group is killed, so nothing this script starts outlives it."""
    p = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                         text=True, start_new_session=True)
    try:
        out, _ = p.communicate(timeout=timeout)
        return p.returncode, out
    except subprocess.TimeoutExpired:
        return 124, ""
    finally:
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            p.wait()


def _tagged(out, tag):
    return [json.loads(line[len(tag):]) for line in out.splitlines()
            if line.startswith(tag)]


def _size(args, phase):
    """(N, TS) of a phase: what the command line says, else the smoke's
    size — or, rehearsing, tiny tiles on the real 32x32 tile grid, so that
    what depends on the grid is rehearsed too (the fused k-chain task's
    1 + 2*32 flows overran a limit no smaller grid could show)."""
    default = (256, 8) if args.rehearsal else (16384, 512)
    return args.n or default[0], args.ts or default[1]


def single_chip_phase(phase, args, env):
    n, ts = _size(args, phase)
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--seed", str(args.seed), "--n", str(n), "--ts", str(ts),
           "--timeout", str(args.timeout)]
    rc, out = _run(cmd + ["--rehearsal"] * args.rehearsal, env,
                   args.timeout + 120)
    recs = _tagged(out, "PHASE ")
    rec = recs[-1] if recs else {"phase": phase, "ok": False,
                                 "error": f"child exited {rc}, no report"}
    if rc != 0:
        rec["ok"] = False
        rec.setdefault("error", f"child exited {rc}")
    rec["rc"] = rc
    return rec


def launch_phase(args, env, nranks=4):
    """One process per chip: the distributed DTD POTRF of
    examples/ex09_tcp_launch.py on a 2x2 block-cyclic grid, rank i bound to
    chip i by the launcher."""
    t0 = time.perf_counter()
    n, ts = _size(args, "launch-4")
    how = ["--cpu", "--mca", "device_tpu_over_cpu", "1"] if args.rehearsal \
        else ["--bind-devices"]
    # one deadline for the whole job: start-up, the DAG, and each rank's
    # reference factorization (XLA compiles a 16384 Cholesky for ~80 s)
    budget = 2 * args.timeout
    rc, out = _run([sys.executable, "-m", "parsec_tpu.launch", "-n",
                    str(nranks), *how, "--timeout", str(budget),
                    os.path.join("examples", "ex09_tcp_launch.py"),
                    "--n", str(n), "--ts", str(ts), "--grid", "2x2"],
                   env, budget + 120)
    ranks = sorted(_tagged(out, "EX09 "), key=lambda r: r["rank"])
    rec = {"phase": "launch-4", "rc": rc, "ranks": ranks,
           "tasks": sum(r["tasks_local"] for r in ranks),
           "wall_s": round(time.perf_counter() - t0, 2)}
    problems = []
    if rc != 0:
        problems.append(f"launcher exited {rc}")
    if len(ranks) != nranks:
        problems.append(f"{len(ranks)} of {nranks} ranks reported")
    for r in ranks:
        if not r["ok"]:
            problems.append(f"rank {r['rank']}: {r.get('error')}")
        if not args.rehearsal and (r["platform"] != "tpu"
                                   or r["device_count"] != 1):
            problems.append(f"rank {r['rank']} not on exactly one chip: "
                            f"{r['platform']} x{r['device_count']}")
        problem = misplaced(r["executed"], r["tasks_local"])
        if problem:
            problems.append(f"rank {r['rank']}: {problem}")
    chips = [r["chip"] for r in ranks]
    if not args.rehearsal and len(set(chips)) != nranks:
        problems.append(f"bound chips not distinct: {chips}")
    rec["ok"] = not problems
    if problems:
        rec["error"] = "; ".join(problems)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=None,
                    help="matrix order (default 16384; tiny in rehearsal)")
    ap.add_argument("--ts", type=int, default=None,
                    help="tile size (default 512; 8 in rehearsal)")
    ap.add_argument("--timeout", type=float, default=420.0,
                    help="seconds allowed to each wait inside a phase")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny size on the CPU backend, Pallas interpreted: "
                         "debugs this script, proves nothing about the chip")
    ap.add_argument("--phases", default=",".join(ALL_PHASES),
                    help="comma-separated subset to run, to debug one phase "
                         "without paying chip time for the rest (launch-4 "
                         "needs four chips)")
    ap.add_argument("--phase", choices=sorted(PHASE_FNS),
                    help=argparse.SUPPRESS)     # child mode
    args = ap.parse_args()
    if args.phase:
        return run_phase(args)
    wanted = args.phases.split(",")
    unknown = sorted(set(wanted) - set(ALL_PHASES))
    if unknown:
        ap.error(f"unknown phases {unknown}; choose from {ALL_PHASES}")

    try:
        from parsec_tpu import native
        from parsec_tpu.launch import chip_env, local_chip_count
    except ImportError as e:
        print(f"chip_smoke: parsec_tpu is not beside this script: {e}",
              file=sys.stderr)
        return 1
    native.require_all()        # built from what this tree holds, or raise

    env = dict(os.environ)
    chips = local_chip_count()
    if args.rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    # a single-chip phase sees exactly one chip; the launcher binds its own
    phase_env = {**env, **chip_env(0)} if chips > 1 and not args.rehearsal \
        else env
    n, ts = _size(args, "dtd-gemm")
    print(f"chip_smoke: N={n} TS={ts} seed={args.seed} "
          f"rehearsal={args.rehearsal} chips={chips}", flush=True)

    records = []

    def record(rec):
        records.append(rec)
        print(f"[{rec['phase']}] {'PASS' if rec['ok'] else 'FAIL'} "
              + json.dumps(rec, sort_keys=True), flush=True)

    for phase in SINGLE_CHIP_PHASES:
        if phase in wanted:
            rec = single_chip_phase(phase, args, phase_env)
            if rec["rc"] == RC_NO_ACCELERATOR:
                return 1        # the child said why on stderr; no result
            record(rec)
    if "launch-4" in wanted and (chips >= 4 or args.rehearsal):
        record(launch_phase(args, env))

    if not records:
        print(f"chip_smoke: none of {wanted} can run on {chips} chip(s)",
              file=sys.stderr)
        return 1
    failed = [r["phase"] for r in records if not r["ok"]]
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
    # the device as the children's JAX reported it (a launch-4 rank's, when
    # that is the only phase run); a child that died before reporting has none
    heads = [h for r in records for h in (r.get("ranks") or [r])
             if "platform" in h]
    if not heads:
        return 1
    head = heads[0]
    summary = {
        "n": n, "ts": ts, "seed": args.seed, "rehearsal": args.rehearsal,
        "versions": {k: head.get(k) for k in ("jax", "jaxlib", "libtpu")},
        "phases": {r["phase"]: {k: r[k] for k in
                                ("ok", "tasks", "compile_s", "wall_s")
                                if k in r}
                   for r in records},
        "compile_s": round(sum(r.get("compile_s", 0.0) for r in records), 2),
    }
    print("SUMMARY " + json.dumps(summary), flush=True)
    # the last line: these keys and no others
    print(json.dumps({"ok": not failed,
                      "device": {"platform": str(head["platform"]),
                                 "kind": str(head["device_kind"]),
                                 "count": int(head["device_count"])}}),
          flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
