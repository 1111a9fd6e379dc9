#!/usr/bin/env python3
"""chipbench/run.py — one run of one cell of BENCHMARK.json on the chip.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (process start, context, operands from ``--seed``, one untimed
warm-up solve), then a closed loop of one client for ``--seconds`` seconds,
then the check of the last solve's result on the device. The last line of
stdout is one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics`` and ``device`` (and ``breakdown`` with ``--trace 1``); with
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. Everything else — solve seconds,
compile counts, per-rank values — is on earlier lines.

The run fails, and prints no result, without a TPU, on a ``device_kind``
that ``peaks.json`` does not hold, when a native artifact did not load,
when a task ran on the CPU device, when an output tile's newest copy is on
the host or when something compiled inside the window (``chip_smoke.py``
holds the DTD paths to no lane assert beyond the native artifacts; whether
the native DTD engine carried the pools is on the ``RUN`` line).
``--rehearsal`` (tiny sizes, CPU backend) exists only to debug
this harness: it prints counts and ``correct``, never a time under a
metric's name. ``chipbench/README.md`` says how a cell's files are found.
"""

import time

T_START = time.perf_counter()           # process start, as near as Python sees
WALL_START = time.time()

import argparse                                             # noqa: E402
import concurrent.futures                                   # noqa: E402
import contextlib                                           # noqa: E402
import importlib                                            # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import shutil                                               # noqa: E402
import signal                                               # noqa: E402
import statistics                                           # noqa: E402
import subprocess                                           # noqa: E402
import sys                                                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

#: exit code for "no accelerator, or fewer chips than the cell asks for"
RC_NO_ACCELERATOR = 3
#: where ranks and the checker child meet (inside the checkout, git-ignored)
SCRATCH = os.path.join(ROOT, ".cache", "chipbench")


class RunFailure(Exception):
    """The run cannot report a result (not: a solve failed)."""


def log(tag, obj):
    print(f"{tag} {json.dumps(obj, sort_keys=True)}", flush=True)


# --------------------------------------------------------------------------
# finding a cell's files by the names BENCHMARK.json gives
# --------------------------------------------------------------------------

def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything its names lead to."""

    def __init__(self, name, rehearsal):
        bench = load_json("BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise RunFailure(f"no cell {name!r} in BENCHMARK.json; "
                             f"it has {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = self.entry["chips"]
        entry = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = load_json(entry["file"])
        self.traffic = load_json("chipbench", "traffic",
                                 self.entry["traffic"] + ".json")
        if rehearsal:
            self.traffic = {**self.traffic, **self.traffic["rehearsal"]}
        self.graph = importlib.import_module(
            "chipbench.graphs." + self.config["graph"])
        self.peaks = load_json("chipbench", "peaks.json")

        def here(metric):
            return name in metric.get("workloads", [name])
        self.end_to_end = [m for m in bench["end_to_end"] if here(m)]
        reported = {m["name"] for m in self.end_to_end}
        # a per-layer metric is reported only where the metric it moves is
        self.per_layer = [m for m in bench["per_layer"]
                          if here(m) and m["moves"] in reported]

    def readers(self, traced):
        """[(metric entry, reader module)] of the metrics this run prints."""
        kind, metrics = ("layers", self.per_layer) if traced \
            else ("end_to_end", self.end_to_end)
        return [(m, importlib.import_module(f"chipbench.{kind}.{m['name']}"))
                for m in metrics]


# --------------------------------------------------------------------------
# what a graph driver and a metric reader see
# --------------------------------------------------------------------------

class Run:
    """One run: the cell, the device, and what was measured. A graph driver
    uses ``span``, ``block``, ``make_tiles`` and ``device_counters``; a
    metric's reader takes its value from the attributes."""

    timeout = 300.0             # seconds allowed to each wait inside a solve

    def __init__(self, cell, args, rank=0, nranks=1, comm=None):
        self.cell, self.config, self.traffic = cell, cell.config, cell.traffic
        self.graph, self.peaks = cell.graph, None
        self.seed = args.seed
        self.traced, self.rehearsal = bool(args.trace), args.rehearsal
        self.rank, self.nranks, self.comm = rank, nranks, comm
        self.tasks_per_solve = cell.graph.tasks(cell.traffic)
        self.flops_per_solve = cell.graph.flops(cell.traffic)
        self.solves = []        # window solves: {"seconds", "cpu", "insert", ...}
        self.first_solve_s = self.setup_s = self.window_s = None
        self.counters = {}      # name -> change over the window
        self.ready_wait = None  # {"count", "buckets"} over the window
        self.trace = None       # reduce_trace.reduce(...) of the traced part
        self.memory_peak_bytes = None
        self.compiles_in_window = 0
        self.attempted = self.failed = 0
        self._spans = {}

    # ---- for graph drivers
    @contextlib.contextmanager
    def span(self, name):
        """A span of this benchmark around a call into a layer: host seconds
        added up under ``name``, and the same span in the profiler's trace."""
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter()
        with TraceAnnotation(name):
            try:
                yield
            finally:
                self._spans[name] = self._spans.get(name, 0.0) \
                    + time.perf_counter() - t0

    def block(self, payload):
        """Wait for an output tile's device copy; a host array here means the
        newest copy is not on the device, which is a different run."""
        if not hasattr(payload, "block_until_ready"):
            raise RunFailure("an output tile's newest copy is on the host "
                             f"({type(payload).__name__})")
        payload.block_until_ready()

    def make_tiles(self, keys, fn):
        """{key: fn(key)} over a few threads (numpy's generators release
        the GIL): host tiles from the seed, made once in set-up."""
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=min(8, os.cpu_count() or 1)) as pool:
            return dict(zip(keys, pool.map(fn, keys)))

    def device_counters(self, ctx):
        """The program's own counts, read as they are."""
        from parsec_tpu.device.tpu import TPUDevice
        stats = ctx.devices.statistics()
        tpus = [d for d in ctx.devices.devices if isinstance(d, TPUDevice)]
        out = {"executed." + name: int(s["executed_tasks"])
               for name, s in stats.items()}
        out["transfer_in_bytes"] = sum(int(s["transfer_in_bytes"])
                                       for s in stats.values())
        out["evictions"] = sum(d.evictions for d in tpus)
        if self.comm is not None:
            out["sent_msgs"] = int(self.comm.sent_msgs)
        return out

    # ---- for metric readers
    def solve_seconds(self):
        return [s["seconds"] for s in self.solves if s["ok"]]

    def per_task(self, key):
        """Host seconds under ``key`` per task, over the window's solves the
        profiler did not slow (all of them, when every one was traced)."""
        picked = [s for s in self.solves if s["ok"] and not s["traced"]] \
            or [s for s in self.solves if s["ok"]]
        tasks = sum(s["local_tasks"] for s in picked)
        return sum(s[key] for s in picked) / tasks if tasks else None


# --------------------------------------------------------------------------
# the measuring process: one per chip
# --------------------------------------------------------------------------

def misplaced(executed, ntasks):
    """Why ``executed`` ({device name: tasks run}) is not "all ``ntasks``
    tasks ran on the accelerator", or None (``chip_smoke.py``'s, copied)."""
    on_tpu = sum(n for name, n in executed.items() if name.startswith("tpu"))
    if executed.get("cpu", 0) or on_tpu != ntasks:
        return (f"{on_tpu} of {ntasks} tasks ran on the accelerator, "
                f"{executed.get('cpu', 0)} on the CPU device: {executed}")
    return None


def start_backend(run):
    """Backend, compile accounting, native lanes, precision. Returns jax,
    the device every result names, and the running compile counts. Exits 3
    without an accelerator."""
    import jax

    from parsec_tpu import native
    from parsec_tpu.utils import compile_cache, mca

    compile_cache.enable()
    compile_log = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_log["compiles"] += 1
            compile_log["compile_s"] += secs

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            compile_log["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    devs = jax.devices()
    if not run.rehearsal:
        if devs[0].platform != "tpu":
            print(f"chipbench: JAX found no accelerator "
                  f"(platform={devs[0].platform!r})", file=sys.stderr)
            sys.exit(RC_NO_ACCELERATOR)
        if len(devs) != 1:
            raise RunFailure(f"a measuring process owns exactly one chip, "
                             f"jax.devices() has {len(devs)}")
        kinds = run.cell.peaks["by_device_kind"]
        if devs[0].device_kind not in kinds:
            raise RunFailure(f"device_kind {devs[0].device_kind!r} is not in "
                             f"chipbench/peaks.json ({sorted(kinds)})")
        run.peaks = kinds[devs[0].device_kind]
    native.require_all()
    mca.set("tile_dot_precision", run.config["precision"])
    if run.rehearsal:
        mca.set("device_tpu_over_cpu", True)
    if run.traced:
        mca.set("hist_enabled", True)       # ready_wait_p99's source
    return jax, devs[0], compile_log


def ready_wait_snapshot():
    from parsec_tpu.utils.hist import histograms
    return histograms.snapshot().get("ptdtd.ready_wait_ns")


def one_solve(run, state, traced):
    """Restore outside the timer, then one timed solve."""
    with run.span("refill"):
        run.graph.restore(state, run)
    if run.comm is not None:
        run.comm.sync(timeout=run.timeout)
    run._spans = {}
    rec = {"ok": False, "traced": traced}
    cpu0, t0 = time.process_time(), time.perf_counter()
    try:
        rec.update(run.graph.solve(state, run))
        rec["ok"] = True
    except (RuntimeError, TimeoutError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["seconds"] = time.perf_counter() - t0
    rec["cpu"] = time.process_time() - cpu0
    rec["insert"] = run._spans.get("insert", 0.0)
    rec["wait"] = run._spans.get("wait", 0.0)
    return rec


def keep_going(run, deadline, index):
    """Start another solve? One client decides by its clock; ranks follow
    rank 0, whose decision crosses in a file before the solve's barrier."""
    more = time.perf_counter() < deadline
    if run.comm is None:
        return more
    flag = os.path.join(SCRATCH, f"{run.cell.name}-{run.seed}", f"go-{index}")
    if run.rank == 0:
        with open(flag, "w") as f:
            f.write("1" if more else "0")
    run.comm.sync(timeout=run.timeout)
    with open(flag) as f:
        return f.read() == "1"


def measure(cell, args, rank=0, nranks=1, comm=None):
    """Set-up, window, check. Returns the finished :class:`Run` and the
    record the last line (or the rank's line) is made from."""
    from chipbench import reduce_trace

    run = Run(cell, args, rank, nranks, comm)
    jax, dev, compile_log = start_backend(run)
    t = time.perf_counter()
    state = cell.graph.build(run)
    build_s = time.perf_counter() - t

    # one untimed warm-up solve: loads every executable, stages the operands
    t = time.perf_counter()
    warm = one_solve(run, state, traced=False)
    if not warm["ok"]:
        raise RunFailure(f"the warm-up solve failed: {warm.get('error')}")
    run.first_solve_s = time.perf_counter() - t
    setup_compiles = dict(compile_log)

    trace_dir = os.path.join(SCRATCH, f"trace-{cell.name}-{args.seed}-r{rank}")
    tracing = run.traced
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # no per-call hook in the host path
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        trace_until = time.perf_counter() \
            + cell.traffic.get("trace_seconds", 3.0)

    traced_solves = 0

    before = cell.graph.counters(state, run)
    hist0 = ready_wait_snapshot()
    run.setup_s = time.perf_counter() - T_START
    setup_wall_end = time.time()
    t0 = time.perf_counter()
    deadline = t0 + args.seconds
    while keep_going(run, deadline, len(run.solves)):
        run.solves.append(one_solve(run, state, traced=tracing))
        if tracing and time.perf_counter() >= trace_until:
            # whole solves only: the traced part ends on a solve's end
            jax.profiler.stop_trace()
            tracing, traced_solves = False, len(run.solves)
    if tracing:
        jax.profiler.stop_trace()
        traced_solves = len(run.solves)
    run.window_s = time.perf_counter() - t0
    if run.traced:
        run.trace = reduce_trace.reduce_dir(trace_dir)
        run.trace["solves"] = traced_solves
        shutil.rmtree(trace_dir, ignore_errors=True)
    after = cell.graph.counters(state, run)
    hist1 = ready_wait_snapshot()
    run.compiles_in_window = compile_log["compiles"] - setup_compiles["compiles"]
    run.counters = {k: after[k] - before.get(k, 0) for k in after}
    if hist0 is not None and hist1 is not None:
        run.ready_wait = {
            "count": hist1["count"] - hist0["count"],
            "buckets": [b - a for a, b in zip(hist0["buckets"],
                                              hist1["buckets"])]}
    run.attempted = len(run.solves)
    run.failed = sum(not s["ok"] for s in run.solves)
    run.memory_peak_bytes = int(
        (dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    # gates: placement over the window (chip_smoke.py's assert, which with
    # native.require_all() is all it holds the DTD paths to) and compiles
    good = [s for s in run.solves if s["ok"]]
    local_tasks = sum(s["local_tasks"] for s in good)
    problems = []
    if not run.failed:
        executed = {k[len("executed."):]: v for k, v in run.counters.items()
                    if k.startswith("executed.")}
        problem = misplaced(executed, local_tasks)
        if problem:
            problems.append(problem)
    if run.compiles_in_window and not run.config["compiles_in_window_allowed"]:
        problems.append(f"{run.compiles_in_window} backend compilations "
                        f"inside the window")
    if problems:
        raise RunFailure("; ".join(problems))

    # the last solve's result, checked on the device (or dumped for the
    # parent's checker, which has the whole factor)
    t = time.perf_counter()
    with run.span("check"):
        if comm is not None:
            cell.graph.dump(state, run, os.path.join(
                SCRATCH, f"{cell.name}-{args.seed}", "factor"))
            correct, detail = None, {"dumped": True}
        else:
            correct, detail = cell.graph.check(state, run)
    check_s = time.perf_counter() - t

    secs = run.solve_seconds()
    note = {
        "cell": cell.name, "seed": args.seed, "rank": rank,
        "rehearsal": run.rehearsal, "traced": run.traced,
        "tasks_per_solve": run.tasks_per_solve,
        "solves": run.attempted, "failed": run.failed,
        "errors": [s["error"] for s in run.solves if not s["ok"]][:3],
        "compiles_setup": setup_compiles, "compiles_total": dict(compile_log),
        "compiles_in_window": run.compiles_in_window,
        "counters": run.counters, "check": detail,
        "native_dtd_engine": all(s.get("native_engine") for s in good),
    }
    if not run.rehearsal:
        # times only from a chip run
        note.update(build_s=build_s, first_solve_s=run.first_solve_s,
                    setup_s=run.setup_s, check_s=check_s,
                    window_s=run.window_s, solve_seconds=secs)
        if len(secs) >= 2:
            q = statistics.quantiles(secs, n=4, method="inclusive")
            note.update(solve_median_s=q[1], solve_q1_s=q[0], solve_q3_s=q[2])
    log("RUN", note)
    cell.graph.close(state, run)
    return run, {"correct": correct, "setup_wall_end": setup_wall_end,
                 "device": {"platform": str(dev.platform),
                            "kind": str(dev.device_kind), "count": 1,
                            "memory_peak_bytes": run.memory_peak_bytes}}


def metrics_of(cell, run):
    """{name: {"value", "unit"}} from the cell's readers; a reader that
    finds nothing to read returns None and its metric is left out, with a
    word on stderr: the driver refuses a line that lacks a metric
    BENCHMARK.json lists for the cell."""
    out = {}
    for entry, reader in cell.readers(run.traced):
        value = reader.read(run)
        if value is None:
            print(f"chipbench: {entry['name']} found nothing to read in "
                  f"{cell.name}; left out of the line", file=sys.stderr)
        else:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def last_line(run, rec, metrics):
    line = {"correct": bool(rec["correct"]), "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": rec["device"]}
    if run.rehearsal:
        # counts and ``correct`` only: a CPU time never stands under a
        # metric's name
        line["metrics"] = {}
        line["rehearsal"] = True
        line["would_report"] = sorted(metrics)
    elif run.traced and run.trace is not None:
        line["device"]["busy_s"] = run.trace["busy_s"]
        line["device"]["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"][:10],
                             "idle_gaps": run.trace["idle_gaps"][:10]}
    return line


def run_one_chip(cell, args):
    run, rec = measure(cell, args)
    print(json.dumps(last_line(run, rec, metrics_of(cell, run))),
          flush=True)
    return 0


# --------------------------------------------------------------------------
# a cell across chips: one OS rank per chip, the parent never imports JAX
# --------------------------------------------------------------------------

def run_rank(cell, args):
    """One rank of a multi-chip cell (started by ``parsec_tpu.launch``)."""
    from parsec_tpu.comm.tcp import init_from_env

    comm = init_from_env()
    run, rec = measure(cell, args, comm.my_rank, comm.nb_ranks, comm)
    values = {name: m["value"] for name, m in metrics_of(cell, run).items()} \
        if run.traced else {}
    log("RANK", {
        "rank": comm.my_rank, "device": rec["device"],
        "chip": os.environ.get("TPU_VISIBLE_CHIPS"),
        "setup_wall_end": rec["setup_wall_end"],
        "solves": [{k: s[k] for k in ("ok", "seconds", "local_tasks")}
                   for s in run.solves],
        "per_layer": values,
        "trace": None if run.trace is None else
        {k: run.trace[k] for k in ("busy_s", "window_s", "device_ops",
                                   "idle_gaps")}})
    comm.sync(timeout=run.timeout)
    comm.fini()
    return 0


def _run_group(cmd, env, timeout):
    """Run a child in its own process group and return (rc, stdout); on
    timeout or interrupt the whole group is killed, so nothing this
    benchmark starts outlives it (``chip_smoke.py``'s, copied)."""
    p = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
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


def run_across_chips(cell, args):
    """Parent of a cell with ``ranks`` > 1: launch, reduce, check."""
    from parsec_tpu.launch import chip_env, local_chip_count

    nranks = cell.config["ranks"]
    if not args.rehearsal and local_chip_count() < nranks:
        print(f"chipbench: the cell needs {nranks} chips, this host has "
              f"{local_chip_count()}", file=sys.stderr)
        return RC_NO_ACCELERATOR
    meet = os.path.join(SCRATCH, f"{cell.name}-{args.seed}")
    shutil.rmtree(meet, ignore_errors=True)
    os.makedirs(meet)
    env = dict(os.environ)
    how = ["--cpu", "--mca", "device_tpu_over_cpu", "1"] if args.rehearsal \
        else ["--bind-devices"]
    own = [sys.executable, os.path.abspath(__file__), "--workload", cell.name,
           "--seed", str(args.seed), "--seconds", str(args.seconds),
           "--trace", str(args.trace)] + ["--rehearsal"] * args.rehearsal
    budget = 300 + args.seconds
    rc, out = _run_group(
        [sys.executable, "-m", "parsec_tpu.launch", "-n", str(nranks), *how,
         "--timeout", str(budget), *own[1:]], env, budget + 60)
    sys.stdout.write("".join(line + "\n" for line in out.splitlines()
                             if line.startswith(("RUN ", "RANK "))))
    ranks = sorted(_tagged(out, "RANK "), key=lambda r: r["rank"])
    if rc != 0 or len(ranks) != nranks:
        raise RunFailure(f"launcher exited {rc}, {len(ranks)} of {nranks} "
                         f"ranks reported")
    chips = {r["chip"] for r in ranks}
    if not args.rehearsal and len(chips) != nranks:
        raise RunFailure(f"bound chips not distinct: {sorted(chips)}")

    # all ranks' tasks, the slowest rank's seconds
    run = Run(cell, args, nranks=nranks)
    for i in range(len(ranks[0]["solves"])):
        per_rank = [r["solves"][i] for r in ranks]
        run.solves.append({
            "ok": all(s["ok"] for s in per_rank), "traced": False,
            "seconds": max(s["seconds"] for s in per_rank),
            "local_tasks": sum(s["local_tasks"] for s in per_rank)})
    run.attempted = len(run.solves)
    run.failed = sum(not s["ok"] for s in run.solves)
    run.setup_s = max(r["setup_wall_end"] for r in ranks) - WALL_START
    run.memory_peak_bytes = max(r["device"]["memory_peak_bytes"]
                                for r in ranks)
    if args.trace:
        metrics = {}
        for entry, reader in cell.readers(True):
            values = [r["per_layer"][entry["name"]] for r in ranks
                      if entry["name"] in r["per_layer"]]
            if len(values) == nranks:
                how_ = getattr(reader, "RANKS", "mean")
                value = {"mean": statistics.fmean, "sum": sum,
                         "max": max}[how_](values)
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
            else:
                print(f"chipbench: {entry['name']} read on {len(values)} of "
                      f"{nranks} ranks; left out of the line",
                      file=sys.stderr)
        traces = [r["trace"] for r in ranks if r["trace"]]
        if len(traces) == nranks:
            run.trace = {
                "busy_s": statistics.fmean(t["busy_s"] for t in traces),
                "window_s": statistics.fmean(t["window_s"] for t in traces),
                # the breakdown is rank 0's; every rank's is on its line
                "device_ops": traces[0]["device_ops"],
                "idle_gaps": traces[0]["idle_gaps"]}
    else:
        metrics = metrics_of(cell, run)

    # the whole factor, once the ranks have gone: one checker child, one chip
    checker_env = {**env, **chip_env(0)} if not args.rehearsal \
        else {**env, "JAX_PLATFORMS": "cpu"}
    rc, out = _run_group(own + ["--check-dumped", os.path.join(meet, "factor")],
                         checker_env, 300)
    checks = _tagged(out, "CHECK ")
    if rc != 0 or not checks:
        raise RunFailure(f"the checker child exited {rc}")
    log("CHECK", checks[-1])
    shutil.rmtree(meet, ignore_errors=True)
    dev0 = ranks[0]["device"]
    rec = {"correct": checks[-1]["correct"],
           "device": {"platform": dev0["platform"], "kind": dev0["kind"],
                      "count": sum(r["device"]["count"] for r in ranks),
                      "memory_peak_bytes": run.memory_peak_bytes}}
    print(json.dumps(last_line(run, rec, metrics)), flush=True)
    return 0


def check_dumped(cell, args):
    """The checker child: the configuration's residual over dumped tiles."""
    run = Run(cell, args)
    start_backend(run)
    correct, detail = cell.graph.check_dumped(
        args.check_dumped, cell.traffic, args.seed, cell.config)
    log("CHECK", {"correct": correct, **detail})
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window "
                         "(default: BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend: debugs this "
                         "harness, measures nothing")
    ap.add_argument("--check-dumped", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.seconds is None:
        args.seconds = float(load_json("BENCHMARK.json")["run_seconds"])
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        try:
            import parsec_tpu                               # noqa: F401
            from parsec_tpu.comm.tcp import ENV_RANK
            from parsec_tpu.launch import chip_env, local_chip_count
        except ImportError as e:
            raise RunFailure(f"the program is not beside chipbench/: {e}")
        cell = Cell(args.workload, args.rehearsal)
        if args.check_dumped:
            return check_dumped(cell, args)
        if os.environ.get(ENV_RANK) is not None:
            return run_rank(cell, args)
        if cell.config["ranks"] > 1:
            return run_across_chips(cell, args)
        if not args.rehearsal and local_chip_count() > 1:
            # a one-chip cell on a larger host sees exactly chip 0; the
            # environment must be in place before JAX starts
            os.environ.update(chip_env(0))
        return run_one_chip(cell, args)
    except RunFailure as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
