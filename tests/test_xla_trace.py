"""XLA profiler bridge smoke test (the NVTX-swap role)."""

import glob
import os

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.dsl.dtd import DTDTaskpool, RW
from parsec_tpu.utils.xla_trace import TaskAnnotator, xla_trace


def test_xla_trace_capture(tmp_path):
    ctx = Context(nb_cores=1)
    ann = TaskAnnotator()
    ann.enable(ctx)
    logdir = str(tmp_path / "tb")
    with xla_trace(logdir):
        tp = DTDTaskpool(ctx, "xt")
        t = tp.tile_new((8, 8), np.float32)
        for _ in range(4):
            tp.insert_task(lambda x: x * 1.5, (t, RW))
        tp.wait(); tp.close(); ctx.wait()
    ctx.fini()
    # a profile directory with at least one trace artifact exists
    produced = glob.glob(os.path.join(logdir, "**", "*"), recursive=True)
    assert any(os.path.isfile(p) for p in produced), produced


def test_xla_trace_noop_without_dir():
    with xla_trace(None):
        pass  # must be a clean no-op


def test_task_annotator_fires_once_per_task_on_the_native_per_task_lane():
    """A DTD pool on a context with an accelerator device takes the native
    engine's per-task lane (the batched lane refuses it); EXEC_BEGIN/END —
    the enqueue, for a device task — fire once per task there."""
    from parsec_tpu.utils import mca

    class Counting(TaskAnnotator):
        begun = ended = 0

        def _begin(self, stream, task, extra):
            Counting.begun += 1
            super()._begin(stream, task, extra)

        def _end(self, stream, task, extra):
            Counting.ended += 1
            super()._end(stream, task, extra)

    mca.set("device_tpu_over_cpu", True)
    try:
        ctx = Context(nb_cores=1)
        ann = Counting()
        ann.enable(ctx)
        tp = DTDTaskpool(ctx, "xt-native")
        t = tp.tile_new((8, 8), np.float32)

        def body(x):
            return x * 1.5

        for _ in range(12):
            tp.insert_task(body, (t, RW))
        tp.wait(); tp.close(); ctx.wait()
        native, batched = tp._neng is not None, tp._batch_on
        ann.disable(ctx)
        ctx.fini()
    finally:
        mca.params.unset("device_tpu_over_cpu")
    if not native:
        pytest.skip("native _ptdtd unavailable")
    assert not batched
    assert Counting.begun == Counting.ended == 12 and not ann._open


def _host_plane_spans(logdir, names):
    """The events named in ``names`` of the trace under ``logdir``: how
    often each was seen and the names each stood directly inside; any two
    of one thread are disjoint or nested, and all are on a host plane."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    seen, parents = {}, {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in line.events if ev.name in names),
                         key=lambda e: (e[0], -e[1]))
            stack = []
            for s, e, name in evs:
                assert plane.name.startswith("/host:"), plane.name
                seen[name] = seen.get(name, 0) + 1
                while stack and stack[-1][1] <= s:
                    stack.pop()
                if stack:
                    assert e <= stack[-1][1], (name, stack[-1][2])
                    parents.setdefault(name, set()).add(stack[-1][2])
                stack.append((s, e, name))
    return seen, parents


def _traced_dtd_pool(ctx, logdir):
    from parsec_tpu.data.matrix import TiledMatrix

    A = TiledMatrix("XS", 64, 16, 16, 16)
    A.fill(lambda m, n: np.ones((16, 16), np.float32))
    with xla_trace(logdir):
        tp = DTDTaskpool(ctx, "xt-spans")

        def body(x):
            return x + 1.0

        for i in range(32):
            tp.insert_task(body, (tp.tile_of(A, i % 4, 0), RW))
        tp.wait(); tp.close(); ctx.wait()


def _traced_ptg_pool(ctx, logdir):
    """``ex06``'s GEMM, 2 x 2 x 2 tiles, as one fused region on ``ptdev``."""
    import sys

    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.dsl.ptg.compiler import compile_ptg
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples"))
    import ex06_gemm_ptg

    mats = {}
    for name in "ABC":
        M = TiledMatrix(f"xp{name}", 32, 32, 16, 16)
        M.fill(lambda m, n: np.ones((16, 16), np.float32))
        mats["desc" + name] = M
    prog = compile_ptg(ex06_gemm_ptg.SRC, "xt-ptg")
    with xla_trace(logdir):
        tp = prog.instantiate(ctx, globals={"MT": 2, "NT": 2, "KT": 2},
                              collections=mats)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
    assert tp.completed and ctx._ptdev.failed() is None


@pytest.mark.parametrize("path", ["dtd", "ptg"])
def test_runtime_spans_stand_on_the_host_plane_properly_nested(tmp_path, path):
    """A jax.profiler trace of a small pool with the spans on, through the
    per-task manager and through the ``ptdev`` lane: the span names are
    TraceMe events of a host plane, on each thread any two of them are
    disjoint or one inside the other, and the sub-spans of ISSUE 37 stand
    where they should: ``dev.gather`` / ``dev.call`` inside
    ``dev.submit``, ``ptdev.push`` / ``ptdev.call`` inside
    ``ptdev.dispatch``, a stage-in miss inside the gather or the push."""
    from parsec_tpu import native as native_mod
    from parsec_tpu.utils import mca
    from parsec_tpu.utils import xla_trace as X

    if path == "ptg" and (native_mod.load_ptexec() is None
                          or native_mod.load_ptdev() is None):
        pytest.skip("native _ptexec/_ptdev unavailable")
    params = {"device_tpu_over_cpu": True, "hist_enabled": True,
              "dtd_window_size": 8, "dtd_threshold_size": 4}
    for k, v in params.items():
        mca.set(k, v)
    try:
        ctx = Context(nb_cores=1)
        (_traced_dtd_pool if path == "dtd" else _traced_ptg_pool)(
            ctx, str(tmp_path))
        dev = next(d for d in ctx.devices.devices if d.name.startswith("tpu"))
        ctx.fini()
    finally:
        for k in params:
            mca.params.unset(k)
    if path == "ptg":
        names = {X.PTG_LOWER, X.PTDEV_DISPATCH, X.PTDEV_PUSH, X.PTDEV_CALL,
                 X.PTDEV_POLL, X.PTDEV_RETIRE, X.DEV_STAGE_IN}
        seen, parents = _host_plane_spans(str(tmp_path), names)
        assert set(seen) == names
        # one a program that pushes: one region, one callback
        assert seen[X.PTDEV_PUSH] == seen[X.PTDEV_DISPATCH]
        assert seen[X.PTDEV_CALL] == seen[X.PTDEV_RETIRE] == 1  # one region
        # A, B, C: 12 tiles, every one a miss, in one put of the one callback
        assert seen[X.DEV_STAGE_IN] == seen[X.PTDEV_PUSH] == 1
        assert dev.transfer_in_bytes == 12 * 16 * 16 * 4
        assert parents[X.PTDEV_PUSH] == parents[X.PTDEV_CALL] \
            == {X.PTDEV_DISPATCH}
        assert parents[X.DEV_STAGE_IN] == {X.PTDEV_PUSH}
        assert parents[X.PTDEV_RETIRE] == {X.PTDEV_POLL}
        assert X.PTDEV_DISPATCH not in parents
        return
    names = {X.DTD_LINK, X.DTD_STALL, X.DEV_SUBMIT, X.DEV_STAGE_IN,
             X.DEV_POLL, X.DEV_RETIRE, X.DEV_GATHER, X.DEV_CALL}
    seen, parents = _host_plane_spans(str(tmp_path), names)
    assert set(seen) == names
    assert seen[X.DTD_LINK] == seen[X.DEV_RETIRE] == 32
    # one dev.submit span a program: a group of tasks is issued under one
    assert seen[X.DEV_SUBMIT] == seen[X.DEV_GATHER] == seen[X.DEV_CALL] == \
        32 - dev.batched_tasks + dev.batched_dispatches
    assert seen[X.DEV_STAGE_IN] == 4
    assert parents[X.DEV_GATHER] == parents[X.DEV_CALL] == {X.DEV_SUBMIT}
    assert parents[X.DEV_STAGE_IN] == {X.DEV_GATHER}
    assert parents[X.DEV_RETIRE] == {X.DEV_POLL}
    assert X.DTD_STALL in parents[X.DEV_SUBMIT]
