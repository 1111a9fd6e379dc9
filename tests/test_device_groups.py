"""Device issue in groups (ISSUE 27): when the manager observes that the host
paces a task class, it issues the pending tasks of that class as ONE flat
program. Over a host jax device (``device_tpu_over_cpu``), where a program is
complete by the time its issuing pass polls: the engaged side of the rule.
The other side is a stubbed ``is_ready``. Counts and results only: no test
here reads a clock."""

import types

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.device import tpu as tpu_mod
from parsec_tpu.device.tpu import TPUDevice, TPUTask
from parsec_tpu.dsl import dtd as dtd_mod
from parsec_tpu.dsl.dtd import DTDTaskpool, RW
from parsec_tpu.ops.potrf import insert_potrf_tasks, tile_gemm_update
from parsec_tpu.utils import hist as H
from parsec_tpu.utils import mca

TS = 16


@pytest.fixture()
def dctx():
    mca.set("device_tpu_over_cpu", True)
    dtd_mod._ladders.clear()    # every test's pools are the first of a body
    c = Context(nb_cores=1)
    yield c
    c.fini()
    mca.params.unset("device_tpu_over_cpu")


def _dev(ctx):
    return next(d for d in ctx.devices.devices if isinstance(d, TPUDevice))


def _column(name, ntiles, value=lambda m: float(m)):
    A = TiledMatrix(name, TS * ntiles, TS, TS, TS)
    A.fill(lambda m, n: np.full((TS, TS), value(m), np.float32))
    return A


def _tile(A, m):
    return np.asarray(A.data_of(m, 0).newest_copy().payload)


def _record_programs(dev, monkeypatch):
    """[(class name, [ident of each task])] in issue order; ``.arrived`` has
    the idents in the order the tasks reached the device."""
    issued = _Issued()
    one, group, enqueue = dev._submit_one, dev._submit_group, \
        dev.kernel_scheduler

    def kernel_scheduler(stream, task, tpu_task=None, submit=None):
        issued.arrived.append(task.ident)
        return enqueue(stream, task, tpu_task=tpu_task, submit=submit)

    monkeypatch.setattr(dev, "kernel_scheduler", kernel_scheduler)

    def submit_one(gt):
        one(gt)
        issued.append((gt.task.task_class.name, [gt.task.ident]))

    def submit_group(members):
        out = group(members)
        for program in out:
            if len(program) > 1:    # a fall-back's singles are recorded above
                issued.append((program[0].task.task_class.name,
                               [g.task.ident for g in program]))
        return out

    monkeypatch.setattr(dev, "_submit_one", submit_one)
    monkeypatch.setattr(dev, "_submit_group", submit_group)
    return issued


class _Issued(list):
    def __init__(self):
        super().__init__()
        self.arrived = []

    def idents(self):
        return [i for _name, ids in self for i in ids]


def scale(x):
    return x * 3.0


def shift(x):
    return x + 0.5


# ------------------------------------------------------------ the program

@pytest.mark.parametrize("k", [2, 4, 8, 16])
def test_group_program_is_flat_and_carries_the_bodys_name(k):
    """``k`` tasks' operands side by side in, ``k`` separate outputs out,
    each what the task's own program gives; the XLA module keeps the name
    ``jit_<body>``, which ``chipbench/reduce_trace.py`` adds work under."""
    import jax
    rng = np.random.default_rng(k)
    ops = [rng.standard_normal((TS, TS)).astype(np.float32)
           for _ in range(3 * k)]
    prog = dtd_mod._grouped(tile_gemm_update, k)
    assert prog is dtd_mod._grouped(tile_gemm_update, k)     # cached
    outs = prog(*ops)
    assert len(outs) == k
    single = jax.jit(tile_gemm_update)
    for i in range(k):
        np.testing.assert_array_equal(np.asarray(outs[i]),
                                      np.asarray(single(*ops[3 * i:3 * i + 3])))
    text = prog.lower(*ops).as_text()
    assert "jit_tile_gemm_update" in text.split("\n", 1)[0]


@pytest.mark.parametrize("pending,sizes", [
    (1, [1]), (2, [2]), (3, [2, 1]), (7, [4, 2, 1]), (16, [16]),
    (21, [16, 4, 1]), (33, [16, 16, 1])])
def test_a_pending_run_splits_down_the_ladder(dctx, pending, sizes):
    dev = _dev(dctx)
    tc = types.SimpleNamespace(name="c")
    hook = object()
    dev._pending.extend(
        TPUTask(types.SimpleNamespace(task_class=tc, ident=i), None,
                batch_submit=hook) for i in range(pending))
    got, order = [], []
    while dev._pending:
        group = dev._collect(dev._pending.popleft())
        got.append(len(group))
        order += [g.task.ident for g in group]
    assert got == sizes and order == list(range(pending))


def test_the_ladder_is_capped_by_batch_max(dctx):
    mca.set("device_tpu_batch_max", 4)
    try:
        assert _dev(dctx).group_sizes() == [4, 2]
    finally:
        mca.params.unset("device_tpu_batch_max")


# ------------------------------------------------------- when it engages

def test_groups_form_by_observation_alone(dctx, monkeypatch):
    """No ``batch=True``, nobody holds the manager lock: the first programs
    of the class go one by one, each complete at its first poll; after
    ``PACED_STREAK`` of them the burst's enqueues wait in ``_pending`` and
    the loop's device poll issues them as groups."""
    dev = _dev(dctx)
    issued = _record_programs(dev, monkeypatch)
    A = _column("GO", 64)
    tp = DTDTaskpool(dctx, "observed")
    for m in range(64):
        tp.insert_task(scale, (tp.tile_of(A, m, 0), RW))
    tp.wait(); tp.close(); dctx.wait()
    for m in range(64):
        assert np.allclose(_tile(A, m), 3.0 * m)
    sizes = [len(ids) for _name, ids in issued]
    assert sizes[:tpu_mod.PACED_STREAK] == [1] * tpu_mod.PACED_STREAK
    assert max(sizes) > 1 and sum(sizes) == 64 == dev.executed_tasks
    assert dev.batched_tasks == sum(s for s in sizes if s > 1)
    assert dev.batched_dispatches == sum(s > 1 for s in sizes)
    assert set(sizes) <= {1, *tpu_mod.GROUP_LADDER}
    assert issued.idents() == issued.arrived    # arrival order kept
    stats = dctx.devices.statistics()[dev.name]
    assert stats["batched_tasks"] == dev.batched_tasks
    assert stats["batched_dispatches"] == dev.batched_dispatches


@pytest.mark.parametrize("tiles_of_room, grouped", [(1, False), (64, True)])
def test_a_cycle_that_evicts_judges_no_program(dctx, monkeypatch,
                                               tiles_of_room, grouped):
    """ISSUE 35: the same 64 tasks, each staging a tile of its own. Under a
    budget of one tile every stage-in but the first evicts (and writes a
    scaled tile back), so every program is found complete after a cycle
    that evicted and none is judged: the class goes a program a task to the
    end. With room for all 64 nothing evicts and the groups form as above."""
    dev = _dev(dctx)
    dev.set_budget(tiles_of_room * TS * TS * 4, unit=1024)
    issued = _record_programs(dev, monkeypatch)
    A = _column("EV", 64)
    tp = DTDTaskpool(dctx, "evicting")
    for m in range(64):
        tp.insert_task(scale, (tp.tile_of(A, m, 0), RW))
    tp.wait(); tp.close(); dctx.wait()
    for m in range(64):
        assert np.allclose(_tile(A, m), 3.0 * m)
    sizes = [len(ids) for _name, ids in issued]
    assert sum(sizes) == 64 == dev.executed_tasks
    assert (max(sizes) > 1) is grouped
    assert (dev.evictions > 0) is not grouped
    if not grouped:
        assert dev.batched_tasks == 0 and dev.owned_evictions > 0
        assert dev.transfer_out_bytes == dev.owned_evictions * TS * TS * 4


class _Late:
    """An output whose completion event fires at the ``after``-th poll."""

    def __init__(self, array, after):
        self.array, self.left = array, after

    def is_ready(self):
        self.left -= 1
        return self.left < 0


def _make_late(dev, monkeypatch, after):
    """Every program's outputs show complete only at the ``after[0]``-th
    poll (0: at once, as the host device does)."""
    one, group, epilog = dev._submit_one, dev._submit_group, dev._epilog

    def wrap(gt):
        # stubbed at 0 too: the host device under load may not have finished
        # a program one host cycle later, and the test would read the load
        gt.out_arrays = tuple(_Late(a, after[0]) for a in gt.out_arrays)

    def submit_one(gt):
        one(gt)
        wrap(gt)

    def submit_group(members):
        out = group(members)
        for program in out:
            for gt in program:
                wrap(gt)
        return out

    def unwrap(stream, gt):
        gt.out_arrays = tuple(getattr(a, "array", a) for a in gt.out_arrays)
        epilog(stream, gt)

    monkeypatch.setattr(dev, "_submit_one", submit_one)
    monkeypatch.setattr(dev, "_submit_group", submit_group)
    monkeypatch.setattr(dev, "_epilog", unwrap)


def test_a_class_behind_a_backlog_is_never_grouped(dctx, monkeypatch):
    """Programs not complete one host cycle after their submit (a stubbed
    ``is_ready``): the chip has work queued, no streak forms, every task
    is its own program and the programs leave in arrival order, each
    enqueue driving the manager as before."""
    dev = _dev(dctx)
    issued = _record_programs(dev, monkeypatch)
    _make_late(dev, monkeypatch, [3])
    A = _column("LATE", 48)
    tp = DTDTaskpool(dctx, "late")
    for m in range(48):
        tp.insert_task(scale, (tp.tile_of(A, m, 0), RW))
    tp.wait(); tp.close(); dctx.wait()
    for m in range(48):
        assert np.allclose(_tile(A, m), 3.0 * m)
    assert [len(ids) for _n, ids in issued] == [1] * 48
    assert issued.idents() == issued.arrived
    assert dev.batched_tasks == dev.batched_dispatches == 0
    assert not dev._paced and not dev._pending


def test_the_first_late_program_switches_the_class_back(dctx, monkeypatch):
    dev = _dev(dctx)
    issued = _record_programs(dev, monkeypatch)
    after = [0]
    _make_late(dev, monkeypatch, after)
    A = _column("BACK", 176)
    tp = DTDTaskpool(dctx, "back")
    for m in range(48):
        tp.insert_task(scale, (tp.tile_of(A, m, 0), RW))
    tp.wait()
    engaged = len(issued)
    assert dev.batched_tasks > 0
    tc = next(iter(dev._paced))
    assert dev._paced[tc] >= tpu_mod.PACED_STREAK
    after[0] = 3                    # the chip falls behind from here on
    for m in range(48, 176):
        tp.insert_task(scale, (tp.tile_of(A, m, 0), RW))
    tp.wait(); tp.close(); dctx.wait()
    for m in range(176):
        assert np.allclose(_tile(A, m), 3.0 * m)
    assert tc not in dev._paced
    sizes = [len(ids) for _n, ids in issued[engaged:]]
    first_single = sizes.index(1)
    # a program is judged by the next pass that issues: two bursts of the
    # progress loop may still leave as groups; from the first judged miss
    # on, one program a task
    assert sizes[first_single:] == [1] * (len(sizes) - first_single)
    assert sum(sizes[:first_single]) <= 64 and sum(sizes) == 128


@pytest.mark.parametrize("in_loop,groupable,drives", [
    (False, True, True), (True, True, False), (True, False, True)])
def test_who_drives_the_manager_after_an_enqueue(dctx, monkeypatch, in_loop,
                                                 groupable, drives):
    """A task that waits for companions is left in ``_pending`` only where a
    running loop polls the device next; a caller in no loop, and a task
    that is never grouped (PTG's hook, the ``ptdev`` lane), drive the
    manager from the enqueue as before."""
    dev = _dev(dctx)
    driven = []
    monkeypatch.setattr(dev, "progress", lambda stream: driven.append(1) or 0)
    monkeypatch.setattr(dctx, "in_progress_loop", lambda: in_loop)
    tc = types.SimpleNamespace(name="c", time_estimate=None)
    gt = TPUTask(types.SimpleNamespace(task_class=tc), None, batchable=True,
                 batch_submit=object() if groupable else None)
    dev.kernel_scheduler(None, gt.task, tpu_task=gt)
    assert bool(driven) == drives and list(dev._pending) == [gt]
    dev._pending.clear()
    dev.load_sub(gt.load)


# ------------------------------------------------- what a group is made of

def test_mixed_classes_in_the_pending_window_group_per_class(dctx, monkeypatch):
    """Interleaved enqueues of two classes: each class leaves as one group
    taken across the pending window, not as runs at its head."""
    dev = _dev(dctx)
    issued = _record_programs(dev, monkeypatch)
    A, B = _column("MA", 8), _column("MB", 8)
    tp = DTDTaskpool(dctx, "mixed")
    for m in range(8):
        tp.insert_task(scale, (tp.tile_of(A, m, 0), RW), batch=True)
        tp.insert_task(shift, (tp.tile_of(B, m, 0), RW), batch=True)
    with dev._manager_lock:         # enqueue all sixteen, issue none
        dctx._progress_loop(dctx.streams[0],
                            until=lambda: len(dev._pending) == 16, timeout=10)
    pending = [g.task.task_class.name for g in dev._pending]
    tp.wait(); tp.close(); dctx.wait()
    assert pending in (["scale", "shift"] * 8, ["shift", "scale"] * 8)
    assert [(n, len(ids)) for n, ids in issued] == \
        [(pending[0], 8), (pending[1], 8)]
    by_class = {n: [i for i in issued.arrived if i in ids] for n, ids in issued}
    for name, ids in issued:        # arrival order kept inside a class
        assert ids == by_class[name]
    for m in range(8):
        assert np.allclose(_tile(A, m), 3.0 * m)
        assert np.allclose(_tile(B, m), m + 0.5)
    assert (dev.batched_dispatches, dev.batched_tasks) == (2, 16)


def _no_reader_left(dev):
    return all(copy.readers == 0 for copy in dev._lru.values())


@pytest.mark.parametrize("fault", ["ragged", "oom", "oom-in-gather"])
def test_a_group_that_cannot_go_falls_back_to_singles(dctx, monkeypatch, fault):
    """Ragged operand shapes (boundary tiles), or an OOM from the group's
    program or from its stage-in: every member is unpinned and submitted on
    its own, and nothing stays pinned afterwards."""
    dev = _dev(dctx)
    issued = _record_programs(dev, monkeypatch)
    A = _column("FA", 4)
    B = TiledMatrix("FB", 8 * 4, 8, 8, 8)       # a smaller tile, same body
    B.fill(lambda m, n: np.full((8, 8), float(m), np.float32))
    tp = DTDTaskpool(dctx, "fallback")
    if fault == "oom":
        def no_room(device, tasks, inputs_list):
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        monkeypatch.setattr(tp, "_tpu_batch_submit", no_room)
    if fault == "oom-in-gather":
        gather, calls = dev._gather_inputs, [0]

        def flaky(gt):
            calls[0] += 1
            if calls[0] == 3:       # mid-gather: two members already pinned
                raise RuntimeError("RESOURCE_EXHAUSTED: injected")
            return gather(gt)
        monkeypatch.setattr(dev, "_gather_inputs", flaky)
    for m in range(4):
        tp.insert_task(scale, (tp.tile_of(A, m, 0), RW), batch=True)
        if fault == "ragged":
            tp.insert_task(scale, (tp.tile_of(B, m, 0), RW), batch=True)
    n = 8 if fault == "ragged" else 4
    with dev._manager_lock:
        dctx._progress_loop(dctx.streams[0],
                            until=lambda: len(dev._pending) == n, timeout=10)
    tp.wait(); tp.close(); dctx.wait()
    assert [len(ids) for _n, ids in issued] == [1] * n
    assert dev.batched_tasks == dev.batched_dispatches == 0
    assert dev.executed_tasks == n and _no_reader_left(dev)
    for m in range(4):
        assert np.allclose(_tile(A, m), 3.0 * m)
        if fault == "ragged":
            assert np.allclose(_tile(B, m), 3.0 * m)


# ----------------------------------------------------- a whole factorization

NT = 8


def _spd(seed):
    rng = np.random.default_rng(seed)
    n = NT * TS
    r = rng.standard_normal((n, n)).astype(np.float32)
    return (r @ r.T / n + np.eye(n, dtype=np.float32) * 4.0).astype(np.float32)


def _potrf(ctx, dense, name):
    A = TiledMatrix(name, NT * TS, NT * TS, TS, TS)
    A.fill(lambda m, n: dense[m * TS:(m + 1) * TS, n * TS:(n + 1) * TS].copy())
    tp = DTDTaskpool(ctx, name)
    inserted = insert_potrf_tasks(tp, A)
    tp.wait(); tp.close(); ctx.wait()
    assert inserted == NT * (NT + 1) * (NT + 2) // 6
    return np.tril(A.to_dense())


def test_potrf_grouped_equals_ungrouped(dctx, monkeypatch):
    dev = _dev(dctx)
    dense = _spd(27)
    grouped = _potrf(dctx, dense, "PG")
    in_groups = dev.batched_tasks
    assert in_groups > 0
    monkeypatch.setattr(tpu_mod, "PACED_STREAK", 1 << 60)   # never engages
    single = _potrf(dctx, dense, "PS")
    assert dev.batched_tasks == in_groups
    assert np.max(np.abs(grouped - single)) <= 1e-6
    np.testing.assert_allclose(grouped @ grouped.T, dense, atol=2e-4)


def test_a_second_solve_of_the_same_dag_compiles_nothing(dctx):
    """A class's whole ladder is built at its first group, so which
    programs a later solve can need is decided by the first solve of the
    DAG and never by how the groups happened to fall."""
    compiles, on = _compile_counter()
    try:
        dev = _dev(dctx)
        _potrf(dctx, _spd(1), "C1")
        built = len(compiles)
        assert dev.batched_tasks > 0
        for seed in (2, 3):
            _potrf(dctx, _spd(seed), f"C{seed + 1}")
        assert len(compiles) == built, compiles[built:]
        # the ladder: every size, for a class that formed a group
        sizes = {k for (fn, k) in
                 (key for key in dtd_mod._jit_cache if isinstance(key, tuple))
                 if fn is tile_gemm_update}
        assert sizes == set(tpu_mod.GROUP_LADDER)
    finally:
        on[0] = False


def thrice(x):
    return x * 3.0


def _compile_counter():
    import jax
    compiles, on = [], [True]

    def listener(event, secs, **_kw):
        if on[0] and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listener)
    return compiles, on


def test_no_program_is_first_built_inside_a_later_pool(dctx, monkeypatch):
    """The first pool that runs a body on the device forms no group (every
    program late: a backlog behind a cold compile); the second is paced by
    the host and would group. It issues a program a task and compiles
    nothing: the ladder is built in a class's first pool or not at all."""
    dev = _dev(dctx)
    issued = _record_programs(dev, monkeypatch)
    after = [3]
    _make_late(dev, monkeypatch, after)
    compiles, on = _compile_counter()
    try:
        A = _column("NB", 48 + 64)
        tp = DTDTaskpool(dctx, "first")
        for m in range(48):
            tp.insert_task(thrice, (tp.tile_of(A, m, 0), RW))
        tp.wait(); tp.close(); dctx.wait()
        assert [len(ids) for _n, ids in issued] == [1] * 48
        built = len(compiles)
        assert built >= 1           # the single program, in the first pool
        after[0] = 0                # the host paces the class from here on
        del issued[:]
        tp = DTDTaskpool(dctx, "second")
        for m in range(48, 48 + 64):
            tp.insert_task(thrice, (tp.tile_of(A, m, 0), RW))
        tp.wait(); tp.close(); dctx.wait()
        assert len(compiles) == built, compiles[built:]
        assert [len(ids) for _n, ids in issued] == [1] * 64
        assert dev.batched_tasks == dev.batched_dispatches == 0
        assert not any(isinstance(key, tuple) and key[0] is thrice
                       for key in dtd_mod._jit_cache)
        for m in range(48 + 64):
            assert np.allclose(_tile(A, m), 3.0 * m)
        # the rule is per (body, operand signature): another body's first
        # pool on the same device still groups, and builds its ladder there
        B = _column("NC", 64)
        tp = DTDTaskpool(dctx, "other")
        for m in range(64):
            tp.insert_task(shift, (tp.tile_of(B, m, 0), RW))
        tp.wait(); tp.close(); dctx.wait()
        assert dev.batched_tasks > 0
        assert {k for key in dtd_mod._jit_cache if isinstance(key, tuple)
                for fn, k in [key] if fn is shift} == set(tpu_mod.GROUP_LADDER)
    finally:
        on[0] = False


def test_a_later_pool_uses_the_ladder_its_first_pool_built(dctx):
    """Built in the first pool, used by every later one, whoever is alive."""
    dev = _dev(dctx)
    A = _column("LU", 128)
    for p in range(2):
        tp = DTDTaskpool(dctx, f"ladder{p}")
        for m in range(64 * p, 64 * p + 64):
            tp.insert_task(scale, (tp.tile_of(A, m, 0), RW))
        tp.wait(); tp.close(); dctx.wait()
        del tp
        assert dev.batched_tasks > 0
        seen, dev.batched_tasks = dev.batched_tasks, 0
    assert seen > 0
    assert any(state is True for (fn, _sig), state in dtd_mod._ladders.items()
               if fn is scale)


# ------------------------------------------------------- the engagement record

def _counts(field):
    return {k: v[field] for k, v in H.histograms.snapshot().items()}


def test_group_histogram_and_per_task_counts(monkeypatch):
    """``dev.submit``, ``dev.retire`` and the ready-wait count once per
    task whatever carried it; ``tpudev.group_tasks`` has one record per
    multi-task program, its size."""
    params = {"device_tpu_over_cpu": True, "hist_enabled": True}
    for k, v in params.items():
        mca.set(k, v)
    try:
        n0, s0 = _counts("count"), _counts("sum_ns")
        ctx = Context(nb_cores=1)
        dev = _dev(ctx)
        _potrf(ctx, _spd(5), "H")
        ntasks = NT * (NT + 1) * (NT + 2) // 6
        n1, s1 = _counts("count"), _counts("sum_ns")

        def delta(after, before, key):
            return after[key] - before.get(key, 0)
        for key in ("tpudev.submit_ns", "tpudev.retire_ns",
                    "ptdtd.ready_wait_ns"):
            assert delta(n1, n0, key) == ntasks == dev.executed_tasks
        assert dev.batched_tasks > 0
        assert delta(s1, s0, "tpudev.group_tasks") == dev.batched_tasks
        assert delta(n1, n0, "tpudev.group_tasks") == dev.batched_dispatches
        ctx.fini()
    finally:
        for k in params:
            mca.params.unset(k)


def test_group_tasks_is_a_registered_histogram():
    assert "group_tasks" in H.HIST_NAMES["tpudev"]
