"""DPLASMA's dpotrf written as a JDF (ISSUE 33): ``ops/potrf.py:POTRF_JDF``,
four classes over a triangular task space with ranges in DPLASMA's
declaration order, bodies calling the program's tile functions by name,
through ``ptexec`` + region fusion + ``ptdev`` (the device module over a
host device). Against the plain reference (``np.linalg.cholesky`` in
float64 on the host) and the DTD twin ``insert_potrf_tasks``. A fused
region donates the slot operands it is the last reader of (ISSUE 34): what
the plan gives away, what it never does, and what a solve leaves behind.
Counts and results only: no test here reads a clock."""

import warnings

import numpy as np
import pytest

from parsec_tpu import native as native_mod
from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.device.native import PTDEV_STATS
from parsec_tpu.dsl.dtd import DTDTaskpool
from parsec_tpu.dsl.fusion import CAPTURE_CACHE_STATS
from parsec_tpu.dsl.ptg import compiler as C
from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS, compile_ptg
from parsec_tpu.ops import potrf as ops
from parsec_tpu.utils import hist as H
from parsec_tpu.utils import mca
from parsec_tpu.utils.counters import counters

pytestmark = pytest.mark.skipif(
    native_mod.load_ptexec() is None or native_mod.load_ptdev() is None,
    reason="native _ptexec/_ptdev unavailable")

TS = 8
TILE_FNS = {"tile_potrf": ops.tile_potrf, "tile_trsm": ops.tile_trsm,
            "tile_syrk": ops.tile_syrk,
            "tile_gemm_update": ops.tile_gemm_update}


def ntasks(nt):
    return nt * (nt + 1) * (nt + 2) // 6


@pytest.fixture()
def dctx():
    mca.set("device_tpu_over_cpu", True)
    c = Context(nb_cores=1)
    yield c
    c.fini()
    mca.params.unset("device_tpu_over_cpu")


@pytest.fixture()
def ctx():
    c = Context(nb_cores=1)
    yield c
    c.fini()


def _matrix(nt, seed=0):
    n = nt * TS
    a = ops.make_spd(n, seed=seed)
    A = TiledMatrix(f"A{nt}", n, n, TS, TS)
    A.fill(lambda m, k: a[m * TS:(m + 1) * TS, k * TS:(k + 1) * TS].copy())
    return a, A


def _factor(ctx, A, prog=None):
    """One PTG solve of ``A``; returns its lower factor, dense."""
    nt = A.mt
    tp = ops.potrf_taskpool(ctx, A) if prog is None else prog.instantiate(
        ctx, globals={"NT": nt, **TILE_FNS}, collections={"descA": A})
    ctx.add_taskpool(tp)
    ctx.wait(timeout=300)
    assert tp.completed
    return np.tril(np.asarray(A.to_dense()))


def _plan_of(prog, nt):
    """The fusion plan ``prog`` holds for the pool of ``nt`` x ``nt`` tiles."""
    plan, = [e["fusion"] for e in prog._ptexec_cache.values()
             if e["fusion"] is not None and e["fusion"]["n_fused"] == ntasks(nt)]
    return plan


def _donated(plan):
    """Per region of ``plan``, the operands its program is given for good
    (a property of the region's shape)."""
    return [plan["shapes"][r["shape"]]["n_donated"] for r in plan["regions"]]


def _donated_and_returned(plan):
    """(operands the plan's device regions donate, arrays their programs
    return) a solve."""
    return (sum(_donated(plan)),
            sum(len(r["out_slots"]) + len(r["wb_keys"])
                for r in plan["regions"]))


def _reference(a):
    """The plain reference: float64 Cholesky on the host."""
    return np.linalg.cholesky(a.astype(np.float64))


def _assert_factor(got, a):
    np.testing.assert_allclose(got, _reference(a), rtol=0, atol=2e-5)
    assert np.abs(got @ got.T - a).max() <= 1e-5 * np.abs(a).max()


@pytest.mark.parametrize("nt", [1, 2, 3, 5, 8, 12])
def test_the_jdf_against_the_reference_and_the_dtd_twin(dctx, nt):
    """Every task on both lanes, none declined; NT = 12 is 364 tasks in
    three regions with edges between them."""
    a, A = _matrix(nt, seed=nt)
    x0, d0 = PTEXEC_STATS.snapshot(), PTDEV_STATS.snapshot()
    got = _factor(dctx, A)
    dx, dd = PTEXEC_STATS.delta(x0), PTDEV_STATS.delta(d0)
    assert dx["pools_engaged"] == dd["pools_engaged"] == 1
    assert dx["tasks_engaged"] == dd["tasks_engaged"] == ntasks(nt)
    assert dx["tasks_device"] == ntasks(nt)
    assert dx["pools_fallback"] == dx["pools_ineligible"] == 0
    assert dd["pools_fallback"] == dd["pools_ineligible"] == 0
    # one task is nothing to fuse: no plan, the task alone on the lanes
    assert dx["fused_tasks"] == (ntasks(nt) if nt > 1 else 0)
    assert dx["seam_tasks"] == 0
    if nt == 12:
        assert dx["fused_regions"] == dx["mixed_regions"] == 3
    assert counters.read("ptdev.cb_errors") == 0
    # what the lane gave away and got back is what the plan said it would
    want = _donated_and_returned(_plan_of(ops.potrf_program(), nt)) \
        if nt > 1 else (0, 0)
    assert (dd["donated"], dd["region_outputs"]) == want
    # one region returns its write-backs alone; three hand 100 slots on
    assert want == {12: (83, 178)}.get(nt, (0, nt * (nt + 1) // 2 * (nt > 1)))
    _assert_factor(got, a)
    _a, T = _matrix(nt, seed=nt)
    tp = DTDTaskpool(dctx, f"twin{nt}")
    assert ops.insert_potrf_tasks(tp, T) == ntasks(nt)
    assert tp.wait(timeout=300)
    tp.close()
    dctx.wait(timeout=300)
    np.testing.assert_allclose(got, np.tril(np.asarray(T.to_dense())),
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("knob, fixture, engaged, regions", [
    ("region_fusion", "dctx", ntasks(5), 0),    # per-task dispatch on the lanes
    ("ptg_native_exec", "dctx", 0, 0),          # the interpreted FSM
    (None, "ctx", ntasks(5), 1),    # no accelerator: the bodies' CPU twins
])
def test_the_other_paths_agree(request, knob, fixture, engaged, regions):
    if knob:
        mca.set(knob, False)
    try:
        a, A = _matrix(5, seed=3)
        x0 = PTEXEC_STATS.snapshot()
        got = _factor(request.getfixturevalue(fixture), A)
        dx = PTEXEC_STATS.delta(x0)
        assert dx["tasks_engaged"] == engaged
        assert dx["fused_regions"] == regions
        _assert_factor(got, a)
    finally:
        if knob:
            mca.params.unset(knob)


def test_a_second_instantiation_builds_nothing_with_callable_globals(dctx):
    """The bodies call ``tile_*`` by name, handed in as globals: module-
    level functions enter the flatten signature and the region-program key
    by identity, so later pools of the program build no plan and no
    executable. A function made per call keeps the pool uncacheable."""
    prog = compile_ptg(ops.POTRF_JDF, "potrf")
    assert prog.globals_named >= set(TILE_FNS)
    for solve in range(3):
        a, A = _matrix(12, seed=solve)
        x0, c0 = PTEXEC_STATS.snapshot(), CAPTURE_CACHE_STATS.snapshot()
        d0 = PTDEV_STATS["donated"]
        _assert_factor(_factor(dctx, A, prog), a)
        dx, dc = PTEXEC_STATS.delta(x0), CAPTURE_CACHE_STATS.delta(c0)
        assert dx["fused_regions"] == 3
        assert dx["region_programs"] == (0 if solve else 3)
        assert PTDEV_STATS["donated"] - d0 == 83    # every solve alike
        assert dc["cache_hits"] == (3 if solve else 0)
        assert dc["cache_evictions"] == 0
    assert len(prog._ptexec_cache) == 1
    cache = prog.region_programs
    assert (len(cache), cache.misses, cache.hits) == (3, 3, 6)

    def made_per_call(t):
        return ops.tile_potrf(t)
    a, A = _matrix(3, seed=9)
    tp = prog.instantiate(dctx, globals={"NT": 3, **TILE_FNS,
                                         "tile_potrf": made_per_call},
                          collections={"descA": A})
    dctx.add_taskpool(tp)
    dctx.wait(timeout=300)
    _assert_factor(np.tril(np.asarray(A.to_dense())), a)
    assert len(prog._ptexec_cache) == 1


def test_pools_of_two_sizes_share_one_program_object(dctx):
    """NT = 6, then NT = 7, then NT = 6 again: the shape keys of mixed
    regions keep the pools apart (a region's canonical plan holds its
    members' classes and wiring), and the third pool builds nothing."""
    prog = compile_ptg(ops.POTRF_JDF, "potrf")
    built = []
    for nt in (6, 7, 6):
        a, A = _matrix(nt, seed=nt)
        x0 = PTEXEC_STATS.snapshot()
        _assert_factor(_factor(dctx, A, prog), a)
        dx = PTEXEC_STATS.delta(x0)
        assert dx["tasks_engaged"] == ntasks(nt) and dx["mixed_regions"] == 1
        built.append(dx["region_programs"])
    assert built == [1, 1, 0]
    assert len(prog.region_programs) == 2


def test_a_region_program_names_each_members_class(dctx, monkeypatch):
    """``jax.named_scope(<class>)`` around each member's body: the
    operations of a mixed region's program say whose they are."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.dsl.ptg import compiler as C

    built = []
    make = C._mk_region_program
    monkeypatch.setattr(C, "_mk_region_program",
                        lambda *a: built.append(make(*a)) or built[-1])
    prog = compile_ptg(ops.POTRF_JDF, "potrf")
    a, A = _matrix(4, seed=4)
    _assert_factor(_factor(dctx, A, prog), a)
    plan, = [e["fusion"] for e in prog._ptexec_cache.values()]
    tile = jax.ShapeDtypeStruct((TS, TS), jnp.float32)
    text = jax.jit(built[0]).lower(
        (), (tile,) * len(plan["regions"][0]["ext"])).as_text(debug_info=True)
    assert "module @jit_ptg_region_POTRF_TRSM_SYRK_GEMM" in text
    for name in ("POTRF", "TRSM", "SYRK", "GEMM"):
        assert f"ptg_region_POTRF_TRSM_SYRK_GEMM)/{name}/" in text


def _hist(field):
    return {k: v[field] for k, v in H.histograms.snapshot().items()}


def test_inflight_and_region_tasks_record_where_hist_enabled_says_so():
    """``ptexec.region_tasks``: one record a fused region a pool binds, its
    members. ``ptdev.inflight``: one record a ``dispatch`` callback, the
    programs in flight before it. ``ptdev.stage_in_ns``: the lower tiles
    handed in as numpy, each staged in once."""
    mca.set("hist_enabled", True)
    mca.set("device_tpu_over_cpu", True)
    try:
        c = Context(nb_cores=1)
        assert c._spans is not None
        n0, s0 = _hist("count"), _hist("sum_ns")
        a, A = _matrix(12, seed=1)
        _assert_factor(_factor(c, A), a)
        n1, s1 = _hist("count"), _hist("sum_ns")

        def delta(snap1, snap0, key):
            return snap1.get(key, 0) - snap0.get(key, 0)
        assert delta(n1, n0, "ptexec.region_tasks") == 3
        assert delta(s1, s0, "ptexec.region_tasks") == ntasks(12)
        assert 1 <= delta(n1, n0, "ptdev.inflight") <= 3
        assert delta(n1, n0, "ptdev.inflight") == delta(n1, n0, "ptdev.pins")
        # over a host device a program is complete at once: never more in
        # flight than the pool has programs
        assert 0 <= delta(s1, s0, "ptdev.inflight") <= 3
        assert delta(n1, n0, "ptdev.stage_in_ns") == 12 * 13 // 2
        c.fini()
    finally:
        mca.params.unset("hist_enabled")
        mca.params.unset("device_tpu_over_cpu")
    assert "inflight" in H.HIST_NAMES["ptdev"]
    assert "region_tasks" in H.HIST_NAMES["ptexec"]


def test_the_new_histograms_record_nothing_with_the_spans_off(dctx):
    assert dctx._spans is None
    n0 = _hist("count")
    a, A = _matrix(5, seed=2)
    _assert_factor(_factor(dctx, A), a)
    n1 = _hist("count")
    for key in ("ptdev.inflight", "ptexec.region_tasks",
                "ptdev.stage_in_ns"):
        assert n1.get(key, 0) == n0.get(key, 0)


# ------------------------------------------- donation (ISSUE 34): the plan

class _PlanOnly(Exception):
    pass


def _plan_without_running(monkeypatch, ctx, prog, nt, A):
    """Instantiate and lower as far as the fusion plan: nothing is bound
    to a lane, nothing traced, nothing compiled."""
    def stop(*_a, **_k):
        raise _PlanOnly
    monkeypatch.setattr(C.PTGTaskpool, "_ptexec_lane_fused", stop)
    tp = prog.instantiate(ctx, globals={"NT": nt, **TILE_FNS},
                          collections={"descA": A})
    with pytest.raises(_PlanOnly):
        tp._ptexec_prepare(set())
    monkeypatch.undo()
    return _plan_of(prog, nt)


def test_the_benchmark_cells_plan_donates_every_update_chain(dctx,
                                                             monkeypatch):
    """``ptg_potrf.ts512``'s pool (NT = 32, the plan only): of the 6,562
    operands its 47 region programs take, 5,158 are slots the region is
    the last reader of, each the head of an update chain of the JDF (the
    ``RW C`` of GEMM from the GEMM before it, the ``RW T`` of SYRK, TRSM's
    ``C``, POTRF's ``T``); 968 of the 6,130 arrays a solve's programs
    return are then new buffers. The first four regions hold every memory
    read of the pool's head and donate nothing; a memory operand, a slot
    with a write-back or with a second reader is never among the donated,
    and every donated operand has an output of its own to become."""
    nt = 32
    A = TiledMatrix("A32", nt * TS, nt * TS, TS, TS)
    prog = compile_ptg(ops.POTRF_JDF, "potrf")
    plan = _plan_without_running(monkeypatch, dctx, prog, nt, A)
    regs = plan["regions"]
    assert len(regs) == 47 and {r["kind"] for r in regs} == {"dev"}
    assert sum(len(r["ext"]) for r in regs) == 6562
    assert sum(len(r["ext_mems"]) for r in regs) == 528
    assert _donated_and_returned(plan) == (5158, 6130)
    given = _donated(plan)
    assert given[:5] == [0, 0, 0, 0, 112]
    assert all(120 <= nd <= 128 for nd in given[5:42])
    assert given[42:] == [105, 91, 66, 55, 28]
    (ent,) = prog._ptexec_cache.values()
    data = ent["flat"]["data"]
    written_back = {data["slot_base"][tid] + dj
                    for tid, dj, _dc, _ix in data["writebacks"]}
    taken = set()
    for r, nd in zip(regs, given):
        shape = plan["shapes"][r["shape"]]
        lead = r["ext"][:nd]
        assert all(kind == "slot" for kind, _v in lead)
        assert not any(kind == "slot" and plan["slot_uses"][v] == 1
                       and v not in written_back
                       for kind, v in r["ext"][nd:]), \
            "an operand with one reader and no write-back was kept"
        for _kind, v in lead:
            assert plan["slot_uses"][v] == 1 and v not in written_back
            assert v not in taken
            taken.add(v)
        # each donated operand is paired with an output of its own, and
        # those lead what the program returns
        first, rest = shape["ret"]
        if nd:
            assert len(first) == len(set(first)) == nd
        assert len(first) + len(rest) == \
            len(r["out_slots"]) + len(r["wb_keys"])
        assert sorted(shape["out_pos"] + shape["wb_pos"]) == \
            list(range(len(first) + len(rest)))
        # a shape that donates nothing keeps the key it always had
        assert len(shape["sig"]) == (4 if nd else 3)
    assert len(plan["shapes"]) == 47


# small JDFs: a chain S(0) .. S(N-1) over one tile, cut into regions of two
_HEAD = "%global N\n%global descX\n%global descY\n"


def _chain(flow="", dep="", body="X = X + 1.0", where=" [type=TPU]",
           more=""):
    return (_HEAD + "S(k)\n  k = 0 .. N-1\n  : descX(0, 0)\n" + flow +
            "  RW X <- (k == 0) ? descX(0, 0) : X S(k-1)\n"
            "       -> (k < N-1) ? X S(k+1) : descX(0, 0)\n" + dep +
            f"BODY{where}\n  {body}\nEND\n" + more)


_READER = ("R(k)\n  k = 0 .. N-1\n  : descY(0, k)\n  READ V <- X S(k)\n"
           "  RW W <- descY(0, k)\n       -> descY(0, k)\n"
           "BODY [type=TPU]\n  W = W + V\nEND\n")


def _run_chain(dctx, src, name, n=6):
    X = TiledMatrix("X", TS, TS, TS, TS)
    X.fill(lambda m, k: np.zeros((TS, TS), np.float32))
    Y = TiledMatrix("Y", TS, n * TS, TS, TS)
    Y.fill(lambda m, k: np.full((TS, TS), float(k), np.float32))
    mca.set("region_fusion_max", 2)
    try:
        prog = compile_ptg(src, name)
        d0 = PTDEV_STATS.snapshot()
        with warnings.catch_warnings(record=True) as seen:
            warnings.simplefilter("always")
            tp = prog.instantiate(dctx, globals={"N": n},
                                  collections={"descX": X, "descY": Y})
            dctx.add_taskpool(tp)
            dctx.wait(timeout=120)
        assert tp.completed and counters.read("ptdev.cb_errors") == 0
        assert not [w for w in seen if "onat" in str(w.message)], \
            [str(w.message) for w in seen]
    finally:
        mca.params.unset("region_fusion_max")
    (ent,) = prog._ptexec_cache.values()
    x = np.asarray(X.data_of(0, 0).newest_copy().payload)
    return ent["fusion"], PTDEV_STATS.delta(d0), x, Y


@pytest.mark.parametrize("what", ["chain", "memory", "write-back",
                                  "second reader", "host body"])
def test_what_a_region_never_donates(dctx, what):
    """The chain alone: each region after the first is the one reader of
    the slot the region before it wrote, and takes it. Then the four
    refusals: a memory operand beside it is the residency table's; a slot
    also written back belongs to its ``Data`` as well; a slot with a
    second reader is still needed; a region on the host donates nothing."""
    src = {"chain": _chain(),
           "memory": _chain(flow="  READ M <- descY(0, k)\n",
                            body="X = X + M"),
           "write-back": _chain(dep="       -> descY(0, k)\n"),
           "second reader": _chain(dep="       -> V R(k)\n", more=_READER),
           "host body": _chain(where="")}[what]
    plan, dd, x, Y = _run_chain(dctx, src, "s-" + what.replace(" ", "-"))
    regions = plan["regions"]
    donates = what in ("chain", "memory")
    assert _donated(plan)[:3] == ([0, 1, 1] if donates else [0, 0, 0])
    assert dd["donated"] == (2 if donates else 0)
    for r, nd in zip(regions, _donated(plan)):
        assert all(k == "slot" for k, _v in r["ext"][:nd])
    if what == "memory":
        assert [len(r["ext_mems"]) for r in regions] == [3, 2, 2]
        np.testing.assert_array_equal(x, np.full((TS, TS), 15.0))
    else:
        np.testing.assert_array_equal(x, np.full((TS, TS), 6.0))
    if what == "write-back":
        assert dd["region_outputs"] == 9     # 2 slots + 7 write-backs
        for k in range(6):
            np.testing.assert_array_equal(
                np.asarray(Y.data_of(0, k).newest_copy().payload), k + 1.0)
    if what == "second reader":
        assert len(regions) == 6 and dd["region_outputs"] == 13
        for k in range(6):      # R(k) read S(k)'s X, which S(k+1) read too
            np.testing.assert_array_equal(
                np.asarray(Y.data_of(0, k).newest_copy().payload),
                2.0 * k + 1.0)
    if what == "host body":
        assert {r["kind"] for r in regions} == {"cpu"}
        assert dd["region_outputs"] == 0


def test_a_body_that_returns_another_shape_is_run_undonated(dctx):
    """The plan pairs an operand with the output its chain ends in by
    structure; the program's own trace sees the shapes. A body that
    doubles its tile leaves no output a donated operand could become: the
    shape runs undonated, without JAX's warning, and counts nothing."""
    plan, dd, x, _Y = _run_chain(
        dctx, _chain(body="X = jnp.concatenate([X, X + 1.0])"), "s-grows")
    assert _donated(plan) == [0, 1, 1]          # the plan's offer
    assert dd["donated"] == 0 and dd["region_outputs"] == 3
    assert x.shape == (TS * 2 ** 6, TS) and x[-1, 0] == 6.0 and x[0, 0] == 0.0


def test_after_a_solve_every_donated_operand_is_gone(dctx, monkeypatch):
    """NT = 12: the 83 slot values the second and third regions take are
    deleted arrays once their program has been called, no slot still holds
    one of them when the pool is finalized, and what the slots do hold is
    alive."""
    taken, left = [], []
    timed = C._timed_region_program

    def spy(fn, n_members):
        def call(donated, kept):
            taken.extend(donated)
            return fn(donated, kept)
        return timed(call, n_members)
    monkeypatch.setattr(C, "_timed_region_program", spy)
    finalize = C.PTGTaskpool._ptexec_finalize

    def snapshot(self, lane):
        left.extend(v for v in lane["slots"] if v is not None)
        return finalize(self, lane)
    monkeypatch.setattr(C.PTGTaskpool, "_ptexec_finalize", snapshot)
    prog = compile_ptg(ops.POTRF_JDF, "potrf")
    a, A = _matrix(12, seed=5)
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        _assert_factor(_factor(dctx, A, prog), a)
    assert not [w for w in seen if "onat" in str(w.message)]
    assert len(taken) == 83 and all(v.is_deleted() for v in taken)
    gone = {id(v) for v in taken}
    assert left and not any(id(v) in gone or v.is_deleted() for v in left)
    # 178 arrays came back, 83 of them in a donated operand's buffer: the
    # slots hold the other 17 of the 100 handed on, and the last region's
    assert len(left) == 100 - 83


def test_the_benchmarks_reader_gives_the_share_or_nothing(monkeypatch):
    """``chipbench/layers/donated_share.py`` reads the lane's two counts:
    the cell's 5,158 of 6,130, 0 where nothing is donated, and nothing to
    read where no region program ran or the program has no such counters
    (the parent commit under this benchmark)."""
    import importlib
    import os
    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    reader = importlib.import_module("chipbench.layers.donated_share")
    monkeypatch.setitem(PTDEV_STATS, "donated", 5158)
    monkeypatch.setitem(PTDEV_STATS, "region_outputs", 6130)
    assert reader.read(None) == pytest.approx(84.1436, abs=1e-4)
    monkeypatch.setitem(PTDEV_STATS, "donated", 0)
    assert reader.read(None) == 0.0
    monkeypatch.setitem(PTDEV_STATS, "region_outputs", 0)
    assert reader.read(None) is None
    monkeypatch.delitem(PTDEV_STATS, "region_outputs")
    monkeypatch.delitem(PTDEV_STATS, "donated")
    assert reader.read(None) is None


# --------------------------------- release at dispatch (ISSUE 36): the plan

@pytest.mark.parametrize("nt, nodes, programs", [(12, 3, 3), (32, 47, 47)])
def test_the_plan_releases_every_region_with_device_successors_only(
        dctx, monkeypatch, nt, nodes, programs):
    """The POTRF JDF's plan (the plan only): a region qualifies for
    release at dispatch when it has successors and every one of them is a
    device region of the pool; the sink does not. At NT = 32 that is 46
    of the 47, node 46 the one sink, 1 to 5 distinct successors a node.
    The finding is no part of a region's shape: the executables are the
    parent's 47 (3 at NT = 12), and no shape's key knows of it."""
    A = TiledMatrix(f"A{nt}", nt * TS, nt * TS, TS, TS)
    prog = compile_ptg(ops.POTRF_JDF, "potrf")
    plan = _plan_without_running(monkeypatch, dctx, prog, nt, A)
    early, mask = plan["dev_early"], plan["dev_mask"]
    off, succs = plan["off"], plan["succs"]
    assert len(early) == len(mask) == nodes and all(mask)
    for i in range(nodes):
        after = set(succs[off[i]:off[i + 1]])
        assert early[i] == (1 if after and all(mask[t] for t in after)
                            else 0)
        assert len(after) <= 5 and i not in after
    sinks = [i for i in range(nodes) if off[i] == off[i + 1]]
    assert sinks == [nodes - 1] and not early[nodes - 1]
    assert sum(early) == nodes - 1
    assert len(plan["shapes"]) == programs
    assert C._released_at_dispatch(mask, off, succs) == early
    # a host-bodied or CTL successor (a node outside the mask) refuses it
    host = [1] * nodes
    host[nodes - 1] = 0
    refused = C._released_at_dispatch(host, off, succs)
    assert all(not refused[i] for i in range(nodes)
               if nodes - 1 in succs[off[i]:off[i + 1]])
    assert not refused[nodes - 1]


def _tpu_dev(ctx):
    from parsec_tpu.device.tpu import TPUDevice
    return [d for d in ctx.devices.devices if isinstance(d, TPUDevice)][0]


def _spy_lane(monkeypatch, before_dispatch=None, after_poll=None):
    """Wrap the pool's closures as the lane receives them."""
    from parsec_tpu.device import lane_pool
    make = lane_pool._closures

    def spied(*args, **kw):
        dispatch, poll, drop, held = make(*args, **kw)

        def spy_dispatch(ids):
            if before_dispatch is not None:
                before_dispatch(list(ids))
            return dispatch(ids)

        def spy_poll():
            done = poll()
            if after_poll is not None:
                after_poll(list(done), held)
            return done
        return spy_dispatch, spy_poll, drop, held
    monkeypatch.setattr(lane_pool, "_closures", spied)


def _late_is_ready(monkeypatch, dctx, asks=3):
    """Every array reads complete only from its ``asks``-th ask on, so a
    program stays in flight over several polls; returns the arrays asked
    about after they were deleted (there must be none) and the ids of
    those that have read complete."""
    import jax
    dev = _tpu_dev(dctx)
    kind = type(jax.device_put(np.zeros(1, np.float32), dev.jax_device))
    real = kind.is_ready
    left, deleted, seen = {}, [], set()

    def is_ready(array):
        if array.is_deleted():
            deleted.append(array)
            return real(array)
        ent = left.setdefault(id(array), [array, asks])
        ent[1] -= 1
        if ent[1] < 0 and real(array):
            seen.add(id(array))
            return True
        return False
    monkeypatch.setattr(kind, "is_ready", is_ready)
    return deleted, seen


def _assert_pins_given_back(dctx, mats):
    dev = _tpu_dev(dctx)
    for M in mats:
        for m in range(M.mt):
            for n in range(M.nt):
                data = M.data_of(m, n)
                if dev._ncoh is not None:
                    st = dev._ncoh.state(dev.res_key(data))
                    assert st is None or st[3] == 0, (M.name, m, n, st)
                assert all(c.readers == 0 for c in data.copies.values())


@pytest.mark.parametrize("path", ["regions", "per-task"])
def test_a_factorization_released_at_dispatch(dctx, monkeypatch, path):
    """The Cholesky with its regions (12 of <= 16 tasks at NT = 8) or its
    tasks (35 at NT = 5) released at dispatch, each program held in flight
    over several polls: the factor is the reference's and the DTD twin's,
    the lane released what the plan said it would, and after ``ctx.wait()``
    nothing is in flight: ``dev_held`` empty, every table pin given back."""
    nt = {"regions": 8, "per-task": 5}[path]
    knob, value = {"regions": ("region_fusion_max", 16),
                   "per-task": ("region_fusion", False)}[path]
    deleted, _seen = _late_is_ready(monkeypatch, dctx)
    flying = []
    _spy_lane(monkeypatch, after_poll=lambda done, held: flying.append(
        len(held)))
    a, A = _matrix(nt, seed=36)
    prog = compile_ptg(ops.POTRF_JDF, f"potrf-early-{path}")
    mca.set(knob, value)
    try:
        d0 = PTDEV_STATS.snapshot()
        tp = prog.instantiate(dctx, globals={"NT": nt, **TILE_FNS},
                              collections={"descA": A})
        dctx.add_taskpool(tp)
        dctx.wait(timeout=300)
        dd = PTDEV_STATS.delta(d0)
    finally:
        mca.params.unset(knob)
    assert tp.completed and counters.read("ptdev.cb_errors") == 0
    assert dctx._ptdev.failed() is None and not deleted
    (ent,) = prog._ptexec_cache.values()
    if path == "regions":
        early = ent["fusion"]["dev_early"]
        assert len(early) >= 8
    else:
        assert ent["fusion"] is None
        mask, ndev, early = ent["flat"]["dev"]
        assert ndev == sum(mask) == ntasks(nt)
    assert dd["programs"] == len(early)
    assert dd["released_early"] == sum(early) == len(early) - 1
    assert tp._ptexec_state["dev_held"] == {} and max(flying) > 0
    _assert_pins_given_back(dctx, [A])
    got = np.tril(np.asarray(A.to_dense()))
    _assert_factor(got, a)
    _a, T = _matrix(nt, seed=36)
    twin = DTDTaskpool(dctx, f"twin-early-{path}")
    assert ops.insert_potrf_tasks(twin, T) == ntasks(nt)
    assert twin.wait(timeout=300)
    twin.close()
    dctx.wait(timeout=300)
    np.testing.assert_allclose(got, np.tril(np.asarray(T.to_dense())),
                               rtol=0, atol=2e-5)


def _run_early_chain(dctx, src, name, n=6, x0=0.0, y0=float, fused=True):
    X = TiledMatrix("X", TS, TS, TS, TS)
    X.fill(lambda m, k: np.full((TS, TS), x0, np.float32))
    Y = TiledMatrix("Y", TS, n * TS, TS, TS)
    Y.fill(lambda m, k: np.full((TS, TS), y0(k), np.float32))
    knob, value = ("region_fusion_max", 2) if fused \
        else ("region_fusion", False)
    mca.set(knob, value)
    try:
        prog = compile_ptg(src, name)
        d0 = PTDEV_STATS.snapshot()
        tp = prog.instantiate(dctx, globals={"N": n},
                              collections={"descX": X, "descY": Y})
        dctx.add_taskpool(tp)
        dctx.wait(timeout=120)
        dd = PTDEV_STATS.delta(d0)
    finally:
        mca.params.unset(knob)
    assert tp.completed and counters.read("ptdev.cb_errors") == 0
    assert tp._ptexec_state["dev_held"] == {}
    _assert_pins_given_back(dctx, [X, Y])
    (ent,) = prog._ptexec_cache.values()
    return ent, dd, np.asarray(X.data_of(0, 0).newest_copy().payload), Y


_HOST_READER = _READER.replace("BODY [type=TPU]", "BODY")


@pytest.mark.parametrize("reader", ["host", "device"])
def test_a_producer_with_a_host_bodied_reader_retires_when_complete(
        dctx, monkeypatch, reader):
    """``R(k)`` reads ``S(k)``'s result beside ``S(k+1)``. On the host it
    keeps every ``S`` from being released at dispatch, and each ``R`` is
    handed a value that has read complete; on the device every ``S`` but
    the last is released early (its readers are device tasks all)."""
    _deleted, seen = _late_is_ready(monkeypatch, dctx)
    handed = []
    make = C.PTGTaskpool._mk_ptexec_data_callback

    def spied(self, flat, classes, slots, *args, **kw):
        run_batch = make(self, flat, classes, slots, *args, **kw)
        data = flat["data"]

        def spy_batch(ids, retired):
            for i in ids:       # host tasks: R(k), V its first flow
                v = slots[data["in_refs"][data["slot_base"][i]]]
                handed.append(id(v) in seen)
            return run_batch(ids, retired)
        return spy_batch
    monkeypatch.setattr(C.PTGTaskpool, "_mk_ptexec_data_callback", spied)
    src = _chain(dep="       -> V R(k)\n",
                 more=_HOST_READER if reader == "host" else _READER)
    ent, dd, x, Y = _run_early_chain(dctx, src, f"early-{reader}-reader",
                                     fused=False)
    mask, ndev, early = ent["flat"]["dev"]
    if reader == "host":
        assert ndev == 6 and sum(early) == 0 == dd["released_early"]
        assert dd["programs"] == 6
        assert handed == [True] * 6
    else:
        # S(k) -> S(k+1), R(k): released; S(5) -> R(5): released too; the
        # six R are sinks
        assert ndev == 12 and sum(early) == 6 == dd["released_early"]
        assert dd["programs"] == 12 and not handed
    np.testing.assert_array_equal(x, np.full((TS, TS), 6.0))
    for k in range(6):
        np.testing.assert_array_equal(
            np.asarray(Y.data_of(0, k).newest_copy().payload), 2.0 * k + 1.0)


def test_a_region_whose_outputs_are_all_donated_on_settles_by_the_queue(
        dctx, monkeypatch):
    """A chain of regions each of which hands its one output to the next
    for good: a released region's only completion witness is deleted by its
    successor's call. It settles when a later program is seen complete,
    and no deleted array is ever asked ``is_ready``."""
    deleted, _seen = _late_is_ready(monkeypatch, dctx, asks=5)
    passes = []
    _spy_lane(monkeypatch,
              after_poll=lambda done, held: passes.append(list(done)))
    ent, dd, x, _Y = _run_early_chain(dctx, _chain(), "early-donated-on")
    plan = ent["fusion"]
    assert _donated(plan) == [0, 1, 1] and plan["dev_early"] == [1, 1, 0]
    assert dd["donated"] == 2 and dd["released_early"] == 2
    assert dd["programs"] == 3 and not deleted
    # each released region was reported at the poll after its dispatch
    assert [p for p in passes if p] == [[0], [1], [2]]
    np.testing.assert_array_equal(x, np.full((TS, TS), 6.0))


def test_a_released_regions_write_back_is_what_its_successor_stages_in(
        dctx, monkeypatch):
    """``S(k)`` writes ``descY(0, k)`` back and ``S(k+1)`` reads it from
    memory. Released at dispatch, a region's write-backs land before the
    engine hears of it: when the next region is dispatched (its
    predecessor still in flight) every tile the predecessor wrote is at
    its new version, and the stage-in adopts the array."""
    _deleted, seen = _late_is_ready(monkeypatch, dctx, asks=5)
    versions, box = [], {}

    def before(ids):
        versions.append((ids, [box["Y"].data_of(0, k).version
                               for k in range(6)]))
    _spy_lane(monkeypatch, before_dispatch=before)
    real_fill = TiledMatrix.fill

    def fill(self, fn):
        if self.name == "Y":
            box["Y"] = self
        return real_fill(self, fn)
    monkeypatch.setattr(TiledMatrix, "fill", fill)
    dev = _tpu_dev(dctx)
    adopted = dev.adopted
    src = _chain(flow="  READ M <- (k == 0) ? descY(0, 0) : descY(0, k-1)\n",
                 dep="       -> descY(0, k)\n", body="X = X + M")
    ent, dd, x, Y = _run_early_chain(dctx, src, "early-write-back", x0=1.0,
                                     y0=lambda k: 10.0 * (k + 1))
    assert ent["fusion"]["dev_early"] == [1, 1, 0]
    assert dd["released_early"] == 2
    v0 = versions[0][1]
    assert [ids for ids, _v in versions] == [[0], [1], [2]]
    # region i wrote tiles 2i and 2i + 1 (the last X also goes to descX)
    assert [[b - a for a, b in zip(v0, v)] for _ids, v in versions] == [
        [0] * 6, [1, 1, 0, 0, 0, 0], [1, 1, 1, 1, 0, 0]]
    assert dev.adopted - adopted >= 2
    # 1 + 10 = 11, then doubled by each S(k) that reads what S(k-1) wrote
    np.testing.assert_array_equal(x, np.full((TS, TS), 352.0))
    for k in range(6):
        np.testing.assert_array_equal(
            np.asarray(Y.data_of(0, k).newest_copy().payload),
            11.0 * 2 ** k)


def test_the_benchmarks_reader_gives_the_early_share_or_nothing(monkeypatch):
    """``chipbench/layers/early_release_share.py``: the cell's 46 of 47, 0
    where no program has a successor, and nothing to read where no program
    ran or the program has no such counters (the parent commit under this
    benchmark)."""
    import importlib
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    reader = importlib.import_module("chipbench.layers.early_release_share")
    monkeypatch.setitem(PTDEV_STATS, "programs", 47)
    monkeypatch.setitem(PTDEV_STATS, "released_early", 46)
    assert reader.read(None) == pytest.approx(97.8723, abs=1e-4)
    monkeypatch.setitem(PTDEV_STATS, "released_early", 0)
    assert reader.read(None) == 0.0
    monkeypatch.setitem(PTDEV_STATS, "programs", 0)
    assert reader.read(None) is None
    monkeypatch.delitem(PTDEV_STATS, "programs")
    monkeypatch.delitem(PTDEV_STATS, "released_early")
    assert reader.read(None) is None
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == "early_release_share"]
    assert entry == {"name": "early_release_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "device issue", "moves": "tasks_per_s",
                     "workloads": ["ptg_gemm.ts512", "ptg_potrf.ts512"]}


@pytest.mark.parametrize("path", ["regions", "per-task"])
def test_no_program_of_a_round_reads_what_another_writes_back(
        dctx, monkeypatch, path):
    """A round pushes and calls program by program, so the write-backs of
    a program released at dispatch land before a later program of the
    same round takes its decisions. That changes nothing the later one
    reads: the programs of a round were all ready when it began, so none
    reads from memory a datum that another of them writes back. Checked on
    every ``dispatch`` callback of the factorization (a program a task:
    released programs that write back share rounds with others); the
    factor is the reference's."""
    from parsec_tpu.device import lane_pool
    nt = {"regions": 8, "per-task": 5}[path]
    knob, value = {"regions": ("region_fusion_max", 16),
                   "per-task": ("region_fusion", False)}[path]
    make, pools, rounds = lane_pool._closures, [], []

    def spied(*args):
        dispatch, poll, drop, held = make(*args)
        pools.append(args)
        n = len(pools) - 1

        def spy_dispatch(ids):
            rounds.append((n, list(ids)))
            return dispatch(ids)
        return spy_dispatch, poll, drop, held
    monkeypatch.setattr(lane_pool, "_closures", spied)

    def reads_writes(args, i):
        (slot_base, in_refs, ndflows, cls_of, mem_datas, writebacks,
         fusion) = (args[4], args[5], args[6], args[7], args[12], args[13],
                    args[14])
        if fusion is not None:
            r = fusion["dev_regions"].get(i)
            if r is not None:
                return ({id(mem_datas[m]) for m in r["ext_mems"]},
                        {id(d) for _p, d in r["wb_pairs"]})
            i = fusion["orig_of"][i]
        base = slot_base[i]
        return ({id(mem_datas[-2 - r])
                 for r in in_refs[base:base + ndflows[cls_of[i]]] if r < -1},
                {id(d) for _p, d in writebacks.get(i, ())})
    a, A = _matrix(nt, seed=41)
    prog = compile_ptg(ops.POTRF_JDF, f"potrf-rounds-{path}")
    mca.set(knob, value)
    try:
        got = _factor(dctx, A, prog)
    finally:
        mca.params.unset(knob)
    assert counters.read("ptdev.cb_errors") == 0
    shared = 0
    for n, ids in rounds:
        args = pools[n]
        rw = [reads_writes(args, i) for i in ids]
        for k, (_r, writes) in enumerate(rw):
            for j, (reads, _w) in enumerate(rw):
                assert j == k or not reads & writes, (ids[k], ids[j])
        early = args[17] or ()
        shared += any(early[i] and rw[k][1] for k, i in enumerate(ids[:-1]))
    if path == "per-task":
        assert shared > 0, "no round holds a released writer before another"
    _assert_pins_given_back(dctx, [A])
    _assert_factor(got, a)


@pytest.mark.parametrize("path", ["regions", "per-task"])
def test_the_lane_counts_the_tiles_it_moves_and_the_puts_that_move_them(
        dctx, monkeypatch, path):
    """ISSUE 38: a program's push moves its misses in one ``device_put``.
    Over a factorization the lane moves the lower triangle's tiles, each
    once, in at least one put a callback that moved bytes and at most one a
    program, and the factor is the reference's."""
    from parsec_tpu.device import lane_pool
    nt = {"regions": 8, "per-task": 5}[path]
    knob, value = {"regions": ("region_fusion_max", 16),
                   "per-task": ("region_fusion", False)}[path]
    dev = _tpu_dev(dctx)
    make, moved = lane_pool._closures, []

    def spied(*args, **kw):
        dispatch, poll, drop, held = make(*args, **kw)

        def spy_dispatch(ids):
            before = dev.transfer_in_bytes
            try:
                return dispatch(ids)
            finally:
                moved.append(dev.transfer_in_bytes - before)
        return spy_dispatch, poll, drop, held
    monkeypatch.setattr(lane_pool, "_closures", spied)
    a, A = _matrix(nt, seed=38)
    prog = compile_ptg(ops.POTRF_JDF, f"potrf-puts-{path}")
    mca.set(knob, value)
    try:
        d0 = PTDEV_STATS.snapshot()
        got = _factor(dctx, A, prog)
        dd = PTDEV_STATS.delta(d0)
    finally:
        mca.params.unset(knob)
    assert counters.read("ptdev.cb_errors") == 0
    tiles = nt * (nt + 1) // 2
    assert dd["staged_tiles"] == tiles
    assert sum(moved) == tiles * TS * TS * 4
    # a round pushes program by program: puts >= the callbacks that moved
    # bytes, and <= the programs that had a miss (each tile is one
    # program's miss, and a program reads several in the regions path)
    assert 1 <= sum(1 for b in moved if b) <= dd["stage_in_puts"]
    assert dd["stage_in_puts"] <= min(dd["programs"], tiles)
    if path == "regions":
        assert dd["stage_in_puts"] < tiles
    _assert_pins_given_back(dctx, [A])
    _assert_factor(got, a)
