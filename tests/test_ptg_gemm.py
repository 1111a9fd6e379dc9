"""BASELINE config 2 through the PTG front end (ISSUE 29): ``ex06``'s JDF
through ``ptexec`` + region fusion + ``ptdev`` (the device module over a
host device), against the plain reference ``ops/gemm.py:gemm_reference``.
A region's executable is built once per *shape* of region, a task parameter
enters the shape only where the body names it, sibling k-chains that share
operands pack into one region up to ``region_fusion_max`` tasks (ISSUE 32),
and the path's spans record where ``hist_enabled`` says so. Counts and results only: no test here reads
a clock."""

import os
import sys

import numpy as np
import pytest

from parsec_tpu import native as native_mod
from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.device.native import PTDEV_STATS
from parsec_tpu.dsl.fusion import CAPTURE_CACHE_STATS
from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS, compile_ptg
from parsec_tpu.ops.gemm import gemm_reference
from parsec_tpu.utils import hist as H
from parsec_tpu.utils import mca
from parsec_tpu.utils.counters import counters

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples"))
import ex06_gemm_ptg  # noqa: E402

pytestmark = pytest.mark.skipif(
    native_mod.load_ptexec() is None or native_mod.load_ptdev() is None,
    reason="native _ptexec/_ptdev unavailable")

TS = 16


@pytest.fixture()
def dctx():
    mca.set("device_tpu_over_cpu", True)
    c = Context(nb_cores=1)
    yield c
    c.fini()
    mca.params.unset("device_tpu_over_cpu")


def _operands(mt, nt, kt, ts=TS, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((mt * ts, kt * ts)).astype(np.float32)
    b = rng.standard_normal((kt * ts, nt * ts)).astype(np.float32)
    A = TiledMatrix("A", mt * ts, kt * ts, ts, ts)
    B = TiledMatrix("B", kt * ts, nt * ts, ts, ts)
    C = TiledMatrix("C", mt * ts, nt * ts, ts, ts)
    A.fill(lambda m, k: a[m * ts:(m + 1) * ts, k * ts:(k + 1) * ts])
    B.fill(lambda k, n: b[k * ts:(k + 1) * ts, n * ts:(n + 1) * ts])
    C.fill(lambda m, n: np.zeros((ts, ts), np.float32))
    return a, b, (A, B, C)


@pytest.fixture()
def fusion_max():
    """Set the fusion pass's bound on a region's tasks (the packing rule's
    only limit) for a test; back to the default after it."""
    yield lambda n: mca.set("region_fusion_max", n)
    mca.params.unset("region_fusion_max")


def _solve(ctx, prog, mats, mt, nt, kt):
    A, B, C = mats
    tp = prog.instantiate(ctx, globals={"MT": mt, "NT": nt, "KT": kt},
                          collections={"descA": A, "descB": B, "descC": C})
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    assert tp.completed
    return C.to_dense()


class _JaxWork:
    """Counts what JAX traces and compiles while ``on``."""

    def __init__(self):
        import jax
        self.on, self.traces, self.compiles = True, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, _secs, **_kw):
        if self.on and event.endswith("jaxpr_trace_duration"):
            self.traces += 1
        if self.on and event.endswith("backend_compile_duration"):
            self.compiles += 1


def test_ex06_through_the_lanes_against_the_reference(dctx):
    """MT = NT = KT = 4: 64 tasks in 16 fused k-chains that share their A
    and B tiles, so one pack of 16 chains (64 tasks, under the bound of
    128) on the device lane, ONE region program; a second and a third
    instantiation build, trace and load nothing."""
    a, b, mats = _operands(4, 4, 4)
    prog = compile_ptg(ex06_gemm_ptg.SRC, "gemm")
    assert prog.body_names["GEMM"] >= {"A", "B", "C"}
    assert not prog.body_names["GEMM"] & {"m", "n", "k", "MT", "NT", "KT"}
    work = _JaxWork()
    try:
        for solves in (1, 2, 3):
            x0, d0 = PTEXEC_STATS.snapshot(), PTDEV_STATS.snapshot()
            before = (work.traces, work.compiles)
            got = _solve(dctx, prog, mats, 4, 4, 4)
            dx, dd = PTEXEC_STATS.delta(x0), PTDEV_STATS.delta(d0)
            assert dx["pools_engaged"] == 1 and dx["tasks_engaged"] == 64
            assert dx["pools_fallback"] == dx["pools_ineligible"] == 0
            assert dd["pools_engaged"] == 1 and dd["tasks_engaged"] == 64
            assert dd["pools_fallback"] == dd["pools_ineligible"] == 0
            assert dx["fused_regions"] == 1 and dx["fused_tasks"] == 64
            assert dx["packed_regions"] == 16
            assert dx["region_programs"] == (1 if solves == 1 else 0)
            if solves > 1:
                assert (work.traces, work.compiles) == before
            ref = np.asarray(gemm_reference(a, b, np.zeros_like(got), solves))
            np.testing.assert_allclose(got, ref, rtol=0, atol=1e-4 * solves)
        assert counters.read("ptdev.cb_errors") == 0
        cache = prog.region_programs
        assert (len(cache), cache.misses, cache.hits, cache.evictions) == \
            (1, 1, 2, 0)
    finally:
        work.on = False


@pytest.mark.parametrize("bound, packs, packed, programs", [
    (2, 200, 0, 1),     # a chain fills the bound: 200 regions, one shape
    (8, 50, 200, 2),    # packs of four: a row of ten is 4 + 4 + a 2 x 2
                        # block with the row under it, so two shapes
])
def test_two_hundred_equal_regions_are_few_programs_and_evict_nothing(
        dctx, fusion_max, bound, packs, packed, programs):
    """200 C tiles, so 200 structurally equal k-chains: keyed by region index
    they walked the 128-entry LRU in order and a second instantiation hit
    nothing; keyed by shape there is one entry a shape and no eviction,
    packed or not."""
    mt, nt, kt, ts = 20, 10, 2, 8
    fusion_max(bound)
    a, b, mats = _operands(mt, nt, kt, ts)
    prog = compile_ptg(ex06_gemm_ptg.SRC, "gemm")
    c0, x0 = CAPTURE_CACHE_STATS.snapshot(), PTEXEC_STATS.snapshot()
    for solves in (1, 2):
        got = _solve(dctx, prog, mats, mt, nt, kt)
        ref = np.asarray(gemm_reference(a, b, np.zeros_like(got), solves))
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-4 * solves)
    dx, dc = PTEXEC_STATS.delta(x0), CAPTURE_CACHE_STATS.delta(c0)
    assert dx["fused_regions"] == 2 * packs and dx["fused_tasks"] == 2 * 400
    assert dx["packed_regions"] == 2 * packed
    assert dx["region_programs"] == programs
    assert dc == {"cache_hits": programs, "cache_misses": programs,
                  "cache_evictions": 0}
    assert prog.region_programs.evictions == 0


#: ex06's JDF with a body that names its row: the program of a region then
#: depends on ``m``, and on nothing else of (m, n, k)
SRC_READS_M = ex06_gemm_ptg.SRC.replace(
    "C = C + jnp.dot(", "C = C + (m + 1.0) * jnp.dot(")


@pytest.mark.parametrize("bound, programs", [
    (4, 4),         # a chain a region: one program a row, as before packing
    (16, 4),        # a row a pack: its four chains name one m
    (128, 1),       # the whole grid one pack, that names all four
])
def test_a_body_that_names_a_parameter_is_not_merged(dctx, fusion_max,
                                                     bound, programs):
    """One program per distinct shape, and the parameters a body names are
    part of the shape: the MT rows are never one program for all 16 chains
    unless one pack holds them all, and never 16; the answer is right, and
    a second instantiation still builds nothing."""
    assert SRC_READS_M != ex06_gemm_ptg.SRC
    fusion_max(bound)
    a, b, mats = _operands(4, 4, 4)
    prog = compile_ptg(SRC_READS_M, "gemm_m")
    assert "m" in prog.body_names["GEMM"] and "k" not in prog.body_names["GEMM"]
    x0 = PTEXEC_STATS.snapshot()
    got = _solve(dctx, prog, mats, 4, 4, 4)
    assert PTEXEC_STATS.delta(x0)["region_programs"] == programs
    ref = np.asarray(gemm_reference(a, b, np.zeros_like(got))) \
        * np.repeat(np.arange(1.0, 5.0, dtype=np.float32), TS)[:, None]
    np.testing.assert_allclose(got, ref, rtol=0, atol=4e-4)
    x0 = PTEXEC_STATS.snapshot()
    got2 = _solve(dctx, prog, mats, 4, 4, 4)
    assert PTEXEC_STATS.delta(x0)["region_programs"] == 0
    np.testing.assert_allclose(got2, 2 * ref, rtol=0, atol=8e-4)


def test_a_global_enters_the_key_only_where_a_body_names_it(dctx, fusion_max):
    """The GEMM body names no global: a taller grid of the same packs (2 x 2
    blocks of k-chains) reuses the program. A body that names ``NT`` does
    not share across values of ``NT``."""
    prog = compile_ptg(ex06_gemm_ptg.SRC, "gemm")
    assert prog.globals_named is not None
    assert not prog.globals_named & {"MT", "NT", "KT"}
    fusion_max(16)                  # four chains of four
    x0 = PTEXEC_STATS.snapshot()
    for mt, nt in ((2, 2), (4, 2)):
        a, b, mats = _operands(mt, nt, 4, seed=mt)
        got = _solve(dctx, prog, mats, mt, nt, 4)
        np.testing.assert_allclose(
            got, np.asarray(gemm_reference(a, b, np.zeros_like(got))),
            rtol=0, atol=1e-4)
    dx = PTEXEC_STATS.delta(x0)
    assert dx["region_programs"] == 1 and dx["fused_regions"] == 1 + 2
    scaled = compile_ptg(ex06_gemm_ptg.SRC.replace(
        "C = C + jnp.dot(", "C = C + (1.0 / NT) * jnp.dot("), "gemm_nt")
    assert "NT" in scaled.globals_named
    fusion_max(4)                   # two chains of two, whatever NT
    x0 = PTEXEC_STATS.snapshot()
    for nt in (2, 4):               # the same pairs of k-chains, another NT
        a, b, mats = _operands(2, nt, 2, seed=nt)
        got = _solve(dctx, scaled, mats, 2, nt, 2)
        ref = np.asarray(gemm_reference(a, b, np.zeros_like(got))) / nt
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-4)
    dx = PTEXEC_STATS.delta(x0)
    assert dx["region_programs"] == 2 and dx["fused_regions"] == 2 + 4


def test_the_benchmark_cell_is_256_programs_of_164_operands(dctx):
    """``ptg_gemm.ts512``'s pool (NT = 32, tiny tiles here): 1,024 k-chains
    of 32 at the default bound of 128 are 256 packs of the four chains of a
    row, each passing its 32 A tiles once: 164 operands where four programs
    took 4 x 65; one shape, so one executable."""
    nt = 32
    a, b, mats = _operands(nt, nt, nt, ts=4)
    prog = compile_ptg(ex06_gemm_ptg.SRC, "gemm")
    x0 = PTEXEC_STATS.snapshot()
    got = _solve(dctx, prog, mats, nt, nt, nt)
    dx = PTEXEC_STATS.delta(x0)
    assert dx["packed_regions"] == 1024 and dx["fused_regions"] == 256
    assert dx["fused_tasks"] == dx["tasks_device"] == nt ** 3
    assert dx["region_programs"] == 1
    (ent,) = prog._ptexec_cache.values()
    plan = ent["fusion"]
    assert {len(r["ext"]) for r in plan["regions"]} == {164}
    assert {len(r["members"]) for r in plan["regions"]} == {128}
    # a pack is four neighbours of one row: its C tiles, in order
    assert plan["regions"][9]["wb_keys"] == [
        ("descC", (1, n)) for n in (4, 5, 6, 7)]
    np.testing.assert_allclose(
        got, np.asarray(gemm_reference(a, b, np.zeros_like(got))),
        rtol=0, atol=1e-3)


def test_a_pool_with_nothing_to_pack_counts_none(dctx, fusion_max):
    """One k-chain has no sibling, and chains that fill the bound take none:
    ``packed_regions`` stays 0 and the regions are the partition's."""
    prog = compile_ptg(ex06_gemm_ptg.SRC, "gemm")
    for bound, (mt, nt), regions in ((128, (1, 1), 1), (4, (3, 3), 9)):
        fusion_max(bound)
        a, b, mats = _operands(mt, nt, 4)
        x0 = PTEXEC_STATS.snapshot()
        got = _solve(dctx, prog, mats, mt, nt, 4)
        dx = PTEXEC_STATS.delta(x0)
        assert dx["packed_regions"] == 0 and dx["fused_regions"] == regions
        np.testing.assert_allclose(
            got, np.asarray(gemm_reference(a, b, np.zeros_like(got))),
            rtol=0, atol=1e-4)


@pytest.mark.parametrize("body, named", [
    ("C = C + jnp.dot(A, B)", {"A", "B", "C", "jnp"}),
    ("C = C * eval('k')", None),        # reads k without naming it
    ("C = C * locals()['m']", None),
    ("C = = C", None),                  # does not parse alone
])
def test_what_a_body_names(body, named):
    """A body that can look a name up at run time, or does not parse, is
    taken to name everything: its regions share a program only where every
    parameter and global agrees."""
    from parsec_tpu.dsl.ptg.compiler import _names_in
    assert _names_in(body) == named


@pytest.mark.parametrize("bound", [4, 8, 128],
                         ids=["chains", "packs-of-2", "one-pack"])
def test_fused_equals_unfused(dctx, fusion_max, bound):
    """Packed or not, a C tile is the same dependent dots in the same
    order: bit for bit the unfused run's."""
    fusion_max(bound)
    a, b, mats = _operands(4, 4, 4, seed=3)
    x0 = PTEXEC_STATS.snapshot()
    fused = _solve(dctx, compile_ptg(ex06_gemm_ptg.SRC, "gemm"),
                   mats, 4, 4, 4)
    dx = PTEXEC_STATS.delta(x0)
    assert dx["fused_regions"] == {4: 16, 8: 8, 128: 1}[bound]
    assert dx["packed_regions"] == (0 if bound == 4 else 16)
    mca.set("region_fusion", False)
    try:
        _a, _b, mats = _operands(4, 4, 4, seed=3)
        x0 = PTEXEC_STATS.snapshot()
        unfused = _solve(dctx, compile_ptg(ex06_gemm_ptg.SRC, "gemm"),
                         mats, 4, 4, 4)
        dx = PTEXEC_STATS.delta(x0)
        assert dx["fused_regions"] == 0 and dx["tasks_device"] == 64
    finally:
        mca.params.unset("region_fusion")
    np.testing.assert_array_equal(fused, unfused)
    np.testing.assert_allclose(
        fused, np.asarray(gemm_reference(a, b, np.zeros_like(fused))),
        rtol=0, atol=1e-4)


# ------------------------------------------------------------------ the spans

def _counts():
    return {k: v["count"] for k, v in H.histograms.snapshot().items()}


def test_the_path_records_its_spans_where_hist_enabled_says_so():
    """One ``ptg.lower_ns`` record an instantiation; one
    ``ptdev.dispatch_ns`` and one ``ptdev.retire_ns`` a device program (a
    fused region: here a pack of the four k-chains of a row); ``ptdev.stage_in_ns`` the misses of the push phase that
    moved bytes; ``ptdev.pins`` one record a dispatch callback;
    ``ptdev.poll_ns`` the manager's passes."""
    mca.set("device_tpu_over_cpu", True)
    mca.set("hist_enabled", True)
    mca.set("region_fusion_max", 16)
    try:
        n0 = _counts()
        ctx = Context(nb_cores=1)
        assert ctx._spans is not None
        a, b, mats = _operands(4, 4, 4)
        prog = compile_ptg(ex06_gemm_ptg.SRC, "gemm")
        for _ in range(2):
            _solve(ctx, prog, mats, 4, 4, 4)
        n1 = _counts()

        def delta(key):
            return n1.get(key, 0) - n0.get(key, 0)
        assert delta("ptg.lower_ns") == 2
        assert delta("ptdev.dispatch_ns") == delta("ptdev.retire_ns") == 8
        # A, B and C staged in once; in the second solve C's new version is
        # the device array the write-back left: adopted, no byte moved
        assert delta("ptdev.stage_in_ns") == 48
        assert 2 <= delta("ptdev.pins") <= 8
        assert 1 <= delta("ptdev.poll_ns")
        ctx.fini()
    finally:
        mca.params.unset("region_fusion_max")
        mca.params.unset("hist_enabled")
        mca.params.unset("device_tpu_over_cpu")


def test_nothing_is_recorded_with_the_spans_off(dctx):
    assert dctx._spans is None
    n0 = _counts()
    _a, _b, mats = _operands(2, 2, 2)
    _solve(dctx, compile_ptg(ex06_gemm_ptg.SRC, "gemm"), mats, 2, 2, 2)
    n1 = _counts()
    for kind in ("ptg", "ptdev"):
        for name in H.HIST_NAMES[kind]:
            key = f"{kind}.{name}"
            assert n1.get(key, 0) == n0.get(key, 0)


def test_the_new_histograms_are_registered_and_collide_with_nothing():
    """``ptdev.hist.*`` files beside the lane's own ``ptdev.*`` counters
    (``utils/counters.py``) without taking a name one of them has."""
    assert H.HIST_NAMES["ptg"] == ("lower_ns",)
    assert H.HIST_NAMES["ptdev"] == ("dispatch_ns", "stage_in_ns", "poll_ns",
                                     "retire_ns", "pins", "inflight",
                                     "push_ns", "call_ns")
    from parsec_tpu.device.native import COH_COUNTER_KEYS, DEV_COUNTER_KEYS
    taken = set(DEV_COUNTER_KEYS) | set(COH_COUNTER_KEYS) | set(PTDEV_STATS)
    # nor do the gauges of a pool's account, ``ptdev.pool.*`` (ISSUE 37)
    assert not any(k.startswith(("hist", "pool.")) for k in taken)


def _program_as_it_was(shape, fns, written):
    """A region program as PR 33 built it: one tuple of operands in, the
    externally-consumed slots and the write-backs out."""
    def region_program(ext_vals):
        env, wb_vals = {}, []
        for ci, key, srcs, base, nd, wbs in shape["steps"]:
            vals = [env[v] if kk == "int" else ext_vals[v] for kk, v in srcs]
            outs = fns[ci](*key, *vals)
            for oj, dj in enumerate(written[ci]):
                vals[dj] = outs[oj]
            for dj in range(nd):
                env[base + dj] = vals[dj]
            wb_vals.extend(vals[dj] for dj, _mk in wbs)
        return (tuple(env[s] for s in shape["sig"][2]), tuple(wb_vals))
    region_program.__name__ = region_program.__qualname__ = shape["name"]
    return region_program


def test_the_k_chain_plan_donates_nothing_and_keeps_its_program(
        dctx, monkeypatch):
    """The control of ISSUE 34: every operand of ex06's region programs is
    a memory read and every result a write-back, so the plan finds nothing
    to donate: the shape's signature is (kind, steps, outs) as it was, and
    the program lowers to the module PR 33's builder gave, text for text,
    so the persistent compile cache finds the executable it already has."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.dsl.ptg import compiler as C

    built = []
    make = C._mk_region_program
    monkeypatch.setattr(
        C, "_mk_region_program",
        lambda *a, **k: built.append((a, k, make(*a, **k))) or built[-1][2])
    a, b, mats = _operands(4, 4, 4)
    prog = compile_ptg(ex06_gemm_ptg.SRC, "gemm")
    d0 = PTDEV_STATS.snapshot()
    _solve(dctx, prog, mats, 4, 4, 4)
    dd = PTDEV_STATS.delta(d0)
    assert dd["donated"] == 0 and dd["region_outputs"] == 16
    (ent,) = prog._ptexec_cache.values()
    plan = ent["fusion"]
    (shape,) = plan["shapes"]
    (region,) = plan["regions"]
    assert shape["n_donated"] == 0
    assert {k for k, _v in region["ext"]} == {"mem"}
    kind, steps, outs = shape["sig"]
    assert kind == "dev" and steps == shape["steps"] and outs == ()
    # the C flow of the last of each chain's four tasks (three flows a task)
    assert shape["ret"] == ((), tuple(range(11, 192, 12)))
    (((_shape, fns, written, _scopes), kwargs, program),) = built
    assert kwargs == {}             # no donation, so nothing to check
    tile = jax.ShapeDtypeStruct((TS, TS), jnp.float32)
    ext = (tile,) * len(region["ext"])
    now = jax.jit(program).lower((), ext).as_text()
    was = jax.jit(_program_as_it_was(shape, fns, written)).lower(ext).as_text()
    assert "jit_ptg_region_GEMM" in now and now == was
    assert "tf.aliasing_output" not in now and "buffer_donor" not in now
