"""Region fusion + persistent compiled serving graphs (ISSUE 12).

Layers:

* the fusion pass itself (`dsl/fusion.py partition_regions`): unit shapes
  plus a randomized soundness harness — regions must be kind-homogeneous,
  size-bounded, and the condensed graph (regions + seams) must stay a DAG
  (a condensed cycle is a runtime deadlock);
* the packing rule over its regions (`pack_source_regions`, ISSUE 32):
  which siblings share a program, which stay as they were, and the same
  condensed-DAG property over the packed result;
* the C region support (`ptexec.cpp region_bind`): weighted
  completed/pending/done accounting, reset replay, misuse refusals,
  trace_mark;
* the randomized mixed fusable/un-fusable PTG parity harness, fusion
  on vs off (`--mca region_fusion 0/1`): identical completion sets,
  payloads bit-checked against a numpy replay, data versions, seam
  scheduling, engagement-counter gates;
* persistence: cold-vs-warm double instantiation hits the executable
  cache (`capture.cache_hits`) with identical results, and the flatten
  cache key separates placements (the satellite regression);
* DTD capture-defer fusion: a deferred window replays fused runs +
  seams with exact values and engagement counters.
"""

import random

import numpy as np
import pytest

import parsec_tpu as pt
from parsec_tpu import native as native_mod
from parsec_tpu.dsl.fusion import (CAPTURE_CACHE_STATS, ExecCache,
                                   pack_source_regions, partition_regions,
                                   topo_order)
from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS, compile_ptg
from parsec_tpu.utils import mca

pytestmark = pytest.mark.skipif(native_mod.load_ptexec() is None,
                                reason="native _ptexec unavailable")


def _graph(*args):
    return native_mod.load_ptexec().Graph(*args)


# ------------------------------------------------------------ fusion pass

def _csr(n, edges):
    off = [0] * (n + 1)
    for u, _v in edges:
        off[u + 1] += 1
    for i in range(n):
        off[i + 1] += off[i]
    succs = [0] * len(edges)
    pos = list(off)
    for u, v in sorted(edges):
        succs[pos[u]] = v
        pos[u] += 1
    return off, succs


def test_partition_seam_splits_region():
    # A(cap) -> B(seam) -> C(cap), plus A -> C: fusing {A, C} would
    # create a condensed cycle region -> B -> region; the seam depth
    # argument must keep them apart (and singletons are not regions)
    off, succs = _csr(3, [(0, 1), (1, 2), (0, 2)])
    assert partition_regions(3, off, succs, ["cpu", None, "cpu"]) == []


def test_partition_chain_and_min_size():
    off, succs = _csr(4, [(0, 1), (1, 2), (2, 3)])
    assert partition_regions(4, off, succs, ["cpu"] * 4) == [[0, 1, 2, 3]]
    assert partition_regions(4, off, succs, ["cpu"] * 4, min_size=5) == []


def test_partition_kinds_never_mix():
    # interleaved kinds at the same depth stay separate (a dev->cpu->dev
    # sandwich fused by depth alone would deadlock)
    off, succs = _csr(4, [(0, 1), (1, 2), (2, 3)])
    regs = partition_regions(4, off, succs, ["cpu", "cpu", "dev", "dev"])
    assert sorted(map(sorted, regs)) == [[0, 1], [2, 3]]


def test_partition_max_size_chunks_are_contiguous():
    n = 10
    off, succs = _csr(n, [(i, i + 1) for i in range(n - 1)])
    regs = partition_regions(n, off, succs, ["cpu"] * n, max_size=4)
    assert [len(r) for r in regs] == [4, 4, 2]
    flat = [t for r in regs for t in r]
    assert flat == list(range(n))        # topo-contiguous chunks
    # a sub-min tail folds into its predecessor ONLY within max_size
    # (the hard program-size bound); otherwise it stays per-task
    regs = partition_regions(9, *_csr(9, [(i, i + 1) for i in range(8)]),
                             ["cpu"] * 9, max_size=4)
    assert [len(r) for r in regs] == [4, 4]      # tail of 1 left unfused
    regs = partition_regions(7, *_csr(7, [(i, i + 1) for i in range(6)]),
                             ["cpu"] * 7, min_size=3, max_size=4)
    assert all(len(r) <= 4 for r in regs)


def _condensed_is_dag(n, off, succs, regions):
    reg_of = {}
    for ri, members in enumerate(regions):
        for m in members:
            reg_of[m] = ri
    node_of = lambda t: ("r", reg_of[t]) if t in reg_of else ("t", t)  # noqa: E731
    cedges = set()
    cnodes = {node_of(t) for t in range(n)}
    for u in range(n):
        for k in range(off[u], off[u + 1]):
            a, b = node_of(u), node_of(succs[k])
            if a != b:
                cedges.add((a, b))
    # Kahn over the condensed graph
    indeg = {c: 0 for c in cnodes}
    for _a, b in cedges:
        indeg[b] += 1
    from collections import deque
    q = deque(c for c, d in indeg.items() if d == 0)
    seen = 0
    adj = {}
    for a, b in cedges:
        adj.setdefault(a, []).append(b)
    while q:
        c = q.popleft()
        seen += 1
        for b in adj.get(c, ()):
            indeg[b] -= 1
            if indeg[b] == 0:
                q.append(b)
    return seen == len(cnodes)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
def test_partition_randomized_soundness(seed):
    """Random DAGs x random kind assignments: every region is kind-
    homogeneous and size-bounded, members cover no seam, and the
    condensed graph stays acyclic (the deadlock-freedom invariant)."""
    rng = random.Random(seed)
    n = rng.randrange(20, 120)
    edges = []
    for v in range(1, n):
        for _ in range(rng.randrange(0, 4)):
            edges.append((rng.randrange(0, v), v))
    off, succs = _csr(n, edges)
    kind = [rng.choice(["cpu", "dev", None, "cpu"]) for _ in range(n)]
    mx = rng.choice([4, 16, 128])
    regions = partition_regions(n, off, succs, kind, min_size=2,
                                max_size=mx)
    seen = set()
    for members in regions:
        assert 2 <= len(members) <= mx      # max_size is a HARD bound
        kinds = {kind[m] for m in members}
        assert len(kinds) == 1 and None not in kinds
        assert not (seen & set(members))
        seen |= set(members)
        # members arrive in topological order (a valid serialization)
        t_ix = {t: i for i, t in enumerate(topo_order(n, off, succs))}
        assert [t_ix[m] for m in members] == sorted(t_ix[m]
                                                    for m in members)
    assert _condensed_is_dag(n, off, succs, regions)


# ------------------------------------------------- packing (ISSUE 32)

def _gemm_regions(mt, nt, kt):
    """The k-chains of a tiled GEMM as the packing rule sees them: region
    (m, n) has ``kt`` tasks, reads row m of A, column n of B and C(m, n),
    writes C(m, n), and has no edge to the outside."""
    reads, writes = [], []
    for m in range(mt):
        for n in range(nt):
            rd = []
            for k in range(kt):
                rd += [("A", m, k), ("B", k, n)]
                if k == 0:
                    rd.append(("C", m, n))
            reads.append(rd)
            writes.append([("C", m, n)])
    nr = mt * nt
    return [kt] * nr, ["dev"] * nr, [0] * nr, [0] * nr, reads, writes


@pytest.mark.parametrize("max_size, chains", [
    (128, 4),       # the bound the fusion pass has: four chains of 32
    (64, 2),        # a lowered bound: two
    (100, 3),       # 96 tasks fit, a fourth chain does not
    (32, 1),        # a chain fills the bound: nothing packs
    (31, 1),
])
def test_pack_fills_the_bound_and_goes_no_further(max_size, chains):
    mt, nt, kt = 3, 12, 32
    args = _gemm_regions(mt, nt, kt)
    packs = pack_source_regions(*args, max_size)
    assert packs == pack_source_regions(*args, max_size)    # the same twice
    assert sorted(r for p in packs for r in p) == list(range(mt * nt))
    assert [p[0] for p in packs] == sorted(p[0] for p in packs)
    # siblings of one row, in region order: they share its 32 A tiles
    assert packs == [list(range(lo, lo + chains))
                     for lo in range(0, mt * nt, chains)]
    operands = {len({k for r in p for k in args[4][r]}) for p in packs}
    assert operands == {kt + chains * (kt + 1)}     # 164 at four, 65 at one


def test_pack_of_a_row_that_does_not_divide_takes_the_next_row_too():
    # six chains a row in packs of four: the two left over share B tiles
    # with the two under them, so the grid gives two shapes of pack
    args = _gemm_regions(2, 6, 4)
    assert pack_source_regions(*args, 16) == [
        [0, 1, 2, 3], [4, 5, 10, 11], [6, 7, 8, 9]]


@pytest.mark.parametrize("what", ["producer", "consumer", "cpu", "seam"])
def test_pack_leaves_what_is_no_source_region_as_it_was(what):
    sizes, kinds, ext_in, ext_out, reads, writes = _gemm_regions(1, 3, 4)
    if what == "producer":
        ext_in[1] = 1           # a slot (or a CTL edge) from outside
    elif what == "consumer":
        ext_out[1] = 2          # a slot of its own consumed outside
    else:
        kinds[1] = {"cpu": "cpu", "seam": None}[what]
    assert pack_source_regions(sizes, kinds, ext_in, ext_out, reads,
                               writes, 128) == [[0, 2], [1]]


@pytest.mark.parametrize("rd1, wr1", [
    (["S", "X"], ["Y"]),        # reads what region 0 writes
    (["S"], ["R"]),             # writes what region 0 reads
    (["S"], ["X"]),             # writes what region 0 writes
])
def test_pack_never_orders_what_the_graph_left_unordered(rd1, wr1):
    reads = [["S", "R"], rd1, ["S"]]
    writes = [["X"], wr1, ["Z"]]
    packs = pack_source_regions([2] * 3, ["dev"] * 3, [0] * 3, [0] * 3,
                                reads, writes, 128)
    assert packs == [[0, 2], [1]]


def test_pack_takes_the_most_shared_and_nothing_that_shares_nothing():
    reads = [["a", "b", "c"], ["a"], ["a", "b"], ["q"], ["b", "c", "a"],
             ["q", "r"]]
    packs = pack_source_regions([2] * 6, ["dev"] * 6, [0] * 6, [0] * 6,
                                reads, [[] for _ in reads], 6)
    # 4 shares three with region 0, then 2 (two) before 1 (one); 3 and 5
    # share nothing with that pack and "q" with each other
    assert packs == [[0, 4, 2], [1], [3, 5]]
    alone = pack_source_regions([2] * 2, ["dev"] * 2, [0] * 2, [0] * 2,
                                [["a"], ["b"]], [["a"], ["b"]], 128)
    assert alone == [[0], [1]]
    assert pack_source_regions([], [], [], [], [], [], 128) == []


def _region_edges(n, off, succs, regions):
    reg_of = [-1] * n
    for ri, members in enumerate(regions):
        for m in members:
            reg_of[m] = ri
    ext_in, ext_out = [0] * len(regions), [0] * len(regions)
    for u in range(n):
        for k in range(off[u], off[u + 1]):
            a, b = reg_of[u], reg_of[succs[k]]
            if a != b or a < 0:
                if a >= 0:
                    ext_out[a] += 1
                if b >= 0:
                    ext_in[b] += 1
    return ext_in, ext_out


def _check_random_packs(seed):
    """Pack a random DAG of many components with random kinds and random
    operands, check the result, and say how many packs hold two or more."""
    rng = random.Random(100 + seed)
    edges, kind = [], []
    for _ in range(rng.randrange(12, 40)):      # small trees, a few joined
        lo, k = len(kind), rng.choice(["dev", "dev", "dev", "cpu"])
        for v in range(lo, lo + rng.randrange(2, 7)):
            kind.append(k if rng.random() < 0.9 else None)
            if v > lo:
                edges.append((rng.randrange(lo, v), v))
            if lo and rng.random() < 0.04:
                edges.append((rng.randrange(0, lo), v))
    n = len(kind)
    off, succs = _csr(n, edges)
    mx = rng.choice([8, 16, 128])
    regions = partition_regions(n, off, succs, kind, min_size=2,
                                max_size=mx)
    ext_in, ext_out = _region_edges(n, off, succs, regions)
    pool = [f"m{i}" for i in range(12)]
    reads = [rng.sample(pool, rng.randrange(1, 5)) for _ in regions]
    writes = [rng.sample(pool, rng.choice([0, 0, 1])) for _ in regions]
    rkind = [kind[m[0]] for m in regions]
    packs = pack_source_regions([len(m) for m in regions], rkind, ext_in,
                                ext_out, reads, writes, mx)
    assert sorted(r for p in packs for r in p) == list(range(len(regions)))
    for p in packs:
        if len(p) == 1:
            continue
        assert sum(len(regions[r]) for r in p) <= mx
        for r in p:
            assert rkind[r] == "dev" and not ext_in[r] and not ext_out[r]
            touched = {k for q in p if q != r for k in reads[q] + writes[q]}
            assert not touched & set(writes[r])
            assert any(set(reads[r]) & set(reads[q]) for q in p if q != r)
    packed = [[m for r in p for m in regions[r]] for p in packs]
    assert _condensed_is_dag(n, off, succs, packed)
    return sum(len(p) > 1 for p in packs)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5, 6, 7])
def test_packed_partition_randomized_soundness(seed):
    """The property test of the partition, over the packed result: every
    pack is within the bound, of source regions that order nothing against
    each other, and the condensed graph is still acyclic."""
    _check_random_packs(seed)


def test_the_randomized_packs_are_not_all_singletons():
    assert sum(_check_random_packs(seed) for seed in range(8)) >= 16


# ----------------------------------------------------- C region support

def test_region_bind_weighted_accounting():
    # diamond 0 -> {1, 2} -> 3 where node 1 stands for 3 fused tasks
    g = _graph([0, 1, 1, 2], [0, 2, 3, 4, 4], [1, 2, 3, 3])
    assert g.region_bind([1, 3, 1, 1]) == 6
    for _ in range(2):                    # reset replays weighted
        order = []
        assert g.run(order.extend, 256, 0) == 6
        assert g.done() and g.pending() == 0
        pos = {t: i for i, t in enumerate(order)}
        assert pos[0] < pos[1] and pos[0] < pos[2] and \
            pos[1] < pos[3] and pos[2] < pos[3]
        rs = g.region_stats()
        assert rs["fused_regions"] == 1 and rs["fused_tasks"] == 3 \
            and rs["weighted_total"] == 6
        g.reset()


def test_region_bind_validation():
    g = _graph([0, 1], [0, 1, 1], [1])
    with pytest.raises(ValueError):
        g.region_bind([1])                # wrong length
    with pytest.raises(ValueError):
        g.region_bind([1, 0])             # weight < 1
    g.run(None, 256, 0)
    with pytest.raises(RuntimeError):
        g.region_bind([1, 2])             # already ran


def test_trace_mark_records_region_events():
    import struct
    mod = native_mod.load_ptexec()
    g = _graph([0], [0, 0], [])
    g.trace_mark(mod.EV_REGION, 7, mod.FLAG_START)   # disarmed: no-op
    g.trace_enable(2, 64)
    g.trace_mark(mod.EV_REGION, 7, mod.FLAG_START)
    g.trace_mark(mod.EV_REGION, 7, mod.FLAG_END)
    recs = []
    for _rid, blob in g.trace_drain():
        for off in range(0, len(blob), 24):
            recs.append(struct.unpack_from("<qqII", blob, off))
    evs = [(key, flags) for (_t, _id, key, flags) in recs
           if key == mod.EV_REGION]
    assert (mod.EV_REGION, mod.FLAG_START) in evs
    assert (mod.EV_REGION, mod.FLAG_END) in evs
    # the PBP keyword for merged timelines exists
    from parsec_tpu.utils.native_trace import NATIVE_KEYWORDS
    assert NATIVE_KEYWORDS["ptexec"][mod.EV_REGION] == "ptexec::region"


# ------------------------------------- randomized mixed-DAG PTG parity

_MIX_SRC = """%global N
%global DA
%global DB
%global C
%global E
%global M
%global IC
%global descX
%global descY
A(i, l)
  i = 0 .. N-1
  l = 0 .. DA-1
  RW X <- (l == 0) ? descX(0, i) : X A(i, l-1)
       -> (l < DA-1) ? X A(i, l+1) : X B(i, 0)
       -> (l < DA-1 and i % M == 0) ? Y A(((C*i+E) % N), l+1)
  READ Y <- (l > 0 and ((IC*(i-E)) % N) % M == 0) ? X A(((IC*(i-E)) % N), l-1)
  CTL S -> (l == DA-1) ? S SEAM(i)
BODY
  X = (X * 2.0 + 1.0) if Y is None else (X * 2.0 + Y)
END

SEAM(i)
  i = 0 .. N-1
  CTL S <- S A(i, DA-1)
        -> S B(i, 0)
BODY
  j = i * 2
END

B(i, l)
  i = 0 .. N-1
  l = 0 .. DB-1
  RW X <- (l == 0) ? X A(i, DA-1) : X B(i, l-1)
       -> (l < DB-1) ? X B(i, l+1) : descY(0, i)
  CTL S <- (l == 0) ? S SEAM(i)
BODY
  X = X + 3.0
END
"""


def _mix_params(seed):
    import math
    rng = random.Random(seed)
    N = rng.choice([4, 6, 8])
    C = rng.choice([c for c in range(1, N) if math.gcd(c, N) == 1])
    return dict(N=N, DA=rng.randrange(2, 5), DB=rng.randrange(2, 4),
                C=C, E=rng.randrange(N), M=rng.randrange(2, 4),
                IC=pow(C, -1, N))


def _mix_expected(p, init):
    """Pure-numpy replay of _MIX_SRC (exact in f32: small integers)."""
    N, DA, DB, E, M, IC = (p[k] for k in ("N", "DA", "DB", "E", "M",
                                          "IC"))
    a = [[0.0] * DA for _ in range(N)]
    for l in range(DA):
        for i in range(N):
            xin = init[i] if l == 0 else a[i][l - 1]
            j = (IC * (i - E)) % N
            y = a[j][l - 1] if (l > 0 and j % M == 0) else None
            a[i][l] = xin * 2.0 + 1.0 if y is None else xin * 2.0 + y
    return [a[i][DA - 1] + 3.0 * DB for i in range(N)]


def _run_mix(params, fusion: bool):
    from parsec_tpu.data.matrix import TiledMatrix
    mca.set("region_fusion", bool(fusion))
    ctx = pt.Context(nb_cores=1)
    try:
        N = params["N"]
        X = TiledMatrix("descX", 1, N, 1, 1)
        X.fill(lambda m, i: np.full((1, 1), float(i), np.float32))
        Y = TiledMatrix("descY", 1, N, 1, 1)
        prog = compile_ptg(_MIX_SRC, "mix")
        snap = PTEXEC_STATS.snapshot()
        tp = prog.instantiate(ctx, globals=dict(params),
                              collections={"descX": X, "descY": Y})
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
        assert tp._ptexec_state is not None, "lane should have engaged"
        assert tp._ptexec_state["graph"].done()
        d = PTEXEC_STATS.delta(snap)
        return {
            "executed": sum(s.nb_executed for s in ctx.streams),
            "finals": [float(np.asarray(
                Y.data_of(0, i).newest_copy().payload)[0, 0])
                for i in range(N)],
            "versions": [Y.data_of(0, i).version for i in range(N)],
            "delta": d,
        }
    finally:
        mca.params.unset("region_fusion")
        ctx.fini()


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_mixed_dag_fusion_parity(seed):
    """The randomized mixed fusable/un-fusable harness: fusion on vs off
    produce the identical completion count, bit-exact payloads (checked
    against a numpy replay), and identical data versions; with fusion ON
    the engagement counters prove regions actually fused and the seams
    still scheduled per-task."""
    params = _mix_params(seed)
    N = params["N"]
    ntasks = N * (params["DA"] + params["DB"] + 1)
    on = _run_mix(params, fusion=True)
    off = _run_mix(params, fusion=False)
    assert on["executed"] == off["executed"] == ntasks
    assert on["finals"] == off["finals"]
    assert on["versions"] == off["versions"]
    expect = _mix_expected(params, [float(i) for i in range(N)])
    assert on["finals"] == pytest.approx(expect, rel=0, abs=0)
    # engagement-counter gates
    d_on, d_off = on["delta"], off["delta"]
    assert d_on["fused_regions"] >= 1
    assert d_on["fused_tasks"] >= 2
    assert d_on["fused_tasks"] + d_on["seam_tasks"] == ntasks
    assert d_on["seam_tasks"] >= N            # every SEAM stays per-task
    assert d_on["pools_fallback"] == 0
    assert d_off["fused_regions"] == 0 and d_off["fused_tasks"] == 0


def test_cold_vs_warm_double_instantiation():
    """Persistence: the SAME program object instantiated twice — the
    second instantiation hits the executable cache (zero re-tracing) and
    produces identical results. `capture.cache_hits` is the ci-gate
    signal."""
    from parsec_tpu.data.matrix import TiledMatrix
    params = _mix_params(11)
    N = params["N"]
    prog = compile_ptg(_MIX_SRC, "mix-warm")
    expect = _mix_expected(params, [float(i) for i in range(N)])
    hits = []
    for rep in range(2):
        ctx = pt.Context(nb_cores=1)
        try:
            X = TiledMatrix("descX", 1, N, 1, 1)
            X.fill(lambda m, i: np.full((1, 1), float(i), np.float32))
            Y = TiledMatrix("descY", 1, N, 1, 1)
            snap = CAPTURE_CACHE_STATS.snapshot()
            tp = prog.instantiate(ctx, globals=dict(params),
                                  collections={"descX": X, "descY": Y})
            ctx.add_taskpool(tp)
            ctx.wait(timeout=120)
            assert tp._ptexec_state is not None
            d = CAPTURE_CACHE_STATS.delta(snap)
            hits.append((d["cache_hits"], d["cache_misses"]))
            finals = [float(np.asarray(
                Y.data_of(0, i).newest_copy().payload)[0, 0])
                for i in range(N)]
            assert finals == pytest.approx(expect, rel=0, abs=0)
        finally:
            ctx.fini()
    cold, warm = hits
    assert cold[0] == 0 and cold[1] >= 1, hits      # cold: misses only
    assert warm[0] >= 1 and warm[1] == 0, hits      # warm: all hits


def test_flatten_cache_key_separates_placements():
    """Satellite regression: the flatten/CSR cache key includes the
    device placement fingerprint — re-instantiating the same program
    under a different placement (device lane on vs off) must not replay
    the cached fused CSR against the wrong layout."""
    from parsec_tpu.data.matrix import TiledMatrix

    src = ("%global NT\n%global descA\n"
           "T(k)\n  k = 0 .. NT-1\n"
           "  RW X <- (k == 0) ? descA(0, 0) : X T(k-1)\n"
           "       -> (k < NT-1) ? X T(k+1) : descA(0, 1)\n"
           "BODY [type=TPU]\n  X = X + 1.0\nEND\n")
    prog = compile_ptg(src, "place")
    has_dev = native_mod.load_ptdev() is not None

    def run(over_cpu: bool):
        if over_cpu:
            mca.set("device_tpu_over_cpu", True)
        ctx = pt.Context(nb_cores=1)
        try:
            A = TiledMatrix("descA", 1, 2, 1, 1)
            A.fill(lambda m, k: np.zeros((1, 1), np.float32))
            tp = prog.instantiate(ctx, globals={"NT": 4},
                                  collections={"descA": A})
            ctx.add_taskpool(tp)
            ctx.wait(timeout=60)
            assert tp._ptexec_state is not None
            dev_bound = tp._ptexec_state.get("dev_pool") is not None
            out = float(np.asarray(
                A.data_of(0, 1).newest_copy().payload)[0, 0])
            return out, dev_bound
        finally:
            ctx.fini()
            if over_cpu:
                mca.params.unset("device_tpu_over_cpu")

    out_cpu, dev_cpu = run(over_cpu=False)
    assert out_cpu == 4.0 and not dev_cpu
    if has_dev:
        out_dev, dev_dev = run(over_cpu=True)
        assert out_dev == 4.0 and dev_dev
        # two placements, two cache entries — never one reused unsafely
        assert len(prog._ptexec_cache) == 2
        keys = list(prog._ptexec_cache)
        assert keys[0] != keys[1]
    else:
        assert len(prog._ptexec_cache) == 1


def test_device_region_fusion_parity():
    """Device regions: a [type=TPU] GEMM pool fuses its k-chains into
    region-sized ptdev dispatches — bit-exact vs numpy, task-denominated
    dev accounting, and engagement counters."""
    if native_mod.load_ptdev() is None:
        pytest.skip("native _ptdev unavailable")
    from parsec_tpu.data.matrix import TiledMatrix
    mca.set("device_tpu_over_cpu", True)
    ctx = pt.Context(nb_cores=1)
    try:
        n, ts = 64, 16
        rng = np.random.default_rng(3)
        a = rng.standard_normal((n, n)).astype(np.float32)
        b = rng.standard_normal((n, n)).astype(np.float32)
        src = ("%global MT\n%global KT\n%global descA\n%global descB\n"
               "%global descC\n"
               "GEMM(m, n, k)\n  m = 0 .. MT-1\n  n = 0 .. MT-1\n"
               "  k = 0 .. KT-1\n  : descC(m, n)\n"
               "  READ A <- descA(m, k)\n  READ B <- descB(k, n)\n"
               "  RW   C <- (k == 0) ? descC(m, n) : C GEMM(m, n, k-1)\n"
               "       -> (k < KT-1) ? C GEMM(m, n, k+1) : descC(m, n)\n"
               "BODY [type=TPU]\n"
               "  C = C + jnp.dot(A, B, "
               "preferred_element_type=jnp.float32)\nEND\n")
        A = TiledMatrix("frA", n, n, ts, ts)
        A.fill(lambda m, k: a[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
        B = TiledMatrix("frB", n, n, ts, ts)
        B.fill(lambda m, k: b[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
        C = TiledMatrix("frC", n, n, ts, ts)
        C.fill(lambda m, k: np.zeros((ts, ts), np.float32))
        snap = PTEXEC_STATS.snapshot()
        prog = compile_ptg(src, "fr-gemm")
        tp = prog.instantiate(ctx, globals={"MT": n // ts, "KT": n // ts},
                              collections={"descA": A, "descB": B,
                                           "descC": C})
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
        nt = (n // ts) ** 3
        err = float(np.abs(C.to_dense() - a @ b).max())
        assert err < 1e-2, f"fused device GEMM wrong: {err}"
        assert tp._ptexec_state is not None and \
            tp._ptexec_state.get("dev_pool") is not None
        d = PTEXEC_STATS.delta(snap)
        assert d["fused_regions"] >= 1 and d["pools_fallback"] == 0
        g = tp._ptexec_state["graph"]
        gs = g.dev_stats()
        assert gs["dev_tx"] == gs["dev_done"] == nt and \
            gs["dev_bad"] == 0, gs
        rs = g.region_stats()
        assert rs["fused_tasks"] >= 2 and rs["weighted_total"] == nt
        assert ctx._ptdev.failed() is None
    finally:
        ctx.fini()
        mca.params.unset("device_tpu_over_cpu")


def test_region_trace_intervals_land_in_pbp(tmp_path):
    """End-to-end observability: a profiled fused pool records one
    ptexec::region interval per fused region in the PBP trace (merged
    Perfetto timelines then show regions vs seams)."""
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.tools import trace_reader
    pbp = str(tmp_path / "fuse.pbp")
    mca.set("profile_enabled", True)
    mca.set("profile_filename", pbp)
    ctx = pt.Context(nb_cores=1)
    try:
        params = _mix_params(1)
        N = params["N"]
        X = TiledMatrix("descX", 1, N, 1, 1)
        X.fill(lambda m, i: np.full((1, 1), float(i), np.float32))
        Y = TiledMatrix("descY", 1, N, 1, 1)
        prog = compile_ptg(_MIX_SRC, "tr")
        snap = PTEXEC_STATS.snapshot()
        tp = prog.instantiate(ctx, globals=dict(params),
                              collections={"descX": X, "descY": Y})
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
        assert tp._ptexec_state is not None
        nregions = PTEXEC_STATS.delta(snap)["fused_regions"]
        assert nregions >= 1
    finally:
        ctx.fini()
        mca.params.unset("profile_enabled")
        mca.params.unset("profile_filename")
    df = trace_reader.to_dataframe(trace_reader.read_pbp(pbp))
    assert int((df["name"] == "ptexec::region").sum()) == nregions
    assert int((df["name"] == "ptexec::task").sum()) >= 1   # seams too


# -------------------------------------------------- DTD capture fusion

def test_dtd_defer_fusion_values_and_counters():
    """A deferred capture window replays its capturable prefix as fused
    super-task inserts: exact values, one region per maximal run, and
    the seam (the non-capturable trigger) still runs on its own."""
    from parsec_tpu.dsl.dtd import DTDTaskpool, PTDTD_STATS, RW
    ctx = pt.Context(nb_cores=1)
    try:
        tp = DTDTaskpool(ctx, "defer-fuse", capture=True)
        t = tp.tile_new(np.zeros((4, 4), np.float32), key="t")

        def add1(x):
            return x + 1.0

        def mul2(x):
            return x * 2.0

        side = []

        def tricky(x):
            side.append(1)
            return x + 3.0

        snap = PTDTD_STATS.snapshot()
        for _ in range(6):
            tp.insert_task(add1, (t, RW))
            tp.insert_task(mul2, (t, RW))
        tp.insert_task(tricky, (t, RW), jit=False)   # defers the window
        tp.wait()
        tp.close()
        ctx.wait(timeout=30)
        d = PTDTD_STATS.delta(snap)
        x = 0.0
        for _ in range(6):
            x = (x + 1.0) * 2.0
        x += 3.0
        assert float(np.asarray(t.data.newest_copy().payload)[0, 0]) == x
        assert d["capture_windows_deferred"] == 1, d
        assert d["capture_regions_fused"] == 1, d
        assert d["capture_tasks_fused"] == 12, d
        assert side == [1]
    finally:
        ctx.fini()


def test_dtd_defer_fusion_splits_on_priority_and_where():
    """Fusable runs break on non-default placement/priority: those
    inserts keep their own task so the scheduler still honors them."""
    from parsec_tpu.core.task import DEV_CPU
    from parsec_tpu.dsl.dtd import DTDTaskpool, PTDTD_STATS, RW
    ctx = pt.Context(nb_cores=1)
    try:
        tp = DTDTaskpool(ctx, "defer-split", capture=True)
        t = tp.tile_new(np.zeros((2, 2), np.float32), key="t")

        def add1(x):
            return x + 1.0

        snap = PTDTD_STATS.snapshot()
        for _ in range(3):
            tp.insert_task(add1, (t, RW))
        tp.insert_task(add1, (t, RW), where=DEV_CPU)      # splits the run
        for _ in range(3):
            tp.insert_task(add1, (t, RW))
        tp.insert_task(lambda x: x * 1.0, (t, RW), jit=False)
        tp.wait()
        tp.close()
        ctx.wait(timeout=30)
        d = PTDTD_STATS.delta(snap)
        assert float(np.asarray(t.data.newest_copy().payload)[0, 0]) == 7.0
        assert d["capture_regions_fused"] == 2, d
        assert d["capture_tasks_fused"] == 6, d
    finally:
        ctx.fini()


def test_dtd_defer_fusion_off():
    """--mca region_fusion 0 restores the pure per-task defer replay."""
    from parsec_tpu.dsl.dtd import DTDTaskpool, PTDTD_STATS, RW
    mca.set("region_fusion", False)
    ctx = pt.Context(nb_cores=1)
    try:
        tp = DTDTaskpool(ctx, "defer-off", capture=True)
        t = tp.tile_new(np.zeros((2, 2), np.float32), key="t")

        def add1(x):
            return x + 1.0

        snap = PTDTD_STATS.snapshot()
        for _ in range(4):
            tp.insert_task(add1, (t, RW))
        tp.insert_task(lambda x: x * 1.0, (t, RW), jit=False)
        tp.wait()
        tp.close()
        ctx.wait(timeout=30)
        d = PTDTD_STATS.delta(snap)
        assert float(np.asarray(t.data.newest_copy().payload)[0, 0]) == 4.0
        assert d["capture_regions_fused"] == 0 and \
            d["capture_tasks_fused"] == 0, d
    finally:
        mca.params.unset("region_fusion")
        ctx.fini()


def test_capture_cache_counters_warm_pool():
    """Two captured pools of the same DAG shape: the second hits the
    persistent executable cache (capture.cache_hits) with zero
    re-tracing — the warm-pool serving contract."""
    from parsec_tpu.dsl.dtd import DTDTaskpool, RW
    ctx = pt.Context(nb_cores=1)
    try:
        def body(x):
            return x * 2.0 + 1.0

        hits = []
        for rep in range(2):
            snap = CAPTURE_CACHE_STATS.snapshot()
            tp = DTDTaskpool(ctx, f"warm-{rep}", capture=True)
            t = tp.tile_new(np.full((4, 4), 1.0, np.float32),
                            key=f"t{rep}")
            for _ in range(5):
                tp.insert_task(body, (t, RW))
            tp.wait()
            tp.close()
            ctx.wait(timeout=30)
            d = CAPTURE_CACHE_STATS.delta(snap)
            hits.append((d["cache_hits"], d["cache_misses"]))
            x = 1.0
            for _ in range(5):
                x = x * 2.0 + 1.0
            assert float(np.asarray(
                t.data.newest_copy().payload)[0, 0]) == x
        assert hits[0] == (0, 1), hits       # cold compile
        assert hits[1] == (1, 0), hits       # warm executable
    finally:
        ctx.fini()


def test_exec_cache_lru_eviction_counted():
    stats = {"cache_hits": 0, "cache_misses": 0, "cache_evictions": 0}
    c = ExecCache(2, stats=stats)
    for k in ("a", "b", "c"):
        v, hit = c.get_or_build(k, lambda k=k: k.upper())
        assert v == k.upper() and not hit
    assert stats["cache_evictions"] == 1 and len(c) == 2
    _v, hit = c.get_or_build("c", lambda: "X")
    assert hit and _v == "C"
    # None key: uncacheable — builds fresh, counted as a miss
    v, hit = c.get_or_build(None, lambda: "fresh")
    assert v == "fresh" and not hit
    assert stats["cache_misses"] == 4


# ------------------------------------------------ donation (ISSUE 34)

#: three classes: W writes its flow, R reads it (and so forwards it), E has
#: no body (forwards it unread)
_W, _R, _E = 0, 1, 2


@pytest.mark.parametrize("readers, outside, written_back, want", [
    # the one reader updates it, and the update leaves the region
    ({10: [(_W, 0, 30)]}, {30}, (), [(0, 30)]),
    # ... through a class with no body: the array under another name
    ({10: [(_E, 0, 20)], 20: [(_W, 0, 30)]}, {30}, (), [(0, 30)]),
    # a forwarded slot counts with the array it aliases: R read it too
    ({10: [(_R, 0, 20)], 20: [(_W, 0, 30)]}, {30}, (), []),
    # ... it is read outside the region under its other name
    ({10: [(_E, 0, 20)], 20: [(_W, 0, 30)]}, {20, 30}, (), []),
    # ... it is written back to memory under its other name
    ({10: [(_E, 0, 20)], 20: [(_W, 0, 30)]}, {30}, (20,), []),
    # two flows of one member read it
    ({10: [(_W, 0, 30), (_W, 1, 31)]}, {30, 31}, (), []),
    # the only reader does not write it: no output it could become
    ({10: [(_R, 0, 20)]}, (), (), []),
    # the chain runs on inside the region, past a forwarder, to a write-back
    ({10: [(_W, 0, 30)], 30: [(_W, 0, 40), (_R, 1, 41)],
      40: [(_E, 0, 50)]}, (), (50,), [(0, 50)]),
    # ... and ends inside it: nothing leaves, nothing is donated
    ({10: [(_W, 0, 30)], 30: [(_R, 0, 40)]}, (), (), []),
])
def test_which_slot_operands_a_region_is_given(readers, outside,
                                               written_back, want):
    """``_ptexec_donations`` on plain data: one slot operand (10) of a
    device region, its readers inside the region as (class, flow, the
    flow's own slot), the slots read outside it and those written back."""
    from parsec_tpu.dsl.ptg.compiler import PTGTaskpool
    uses = {s: 0 for s in range(60)}
    uses[10] = len(readers[10])
    for s in outside:
        uses[s] = 1
    got = PTGTaskpool._ptexec_donations(
        [("slot", 10)], readers, uses, set(written_back), lambda r: True,
        [frozenset((0,)), frozenset(), frozenset()], [False, False, True])
    assert got == want
    assert PTGTaskpool._ptexec_donations(      # not the pool's to give
        [("slot", 10)], readers, uses, set(written_back), lambda r: False,
        [frozenset((0,)), frozenset(), frozenset()], [False, False, True]) \
        == []


@pytest.mark.parametrize("bound, donated", [(2, [0] * 6), (3, [0, 1, 0, 1]),
                                            (5, [0, 1, 0])])
def test_a_forwarded_slot_is_donated_with_the_array_or_not_at_all(bound,
                                                                  donated):
    """S(k) adds one, F(k) has no body and hands S(k)'s tile to S(k+1):
    one array under two names. A region that reads S's slot through F and
    updates it once takes it (bounds 3 and 5); a region that reads F's slot
    is handed a value no body of the pool wrote, and keeps its hands off
    (every region at bound 2, every other one at 3)."""
    if native_mod.load_ptdev() is None:
        pytest.skip("native _ptdev unavailable")
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.device.native import PTDEV_STATS
    src = ("%global N\n%global descX\n"
           "S(k)\n  k = 0 .. N-1\n  : descX(0, 0)\n"
           "  RW X <- (k == 0) ? descX(0, 0) : V F(k-1)\n       -> V F(k)\n"
           "BODY [type=TPU]\n  X = X + 1.0\nEND\n"
           "F(k)\n  k = 0 .. N-1\n  : descX(0, 0)\n  RW V <- X S(k)\n"
           "       -> (k < N-1) ? X S(k+1) : descX(0, 0)\n"
           "BODY [type=TPU]\n  pass\nEND\n")
    mca.set("device_tpu_over_cpu", True)
    mca.set("region_fusion_max", bound)
    ctx = pt.Context(nb_cores=1)
    try:
        X = TiledMatrix("fwX", 8, 8, 8, 8)
        X.fill(lambda m, n: np.zeros((8, 8), np.float32))
        prog = compile_ptg(src, "fwd")
        d0 = PTDEV_STATS.snapshot()
        tp = prog.instantiate(ctx, globals={"N": 6},
                              collections={"descX": X})
        ctx.add_taskpool(tp)
        ctx.wait(timeout=120)
        assert tp.completed and ctx._ptdev.failed() is None
        (ent,) = prog._ptexec_cache.values()
        plan = ent["fusion"]
        assert [plan["shapes"][r["shape"]]["n_donated"]
                for r in plan["regions"]] == donated
        dd = PTDEV_STATS.delta(d0)
        assert dd["donated"] == sum(donated)
        assert dd["region_outputs"] == len(donated)
        np.testing.assert_array_equal(
            np.asarray(X.data_of(0, 0).newest_copy().payload), 6.0)
    finally:
        ctx.fini()
        mca.params.unset("region_fusion_max")
        mca.params.unset("device_tpu_over_cpu")


def test_a_donated_buffer_takes_the_next_chains_end():
    """``_region_shape`` on plain data: three one-member chains, each
    updating the operand it is given, the third also written back. The
    chain ends lead what the program returns, turned by one against the
    operands (JAX aliases the i-th donated argument to the i-th output),
    so no member writes the tile it reads; ``out_pos`` / ``wb_pos`` say
    where each slot and write-back went. Without donations the program
    returns the slots, then the write-backs, under the signature it always
    had."""
    from parsec_tpu.dsl.ptg.compiler import _region_shape
    steps = [(0, (k,), (("ext", k),), 10 + k, 1,
              ((0, ("descA", (k,))),) if k == 2 else ()) for k in range(3)]
    reads, names = [frozenset()], ["S"]
    plain = _region_shape("dev", steps, [10, 11, 12], reads, names)
    assert plain["ret"] == ((0, 1, 2), (2,)) and plain["n_donated"] == 0
    assert (plain["out_pos"], plain["wb_pos"]) == ([0, 1, 2], [3])
    assert plain["sig"] == ("dev", plain["steps"], (0, 1, 2))
    given = _region_shape("dev", steps, [10, 11, 12], reads, names,
                          [10, 11, 12])
    assert given["n_donated"] == 3 and given["steps"] == plain["steps"]
    assert given["ret"] == ((1, 2, 0), (2,))
    assert (given["out_pos"], given["wb_pos"]) == ([2, 0, 1], [3])
    assert given["sig"] != plain["sig"] and given["name"] == "ptg_region_S"
    # a chain that ends in a write-back alone: the write-back leads
    only_wb = _region_shape("dev", steps, [10, 11], reads, names, [10, 12])
    assert only_wb["ret"] == ((2, 0), (1,))
    assert (only_wb["out_pos"], only_wb["wb_pos"]) == ([1, 2], [0])
