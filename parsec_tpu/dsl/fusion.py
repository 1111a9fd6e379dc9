"""Region fusion + the persistent compiled-executable cache (ISSUE 12).

The 1810.09868 inversion: whole-region XLA compilation should BEAT
per-task dispatch wherever it applies. This module holds the pieces both
DSLs share:

* :func:`partition_regions` — the fusion pass over a flattened CSR task
  graph: identify *capturable* subgraphs (same-device, jittable bodies,
  no cross-rank edge — the caller encodes all of that in a per-task
  ``kind``) and group them into **convex regions**. Each region later
  collapses into ONE fused super-task whose body is a single jitted
  program replaying the region in a valid serialization order; the
  scheduler handles only the un-fusable seams.

* :func:`pack_source_regions` — the second pass, for PTG pools: device
  regions with no edge to the outside that share memory operands become
  one region, so their program takes each shared operand once (its own
  soundness argument is in its docstring).

* :class:`ExecCache` — the persistent compiled-program cache shared
  across pool instantiations, with hit/miss/evict counters exported
  through the unified registry (``capture.cache_{hits,misses,
  evictions}``). A second instantiation of the same DAG shape re-runs a
  warm executable with zero re-tracing — the repeated-DAG shape of
  steady-state serving traffic (the 2112.01075 schedule-reuse argument).

* :func:`device_fingerprint` — the device/mesh component of every
  executable-cache key (and of the compiler's flatten cache key): a
  cached program can never be replayed against a different device
  layout.

Soundness of the region partition (the condensed graph must stay a DAG —
a cycle between a region and a seam is a deadlock at runtime):

For each capturable kind ``k`` define the *seam depth*
``d_k(t) = [t is not kind k] + max(d_k(pred), default 0)`` over the
task DAG. ``d_k`` is monotone non-decreasing along every edge and
strictly increases across any non-``k`` node. A region is a connected
component (over direct edges) of kind-``k`` tasks with EQUAL ``d_k``.
Any path leaving such a region passes either through a non-``k`` node —
after which every downstream kind-``k`` task has depth > d, so the path
can never re-enter a depth-``d`` region — or through a same-kind,
same-depth task, which by definition of connectivity is in the SAME
region. Hence no condensed cycle. Splitting an oversized region into
chunks contiguous in a global topological order preserves convexity for
the same reason: every escape route is depth-increasing, and direct
same-kind edges only run forward in topo order.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Callable, Dict, Hashable, List, Optional, Sequence, Tuple

from ..utils import mca
from ..utils.counters import LaneStats

mca.register("region_fusion", True,
             "Fusion pass over the flattened CSR (ISSUE 12): capturable "
             "subgraphs (same-device jittable bodies, static shapes, no "
             "cross-rank edge) collapse into ONE fused super-task — a "
             "single jitted program replaying the region in a valid "
             "serialization order — and the scheduler handles only the "
             "un-fusable seams. Applies to eligible PTG data pools on "
             "the native lane and to deferred DTD capture windows. "
             "0 restores per-task dispatch everywhere", type=bool)
mca.register("region_fusion_min", 2,
             "Minimum region size worth fusing: capturable components "
             "smaller than this stay per-task (a 1-task 'region' is "
             "pure wrapper overhead)")
mca.register("region_fusion_max", 128,
             "Maximum tasks per fused region: larger regions split into "
             "topo-contiguous chunks. Bounds XLA program size — "
             "decompose-heavy bodies inlined N times compile "
             "superlinearly (the capture-inline pathology, "
             "docs/capture.md)")

#: unified-registry export (``capture.*`` — installed by
#: utils/counters.install_native_counters): the persistent executable
#: cache's engagement truth. ``cache_hits`` nonzero on the second
#: instantiation of the same DAG shape IS the warm-pool contract the
#: ci gate asserts.
CAPTURE_CACHE_STATS = LaneStats(cache_hits=0, cache_misses=0,
                                cache_evictions=0)


def device_fingerprint() -> Tuple:
    """The device component of every executable-cache key. Two processes
    (or two contexts) with different backend layouts must never share a
    compiled program; identical layouts should."""
    try:
        import jax
        devs = jax.devices()
        return (devs[0].platform, getattr(devs[0], "id", 0), len(devs))
    except Exception:  # noqa: BLE001 — no backend: still a valid key
        return ("nodev",)


class ExecCache:
    """LRU cache of compiled executables keyed by (class signature, tile
    shapes/dtypes, device/mesh fingerprint) — the caller builds the key;
    this class owns lifetime and the unified hit/miss/evict accounting.

    ``get_or_build`` holds the lock across the builder call (builders
    only construct the jitted callable — tracing/compilation happens
    lazily at first call), so two concurrent instantiations of the same
    shape share ONE program instead of racing to build two."""

    def __init__(self, cap: int = 64,
                 stats: Optional[Dict[str, int]] = None) -> None:
        self.cap = cap
        self.stats = CAPTURE_CACHE_STATS if stats is None else stats
        #: this cache's own counts, beside the shared ``stats``
        self.hits = self.misses = self.evictions = 0
        self._d: "collections.OrderedDict[Hashable, Any]" = \
            collections.OrderedDict()
        self._mu = threading.Lock()

    def get_or_build(self, key: Hashable,
                     builder: Callable[[], Any]) -> Tuple[Any, bool]:
        """Return ``(value, hit)``. ``key=None`` (uncacheable shape)
        builds fresh and counts a miss — the honest signal that this
        instantiation paid a trace."""
        if key is None:
            self.stats["cache_misses"] += 1
            self.misses += 1
            return builder(), False
        with self._mu:
            v = self._d.get(key)
            if v is not None:
                self._d.move_to_end(key)
                self.stats["cache_hits"] += 1
                self.hits += 1
                return v, True
            self.stats["cache_misses"] += 1
            self.misses += 1
            v = self._d[key] = builder()
            while len(self._d) > self.cap:
                self._d.popitem(last=False)
                self.stats["cache_evictions"] += 1
                self.evictions += 1
            return v, False

    def __len__(self) -> int:
        with self._mu:
            return len(self._d)

    def clear(self) -> None:
        with self._mu:
            self._d.clear()


def adaptive_fusion_limits(classes: Sequence[Tuple[str, int, str]],
                           ) -> Tuple[set, int, int]:
    """Consumer (b) of the online cost model (ISSUE 18): size the fusion
    pass by MEASUREMENT instead of the static knobs.

    ``classes`` lists each capturable class as ``(name, shape_bucket,
    device_key)`` ('cpu' or 'tpu' — the fused flavor is looked up as
    ``<key>_fused``). Returns ``(declined, min_size, max_size)``:

    * ``declined`` — class indices to UN-fuse: the model has measured
      both flavors and the fused per-task cost (which prices in the
      in-dispatch re-trace a shape-churning workload pays N-bodies-wide
      per region) meets or exceeds the unfused per-task dispatch cost —
      fusion's premise ("dispatch overhead exceeds the region's marginal
      compiled-dispatch cost") measurably fails for that class.
    * ``max_size`` — the measured break-even region cap: the largest
      power-of-two band whose per-member trace cost (the
      ``__region_trace__`` pseudo-class, fed by the compiler timing each
      region program's first call), amortized by the executable cache's
      measured reuse ratio, stays below the measured per-task dispatch
      saving. Replaces the static ``region_fusion_max`` ceiling — the
      static knob stays the hard upper bound (the compile-blowup escape
      hatch is not negotiable), the model only ever splits SOONER.

    ``min_size`` stays the static knob: the fuse-at-all break-even is
    per-class (handled by ``declined``), not size-dependent once the
    batch amortization is in effect. With the model disabled or cold
    this degrades to exactly the static limits — instantiation never
    blocks on measurement."""
    min_size = int(mca.get("region_fusion_min", 2))
    max_size = int(mca.get("region_fusion_max", 128))
    declined: set = set()
    from ..core import costmodel as _cm     # lazy: utils-only module deps
    if not (_cm.enabled() and mca.get("costmodel_fusion", True)):
        return declined, min_size, max_size
    m = _cm.model
    saving = None                # measured per-task dispatch cost avoided
    for ci, (name, bucket, dev) in enumerate(classes):
        if not m.measured(name, bucket, dev):
            continue
        unfused = m.cost(name, bucket, dev)
        if m.measured(name, bucket, dev + "_fused") and \
                m.cost(name, bucket, dev + "_fused") >= unfused:
            declined.add(ci)
            _cm.COSTMODEL_STATS["fusion_declined"] += 1
            continue
        if saving is None or unfused < saving:
            saving = unfused     # conservative: the cheapest class bounds
                                 # what fusion can save per member
    sized = False
    if saving is not None and saving > 0:
        # the break-even comparison RAN on real measurements — a model-
        # derived sizing decision even when it confirms the static cap
        sized = True
        hits = CAPTURE_CACHE_STATS["cache_hits"]
        total = hits + CAPTURE_CACHE_STATS["cache_misses"]
        reuse = (hits / total) if total else 0.0
        cap = max_size
        while cap > min_size:
            per_member = m.region_trace_ns("cpu", cap)
            if per_member is None or per_member * (1.0 - reuse) <= saving:
                break            # unmeasured band: trust the static knob
            # halve only when the model has MEASURED the smaller band
            # cheaper per member: trace cost has a fixed per-program
            # floor, so splitting a region doubles the programs and can
            # RAISE total trace time — without a measured win the split
            # is speculation, and a speculative split re-plans the pool
            # (new flatten key → every region re-traces cold), the exact
            # oscillation this guard exists to prevent
            band = max(0, (cap // 2).bit_length() - 1)
            if not m.measured(_cm.REGION_TRACE, band, "cpu"):
                break
            half = m.region_trace_ns("cpu", cap // 2)
            if half is None or half >= per_member:
                break
            cap //= 2
        if cap != max_size:
            max_size = max(cap, min_size)
    if declined or sized:
        _cm.COSTMODEL_STATS["fusion_sized"] += 1
    return declined, min_size, max_size


def topo_order(n: int, off: Sequence[int], succs: Sequence[int]) -> List[int]:
    """Kahn topological order of a CSR DAG (the flatten output is a DAG
    by construction: indeg == goals was validated)."""
    indeg = [0] * n
    for s in succs:
        indeg[s] += 1
    q = collections.deque(i for i in range(n) if indeg[i] == 0)
    order: List[int] = []
    while q:
        u = q.popleft()
        order.append(u)
        for k in range(off[u], off[u + 1]):
            s = succs[k]
            indeg[s] -= 1
            if indeg[s] == 0:
                q.append(s)
    return order


def partition_regions(n: int, off: Sequence[int], succs: Sequence[int],
                      kind: Sequence[Optional[Hashable]],
                      min_size: int = 2, max_size: int = 128,
                      order: Optional[List[int]] = None,
                      ) -> List[List[int]]:
    """The fusion pass: group capturable tasks into convex regions.

    ``kind[t]`` is None for a seam (un-fusable) task, else a hashable
    capturability kind ('cpu' / 'dev' — tasks of different kinds never
    share a region: a region runs as ONE program on ONE dispatch path).
    Returns regions as member-id lists in topological order; every
    region has ``min_size <= len <= max_size`` and the condensed graph
    (regions + seams) is acyclic (see the module docstring's argument).
    """
    if n == 0:
        return []
    order = topo_order(n, off, succs) if order is None else order
    kinds_present = {k for k in kind if k is not None}
    if not kinds_present:
        return []
    topo_ix = [0] * n
    for ix, t in enumerate(order):
        topo_ix[t] = ix
    # per-kind seam depth, one topo sweep per kind (<= 2 kinds in
    # practice: 'cpu' and 'dev')
    depth: Dict[Hashable, List[int]] = {}
    for k in kinds_present:
        d = [0] * n
        for u in order:
            base = d[u] + (0 if kind[u] == k else 1)
            for e in range(off[u], off[u + 1]):
                s = succs[e]
                if base > d[s]:
                    d[s] = base
        depth[k] = d
    # union-find over direct same-kind same-depth edges
    parent = list(range(n))

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for u in range(n):
        ku = kind[u]
        if ku is None:
            continue
        du = depth[ku][u]
        for e in range(off[u], off[u + 1]):
            s = succs[e]
            if kind[s] == ku and depth[ku][s] == du:
                ru, rs = find(u), find(s)
                if ru != rs:
                    parent[rs] = ru
    groups: Dict[int, List[int]] = {}
    for t in order:                      # members land in topo order
        if kind[t] is None:
            continue
        groups.setdefault(find(t), []).append(t)
    regions: List[List[int]] = []
    for members in groups.values():
        if len(members) < min_size:
            continue
        # topo-contiguous chunking keeps each chunk convex; a tail chunk
        # below min_size folds into its predecessor only while the
        # combined region respects max_size (the knob is a HARD bound on
        # XLA program size — the compile-blowup escape hatch), otherwise
        # the tail stays per-task
        for lo in range(0, len(members), max_size):
            chunk = members[lo:lo + max_size]
            if len(chunk) >= min_size:
                regions.append(chunk)
            elif regions and regions[-1][-1] == members[lo - 1] and \
                    len(regions[-1]) + len(chunk) <= max_size:
                regions[-1].extend(chunk)
    # deterministic output order (instantiations must agree with the
    # cached plan): sort by first member's topo position
    regions.sort(key=lambda m: topo_ix[m[0]])
    return regions


def pack_source_regions(sizes: Sequence[int],
                        kinds: Sequence[Optional[Hashable]],
                        ext_in: Sequence[int], ext_out: Sequence[int],
                        reads: Sequence[Sequence[Hashable]],
                        writes: Sequence[Sequence[Hashable]],
                        max_size: int = 128,
                        max_held: Optional[int] = None) -> List[List[int]]:
    """Pack sibling device regions that share memory operands (ISSUE 32):
    a region program then takes each shared operand once, where its
    members' programs each took it again (four k-chains of one row of a
    tiled GEMM: 164 operands in one call, not 4 x 65 in four).

    Region ``r`` of :func:`partition_regions` has ``sizes[r]`` tasks of
    kind ``kinds[r]``, ``ext_in[r]`` / ``ext_out[r]`` graph edges from /
    to tasks outside it, and reads / writes the memory locations
    ``reads[r]`` / ``writes[r]`` (any hashable). Returns the packs as
    lists of region indices, every region in exactly one, ordered by
    first region; the caller chains the members' lists in that order
    (each is in topological order, and independent regions serialize in
    any order).

    *Candidates* are the ``'dev'`` regions with no external edge at all:
    no producer outside, so they are ready the moment the pool is bound
    and surface to the device lane together, and no consumer outside, so
    nobody waits longer because a sibling was packed in (their results
    leave by write-back). Every other region is a pack of its own. That
    leaves out regions fed by other tasks (the updates of a
    factorization), however much they share: packing those needs a rule
    for what may wait for what, and waits for the cell that can show it
    (ROADMAP M5).

    Two candidates share a pack only if neither writes a location the
    other reads or writes: the pack's trace-time memory env would
    otherwise give an order to what the graph left unordered. A pack
    holds at most ``max_size`` tasks, the fusion pass's own hard bound on
    XLA program size, and reads and writes at most ``max_held``
    distinct locations, a location both read and written counting twice
    (None: no bound): the most a device can hold of them at once, each
    location it reads pinned while the pack runs beside the room of each
    it writes. A pack past it could never be staged whole.

    The choice is greedy and deterministic in region order (the plan is
    cached and instantiations must agree): a pack starts at the first
    unpacked candidate and takes, while the bound allows, the candidate
    that shares the most read locations with the pack so far, the lowest
    index on a tie; one that shares none is never taken.

    Soundness: a candidate has no external in-edge, so no path enters
    one, so a union of candidates has none either and cannot lie on a
    cycle of the condensed graph.
    """
    nr = len(sizes)
    cand = [kinds[r] == "dev" and not ext_in[r] and not ext_out[r]
            for r in range(nr)]
    readers: Dict[Hashable, List[int]] = {}
    for r in range(nr):
        if cand[r]:
            for k in dict.fromkeys(reads[r]):
                readers.setdefault(k, []).append(r)
    smallest = min((sizes[r] for r in range(nr) if cand[r]), default=0)
    packed = [False] * nr
    packs: List[List[int]] = []
    for first in range(nr):
        if packed[first]:
            continue
        pack, total = [first], sizes[first]
        packed[first] = True
        packs.append(pack)
        if not cand[first]:
            continue
        p_reads: set = set()
        p_writes: set = set()
        share: Dict[int, int] = {}   # unpacked candidate -> shared reads
        refused: set = set()         # too large for, or ordered against, it
        r: Optional[int] = first
        while r is not None and total + smallest <= max_size:
            p_writes.update(writes[r])
            for k in reads[r]:
                if k not in p_reads:
                    p_reads.add(k)
                    for c in readers[k]:
                        if not packed[c] and c not in refused:
                            share[c] = share.get(c, 0) + 1
            r = None
            while share and r is None:
                c = max(share, key=lambda c: (share[c], -c))
                del share[c]
                if total + sizes[c] > max_size \
                        or any(k in p_reads or k in p_writes
                               for k in writes[c]) \
                        or any(k in p_writes for k in reads[c]) \
                        or (max_held is not None and len(p_reads | set(
                            reads[c])) + len(p_writes | set(writes[c]))
                            > max_held):
                    refused.add(c)
                else:
                    r = c
            if r is not None:
                pack.append(r)
                packed[r] = True
                total += sizes[r]
    return packs
