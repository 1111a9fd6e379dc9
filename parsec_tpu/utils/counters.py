"""Properties dictionary + software-defined counter export.

Re-design of parsec/dictionary.c (live properties registry) and
parsec/papi_sde.c (PAPI software-defined events exposing runtime counters —
pending tasks, tasks enabled, tasks retired; scheduling.c:330-337,491).
Counters register once and are sampled on read; an aggregation hook serves
the live-visualization role of tools/aggregator_visu.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Union

Sampler = Callable[[], Union[int, float]]

# canonical counter names (ref: PAPI_SDE parsec::SCHEDULER::PENDING_TASKS etc.)
PENDING_TASKS = "scheduler.pending_tasks"
TASKS_ENABLED = "scheduler.tasks_enabled"
TASKS_RETIRED = "scheduler.tasks_retired"


class LaneStats(dict):
    """Engagement-counter dict for the native lanes (PTEXEC_STATS /
    PTDTD_STATS) with proper lifecycle helpers, so CI gates and tests
    stop hand-poking raw keys. Still a plain dict underneath — the hot
    paths keep their ``stats[key] += 1`` shape."""

    def snapshot(self) -> Dict[str, Union[int, float]]:
        """A point-in-time copy (compare with :meth:`delta`)."""
        return dict(self)

    def reset(self) -> None:
        """Zero every counter (bench/test isolation)."""
        for k in self:
            self[k] = 0

    def delta(self, since: Dict[str, Union[int, float]]) -> Dict[str, int]:
        """Per-key change since a :meth:`snapshot`."""
        return {k: self[k] - since.get(k, 0) for k in self}


class CounterRegistry:
    """Process-wide named counters: either atomic accumulators or samplers."""

    def __init__(self) -> None:
        self._acc: Dict[str, float] = {}
        self._samplers: Dict[str, Sampler] = {}
        self._lock = threading.Lock()

    def register(self, name: str, sampler: Optional[Sampler] = None) -> None:
        with self._lock:
            if sampler is not None:
                self._samplers[name] = sampler
            else:
                self._acc.setdefault(name, 0)

    def add(self, name: str, v: Union[int, float] = 1) -> None:
        with self._lock:
            self._acc[name] = self._acc.get(name, 0) + v

    def set(self, name: str, v: Union[int, float]) -> None:
        with self._lock:
            self._acc[name] = v

    def read(self, name: str) -> Union[int, float]:
        s = self._samplers.get(name)
        if s is not None:
            return s()
        with self._lock:
            return self._acc.get(name, 0)

    def snapshot(self, skip: Optional[Callable[[str], bool]] = None
                 ) -> Dict[str, Union[int, float]]:
        """All counters at once (the aggregator_visu export). ``skip``
        filters keys BEFORE their samplers run — a sweeper that doesn't
        want a family of derived gauges (pttel skips ``*.hist.*``) must
        not pay for computing them."""
        out: Dict[str, Union[int, float]] = {}
        with self._lock:
            out.update(self._acc)
            samplers = dict(self._samplers)
        if skip is not None:
            for name in [n for n in out if skip(n)]:
                del out[name]
        for name, s in samplers.items():
            if skip is not None and skip(name):
                continue
            try:
                out[name] = s()
            except Exception:  # noqa: BLE001 - sampling must never break
                out[name] = float("nan")
        return out


counters = CounterRegistry()

# canonical native-lane counter names (the SDE-style export of the lane
# engagement/tracing state; see install_native_counters)
PTEXEC_POOLS_ENGAGED = "ptexec.pools_engaged"
PTDTD_TASKS_BATCHED = "ptdtd.tasks_batched"
TRACE_EVENTS_DROPPED = "trace.events_dropped"
TRACE_EVENTS_NATIVE = "trace.events_native"
PTEXEC_SLOTS_RETIRED = "ptexec.slots_retired"


def install_native_counters() -> None:
    """Register the native lanes' engagement stats, the lane-side
    datarepo retire counter, and the in-lane trace drop/landed counters
    as samplers under canonical names (``ptexec.*``, ``ptdtd.*``,
    ``trace.*``) so :mod:`parsec_tpu.tools.live_view` and the SDE-style
    snapshot export see the lanes. Idempotent."""
    from ..comm import native as _cnative        # lazy: avoid import cycles
    from ..comm import pttel as _tel
    from ..core import costmodel as _cm
    from ..core import sched_plane as _sp
    from ..core import watchdog as _wd
    from ..device import native as _dnative
    from ..dsl import dtd as _dtd
    from ..dsl import fusion as _fus
    from ..dsl.ptg import compiler as _ptg
    from ..serving import fabric as _fab
    from ..serving import reconcile as _rec
    from ..tools import flight as _fl
    from . import native_trace as _nt
    from . import xla_trace as _xt
    from .hist import install_hist_counters

    def _sampler(stats, key):
        return lambda: stats[key]

    for stats, prefix in ((_ptg.PTEXEC_STATS, "ptexec"),
                          (_dtd.PTDTD_STATS, "ptdtd"),
                          (_cnative.PTCOMM_STATS, "ptcomm"),
                          (_dnative.PTDEV_STATS, "ptdev"),
                          (_fab.FAB_STATS, "ptfab"),
                          (_sp.SCHED_STATS, "sched"),
                          # the persistent executable cache (ISSUE 12):
                          # capture.cache_{hits,misses,evictions} — the
                          # warm-pool contract on /metrics
                          (_fus.CAPTURE_CACHE_STATS, "capture"),
                          # the online cost models (ISSUE 18):
                          # costmodel.{keys,folds,decisions,decision_ns,
                          # placements_diverged,...} — the adaptive-
                          # engagement truth the ci gate asserts
                          (_cm.COSTMODEL_STATS, "costmodel"),
                          # the mesh telemetry plane (ISSUE 20):
                          # pttel.{rounds,frames_tx,frames_rx,folds,...}
                          # — the O(log P) frame contract on /metrics
                          (_tel.TEL_STATS, "pttel"),
                          # the lane stall watchdog + flight recorder +
                          # push-mode reconciler (ISSUE 20)
                          (_wd.WATCHDOG_STATS, "watchdog"),
                          (_fl.FLIGHT_STATS, "flight"),
                          (_rec.RECONCILE_STATS, "reconcile")):
        for key in stats:
            counters.register(f"{prefix}.{key}", sampler=_sampler(stats, key))
    # the comm lane's C-side wire counters (summed across live lanes)
    for key in _cnative.COMM_COUNTER_KEYS:
        counters.register(f"ptcomm.{key}",
                          sampler=_cnative.comm_counter_sampler(key))
    # the device lane's C-side counters: dispatch/retire/overlap splits
    # from the Lane, residency/eviction/stage-in from the CohTable —
    # ISSUE 10's "device occupancy shows up on /metrics"
    for key in _dnative.DEV_COUNTER_KEYS:
        counters.register(f"ptdev.{key}",
                          sampler=_dnative.dev_counter_sampler(key))
    for key in _dnative.COH_COUNTER_KEYS:
        counters.register(f"ptdev.{key}",
                          sampler=_dnative.coh_counter_sampler(key))
    # the account of the newest pool that ended on a device lane with the
    # spans on (ISSUE 37): where the lane's manager thread spent its life
    for key in _xt.POOL_ACCOUNT_FIELDS:
        counters.register(
            f"ptdev.pool.{key}", sampler=lambda key=key: (
                _xt.POOL_ACCOUNTS[-1][key] if _xt.POOL_ACCOUNTS else 0))
    # the scheduler plane's C-side counters (summed across live planes):
    # steals, spills, served, queued, admission stalls — ISSUE 9
    for key in _sp.PLANE_COUNTER_KEYS:
        counters.register(f"sched.{key}",
                          sampler=_sp.plane_counter_sampler(key))
    # the serving fabric's wire counters (credit grants/spends/reclaims
    # summed across live fabrics) — ISSUE 11's "credit flow shows up on
    # /metrics"; ptfab.served.<tenant> registers per served tenant
    for name, ckey in _fab.FAB_WIRE_KEYS.items():
        counters.register(f"ptfab.{name}",
                          sampler=_fab.fab_wire_sampler(ckey))
    counters.register(TRACE_EVENTS_DROPPED, sampler=_nt.total_dropped)
    counters.register(TRACE_EVENTS_NATIVE, sampler=_nt.total_landed)
    counters.register(PTEXEC_SLOTS_RETIRED)   # accumulator: lane finalize adds
    # latency percentiles (<kind>.hist.<name>.p99_us etc. — ISSUE 8)
    install_hist_counters()


def install_scheduler_counters(context) -> None:
    """Wire the canonical scheduler counters onto a context via PINS."""
    from ..core import pins as P

    counters.register(TASKS_ENABLED)
    counters.register(TASKS_RETIRED)
    counters.register(PENDING_TASKS, sampler=lambda: (
        counters.read(TASKS_ENABLED) - counters.read(TASKS_RETIRED)))

    def on_sched(stream, tasks, extra) -> None:
        counters.add(TASKS_ENABLED, len(tasks) if isinstance(tasks, list) else 1)

    def on_complete(stream, task, extra) -> None:
        counters.add(TASKS_RETIRED, 1)

    context.pins.register(P.SCHEDULE_END, on_sched)
    context.pins.register(P.COMPLETE_EXEC_END, on_complete)
