"""Native device lane (ptdev): the Python half of L4-in-C.

``native/src/ptdev.cpp`` owns the device hot path — a per-device manager
thread draining a lock-free MPSC pending queue that the execution
engines feed STRAIGHT from their GIL-free release sweeps
(``ptdev_iface.h``), taking the GIL only to issue the asynchronous JAX
dispatch / ``device_put`` and to poll ``jax.Array.is_ready()`` (the
cudaEventQuery of device_gpu.c:2593), then landing completions back into
the engines through the GIL-free ``retire()`` entry. This module is
everything around it:

* **lifecycle** — one :class:`NativeDeviceLane` per (context, device),
  created lazily the first time a TPU-bodied pool prepares for the
  native execution lane and torn down at ``Context.fini``;
* **pool routing** — the manager calls ONE ``dispatch(pool, ids)`` /
  ``poll()`` pair; this module routes them to the per-pool closures of
  ``device/lane_pool.py`` (input gather from the pool's slot array,
  version-checked stage-in through the C coherency table, async jitted
  dispatch, write-backs at completion);
* **counters** — ``PTDEV_STATS`` engagement accounting plus the C-side
  lane and coherency counters exported under ``ptdev.*`` through the
  unified registry (utils/counters.install_native_counters), so a
  silent fall-back to the interpreted device module is a CI failure.

The lane is the FAST path, not the only path: ``device/tpu.py``'s
kernel_scheduler stays as the interpreted route for DTD pools and any
pool the execution lane declines — but its residency/eviction POLICY now
also lives in the C coherency table (``CohTable``), so both paths share
one authoritative view of what is resident at which version.
"""

from __future__ import annotations

import atexit
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..utils import mca, output
from ..utils.counters import LaneStats

mca.register("device_native", True,
             "Drive TPU-bodied native-lane pools through the native "
             "device lane (native/src/ptdev.cpp): per-device async "
             "dispatch queues, event-based retirement into the engines, "
             "C-side coherency/zone accounting. Ineligible pools keep "
             "the interpreted device module (counted)", type=bool)
mca.register("device_native_poll_us", 100,
             "Manager-thread completion poll cadence while device work "
             "is in flight (microseconds)", type=int)

#: lane engagement accounting, the PTEXEC_STATS/PTCOMM_STATS template:
#: ``pools_engaged``/``tasks_engaged`` prove the lane carried device
#: bodies; ``pools_ineligible`` counts by-design declines (mca off,
#: distributed pools, DTD pools this PR); ``pools_fallback`` counts
#: eligible pools that still declined (native module missing) — the
#: silent-regression signal the ci.sh gate asserts is zero.
#: ``donated`` / ``region_outputs`` (ISSUE 34): slot operands fused
#: region programs were given for good, and the arrays those programs
#: returned (write-backs included): what of a solve's output buffers
#: reuses an input's. ``programs`` / ``released_early`` (ISSUE 36): device
#: programs the lane's closures dispatched, and those released to the
#: engine at dispatch (every successor a device node of the same lane).
#: ``staged_tiles`` / ``stage_in_puts`` (ISSUE 38): the tiles the push
#: phases' stage-ins moved onto the device (misses; a hit or an adoption
#: moves nothing), and the ``device_put`` calls that moved them: one a
#: batch that had a miss. ``held_back``: device programs that surfaced
#: ready and waited in their pool's backlog until the residency budget
#: could pin their operands (``device/lane_pool.py``), each counted once.
PTDEV_STATS = LaneStats(lanes_up=0, pools_engaged=0, tasks_engaged=0,
                        pools_fallback=0, pools_ineligible=0,
                        donated=0, region_outputs=0,
                        programs=0, released_early=0,
                        staged_tiles=0, stage_in_puts=0, held_back=0)

#: live lanes, for the process-wide ``ptdev.*`` counter samplers
_lanes: "weakref.WeakSet[NativeDeviceLane]" = weakref.WeakSet()


def _stop_abandoned_lanes() -> None:
    """atexit net: a lane whose context never fini'd must stop its
    manager thread BEFORE interpreter teardown — a C thread blocked in
    PyGILState_Ensure during finalization would hang the exit join."""
    for lane in list(_lanes):
        try:
            lane.clane.stop()
        except Exception:  # noqa: BLE001 — already down
            pass


atexit.register(_stop_abandoned_lanes)

#: C-side counters exported into the unified registry (ptdev.<name>);
#: the lane half comes from Lane.stats(), the coherency half from the
#: bound device's CohTable.stats()
DEV_COUNTER_KEYS = ("submitted", "dispatched", "retired",
                    "dispatch_batches", "overlap_hits", "late_submits",
                    "late_retires", "cb_errors", "inflight")
COH_COUNTER_KEYS = ("evictions", "pinned_skips", "coh_hits", "coh_misses",
                    "stage_in_bytes", "stage_out_bytes", "resident_bytes")


def dev_counter_sampler(key: str):
    """Sampler summing one C-side counter across every live lane (TTL-
    cached snapshot: one stats() call per lane per registry sweep)."""
    def sample():
        total = 0
        for lane in list(_lanes):
            try:
                total += lane.stats_cached()[key]
            except Exception:  # noqa: BLE001 - a torn-down lane samples 0
                pass
        return total
    return sample


def coh_counter_sampler(key: str):
    """Sampler summing one coherency-table counter across every device
    table attached to a live lane's device."""
    def sample():
        total = 0
        for lane in list(_lanes):
            try:
                st = lane.coh_stats_cached()
                if st is not None:
                    total += st[key]
            except Exception:  # noqa: BLE001
                pass
        return total
    return sample


def load_ptdev():
    from .. import native as native_mod
    return native_mod.load_ptdev()


def make_coh_table(budget: int):
    """A C-side coherency/residency table, or None when the native
    module is unavailable (the Python LRU stays the policy then)."""
    if not mca.get("device_native", True):
        return None
    mod = load_ptdev()
    if mod is None:
        return None
    try:
        return mod.CohTable(int(budget))
    except Exception as e:  # noqa: BLE001 — degrade to the Python LRU
        output.debug_verbose(1, "ptdev", f"CohTable unavailable: {e}")
        return None


def admits_pool(cross_rank: bool) -> bool:
    """May a pool with device-bodied classes use the lane at all? Asked
    before placement. The device and the cross-rank lanes are not
    combined yet, and ``--mca device_native 0`` keeps the interpreted
    device module: both by design, counted ``pools_ineligible``."""
    if cross_rank or not mca.get("device_native", True):
        PTDEV_STATS["pools_ineligible"] += 1
        return False
    return True


def lane_for_pool(ctx) -> Optional["NativeDeviceLane"]:
    """The context's lane for a pool that placed classes on the device,
    or None, counted ``pools_fallback``: eligible, device present, and
    the ``_ptdev`` module or the lane is missing (the silent-regression
    signal)."""
    lane = ctx._ptdev_lane()
    if lane is None:
        PTDEV_STATS["pools_fallback"] += 1
    return lane


class NativeDeviceLane:
    """One (context, device) native device lane: the C ``Lane`` object
    plus pool routing and lifecycle."""

    @staticmethod
    def available(ctx) -> Optional[str]:
        """None when the lane can engage, else the reason it cannot."""
        if not mca.get("device_native", True):
            return "disabled by --mca device_native 0"
        from ..core.task import DEV_TPU
        devs = ctx.devices.by_type(DEV_TPU)
        if not devs:
            return "no accelerator device registered"
        if load_ptdev() is None:
            return "native module unavailable"
        return None

    @classmethod
    def maybe_create(cls, ctx) -> Optional["NativeDeviceLane"]:
        reason = cls.available(ctx)
        if reason is not None:
            output.debug_verbose(2, "ptdev",
                                 f"device lane not engaged: {reason}")
            return None
        from ..core.task import DEV_TPU
        return cls(ctx, ctx.devices.by_type(DEV_TPU)[0])

    def __init__(self, ctx, device) -> None:
        self.ctx = ctx
        self.device = device          # the TPUDevice whose chip we drive
        self._mod = load_ptdev()
        self.clane = self._mod.Lane()
        #: pool id -> its (dispatch, poll, drop) closures (device/lane_pool.py)
        self._pools: Dict[int, Tuple[Callable, Callable, Callable]] = {}
        self._next_pool = 1
        self._stats_cache: Tuple[float, Optional[dict]] = (0.0, None)
        self._coh_cache: Tuple[float, Optional[dict]] = (0.0, None)
        self.clane.start(self._dispatch, self._poll,
                         mca.get("device_native_poll_us", 100))
        self._up = True
        PTDEV_STATS["lanes_up"] += 1
        _lanes.add(self)
        # in-lane ring events (EV_DEV_*) land as `ptdev-w*` PBP streams
        # through the same bridge as the execution lanes
        ctx._ntrace_attach("ptdev", self.clane)
        output.debug_verbose(1, "ptdev",
                             f"native device lane up on {device.name}")

    # --------------------------------------------------------- pool routing
    def bind_pool(self, engine, dispatch: Callable, poll: Callable,
                  drop: Callable) -> int:
        """Route a pool's device tasks: ``engine`` provides the GIL-free
        retire entry (dev_retire_capsule); ``dispatch(ids)`` issues the
        async device work; ``poll()`` returns the tids the engine may
        retire (seen complete, or released at dispatch); ``drop()`` gives
        up what the pool still has in flight when it is unbound. Returns
        the lane-local pool id to pass to the engine's ``dev_bind``."""
        pid = self._next_pool
        self._next_pool += 1
        self.clane.bind_pool(pid, engine.dev_retire_capsule(), engine)
        self._pools[pid] = (dispatch, poll, drop)
        return pid

    def unbind_pool(self, pool_id: int) -> None:
        closures = self._pools.pop(pool_id, None)
        if closures is not None:
            # a pool that ran to its end has nothing left; an aborted one
            # may: programs released at dispatch and not yet seen complete
            closures[2]()
        try:
            self.clane.unbind_pool(pool_id)
        except Exception:  # noqa: BLE001 — teardown races are benign
            pass

    def submit_capsule(self):
        return self.clane.submit_capsule()

    def failed(self) -> Optional[str]:
        """The message of the callback exception that poisoned the lane,
        or None. Drain loops surface it as the pool's error."""
        return self.clane.failed()

    # ------------------------------------------------ manager-thread hooks
    # Both run ON the manager thread with the GIL held; self._pools is
    # only mutated under the GIL (bind/unbind), so plain dict ops are
    # safe. A pool unbound between submit and dispatch just drops its
    # ids here (the C side counts unrouted retires as late_retires).
    def _dispatch(self, pool: int, ids: List[int]) -> int:
        closures = self._pools.get(pool)
        return 0 if closures is None else closures[0](ids)

    def _poll(self):
        done = []
        for pid, (_dispatch, poll, _drop) in list(self._pools.items()):
            for tid in poll():
                done.append((pid, tid))
        return done

    # -------------------------------------------------------------- stats
    def stats_cached(self, ttl: float = 0.05) -> Dict[str, Any]:
        now = time.monotonic()
        stamp, snap = self._stats_cache
        if snap is None or now - stamp > ttl:
            snap = self.clane.stats()
            self._stats_cache = (now, snap)
        return snap

    def coh_stats_cached(self, ttl: float = 0.05) -> Optional[Dict[str, Any]]:
        tbl = getattr(self.device, "_ncoh", None)
        if tbl is None:
            return None
        now = time.monotonic()
        stamp, snap = self._coh_cache
        if snap is None or now - stamp > ttl:
            snap = tbl.stats()
            self._coh_cache = (now, snap)
        return snap

    # ------------------------------------------------------------ teardown
    def fini(self) -> None:
        if not self._up:
            return
        self._up = False
        # bounded wait for in-flight dispatches to retire: stopping with
        # work on the chip would strand the owning graphs undone. A
        # poisoned lane or one with no bound pools left can never drain
        # what remains (an unbound pool's completions are uncollectable
        # by design) — break immediately instead of stalling every
        # error-path teardown for the full deadline
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if self.clane.failed() is not None or not self._pools:
                break
            s = self.clane.stats()
            if s["inflight"] == 0 and s["submitted"] == s["dispatched"] \
                    + s["late_submits"]:
                break
            time.sleep(1e-3)
        try:
            self.ctx._ntrace_detach(self.clane)
        except Exception:  # noqa: BLE001 — no bridge attached
            pass
        self.clane.stop()
        for pid in list(self._pools):
            self.unbind_pool(pid)
        output.debug_verbose(1, "ptdev",
                             f"native device lane down on "
                             f"{self.device.name}: {self.clane.stats()}")
