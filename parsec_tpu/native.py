"""ctypes bindings for the native C++ core (native/src/ptcore.cpp).

The library is built on demand with the in-tree Makefile. Inside the
library a missing artifact degrades to the interpreted engine with a
WARNING (docs/native_exec.md: ~100x slower); the entry points that claim to
exercise the lanes — ``chip_smoke.py``, ``parsec_tpu.launch`` — call
:func:`require_all`, which rebuilds incrementally and raises instead.
Wired-in fast paths:

* :class:`NativeDepTable` — the dependency-update engine
  (parsec_update_deps_with_mask role) behind ``Taskpool.update_deps`` for
  integer-tuple keys.
* :class:`NativeZone` — backend for :class:`parsec_tpu.utils.zone_malloc`.

A native ready-deque was prototyped here for the schedulers and REMOVED
after measurement: a ctypes call costs ~2µs at the boundary while a
``collections.deque`` op is ~0.14µs and already GIL-atomic — the
measured gap was 7x IN FAVOR of the Python deque (200k push+pop pairs:
0.39s native vs 0.057s deque, this container). The scheduler
ready-queues therefore use lock-free single-call deque ops
(core/scheduler.py:_LockedDeque); native code is reserved for paths
where the work per call dominates the boundary cost (the dep table:
hash + probe per update).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

from .utils import mca, output

mca.register("native_enabled", True, "Use the native C++ core when available", type=bool)

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_PKG_DIR)
_NATIVE_DIR = os.path.join(_ROOT, "native")
_SO = os.path.join(_NATIVE_DIR, "build", "libptcore.so")


def _installed_so(stem: str):
    """ABI-tagged extension inside the installed package (wheel layout:
    setup.py builds parsec_tpu._ptcore/_ptdtd into the package dir), or
    None. Only the exact RUNNING interpreter's suffix is accepted."""
    import sysconfig
    p = os.path.join(_PKG_DIR, stem + sysconfig.get_config_var("EXT_SUFFIX"))
    return p if os.path.exists(p) else None

_lib = None
_lib_lock = threading.Lock()
_KEY_MAX = 16


def build() -> None:
    """Run the in-tree (incremental) native build for the RUNNING
    interpreter; raises with the compiler's output on failure."""
    import sys
    r = subprocess.run(["make", "-C", _NATIVE_DIR, f"-j{os.cpu_count() or 1}",
                        f"PYTHON={sys.executable}"],
                       capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        raise RuntimeError(f"native build failed (make -C {_NATIVE_DIR}):\n"
                           f"{r.stderr[-2000:]}")


def _build() -> bool:
    try:
        build()
        return True
    except (OSError, RuntimeError, subprocess.TimeoutExpired) as e:
        output.warning(f"native build unavailable, interpreted engine in "
                       f"use: {e}")
        return False


def require_all() -> None:
    """The smoke/launcher rule: what runs is what the source tree holds.
    Always run ``make`` (a no-op when up to date — an existing ``.so``
    older than ``native/src`` is rebuilt, never trusted), then load all six
    artifacts; any build or load failure raises."""
    if os.path.exists(os.path.join(_NATIVE_DIR, "Makefile")):
        build()
    missing = [name for name, loader in (
        ("ptcore", load), ("ptdtd", load_ptdtd), ("ptexec", load_ptexec),
        ("ptcomm", load_ptcomm), ("ptsched", load_ptsched),
        ("ptdev", load_ptdev)) if loader() is None]
    if missing:
        raise RuntimeError(f"native artifacts failed to load: {missing}")


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None on failure."""
    global _lib
    if _lib is not None:
        return _lib
    if not mca.get("native_enabled", True):
        return None
    with _lib_lock:
        if _lib is not None:
            return _lib
        # installed wheel first (parsec_tpu/_ptcore.*.so — a C-ABI library
        # that happens to be built by the Extension machinery), then the
        # in-tree build, then build-on-demand
        so = _installed_so("_ptcore")
        if so is None:
            if not os.path.exists(_SO) and not _build():
                return None
            so = _SO
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            output.warning(f"native core dlopen failed: {e}")
            return None
        # signatures
        lib.pt_dep_table_create.restype = ctypes.c_void_p
        lib.pt_dep_table_create.argtypes = [ctypes.c_uint64]
        lib.pt_dep_table_destroy.argtypes = [ctypes.c_void_p]
        lib.pt_dep_table_size.restype = ctypes.c_int64
        lib.pt_dep_table_size.argtypes = [ctypes.c_void_p]
        lib.pt_dep_table_update.restype = ctypes.c_int32
        lib.pt_dep_table_update.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32]
        lib.pt_dep_table_get.restype = ctypes.c_int64
        lib.pt_dep_table_get.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int32]
        lib.pt_zone_create.restype = ctypes.c_void_p
        lib.pt_zone_create.argtypes = [ctypes.c_int64, ctypes.c_int64]
        lib.pt_zone_destroy.argtypes = [ctypes.c_void_p]
        lib.pt_zone_alloc.restype = ctypes.c_int64
        lib.pt_zone_alloc.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.pt_zone_free.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                     ctypes.c_int64]
        lib.pt_zone_stats.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(ctypes.c_int64)]
        _lib = lib
        output.debug_verbose(1, "native", f"native core loaded from {_SO}")
        return _lib


def available() -> bool:
    return load() is not None


_ptdtd_mod = [None, False]   # [module, attempted]
_ptexec_mod = [None, False]
_ptcomm_mod = [None, False]
_ptsched_mod = [None, False]
_ptdev_mod = [None, False]


def _load_pyext(stem: str, cache):
    """Load a CPython extension (built by native/Makefile or installed in
    the wheel), memoized in ``cache`` ([module, attempted]).

    ``attempted`` is published only AFTER the load finished (inside the
    lock): the unlocked fast check races the loader, and publishing it
    up front let a second thread observe attempted=True with the module
    still None — it then recorded a permanent "native unavailable"
    (found by the serving bench's concurrent first-inserts, where N
    client threads hit the first load simultaneously)."""
    if cache[1]:
        return cache[0]
    with _lib_lock:
        if cache[1]:
            return cache[0]
        try:
            if not mca.get("native_enabled", True):
                return None
            import importlib.util
            import sysconfig
            # installed wheel first; else the in-tree build. Exact
            # ABI-tagged filename of the RUNNING interpreter — a wildcard
            # could load a stale extension built against another Python
            so = _installed_so(stem)
            if so is None:
                so = os.path.join(
                    _NATIVE_DIR, "build",
                    stem + sysconfig.get_config_var("EXT_SUFFIX"))
                if not os.path.exists(so) and not (_build()
                                                   and os.path.exists(so)):
                    return None
            try:
                spec = importlib.util.spec_from_file_location(
                    f"parsec_tpu.{stem}", so)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                cache[0] = mod
                output.debug_verbose(1, "native",
                                     f"{stem} loaded from {so}")
            except Exception as e:  # noqa: BLE001
                output.warning(f"native extension {stem} failed to "
                               f"load, interpreted engine in use: {e}")
            return cache[0]
        finally:
            cache[1] = True


def load_ptdtd():
    """The CPython-extension DTD engine (native/src/ptdtd.cpp), or None.

    A separate artifact from libptcore.so: per-task hot paths need
    C-extension call costs (~0.2us) — the ctypes boundary (~2us) that the
    coarse bindings above tolerate would eat the entire win (module
    docstring)."""
    return _load_pyext("_ptdtd", _ptdtd_mod)


def load_ptexec():
    """The CPython-extension PTG execution lane (native/src/ptexec.cpp),
    or None. Runs the generic task FSM — dep-count decrement, ready
    detect, dispatch, successor release — over a flattened successor
    table, batched, with the GIL dropped across the walk (see
    docs/native_exec.md for the eligibility and GIL contract)."""
    return _load_pyext("_ptexec", _ptexec_mod)


def load_ptcomm():
    """The CPython-extension communication lane (native/src/ptcomm.cpp),
    or None. A funneled C progress thread that multiplexes the cross-rank
    mesh (TCP fds + same-host shm rings), speaks the fixed binary AM
    protocol, and ingests activations straight into the ptexec/ptdtd
    ready structures without the GIL (docs/native_exec.md)."""
    return _load_pyext("_ptcomm", _ptcomm_mod)


def load_ptsched():
    """The CPython-extension scheduler plane (native/src/ptsched.cpp), or
    None. Per-worker bounded hot queues with cross-worker steal-half,
    per-pool overflow heaps, weighted deficit-round-robin arbitration and
    admission windows — the shared ready plane the ptexec/ptdtd engines
    drain through when a Context arms it (docs/scheduling.md)."""
    return _load_pyext("_ptsched", _ptsched_mod)


def load_ptdev():
    """The CPython-extension device lane (native/src/ptdev.cpp), or None.
    Per-device async dispatch queues fed GIL-free from the engines'
    release sweeps, a manager thread issuing JAX dispatch and polling
    completion events, GIL-free retirement back into the engines, and the
    C-side coherency/residency table (docs/device_lane.md)."""
    return _load_pyext("_ptdev", _ptdev_mod)


class NativeDepTable:
    """Dependency tracker for int-tuple keys (mask or counter mode)."""

    __slots__ = ("_t", "_lib")

    def __init__(self, capacity: int = 1 << 16) -> None:
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core unavailable")
        self._t = self._lib.pt_dep_table_create(capacity)
        if not self._t:
            raise MemoryError("pt_dep_table_create failed")

    @staticmethod
    def key_ok(key) -> bool:
        if isinstance(key, int):
            return True
        return (isinstance(key, tuple) and len(key) <= _KEY_MAX
                and all(isinstance(k, int) for k in key))

    @staticmethod
    def _pack(key) -> Tuple[ctypes.Array, int]:
        # fresh array per call: update() is invoked concurrently from worker
        # threads, a shared buffer would race before the C side copies it
        if isinstance(key, int):
            return (ctypes.c_int64 * 1)(key), 1
        return (ctypes.c_int64 * len(key))(*key), len(key)

    def update(self, key, contribution: int, goal: int, count_mode: bool) -> bool:
        buf, klen = self._pack(key)
        rc = self._lib.pt_dep_table_update(self._t, buf, klen, contribution,
                                           goal, 1 if count_mode else 0)
        if rc < 0:
            raise RuntimeError(f"native dep table error {rc}")
        return rc == 1

    def get(self, key) -> int:
        buf, klen = self._pack(key)
        return self._lib.pt_dep_table_get(self._t, buf, klen)

    def __len__(self) -> int:
        return self._lib.pt_dep_table_size(self._t)

    def __del__(self) -> None:
        try:
            if self._t and self._lib:
                self._lib.pt_dep_table_destroy(self._t)
        except Exception:  # noqa: BLE001 - interpreter shutdown
            pass


class NativeZone:
    """Native zone allocator backend (see utils/zone_malloc.py)."""

    __slots__ = ("_z", "_lib")

    def __init__(self, total_bytes: int, unit: int = 1 << 20) -> None:
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core unavailable")
        self._z = self._lib.pt_zone_create(total_bytes, unit)

    def alloc(self, nbytes: int) -> Optional[int]:
        off = self._lib.pt_zone_alloc(self._z, nbytes)
        return None if off < 0 else off

    def free(self, offset: int, nbytes: int) -> None:
        self._lib.pt_zone_free(self._z, offset, nbytes)

    def stats(self) -> dict:
        out = (ctypes.c_int64 * 4)()
        self._lib.pt_zone_stats(self._z, out)
        return {"free_bytes": out[0], "in_use_bytes": out[1],
                "hwm_bytes": out[2], "largest_hole_bytes": out[3]}

    def __del__(self) -> None:
        try:
            if self._z and self._lib:
                self._lib.pt_zone_destroy(self._z)
        except Exception:  # noqa: BLE001
            pass


