"""Multi-controller SPMD: one GLOBAL device mesh spanning OS processes.

The true multi-host shape (the reference's mpirun-over-NCCL/MPI scale-out,
SURVEY §2.3/§2.8): each host runs ONE controller process that owns its
local chips; ``jax.distributed.initialize`` joins them so `jax.devices()`
is the GLOBAL device list, a `Mesh` spans every host, and XLA collectives
inside `shard_map`/`pjit` cross the host boundary on ICI/DCN (Gloo on the
CPU rehearsal backend) — no framework-level message passing at all.

This module is the thin layer that makes the shape usable and testable:

* :func:`init_multihost` — controller bring-up (coordinator rendezvous),
  env-driven so the same script runs under any launcher;
* :func:`global_mesh` — a named mesh over ALL processes' devices;
* :func:`host_local_to_global` — per-host shards assembled into one global
  array (`jax.make_array_from_process_local_data`), the input-feeding
  idiom (each host contributes its local batch);
* :func:`run_multicontroller` — N real controller processes on localhost
  with virtual CPU devices, for tests/rehearsal (the mpirun stand-in).

Every `parallel/` building block (train steps, ring attention, MoE,
pipeline) is mesh-agnostic: handed a global mesh from here, the SAME
compiled program scales from one chip to a pod.
"""

from __future__ import annotations

import os
from typing import Any, Callable, List, Optional, Sequence, Tuple

ENV_COORD = "PARSEC_TPU_COORDINATOR"
ENV_PROC = "PARSEC_TPU_PROCESS_ID"
ENV_NPROC = "PARSEC_TPU_NUM_PROCESSES"


def cpu_collectives_available() -> bool:
    """True when the installed jax can run MULTIPROCESS computations on
    the CPU rehearsal backend (a cross-process collectives implementation
    — Gloo — is wired into the CPU client). Without it, any multi-
    controller CPU job dies with "Multiprocess computations aren't
    implemented on the CPU backend": an environment limit, not a runtime
    bug, so tests skip on it instead of failing."""
    try:
        import jax
        from jax._src.lib import xla_extension as xe
        if not hasattr(xe, "make_gloo_tcp_collectives"):
            return False
        return _cpu_collectives_flag(jax) is not None
    except Exception:  # noqa: BLE001 - any probe failure = unavailable
        return False


def _cpu_collectives_flag(jax):
    """Current value of the CPU-collectives config flag, or None when the
    installed jax has no such flag. Registered config options are not
    always exposed as ``jax.config.<name>`` attributes (0.4.x keeps them
    in the holder registry), so probe both."""
    name = "jax_cpu_collectives_implementation"
    val = getattr(jax.config, name, None)
    if val is not None:
        return val
    holders = getattr(jax.config, "_value_holders", None) or {}
    if name in holders:
        try:
            return holders[name].value or "none"
        except Exception:  # noqa: BLE001
            return "none"
    return None


def _enable_cpu_collectives() -> None:
    """Multi-controller on the CPU rehearsal backend needs a collectives
    implementation compiled into the CPU client (the default is none —
    jax then refuses multiprocess computations outright). Select Gloo
    BEFORE the backend initializes; a no-op when unsupported or when the
    user already chose one (e.g. mpi via JAX_CPU_COLLECTIVES_*)."""
    import jax
    try:
        if _cpu_collectives_flag(jax) in (None, "none"):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:  # noqa: BLE001 - older/newer jax: leave the default
        pass


def init_multihost(coordinator: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> int:
    """Join this controller to the job (env fallbacks: PARSEC_TPU_
    COORDINATOR / PROCESS_ID / NUM_PROCESSES). Returns the process id.
    Call BEFORE any other jax API touches the backend."""
    import jax
    coordinator = coordinator or os.environ.get(ENV_COORD)
    num_processes = int(num_processes if num_processes is not None
                        else os.environ.get(ENV_NPROC, "1"))
    process_id = int(process_id if process_id is not None
                     else os.environ.get(ENV_PROC, "0"))
    if num_processes > 1:
        plats = str(getattr(jax.config, "jax_platforms", "") or "")
        if plats.startswith("cpu"):
            _enable_cpu_collectives()
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    return process_id


def global_mesh(axis_names: Sequence[str],
                shape: Optional[Sequence[int]] = None):
    """A mesh over the GLOBAL device list (every process's chips). With no
    ``shape``, one axis spans all devices; otherwise reshape to ``shape``
    (must multiply to the global device count)."""
    import numpy as np
    import jax
    from jax.sharding import Mesh
    devs = np.array(jax.devices())
    if shape is None:
        shape = (devs.size,) if len(axis_names) == 1 else None
    if shape is None or int(np.prod(shape)) != devs.size:
        raise ValueError(f"mesh shape {shape} != {devs.size} global devices")
    return Mesh(devs.reshape(tuple(shape)), tuple(axis_names))


def host_local_to_global(mesh, pspec, host_data):
    """Assemble per-host data into one global sharded array: every process
    passes ITS slice of the global batch (equal leading-dim shares in
    process order), and the result is addressable wherever sharding says.
    The multi-host input pipeline idiom."""
    import jax
    from jax.sharding import NamedSharding
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, pspec), host_data)


def fetch_replicated(x):
    """Host value of a replicated/global array on every process
    (process-local addressable shards suffice for replicated outputs)."""
    import numpy as np
    import jax
    shard = x.addressable_shards[0]
    return np.asarray(jax.device_get(shard.data))


# ---------------------------------------------------------------- launcher

def run_multicontroller(nprocs: int, script: str,
                        devices_per_proc: int = 4,
                        timeout: float = 240.0,
                        extra_env: Optional[dict] = None) -> List[str]:
    """Run ``script`` as N controller processes on localhost, each with
    ``devices_per_proc`` virtual CPU devices, joined into ONE jax job
    (the mpirun stand-in for tests; ``nprocs=1`` runs plain single-
    controller with the same env plumbing). Returns each stdout.

    Process management mirrors :mod:`parsec_tpu.launch`: one JOB-wide
    deadline (a hung collective must not serialize N full timeouts),
    cleanup in a ``finally`` reaching whole process GROUPS (controllers
    spawn their own children)."""
    import subprocess
    import sys
    import time

    from ..comm.tcp import _free_port
    from ..launch import _kill_group

    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(nprocs):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env[ENV_COORD] = coord
        env[ENV_PROC] = str(pid)
        env[ENV_NPROC] = str(nprocs)
        # replace (not append after) any inherited device-count flag: the
        # caller may itself run under a virtual-device env, and relying on
        # last-flag-wins is fragile
        kept = [f for f in env.get("XLA_FLAGS", "").split()
                if not f.startswith("--xla_force_host_platform_device_count")]
        kept.append(f"--xla_force_host_platform_device_count="
                    f"{devices_per_proc}")
        env["XLA_FLAGS"] = " ".join(kept)
        if extra_env:
            env.update(extra_env)
        procs.append(subprocess.Popen(
            [sys.executable, script], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, start_new_session=True))
    outs: List[str] = []
    failed: List[str] = []
    deadline = time.monotonic() + timeout
    try:
        for p in procs:
            try:
                out, _ = p.communicate(
                    timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                import signal
                _kill_group(p, signal.SIGKILL)
                out, _ = p.communicate()
                failed.append(f"controller timed out:\n{out[-1500:]}")
            outs.append(out or "")
            if p.returncode not in (0, None):
                failed.append(f"controller rc={p.returncode}:\n"
                              f"{(out or '')[-1500:]}")
    finally:
        import signal
        for p in procs:
            if p.poll() is None:
                _kill_group(p, signal.SIGKILL)
    if failed:
        # EVERY failing controller's tail rides along: the root cause
        # (e.g. a collectives-layer abort) often lives in the peer that
        # died first, not the one that reported first
        raise RuntimeError("\n---\n".join(failed))
    return outs
