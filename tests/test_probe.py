"""The one backend rule: discovery is ``jax.devices()`` in-process.

No subprocess probe, no cache file, no platform switch from library code: a
CPU pin (``JAX_PLATFORMS=cpu``, the launcher's ``--cpu``) is the only way
onto the CPU, and a run that finds no chip must not look like one that did.
The chip itself is out of reach here; these tests hold the rule's edges that
a CPU host can show.
"""

import os
import subprocess
import sys

import jax
import pytest

from parsec_tpu import launch
from parsec_tpu.device import tpu as tpu_mod
from parsec_tpu.utils import mca

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _no_subprocess(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("backend discovery spawned a subprocess")
    monkeypatch.setattr(subprocess, "run", boom)
    monkeypatch.setattr(subprocess, "Popen", boom)


def test_cpu_pin_honoured_without_subprocess(monkeypatch):
    """Under the CPU pin discovery registers no accelerator, spawns nothing,
    and leaves ``jax_platforms`` exactly as it found it."""
    from parsec_tpu.core.context import Context
    _no_subprocess(monkeypatch)
    before = jax.config.jax_platforms
    assert tpu_mod.discover_tpu_devices() == []
    ctx = Context(nb_cores=1)
    try:
        assert [d.name for d in ctx.devices.devices] == ["cpu", "recursive"]
    finally:
        ctx.fini()
    assert jax.config.jax_platforms == before == "cpu"


def test_over_cpu_test_mode_registers_one_host_device(monkeypatch):
    _no_subprocess(monkeypatch)
    mca.set("device_tpu_over_cpu", True)
    mca.set("device_tpu_over_cpu_index", 3)
    try:
        devs = tpu_mod.discover_tpu_devices()
    finally:
        mca.params.unset("device_tpu_over_cpu")
        mca.params.unset("device_tpu_over_cpu_index")
    assert [d.jax_device for d in devs] == [jax.devices()[3]]


def test_discovery_failure_propagates(monkeypatch):
    """A backend that cannot initialise is an error, not a quiet CPU run."""
    from parsec_tpu.core.context import Context

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        Context(nb_cores=1)


class _FakeChip:
    platform = "tpu"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_accelerator_without_bytes_limit_raises():
    assert tpu_mod._device_bytes_limit(_FakeChip({"bytes_limit": 1 << 34})) \
        == 1 << 34
    for stats in (None, {}, {"bytes_in_use": 5}):
        with pytest.raises(RuntimeError, match="no bytes_limit"):
            tpu_mod._device_bytes_limit(_FakeChip(stats))


def test_bind_devices_more_ranks_than_chips_errors(monkeypatch, capsys):
    """Ranks are never moved to the CPU: with --bind-devices, more ranks
    than chips refuses to start, before anything is spawned."""
    _no_subprocess(monkeypatch)
    monkeypatch.setattr(launch, "local_chip_count", lambda: 1)
    with pytest.raises(SystemExit) as e:
        launch.main(["-n", "2", "--bind-devices", "nonexistent.py"])
    assert e.value.code == 2
    assert "2 ranks but this host has 1 chip" in capsys.readouterr().err


def test_unbound_ranks_on_a_chip_host_error(monkeypatch, capsys):
    """Several ranks that would all claim the host's chips must say how
    they share them: --bind-devices or --cpu."""
    _no_subprocess(monkeypatch)
    monkeypatch.setattr(launch, "local_chip_count", lambda: 4)
    with pytest.raises(SystemExit):
        launch.main(["-n", "2", "nonexistent.py"])
    assert "--bind-devices" in capsys.readouterr().err


def test_chip_count_is_what_the_process_may_open(tmp_path):
    """Four chips on the PCI bus, one vfio group granted: one chip. (The
    one-chip machine of the chip tool looks exactly like this; counting
    the bus alone started a four-rank phase there.)"""
    pci, vfio, groups = (tmp_path / d for d in ("pci", "vfio", "groups"))
    vfio.mkdir()
    for i, dev_id in enumerate(["0x0063"] * 4 + ["0x1234"]):
        d = pci / f"0000:00:0{i}.0"
        d.mkdir(parents=True)
        (d / "vendor").write_text("0x1ae0\n")
        (d / "device").write_text(dev_id + "\n")
        (groups / str(i)).mkdir(parents=True)
        (d / "iommu_group").symlink_to(groups / str(i))
    other = pci / "0000:00:09.0"            # another vendor's device
    other.mkdir()
    (other / "vendor").write_text("0x8086\n")
    (other / "device").write_text("0x0063\n")
    count = lambda: launch.local_chip_count(str(pci), str(vfio))  # noqa: E731
    assert count() == 0
    (vfio / "0").touch()
    (vfio / "vfio").touch()
    assert count() == 1
    for g in ("1", "2", "3", "4"):          # group 4 is not a TPU
        (vfio / g).touch()
    assert count() == 4


def test_chip_binding_is_environment_only():
    """Rank i gets exactly chip i through variables libtpu reads at start-up
    — nothing a child could apply after its backend is up."""
    envs = [launch.chip_env(i) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" and
               e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert launch.local_chip_count() == 0        # this host has none


def test_launcher_and_smoke_parents_do_not_import_jax():
    """A process that has touched JAX holds the chip; the two parents that
    start chip-owning children must stay clear of it — through the native
    build and the chip count included."""
    src = ("import sys; sys.path.insert(0, %r); "
           "import chip_smoke, parsec_tpu.launch as L; "
           "from parsec_tpu import native; from parsec_tpu.utils import "
           "compile_cache; native.require_all(); L.local_chip_count(); "
           "compile_cache.export({}); "
           "bad = [m for m in sys.modules if m == 'jax' or "
           "m.startswith(('jax.', 'jaxlib'))]; "
           "assert not bad, bad" % REPO)
    p = subprocess.run([sys.executable, "-c", src], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_launcher_hands_on_whole_lines():
    """Four ranks that print long lines at the same moment, unbuffered (a
    ``print`` is then two writes, text and newline): the launcher's stdout
    still holds every line whole. The ranks of a benchmark cell report so
    after their last barrier, and their parent parses line by line."""
    import re
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    p = subprocess.run(
        [sys.executable, "-m", "parsec_tpu.launch", "-n", "4", "--cpu",
         os.path.join("tests", "_launch_shout.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.splitlines()
    broken = [l[:80] for l in lines
              if not re.fullmatch(r"RANK \d \d+ x{1500}", l)]
    assert len(lines) == 4 * 150 and not broken, (len(lines), broken[:3])


def test_launcher_ends_when_its_reader_is_gone():
    """A launcher whose stdout reader dies does not leave its ranks blocked
    on full pipes until the job's deadline (holding the multiproc lock all
    the while): their next write fails and the job ends at once."""
    import time
    p = subprocess.Popen(
        [sys.executable, "-m", "parsec_tpu.launch", "-n", "2", "--cpu",
         "--timeout", "120", os.path.join("tests", "_launch_shout.py"),
         "1000000"],
        cwd=REPO, env=dict(os.environ, PYTHONUNBUFFERED="1"),
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
    try:
        assert p.stdout.readline().startswith(b"RANK ")
        t0 = time.monotonic()
        p.stdout.close()
        assert p.wait(timeout=60) != 0
        assert time.monotonic() - t0 < 60
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()


def test_compile_cache_placed_from_outside(monkeypatch):
    from parsec_tpu.utils import compile_cache
    env = {}
    compile_cache.export(env)
    assert env[compile_cache.ENV_DIR] == os.path.join(REPO, ".cache", "jax")
    env = {compile_cache.ENV_DIR: "/somewhere/else"}
    compile_cache.export(env)
    assert env[compile_cache.ENV_DIR] == "/somewhere/else"
    env = {"JAX_PLATFORMS": "cpu"}          # CPU-pinned: left alone
    compile_cache.export(env)
    assert compile_cache.ENV_DIR not in env
    before = jax.config.jax_compilation_cache_dir
    compile_cache.enable()                  # this process is CPU-pinned
    assert jax.config.jax_compilation_cache_dir == before


def test_chip_smoke_rehearsal_passes():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                        "--rehearsal"], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])
    import json
    lines = p.stdout.strip().splitlines()
    # the last line holds the contract's keys and no others
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int
    assert lines[-2].startswith("SUMMARY ")
    summary = json.loads(lines[-2][len("SUMMARY "):])
    assert summary["rehearsal"] is True
    assert set(summary["phases"]) == {"kernels", "dtd-gemm", "dtd-potrf",
                                      "ptg-gemm", "launch-4"}


def test_chip_smoke_fails_without_a_chip():
    """Plain chip_smoke.py on the CPU backend: non-zero exit, and no result
    line for a reader to mistake for a pass."""
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "no accelerator" in p.stderr
