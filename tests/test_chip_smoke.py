"""The chip smoke in tier-1: the same script under an explicit
``JAX_PLATFORMS=cpu`` at a tiny size (tens of clients, Pallas
interpreted), its refusals, and the start-up rules it stands on — a
device path never runs on a CPU nobody asked for, a failed warm-up is
fatal, the smoke's parent never imports jax, native libraries are keyed
on source content."""

import ast
import ctypes
import glob
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

# the no-chip refusals below hold on the CPU-only hosts tier-1 runs on
no_accelerator = pytest.mark.skipif(
    bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*")),
    reason="an accelerator is attached to this host")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH",
                        "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = REPO
    env.update(extra)
    return env


def _json_lines(stdout: str) -> list:
    rows = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            try:
                rows.append(json.loads(line))
            except ValueError:
                pass
    return rows


def test_smoke_tiny_on_explicit_cpu():
    """All three legs end to end on the CPU: real marshal + broker
    binaries, clientpack processes over TCP, the four-shard mesh group on
    virtual devices; per-leg JSON, then the result line, exit code 0."""
    proc = subprocess.run(
        [sys.executable, SMOKE, "--tiny"], capture_output=True, text=True,
        timeout=600, env=_env(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=8"))
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    rows = _json_lines(proc.stdout)
    legs = {r["leg"]: r for r in rows if "leg" in r}
    assert set(legs) == {"kernels", "served", "mesh"}, proc.stdout[-2000:]
    device = {"platform": "cpu", "kind": "cpu", "count": 8}
    assert all(leg["ok"] and leg["device"] == device
               for leg in legs.values())
    kernels = {c["kernel"].split(" U=")[0]: c
               for c in legs["kernels"]["checks"]}
    assert all(c["match"] for c in legs["kernels"]["checks"])
    # off the chip the Pallas kernels are exercised through the
    # interpreter; the untileable lanes take the XLA twin by rule
    assert kernels["dense"]["impl"] in ("pallas", "xla")
    impls = [(c["impl"], c["interpret"])
             for c in legs["kernels"]["checks"]]
    assert ("pallas", True) in impls and ("xla", False) in impls
    assert kernels["ragged"]["impl"] == "pallas"
    served = legs["served"]
    assert served["subscribers"] == 24
    assert served["users_connected"] == 25
    assert served["frames_staged"] == served["frames_sent"] > 0
    assert served["device_deliveries"] == served["deliveries_expected"]
    assert served["device_steps"] > 0 and served["broker_exit"] == 0
    assert [b["burst"] for b in served["bursts"]] == [
        "1KB round 0", "1KB round 1", "10KB", "directs"]
    mesh = legs["mesh"]
    assert mesh["collectives_per_tick"] == 1
    assert len(mesh["output_devices"]) == mesh["shards"] == 4
    assert mesh["steps"] > 0 and mesh["device_deliveries"] > 0
    # the result line is the LAST line of stdout
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {
        "ok": True, "device": device}


def test_smoke_without_tiny_refuses_the_cpu():
    """No accelerator, no result: held to the CPU the script fails at
    once and prints nothing that could be read as a pass."""
    proc = subprocess.run(
        [sys.executable, SMOKE], capture_output=True, text=True,
        timeout=120, env=_env(JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert not _json_lines(proc.stdout), proc.stdout
    # and the dry run is never a way to pass without saying so
    proc = subprocess.run(
        [sys.executable, SMOKE, "--tiny"], capture_output=True, text=True,
        timeout=120, env=_env())
    assert proc.returncode != 0 and not _json_lines(proc.stdout)


@no_accelerator
def test_no_chip_and_no_jax_platforms_exits_nonzero(tmp_path):
    """With JAX_PLATFORMS unset JAX itself drops to the CPU when no
    accelerator initialises; the smoke, bench.py and a --device-plane
    broker must each refuse that CPU instead of running on it."""
    smoke = subprocess.run([sys.executable, SMOKE], capture_output=True,
                           text=True, timeout=300, env=_env())
    assert smoke.returncode != 0
    assert not any(r.get("ok") for r in _json_lines(smoke.stdout))
    bench = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300, env=_env())
    assert bench.returncode != 0 and "refusing" in bench.stderr
    assert not _json_lines(bench.stdout)
    broker = subprocess.run(
        [sys.executable, "-m", "pushcdn_tpu.bin.broker",
         "--discovery-endpoint", str(tmp_path / "d.sqlite"),
         "--public-bind-endpoint", "127.0.0.1:0",
         "--private-bind-endpoint", "127.0.0.1:0",
         "--user-transport", "tcp", "--device-plane"],
        capture_output=True, text=True, timeout=300, env=_env())
    assert broker.returncode != 0 and "refusing" in broker.stderr


_WARMUP_RAISES = """
import sys
from pushcdn_tpu.broker import device_plane

def boom(self):
    raise RuntimeError("injected warm-up failure")

device_plane.DevicePlane._warmup = boom
from pushcdn_tpu.bin import broker
sys.argv = ["broker"] + sys.argv[1:]
broker.main()
"""


def test_device_plane_broker_whose_warmup_raises_exits_nonzero(tmp_path):
    """A broker asked for a device plane never comes up as a silent host
    broker: the warm-up's exception ends the process."""
    proc = subprocess.run(
        [sys.executable, "-c", _WARMUP_RAISES,
         "--discovery-endpoint", str(tmp_path / "d.sqlite"),
         "--public-advertise-endpoint", "127.0.0.1:1",
         "--public-bind-endpoint", "127.0.0.1:0",
         "--private-advertise-endpoint", "127.0.0.1:2",
         "--private-bind-endpoint", "127.0.0.1:0",
         "--user-transport", "tcp", "--device-plane"],
        capture_output=True, text=True, timeout=120,
        env=_env(JAX_PLATFORMS="cpu"))
    assert proc.returncode not in (0, None), proc.stdout + proc.stderr
    assert "injected warm-up failure" in proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# the parent never imports jax
# ---------------------------------------------------------------------------

# modules that pull jax in; the smoke may import them only inside a leg
# that runs as a chip-owning child
_JAX_SIDE = ("jax", "pushcdn_tpu.parallel", "pushcdn_tpu.ops",
             "pushcdn_tpu.broker.device_plane",
             "pushcdn_tpu.broker.mesh_group",
             "pushcdn_tpu.testing.mesh_cluster", "__graft_entry__")
_CHILD_LEGS = {"leg_kernels", "leg_mesh", "_np_delivery", "_seeded_table",
               "_seeded_frames"}


def _imports(node):
    for sub in ast.walk(node):
        if isinstance(sub, ast.Import):
            yield from (a.name for a in sub.names)
        elif isinstance(sub, ast.ImportFrom) and sub.module:
            yield sub.module
            yield from (f"{sub.module}.{a.name}" for a in sub.names)


def test_smoke_parent_imports_no_jax_statically():
    tree = ast.parse(open(SMOKE).read())
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in _CHILD_LEGS:
            continue
        # parallel.runtime is the one jax-free module of that package
        # (lazy imports): the parent reads cpu_requested from it
        bad = [m for m in _imports(node)
               if not m.startswith("pushcdn_tpu.parallel.runtime")
               and (m in _JAX_SIDE or m.startswith(
                   tuple(p + "." for p in _JAX_SIDE)))]
        assert not bad, (getattr(node, "name", node), bad)


def test_host_side_modules_leave_jax_unimported():
    """What the smoke's parent, the marshal, the client binary and the
    clientpack import must not drag jax in (they would hold the chip)."""
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import chip_smoke, pushcdn_tpu.native\n"
        "import pushcdn_tpu.parallel.runtime\n"
        "import pushcdn_tpu.bin.common, pushcdn_tpu.bin.marshal\n"
        "import pushcdn_tpu.bin.client, pushcdn_tpu.client\n"
        "import pushcdn_tpu.testing.clientpack\n"
        "import pushcdn_tpu.proto.crypto.signature\n"
        "import pushcdn_tpu.proto.transport\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n" % REPO)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=_env())
    assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# start-up rules
# ---------------------------------------------------------------------------


def test_compile_cache_is_placeable_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set -> nothing set in code (JAX reads it
    itself); unset on an accelerator -> the fixed .build/jax_cache; an
    explicit CPU places none."""
    code = (
        "import sys, jax\n"
        "from pushcdn_tpu.parallel import runtime\n"
        "rt = runtime.init('t')\n"
        "print(rt.cache_dir, jax.config.jax_compilation_cache_dir,"
        " jax.config.jax_persistent_cache_min_compile_time_secs)\n")
    placed = str(tmp_path / "cache")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=_env(JAX_PLATFORMS="cpu",
                              JAX_COMPILATION_CACHE_DIR=placed))
    assert out.stdout.split() == [placed, placed, "0.0"], out.stderr
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=_env(JAX_PLATFORMS="cpu"))
    assert out.stdout.split() == ["None", "None", "0.0"], out.stderr
    from pushcdn_tpu.parallel import runtime
    assert runtime.DEFAULT_CACHE_DIR == os.path.join(
        REPO, ".build", "jax_cache")


def test_dispatch_rule_is_shape_and_backend_only():
    from pushcdn_tpu.ops.delivery_kernel import selects_pallas
    from pushcdn_tpu.ops.ragged_delivery import ragged_selects_pallas
    # on this (CPU) backend auto never picks Pallas; forced, only where
    # the shape tiles — the wide lane and the latency slice do not
    assert not selects_pallas(1024, 1024) and not ragged_selects_pallas()
    assert selects_pallas(1024, 1024, True) and selects_pallas(64, 128, True)
    assert not selects_pallas(1024, 64, True)
    assert not selects_pallas(1024, 8, True)
    assert ragged_selects_pallas(True)


def test_native_library_is_keyed_on_source_content(tmp_path):
    """A .so is loaded only if it was compiled from exactly the source
    bytes present — whatever the mtimes say (a copied tree does not keep
    them) — and the library it replaces is removed."""
    from pushcdn_tpu import native
    src = tmp_path / "unit.cpp"
    name = f"testunit{os.getpid()}"
    try:
        src.write_text('extern "C" int answer() { return 1; }\n')
        first = native.lib_path(name, (str(src),))
        assert native._build_lib(name, (str(src),), ctypes.CDLL).answer() == 1
        assert os.path.exists(first)
        old = os.stat(src)
        src.write_text('extern "C" int answer() { return 2; }\n')
        # new content, OLDER mtime than the cached library
        os.utime(src, (old.st_atime - 3600, old.st_mtime - 3600))
        second = native.lib_path(name, (str(src),))
        assert second != first
        assert native._build_lib(name, (str(src),), ctypes.CDLL).answer() == 2
        assert not os.path.exists(first)
    finally:
        for path in glob.glob(os.path.join(
                native._BUILD_DIR, f"libpushcdn_{name}-*.so")):
            os.remove(path)
