"""CI lint gate (ISSUE 4 satellite): run ``ruff check`` over the package,
tests, benches and scripts with the repo's ruff.toml baseline, so new
instrumentation code lands lint-clean.

The container image may not ship ruff (it is not pip-installable here);
in that case the test SKIPS with an explicit reason rather than
vacuously passing — the gate engages wherever ruff exists.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ruff_cmd():
    exe = shutil.which("ruff")
    if exe:
        return [exe]
    try:
        import ruff  # noqa: F401
        return [sys.executable, "-m", "ruff"]
    except ImportError:
        return None


# the directories the gate covers — every new observability file (ISSUE 5:
# proto/health.py, scripts/trace_report.py, tests/test_health.py,
# tests/test_trace_report.py) lives inside them and is asserted present
# below so a future move out of the linted tree fails loudly
RUFF_SCOPE = ["pushcdn_tpu", "tests", "benches", "scripts", "bench.py",
              "chip_smoke.py"]

ISSUE5_FILES = [
    "pushcdn_tpu/proto/health.py",
    "scripts/trace_report.py",
    "tests/test_health.py",
    "tests/test_trace_report.py",
]


ISSUE13_FILES = [
    # the io_uring host data plane (ISSUE 13): native layer, ctypes
    # binding, transport engine, syscall-attribution interposer binding,
    # and the equivalence/fault suite
    "pushcdn_tpu/proto/transport/uring.py",
    "pushcdn_tpu/native/uring.py",
    "pushcdn_tpu/native/syscount.py",
    "pushcdn_tpu/testing/routebench.py",
    "tests/test_uring.py",
]


def test_issue5_files_inside_lint_scope():
    for rel in ISSUE5_FILES:
        assert os.path.exists(os.path.join(REPO, rel)), rel
        assert any(rel == scope or rel.startswith(scope + "/")
                   for scope in RUFF_SCOPE), \
            f"{rel} is outside the ruff gate's scope {RUFF_SCOPE}"


ISSUE14_FILES = [
    # durable topics (ISSUE 14): retention rings + replay subscribe +
    # wildcard namespace, the seeded handover/lease suite, and the
    # consensus replay_catchup scenario wiring
    "pushcdn_tpu/broker/retention.py",
    "pushcdn_tpu/proto/topic.py",
    "tests/test_retention.py",
    "benches/consensus_bench.py",
]


def test_issue14_files_inside_lint_scope():
    for rel in ISSUE14_FILES:
        assert os.path.exists(os.path.join(REPO, rel)), rel
        assert any(rel == scope or rel.startswith(scope + "/")
                   for scope in RUFF_SCOPE), \
            f"{rel} is outside the ruff gate's scope {RUFF_SCOPE}"


def test_issue13_files_inside_lint_scope():
    for rel in ISSUE13_FILES:
        assert os.path.exists(os.path.join(REPO, rel)), rel
        assert any(rel == scope or rel.startswith(scope + "/")
                   for scope in RUFF_SCOPE), \
            f"{rel} is outside the ruff gate's scope {RUFF_SCOPE}"


ISSUE17_FILES = [
    # the fused data-plane pump (ISSUE 17): native composition kernel,
    # ctypes binding, policy plane, and the fault/equivalence suites
    "native/pump.cpp",
    "pushcdn_tpu/native/pump.py",
    "pushcdn_tpu/proto/transport/pump.py",
    "tests/test_uring.py",
    "tests/test_route_cutthrough.py",
]


def test_issue17_files_inside_lint_scope():
    for rel in ISSUE17_FILES:
        assert os.path.exists(os.path.join(REPO, rel)), rel
        if rel.endswith(".cpp"):
            continue  # native sources sit outside the ruff gate
        assert any(rel == scope or rel.startswith(scope + "/")
                   for scope in RUFF_SCOPE), \
            f"{rel} is outside the ruff gate's scope {RUFF_SCOPE}"


ISSUE19_FILES = [
    # native-path telemetry + flow accounting + collector (ISSUE 19):
    # shm telemetry block (C), class taxonomy, metrics families, the
    # one-pane collector, and the telemetry/class test surfaces
    "native/io_uring.cpp",
    "native/pump.cpp",
    "pushcdn_tpu/proto/flowclass.py",
    "pushcdn_tpu/proto/metrics.py",
    "pushcdn_tpu/native/uring.py",
    "scripts/cdn_top.py",
    "tests/test_uring.py",
    "tests/test_route_cutthrough.py",
]


def test_issue19_files_inside_lint_scope():
    for rel in ISSUE19_FILES:
        assert os.path.exists(os.path.join(REPO, rel)), rel
        if rel.endswith(".cpp"):
            continue  # native sources sit outside the ruff gate
        assert any(rel == scope or rel.startswith(scope + "/")
                   for scope in RUFF_SCOPE), \
            f"{rel} is outside the ruff gate's scope {RUFF_SCOPE}"


ISSUE20_FILES = [
    # frame-fate conservation ledger (ISSUE 20): fate taxonomy + per-link
    # counters + auditor + SLO burn engine, the wire/class rule, the
    # instrumented terminal paths, mesh-wide audit tooling, and the
    # client-side gap detector
    "native/io_uring.cpp",
    "native/pump.cpp",
    "pushcdn_tpu/proto/ledger.py",
    "pushcdn_tpu/proto/flowclass.py",
    "pushcdn_tpu/proto/metrics.py",
    "pushcdn_tpu/proto/transport/base.py",
    "pushcdn_tpu/native/uring.py",
    "pushcdn_tpu/broker/broker.py",
    "pushcdn_tpu/broker/connections.py",
    "pushcdn_tpu/broker/sharding.py",
    "pushcdn_tpu/broker/admission.py",
    "pushcdn_tpu/broker/retention.py",
    "pushcdn_tpu/broker/tasks/handlers.py",
    "pushcdn_tpu/broker/tasks/cutthrough.py",
    "pushcdn_tpu/broker/tasks/senders.py",
    "pushcdn_tpu/broker/tasks/sync.py",
    "pushcdn_tpu/client/client.py",
    "pushcdn_tpu/testing/clientpack.py",
    "pushcdn_tpu/bin/broker.py",
    "scripts/cdn_top.py",
    "scripts/local_cluster.py",
    "tests/test_ledger.py",
]


def test_issue20_files_inside_lint_scope():
    for rel in ISSUE20_FILES:
        assert os.path.exists(os.path.join(REPO, rel)), rel
        if rel.endswith(".cpp"):
            continue  # native sources sit outside the ruff gate
        assert any(rel == scope or rel.startswith(scope + "/")
                   for scope in RUFF_SCOPE), \
            f"{rel} is outside the ruff gate's scope {RUFF_SCOPE}"


def test_ruff_check_clean():
    cmd = _ruff_cmd()
    if cmd is None:
        pytest.skip("ruff not installed in this image; lint gate inactive")
    proc = subprocess.run(
        [*cmd, "check", *RUFF_SCOPE],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, \
        f"ruff check found issues:\n{proc.stdout}\n{proc.stderr}"
