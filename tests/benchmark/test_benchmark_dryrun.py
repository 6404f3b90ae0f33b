"""The benchmark end to end as a dry run (ISSUE 22): the real command on
an explicit ``JAX_PLATFORMS=cpu`` with the user count cut to 16 by a
test-only argument of the harness, and the refusals its contract asks
for. Under a minute, with time limits of its own."""

import ast
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(REPO, "benchmark", "run.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH",
                        "JAX_COMPILATION_CACHE_DIR")}
    env.update(extra)
    return env


def test_dry_run_echo_sparse_on_explicit_cpu():
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "broker1-1k.echo-sparse",
         "--seed", "11", "--seconds", "2", "--trace", "0",
         "--test-size", "16,2,2"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=_env(JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    # every number ``correct`` rests on, beside its limit: all met
    assert len(line["checks"]) >= 10
    assert all(number == limit for number, limit in line["checks"].values())
    assert line["checks"]["users_connected"] == [16, 16]
    assert proc.stderr.strip().splitlines()[-1] == \
        "check accelerator_missing: 0 (limit 0)"
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 100
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {
        "delivery_p50_ms", "delivery_p99_ms", "broker_cpu_us_per_delivery",
        "setup_s"}
    for metric in line["metrics"].values():
        assert metric["value"] > 0 and metric["unit"]
    # the parent would have refused to print had it imported jax; the
    # earlier lines carry the counts the last one leaves out
    assert "[bench] window:" in proc.stdout
    assert "latency samples" in proc.stdout


def test_traced_dry_run_shows_the_control_cell_bypassed():
    """``echo-sparse`` is the bypass control: none of its own frames is
    staged and no step runs in the window, yet the traced span (warm-up
    and the window's first seconds) sees the warm-up prelude on the
    device, so ``busy_s`` is above 0 without any traffic made for it."""
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "broker1-1k.echo-sparse",
         "--seed", "12", "--seconds", "2", "--trace", "1",
         "--test-size", "16,2,2"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=_env(JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-3000:]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["bypassed_share"] == 1.0
    assert metrics["steps_per_s"] == 0.0
    assert "staged_share" not in metrics and "step_device_us" not in metrics
    assert "delivery_p50_ms" not in metrics  # traced: no end-to-end metric
    device = line["device"]
    assert 0 < device["busy_s"] < 0.05 * device["window_s"]
    assert 4.0 < device["window_s"] < 5.5  # 0.3 + 2 s warm-up + 2 s window
    assert len(line["breakdown"]["device_ops"]) >= 1


def test_the_parent_never_imports_jax_statically():
    """No module the parent loads imports jax at module level (the run
    asserts the same of ``sys.modules`` before it prints)."""
    parent_modules = ["run.py", "manifest.py", "reference.py",
                      os.path.join("loadgen", "plan.py"),
                      os.path.join("loadgen", "hist.py")]
    for rel in parent_modules:
        with open(os.path.join(REPO, "benchmark", rel)) as f:
            tree = ast.parse(f.read())
        for node in tree.body:
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            assert not any(n == "jax" or n.startswith("jax.") for n in names), rel


def test_no_result_outside_a_checkout(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own paths the command exits non-zero and prints no result."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"),
         "--workload", "broker1-1k.echo-sparse", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=60, cwd=tmp_path,
        env=_env(JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "not in a checkout" in proc.stderr


def test_unknown_workload_is_refused():
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "no-such.cell", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=60, cwd=REPO,
        env=_env(JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and "{" not in proc.stdout
