"""Manifest lint (ISSUE 22): ``BENCHMARK.json`` and every file it names
meet the rules a later PR's added files are held to — and a cell, a
configuration, a traffic mix and a layer metric can each be added with
new files and new entries, editing nothing that is there."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest  # noqa: E402


def test_the_committed_manifest_is_clean():
    assert manifest.lint(REPO) == []


def test_contract_shape():
    m = manifest.load(REPO)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["command"][-1].startswith(m["paths"][0] + "/")
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 2 <= len(m["workloads"]) <= 24 and 1 <= len(m["configs"]) <= 24
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    for metric in m["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.25, metric
    setup = next(x for x in m["end_to_end"] if x["name"] == "setup_s")
    assert setup["bound"] == 0.25
    # at most one cell takes four chips, and only where the thing it
    # measures exists only across chips
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= 1


@pytest.mark.parametrize("cell", [
    w["name"] for w in manifest.load(REPO)["workloads"]])
def test_every_cell_resolves(cell):
    c = manifest.Cell(manifest.load(REPO), cell, REPO)
    assert os.path.isfile(c.config_file) and os.path.isfile(c.traffic_file)
    assert c.config["chips"] == c.workload["chips"]
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        module = manifest.layer_metric(REPO, m["name"])
        assert module.LAYER in manifest.LAYERS and callable(module.read)
        assert any(e["name"] == module.MOVES for e in c.end_to_end), m["name"]


@pytest.fixture
def scratch(tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    return str(root)


def _add_dummies(root):
    """What a later PR would add: files, and entries at the ends of lists."""
    def write(path, obj):
        with open(os.path.join(root, path), "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    base = manifest.read_json(root, "benchmark/configs/broker1-1k.json")
    write("benchmark/configs/dummy-config.json",
          {**base, "name": "dummy-config", "source": "https://example.org/x"})
    write("benchmark/traffic/dummy-mix.json", {
        "name": "dummy-mix", "who": "nobody", "why": "a dummy",
        "subscriptions": [{"users": "all", "topic": {"fixed": 3}}],
        "flows": [{"name": "f", "publishers": 2,
                   "loop": {"kind": "open", "arrivals": "poisson",
                            "rate_per_s": 10.0},
                   "mix": [{"share": 1.0, "kind": "broadcast", "bytes": 100,
                            "topic": {"fixed": 3}}]}]})
    write("benchmark/layer_metrics/dummy_metric.py",
          '"""A dummy."""\n\nLAYER = "egress"\nUNIT = "x"\nBETTER = "lower"\n'
          'SOURCE = "program_counter"\nMOVES = "delivery_p50_ms"\n\n\n'
          'def read(run):\n    return None\n')
    m = manifest.load(root)
    m["configs"].append({
        "name": "dummy-config", "source": "https://example.org/x",
        "file": "benchmark/configs/dummy-config.json",
        "reduced": sorted(base["reduced"]), "why": "a dummy"})
    m["workloads"].append({
        "name": "dummy-config.dummy-mix", "config": "dummy-config",
        "traffic": "dummy-mix", "chips": 1, "why": "a dummy cell"})
    for metric in m["end_to_end"]:
        if metric["name"] in ("delivery_p50_ms", "delivery_p99_ms"):
            metric["workloads"].append("dummy-config.dummy-mix")
    m["per_layer"].append({
        "name": "dummy_metric", "unit": "x", "better": "lower",
        "source": "program_counter", "layer": "egress",
        "moves": "delivery_p50_ms",
        "workloads": ["dummy-config.dummy-mix"]})
    write("BENCHMARK.json", m)


def test_a_cell_config_mix_and_metric_are_added_by_files_alone(scratch):
    before = {}
    for dirpath, _dirs, files in os.walk(scratch):
        for name in files:
            if name != "BENCHMARK.json":
                path = os.path.join(dirpath, name)
                with open(path, "rb") as f:
                    before[path] = f.read()
    _add_dummies(scratch)
    assert manifest.lint(scratch) == []
    cell = manifest.Cell(manifest.load(scratch), "dummy-config.dummy-mix",
                         scratch)
    assert [m["name"] for m in cell.per_layer if m["name"] == "dummy_metric"]
    for path, content in before.items():  # nothing that was there changed
        with open(path, "rb") as f:
            assert f.read() == content, path


@pytest.mark.parametrize("breakage,needle", [
    (lambda m: m["workloads"][0].update(traffic="nope"), "no benchmark/traffic/nope.json"),
    (lambda m: m["workloads"][0].update(chips=4), "chips differ"),
    (lambda m: m["per_layer"][0].update(moves="nope"), "no end-to-end metric"),
    (lambda m: m["per_layer"][0].update(layer="made_up"), "LAYER differs"),
    (lambda m: m["per_layer"][0].update(layer="made up"), "layer 'made up' is not plain"),
    (lambda m: m["per_layer"].append(dict(m["per_layer"][0], name="ghost")),
     "no benchmark/layer_metrics/ghost.py"),
    (lambda m: m["workloads"][0].update(name="bad name!"), "is not plain"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound 0.5"),
    (lambda m: m["configs"][0].update(reduced=[]), "reduced differs"),
])
def test_lint_catches(scratch, breakage, needle):
    m = manifest.load(scratch)
    breakage(m)
    with open(os.path.join(scratch, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    assert any(needle in p for p in manifest.lint(scratch)), \
        manifest.lint(scratch)
