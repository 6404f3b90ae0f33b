"""``BENCHMARK.json`` and the files it names: loading, finding by name,
and the lint that keeps the harness driven by data.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by its name:

    benchmark/configs/<config>.json          (named by configs[].file)
    benchmark/traffic/<traffic>.json
    benchmark/launchers/<launcher>.py        (named by the configuration)
    benchmark/layer_metrics/<metric>.py

No cell's name appears in code.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import List, Optional

from benchmark.loadgen import plan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
LAYERS = (
    "load_generator", "process_start", "compile_cache", "marshal_auth",
    "transport_ingress", "scalar_ingress", "stage_pack", "routing_step",
    "kernels", "mesh_tick", "egress", "client_decode", "host_path", "device",
    "end_to_end",
)
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
LOOPS = ("open", "windowed", "echo")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def read_json(root: str, path: str) -> dict:
    with open(os.path.join(root, path)) as f:
        return json.load(f)


def traffic_path(traffic: str) -> str:
    return os.path.join("benchmark", "traffic", f"{traffic}.json")


def launcher_path(launcher: str) -> str:
    return os.path.join("benchmark", "launchers", f"{launcher}.py")


def layer_metric_path(metric: str) -> str:
    return os.path.join("benchmark", "layer_metrics", f"{metric}.py")


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def layer_metric(root: str, name: str):
    """The reader module of one per-layer metric."""
    path = os.path.join(root, layer_metric_path(name))
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One workload with everything it names, loaded."""

    def __init__(self, manifest: dict, name: str, root: str = ROOT):
        self.root = root
        self.workload = next(
            (w for w in manifest["workloads"] if w["name"] == name), None)
        if self.workload is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        entry = next(c for c in manifest["configs"]
                     if c["name"] == self.workload["config"])
        self.config_file = os.path.join(root, entry["file"])
        self.config = read_json(root, entry["file"])
        self.traffic_file = os.path.join(
            root, traffic_path(self.workload["traffic"]))
        self.traffic = read_json(root, traffic_path(self.workload["traffic"]))
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if applies(m, name)]
        self.per_layer = [m for m in manifest["per_layer"]
                          if applies(m, name)]


def lint(root: str = ROOT) -> List[str]:
    """Every rule a later PR's added files must meet; returns what is
    wrong, as text."""
    bad: List[str] = []
    manifest = load(root)

    def exists(path: str) -> bool:
        return os.path.isfile(os.path.join(root, path))

    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in manifest[key]]
    for name in names:
        if not NAME.match(name):
            bad.append(f"name {name!r} is not plain")
    for name in {n for n in names if names.count(n) > 1}:
        bad.append(f"name {name!r} is used more than once")

    configs = {}
    for entry in manifest["configs"]:
        if not exists(entry["file"]):
            bad.append(f"config {entry['name']}: no file {entry['file']}")
            continue
        cfg = configs[entry["name"]] = read_json(root, entry["file"])
        for key in ("source", "launcher", "chips", "users", "reduced",
                    "assumed", "guarantees", "client_processes",
                    "placement_groups"):
            if key not in cfg:
                bad.append(f"config {entry['name']}: no {key!r}")
        if cfg.get("chips") not in (1, 4):
            bad.append(f"config {entry['name']}: chips must be 1 or 4")
        if cfg.get("source") != entry["source"]:
            bad.append(f"config {entry['name']}: source differs from its file's")
        if sorted(cfg.get("reduced", {})) != sorted(entry["reduced"]):
            bad.append(f"config {entry['name']}: reduced differs from its file's")
        if "launcher" in cfg and not exists(launcher_path(cfg["launcher"])):
            bad.append(f"config {entry['name']}: no launcher "
                       f"{launcher_path(cfg['launcher'])}")
        if not any(w["config"] == entry["name"] for w in manifest["workloads"]):
            bad.append(f"config {entry['name']}: no cell uses it")

    pairs = set()
    for w in manifest["workloads"]:
        cfg = configs.get(w["config"])
        if cfg is None:
            bad.append(f"workload {w['name']}: no config {w['config']!r}")
        elif cfg.get("chips") != w["chips"]:
            bad.append(f"workload {w['name']}: chips differ from its config's")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if len(w.get("why", "")) > 200 or not w.get("why"):
            bad.append(f"workload {w['name']}: why is missing or over 200")
        if not exists(traffic_path(w["traffic"])):
            bad.append(f"workload {w['name']}: no {traffic_path(w['traffic'])}")
            continue
        bad += [f"traffic {w['traffic']}: {p}" for p in
                _lint_traffic(read_json(root, traffic_path(w["traffic"])))]
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(manifest["workloads"]) // 2):
        bad.append(f"{four} cells ask for 4 chips")

    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s among the end-to-end metrics")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']}: source {m['source']!r}")
        if not 0 < m["bound"] <= 0.25:
            bad.append(f"metric {m['name']}: bound {m['bound']}")
    for cell in cells:
        mine = [m for m in manifest["end_to_end"] if applies(m, cell)]
        if len(mine) < 2 or not any(m["name"] == "setup_s" for m in mine):
            bad.append(f"workload {cell}: needs setup_s and one more metric")
        if not any(applies(m, cell) for m in manifest["per_layer"]):
            bad.append(f"workload {cell}: no per-layer metric")
    for m in manifest["per_layer"]:
        name = m["name"]
        if m["source"] not in SOURCES:
            bad.append(f"metric {name}: source {m['source']!r}")
        if not LAYER.match(m["layer"]):
            bad.append(f"metric {name}: layer {m['layer']!r} is not plain")
        moved = e2e.get(m["moves"])
        if moved is None:
            bad.append(f"metric {name}: moves {m['moves']!r}, which is no "
                       "end-to-end metric")
        else:
            for cell in cells:
                if applies(m, cell) and not applies(moved, cell):
                    bad.append(f"metric {name}: reported in {cell}, where "
                               f"{m['moves']} is not")
        if not exists(layer_metric_path(name)):
            bad.append(f"metric {name}: no {layer_metric_path(name)}")
            continue
        module = layer_metric(root, name)
        if getattr(module, "LAYER", None) not in LAYERS:
            bad.append(f"metric {name}: LAYER {getattr(module, 'LAYER', None)!r} "
                       "is not in the layer table")
        for attr, key in (("LAYER", "layer"), ("UNIT", "unit"),
                          ("BETTER", "better"), ("SOURCE", "source"),
                          ("MOVES", "moves")):
            if getattr(module, attr, None) != m[key]:
                bad.append(f"metric {name}: {attr} differs from BENCHMARK.json")
        if not callable(getattr(module, "read", None)):
            bad.append(f"metric {name}: no read(run)")
    return bad


def _lint_traffic(traffic: dict) -> List[str]:
    bad: List[str] = []
    for key in ("who", "why", "subscriptions", "flows"):
        if key not in traffic:
            bad.append(f"no {key!r}")
    for flow in traffic.get("flows", []):
        loop = flow.get("loop", {})
        if loop.get("kind") not in LOOPS:
            bad.append(f"flow {flow.get('name')}: loop kind {loop.get('kind')!r}")
        if not flow.get("publishers", 0) > 0:
            bad.append(f"flow {flow.get('name')}: no publishers")
        for entry in flow.get("mix", []):
            if not plan.HEADER_BYTES + 4 <= entry["bytes"] <= plan.MAX_PAYLOAD_BYTES:
                bad.append(f"flow {flow.get('name')}: {entry['bytes']} bytes")
            if entry["kind"] not in ("broadcast", "direct"):
                bad.append(f"flow {flow.get('name')}: kind {entry['kind']!r}")
    return bad


def find_cell(name: str, root: str = ROOT) -> Optional[Cell]:
    try:
        return Cell(load(root), name, root)
    except KeyError:
        return None
