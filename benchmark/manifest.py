"""``BENCHMARK.json`` and the files it names: loading, finding by name,
and the lint that keeps the harness driven by data.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by its name:

    benchmark/configs/<config>.json          (named by configs[].file)
    benchmark/traffic/<traffic>.json
    benchmark/launchers/<launcher>.py        (named by the configuration)
    benchmark/layer_metrics/<metric>.py      (``<metric>.<tag>`` shares it)

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
    "kernels", "mesh_tick", "broker_links", "egress", "client_decode",
    "host_path", "device", "end_to_end",
)
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
LOOPS = ("open", "windowed", "echo")
# What the users connect over and sign with: a configuration's optional
# ``user_transport`` and ``signature_scheme`` keys, the first of each
# the default and the last what upstream's production definition uses. The names are the program's (``pushcdn_tpu/bin/common.py``:
# ``TRANSPORTS``, ``SCHEMES``; a test holds these to be among them), the
# choice is what upstream's definitions use (``cdn-proto/src/def.rs``).
USER_TRANSPORTS = ("tcp", "tcp+tls")
SIGNATURE_SCHEMES = ("ed25519", "bls-bn254")
# The flag of ``bin/broker`` and ``bin/marshal`` that has to agree with
# each key, and what the binaries take when the flag is not given.
CLIENT_KEYS = {
    "user_transport": (USER_TRANSPORTS, "--user-transport", "tcp+tls"),
    "signature_scheme": (SIGNATURE_SCHEMES, "--scheme", "ed25519"),
}


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


def base_name(metric: str) -> str:
    """``step_wall_ms.global1k`` is ``step_wall_ms``: a per-layer metric
    entered apart (``<metric>.<tag>``), the same number from the same
    reader, for the cells in which it moves another end-to-end metric
    than the one its reader names (a cell that does not report that
    one). An end-to-end metric is never entered apart."""
    return metric.split(".", 1)[0]


def layer_metric_path(metric: str) -> str:
    return os.path.join("benchmark", "layer_metrics",
                        f"{base_name(metric)}.py")


def client_settings(config: dict) -> dict:
    """The users' transport and signature scheme by name, as the
    configuration states them or by default."""
    return {key: config.get(key, names[0])
            for key, (names, _flag, _unset) in CLIENT_KEYS.items()}


def four_chip_cells_allowed(cells: int) -> int:
    """A cell takes four chips only where what it measures exists only
    across chips, and costs four times the chip time in every later
    check: at most half the cells, rounded down, and one always."""
    return max(1, cells // 2)


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
        bad += [f"config {entry['name']}: {p}" for p in _lint_clients(cfg)]

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
    if four > four_chip_cells_allowed(len(manifest["workloads"])):
        bad.append(f"{four} cells of {len(manifest['workloads'])} ask for "
                   "4 chips")

    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s among the end-to-end metrics")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']}: source {m['source']!r}")
        if not 0 < m["bound"] <= 0.25:
            bad.append(f"metric {m['name']}: bound {m['bound']}")
        if m["name"] != base_name(m["name"]):
            bad.append(f"metric {m['name']}: an end-to-end metric is not "
                       "entered apart")
    for cell in cells:
        mine = [m for m in manifest["end_to_end"] if applies(m, cell)]
        if len(mine) < 2 or not any(m["name"] == "setup_s" for m in mine):
            bad.append(f"workload {cell}: needs setup_s and one more metric")
        if not any(applies(m, cell) for m in manifest["per_layer"]):
            bad.append(f"workload {cell}: no per-layer metric")
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
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
                          ("BETTER", "better"), ("SOURCE", "source")):
            if getattr(module, attr, None) != m[key]:
                bad.append(f"metric {name}: {attr} differs from BENCHMARK.json")
        base = per_layer.get(base_name(name), m)
        if base is m:
            if getattr(module, "MOVES", None) != m["moves"]:
                bad.append(f"metric {name}: MOVES differs from BENCHMARK.json")
        elif m["moves"] == base["moves"] or any(
                applies(m, c) and applies(base, c) for c in cells):
            # entered apart: only to move another metric in other cells
            bad.append(f"metric {name}: is {base['name']} entered apart, yet "
                       "moves what it moves or shares a cell with it")
        if not callable(getattr(module, "read", None)):
            bad.append(f"metric {name}: no read(run)")
    return bad


def _lint_clients(cfg: dict) -> List[str]:
    """The users and the deployment must agree on transport and scheme,
    and a key at upstream's value is not a reduction."""
    bad: List[str] = []
    for key, value in client_settings(cfg).items():
        names, flag, unset = CLIENT_KEYS[key]
        if value not in names:
            bad.append(f"{key} {value!r} is not one of {names}")
            continue
        if value == names[-1] and key in cfg.get("reduced", {}):
            bad.append(f"{key} {value!r} is upstream's, yet listed "
                       "under reduced")
        for flags in ("broker_flags", "marshal_flags"):
            argv = cfg.get(flags)
            if argv is None:
                continue  # a launcher that starts no such binary
            said = argv[argv.index(flag) + 1] if flag in argv[:-1] else unset
            if said != value:
                bad.append(f"{key} is {value!r}, {flags} say {said!r}")
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
            topic = entry.get("topic", {})
            if "zipf" in topic and not (
                    isinstance(topic["zipf"], int) and topic["zipf"] >= 1
                    and topic.get("s", 1.0) >= 0):
                bad.append(f"flow {flow.get('name')}: topic {topic}")
    return bad


def find_cell(name: str, root: str = ROOT) -> Optional[Cell]:
    try:
        return Cell(load(root), name, root)
    except KeyError:
        return None
