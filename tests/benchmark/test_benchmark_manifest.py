"""Manifest lint (ISSUE 22): ``BENCHMARK.json`` and every file it names
meet the rules a later PR's added files are held to — and a cell, a
configuration, a traffic mix and a layer metric can each be added with
new files and new entries, editing nothing that is there. The tests
themselves hold on a manifest that has grown (ISSUE 33): no case counts the
committed cells, and the ones that start no deployment are run on a tree
with one more cell."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

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
    # a cell takes four chips only where the thing it measures exists
    # only across chips; how many may is the lint's rule, not one of the
    # test's own (test_lint_catches holds it to one more than the rule
    # allows of the cells there are: three of five today)
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= \
        manifest.four_chip_cells_allowed(len(m["workloads"]))


def test_the_four_chip_rule_is_half_the_cells_rounded_down_and_one_always():
    assert [manifest.four_chip_cells_allowed(n) for n in (1, 2, 3, 5, 6, 24)] \
        == [1, 1, 1, 2, 3, 12]


def test_the_client_names_are_the_programs():
    """The lint imports no program code; its two tuples are held here to
    be names ``bin/common.py`` resolves."""
    from pushcdn_tpu.bin import common
    assert set(manifest.USER_TRANSPORTS) <= set(common.TRANSPORTS)
    assert set(manifest.SIGNATURE_SCHEMES) <= set(common.SCHEMES)
    base = manifest.read_json(REPO, "benchmark/configs/broker1-1k.json")
    assert manifest.client_settings(base) == {
        "user_transport": "tcp", "signature_scheme": "ed25519"}
    # what the binaries take when a flag is not given, as the lint assumes
    from pushcdn_tpu.bin import broker, marshal
    for binary in (broker, marshal):
        args = binary.build_parser().parse_args(["--discovery-endpoint", "x"])
        for key, (_names, flag, unset) in manifest.CLIENT_KEYS.items():
            assert getattr(args, flag[2:].replace("-", "_")) == unset, key


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
        assert any(e["name"] == m["moves"] for e in c.end_to_end), m["name"]
        if m["name"] == manifest.base_name(m["name"]):
            assert module.MOVES == m["moves"], m["name"]


def test_a_per_layer_metric_entered_apart_is_its_base_moving_another_metric():
    """``<metric>.<tag>``: the same number from the same reader, in cells
    that do not report the end-to-end metric its reader names (PR 32:
    ``global-steady``'s p50 scatters too widely for any bound, so it is
    recorded per layer there, and the step's breakdown moves what that
    cell still judges)."""
    assert manifest.base_name("step_wall_ms.global1k") == "step_wall_ms"
    assert manifest.base_name("setup_s") == "setup_s"
    assert manifest.layer_metric_path("step_wall_ms.global1k") == \
        manifest.layer_metric_path("step_wall_ms")
    m = manifest.load(REPO)
    assert all(x["name"] == manifest.base_name(x["name"])
               for x in m["end_to_end"])
    apart = [x for x in m["per_layer"]
             if x["name"] != manifest.base_name(x["name"])]
    assert apart
    for x in apart:
        base = next(y for y in m["per_layer"]
                    if y["name"] == manifest.base_name(x["name"]))
        assert not set(x["workloads"]) & set(base["workloads"]), x["name"]
        for key in ("unit", "better", "source", "layer"):
            assert x[key] == base[key], (x["name"], key)
        assert x["moves"] != base["moves"], x["name"]
    # no cell lost a reading: both steady cells carry the step's breakdown
    # under one name or the other, and a median, judged or recorded
    for cell, median in (
            ("broker1-1k.global-steady", "steady_delivery_p50_ms"),
            ("broker1-5k.global5k-steady", "delivery_p50_ms")):
        c = manifest.Cell(m, cell, REPO)
        assert {manifest.base_name(x["name"]) for x in c.per_layer} >= {
            "step_wall_ms", "step_handoff_ms", "ring_wait_ms", "steps_per_s",
            "step_device_us", "delivery_kernel_roofline", "gen_late_p99_ms"}
        assert median in {x["name"] for x in c.per_layer + c.end_to_end}


@pytest.fixture
def scratch(tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    return str(root)


METRIC_FILE = '''"""A dummy."""

{imports}LAYER = "{layer}"
UNIT = "x"
BETTER = "lower"
SOURCE = "{source}"
MOVES = "delivery_p50_ms"


def read(run):
    return {expr}
'''
# name -> (layer, source, what read() returns, imports): one on an old
# layer that reads nothing, one on the broker_links layer, one that reads
# a stat of a span and one a counter that no file here names
DUMMY_METRICS = {
    "dummy_metric": ("egress", "program_counter", "None", ""),
    "dummy_links_metric": ("broker_links", "host_clock", "None", ""),
    "dummy_span_metric": (
        "broker_links", "program_span",
        'span_reduce.stat_sum(run, "links.forward", "peers")',
        "from benchmark import span_reduce\n\n"),
    "dummy_counter_metric": (
        "broker_links", "program_counter",
        'run.window.counters["end"].get("frames_forwarded")', ""),
}
DUMMY_CELLS = ("dummy-config.dummy-mix", "dummy-mesh.dummy-mix",
               "dummy-prod.dummy-mix")


def _add_dummies(root):
    """What the next PRs would add: files, and entries at the ends of
    lists. A plain cell; a second four-chip cell on ``mesh_inprocess``; a
    deployment whose users come over TCP+TLS with BLS-BN254 keys, as
    upstream's do; and the metrics of ``DUMMY_METRICS``."""
    def write(path, obj):
        with open(os.path.join(root, path), "w") as f:
            f.write(obj if isinstance(obj, str) else json.dumps(obj))

    base = manifest.read_json(root, "benchmark/configs/broker1-1k.json")
    mesh = manifest.read_json(root, "benchmark/configs/mesh4-1k.json")
    prod = {**base, "user_transport": "tcp+tls",
            "signature_scheme": "bls-bn254",
            "broker_flags": ["--device-plane", "--user-transport", "tcp+tls",
                             "--scheme", "bls-bn254"],
            "marshal_flags": ["--user-transport", "tcp+tls",
                              "--scheme", "bls-bn254"],
            "reduced": {"brokers": base["reduced"]["brokers"]}}
    m = manifest.load(root)
    for name, cfg in (("dummy-config", base), ("dummy-mesh", mesh),
                      ("dummy-prod", prod)):
        source = f"https://example.org/{name}"
        write(f"benchmark/configs/{name}.json",
              {**cfg, "name": name, "source": source})
        m["configs"].append({
            "name": name, "source": source,
            "file": f"benchmark/configs/{name}.json",
            "reduced": sorted(cfg["reduced"]), "why": "a dummy"})
        m["workloads"].append({
            "name": f"{name}.dummy-mix", "config": name,
            "traffic": "dummy-mix", "chips": cfg["chips"],
            "why": "a dummy cell"})
    write("benchmark/traffic/dummy-mix.json", {
        "name": "dummy-mix", "who": "nobody", "why": "a dummy",
        "subscriptions": [{"users": "all", "topic": {"mod": 8}}],
        "flows": [{"name": "f", "publishers": 2,
                   "loop": {"kind": "open", "arrivals": "poisson",
                            "rate_per_s": 10.0},
                   "mix": [{"share": 0.5, "kind": "broadcast", "bytes": 100,
                            "topic": {"fixed": 3}},
                           {"share": 0.5, "kind": "broadcast", "bytes": 100,
                            "topic": {"zipf": 8, "s": 1.0}}]}]})
    for metric in m["end_to_end"]:
        if metric["name"] == "delivery_p50_ms":
            # the last cell's median is not judged: its per-layer metrics
            # are entered apart there and move what it does report: an
            # entry each, no file, no code
            metric["workloads"] += DUMMY_CELLS[:-1]
    for name, (layer, source, expr, imports) in DUMMY_METRICS.items():
        write(f"benchmark/layer_metrics/{name}.py", METRIC_FILE.format(
            layer=layer, source=source, expr=expr, imports=imports))
        entry = {
            "name": name, "unit": "x", "better": "lower", "source": source,
            "layer": layer, "moves": "delivery_p50_ms",
            "workloads": list(DUMMY_CELLS[:-1])}
        m["per_layer"] += [entry, {
            **entry, "name": name + ".dummy",
            "moves": "broker_cpu_us_per_delivery",
            "workloads": [DUMMY_CELLS[-1]]}]
    write("BENCHMARK.json", m)


def test_a_cell_config_mix_and_metric_are_added_by_files_alone(scratch):
    before = {}
    for dirpath, _dirs, files in os.walk(scratch):
        for name in files:
            if name != "BENCHMARK.json":
                path = os.path.join(dirpath, name)
                with open(path, "rb") as f:
                    before[path] = f.read()
    four_before = _four_chip_cells(manifest.load(scratch))
    _add_dummies(scratch)
    assert manifest.lint(scratch) == []
    m = manifest.load(scratch)
    assert _four_chip_cells(m) == four_before + 1  # ``dummy-mesh``'s
    for name in DUMMY_CELLS:
        cell = manifest.Cell(m, name, scratch)
        assert set(DUMMY_METRICS) <= {
            manifest.base_name(x["name"]) for x in cell.per_layer}
    assert {name + ".dummy" for name in DUMMY_METRICS} <= {
        x["name"] for x in cell.per_layer}
    assert "delivery_p50_ms" not in {x["name"] for x in cell.end_to_end}
    assert manifest.client_settings(cell.config) == {
        "user_transport": "tcp+tls", "signature_scheme": "bls-bn254"}
    # the new span metric and the new counter metric read what the
    # program says through the two pass-throughs, and nothing where an
    # older commit says nothing
    from benchmark import span_reduce
    from benchmark.span_reduce import Span
    spans = span_reduce.reduce([
        Span("links.forward", 0, 0.0, 1e6, {"peers": 3, "step": 1}),
        Span("links.forward", 0, 2e6, 3e6, {"peers": 2, "step": 2})])
    new = SimpleNamespace(window=SimpleNamespace(
        spans=spans, counters={"end": {"frames_forwarded": 7}}))
    old = SimpleNamespace(window=SimpleNamespace(
        spans=span_reduce.reduce([Span("plane.take", 0, 0.0, 1e6, {})]),
        counters={"end": {}}))
    for name, want in (("dummy_span_metric", 5), ("dummy_counter_metric", 7),
                       ("dummy_links_metric", None)):
        reader = manifest.layer_metric(scratch, name)
        assert reader.read(new) == want and reader.read(old) is None, name
    # the plan pin of test_benchmark_arith.py holds on the copy too: a new
    # traffic file and new cells on it are none of its business
    spec = importlib.util.spec_from_file_location(
        "benchmark_arith_tests", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "test_benchmark_arith.py"))
    arith = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arith)
    assert "dummy-mix" not in arith.PLAN_PIN
    for traffic in arith.PLAN_PIN:
        arith.assert_plan_pinned(scratch, traffic)
    for path, content in before.items():  # nothing that was there changed
        with open(path, "rb") as f:
            assert f.read() == content, path


def _edit(root, path, change):
    """Change one of the scratch copy's JSON files in place."""
    obj = manifest.read_json(root, path)
    change(obj)
    with open(os.path.join(root, path), "w") as f:
        json.dump(obj, f)


def _save(root, m):
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)


def _assert_lint_says(root, breakage, needle):
    """Break ``root``'s manifest and find ``needle`` (text, or what to
    look for given the manifest before the breakage) in what the lint says."""
    m = manifest.load(root)
    if callable(needle):
        needle = needle(m)
    breakage(m, root)
    _save(root, m)
    problems = manifest.lint(root)
    assert any(needle in p for p in problems), problems


def _four_chip_cells(m):
    return sum(w["chips"] == 4 for w in m["workloads"])


def _one_four_chip_cell_too_many(m, _root):
    """As many one-chip cells moved to four chips as it takes to stand one
    over what the lint's own rule allows of the cells there are."""
    more = manifest.four_chip_cells_allowed(len(m["workloads"])) + 1 \
        - _four_chip_cells(m)
    one_chip = [w for w in m["workloads"] if w["chips"] == 1]
    assert 0 < more <= len(one_chip)
    for w in one_chip[:more]:
        w["chips"] = 4


def _one_too_many_said(m):
    """The lint's message for it: three of five today, four of six with
    the next cell, five of the grown copy's eight."""
    cells = len(m["workloads"])
    return (f"{manifest.four_chip_cells_allowed(cells) + 1} cells of "
            f"{cells} ask for 4 chips")


def _a_cell_the_first_leaves_out(m):
    """A cell that reports what the first per-layer metric moves and is
    not among that metric's own (``echo-sparse`` today)."""
    first = m["per_layer"][0]
    moved = next(e for e in m["end_to_end"] if e["name"] == first["moves"])
    return next(w["name"] for w in m["workloads"]
                if manifest.applies(moved, w["name"])
                and not manifest.applies(first, w["name"]))


def _first_entered_apart_said(m):
    name = m["per_layer"][0]["name"]
    return f"metric {name}.apart: is {name} entered apart, yet"


BROKER1 = "benchmark/configs/broker1-1k.json"


@pytest.mark.parametrize("breakage,needle", [
    (lambda m, _: m["workloads"][0].update(traffic="nope"), "no benchmark/traffic/nope.json"),
    (lambda m, _: next(w for w in m["workloads"] if w["chips"] == 1).update(
        chips=4), "chips differ"),
    (lambda m, _: m["per_layer"][0].update(moves="nope"), "no end-to-end metric"),
    (lambda m, _: m["per_layer"][0].update(layer="made_up"), "LAYER differs"),
    (lambda m, _: m["per_layer"][0].update(layer="made up"), "layer 'made up' is not plain"),
    (lambda m, _: m["per_layer"].append(dict(m["per_layer"][0], name="ghost")),
     "no benchmark/layer_metrics/ghost.py"),
    (lambda m, _: m["workloads"][0].update(name="bad name!"), "is not plain"),
    (lambda m, _: m["end_to_end"][0].update(bound=0.5), "bound 0.5"),
    (lambda m, _: m["configs"][0].update(reduced=[]), "reduced differs"),
    # half the cells, rounded down, and one more: three of five (three of
    # six would pass, as it does at the driver), whatever cells there are
    (_one_four_chip_cell_too_many, _one_too_many_said),
    (lambda _, root: _edit(root, BROKER1, lambda c: c.update(
        user_transport="quic")), "user_transport 'quic' is not one of"),
    # the clients would sign with BLS, the broker and the marshal verify
    # Ed25519 (no --scheme among the flags)
    (lambda _, root: _edit(root, BROKER1, lambda c: c.update(
        signature_scheme="bls-bn254")),
     "signature_scheme is 'bls-bn254', broker_flags say 'ed25519'"),
    (lambda _, root: _edit(root, BROKER1, lambda c: c.update(
        user_transport="tcp+tls",
        broker_flags=["--device-plane", "--user-transport", "tcp+tls"],
        marshal_flags=["--user-transport", "tcp+tls"])),
     "user_transport 'tcp+tls' is upstream's, yet listed under reduced"),
    # only a per-layer metric is entered apart, and only to move another
    # metric in cells its base does not list
    (lambda m, _: m["end_to_end"].append(dict(
        m["end_to_end"][0], name=m["end_to_end"][0]["name"] + ".twice")),
     "an end-to-end metric is not entered apart"),
    (lambda m, _: m["per_layer"].append(dict(
        m["per_layer"][0], name=m["per_layer"][0]["name"] + ".apart",
        moves="setup_s")), _first_entered_apart_said),
    (lambda m, _: m["per_layer"].append(dict(
        m["per_layer"][0], name=m["per_layer"][0]["name"] + ".apart",
        workloads=[_a_cell_the_first_leaves_out(m)])),
     _first_entered_apart_said),
    (lambda _, root: _edit(
        root, "benchmark/traffic/fanout4-sat.json",
        lambda t: t["flows"][0]["mix"][0].update(topic={"zipf": 0})),
     "topic {'zipf': 0}"),
    (lambda _, root: _edit(
        root, "benchmark/traffic/fanout4-sat.json",
        lambda t: t["flows"][0]["mix"][0].update(
            topic={"zipf": 250, "s": -1.0})), "topic {'zipf': 250, 's': -1.0}"),
])
@pytest.mark.parametrize("grown", [False, True], ids=["committed", "grown"])
def test_lint_catches(scratch, breakage, needle, grown):
    """Each breakage on the committed manifest, and again on the copy
    ``_add_dummies`` has grown (three more cells, one of them on four
    chips, three configurations, a mix, eight per-layer entries): a case
    that counts the cells there are, or finds its entry by a place that an
    appended entry moves, fails here in the PR that writes it and not in
    the PR that adds a cell."""
    if grown:
        _add_dummies(scratch)
    _assert_lint_says(scratch, breakage, needle)


def _append_a_cell(root, chips):
    """One more cell as the queue's next PRs will bring it, but for the
    files: a committed configuration on ``chips`` chips under a committed
    mix it does not run yet, at the end of ``workloads``, its name added
    to every ``workloads`` list that holds a committed cell of that
    configuration. Returns its name."""
    m = manifest.load(root)
    like = next(w for w in m["workloads"] if w["chips"] == chips)
    pairs = {(w["config"], w["traffic"]) for w in m["workloads"]}
    traffic = next(w["traffic"] for w in m["workloads"]
                   if (like["config"], w["traffic"]) not in pairs)
    name = f"{like['config']}.{traffic}"
    m["workloads"].append({
        "name": name, "config": like["config"], "traffic": traffic,
        "chips": chips, "why": "one more cell"})
    for metric in m["end_to_end"] + m["per_layer"]:
        if like["name"] in metric.get("workloads", ()):
            metric["workloads"].append(name)
    _save(root, m)
    return name


# One more cell on the committed manifest, and the tests of this directory
# that start no deployment run on that tree in a child: what a
# ``model_config`` PR's tier-1 run will be. The child holds the lint, the
# whole of ``test_lint_catches`` (its four-chip case with it) and every
# per-cell assertion of the three files to the grown manifest; a test that
# pins the committed set of cells fails here, in the PR that writes it.
GROWN_TREE_TESTS = ("test_benchmark_manifest.py", "test_span_reduce.py",
                    "test_benchmark_arith.py")
# Left out of the child by node id, not by a pattern of names: the one test
# of those files that starts a deployment of a committed cell (tens of
# seconds, and nothing of it reads the set of cells), and this test itself.
# A later test of those files that starts one runs in the child, inside its
# time limit, until its own PR lists it here.
NOT_IN_THE_CHILD = (
    "test_span_reduce.py::"
    "test_traced_dry_run_reports_the_host_side_of_the_step",
    "test_benchmark_manifest.py::"
    "test_one_more_cell_leaves_the_tests_that_start_nothing_whole",
)


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "four_chips"])
def test_one_more_cell_leaves_the_tests_that_start_nothing_whole(
        scratch, chips):
    before = manifest.load(scratch)
    name = _append_a_cell(scratch, chips)
    # what the child cannot say of the cell it does not know by name
    m = manifest.load(scratch)
    assert len(m["workloads"]) == len(before["workloads"]) + 1
    assert _four_chip_cells(m) == _four_chip_cells(before) + (chips == 4)
    cell = manifest.Cell(m, name, scratch)
    assert len(cell.end_to_end) >= 2 and cell.per_layer

    here = os.path.dirname(os.path.abspath(__file__))
    there = os.path.join(scratch, "tests", "benchmark")
    os.makedirs(there)
    for name in GROWN_TREE_TESTS:
        shutil.copy(os.path.join(here, name), there)
    for node in NOT_IN_THE_CHILD:  # pytest says nothing of an id it has not
        path, func = node.split("::")
        with open(os.path.join(there, path)) as f:
            assert f"\ndef {func}(" in f.read(), node
    # the recorded trace two of them read, and the program beside them as
    # it is: one test holds the lint's two tuples to be names
    # ``bin/common.py`` resolves
    shutil.copytree(os.path.join(REPO, "benchmark", "fixtures"),
                    os.path.join(scratch, "benchmark", "fixtures"))
    os.symlink(os.path.join(REPO, "pushcdn_tpu"),
               os.path.join(scratch, "pushcdn_tpu"))
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "--rootdir", scratch,
         *(f"--deselect=tests/benchmark/{node}" for node in NOT_IN_THE_CHILD),
         *(os.path.join(there, name) for name in GROWN_TREE_TESTS)],
        capture_output=True, text=True, timeout=120, cwd=scratch,
        env={**env, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout[-6000:] + proc.stderr[-2000:]
    assert " passed" in proc.stdout and "failed" not in proc.stdout
    assert " deselected" in proc.stdout
