#!/usr/bin/env python
"""Cross-round bench series: merge every ``BENCH_r*.json`` in the repo
root into ``BENCH_SERIES.md`` and (optionally) gate on regressions.

Each PR round leaves one ``BENCH_r<N>.json`` behind (written by
``benches/route_bench.py::write_bench_json``: per-section ``headline``
scalars + rows + provenance). This tool is the longitudinal view — the
same headline metric tracked round over round, so a perf regression is a
visible diff in BENCH_SERIES.md instead of an archaeology project:

    python scripts/bench_series.py                  # rewrite BENCH_SERIES.md
    python scripts/bench_series.py --gate           # exit 1 on >10% regression
    python scripts/bench_series.py --gate --threshold 0.25

The gate compares the LATEST round's metrics against the most recent
earlier round that carries the same metric (sections come and go as PRs
focus on different subsystems; a missing metric is not a regression).
Direction is inferred from the metric name — latency/footprint suffixes
(``_ms``/``_us``/``p99``/``lag``/``rss``…) are lower-is-better,
throughput suffixes (``msgs_s``/``ticks_s``/``ratio``/``ops``…) are
higher-is-better — and metrics with no inferable direction are tracked
in the table but never gated.

Absolute numbers are only comparable on the same host: the gate checks
the per-section provenance host fingerprint (platform + cpu count,
recorded since r13) and WAIVES — loudly, not silently — any comparison
whose baseline ran on a different host or predates provenance. The next
round on the same host re-engages the gate against the fresh baseline.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")

# direction inference on whole ``_``-separated tokens (substring matching
# is too greedy: ``chaos_scenarios`` contains ``_s``). Higher-better wins
# a conflict — ``msgs_s`` is a rate, not a time.
HIGHER_PARTS = {"msgs", "ops", "ratio", "users", "subs", "sheds",
                "chains", "delivered", "ticks", "frames", "throughput"}
LOWER_PARTS = {"ms", "us", "ns", "s", "p50", "p95", "p99", "lag",
               "overhead", "rss", "staleness", "bytes", "orphans",
               "orphaned", "stalled", "catchup", "latency"}


def direction(metric: str) -> int:
    """+1 higher-is-better, -1 lower-is-better, 0 unknown (not gated)."""
    parts = set(re.split(r"[^a-z0-9]+", metric.lower()))
    if parts & HIGHER_PARTS:
        return 1
    if parts & LOWER_PARTS:
        return -1
    return 0


def load_rounds(root: str) -> dict:
    """{round: {section: {metric: value}}} from every BENCH_r*.json."""
    rounds = {}
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        m = ROUND_RE.search(path)
        if not m:
            continue
        rnd = int(m.group(1))
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, ValueError) as exc:
            print(f"[series] skipping unreadable {path}: {exc}",
                  file=sys.stderr)
            continue
        sections = {}
        for name, body in doc.items():           # per-section headline
            if name == "round" or not isinstance(body, dict):
                continue
            headline = body.get("headline") or {}
            metrics = {k: v for k, v in headline.items()
                       if isinstance(v, (int, float))
                       and not isinstance(v, bool)}
            if metrics:
                sections[name] = metrics
        if sections:
            rounds[rnd] = sections
    return rounds


def _fingerprint(prov) -> "tuple | None":
    """Host identity a throughput number is only comparable within:
    (platform, cpus) from a section's provenance, or None when the round
    predates provenance recording (pre-r13) or left it empty."""
    if not isinstance(prov, dict):
        return None
    platform, cpus = prov.get("platform"), prov.get("cpus")
    if platform is None and cpus is None:
        return None
    return (platform, cpus)


def load_fingerprints(root: str) -> dict:
    """{round: {section: fingerprint-or-None}} — the per-section host
    identity alongside :func:`load_rounds` (rounds without provenance
    get None)."""
    fps = {}
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        m = ROUND_RE.search(path)
        if not m:
            continue
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        if "round" not in doc:
            continue
        for name, body in doc.items():
            if name == "round" or not isinstance(body, dict):
                continue
            fps.setdefault(int(m.group(1)), {})[name] = \
                _fingerprint(body.get("provenance"))
    return fps


def _fmt(value) -> str:
    if value is None:
        return "—"
    if isinstance(value, float):
        return f"{value:,.4g}" if abs(value) < 1000 else f"{value:,.0f}"
    return f"{value:,}"


def render_markdown(rounds: dict) -> str:
    order = sorted(rounds)
    out = ["# Bench series", "",
           "Headline metrics per PR round, merged from `BENCH_r*.json` by",
           "`scripts/bench_series.py` (regenerate with no args; `--gate`",
           "fails CI on a >10% regression vs the previous round carrying",
           "the metric). Direction: ↑ higher-is-better, ↓ lower-is-better,",
           "· untracked.", ""]
    sections = sorted({s for secs in rounds.values() for s in secs})
    for section in sections:
        present = [r for r in order if section in rounds[r]]
        metrics = sorted({m for r in present for m in rounds[r][section]})
        out.append(f"## {section}")
        out.append("")
        head = "| metric | " + " | ".join(f"r{r}" for r in present) + " |"
        out.append(head)
        out.append("|" + "---|" * (len(present) + 1))
        for metric in metrics:
            arrow = {1: "↑", -1: "↓", 0: "·"}[direction(metric)]
            cells = [_fmt(rounds[r][section].get(metric)) for r in present]
            out.append(f"| {arrow} `{metric}` | " + " | ".join(cells) + " |")
        out.append("")
    return "\n".join(out)


def gate(rounds: dict, threshold: float, fingerprints: dict = None,
         waived: list = None) -> list:
    """Regressions of the latest round vs the nearest earlier round that
    carries the same metric: [(section, metric, prev_round, prev, cur,
    pct_worse), ...].

    When ``fingerprints`` (from :func:`load_fingerprints`) is given, a
    metric whose baseline round ran on a different host — or predates
    provenance recording while the latest round carries it — is NOT
    gated: absolute throughput/latency across hosts is noise, not a
    regression. Would-be failures land in ``waived`` (if provided) so
    the re-baseline is loud, and the next same-host round re-engages the
    gate automatically against the freshly recorded numbers."""
    if len(rounds) < 2:
        return []
    order = sorted(rounds)
    latest = order[-1]
    failures = []
    for section, metrics in rounds[latest].items():
        for metric, cur in metrics.items():
            sign = direction(metric)
            if sign == 0:
                continue
            prev_round = prev = None
            for r in reversed(order[:-1]):
                candidate = rounds[r].get(section, {}).get(metric)
                if candidate is not None:
                    prev_round, prev = r, candidate
                    break
            if prev is None or prev == 0:
                continue
            # pct_worse > 0 means the metric moved the wrong way
            change = (cur - prev) / abs(prev)
            pct_worse = -change if sign > 0 else change
            if pct_worse <= threshold:
                continue
            if fingerprints is not None:
                fp_prev = fingerprints.get(prev_round, {}).get(section)
                fp_cur = fingerprints.get(latest, {}).get(section)
                if fp_prev != fp_cur:
                    if waived is not None:
                        waived.append((section, metric, prev_round, prev,
                                       cur, pct_worse, fp_prev, fp_cur))
                    continue
            failures.append((section, metric, prev_round, prev, cur,
                             pct_worse))
    return failures


def reduce_timeline(path: str) -> "dict | None":
    """Reduce a ``cdn_top.py --record`` JSONL timeline into one headline
    dict: per-sample cluster scalars collapse to the mean (rates/ratios),
    the max (worst-case delays, lags, cumulative sheds), or the min
    (process-up/ready counts — a flapping process must show). Returns
    None when the file holds no usable samples."""
    samples = []
    try:
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
                head = doc.get("headline")
                if isinstance(head, dict):
                    samples.append((doc.get("t"), head))
    except OSError as exc:
        print(f"[series] cannot read timeline {path}: {exc}",
              file=sys.stderr)
        return None
    if not samples:
        return None
    keys = sorted({k for _, h in samples for k in h
                   if isinstance(h.get(k), (int, float))
                   and not isinstance(h.get(k), bool)})
    out = {}
    for key in keys:
        vals = [h[key] for _, h in samples if isinstance(
            h.get(key), (int, float)) and not isinstance(h.get(key), bool)]
        if not vals:
            continue
        parts = set(re.split(r"[^a-z0-9]+", key.lower()))
        if parts & {"p99", "p95", "lag", "sheds", "max"}:
            out[key] = max(vals)
        elif parts & {"procs", "up", "ready"}:
            out[key] = min(vals)
        else:
            out[key] = sum(vals) / len(vals)
    out["timeline_samples"] = len(samples)
    times = [t for t, _ in samples if isinstance(t, (int, float))]
    if len(times) >= 2:
        out["timeline_span_s"] = max(times) - min(times)
    return out


def ingest_timeline(root: str, path: str, rnd: int, section: str) -> bool:
    """Merge a reduced timeline into ``BENCH_r<rnd>.json`` as a section
    (headline + provenance), creating the round file if absent."""
    headline = reduce_timeline(path)
    if headline is None:
        print(f"[series] timeline {path} holds no samples", file=sys.stderr)
        return False
    bench_path = os.path.join(root, f"BENCH_r{rnd:02d}.json")
    doc = {"round": rnd}
    if os.path.exists(bench_path):
        try:
            with open(bench_path) as fh:
                doc = json.load(fh)
        except (OSError, ValueError) as exc:
            print(f"[series] cannot merge into {bench_path}: {exc}",
                  file=sys.stderr)
            return False
    try:
        sys.path.insert(0, REPO)
        from pushcdn_tpu.testing.provenance import provenance
        prov = provenance()
    except Exception:
        prov = {}
    doc[section] = {"headline": headline, "provenance": prov,
                    "source": os.path.basename(path)}
    with open(bench_path, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"[series] ingested {len(headline)} timeline metrics into "
          f"{bench_path} section {section!r}")
    return True


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", default=REPO,
                    help="directory holding BENCH_r*.json (default: repo)")
    ap.add_argument("--out", default=None,
                    help="output markdown (default: <root>/BENCH_SERIES.md)")
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 if the latest round regressed >threshold "
                         "vs the previous round carrying the metric")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="gate threshold as a fraction (default 0.10)")
    ap.add_argument("--ingest-timeline", metavar="JSONL", default=None,
                    help="reduce a scripts/cdn_top.py --record timeline "
                         "into a BENCH_r<round>.json section before "
                         "rendering the series")
    ap.add_argument("--round", type=int, default=None,
                    help="round number for --ingest-timeline")
    ap.add_argument("--section", default="cluster_top",
                    help="section name for --ingest-timeline "
                         "(default cluster_top)")
    args = ap.parse_args()

    if args.ingest_timeline:
        if args.round is None:
            print("[series] --ingest-timeline needs --round",
                  file=sys.stderr)
            return 1
        if not ingest_timeline(args.root, args.ingest_timeline, args.round,
                               args.section):
            return 1

    rounds = load_rounds(args.root)
    if not rounds:
        print("[series] no BENCH_r*.json found", file=sys.stderr)
        return 1
    out_path = args.out or os.path.join(args.root, "BENCH_SERIES.md")
    with open(out_path, "w") as fh:
        fh.write(render_markdown(rounds))
    print(f"[series] wrote {out_path} "
          f"({len(rounds)} rounds: r{min(rounds)}..r{max(rounds)})")

    if args.gate:
        waived = []
        failures = gate(rounds, args.threshold, load_fingerprints(args.root),
                        waived)
        for (section, metric, prev_round, prev, cur, pct,
             fp_prev, fp_cur) in waived:
            print(f"[series] gate WAIVED {section}.{metric}: "
                  f"r{prev_round}={_fmt(prev)} -> r{max(rounds)}={_fmt(cur)} "
                  f"({pct:+.1%}) — host fingerprint changed "
                  f"({fp_prev or 'unrecorded'} -> {fp_cur or 'unrecorded'}); "
                  f"cross-host absolutes are not gated")
        for section, metric, prev_round, prev, cur, pct in failures:
            print(f"[series] GATE FAIL {section}.{metric}: "
                  f"r{prev_round}={_fmt(prev)} -> r{max(rounds)}={_fmt(cur)} "
                  f"({pct:+.1%} worse; threshold {args.threshold:.0%})")
        if failures:
            return 1
        print(f"[series] gate OK: no metric regressed "
              f">{args.threshold:.0%} vs its previous round on the "
              f"same host")
    return 0


if __name__ == "__main__":
    sys.exit(main())
