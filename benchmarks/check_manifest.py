#!/usr/bin/env python3
"""Checks ``BENCHMARK.json`` and the data files it names before anything
else runs (``run.py`` calls it first, and so does the test): a manifest
that breaks a limit is refused before a single run, so it is refused here
before it is sent.

    python benchmarks/check_manifest.py        # exit 0, or 1 with reasons
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_.\-/%]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


class ManifestError(Exception):
    """``BENCHMARK.json`` or a file it names breaks a limit."""


def _line(value: object) -> bool:
    """1 to 200 printable ASCII characters on one line, no tab."""
    return (isinstance(value, str) and 1 <= len(value) <= 200
            and all(32 <= ord(ch) < 127 for ch in value))


def answers_from_problems(doc: dict, bench: Path) -> list[str]:
    """A configuration's file states who may answer its requests:
    ``guarantees.answers_from``, from a source (a file of
    ``answer_sources/``) to the counter that file names, ``device`` among
    them. ``correct`` holds the run to exactly that (``run.py``)."""
    named = (doc.get("guarantees") or {}).get("answers_from")
    if not isinstance(named, dict):
        return ["its file states no guarantees.answers_from"]
    bad = []
    if "device" not in named:
        bad.append("guarantees.answers_from must name device: every cell "
                   "drives the device path")
    for source, counter in named.items():
        file = bench / "answer_sources" / f"{source}.json"
        if not (isinstance(source, str) and NAME.match(source)
                and file.is_file()):
            bad.append(f"guarantees.answers_from names {source!r}, which "
                       f"is no source of {bench.name}/answer_sources/")
            continue
        spec = json.loads(file.read_text(encoding="utf-8"))
        if spec["counts"] != "requests":
            bad.append(f"source {source} counts {spec['counts']}, not "
                       "requests: it can answer for none")
        if counter != spec["counter"]:
            bad.append(f"source {source} is counted by "
                       f"{spec['counter']}, not {counter!r}")
    return bad


def problems(manifest: dict, root: Path = ROOT) -> list[str]:
    """Every reason the manifest would be refused; empty when it is sound."""
    bad: list[str] = []
    if set(manifest) != TOP_KEYS:
        bad.append(f"top-level keys must be exactly {sorted(TOP_KEYS)}")
        return bad

    def name(kind: str, value: object) -> None:
        if not isinstance(value, str) or not NAME.match(value):
            bad.append(f"{kind} name {value!r} must match {NAME.pattern}")

    def keys(kind: str, entry: dict, required: set, optional: set = frozenset()) -> None:
        if not required <= set(entry) or set(entry) - required - optional:
            bad.append(f"{kind} {entry.get('name')!r} must have keys "
                       f"{sorted(required)} (+ {sorted(optional)})")

    paths = manifest["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16
            and all(isinstance(p, str) and PATH.match(p)
                    and not p.startswith("/") and ".." not in p.split("/")
                    for p in paths)):
        bad.append("paths must be 1 to 16 relative directories")
        return bad
    command = manifest["command"]
    if not (isinstance(command, list) and 1 <= len(command) <= 32
            and all(_line(w) for w in command)):
        bad.append("command must be 1 to 32 one-line strings")
    seconds = manifest["run_seconds"]
    if not (isinstance(seconds, int) and 1 <= seconds <= 51):
        bad.append("run_seconds must be a whole number from 1 to 51")

    def under_paths(file: str) -> bool:
        return PATH.match(file) is not None and any(
            file.startswith(p.rstrip("/") + "/") for p in paths)

    configs: dict[str, dict] = {}
    files: set[str] = set()
    for c in manifest["configs"]:
        keys("config", c, {"name", "source", "file", "reduced", "why"})
        name("config", c.get("name"))
        if c.get("name") in configs:
            bad.append(f"two configs are named {c.get('name')!r}")
        configs[c.get("name")] = c
        if not _line(c.get("source")):
            bad.append(f"config {c.get('name')}: source must be 1 to 200 "
                       f"printable ASCII characters, not "
                       f"{len(str(c.get('source')))}")
        if not _line(c.get("why")):
            bad.append(f"config {c.get('name')}: why must be 1 to 200 "
                       "printable ASCII characters")
        file = c.get("file", "")
        if not under_paths(file) or file in files:
            bad.append(f"config {c.get('name')}: file {file!r} must lie "
                       "under paths and be no other configuration's")
        elif not (root / file).is_file():
            bad.append(f"config {c.get('name')}: no file {file}")
        else:
            doc = json.loads((root / file).read_text(encoding="utf-8"))
            if doc.get("source") != c.get("source"):
                bad.append(f"config {c.get('name')}: the file's source "
                           "differs from the manifest's")
            bad += [f"config {c.get('name')}: {reason}" for reason in
                    answers_from_problems(doc, root / paths[0])]
        files.add(file)
        reduced = c.get("reduced")
        if not isinstance(reduced, list) or len(reduced) > 16:
            bad.append(f"config {c.get('name')}: reduced is a list of at "
                       "most 16 keys")
        else:
            for key in reduced:
                name("reduced key", key)
    if not 1 <= len(configs) <= 24:
        bad.append("1 to 24 configs")

    cells: dict[str, dict] = {}
    pairs: set[tuple] = set()
    for w in manifest["workloads"]:
        keys("workload", w, {"name", "config", "traffic", "chips", "why"})
        name("workload", w.get("name"))
        name("traffic", w.get("traffic"))
        if w.get("name") in cells:
            bad.append(f"two workloads are named {w.get('name')!r}")
        cells[w.get("name")] = w
        if w.get("config") not in configs:
            bad.append(f"workload {w.get('name')}: no config "
                       f"{w.get('config')!r}")
        if w.get("chips") not in (1, 4):
            bad.append(f"workload {w.get('name')}: chips is 1 or 4")
        if not _line(w.get("why")):
            bad.append(f"workload {w.get('name')}: why must be 1 to 200 "
                       "printable ASCII characters")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            bad.append(f"workload {w.get('name')}: the pair {pair} "
                       "appears twice")
        pairs.add(pair)
        if not any((root / paths[0] / "traffic"
                    / f"{w.get('traffic')}{suffix}").is_file()
                   for suffix in TRAFFIC_SUFFIXES):
            bad.append(f"workload {w.get('name')}: no traffic file "
                       f"{paths[0]}/traffic/{w.get('traffic')}.json")
    if not 1 <= len(cells) <= 24:
        bad.append("1 to 24 workloads")
    four = sum(1 for w in cells.values() if w.get("chips") == 4)
    if four > max(1, len(cells) // 2):
        bad.append(f"{four} of {len(cells)} workloads ask for 4 chips: at "
                   "most half, rounded down, and one always may")
    for cname in configs:
        if not any(w.get("config") == cname for w in cells.values()):
            bad.append(f"config {cname} is used by no workload")

    metrics: set[str] = set()
    e2e: dict[str, dict] = {}
    for m in manifest["end_to_end"]:
        keys("end-to-end metric", m,
             {"name", "unit", "better", "bound", "source"}, {"workloads"})
        e2e[m.get("name")] = m
        if m.get("source") not in ("host_clock", "device_trace"):
            bad.append(f"metric {m.get('name')}: an end-to-end metric's "
                       "source is host_clock or device_trace")
        bound = m.get("bound")
        if not (isinstance(bound, (int, float)) and 0 < bound <= 0.25):
            bad.append(f"metric {m.get('name')}: bound must be in (0, 0.25]")
    if "setup_s" not in e2e:
        bad.append("end_to_end must hold setup_s")
    if not 1 <= len(e2e) <= 16:
        bad.append("1 to 16 end-to-end metrics")

    def reported_in(metric: dict) -> set[str]:
        return set(metric.get("workloads") or cells)

    layers = manifest["per_layer"]
    if not 1 <= len(layers) <= 128:
        bad.append("1 to 128 per-layer metrics")
    for m in [*manifest["end_to_end"], *layers]:
        name("metric", m.get("name"))
        if m.get("name") in metrics:
            bad.append(f"two metrics are named {m.get('name')!r}")
        metrics.add(m.get("name"))
        if not isinstance(m.get("unit"), str) or not UNIT.match(m["unit"]):
            bad.append(f"metric {m.get('name')}: unit {m.get('unit')!r} "
                       f"must match {UNIT.pattern}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m.get('name')}: better is lower or higher")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m.get('name')}: source is one of {SOURCES}")
        for cell in m.get("workloads") or ():
            if cell not in cells:
                bad.append(f"metric {m.get('name')}: no workload {cell!r}")
    for m in layers:
        keys("per-layer metric", m,
             {"name", "unit", "better", "source", "layer", "moves"},
             {"workloads"})
        if not _line(m.get("layer")):
            bad.append(f"metric {m.get('name')}: layer must be 1 to 200 "
                       "printable ASCII characters")
        moved = e2e.get(m.get("moves"))
        if moved is None:
            bad.append(f"metric {m.get('name')}: moves {m.get('moves')!r} "
                       "is no end-to-end metric")
        elif not reported_in(m) <= reported_in(moved):
            bad.append(f"metric {m.get('name')}: not all of its workloads "
                       f"report {m.get('moves')}")
        if not (root / paths[0] / "layer_metrics"
                / f"{m.get('name')}.json").is_file():
            bad.append(f"metric {m.get('name')}: no reader file "
                       f"{paths[0]}/layer_metrics/{m.get('name')}.json")
    for cname, cell in cells.items():
        mine = [m for m in e2e.values() if cname in reported_in(m)]
        if len(mine) < 2 or not any(m.get("name") == "setup_s" for m in mine):
            bad.append(f"workload {cname} reports setup_s and one more "
                       "end-to-end metric at least")
        if not any(cname in reported_in(m) for m in layers):
            bad.append(f"workload {cname} reports no per-layer metric")
    return bad


def load(root: Path = ROOT) -> dict:
    """The checked manifest; raises ManifestError with every reason."""
    path = root / "BENCHMARK.json"
    raw = path.read_bytes()
    if len(raw) > 64 * 1024:
        raise ManifestError("BENCHMARK.json is over 64 KiB")
    manifest = json.loads(raw)
    bad = problems(manifest, root)
    if bad:
        raise ManifestError("BENCHMARK.json is refused:\n  " + "\n  ".join(bad))
    return manifest


if __name__ == "__main__":
    try:
        load()
    except ManifestError as e:
        print(e, file=sys.stderr)
        sys.exit(1)
    print("BENCHMARK.json: sound")
