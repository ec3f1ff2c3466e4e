#!/usr/bin/env python3
"""Checks ``BENCHMARK.json`` and the data files it names before anything
else runs (``run.py`` calls it first, and so does the test): a manifest
that breaks a limit is refused before a single run, so it is refused here
before it is sent.

    python benchmarks/check_manifest.py        # exit 0, or 1 with reasons
"""

from __future__ import annotations

import ast
import importlib
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_.\-/%]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
# what a configuration's "reference" or a mix's "generator" names: a module
# under this directory of the benchmark, which defines these names; the
# benchmark's own (reference.py, traffic.py) where none is named or where a
# mix names "pod_reviews"
PLUGINS = {
    "reference": ("references", ("MODULES", "review_response",
                                 "http_response")),
    "generator": ("generators", ("Traffic", "uid_of")),
}
OWN = {"reference": (None, "reference"), "generator": ("pod_reviews", "traffic")}
# what the harness calls on a generator's Traffic, built from (mix, seed,
# policy ids)
TRAFFIC_METHODS = ("request", "policy_of", "shape_of")
PROGRAM = "policy_server_tpu"
WARM_KEYS = ("why", "pause_phase", "pause_quiet_s", "open_after_pause_s",
             "chunk_requests")


class ManifestError(Exception):
    """``BENCHMARK.json`` or a file it names breaks a limit."""


def _line(value: object) -> bool:
    """1 to 200 printable ASCII characters on one line, no tab."""
    return (isinstance(value, str) and 1 <= len(value) <= 200
            and all(32 <= ord(ch) < 127 for ch in value))


def plugin_file(kind: str, name: str | None, bench: Path = HERE) -> Path:
    """The module a configuration's ``reference`` (``kind`` "reference") or
    a mix's ``generator`` names, under ``bench``; the benchmark's own
    beside this file where it names none of its own."""
    own, module = OWN[kind]
    if name is None or name == own:
        return HERE / f"{module}.py"
    return bench / PLUGINS[kind][0] / f"{name}.py"


def _imports(tree: ast.AST) -> list[str]:
    """Every module a file imports, by statement or by a call with the
    module's name written in."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out += [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            out.append(node.module or "")
        elif (isinstance(node, ast.Call) and node.args
              and isinstance(node.args[0], ast.Constant)
              and isinstance(node.args[0].value, str)
              and getattr(node.func, "attr", getattr(node.func, "id", None))
              in ("import_module", "__import__")):
            out.append(node.args[0].value)
    return out


def module_at(file: str | Path) -> ModuleType:
    """The module in ``file``: the benchmark's own by its import name (this
    directory is on the path of every process of a run), any other loaded
    from its file."""
    file = Path(file).resolve()
    if file.parent == HERE:
        return importlib.import_module(file.stem)
    spec = importlib.util.spec_from_file_location(
        f"_{file.parent.name}_{file.stem}", file)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _plugin(kind: str, name: object,
            bench: Path) -> tuple[ModuleType | None, list[str]]:
    """The module a name resolves to, or why it cannot stand in the
    yardstick: it is missing, imports anything of the program, or lacks a
    name the harness calls (for a generator's ``Traffic`` also a method;
    the judge reads its ``reviews`` and ``policy_ids`` as well)."""
    if name is not None and not (isinstance(name, str) and NAME.match(name)):
        return None, [f"{kind} {name!r} must match {NAME.pattern}"]
    file = plugin_file(kind, name, bench)
    if not file.is_file():
        return None, [f"{kind} {name!r}: no module "
                      f"{PLUGINS[kind][0]}/{name}.py"]
    try:
        tree = ast.parse(file.read_text(encoding="utf-8"), str(file))
    except SyntaxError as e:
        return None, [f"{kind} {name!r} does not parse: {e}"]
    bad = [f"{kind} {name!r} imports {module}: the yardstick imports "
           "nothing of the program"
           for module in _imports(tree)
           if module == PROGRAM or module.startswith(PROGRAM + ".")]
    if bad:
        return None, bad
    try:
        module = module_at(file)
    except Exception as e:  # noqa: BLE001 - any failure is the module's
        return None, [f"{kind} {name!r} cannot be loaded: {e!r}"]
    bad = [f"{kind} {name!r} defines no {want}"
           for want in PLUGINS[kind][1] if not hasattr(module, want)]
    if kind == "generator" and not bad:
        bad = [f"generator {name!r}: its Traffic has no {want}"
               for want in TRAFFIC_METHODS
               if not callable(getattr(module.Traffic, want, None))]
    return (None if bad else module), bad


def plugin_problems(kind: str, name: object, bench: Path = HERE) -> list[str]:
    """Why the module a configuration's ``reference`` or a mix's
    ``generator`` names cannot stand in the yardstick; empty when it can."""
    return _plugin(kind, name, bench)[1]


def plugin(kind: str, name: str | None, bench: Path = HERE) -> ModuleType:
    """The checked module a configuration's ``reference`` or a mix's
    ``generator`` names; raises ManifestError with every reason."""
    module, bad = _plugin(kind, name, bench)
    if bad:
        raise ManifestError("; ".join(bad))
    return module


def warm_problems(doc: object) -> list[str]:
    """A mix's warm state (``warm/<mix>.json``, read by ``run.py
    Rig._until_paused``): the ring phase of the server's background work
    whose pause the window waits for, how long a pause is, how long after
    its start the window may open, and the chunk of traffic sent
    meanwhile."""
    if not isinstance(doc, dict) or set(doc) != set(WARM_KEYS):
        return [f"a warm state holds {list(WARM_KEYS)}"]
    bad = []
    if not (isinstance(doc["pause_phase"], str)
            and NAME.match(doc["pause_phase"])):
        bad.append("warm state: pause_phase must be a phase's name")
    if not (isinstance(doc["chunk_requests"], int)
            and doc["chunk_requests"] > 0):
        bad.append("warm state: chunk_requests must be a whole number over 0")
    bad += [f"warm state: {key} must be seconds over 0"
            for key in ("pause_quiet_s", "open_after_pause_s")
            if not (isinstance(doc[key], (int, float)) and doc[key] > 0)]
    return bad


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
            if "reference" in doc:
                bad += [f"config {c.get('name')}: {reason}" for reason in
                        plugin_problems("reference", doc["reference"],
                                        root / paths[0])]
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
        mix = root / paths[0] / "traffic" / f"{w.get('traffic')}.json"
        if mix.is_file():
            generator = json.loads(mix.read_text(encoding="utf-8")).get(
                "generator")
            bad += [f"workload {w.get('name')}: {reason}" for reason in
                    plugin_problems("generator", generator, root / paths[0])]
        warm = root / paths[0] / "warm" / f"{w.get('traffic')}.json"
        if warm.is_file():
            bad += [f"workload {w.get('name')}: {reason}" for reason in
                    warm_problems(json.loads(warm.read_text(encoding="utf-8")))]
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
