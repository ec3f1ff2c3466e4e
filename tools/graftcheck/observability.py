"""Checker 3 — observability consistency (counter ↔ OTLP ↔ dashboard).

The serving runtime exports metrics through ONE funnel: names declared
as constants in ``telemetry/metrics.py``, registered either as
prometheus instruments there or as ``runtime_stats`` yields in
``server.py`` (scraped by ``_RuntimeStatsCollector``), and pushed over
OTLP by ``prometheus_to_otlp`` — which walks the same registry, so
push/pull consistency reduces to: every yield's kind must be one the
collector/converter handles. The dashboard is the third leg: every
exported family must be on a panel, and no panel may reference a family
the server does not export.

Rules:

* **OB01** — ``runtime_stats`` yields a literal metric name instead of
  a ``telemetry/metrics.py`` constant (drift magnet: the dashboard and
  tests can't grep one spelling).
* **OB02** — yielded kind outside {counter, gauge}: silently dropped by
  ``_RuntimeStatsCollector``/``prometheus_to_otlp`` — the metric would
  exist in code and never reach /metrics or OTLP.
* **OB03** — dead registered metric: a metrics.py constant that is
  never registered (prometheus instrument or runtime_stats yield).
* **OB04** — exported metric missing from the dashboard (no panel
  references any of its sample names).
* **OB05** — dashboard references a sample name the server does not
  export (dead panel, or a counter referenced without its ``_total``
  sample suffix).
* **OB06** — dashboard uses a label absent from the instrument's label
  schema (``_EVAL_LABELS``/``_INIT_LABELS``).
* **OB07** — optimizer stats-dict drift (round 15): every key of
  ``EvaluationEnvironment``'s ``OPTIMIZER_STAT_KEYS`` tuple must map to
  a metrics.py constant named ``policy_server_predicate_<key>``
  that the server exports — a stats key the observability funnel does
  not carry is invisible work (and OB03/OB04 then anchor the constant
  to a registration and a dashboard panel).
* **OB08** — flight-recorder phase totality (round 18): every phase
  name in ``telemetry/flightrec.py``'s ``PHASES`` tuple must be a
  module constant stamped by exactly ONE ``record_phase`` call site in
  the package (zero sites = a phase the timeline can never show;
  multiple sites = double-attributed time the phase-attribution
  report silently inflates), and every HISTOGRAM family registered in
  metrics.py must appear on a dashboard panel (OB04 covers families
  generally; this re-asserts it for histograms specifically, whose
  ``_bucket`` sample-name indirection makes dead panels easy to miss).
"""

from __future__ import annotations

import ast
import json
import re
from pathlib import Path

from tools.graftcheck.base import Finding

_PREFIXES = ("kubewarden_", "policy_server_")
_TOKEN_RE = re.compile(r"[a-zA-Z_][a-zA-Z0-9_]*")
_SELECTOR_RE = re.compile(r"([a-zA-Z_][a-zA-Z0-9_]*)\s*\{([^}]*)\}")
_LABEL_RE = re.compile(r"([a-zA-Z_][a-zA-Z0-9_]*)\s*(=~|!~|!=|=)")


def _metric_constants(metrics_path: Path) -> dict[str, str]:
    tree = ast.parse(metrics_path.read_text())
    out: dict[str, str] = {}
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
            and node.value.value.startswith(_PREFIXES)
        ):
            out[node.targets[0].id] = node.value.value
    return out


def _label_tuples(metrics_path: Path) -> dict[str, tuple[str, ...]]:
    tree = ast.parse(metrics_path.read_text())
    out: dict[str, tuple[str, ...]] = {}
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id in ("_EVAL_LABELS", "_INIT_LABELS")
            and isinstance(node.value, ast.Tuple)
        ):
            out[node.targets[0].id] = tuple(
                e.value
                for e in node.value.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            )
    return out


def _prom_instruments(metrics_path: Path, consts: dict[str, str]) -> dict[str, str]:
    """Reference instruments registered directly on prometheus_client:
    exported family name -> 'counter' | 'histogram'."""
    tree = ast.parse(metrics_path.read_text())
    out: dict[str, str] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        fname = (
            node.func.attr
            if isinstance(node.func, ast.Attribute)
            else node.func.id
            if isinstance(node.func, ast.Name)
            else None
        )
        if fname not in ("Counter", "Histogram", "Gauge"):
            continue
        arg = node.args[0]
        name = None
        if isinstance(arg, ast.Name):
            name = consts.get(arg.id)
        elif isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            name = arg.value
        if name:
            out[name] = fname.lower()
    return out


def _runtime_yields(
    server_path: Path, consts: dict[str, str], relpath: str
) -> tuple[list[tuple[str, str, int]], list[Finding]]:
    """(name, kind, line) triples yielded by runtime_stats + OB01/OB02
    findings for literals and unexportable kinds."""
    tree = ast.parse(server_path.read_text())
    findings: list[Finding] = []
    yields: list[tuple[str, str, int]] = []
    for node in ast.walk(tree):
        if not (
            isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name == "runtime_stats"
        ):
            continue
        for sub in ast.walk(node):
            if not (isinstance(sub, ast.Yield) and isinstance(sub.value, ast.Tuple)):
                continue
            elts = sub.value.elts
            if len(elts) < 3:
                continue
            name_expr, kind_expr = elts[0], elts[1]
            kind = (
                kind_expr.value
                if isinstance(kind_expr, ast.Constant)
                else "?"
            )
            if isinstance(name_expr, ast.Constant) and isinstance(
                name_expr.value, str
            ):
                name = name_expr.value
                findings.append(
                    Finding(
                        "observability", "OB01", relpath, sub.lineno,
                        f"runtime_stats:{name}",
                        f"runtime_stats yields literal name '{name}' — "
                        "declare it as a telemetry/metrics.py constant",
                    )
                )
            elif isinstance(name_expr, (ast.Attribute, ast.Name)):
                ident = (
                    name_expr.attr
                    if isinstance(name_expr, ast.Attribute)
                    else name_expr.id
                )
                name = consts.get(ident)
                if name is None:
                    # a constant the metrics-module scan did not yield —
                    # wrong prefix, alias defined elsewhere, or a typo;
                    # it would otherwise escape every OB cross-check
                    findings.append(
                        Finding(
                            "observability", "OB01", relpath, sub.lineno,
                            f"runtime_stats:unresolved:{ident}",
                            f"runtime_stats yields '{ident}' which is not "
                            "a kubewarden_/policy_server_-prefixed "
                            "telemetry/metrics.py constant — the "
                            "dashboard/OTLP cross-check cannot see it",
                        )
                    )
                    name = f"?{ident}"
            else:
                # computed name (BinOp / f-string / call): rejected
                # outright — it can never be cross-checked against the
                # dashboard, which is the whole point of the funnel
                findings.append(
                    Finding(
                        "observability", "OB01", relpath, sub.lineno,
                        f"runtime_stats:computed:{sub.lineno}",
                        "runtime_stats yields a COMPUTED metric name — "
                        "names must be telemetry/metrics.py constants so "
                        "the dashboard/OTLP mapping stays checkable",
                    )
                )
                continue
            if kind not in ("counter", "gauge"):
                findings.append(
                    Finding(
                        "observability", "OB02", relpath, sub.lineno,
                        f"runtime_stats:{name}:{kind}",
                        f"runtime_stats yields kind '{kind}' for '{name}' — "
                        "_RuntimeStatsCollector/prometheus_to_otlp only "
                        "export counter/gauge",
                    )
                )
            yields.append((name, kind, sub.lineno))
    return yields, findings


def _sample_names(family: str, kind: str) -> set[str]:
    """The exposition sample names one family produces (what PromQL
    actually references)."""
    if kind == "counter":
        base = family[:-6] if family.endswith("_total") else family
        return {base + "_total"}
    if kind == "histogram":
        return {family + "_bucket", family + "_sum", family + "_count"}
    return {family}


def _dashboard_exprs(dashboard: dict) -> list[str]:
    out: list[str] = []

    def walk(panels: list[dict]) -> None:
        for p in panels:
            for t in p.get("targets", []):
                e = t.get("expr")
                if e:
                    out.append(e)
            if "panels" in p:
                walk(p["panels"])

    walk(dashboard.get("panels", []))
    return out


def _stat_key_tuples(environment_path: Path) -> dict[str, tuple[str, ...]]:
    """The OPTIMIZER_STAT_KEYS tuple from
    evaluation/environment.py (module-level string-tuple assignments).
    Fixture trees without an environment module simply have no stats
    contract to enforce."""
    if not environment_path.exists():
        return {}
    tree = ast.parse(environment_path.read_text())
    out: dict[str, tuple[str, ...]] = {}
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == "OPTIMIZER_STAT_KEYS"
            and isinstance(node.value, ast.Tuple)
        ):
            out[node.targets[0].id] = tuple(
                e.value
                for e in node.value.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            )
    return out


def _flightrec_phases(flightrec_path: Path) -> tuple[dict[str, str], tuple]:
    """(PH_* constant name → phase string, PHASES member names) from
    telemetry/flightrec.py. Fixture trees without a flightrec module
    have no phase contract to enforce."""
    if not flightrec_path.exists():
        return {}, ()
    tree = ast.parse(flightrec_path.read_text())
    consts: dict[str, str] = {}
    members: tuple = ()
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            name = node.targets[0].id
            if (
                name.startswith("PH_")
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
            ):
                consts[name] = node.value.value
            elif name == "PHASES" and isinstance(node.value, ast.Tuple):
                members = tuple(
                    e.id for e in node.value.elts if isinstance(e, ast.Name)
                )
    return consts, members


def _phase_record_sites(
    package_root: Path, ph_consts: dict[str, str]
) -> dict[str, list[tuple[str, int]]]:
    """phase string → [(relpath, line), ...] for every ``record_phase``
    call whose first argument names a PH_ constant. The recorder's own
    internal writes (variable phase args, row-segment replay) do not
    count — the contract is about the STAMPING sites."""
    sites: dict[str, list[tuple[str, int]]] = {}
    for py in sorted(package_root.rglob("*.py")):
        rel = str(py.relative_to(package_root.parent))
        try:
            tree = ast.parse(py.read_text())
        except SyntaxError:  # pragma: no cover — unparseable file
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fname = (
                node.func.attr
                if isinstance(node.func, ast.Attribute)
                else node.func.id if isinstance(node.func, ast.Name)
                else None
            )
            if fname != "record_phase" or not node.args:
                continue
            arg = node.args[0]
            ident = (
                arg.attr if isinstance(arg, ast.Attribute)
                else arg.id if isinstance(arg, ast.Name) else None
            )
            phase = ph_consts.get(ident) if ident else None
            if phase is not None:
                sites.setdefault(phase, []).append((rel, node.lineno))
    return sites


def check(
    root: str | Path,
    metrics_path: str = "policy_server_tpu/telemetry/metrics.py",
    server_path: str = "policy_server_tpu/server.py",
    dashboard_path: str = "kubewarden-dashboard.json",
    environment_path: str = "policy_server_tpu/evaluation/environment.py",
    flightrec_path: str = "policy_server_tpu/telemetry/flightrec.py",
    package_path: str = "policy_server_tpu",
) -> list[Finding]:
    root = Path(root)
    findings: list[Finding] = []
    mpath = root / metrics_path
    spath = root / server_path
    dpath = root / dashboard_path

    consts = _metric_constants(mpath)
    labels = _label_tuples(mpath)
    instruments = _prom_instruments(mpath, consts)  # family -> kind
    yields, yfindings = _runtime_yields(spath, consts, server_path)
    findings.extend(yfindings)

    # OB07: every optimizer stats-dict key maps to a metrics.py
    # constant (policy_server_predicate_<key>) — OB03/OB04 then anchor
    # that constant to a registration and a dashboard panel, so the
    # whole funnel is transitively total
    _STAT_PREFIX = {
        "OPTIMIZER_STAT_KEYS": "policy_server_predicate_",
    }
    const_values = set(consts.values())
    for tuple_name, keys in sorted(
        _stat_key_tuples(root / environment_path).items()
    ):
        prefix = _STAT_PREFIX.get(tuple_name)
        if prefix is None:
            continue
        for key in keys:
            family = f"{prefix}{key}"
            if family not in const_values:
                findings.append(
                    Finding(
                        "observability", "OB07", environment_path, 0,
                        f"stat:{tuple_name}:{key}",
                        f"stats key '{key}' of {tuple_name} has no "
                        f"metrics.py constant '{family}' — the "
                        "observability funnel does not carry this "
                        "optimizer stat",
                    )
                )

    # exported families: family name -> kind
    exported: dict[str, str] = dict(instruments)
    for name, kind, _line in yields:
        if name.startswith("?"):
            continue
        family = name[:-6] if (kind == "counter" and name.endswith("_total")) else name
        exported[family] = kind
    # instruments keyed by declared name may carry _total; normalize
    normalized: dict[str, str] = {}
    for family, kind in exported.items():
        if kind == "counter" and family.endswith("_total"):
            family = family[:-6]
        normalized[family] = kind
    exported = normalized

    # OB03: declared constants never registered
    registered_names = set(instruments)
    for name, _kind, _line in yields:
        registered_names.add(name)
    for const, value in consts.items():
        if value not in registered_names:
            findings.append(
                Finding(
                    "observability", "OB03", metrics_path, 0,
                    f"const:{const}",
                    f"metric constant {const} = '{value}' is never "
                    "registered (no prometheus instrument, no "
                    "runtime_stats yield) — dead instrument",
                )
            )

    # dashboard legs
    dashboard = json.loads(dpath.read_text())
    exprs = _dashboard_exprs(dashboard)
    valid_samples: dict[str, str] = {}  # sample -> family
    for family, kind in exported.items():
        for s in _sample_names(family, kind):
            valid_samples[s] = family

    referenced_families: set[str] = set()
    seen_tokens: set[str] = set()
    for expr in exprs:
        for token in _TOKEN_RE.findall(expr):
            if not token.startswith(_PREFIXES) or token in seen_tokens:
                continue
            seen_tokens.add(token)
            fam = valid_samples.get(token)
            if fam is None:
                findings.append(
                    Finding(
                        "observability", "OB05", dashboard_path, 0,
                        f"panel:{token}",
                        f"dashboard references '{token}' which the server "
                        "does not export (dead panel or missing _total "
                        "sample suffix)",
                    )
                )
            else:
                referenced_families.add(fam)

    for family, kind in sorted(exported.items()):
        if family not in referenced_families:
            findings.append(
                Finding(
                    "observability", "OB04", dashboard_path, 0,
                    f"family:{family}",
                    f"exported {kind} '{family}' has no dashboard panel "
                    "referencing it",
                )
            )

    # OB08: flight-recorder phase totality — every PHASES member stamped
    # by exactly one record_phase site, every histogram family on a
    # panel. Trees without a flightrec module have no phase contract.
    ph_consts, ph_members = _flightrec_phases(root / flightrec_path)
    if ph_members:
        member_values = sorted(
            ph_consts[m] for m in ph_members if m in ph_consts
        )
        sites = _phase_record_sites(root / package_path, ph_consts)
        for phase in member_values:
            hits = sites.get(phase, [])
            if len(hits) == 0:
                findings.append(
                    Finding(
                        "observability", "OB08", flightrec_path, 0,
                        f"phase:unstamped:{phase}",
                        f"flight-recorder phase '{phase}' is in PHASES "
                        "but no record_phase call site stamps it — the "
                        "timeline can never show this phase",
                    )
                )
            elif len(hits) > 1:
                where = ", ".join(f"{p}:{ln}" for p, ln in hits)
                findings.append(
                    Finding(
                        "observability", "OB08", flightrec_path, 0,
                        f"phase:multi:{phase}",
                        f"flight-recorder phase '{phase}' is stamped by "
                        f"{len(hits)} sites ({where}) — double-stamped "
                        "time inflates the phase-attribution report",
                    )
                )
        for family, kind in sorted(instruments.items()):
            if kind != "histogram":
                continue
            if family not in referenced_families:
                findings.append(
                    Finding(
                        "observability", "OB08", dashboard_path, 0,
                        f"histogram:{family}",
                        f"histogram family '{family}' has no dashboard "
                        "panel referencing any of its _bucket/_sum/"
                        "_count samples",
                    )
                )

    # OB06: label schema consistency for the reference instruments
    eval_labels = set(labels.get("_EVAL_LABELS", ())) | {"le"}
    init_labels = set(labels.get("_INIT_LABELS", ()))
    for expr in exprs:
        for metric, body in _SELECTOR_RE.findall(expr):
            if not metric.startswith("kubewarden_"):
                continue
            allowed = (
                init_labels
                if "initialization" in metric
                else eval_labels
            )
            for label, _op in _LABEL_RE.findall(body):
                if label not in allowed:
                    findings.append(
                        Finding(
                            "observability", "OB06", dashboard_path, 0,
                            f"label:{metric}:{label}",
                            f"dashboard filters '{metric}' by label "
                            f"'{label}' which is not in its label schema",
                        )
                    )
    return findings
