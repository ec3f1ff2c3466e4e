"""The benchmark's arithmetic: from client records, ``/metrics`` texts and
a profiler trace to the numbers a run prints. Nothing here is read from the
program but its counters' names (in the layer metrics' data files) and its
trace; later PRs add data files, not code.

A per-layer metric is a data file ``benchmarks/layer_metrics/<name>.json``
naming a reader kind and its parameters: a kind of ``READERS`` here, or of
the ``READERS`` of the module of this directory its ``"module"`` names
(``host_spans``), so a later reader is a file of its own. A reader that
finds nothing to read returns ``None`` and the metric is left out of the
line.

A way the program can answer a request is a data file
``benchmarks/answer_sources/<source>.json`` naming the counter that counts
the requests it answered; a configuration's ``guarantees.answers_from``
names those that may answer in that deployment (``held_to_its_sources``).
"""

from __future__ import annotations

import fnmatch
import importlib
import json
from pathlib import Path
from typing import Any, Callable

import numpy as np

HERE = Path(__file__).resolve().parent
Samples = dict[str, list[tuple[dict[str, str], float]]]


# -- client records -----------------------------------------------------------


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks."""
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def latency_ms(due, done, good, timeout_s: float) -> np.ndarray:
    """Latency of every request from the instant it was due to its last
    byte; a request with no good answer counts as the caller's limit."""
    lat = (np.asarray(done, np.float64) - np.asarray(due, np.float64)) * 1e3
    return np.where(np.asarray(good, bool), lat, timeout_s * 1e3)


def rate_per_s(done, good, t0: float, seconds: float) -> float:
    """Good answers received inside the window over the window's length:
    all the work over all the time."""
    done = np.asarray(done, np.float64)
    inside = np.asarray(good, bool) & (done >= t0) & (done <= t0 + seconds)
    return float(inside.sum()) / seconds


TAILS = (50, 75, 90, 95, 99)  # the percentiles of latency a window reports


def client_stats(rec: dict[str, np.ndarray], t0: float, seconds: float,
                 timeout_s: float) -> dict[str, float]:
    lat = latency_ms(rec["due"], rec["done"], rec["good"], timeout_s)
    late = (rec["sent"] - rec["due"]) * 1e3
    late = late[~np.isnan(late)]
    return {
        **{f"latency_p{q}_ms": percentile(lat, q) for q in TAILS},
        "reviews_per_s": rate_per_s(rec["done"], rec["good"], t0, seconds),
        "late_p99_ms": percentile(late, 99) if late.size else float("nan"),
    }


# -- /metrics -----------------------------------------------------------------


def parse_metrics(text: str) -> Samples:
    """Prometheus text exposition → {sample name: [(labels, value)]}."""
    from prometheus_client.parser import text_string_to_metric_families

    out: Samples = {}
    for family in text_string_to_metric_families(text):
        for sample in family.samples:
            out.setdefault(sample.name, []).append(
                (dict(sample.labels), sample.value)
            )
    return out


def sample(samples: Samples, want: str | dict) -> float | None:
    """Value of one sample: a family's name (counters also expose as
    ``<name>_total``) or ``{"name": ..., "labels": {...}}``."""
    if isinstance(want, str):
        want = {"name": want}
    labels = want.get("labels") or {}
    for name in (want["name"], want["name"] + "_total"):
        for have, value in samples.get(name, ()):
            if all(have.get(k) == v for k, v in labels.items()):
                return value
    return None


def delta(before: Samples, after: Samples, want: str | dict) -> float | None:
    a, b = sample(before, want), sample(after, want)
    return None if a is None or b is None else b - a


# -- who answered ---------------------------------------------------------------


def answer_sources() -> dict[str, dict]:
    """Every way the program can answer a request with a verdict, by name:
    ``{"counter": ..., "counts": "requests" | "events", "what": ...}``."""
    return {path.stem: json.loads(path.read_text(encoding="utf-8"))
            for path in sorted((HERE / "answer_sources").glob("*.json"))}


def answers_by_source(before: Samples, after: Samples) -> dict[str, float]:
    """What each source's counter moved by over the window; a program
    without the counter has no such source: 0."""
    return {name: delta(before, after, spec["counter"]) or 0.0
            for name, spec in answer_sources().items()}


def held_to_its_sources(config: dict, before: Samples, after: Samples,
                        answers: int) -> dict[str, float]:
    """The two numbers that hold a configuration to who may answer its
    requests, both with the limit 0. ``answered_off_device``: what the
    sources its ``guarantees.answers_from`` does NOT name answered (for a
    configuration that names the device alone, everything off the device).
    ``rows_not_dispatched``: how far the sources it DOES name are from
    having counted every answer exactly once (for such a configuration,
    |answers - rows dispatched|)."""
    named = config["guarantees"]["answers_from"]
    moved = answers_by_source(before, after)
    return {
        "answered_off_device": sum(
            n for source, n in moved.items() if source not in named),
        "rows_not_dispatched": abs(answers - sum(
            moved[source] for source in named)),
    }


# -- the trace ----------------------------------------------------------------


def load_trace(path: Path) -> dict[str, Any]:
    """An ``.xplane.pb`` → {"devices": {plane: {line: [[name, start_ns,
    dur_ns], ...]}}} for the device planes; needs no JAX backend."""
    from jax.profiler import ProfileData

    devices: dict[str, dict[str, list]] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = devices.setdefault(plane.name, {})
        for line in plane.lines:
            # an operation's name is its whole HLO line: keep what comes
            # before " = ", the name a reader can find again
            lines[line.name] = [
                [ev.name.split(" = ", 1)[0], float(ev.start_ns),
                 float(ev.duration_ns)]
                for ev in line.events
            ]
    return {"devices": devices}


def _ops_line(lines: dict[str, list]) -> list:
    """The line of a device plane whose events are the operations run."""
    for name in ("XLA Ops", "XLA Modules"):
        if lines.get(name):
            return lines[name]
    return []


def union_ns(events: list) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, -1.0
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def busy_seconds(trace: dict[str, Any]) -> dict[str, float]:
    """Seconds in which an operation ran, for each device of the trace."""
    return {
        plane: union_ns(_ops_line(lines)) / 1e9
        for plane, lines in trace["devices"].items()
        if _ops_line(lines)
    }


def module_seconds(trace: dict[str, Any], patterns: list[str]) -> float | None:
    """Summed device time of the programs (``XLA Modules`` events) whose
    name matches a pattern, on the busiest device; None if none ran."""
    best = None
    for lines in trace["devices"].values():
        total = sum(
            dur for name, _s, dur in lines.get("XLA Modules", ())
            if any(fnmatch.fnmatchcase(name, p) for p in patterns)
        )
        if total > 0 and (best is None or total > best):
            best = total
    return None if best is None else best / 1e9


def breakdown(trace: dict[str, Any], top: int = 10) -> dict[str, list]:
    """The device operations that took most time, and the longest idle
    gaps of every device, none laid to a host phase: what a trace alone can
    say. ``host_spans.breakdown`` names the busiest device's gaps where the
    program's spans allow it, and falls back to this."""
    per_op: dict[str, float] = {}
    gaps: list[float] = []
    for lines in trace["devices"].values():
        end = None
        for name, start, dur in sorted(_ops_line(lines), key=lambda e: e[1]):
            per_op[name] = per_op.get(name, 0.0) + dur
            if end is not None and start > end:
                gaps.append(start - end)
            end = max(end or 0.0, start + dur)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": [["host:unattributed", ns / 1e9]
                      for ns in sorted(gaps, reverse=True)[:top]],
    }


# -- readers ------------------------------------------------------------------
# ctx: before/after (/metrics samples around the window), client (stats of
# the window), trace (load_trace's, or None), traced_s, trace_before /
# trace_after (samples around the traced part), config, peaks.


def counter_ratio(p: dict, ctx: dict) -> float | None:
    num = delta(ctx["before"], ctx["after"], p["numerator"])
    den = delta(ctx["before"], ctx["after"], p["denominator"])
    if num is None or not den:
        return None
    return p.get("scale", 1.0) * num / den


def counter_delta(p: dict, ctx: dict) -> float | None:
    parts = [delta(ctx["before"], ctx["after"], c) for c in p["counters"]]
    return None if any(x is None for x in parts) else float(sum(parts))


def client_stat(p: dict, ctx: dict) -> float | None:
    value = ctx["client"].get(p["stat"])
    return None if value is None or value != value else value


def trace_idle(p: dict, ctx: dict) -> float | None:
    if not ctx.get("trace") or not ctx.get("traced_s"):
        return None
    busy = busy_seconds(ctx["trace"])
    if not busy:
        return None
    return 100.0 * (1.0 - max(busy.values()) / ctx["traced_s"])


def trace_roofline(p: dict, ctx: dict) -> float | None:
    """Least time the chip could take over the device time it took. Least
    time: rows dispatched while traced × the configuration's bytes per row
    ÷ the peak of ``p["bound"]``, per chip."""
    if not ctx.get("trace"):
        return None
    device_s = module_seconds(ctx["trace"], p["module_patterns"])
    rows = delta(ctx["trace_before"], ctx["trace_after"], p["rows"])
    if not device_s or not rows:
        return None
    per_row = sum(ctx["config"][key]["value"] for key in p["bytes_per_row"])
    chips = int(ctx["config"]["chips"])
    least_s = rows * per_row / chips / ctx["peaks"][p["bound"]]
    return 100.0 * least_s / device_s


READERS: dict[str, Callable[[dict, dict], float | None]] = {
    "counter_ratio": counter_ratio,
    "counter_delta": counter_delta,
    "client_stat": client_stat,
    "trace_idle": trace_idle,
    "trace_roofline": trace_roofline,
}


def read_layer_metric(name: str, ctx: dict) -> float | None:
    p = json.loads(
        (HERE / "layer_metrics" / f"{name}.json").read_text(encoding="utf-8"))
    readers = (importlib.import_module(p["module"]).READERS
               if "module" in p else READERS)
    return readers[p["reader"]](p, ctx)


def peaks_of(device_kind: str) -> dict[str, float]:
    table = json.loads((HERE / "peaks.json").read_text(encoding="utf-8"))
    if device_kind not in table["devices"]:
        raise KeyError(
            f"peaks.json has no device kind {device_kind!r}: add its "
            "published peaks, with their source")
    return table["devices"][device_kind]
