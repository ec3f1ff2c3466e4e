"""Which host phase filled each idle gap of the device.

The program keeps its own spans in the flight recorder's ring
(``GET /debug/timeline``: batch-granular phase intervals on
``CLOCK_MONOTONIC``, one id per batch) and writes ONE annotation into the
profiler's trace, ``ps:launch``, around each launch of the fused program,
carrying ``batch``, ``rows`` and a ``perf_counter_ns`` reading. This module
puts the two on one clock and reads them together:

* **the clock.** A ``ps:launch`` event has a start on the trace's host
  clock and a reading of the ring's clock taken just before it; their
  difference, taken as the median over the launches of the trace, is the
  offset. The spread of the per-launch offsets is the check (``clock()``).
* **the link.** A device runs its programs in the order they were
  enqueued. Inside each launch the runtime's own
  ``PJRT_LoadedExecutable_Execute`` event marks the enqueue (the launch's
  start stands in where a trace has none), so the executions of the fused
  program on a device (``XLA Modules`` events whose name matches a pattern
  of ``predicate_roofline.json``) and the launches pair off in order. A
  trace cuts both ends (an execution whose launch came before it began, a
  launch whose execution came after it stopped), so the pairing tries the
  few ways the two rows can be laid against each other and keeps the one
  in which the time from enqueue to execution varies least (``link()``).
* **the device's clock.** On the chip a device plane's clock is not the
  host plane's: in PR 27's traces it ran 1.7-2.8 ms ahead, so that
  executions seemed to start before they were enqueued. No execution
  starts before its enqueue, so the shift added to a device's clock is
  the least that puts none before (``link()``'s ``shift_ns``); it is
  reported, and checked from the other side: no execution may end after
  its batch's ``fetch`` interval does.
* **the attribution rule.** Take the busiest device. Its idle time is the
  gaps between its operations (``XLA Ops``). A gap that lies inside one
  execution of the program is ``in_program`` (the device waits for
  another chip, or between its own operations) and is not laid to the
  host. Every other gap ``[a, b]`` ends at the start of the program of
  some batch *k*. Inside ``[a, b]``: time under a ``gc`` interval is
  ``gc``; of the rest, time under one of batch *k*'s own ring phases up
  to its launch (``K_PHASES``) goes to that phase, the innermost (the one that began
  last) where they nest; of the rest, time under the return leg
  (``RETURN_PHASES``) of the batch whose program ran before the gap, or
  under a ``native_serialize`` interval, goes to that phase; of the rest,
  time under batch *k*'s ``dispatch`` window, which no phase inside it
  covers, is ``dispatch_self``; time before batch *k*'s earliest request
  was enqueued (the start of its ``queue_wait``) is ``no_request``: the
  load had nothing to offer; what is left is ``unattributed``. A gap that
  ends at an execution with no launch in the trace lies where the device's
  tracer ran and the host's did not (it starts earlier and stops later,
  around the profiler's own start and stop): it is no idle time the host's
  spans can speak of, and is counted apart (``outside_the_hosts_trace_s``).
  ``dispatch_self`` and ``unattributed`` are the two parts that name no
  phase: ``idle_attributed_share`` counts neither.

Nothing of the program is imported: its names (phases, the annotation and
its three stats) are the constants below.

    python benchmarks/host_spans.py <trace.xplane.pb> <timeline.json>
"""

from __future__ import annotations

import bisect
import fnmatch
import json
import statistics
import sys
import urllib.request
from pathlib import Path
from typing import Any, NamedTuple

import reduce

LAUNCH = "ps:launch"
ENQUEUE = "PJRT_LoadedExecutable_Execute"  # the runtime's, inside a launch
# batch k's own phases up to its launch; the innermost wins where they nest
K_PHASES = ("queue_wait", "form", "handoff", "prepare", "encode",
            "blob_dedup", "bookkeeping", "launch")
# the return leg of the batch whose program ran before the gap
RETURN_PHASES = ("fetch", "materialize", "deliver")
# the phases that lie inside a batch's ``dispatch`` window on its own
# pipeline thread (device_execute runs under the fetch wait: not additive)
DISPATCH_NESTED = ("handoff", "prepare", "encode", "blob_dedup", "launch",
                   "fetch", "materialize", "bookkeeping")
NAMELESS = ("dispatch_self", "unattributed", "in_program")
Interval = tuple[float, float]


class Launch(NamedTuple):
    """One ``ps:launch`` event; a trace keeps them as plain lists."""

    start_ns: float  # on the trace's host clock, as enqueue_ns
    dur_ns: float
    batch: int
    rows: int
    perf_counter_ns: int  # the ring's clock, read just before start_ns
    enqueue_ns: float


def launches_of(trace: dict) -> list[Launch]:
    return [Launch(*row) for row in trace.get("launches", ())]


# -- reading --------------------------------------------------------------------


def load_trace(path: Path) -> dict[str, Any]:
    """``reduce.load_trace``'s dict (device planes untouched) plus
    ``launches``: the host planes' ``ps:launch`` events, ``[start_ns,
    dur_ns, batch, rows, perf_counter_ns, enqueue_ns]`` sorted by enqueue.
    ``enqueue_ns`` is the start of the ``PJRT_LoadedExecutable_Execute``
    event inside the launch, on the same thread, else the launch's own
    start. A host thread is a line, and several lines share a name, so
    every line is read."""
    from jax.profiler import ProfileData

    trace = reduce.load_trace(path)
    launches = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            inside = None  # the launch this thread is in, enqueue not seen
            for ev in sorted(line.events, key=lambda e: e.start_ns):
                if ev.name == LAUNCH:
                    stats = dict(ev.stats)
                    if "perf_counter_ns" not in stats:
                        continue
                    inside = [
                        float(ev.start_ns), float(ev.duration_ns),
                        int(stats.get("batch", -1)),
                        int(stats.get("rows", 0)),
                        int(stats["perf_counter_ns"]), float(ev.start_ns)]
                    launches.append(inside)
                elif (inside is not None and ev.name.startswith(ENQUEUE)
                      and ev.start_ns <= inside[0] + inside[1]):
                    inside[5], inside = float(ev.start_ns), None
    trace["launches"] = sorted(launches, key=lambda row: row[5])
    return trace


def fetch_timeline(port: int, since_ns: int, until_ns: int) -> dict:
    """The ring's events that overlap ``[since_ns, until_ns]``, from the
    readiness port, as ``ring()`` takes them. A program without the route's
    filter returns its whole ring, which reads the same, only slower; one
    without the route, or with the recorder off, gives no events."""
    url = (f"http://127.0.0.1:{port}/debug/timeline"
           f"?since_ns={since_ns}&until_ns={until_ns}")
    try:
        with urllib.request.urlopen(url, timeout=60) as r:
            doc = json.loads(r.read())
    except (OSError, ValueError):
        doc = {}
    return {"since_ns": since_ns, "until_ns": until_ns,
            "traceEvents": doc.get("traceEvents", [])}


def ring(timeline: dict) -> list[tuple[str, float, float, int]]:
    """The batch-granular intervals of a ``/debug/timeline`` document,
    ``(phase, start_ns, end_ns, batch)`` on the ring's clock (pid 1; pid 2
    holds the sampled rows' replayed segments)."""
    out = []
    for ev in timeline.get("traceEvents", ()):
        if ev.get("ph") != "X" or ev.get("pid") != 1:
            continue
        start = ev["ts"] * 1e3
        out.append((ev["name"], start, start + ev["dur"] * 1e3,
                    int(ev.get("args", {}).get("batch", -1))))
    return out


# -- the clock and the link -----------------------------------------------------


def clock(trace: dict) -> dict[str, float] | None:
    """The offset from the trace's clock to the ring's (ring = trace +
    offset), the median over the launches, and how far they disagree."""
    offsets = sorted(at.perf_counter_ns - at.start_ns
                     for at in launches_of(trace))
    if not offsets:
        return None
    quartiles = (statistics.quantiles(offsets, n=4)
                 if len(offsets) > 1 else [offsets[0]] * 3)
    return {"offset_ns": statistics.median(offsets),
            "launches": len(offsets),
            "offset_iqr_us": (quartiles[2] - quartiles[0]) / 1e3,
            "offset_range_us": (offsets[-1] - offsets[0]) / 1e3}


def module_patterns() -> list[str]:
    return json.loads((reduce.HERE / "layer_metrics" / "predicate_roofline.json")
                      .read_text(encoding="utf-8"))["module_patterns"]


def programs(lines: dict[str, list], patterns: list[str]) -> list:
    """A device's executions of the fused program, by start."""
    return sorted(
        (ev for ev in lines.get("XLA Modules", ())
         if any(fnmatch.fnmatchcase(ev[0], p) for p in patterns)),
        key=lambda ev: ev[1])


def link(execs: list, launches: list[Launch], slack: int = 3
         ) -> tuple[list[Launch | None], float]:
    """For each execution (by start) its launch or None, and the shift to
    add to the device's clock. Both rows are in the device's order, so
    they pair off one to one; what is not known is how many executions at
    the head have their launch before the trace (and launches at the tail
    their execution after it). Every lay of the two rows within ``slack``
    (an eighth of the shorter row at most) of meeting at either end is
    tried, and the one kept is that in which
    enqueue-to-execution varies least (between its quartiles; the most
    pairs on a tie). The shift is the least that leaves no execution
    before its enqueue."""
    none: list[Launch | None] = [None] * len(execs)
    if not execs or not launches:
        return none, 0.0
    best = None
    span = len(execs) - len(launches)
    # a short row cannot tell a lay that drops pairs from one that fits
    slack = min(slack, min(len(execs), len(launches)) // 8)
    for head in range(min(0, span) - slack, max(0, span) + slack + 1):
        # execution i pairs with launch i - head
        pairs = [(i, i - head) for i in range(len(execs))
                 if 0 <= i - head < len(launches)]
        if not pairs:
            continue
        waits = sorted(execs[i][1] - launches[j].enqueue_ns
                       for i, j in pairs)
        q = (statistics.quantiles(waits, n=4) if len(waits) > 1
             else [waits[0]] * 3)
        key = (q[2] - q[0], -len(pairs))
        if best is None or key < best[0]:
            best = (key, pairs, -waits[0])
    if best is None:
        return none, 0.0
    _key, pairs, shift = best
    for i, j in pairs:
        none[i] = launches[j]
    return none, shift


# -- interval arithmetic ----------------------------------------------------------


def _clip(intervals: list[Interval], a: float, b: float) -> list[Interval]:
    return [(max(s, a), min(e, b)) for s, e in intervals if e > a and s < b]


def _union(intervals: list[Interval]) -> list[Interval]:
    out: list[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _length(intervals: list[Interval]) -> float:
    return sum(e - s for s, e in _union(intervals))


def _take(free: list[Interval], cover: list[Interval]
          ) -> tuple[float, list[Interval]]:
    """The length of ``free`` (disjoint, sorted) that ``cover`` covers, and
    what of ``free`` is left."""
    cover = _union(cover)
    taken, left = 0.0, []
    for s, e in free:
        at = s
        for cs, ce in cover:
            if ce <= at or cs >= e:
                continue
            if cs > at:
                left.append((at, cs))
            taken += min(ce, e) - max(cs, at)
            at = min(ce, e)
        if at < e:
            left.append((at, e))
    return taken, left


def _innermost(phases: list[tuple[str, float, float]], free: list[Interval]
               ) -> tuple[dict[str, float], list[Interval]]:
    """Lay ``free`` to the phases that cover it; where phases nest or
    overlap, the one that began last has the time."""
    parts: dict[str, float] = {}
    for name, s, e in sorted(phases, key=lambda p: -p[1]):
        taken, free = _take(free, [(s, e)])
        if taken:
            parts[name] = parts.get(name, 0.0) + taken
    return parts, free


# -- attribution --------------------------------------------------------------------


def _busiest(trace: dict) -> str | None:
    busy = reduce.busy_seconds(trace)
    return max(busy, key=busy.get) if busy else None


class _Spans:
    """The ring on the trace's clock, and the busiest device's executions
    of the fused program with their launches."""

    def __init__(self, events: list, offset: float, execs: list,
                 linked: list) -> None:
        self.by_batch: dict[int, list[tuple[str, float, float]]] = {}
        # intervals that belong to no batch and hold the whole process
        self.anywhere: dict[str, list[Interval]] = {
            "gc": [], "native_serialize": []}
        for name, s, e, batch in events:
            if name in self.anywhere:
                self.anywhere[name].append((s - offset, e - offset))
            elif batch >= 0:
                self.by_batch.setdefault(batch, []).append(
                    (name, s - offset, e - offset))
        self.execs, self.linked = execs, linked
        self._starts = [ev[1] for ev in execs]

    def phase(self, batch: int, name: str) -> Interval | None:
        return next(((s, e) for n, s, e in self.by_batch.get(batch, ())
                     if n == name), None)

    def clipped(self, batch: int, names: tuple, a: float, b: float) -> list:
        return [(n, *c) for n, s, e in self.by_batch.get(batch, ())
                if n in names for c in _clip([(s, e)], a, b)]

    def exec_at(self, t: float) -> int | None:
        """The execution that holds instant ``t``."""
        i = bisect.bisect_right(self._starts, t) - 1
        held = i >= 0 and t <= self.execs[i][1] + self.execs[i][2]
        return i if held else None

    def batch_of(self, i: int | None) -> int:
        launch = self.linked[i] if i is not None else None
        return -1 if launch is None else launch.batch

    def gap(self, a: float, b: float) -> dict[str, float]:
        """One gap's nanoseconds by part (the module docstring's rule)."""
        after = self.exec_at(b)
        if after is not None and self.exec_at(a) == after:
            return {"in_program": b - a}
        k = self.batch_of(after)
        if k < 0:
            return {"unattributed": b - a}
        parts: dict[str, float] = {}

        def lay(name: str, cover: list[Interval], free: list) -> list:
            taken, free = _take(free, _clip(cover, a, b))
            if taken:
                parts[name] = parts.get(name, 0.0) + taken
            return free

        free = lay("gc", self.anywhere["gc"], [(a, b)])
        mine, free = _innermost(self.clipped(k, K_PHASES, a, b), free)
        back = self.clipped(
            self.batch_of(self.exec_at(a)), RETURN_PHASES, a, b)
        back += [("native_serialize", *c)
                 for c in _clip(self.anywhere["native_serialize"], a, b)]
        theirs, free = _innermost(back, free)
        for found in (mine, theirs):
            for name, ns in found.items():
                parts[name] = parts.get(name, 0.0) + ns
        window = self.phase(k, "dispatch")
        if window is not None:
            free = lay("dispatch_self", [window], free)
        enqueued = self.phase(k, "queue_wait")
        if enqueued is not None:
            free = lay("no_request", [(a, enqueued[0])], free)
        left = sum(e - s for s, e in free)
        if left > 0 or not parts:
            parts["unattributed"] = left
        return parts


def attribute(trace: dict, timeline: dict) -> dict[str, Any] | None:
    """The module docstring's rule over the busiest device; None where the
    trace has no launch or the timeline no event (an older program, the
    recorded fixture). Times come back in seconds."""
    timing = clock(trace)
    events = ring(timeline or {})
    device = _busiest(trace)
    if timing is None or not events or device is None:
        return None
    lines, launches = trace["devices"][device], launches_of(trace)
    execs = programs(lines, module_patterns())
    linked, shift = link(execs, launches)
    # from here on the device's events are on the host plane's clock
    execs = [[name, start + shift, dur] for name, start, dur in execs]
    ops = sorted(([name, start + shift, dur]
                  for name, start, dur in reduce._ops_line(lines)),
                 key=lambda e: e[1])
    spans = _Spans(events, timing["offset_ns"], execs, linked)
    # the link's check: an execution lies after its enqueue (the shift
    # gives that), ends before its batch's fetch interval does, and starts
    # inside its batch's dispatch window
    paired = [i for i, launch in enumerate(linked) if launch is not None]
    waits = sorted(execs[i][1] - linked[i].enqueue_ns for i in paired)
    counts = {
        "executions": len(execs), "linked": len(paired),
        # the trace's two ends: launched before it began, and launched
        # after the host's tracer stopped (the device's stops later)
        "unlinked_at_the_head": paired[0] if paired else len(execs),
        "unlinked_at_the_tail": len(execs) - 1 - paired[-1] if paired else 0,
        "outside_fetch": 0, "outside_dispatch": 0,
        "device_shift_us": shift / 1e3,
        "enqueue_to_execution_us": (
            {"median": statistics.median(waits) / 1e3,
             "max": waits[-1] / 1e3} if waits else None),
    }
    for i in paired:
        ev, batch = execs[i], linked[i].batch
        fetch = spans.phase(batch, "fetch")
        if fetch is not None and ev[1] + ev[2] > fetch[1]:
            counts["outside_fetch"] += 1
        window = spans.phase(batch, "dispatch")
        if window is not None and not window[0] <= ev[1] <= window[1]:
            counts["outside_dispatch"] += 1

    totals: dict[str, float] = {}
    gaps: list[tuple[float, str]] = []
    outside = 0.0
    end = None
    for _name, start, dur in ops:
        if end is not None and start > end:
            after = spans.exec_at(start)
            if after is not None and linked[after] is None:
                # the device's tracer runs longer than the host's at both
                # ends (the profiler's own start and stop are there)
                outside += start - end
                end = max(end, start + dur)
                continue
            parts = spans.gap(end, start)
            for name, ns in parts.items():
                totals[name] = totals.get(name, 0.0) + ns
            gaps.append(((start - end) / 1e9, max(parts, key=parts.get)))
        end = max(end or 0.0, start + dur)
    named = sum(ns for name, ns in totals.items() if name not in NAMELESS)
    return {
        "device": device,
        "clock": timing,
        "link": counts,
        "idle_s": sum(totals.values()) / 1e9,
        "outside_the_hosts_trace_s": outside / 1e9,
        "attributed_s": named / 1e9,
        "idle_by_part_s": {name: ns / 1e9 for name, ns in
                           sorted(totals.items(), key=lambda kv: -kv[1])},
        "gaps": sorted(gaps, reverse=True),
    }


def breakdown(trace: dict, found: dict | None, top: int = 10) -> dict:
    """``reduce.breakdown`` with each of the busiest device's gaps named
    ``host:<the part that took most of it>``. ``found`` is ``attribute``'s
    result; where that is None (no launch in the trace, no event in the
    timeline), exactly ``reduce.breakdown``'s."""
    out = reduce.breakdown(trace, top)
    if found is not None:
        out["idle_gaps"] = [
            [("device:" if name == "in_program" else "host:") + name, seconds]
            for seconds, name in found["gaps"][:top]]
    return out


# -- readers ------------------------------------------------------------------------
# ctx as reduce's readers take it, plus ``timeline`` (fetch_timeline's) and
# ``spans`` (attribute's result over a trace that load_trace of this module
# made, or None).


def idle_attributed(p: dict, ctx: dict) -> float | None:
    """100 x the busiest device's idle seconds laid to a named phase, ``gc``
    or ``no_request`` over its idle seconds."""
    found = ctx.get("spans")
    if not found or not found["idle_s"]:
        return None
    return 100.0 * found["attributed_s"] / found["idle_s"]


def dispatch_unattributed(p: dict, ctx: dict) -> float | None:
    """The ring alone: over the batches whose ``dispatch`` window lies
    inside the traced interval, the mean of the window less the union of
    the phases nested in it, in milliseconds."""
    timeline = ctx.get("timeline")
    if not timeline:
        return None
    since, until = timeline["since_ns"], timeline["until_ns"]
    windows: dict[int, Interval] = {}
    nested: dict[int, list[Interval]] = {}
    for name, s, e, batch in ring(timeline):
        if batch < 0:
            continue
        if name == "dispatch" and s >= since and e <= until:
            windows[batch] = (s, e)
        elif name in DISPATCH_NESTED:
            nested.setdefault(batch, []).append((s, e))
    if not windows:
        return None
    left = [
        (e - s) - _length(_clip(nested.get(batch, []), s, e))
        for batch, (s, e) in windows.items()]
    return p.get("scale", 1e-6) * sum(left) / len(left)


READERS = {
    "idle_attributed": idle_attributed,
    "dispatch_unattributed": dispatch_unattributed,
}


def main(argv: list[str]) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    trace = load_trace(Path(argv[0]))
    timeline = json.loads(Path(argv[1]).read_text(encoding="utf-8"))
    print(json.dumps(attribute(trace, timeline), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
