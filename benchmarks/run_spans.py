#!/usr/bin/env python3
"""``run.py`` with the host's spans read: the same run of the same cell,
and in a ``--trace 1`` run also the flight recorder's ring for the traced
interval, the idle gaps named by the host phase that filled them, and the
two metrics ``host_spans.py`` reads.

    python benchmarks/run_spans.py --workload <cell> --seed <n> --seconds <s> --trace 1 [--spans-out DIR]

``--spans-out DIR`` keeps what was read: the ``.xplane.pb``,
``timeline.json`` and ``spans.json`` (``host_spans.attribute``'s, every gap
named). ``run.py``'s ``--keep`` keeps the trace too, after minutes over
its ``counters.json``.

PR 27 could only add files to the benchmark, and ``run.py`` and
``reduce.py`` are files it had. So this entry point does from outside what
three lines inside them would do, and changes no number ``run.py`` prints:

* after a traced window it fetches ``/debug/timeline`` for the traced
  interval from the readiness port, where ``run.py`` reads ``/metrics``
  (in ``Rig.window``, after ``trace.done``: ``traced["timeline"] =
  host_spans.fetch_timeline(...)``);
* it reads the trace with ``host_spans.load_trace``, which keeps the
  ``ps:launch`` events, lays the gaps once with ``host_spans.attribute``
  and names them with ``host_spans.breakdown`` (in ``run()``, where
  ``reduce.load_trace`` and ``reduce.breakdown`` are called, with
  ``timeline=`` and ``spans=`` into ``ctx``);
* it reads the metrics of ``pending/per_layer.json`` with
  ``host_spans.READERS`` (in ``reduce.py``: ``READERS.update(
  host_spans.READERS)``, and the entries appended to ``BENCHMARK.json``).

The last line gains, in ``breakdown``, ``host_spans``: idle seconds by
part, the clock's offset and spread, the link's counts, and the
collector's passes and pauses over the window by generation.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

import run  # noqa: I001 — run.py puts this directory on sys.path
import host_spans
import reduce

PENDING = run.HERE / "pending" / "per_layer.json"


class SpanRig(run.Rig):
    """A rig that keeps, of a traced window, the ring's events too."""

    traced: dict = {}

    def window(self, seconds: float, trace: bool = False,
               rate: float | None = None) -> dict:
        win = super().window(seconds, trace, rate)
        traced = win["traced"]
        if traced:
            since = int(run.load_json(
                self.control_dir / "trace.started")["at"] * 1e9)
            until = since + int(traced["done"]["traced_s"] * 1e9)
            traced["timeline"] = host_spans.fetch_timeline(
                self.server.ready_port, since, until)
            traced["window"] = (win["before"], win["after"])
            self.traced = traced
        return win


def collector(before: reduce.Samples, after: reduce.Samples) -> dict:
    """The collector's passes and pauses over the window, by generation."""
    def moved(name: str) -> list:
        return [reduce.delta(before, after, {
            "name": name, "labels": {"generation": str(g)}}) for g in range(3)]

    return {"passes": moved("policy_server_gc_passes_total"),
            "pause_s": moved("policy_server_gc_pause_seconds_total")}


def run_with_spans(args, rig: SpanRig) -> dict:
    result = _run(args, rig)
    traced = rig.traced
    if not traced:
        return result
    timeline = traced["timeline"]
    if OUT is not None:
        OUT.mkdir(parents=True, exist_ok=True)
        shutil.copy(traced["file"], OUT / traced["file"].name)
        (OUT / "timeline.json").write_text(
            json.dumps(timeline), encoding="utf-8")
    if "breakdown" not in result:  # a rehearsal: no device to read
        return result
    trace = host_spans.load_trace(traced["file"])
    found = host_spans.attribute(trace, timeline)
    ctx = {"timeline": timeline, "spans": found}
    for m in json.loads(PENDING.read_text(encoding="utf-8")):
        if rig.cell["name"] not in m["workloads"]:
            continue
        p = run.load_json(run.HERE / "layer_metrics" / f"{m['name']}.json")
        value = host_spans.READERS[p["reader"]](p, ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["breakdown"] = host_spans.breakdown(trace, found)
    if found is not None:
        if OUT is not None:
            (OUT / "spans.json").write_text(
                json.dumps(found, indent=1), encoding="utf-8")
        found = {**found, "collector_over_the_window":
                 collector(*traced["window"])}
        del found["gaps"]
        result["breakdown"]["host_spans"] = found
        run.say(f"host spans: {json.dumps(found)}")
    # compared stays the line's last key
    result["compared"] = result.pop("compared")
    return result


_run = run.run
OUT: Path | None = None

if __name__ == "__main__":
    own = argparse.ArgumentParser(add_help=False)
    own.add_argument("--spans-out", type=Path, default=None)
    mine, rest = own.parse_known_args()
    OUT = mine.spans_out
    run.Rig, run.run = SpanRig, run_with_spans
    sys.exit(run.main(rest))
