#!/usr/bin/env python3
"""The compliance scanner's reports against the benchmark's plain reference.

    python3 tools/audit_report_check.py --workload <cell> --seed <n> \\
        --seconds <s> [--platform cpu]

Boots a cell of ``BENCHMARK.json`` as ``benchmarks/run.py`` does (its ``Rig``: the same
configuration file, flags, clients and warm passes), drives one window of
the cell's traffic, then keeps the server alive, waits until the scanner
has re-judged everything the traffic left dirty, fetches
``GET /audit/reports/<namespace>`` for every namespace from the readiness
port and holds every row to ``benchmarks/reference.py`` applied to the same
object under audit-origin semantics: the raw verdict, monitor mode and
``allowedToMutate`` not applied (reference handlers.rs:69-90). It also
holds the listing to what the configuration's ``audit`` guarantee says of
it: the reports speak of the objects resident in the snapshot store, each
under every policy of the set. So every object a request of the window
created or updated is listed with a row for each policy or, if the byte
budget pushed it out, not listed at all; no more objects are missing than
the store says it evicted; and the objects listed are as many as the
store holds. Below the budget (a short window) that is every object
served, whole; above it (the cell's own 20 s) it is the resident ones.

The last line of standard output is one JSON object: ``rows_compared``,
``mismatched``, ``objects`` (listed), ``objects_served`` (by the window's
answered requests), ``objects_not_listed`` and
``objects_without_all_rows`` of those (the second counts the first and
the objects listed in part), the scanner's own counters and the store's
(``resources``, ``evicted``, ``evicted_dirty``). Exit code 0 when nothing
differs and the listing is what the guarantee says.

``expected_row`` and ``compare`` are what ``tests/test_audit_deployment.py``
holds an in-process server to on the CPU.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmarks"

NAME_PREFIX = "pod-"


def _bench(name: str):
    """A module of the benchmark, imported as the benchmark imports it."""
    import importlib

    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    return importlib.import_module(name)


def expected_row(entry: dict, request: dict, signed: set) -> dict:
    """What a report row must say of ``request``'s object under one policy
    entry of the configuration's file: the reference's answer with the
    entry's ``policyMode`` left out (a monitor policy reports what it
    found) and mutation allowed (a patch is reported, not gated)."""
    raw = {k: v for k, v in entry.items() if k != "policyMode"}
    if "expression" not in raw:
        raw["allowedToMutate"] = True
    response = _bench("reference").review_response(raw, request, signed)
    status = response.get("status") or {}
    return {
        "allowed": response["allowed"],
        "mutated": "patch" in response,
        "message": status.get("message"),
        "code": status.get("code"),
        "error": False,
    }


def compare(reports: list[dict], traffic, policies: dict, signed: set,
            served: set[int] | None = None) -> dict:
    """Every row of a reports listing against ``expected_row``. The
    object a row speaks of is found by the request number stamped into
    its name. → rows compared, rows that differ (the first kept), rows of
    an object no request created (a DELETE's, or no request's at all),
    the rows found for each object, and, where ``served`` names the
    requests that were answered, ``lacking(...)`` of them."""
    expected: dict[tuple[int, str], dict] = {}
    mismatched = strangers = 0
    first = None
    rows_of: dict[int, int] = {}
    for row in reports:
        name = row["name"]
        digits = name[len(NAME_PREFIX):]
        n = int(digits) if (name.startswith(NAME_PREFIX)
                            and digits.isdigit()) else -1
        shape = traffic.shape_of(n) if n >= 0 else -1
        request = traffic.reviews[shape]["request"] if n >= 0 else None
        if request is None or request["operation"] == "DELETE":
            strangers += 1  # a deleted object is no cluster posture
            continue
        rows_of[n] = rows_of.get(n, 0) + 1
        want = expected.get((shape, row["policy_id"]))
        if want is None:
            want = expected[shape, row["policy_id"]] = expected_row(
                policies[row["policy_id"]], request, signed)
        placed = (row["namespace"] == request["namespace"]
                  and row["kind"] == "Pod" and not row["stale"]
                  and row["resource"] == f"/v1/Pod/{request['namespace']}"
                                         f"/{name}")
        if not placed or any(row[k] != v for k, v in want.items()):
            mismatched += 1
            first = first or {"row": row, "want": want}
    out = {"rows_compared": len(reports) - strangers,
           "mismatched": mismatched, "rows_of_no_object": strangers,
           "objects": len(rows_of), "first_mismatch": first,
           "rows_of": rows_of}
    if served is not None:
        out.update(lacking(rows_of, served, traffic, len(policies)))
    return out


def lacking(rows_of: dict[int, int], served: set[int], traffic,
            n_policies: int) -> dict:
    """Of the objects the answered requests ``served`` created or updated,
    how many there are, how many are not listed at all and how many lack
    a row of some policy (the unlisted among them)."""
    kept = [n for n in served if traffic.reviews[traffic.shape_of(n)][
        "request"]["operation"] != "DELETE"]
    return {"objects_served": len(kept),
            "objects_not_listed": sum(1 for n in kept if n not in rows_of),
            "objects_without_all_rows": sum(
                1 for n in kept if rows_of.get(n, 0) != n_policies)}


def held_to_the_guarantee(result: dict, scanner: dict) -> list[str]:
    """What of a whole run's ``result`` breaks the ``audit`` guarantee of
    the configuration's file; empty when nothing does."""
    store = scanner["snapshot"]
    in_part = result["objects_without_all_rows"] - result["objects_not_listed"]
    said = {
        "rows differ from the reference": result["mismatched"],
        "no row was compared": result["rows_compared"] == 0,
        "sweeps failed": scanner["sweep_errors"],
        "objects listed under some policies only": in_part,
        "more served objects unlisted than the store evicted": max(
            0, result["objects_not_listed"] - store["evicted"]),
        "objects listed and objects resident differ":
            result["objects"] - store["resources"],
    }
    return [f"{what}: {n}" for what, n in said.items() if n]


# -- the chip run -----------------------------------------------------------------


def _get(port: int, path: str) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=600) as r:
        return json.loads(r.read())


def wait_for_the_scanner(port: int, timeout: float, say) -> dict:
    """Poll the scanner's own facts (the listing of a namespace nothing
    lives in carries them and no row) until nothing is dirty, two polls
    in a row scanned nothing more, and one more sweep has begun since
    (its head drops the rows of what the store pushed out during the
    one before); → the facts."""
    deadline = time.monotonic() + timeout
    last, settled_at = -1, None
    while True:
        scanner = _get(port, "/audit/reports/no-such-namespace")["scanner"]
        dirty, scanned = scanner["snapshot"]["dirty"], scanner["rows_scanned"]
        sweeps = scanner["dirty_sweeps"] + scanner["full_sweeps"]
        if dirty or scanned != last:
            settled_at = None
        elif settled_at is None:
            settled_at = sweeps
        elif sweeps > settled_at + 1:
            return scanner
        if time.monotonic() > deadline:
            raise SystemExit(
                f"the scanner still had {dirty} dirty objects after "
                f"{timeout:.0f}s ({scanned} rows scanned)")
        say(f"scanner: {dirty} dirty, {scanned} rows scanned, "
            f"{scanner['dirty_sweeps']} dirty sweeps")
        last = scanned
        time.sleep(2.0)


def main(argv: list[str] | None = None) -> int:
    run = _bench("run")
    traffic_mod = _bench("traffic")
    ap = run.parser(__doc__.split("\n\n")[0])
    ap.add_argument("--drain-timeout", type=float, default=900.0)
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = "cpu"  # this process holds no chip
    work = Path(tempfile.mkdtemp(prefix="audit-check-"))
    rig = None
    try:
        rig = run.Rig(args, work)
        rig.boot()
        win = rig.window(args.seconds)
        port = rig.server.ready_port
        t0 = time.monotonic()
        scanner = wait_for_the_scanner(port, args.drain_timeout, run.say)
        drained_s = time.monotonic() - t0
        traffic = traffic_mod.Traffic(rig.mix, args.seed, rig.policy_ids)
        signed = set(rig.config["signing"]["signed_images"])
        answered = {r[0] for r in win["records"] if r[4] is not None}
        result: dict = {"rows_compared": 0, "mismatched": 0,
                        "rows_of_no_object": 0, "first_mismatch": None}
        rows_of: dict[int, int] = {}
        for namespace in sorted(traffic_mod._NAMESPACES):
            reports = _get(port, f"/audit/reports/{namespace}")["reports"]
            part = compare(reports, traffic, rig.policies, signed)
            for key in ("rows_compared", "mismatched", "rows_of_no_object"):
                result[key] += part[key]
            result["first_mismatch"] = (
                result["first_mismatch"] or part["first_mismatch"])
            rows_of.update(part["rows_of"])  # an object has one namespace
            run.say(f"{namespace}: {part['rows_compared']} rows, "
                    f"{part['mismatched']} differ")
        result.update(
            lacking(rows_of, answered, traffic, len(rig.policies)),
            objects=len(rows_of), drained_s=drained_s, scanner=scanner,
            reviews_per_s=win["stats"]["reviews_per_s"],
            device=rig.info.get("device_kind", "none"),
            platform=rig.info.get("platform", "none"))
    except (run.RunFailure, run.check_manifest.ManifestError) as e:
        print(f"[audit-check] FAILED: {e}", file=sys.stderr, flush=True)
        return 3
    finally:
        if rig is not None:
            rig.stop()
        shutil.rmtree(work, ignore_errors=True)
    result["broken"] = held_to_the_guarantee(result, scanner)
    print(json.dumps(result), flush=True)
    return 1 if result["broken"] else 0


if __name__ == "__main__":
    sys.exit(main())
