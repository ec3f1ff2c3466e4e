"""PR 38: ``flagship32-audit``, live admission with the in-process
compliance scanner on, as a deployment the benchmark holds to its
guarantees (benchmarks/configs/flagship32-audit.json).

* a real server under the configuration's flags (routing pinned to the
  device, cache off, ``--audit-mode interval``) serves a seeded stream of
  257 pod shapes over HTTP on BOTH front-ends; once the scanner has swept
  what the stream left dirty, every row of ``GET /audit/reports`` equals
  the benchmark's plain reference applied to the same object under
  audit-origin semantics (monitor mode and ``allowedToMutate`` not
  applied), every object served has a row of every policy, and the live
  answers were byte-exact while the scanner ran;
* one answer, one source: audit rows move
  ``policy_server_audit_rows_dispatched`` and no answer source
  (``reduce.held_to_its_sources`` gives 0 and 0 over the stream);
* the native front-end's zero-parse request carries the object's identity
  (kind, name) off the canonical payload's head, so the snapshot store is
  fed there too, without a parse of the object;
* lane discipline: a live batch that arrives while an audit job is queued
  is dispatched first and the job handed back (``audit_preemptions`` +1);
  a job wider than ``--max-batch-size`` goes out in live-sized slices; the
  lane waits while every pipeline worker holds a live batch;
* what the byte budget costs in coverage: an object the store pushes out
  loses its report rows at the next sweep's head, a sweep that had
  collected it skips it, and one no sweep had finished with is counted
  (``policy_server_audit_objects_unjudged``); the whole-run check of
  ``tools/audit_report_check.py`` holds a listing to exactly that;
* one ring phase ``audit_dispatch`` with one stamp site, under a batch id
  of its own, the environment's phases inside it;
* the benchmark's data files: the configuration differs from
  ``flagship32.json`` in the listed keys only, the pending mix from
  ``unique-saturate.json`` in ``connections`` only, the cell is the
  issue's letter for letter but for the four accepted metrics that would
  mix the lane's launches with the live ones, the new layer metrics read the program's own
  counters and read nothing on a program without them.
"""

from __future__ import annotations

import json
import sys
import threading
from pathlib import Path

import pytest
import requests

from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.audit.snapshot import SnapshotStore, resource_key
from policy_server_tpu.models import (
    AdmissionRequest,
    AdmissionReviewRequest,
    ValidateRequest,
)
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.runtime.batcher import MicroBatcher
from policy_server_tpu.runtime.frontend import (
    _IDENTITY,
    WireValidateRequest,
)
from policy_server_tpu.telemetry import flightrec
from policy_server_tpu.telemetry import metrics as metrics_mod
from policy_server_tpu.telemetry.flightrec import FlightRecorder

from test_audit import _wait_until, make_scanner, pod_review
# the same 32 policies, built and planted as the cached deployment's tests
# do (this file pins its configuration's against flagship32's, that one its)
from test_cached_deployment import (  # noqa: F401 (policies: the fixture)
    PHASE,
    _planted,
    policies,
)
from test_server import ServerHandle, make_config

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))
try:
    import check_manifest
    import reduce
    import reference
    from traffic import Traffic, uid_of
finally:
    sys.path.remove(str(BENCH))
from tools import audit_report_check  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((BENCH / "configs" / "flagship32-audit.json").read_text())
BASE = json.loads((BENCH / "configs" / "flagship32.json").read_text())
CELL = "flagship32-audit.unique-saturate"
# asked for beside it by ISSUE 38, measured by the builder, and left out as
# the issue rules: its runs spread wider than a new cell may (PERF.md §7)
TRICKLE = "flagship32-audit.unique-trickle"
# the default install with the scanner on, under that mix: a later cell
DEFAULTS_CELL = "flagship32-audit-defaults.unique-trickle"
NEW_METRICS = ("audit_rows_per_live_row", "audit_rows_per_batch",
               "audit_preemptions_per_batch", "audit_dispatch_ms_mean",
               "audit_observe_us_per_req",
               "audit_snapshot_evictions_in_window",
               "audit_objects_unjudged_in_window")
# accepted metrics flagship32.unique-saturate reports that this cell does
# not: each would fold the lane's 128-row launches in with the live
# batches' (three means over all launches, and a roofline whose rows are
# the live ones while its device time is everybody's)
MIXED = {"predicate_roofline", "launch_ms_mean", "materialize_ms_mean",
         "h2d_arrays_per_launch"}
SIGNED = set(CONFIG["signing"]["signed_images"])
SEED = 2**31 + 38
MIX = {"generator": "pod_reviews", "pool_shapes": 257, "arrival": "closed"}
REQUESTS = 3 * 257  # every shape three times, under three policy ids


def _metrics(handle: ServerHandle) -> reduce.Samples:
    return reduce.parse_metrics(
        requests.get(handle.readiness_url("/metrics"), timeout=30).text)


@pytest.fixture(scope="module", params=["python", "native"])
def served(request, policies) -> dict:
    """The deployment behind one front-end, the stream served through it
    from four callers at once, and the scanner left to finish."""
    metrics_mod.reset_metrics_for_tests()
    flags = CONFIG["server_flags"]
    assert flags[flags.index("--audit-mode") + 1] == "interval"
    handle = ServerHandle(make_config(
        policies={k: parse_policy_entry(k, v) for k, v in policies.items()},
        frontend=request.param, policy_timeout_seconds=10.0,
        max_batch_size=128, host_fastpath_threshold=0, latency_budget_ms=0,
        verdict_cache_size=0, audit_mode="interval",
        # the file's cadence is 2 s; a sweep's work is the same at any
        audit_interval_seconds=0.25))
    try:
        ids = list(policies)
        traffic = Traffic(MIX, SEED, ids)
        before = _metrics(handle)
        answers: list = [None] * REQUESTS

        def call(k: int) -> None:
            with requests.Session() as session:
                for n in range(k, REQUESTS, 4):
                    head, _, body = traffic.request(n).partition(b"\r\n\r\n")
                    path = head.split(b" ", 2)[1].decode()
                    answers[n] = session.post(
                        handle.url(path), data=body, timeout=60,
                        headers={"Content-Type": "application/json"})

        callers = [threading.Thread(target=call, args=(k,)) for k in range(4)]
        for c in callers:
            c.start()
        for c in callers:
            c.join()
        scanner = handle.server.state.audit
        kept = sum(1 for n in range(REQUESTS) if traffic.reviews[
            traffic.shape_of(n)]["request"]["operation"] != "DELETE")
        swept = _wait_until(lambda: (
            scanner.snapshot.stats()["dirty"] == 0
            and scanner.reports.stats()["resident"] == kept * len(ids)),
            timeout=60)
        reports = requests.get(
            handle.readiness_url("/audit/reports"), timeout=60).json()
        yield {"handle": handle, "traffic": traffic, "answers": answers,
               "reports": reports, "swept": swept, "kept": kept,
               "before": before, "after": _metrics(handle),
               "policies": policies, "frontend": request.param}
    finally:
        handle.stop()
        metrics_mod.reset_metrics_for_tests()


# -- the scanner's reports against the plain reference ----------------------------


def test_every_report_row_equals_the_reference(served):
    assert served["swept"], served["reports"]["scanner"]
    found = audit_report_check.compare(
        served["reports"]["reports"], served["traffic"], served["policies"],
        SIGNED, served=set(range(REQUESTS)))
    assert found["first_mismatch"] is None, json.dumps(
        found["first_mismatch"])
    assert set(found["rows_of"].values()) == {32}
    assert found["mismatched"] == 0 and found["rows_of_no_object"] == 0
    # every object a request created or updated, under every policy
    assert found["objects"] == found["objects_served"] == served["kept"]
    assert found["rows_compared"] == served["kept"] * 32
    assert found["objects_without_all_rows"] == 0
    assert served["reports"]["scanner"]["sweep_errors"] == 0


def test_the_reports_hold_raw_verdicts_not_the_callers_answers(served):
    """The two constraints the service layer applies to a caller's answer
    are not in a report row: a monitor policy reports what it found, and
    the comparison above would pass vacuously if no row differed from the
    answer a caller got."""
    by_policy: dict[str, list] = {}
    for row in served["reports"]["reports"]:
        by_policy.setdefault(row["policy_id"], []).append(row)
    assert len(by_policy) == 32
    # audit-unhappy is always-unhappy in monitor mode: callers are let in
    assert all(r["allowed"] is False and r["code"] == 400
               for r in by_policy["audit-unhappy"])
    assert any(r["allowed"] is False
               for r in by_policy["pod-privileged-monitor"])
    assert any(r["mutated"] for r in by_policy["psp-capabilities"])
    assert {r["allowed"] for r in by_policy["pod-security-group"]} == {
        True, False}


def test_the_live_answers_were_exact_while_the_scanner_ran(served):
    traffic, ids = served["traffic"], list(served["policies"])
    for n, answer in enumerate(served["answers"]):
        assert answer.status_code == 200
        want = reference.http_response(
            ["-"], uid_of(n), reference.review_response(
                served["policies"][ids[traffic.policy_of(n)]],
                traffic.reviews[traffic.shape_of(n)]["request"], SIGNED),
        ).partition(b"\r\n\r\n")[2]
        assert answer.content == want, n


def test_an_audit_row_is_counted_by_the_lane_and_by_no_answer_source(served):
    before, after = served["before"], served["after"]
    held = reduce.held_to_its_sources(CONFIG, before, after, REQUESTS)
    assert held == {"answered_off_device": 0, "rows_not_dispatched": 0}
    assert reduce.delta(
        before, after, "policy_server_dispatched_rows_total") == REQUESTS
    rows = reduce.delta(before, after, "policy_server_audit_rows_dispatched")
    assert rows == served["kept"] * 32
    env = served["handle"].server.state.evaluation_environment
    assert env.host_profile["audit_rows"] == rows
    # and the lane's other counters are on /metrics beside it
    assert reduce.delta(
        before, after, "policy_server_audit_batches_dispatched") >= rows / 256
    assert reduce.delta(
        before, after, "policy_server_audit_observe_seconds_total") > 0
    assert reduce.delta(
        before, after, "policy_server_audit_snapshot_evictions") == 0
    assert reduce.delta(
        before, after, "policy_server_audit_objects_unjudged") == 0
    # one observation of the lane's phase a job (none before the stream)
    assert reduce.sample(after, {
        "name": "policy_server_phase_latency_seconds_count",
        "labels": {"phase": "audit_dispatch"}}) == reduce.sample(
            after, "policy_server_audit_batches_dispatched")


def test_the_store_holds_what_was_served_and_a_delete_takes_it_out(served):
    snapshot = served["handle"].server.state.audit.snapshot.stats()
    assert snapshot["resources"] == served["kept"]
    assert snapshot["recorded"] == served["kept"]
    assert snapshot["evicted"] == snapshot["evicted_dirty"] == 0
    if served["frontend"] == "native":
        front = served["handle"].server.state.native_frontend.stats()
        assert front["parse_fallbacks"] == 0  # no body went through Python


# -- the native request's identity ------------------------------------------------


def _canonical(request: dict) -> bytes:
    return json.dumps(AdmissionRequest.from_dict(request).to_dict(),
                      separators=(",", ":")).encode()


IDENTITIES = {
    "a pod review of the traffic": lambda r: r,
    "no name, no namespace": lambda r: {
        k: v for k, v in r.items() if k not in ("name", "namespace")},
    "every optional key before the name": lambda r: dict(
        r, subResource="status", requestSubResource="scale",
        requestKind={"group": "apps", "version": "v1", "kind": "Deployment"},
        requestResource={"group": "apps", "version": "v1",
                         "resource": "deployments"}),
    "escapes and a name that spells a key": lambda r: dict(
        r, name='p,"name":"other"\\', namespace="né",
        kind={"group": 'a"b', "version": "v\\1", "kind": "Ké"}),
    "a sub-resource that is no string (parsed whole)": lambda r: dict(
        r, subResource={"name": "trap"}),
}


@pytest.mark.parametrize("change", IDENTITIES.values(), ids=list(IDENTITIES))
def test_a_wire_request_names_its_object_as_a_parsed_one_does(change):
    review = Traffic(MIX, SEED, ["p"]).reviews[0]["request"]
    request = change(dict(review, uid="u-1", name="pod-0000000001"))
    parsed = ValidateRequest.from_admission(
        AdmissionRequest.from_dict(request))
    payload = _canonical(request)
    header = {"uid": "u-1", "namespace": request.get("namespace"),
              "operation": request["operation"], "kind": "Pod"}
    wire = WireValidateRequest(header, payload)
    assert wire.admission_request.kind == parsed.admission_request.kind
    assert wire.admission_request.name == parsed.admission_request.name
    assert resource_key(wire) == resource_key(parsed)
    whole = "no string" in [k for k, v in IDENTITIES.items()
                            if v is change][0]
    assert (_IDENTITY.match(payload) is None) == whole
    assert wire._payload_cache is None  # the object was never parsed
    store = SnapshotStore()
    store.observe([wire])
    assert store.stats()["resources"] == (
        0 if request["operation"] == "DELETE" else 1)


# -- lane discipline ----------------------------------------------------------------


def _pairs(policies: dict, count: int) -> list:
    traffic = Traffic(MIX, SEED + 1, list(policies))
    out = []
    for n in range(count):
        body = traffic.request(n).partition(b"\r\n\r\n")[2]
        out.append((traffic.policy_ids[traffic.policy_of(n)],
                    ValidateRequest.from_admission(
                        AdmissionReviewRequest.from_dict(
                            json.loads(body)).request)))
    return out


@pytest.fixture(scope="module")
def env(served):
    return served["handle"].server.state.evaluation_environment


def test_a_live_batch_goes_first_and_the_audit_job_is_handed_back(
        env, policies):
    """Driven by hand (the dispatch loop not started), so the race the
    lane guards against is forced: the job is popped, live work is there
    before its worker starts, the job goes back to the head of the lane."""
    order: list[str] = []
    batcher = MicroBatcher(
        env, max_batch_size=128, policy_timeout=60.0,
        host_fastpath_threshold=0, latency_budget_ms=0)
    # the loop hands a live batch to its pool before it looks at the lane
    # again, so these two are stamped in the order it decided them
    launch_live, audit_dispatch = (
        batcher._launch_batch, batcher._dispatch_audit)
    batcher._launch_batch = lambda b, taken, filled: (
        order.append("live"), launch_live(b, taken, filled))[1]
    batcher._dispatch_audit = lambda p: (
        order.append("audit"), audit_dispatch(p))[1]
    pairs = _pairs(policies, 256 + 32)
    try:
        job = batcher.submit_audit(pairs[:256])
        live = batcher.submit_many(pairs[256:], RequestOrigin.VALIDATE)
        batcher._maybe_dispatch_audit()
        assert _wait_until(lambda: batcher.audit_lane_depth() == 1
                           and not batcher._audit_inflight, timeout=10)
        assert batcher.stats_snapshot()["audit_preemptions"] == 1
        assert not job.done() and order == []
        batcher.start()
        assert all(f.result(timeout=60).uid for f in live)
        assert len(job.result(timeout=60)) == 256
        assert order[0] == "live" and order[-1] == "audit"
        stats = batcher.stats_snapshot()
        assert stats["audit_batches_dispatched"] == 1
        assert stats["audit_rows_judged"] == 256
        assert stats["requests_dispatched"] == 32
    finally:
        batcher.shutdown()


def test_an_audit_job_goes_out_in_live_sized_slices(env, policies):
    """--audit-batch-size 256 over --max-batch-size 128: two launches of
    a bucket warm-up compiled, none of one it did not, and the results in
    the order of the pairs."""
    calls: list[tuple[int, dict]] = []

    class Watched:
        def __getattr__(self, name):
            return getattr(env, name)

        def validate_batch(self, items, **kw):
            calls.append((len(items), kw))
            return env.validate_batch(items, **kw)

    batcher = MicroBatcher(
        Watched(), max_batch_size=128, policy_timeout=60.0,
        host_fastpath_threshold=0, latency_budget_ms=0).start()
    pairs = _pairs(policies, 256 + 96)
    before = env.host_profile
    try:
        got = batcher.submit_audit(pairs).result(timeout=60)
    finally:
        batcher.shutdown()
    assert [n for n, _kw in calls] == [128, 128, 96]
    assert all(kw == {"audit": True} for _n, kw in calls)
    want = env.validate_batch(pairs[:128]) + env.validate_batch(pairs[128:])
    assert [r.to_dict() for r in got] == [r.to_dict() for r in want]
    after = env.host_profile
    assert after["audit_rows"] - before["audit_rows"] == 256 + 96
    # the direct calls above are nobody's audit: they count as dispatched
    assert after["dispatched_rows"] - before["dispatched_rows"] == 256 + 96
    assert batcher.stats_snapshot()["audit_rows_judged"] == 256 + 96


# -- the lane's phase -----------------------------------------------------------------


def test_audit_dispatch_is_a_phase_with_one_stamp_site_and_a_panel():
    from tools.graftcheck import observability as ob

    assert flightrec.PH_AUDIT_DISPATCH == "audit_dispatch" in flightrec.PHASES
    consts, members = ob._flightrec_phases(
        ROOT / "policy_server_tpu" / "telemetry" / "flightrec.py")
    assert "PH_AUDIT_DISPATCH" in members
    sites = ob._phase_record_sites(ROOT / "policy_server_tpu", consts)
    (site,) = sites["audit_dispatch"]
    assert site[0].endswith("runtime/batcher.py")
    dashboard = json.loads((ROOT / "kubewarden-dashboard.json").read_text())
    exprs = " ".join(
        t["expr"] for p in dashboard["panels"] for t in p["targets"])
    for family in ('phase_latency_seconds_sum{phase="audit_dispatch"}',
                   "policy_server_audit_rows_dispatched_total",
                   "policy_server_audit_observe_seconds_total",
                   "policy_server_audit_snapshot_evictions_total",
                   "policy_server_audit_objects_unjudged_total"):
        assert family in exprs


def test_an_audit_job_is_one_phase_under_a_batch_of_its_own(env, policies):
    rec = flightrec.install(FlightRecorder(capacity=4096))
    batcher = MicroBatcher(
        env, max_batch_size=128, policy_timeout=60.0,
        host_fastpath_threshold=0, latency_budget_ms=0).start()
    try:
        batcher.submit_audit(_pairs(policies, 256)).result(timeout=60)
    finally:
        batcher.shutdown()
        flightrec.install(None)
    events = [e for e in rec.snapshot() if e["kind"] == "batch"]
    (job,) = [e for e in events if e["phase"] == "audit_dispatch"]
    assert job["rows"] == 256 and job["batch"] >= 0
    inside = [e for e in events if e["batch"] == job["batch"]
              and e["phase"] != "audit_dispatch"]
    # two slices, each encoded, launched and landed inside the job's window
    for phase in ("encode", "launch", "fetch", "materialize"):
        stamps = [e for e in inside if e["phase"] == phase]
        assert len(stamps) == 2, phase
        assert all(job["start_ns"] <= e["start_ns"]
                   and e["end_ns"] <= job["end_ns"] for e in stamps)
    assert not [e for e in events if e["phase"] == "dispatch"]


# -- the store's sources -----------------------------------------------------------------


def test_the_command_line_names_the_stores_source(tmp_path):
    from policy_server_tpu.config.cli import build_cli
    from policy_server_tpu.config.config import Config

    cli = build_cli()
    (tmp_path / "policies.yml").write_text("{}", encoding="utf-8")
    at = ["--policies", str(tmp_path / "policies.yml")]
    assert Config.from_args(cli.parse_args(at)).audit_observe_admissions
    for value, want in (("on", True), ("off", False)):
        ns = cli.parse_args([*at, "--audit-observe-admissions", value])
        assert Config.from_args(ns).audit_observe_admissions is want
    # the configuration's own command line parses here; a program without
    # one of its flags refuses it (argparse: exit 2), as the parent of PR 38
    # does, and is not measured in the cell with an empty store
    assert cli.parse_args(CONFIG["server_flags"]).audit_mode == "interval"
    with pytest.raises(SystemExit) as refused:
        cli.parse_args([*CONFIG["server_flags"], "--no-such-flag", "on"])
    assert refused.value.code == 2


@pytest.mark.parametrize("observe", [True, False], ids=["on", "off"])
def test_admissions_feed_the_store_unless_told_not_to(observe):
    metrics_mod.reset_metrics_for_tests()
    handle = ServerHandle(make_config(
        policy_timeout_seconds=5.0, audit_mode="interval",
        audit_interval_seconds=60.0, audit_observe_admissions=observe))
    try:
        from test_server import pod_review_body

        answer = requests.post(handle.url("/validate/pod-privileged"),
                               json=pod_review_body(False), timeout=30)
        assert answer.status_code == 200
        batcher, scanner = handle.server.state.batcher, (
            handle.server.state.audit)
        # the store is there either way (a seed file or the watch feed may
        # fill it); only the dispatch path's hand is taken off it
        assert (batcher.audit_tracker is scanner.snapshot) is observe
        assert scanner.snapshot.stats()["recorded"] == int(observe)
        assert batcher.stats_snapshot()["audit_observe_ns"] > 0 or not observe
    finally:
        handle.stop()
        metrics_mod.reset_metrics_for_tests()


# -- the benchmark's data files ---------------------------------------------------------


def test_the_file_differs_from_flagship32_in_the_listed_keys_only():
    assert check_manifest.problems(MANIFEST, ROOT) == []
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "flagship32-audit")
    # last of the configurations this deployment's cell found; later
    # ones come after it
    assert MANIFEST["configs"].index(entry) == 4
    assert entry["file"] == "benchmarks/configs/flagship32-audit.json"
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert entry["source"] == CONFIG["source"] and "Audit Scanner" in (
        entry["source"])
    changed = {k for k in CONFIG if CONFIG[k] != BASE.get(k)}
    assert changed == {"name", "source", "server_flags", "assumed",
                       "deployment", "guarantees", "why"}
    assert list(CONFIG)[:len(BASE)] == list(BASE)
    # the issue's two flags, and the store's one source named (a default,
    # named so that a program without the flag refuses the command line)
    assert CONFIG["server_flags"] == [
        *BASE["server_flags"], "--audit-mode", "interval",
        "--audit-interval-seconds", "2", "--audit-observe-admissions", "on"]
    for flag in ("--audit-resources-file", "--audit-matrix", "--audit-watch",
                 "--audit-batch-size", "--audit-max-snapshot-bytes"):
        assert flag not in CONFIG["server_flags"]
    # what flagship32 assumed still holds, and the new flags are argued
    assert BASE["assumed"].items() <= CONFIG["assumed"].items()
    assert set(CONFIG["assumed"]) - set(BASE["assumed"]) == {
        "--audit-mode interval", "--audit-interval-seconds 2",
        "--audit-observe-admissions on", "defaults kept"}
    # flagship32's guarantees, who may answer among them, plus audit's
    guarantees = CONFIG["guarantees"]
    assert set(guarantees) - set(BASE["guarantees"]) == {"audit"}
    for key in ("exact", "answered", "no_compile", "answers_from"):
        assert guarantees[key] == BASE["guarantees"][key]
    assert guarantees["answers_from"] == {
        "device": "policy_server_dispatched_rows"}
    assert guarantees["device"].startswith(BASE["guarantees"]["device"])


def test_the_cell_is_the_issues_letter_for_letter():
    # last of the cells when it was added; later ones come after it
    entry = MANIFEST["workloads"][6]
    assert {k: entry[k] for k in ("name", "config", "traffic", "chips")} == {
        "name": CELL, "config": "flagship32-audit",
        "traffic": "unique-saturate", "chips": 1}
    assert len(entry["why"]) <= 200
    assert [w["config"] for w in MANIFEST["workloads"]].count(
        "flagship32-audit") == 1
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    # this deployment's trickle cell is in no list; its mix waits where
    # unlisted mixes do, unique-saturate.json with 32 connections and
    # nothing else changed (a copy runs the default install's cell,
    # flagship32-audit-defaults.unique-trickle)
    assert TRICKLE not in json.dumps(MANIFEST)
    mixes = {name: json.loads((BENCH / where / f"{name}.json").read_text())
             for where, name in (("pending", "unique-trickle"),
                                 ("traffic", "unique-saturate"))}
    parameters = {name: {k: v for k, v in mix.items()
                         if not k.endswith("why")}
                  for name, mix in mixes.items()}
    assert parameters["unique-trickle"] == dict(
        parameters["unique-saturate"], connections=32)
    assert parameters["unique-saturate"]["connections"] == 512
    reported = {m["name"]
                for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
                if CELL in (m.get("workloads") or [CELL])}
    assert {"reviews_per_s", "setup_s", *NEW_METRICS} <= reported
    assert "latency_p50_ms" not in reported
    # whatever flagship32 reports on the same traffic it reports too, but
    # for the four that cannot tell the lane's launches from the live ones
    sibling = {m["name"] for m in MANIFEST["per_layer"]
               if "flagship32.unique-saturate" in m["workloads"]}
    assert MIXED <= sibling and sibling - MIXED <= reported
    assert not MIXED & reported
    # and the configuration's entry says how much of the lane this cell sees
    assert "8% of device rows" in MANIFEST["configs"][4]["why"]


def test_the_new_entries_come_last_and_share_a_layer():
    """Last as PR 38 left the list: what later PRs appended (PR 40's
    host_declined_batch_share) comes after them, never between."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert at >= 34  # nothing that was there moved behind them
    last = MANIFEST["per_layer"][at : at + len(NEW_METRICS)]
    assert tuple(m["name"] for m in last) == NEW_METRICS
    assert len({m["layer"] for m in last}) == 1
    for m in last:
        assert m["moves"] == "reviews_per_s"
        # and the default install's cell, which runs the same lane
        assert m["workloads"] == [CELL, DEFAULTS_CELL]
        spec = json.loads(
            (BENCH / "layer_metrics" / f"{m['name']}.json").read_text())
        assert spec["reader"] in ("counter_ratio", "counter_delta")
        assert "module" not in spec
    # no code came with them, here or anywhere under the benchmark
    assert not [p.name for p in (BENCH / "layer_metrics").iterdir()
                if p.suffix != ".json"]
    # (queue_spans.py came later, with the metrics of queue_wait's parts)
    assert sorted(p.name for p in BENCH.glob("*.py")) == [
        "check_manifest.py", "client.py", "control_server.py",
        "host_spans.py", "queue_spans.py", "reduce.py", "reference.py",
        "run.py", "serve.py", "sweep.py", "traffic.py"]


WINDOW = {  # 60,000 live requests beside 1,200 audit jobs of 256 rows
    "policy_server_requests_dispatched_total": 60_000,
    "policy_server_audit_rows_dispatched_total": 307_200,
    "policy_server_audit_batches_dispatched_total": 1_200,
    "policy_server_audit_preemptions_total": 30,
    "policy_server_audit_observe_seconds_total": 0.3,
    "policy_server_audit_snapshot_evictions_total": 4_100,
    "policy_server_audit_objects_unjudged_total": 3_900,
    PHASE % ("count", "audit_dispatch"): 1_200,
    PHASE % ("sum", "audit_dispatch"): 9.6,
}


@pytest.mark.parametrize("name, want", zip(NEW_METRICS, (
    5.12, 256.0, 0.025, 8.0, 5.0, 4_100.0, 3_900.0)))
def test_a_new_layer_metric_reads_the_programs_counters(name, want):
    before, after = _planted(WINDOW)
    ctx = {"before": before, "after": after}
    assert reduce.read_layer_metric(name, ctx) == pytest.approx(want)
    # a program without the phase or the counters (the parent) gives
    # nothing to read, and nothing is raised
    assert reduce.read_layer_metric(name, {"before": {}, "after": {}}) is None


def test_the_new_metrics_counters_are_the_programs_own():
    names = {metrics_mod.AUDIT_ROWS_DISPATCHED,
             metrics_mod.AUDIT_BATCHES_DISPATCHED,
             metrics_mod.AUDIT_PREEMPTIONS, metrics_mod.AUDIT_OBSERVE_SECONDS,
             metrics_mod.AUDIT_SNAPSHOT_EVICTIONS,
             metrics_mod.AUDIT_OBJECTS_UNJUDGED,
             metrics_mod.REQUESTS_DISPATCHED,
             "policy_server_phase_latency_seconds_sum",
             "policy_server_phase_latency_seconds_count"}
    read = set()
    for name in NEW_METRICS:
        spec = json.loads(
            (BENCH / "layer_metrics" / f"{name}.json").read_text())
        for part in (spec.get("numerator"), spec.get("denominator"),
                     *spec.get("counters", ())):
            if part is not None:
                read.add(part if isinstance(part, str) else part["name"])
    assert read == names


# -- what the store keeps of a native request ---------------------------------------


def test_the_store_keeps_a_native_request_as_atoms_and_gives_it_back():
    """A stored native request is a tuple of bytes, strings and None, so
    the collector drops it from its lists at its first pass; a sweep gets
    a request again, from the class that froze it, that says what the
    first one said."""
    import gc

    review = Traffic(MIX, SEED, ["p"]).reviews[3]["request"]
    request = dict(review, uid="u-7", name="pod-0000000007",
                   operation="CREATE")
    payload = _canonical(request)
    wire = WireValidateRequest(
        {"uid": "u-7", "namespace": request["namespace"],
         "operation": "CREATE", "kind": "Pod"}, payload)
    store = SnapshotStore(max_bytes=3 * len(payload))
    store.observe([wire])
    ((key, stored),) = store.collect(dirty_only=True)
    assert key == resource_key(wire)
    assert type(stored) is tuple
    assert all(x is None or isinstance(x, (bytes, str)) for x in stored)
    gc.collect()
    assert not gc.is_tracked(stored)
    back = store.request_of(stored)
    assert isinstance(back, WireValidateRequest) and back is not wire
    assert back.payload_json() == payload and back.uid() == "u-7"
    for field in ("uid", "namespace", "operation", "kind", "name"):
        assert getattr(back.admission_request, field) == getattr(
            wire.admission_request, field)
    assert back.admission_request.request_kind.kind == "Pod"
    # the budget counts the payload's bytes, as for any request, and the
    # other readers of the store see requests, not tuples
    assert store.stats()["bytes"] == len(payload)
    assert store.export_rows() == [(key, payload)]
    ((_, again),) = store.rows_snapshot()
    assert again.payload_json() == payload
    parsed = ValidateRequest.from_admission(AdmissionRequest.from_dict(
        dict(request, name="pod-0000000008")))
    store.observe([parsed])  # a request that cannot freeze is kept as it is
    kept = dict(store.collect())
    assert kept[resource_key(parsed)] is parsed
    assert store.request_of(parsed) is parsed
    for n in range(9, 12):  # over the budget: the oldest go, counted
        store.observe([WireValidateRequest(
            {"uid": f"u-{n}", "namespace": request["namespace"],
             "operation": "CREATE", "kind": "Pod"},
            _canonical(dict(request, name=f"pod-{n:010d}")))])
    stats = store.stats()
    assert stats["evicted"] == stats["recorded"] - stats["resources"] > 0
    assert stats["bytes"] <= store.max_bytes


# -- the pipeline gate --------------------------------------------------------------------


def test_the_lane_waits_while_every_pipeline_worker_holds_a_live_batch(
        env, policies):
    """An empty queue is no idle slot when the pipeline is full: the lane
    sends nothing until a worker comes free. The gate is the pipeline's
    own semaphore, probed and given back."""
    batcher = MicroBatcher(
        env, max_batch_size=128, policy_timeout=60.0,
        host_fastpath_threshold=0, latency_budget_ms=0)
    job = batcher.submit_audit(_pairs(policies, 32))
    slots = batcher._batch_workers
    try:
        for _ in range(slots):  # every worker holds a live batch
            assert batcher._inflight.acquire(blocking=False)
        batcher._maybe_dispatch_audit()
        assert batcher.audit_lane_depth() == 1 and not batcher._audit_inflight
        batcher._inflight.release()  # one comes free
        batcher._maybe_dispatch_audit()
        assert len(job.result(timeout=60)) == 32
        assert batcher.stats_snapshot()["audit_preemptions"] == 0
        # the probe kept no slot: the one free worker is still free, and
        # giving the others back fills the semaphore exactly
        assert batcher._inflight.acquire(blocking=False)
        for _ in range(slots):
            batcher._inflight.release()
        with pytest.raises(ValueError):
            batcher._inflight.release()
        batcher.start()
        live = batcher.submit_many(_pairs(policies, 8), RequestOrigin.VALIDATE)
        assert all(f.result(timeout=60).uid for f in live)
    finally:
        batcher.shutdown()


# -- what the byte budget costs in coverage -------------------------------------------------


def _pods(names) -> list:
    return [pod_review(name) for name in names]


def test_an_object_pushed_out_dirty_is_counted_and_one_swept_is_not():
    one = len(pod_review("n0").payload_json())
    store = SnapshotStore(max_bytes=int(one * 3.5))
    store.observe(_pods(["n0", "n1", "n2"]))
    assert len(store.collect(dirty_only=True)) == 3  # a sweep has them
    store.observe(_pods(["n3", "n4"]))  # n0, n1 go: a sweep had them
    stats = store.stats()
    assert (stats["evicted"], stats["evicted_dirty"]) == (2, 0)
    store.observe(_pods(["n5", "n6", "n7"]))  # n2 clean; n3, n4 dirty
    stats = store.stats()
    assert (stats["evicted"], stats["evicted_dirty"]) == (5, 2)
    assert stats["dirty"] == stats["resources"] == 3
    gone = store.take_evictions()
    assert sorted(k[-2:] for k in gone) == ["n0", "n1", "n2", "n3", "n4"]
    assert store.take_evictions() == set()
    assert store.holds(gone | {"/v1/Pod/default/n6", "x"}) == {
        "/v1/Pod/default/n6"}
    # an object recorded again is nobody's eviction any more
    store.observe(_pods(["n8"]))
    assert {k[-2:] for k in store.take_evictions()} == {"n5"}
    store.observe(_pods(["n5"]))
    assert "/v1/Pod/default/n5" not in store.take_evictions()


def test_the_rows_of_an_object_pushed_out_go_at_the_next_sweeps_head(env):
    """They used to stay until a FULL sweep, which only a promotion asks
    for: the reports then listed objects no sweep would visit again."""
    batcher = MicroBatcher(
        env, max_batch_size=128, policy_timeout=60.0,
        host_fastpath_threshold=0, latency_budget_ms=0).start()
    scanner = make_scanner(env, batcher, batch_size=256)
    one = len(pod_review("n0").payload_json())
    scanner.snapshot.max_bytes = int(one * 2.5)
    policies = len(list(env.policy_ids()))
    try:
        scanner.snapshot.observe(_pods(["n0", "n1"]))
        assert scanner.sweep(full=False) == 2 * policies
        assert scanner.report_payload()["summary"]["resources"] == 2
        scanner.snapshot.observe(_pods(["n2"]))  # n0 goes, swept: clean
        listed = {r["name"] for r in scanner.report_payload()["reports"]}
        assert listed == {"n0", "n1"}  # until a sweep begins
        assert scanner.sweep(full=False) == policies
        body = scanner.report_payload()
        assert {r["name"] for r in body["reports"]} == {"n1", "n2"}
        assert body["summary"]["results"] == 2 * policies
        assert body["scanner"]["snapshot"]["evicted"] == 1
        stats = scanner.stats()
        assert stats["snapshot_evictions"] == 1
        assert stats["objects_unjudged"] == 0
        # pushed out while dirty (n3, behind n1 and n2 whom a sweep had
        # judged): counted, and never listed
        scanner.snapshot.observe(_pods(["n3", "n4", "n5"]))
        assert scanner.sweep(full=False) == 2 * policies
        assert {r["name"] for r in scanner.report_payload()["reports"]} == {
            "n4", "n5"}
        stats = scanner.stats()
        assert stats["snapshot_evictions"] == 4
        assert stats["objects_unjudged"] == 1
    finally:
        batcher.shutdown()


def test_a_sweep_skips_what_the_store_pushed_out_since_it_collected(env):
    """A sweep under load outlasts the budget's turnover: what it
    collected and the store no longer has gets no lane job and no row,
    and is counted once however many of the sweep's jobs it spans."""
    jobs: list[int] = []

    class Lane:  # the batcher's lane, with the store turning over beside it
        def __init__(self, batcher):
            self.batcher = batcher

        def submit_audit(self, pairs):
            jobs.append(len(pairs))
            if len(jobs) == 1:  # while the first job is out, two go
                scanner.snapshot.observe(_pods(["n4", "n5"]))
            return self.batcher.submit_audit(pairs)

        def cancel_audit(self, future):
            return self.batcher.cancel_audit(future)

    batcher = MicroBatcher(
        env, max_batch_size=128, policy_timeout=60.0,
        host_fastpath_threshold=0, latency_budget_ms=0).start()
    policies = len(list(env.policy_ids()))
    # 1.5 objects a job: the second object spans the first two jobs
    scanner = make_scanner(env, Lane(batcher), batch_size=policies * 3 // 2)
    one = len(pod_review("n0").payload_json())
    scanner.snapshot.max_bytes = int(one * 4.5)
    try:
        scanner.snapshot.observe(_pods(["n0", "n1", "n2", "n3"]))
        judged = scanner.sweep(full=True)  # a full sweep goes in LRU order
        # n0 whole and half of n1 went out with the first job; then n0 and
        # n1 were pushed out: the rest of n1 is skipped, n2 and n3 judged
        assert jobs == [policies * 3 // 2, policies, policies]
        assert judged == sum(jobs)
        assert scanner.stats()["objects_unjudged"] == 1  # n1, once
        assert scanner.stats()["snapshot_evictions"] == 2
        # n0's rows and n1's half are listed until the next sweep's head
        assert scanner.report_payload()["summary"]["results"] == sum(jobs)
        assert scanner.sweep(full=False) == 2 * policies
        body = scanner.report_payload()
        assert {r["name"] for r in body["reports"]} == {
            "n2", "n3", "n4", "n5"}
        assert body["summary"]["results"] == 4 * policies
        assert body["scanner"]["objects_skipped"] == 1
        assert scanner.snapshot.stats()["dirty"] == 0
    finally:
        batcher.shutdown()


class _Stream:  # what compare() asks of a Traffic: one CREATE shape
    reviews = [{"request": {"operation": "CREATE"}}]

    @staticmethod
    def shape_of(n: int) -> int:
        return 0


WHOLE = dict(rows_compared=96, mismatched=0, objects=3, objects_served=5,
             objects_not_listed=2, objects_without_all_rows=2)
FACTS = dict(sweep_errors=0, snapshot=dict(resources=3, evicted=2))


@pytest.mark.parametrize("result, facts, broken", [
    ({}, {}, []),
    (dict(objects_not_listed=0, objects_without_all_rows=0, objects=5,
          objects_served=5), dict(snapshot=dict(resources=5, evicted=0)), []),
    (dict(mismatched=1), {}, ["rows differ from the reference: 1"]),
    (dict(rows_compared=0), {}, ["no row was compared: True"]),
    ({}, dict(sweep_errors=2), ["sweeps failed: 2"]),
    (dict(objects_without_all_rows=3), {},
     ["objects listed under some policies only: 1"]),
    ({}, dict(snapshot=dict(resources=3, evicted=1)),
     ["more served objects unlisted than the store evicted: 1"]),
    (dict(objects=4), {}, ["objects listed and objects resident differ: 1"]),
], ids=["above the budget, as the guarantee says", "below it, whole",
        "a row differs", "nothing compared", "a sweep failed",
        "an object listed in part", "an object lost, not evicted",
        "a listed object the store does not hold"])
def test_a_whole_runs_listing_is_held_to_the_audit_guarantee(
        result, facts, broken):
    assert audit_report_check.held_to_the_guarantee(
        {**WHOLE, **result}, {**FACTS, **facts}) == broken


def test_the_objects_served_are_listed_whole_or_not_at_all():
    rows_of = {0: 32, 1: 32, 3: 31}
    assert audit_report_check.lacking(rows_of, {0, 1, 2, 3, 4}, _Stream, 32) == {
        "objects_served": 5, "objects_not_listed": 2,
        "objects_without_all_rows": 3}
    guarantee = CONFIG["guarantees"]["audit"]
    for said in ("while it is resident", "policy_server_audit_objects_unjudged",
                 "NOT of every object served", "tools/audit_report_check.py"):
        assert said in guarantee
