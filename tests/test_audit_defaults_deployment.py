"""``flagship32-audit-defaults``: Kubewarden's default install, every routing
flag at its documented default and the compliance scanner on, as a
deployment the benchmark holds to its guarantees
(benchmarks/configs/flagship32-audit-defaults.json).

* the configuration is ``flagship32-defaults``' flags plus
  ``flagship32-audit``'s scanner flags, key by key, and its cell's mix is
  ``benchmarks/pending/unique-trickle.json`` unchanged;
* one answer, one source with the lane on the tiers: an audit-lane job the
  row tier, the blob tier and the in-batch collapse each answer part of
  moves the lane's counters (``audit_tier_hits`` and its breakdown by tier,
  ``audit_rows_judged``) and none of the five answer sources, and its
  report rows are what ``tools/audit_report_check.py expected_row`` says;
  the lane's plan is the ring phase ``audit_bookkeeping``, not
  ``bookkeeping``;
* the manifest: one configuration, one cell and three layer metrics
  appended, the cell's name appended to the lists the issue names, and
  each new metric reads the program's counters and nothing on a program
  without them;
* a CPU rehearsal of the cell through ``benchmarks/run.py`` under a tiny
  mix: ``correct``, every answer counted by exactly one source.
"""

from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

import pytest

from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.audit.reports import PolicyReportStore
from policy_server_tpu.evaluation.environment import DEFAULT_VERDICT_CACHE_SIZE
from policy_server_tpu.evaluation.verdict_cache import AUDIT_TIERS
from policy_server_tpu.models import AdmissionReviewRequest, ValidateRequest
from policy_server_tpu.runtime.batcher import MicroBatcher, bucket_size
from policy_server_tpu.telemetry import flightrec
from policy_server_tpu.telemetry import metrics as metrics_mod
from policy_server_tpu.telemetry.flightrec import FlightRecorder

# the same 32 policies, built and planted as the cached deployment's tests do
from test_cached_deployment import (  # noqa: F401 (policies: the fixture)
    PHASE,
    _build,
    _planted,
    policies,
)

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))
try:
    import check_manifest
    import reduce
    import run as bench_run
    from traffic import Traffic
finally:
    sys.path.remove(str(BENCH))
from tools import audit_report_check  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = BENCH / "configs"
CONFIG = json.loads((CONFIGS / "flagship32-audit-defaults.json").read_text())
DEFAULTS = json.loads((CONFIGS / "flagship32-defaults.json").read_text())
AUDIT = json.loads((CONFIGS / "flagship32-audit.json").read_text())
NAME = "flagship32-audit-defaults"
CELL = f"{NAME}.unique-trickle"
NEW_METRICS = ("audit_tier_answered_share", "audit_rows_judged_per_live_row",
               "audit_bookkeeping_ms_mean")
# the accepted metrics whose lists gain the cell: what the audit cell
# reports, the live tier shares and the defaults' host-route shares
TIERS = ("device_answered_share", "row_tier_hit_share",
         "batch_duplicate_share", "blob_tier_bypassed_share",
         "cache_evictions_in_window", "cache_bytes_per_put")
HOST = ("host_answered_share", "host_batch_share",
        "host_declined_batch_share", "host_eval_ms_mean")
# ... and not these, which fold the lane's launches in with the live ones
MIXED = {"launch_ms_mean", "materialize_ms_mean", "h2d_arrays_per_launch",
         "predicate_roofline", "launched_batch_share", "bookkeeping_ms_mean"}
SIGNED = set(CONFIG["signing"]["signed_images"])
SEED = 2**31 + 45
MIX = {"generator": "pod_reviews", "pool_shapes": 257, "arrival": "closed"}


# -- the configuration and its mix ----------------------------------------------


def test_the_flags_are_the_defaults_plus_the_scanners():
    scanner = AUDIT["server_flags"][AUDIT["server_flags"].index("--audit-mode"):]
    assert scanner == ["--audit-mode", "interval", "--audit-interval-seconds",
                       "2", "--audit-observe-admissions", "on"]
    assert CONFIG["server_flags"] == DEFAULTS["server_flags"] + scanner == [
        "--frontend", "native", "--policy-timeout", "10", "--mesh", "auto",
        *scanner]
    # every routing flag and the scanner's budgets at their defaults
    for flag in ("--host-fastpath-threshold", "--latency-budget-ms",
                 "--verdict-cache-size", "--audit-batch-size",
                 "--audit-max-snapshot-bytes", "--audit-resources-file",
                 "--audit-matrix", "--audit-watch"):
        assert flag not in CONFIG["server_flags"]
    assert DEFAULT_VERDICT_CACHE_SIZE == 256 * 1024 * 1024
    # the configuration's own command line parses
    from policy_server_tpu.config.cli import build_cli

    ns = build_cli().parse_args(CONFIG["server_flags"])
    assert (ns.audit_mode, ns.audit_interval_seconds) == ("interval", 2.0)


def test_the_file_is_the_two_deployments_it_joins_key_by_key():
    assert list(CONFIG) == list(DEFAULTS)
    for key in ("policies", "signing", "response_head", "response_head_why",
                "row_bytes_dense", "verdict_bytes_per_row",
                "work_per_review_why", "chips", "mesh"):
        assert CONFIG[key] == DEFAULTS[key] == AUDIT[key], key
    assert CONFIG["name"] == NAME and len(CONFIG["source"]) <= 200
    for word in ("Audit Scanner", "kubewarden-controller", "cli-docs.md",
                 "configs[3]"):
        assert word in CONFIG["source"]
    # the defaults' assumptions hold, and the scanner's flags are argued
    assert DEFAULTS["assumed"].items() <= CONFIG["assumed"].items()
    assert set(CONFIG["assumed"]) - set(DEFAULTS["assumed"]) == {
        "--audit-mode interval", "--audit-interval-seconds 2",
        "--audit-observe-admissions on", "defaults kept"}
    kept = CONFIG["assumed"]["defaults kept"]
    for word in ("--audit-batch-size 256", "--audit-max-snapshot-bytes 64Mi",
                 "no --audit-resources-file", "no --audit-matrix",
                 "no --audit-watch"):
        assert word in kept
    guarantees = CONFIG["guarantees"]
    assert set(guarantees) == set(DEFAULTS["guarantees"]) | {"audit"}
    for key in ("exact", "answered", "no_compile", "device", "answers_from"):
        assert guarantees[key] == DEFAULTS["guarantees"][key], key
    assert set(guarantees["answers_from"]) == {
        "device", "host_fastpath", "row_tier", "blob_tier", "batch_duplicate"}
    audit = guarantees["audit"]
    for said in ("while it is resident", "policy_server_audit_objects_unjudged",
                 "NOT of every object served", "tools/audit_report_check.py",
                 "a lane row a tier answers is the lane's, counted under "
                 "policy_server_audit_tier_hits, and answers nobody"):
        assert said in audit


def test_the_mix_is_the_pending_trickle_unchanged():
    pending = (BENCH / "pending" / "unique-trickle.json").read_text()
    assert (BENCH / "traffic" / "unique-trickle.json").read_text() == pending
    mix = json.loads(pending)
    assert {k: v for k, v in mix.items() if not k.endswith("why")} == {
        "generator": "pod_reviews", "pool_shapes": 16381, "arrival": "closed",
        "connections": 32, "client_processes": 4, "warm_requests": 8192,
        "timeout_s": 10}


# -- one answer, one source with the lane on the tiers ----------------------------


def _pairs(policies: dict, numbers) -> list:
    """Every policy of the set on the object of each request number."""
    traffic = Traffic(MIX, SEED, list(policies))
    out = []
    for n in numbers:
        body = traffic.request(n).partition(b"\r\n\r\n")[2]
        request = ValidateRequest.from_admission(
            AdmissionReviewRequest.from_dict(json.loads(body)).request)
        out += [(pid, request, traffic.reviews[traffic.shape_of(n)]["request"])
                for pid in policies]
    return out


def _counts(env, batcher) -> dict[str, int]:
    dedup = env.dedup_stats
    return {
        # the five answer sources of the configuration's answers_from
        "device": env.host_profile["dispatched_rows"],
        "host_fastpath": env.host_fastpath_requests,
        "row_tier": dedup["cache_hits"],
        "blob_tier": dedup["blob_cache_hits"],
        "batch_duplicate": dedup["batch_dup_hits"],
        # the lane's
        **{f"lane_{tier}": dedup["audit_tier_hits_" + tier]
           for tier in AUDIT_TIERS},
        "lane_device": env.host_profile["audit_rows"],
        "lane_judged": batcher.stats_snapshot()["audit_rows_judged"],
    }


@pytest.fixture(scope="module")
def lane(policies):
    """The deployment's environment (the cache at its default) behind a
    batcher with the routing defaults, a flight recorder on."""
    env = _build(policies)
    batcher = MicroBatcher(
        env, max_batch_size=128, batch_timeout_ms=1.0, policy_timeout=60.0,
        host_fastpath_threshold=64, latency_budget_ms=50.0)
    # the router's estimates pinned, as the defaults' tests pin them
    # (ROADMAP D15): a block over the threshold goes to the device
    batcher._dev_rtt = {bucket_size(n): 1e-3 for n in range(1, 129)}
    batcher._host_cost_per_row = 1e-4
    batcher._observe_dispatch = lambda *args, **kwargs: None
    rec = flightrec.install(FlightRecorder(capacity=1 << 14))
    batcher.start()
    yield env, batcher, rec
    batcher.shutdown()
    flightrec.install(None)
    env.close()


# fresh objects (their 32 rows collapse onto one dispatched row each), the
# same objects again (the blob tier holds one representative each, the row
# tier the rest), new objects of the same pod shapes (the row tier alone)
JOBS = {"fresh": range(4), "replayed": range(4), "same shapes": range(257, 261)}


@pytest.fixture(scope="module")
def judged(lane, policies) -> dict:
    env, batcher, _rec = lane
    env.reset_verdict_cache()
    out = {}
    for name, numbers in JOBS.items():
        pairs = _pairs(policies, numbers)
        before = _counts(env, batcher)
        results = batcher.submit_audit(
            [(pid, request) for pid, request, _r in pairs]).result(timeout=120)
        after = _counts(env, batcher)
        out[name] = {"pairs": pairs, "results": results,
                     "moved": {k: after[k] - before[k] for k in after}}
    return out


@pytest.mark.parametrize("job", list(JOBS))
def test_a_lane_row_a_tier_answers_is_the_lanes_and_answers_nobody(
        judged, job):
    moved, rows = judged[job]["moved"], len(judged[job]["pairs"])
    for source in ("device", "host_fastpath", "row_tier", "blob_tier",
                   "batch_duplicate"):
        assert moved[source] == 0, (source, moved)
    assert moved["lane_judged"] == rows == 4 * 32
    # every lane row judged once: by the device or by one of the tiers
    tiers = sum(moved[f"lane_{tier}"] for tier in AUDIT_TIERS)
    assert moved["lane_device"] + tiers == rows, moved
    assert tiers > 0


def test_each_tier_answers_the_lane_where_it_can(judged):
    fresh, replayed, shapes = (judged[j]["moved"] for j in JOBS)
    # an object's 32 rows are one packed row: the chunk ships it once
    assert fresh["lane_duplicate"] > 0 and fresh["lane_device"] >= 1
    assert fresh["lane_row"] == fresh["lane_blob"] == 0
    # the same bytes again: the blob tier answers what it was taught
    assert replayed["lane_blob"] > 0 and replayed["lane_row"] > 0
    assert replayed["lane_device"] == 0
    # a new uid and name on a known pod shape: the row tier
    assert shapes["lane_row"] > 0 and shapes["lane_blob"] == 0
    assert shapes["lane_device"] == 0


@pytest.mark.parametrize("job", list(JOBS))
def test_the_report_rows_are_the_references(judged, policies, job):
    for (pid, request, review), result in zip(
            judged[job]["pairs"], judged[job]["results"]):
        row = PolicyReportStore.row_from_result(
            "key", pid, request, result, epoch=0)
        want = audit_report_check.expected_row(policies[pid], review, SIGNED)
        assert {k: row[k] for k in want} == want, (pid, job)


def test_the_lanes_plan_is_its_own_phase(lane, judged, policies):
    env, batcher, rec = lane
    # a live block over the threshold rides the device, and its plan is
    # bookkeeping, in the same ring as the lane's
    live = [(pid, request) for pid, request, _r in _pairs(
        policies, range(600, 603))]
    for future in batcher.submit_many(live, RequestOrigin.VALIDATE):
        future.result(timeout=120)
    events = [e for e in rec.snapshot() if e["kind"] == "batch"]
    jobs = {e["batch"] for e in events if e["phase"] == "audit_dispatch"}
    planned = [e for e in events if e["phase"] == "audit_bookkeeping"]
    assert len(jobs) >= len(JOBS) and planned
    assert {e["batch"] for e in planned} <= jobs
    assert not [e for e in events
                if e["phase"] == "bookkeeping" and e["batch"] in jobs]
    assert [e for e in events
            if e["phase"] == "bookkeeping" and e["batch"] not in jobs]


def test_the_lanes_phase_has_one_stamp_site_and_a_panel():
    from tools.graftcheck import observability as ob

    assert flightrec.PH_AUDIT_BOOKKEEPING == "audit_bookkeeping"
    assert "audit_bookkeeping" in flightrec.PHASES
    consts, _members = ob._flightrec_phases(
        ROOT / "policy_server_tpu" / "telemetry" / "flightrec.py")
    sites = ob._phase_record_sites(ROOT / "policy_server_tpu", consts)
    ((where, _line),) = sites["audit_bookkeeping"]
    assert where.endswith("evaluation/environment.py")
    dashboard = json.loads((ROOT / "kubewarden-dashboard.json").read_text())
    exprs = " ".join(
        t["expr"] for p in dashboard["panels"] for t in p["targets"])
    for family in ('phase_latency_seconds_sum{phase="audit_bookkeeping"}',
                   "policy_server_audit_rows_judged_total",
                   "policy_server_audit_tier_hits_total",
                   "policy_server_audit_tier_hits_by_tier_total"):
        assert family in exprs


# -- the benchmark's data files ---------------------------------------------------


def test_the_manifest_gains_one_configuration_one_cell_three_metrics():
    assert check_manifest.problems(MANIFEST, ROOT) == []
    entry = MANIFEST["configs"][-1]
    assert {k: entry[k] for k in ("name", "file", "reduced", "source")} == {
        "name": NAME, "file": f"benchmarks/configs/{NAME}.json",
        "reduced": [], "source": CONFIG["source"]}
    cell = MANIFEST["workloads"][-1]
    assert {k: cell[k] for k in ("name", "config", "traffic", "chips")} == {
        "name": CELL, "config": NAME, "traffic": "unique-trickle", "chips": 1}
    assert len(cell["why"]) <= 200
    # one block, appended after what the benchmark had (later PRs append
    # their own metrics after it)
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at - 1] == "blob_tier_bypassed_share"
    last = MANIFEST["per_layer"][at : at + len(NEW_METRICS)]
    assert tuple(m["name"] for m in last) == NEW_METRICS
    for m in last:
        assert m["moves"] == "reviews_per_s" and m["workloads"] == [CELL]
        spec = json.loads(
            (BENCH / "layer_metrics" / f"{m['name']}.json").read_text())
        assert spec["reader"] == "counter_ratio" and "module" not in spec
    layers = {m["name"]: m["layer"] for m in MANIFEST["per_layer"]}
    assert layers["audit_tier_answered_share"] == layers["row_tier_hit_share"]
    assert layers["audit_bookkeeping_ms_mean"] == layers["bookkeeping_ms_mean"]
    assert (layers["audit_rows_judged_per_live_row"]
            == layers["audit_rows_per_live_row"])


def test_the_cell_is_appended_to_the_lists_the_issue_names():
    reported = {m["name"] for m in MANIFEST["per_layer"]
                if CELL in m.get("workloads", ())}
    audit = {m["name"] for m in MANIFEST["per_layer"]
             if "flagship32-audit.unique-saturate" in m.get("workloads", ())}
    assert reported == audit | set(TIERS) | set(HOST) | set(NEW_METRICS)
    assert not reported & MIXED
    for m in MANIFEST["per_layer"]:
        if CELL in m.get("workloads", ()) and m["name"] not in NEW_METRICS:
            assert m["workloads"][-1] == CELL  # appended, nothing else moved
    e2e = {m["name"] for m in MANIFEST["end_to_end"]
           if CELL in (m.get("workloads") or [CELL])}
    assert e2e == {"reviews_per_s", "setup_s"}


WINDOW = {  # 50,000 live requests beside 300,000 lane rows, 240,000 a tier's
    "policy_server_requests_dispatched_total": 50_000,
    "policy_server_audit_rows_judged_total": 300_000,
    "policy_server_audit_tier_hits_total": 240_000,
    PHASE % ("count", "audit_bookkeeping"): 2_400,
    PHASE % ("sum", "audit_bookkeeping"): 3.6,
}


@pytest.mark.parametrize("name, want", zip(NEW_METRICS, (80.0, 6.0, 1.5)))
def test_a_new_layer_metric_reads_the_programs_counters(name, want):
    before, after = _planted(WINDOW)
    assert reduce.read_layer_metric(
        name, {"before": before, "after": after}) == pytest.approx(want)
    # a program without the counters or the phase (the parent): nothing
    parent = {k: v for k, v in WINDOW.items() if "requests_dispatched" in k}
    before, after = _planted(parent)
    assert reduce.read_layer_metric(
        name, {"before": before, "after": after}) is None


def test_the_new_metrics_counters_are_the_programs_own():
    names = {metrics_mod.AUDIT_TIER_HITS, metrics_mod.AUDIT_ROWS_JUDGED,
             metrics_mod.REQUESTS_DISPATCHED,
             metrics_mod.PHASE_LATENCY_SECONDS + "_sum",
             metrics_mod.PHASE_LATENCY_SECONDS + "_count"}
    read = set()
    for name in NEW_METRICS:
        spec = json.loads(
            (BENCH / "layer_metrics" / f"{name}.json").read_text())
        for side in ("numerator", "denominator"):
            part = spec[side]
            read.add(part if isinstance(part, str) else part["name"])
    assert read == names


# -- the cell, rehearsed on the CPU -----------------------------------------------


def test_a_rehearsal_of_the_cell_is_correct(monkeypatch, capsys, tmp_path):
    """The cell's configuration under the benchmark's tiny mix, through
    ``benchmarks/run.py --platform cpu``: the server, the clients, the
    window and the comparison are the run's own."""
    manifest = copy.deepcopy(MANIFEST)
    tiny = f"{NAME}.tiny-closed"
    manifest["workloads"].append(dict(
        MANIFEST["workloads"][-1], name=tiny, traffic="tiny-closed"))
    monkeypatch.setattr(bench_run.check_manifest, "load",
                        lambda root=None: manifest)
    # one device, as the cell asks (the suite's eight virtual devices are
    # this process's)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    load_json = bench_run.load_json

    def rehearsed(path: Path) -> dict:
        """The tiny mix over 31 pod shapes, and the scanner's cadence at a
        quarter second: every shape reaches the device through the lane
        during the warm passes, so the column set settles before the
        window as it does in the cell's own warm passes (a sweep's work is
        the same at any cadence)."""
        doc = load_json(path)
        if path.name == "tiny-closed.json":
            doc = dict(doc, pool_shapes=31, warm_requests=256)
        elif path.name == f"{NAME}.json":
            flags = list(doc["server_flags"])
            flags[flags.index("--audit-interval-seconds") + 1] = "0.25"
            doc = dict(doc, server_flags=flags)
        return doc

    monkeypatch.setattr(bench_run, "load_json", rehearsed)
    started = time.monotonic()
    rc = bench_run.main([
        "--workload", tiny, "--seed", str(SEED), "--seconds", "3",
        "--trace", "0", "--platform", "cpu", "--keep", str(tmp_path)])
    out = capsys.readouterr()
    assert rc == 0, out.err[-3000:]
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["compared"]["rows_not_dispatched"] == [0, 0]
    assert result["compared"]["answered_off_device"] == [0, 0]
    assert result["attempted"] > 50 and result["failed"] == 0
    moved = json.loads((tmp_path / "counters.json").read_text())
    assert moved.get("policy_server_host_fastpath_requests_total", 0) > 0
    assert time.monotonic() - started < 600
