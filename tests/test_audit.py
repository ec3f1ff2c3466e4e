"""Background audit scanner (round 10): snapshot-store mechanics, the
micro-batcher's best-effort audit lane (idle-only dispatch, single
in-flight cap, preemption), the sweep pipeline (full / dirty / breaker
pause / fault abort+resume), epoch coherence (promote → full re-scan,
rollback → stale reports), the GET /audit/reports surface, and the
audit-vs-validate constraint-skip pin (reference handlers.rs:69-90)."""

from __future__ import annotations

import base64
import json
import threading
import time
from types import SimpleNamespace

import pytest

from policy_server_tpu import failpoints
from policy_server_tpu.audit import (
    AuditScanner,
    PolicyReportStore,
    SnapshotStore,
    resource_key,
)
from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.evaluation.environment import (
    EvaluationEnvironmentBuilder,
)
from policy_server_tpu.models import (
    AdmissionResponse,
    AdmissionReviewRequest,
    ValidateRequest,
)
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.runtime.batcher import DEADLINE_MESSAGE, MicroBatcher
from policy_server_tpu.telemetry import metrics as metrics_mod

from conftest import build_admission_review_dict


@pytest.fixture(autouse=True)
def fresh_metrics():
    metrics_mod.reset_metrics_for_tests()
    yield
    metrics_mod.reset_metrics_for_tests()


@pytest.fixture(autouse=True)
def clean_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


def pod_review(
    name: str = "p",
    namespace: str = "default",
    privileged: bool = False,
    operation: str = "CREATE",
) -> ValidateRequest:
    doc = build_admission_review_dict()
    doc["request"]["uid"] = f"uid-{namespace}-{name}"
    doc["request"]["name"] = name
    doc["request"]["namespace"] = namespace
    doc["request"]["operation"] = operation
    doc["request"]["kind"] = {"group": "", "version": "v1", "kind": "Pod"}
    doc["request"]["object"] = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": namespace},
        "spec": {
            "containers": [
                {
                    "name": "c",
                    "image": "nginx",
                    "securityContext": {"privileged": privileged},
                }
            ]
        },
    }
    return ValidateRequest.from_admission(
        AdmissionReviewRequest.from_dict(doc).request
    )


# ---------------------------------------------------------------------------
# snapshot store
# ---------------------------------------------------------------------------


def test_snapshot_key_supersede_delete_and_dirty():
    store = SnapshotStore(max_bytes=10 * 1024 * 1024)
    a1 = pod_review("a", privileged=False)
    a2 = pod_review("a", privileged=True)  # same object, newer admission
    b = pod_review("b")
    assert resource_key(a1) == resource_key(a2)
    store.observe([a1, b])
    assert len(store) == 2
    # later admission supersedes the earlier snapshot of the same object
    store.observe([a2])
    assert len(store) == 2
    rows = dict(store.collect())
    assert rows[resource_key(a2)] is a2
    # collect cleared the dirty set; a fresh observe re-dirties only "a"
    store.observe([a2])
    dirty = store.collect(dirty_only=True)
    assert [k for k, _ in dirty] == [resource_key(a2)]
    # DELETE evicts the object from the snapshot
    store.observe([pod_review("a", operation="DELETE")])
    assert len(store) == 1
    stats = store.stats()
    # two supersedes: a2 over a1, then the re-observe of a2 over itself
    assert stats["superseded"] == 2 and stats["deleted"] == 1
    # raw requests are untrackable and ignored
    store.observe([ValidateRequest.from_raw({"uid": "r"})])
    assert len(store) == 1


def test_snapshot_byte_budget_evicts_lru():
    one = len(pod_review("x").payload_json())
    store = SnapshotStore(max_bytes=int(one * 2.5))
    store.observe([pod_review(f"n{i}") for i in range(4)])
    assert len(store) == 2  # only the 2 newest fit the budget
    assert store.stats()["evicted"] == 2
    assert store.stats()["bytes"] <= int(one * 2.5)
    kept = [k for k, _ in store.collect()]
    assert all(k.endswith(("n2", "n3")) for k in kept)


def test_snapshot_seed_from_file(tmp_path):
    path = tmp_path / "resources.yml"
    path.write_text(
        json.dumps(
            {
                "items": [
                    {
                        "apiVersion": "v1",
                        "kind": "Pod",
                        "metadata": {"name": "seeded", "namespace": "ns1"},
                        "spec": {"containers": [{"name": "c", "image": "i"}]},
                    },
                    {"not-an-object": True},
                ]
            }
        )
    )
    store = SnapshotStore()
    assert store.seed_from_file(str(path)) == 1
    (key, req), = store.collect()
    assert key == "/v1/Pod/ns1/seeded"
    assert req.admission_request.operation == "CREATE"


# ---------------------------------------------------------------------------
# report store
# ---------------------------------------------------------------------------


def test_report_store_rows_summary_and_rollback_staleness():
    store = PolicyReportStore()
    req = pod_review("a", namespace="ns1")
    key = resource_key(req)
    deny = AdmissionResponse.reject("u", "denied", 400)
    allow = AdmissionResponse(uid="u", allowed=True)
    store.put([
        store.row_from_result(key, "p1", req, deny, epoch=0),
        store.row_from_result(key, "p2", req, allow, epoch=0),
        store.row_from_result(key, "p3", req, RuntimeError("boom"), epoch=0),
    ])
    body = store.payload()
    assert body["summary"] == {
        # the error row carries allowed=None: neither pass nor fail
        "results": 3, "resources": 1, "pass": 1, "fail": 1, "error": 1,
        "mutated": 0, "stale": 0,
    }
    # namespace filter
    assert store.payload("other")["summary"]["results"] == 0
    assert store.payload("ns1")["summary"]["results"] == 3
    # a re-scan under epoch 1 overwrites per (resource, policy)
    store.put([store.row_from_result(key, "p1", req, allow, epoch=1)])
    assert store.payload()["summary"]["results"] == 3
    # rollback of epoch 1 marks exactly its rows stale; stale rows drop
    # out of pass/fail but stay listed
    assert store.mark_epoch_stale(1) == 1
    body = store.payload()
    assert body["summary"]["stale"] == 1
    assert body["summary"]["pass"] == 1  # p2's epoch-0 allow
    stale_rows = [r for r in body["reports"] if r["stale"]]
    assert [r["policy_id"] for r in stale_rows] == ["p1"]


# ---------------------------------------------------------------------------
# the batcher's best-effort audit lane
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def env():
    policies = {
        "priv": parse_policy_entry(
            "priv", {"module": "builtin://pod-privileged"}
        ),
        "happy": parse_policy_entry(
            "happy", {"module": "builtin://always-happy"}
        ),
    }
    e = EvaluationEnvironmentBuilder(backend="jax").build(policies)
    yield e
    e.close()


def test_audit_lane_dispatches_raw_verdicts_when_idle(env):
    batcher = MicroBatcher(env, max_batch_size=8, policy_timeout=10.0).start()
    try:
        pairs = [
            ("priv", pod_review("lane-a", privileged=True)),
            ("priv", pod_review("lane-b", privileged=False)),
        ]
        results = batcher.submit_audit(pairs).result(timeout=30)
        assert results[0].allowed is False
        assert results[1].allowed is True
        snap = batcher.stats_snapshot()
        assert snap["audit_batches_dispatched"] == 1
        assert snap["audit_rows_judged"] == 2
        assert batcher.audit_lane_depth() == 0
    finally:
        batcher.shutdown()


def test_audit_lane_single_inflight_cap(env):
    """Two audit jobs with a blocked dispatch: the second must not start
    until the first finishes — the lane's in-flight cap is exactly 1."""
    release = threading.Event()
    started: list[float] = []
    real = env.validate_batch

    class Blocking:
        def __getattr__(self, name):
            return getattr(env, name)

        def validate_batch(self, pairs, **kw):
            started.append(time.perf_counter())
            assert release.wait(timeout=30)
            return real(pairs, **kw)

    batcher = MicroBatcher(
        Blocking(), max_batch_size=8, policy_timeout=10.0
    ).start()
    try:
        f1 = batcher.submit_audit([("happy", pod_review("c1"))])
        f2 = batcher.submit_audit([("happy", pod_review("c2"))])
        deadline = time.perf_counter() + 5
        while not started and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert len(started) == 1  # second job waits for the slot
        time.sleep(0.3)
        assert len(started) == 1
        release.set()
        assert f1.result(timeout=30)[0].allowed is True
        assert f2.result(timeout=30)[0].allowed is True
        assert len(started) == 2
    finally:
        release.set()
        batcher.shutdown()


def test_audit_preemption_requeues_for_live_work(env):
    """A popped audit job observing live work re-queues itself at the
    lane head and counts a preemption (driven synchronously: the
    dispatch loop is not running, so the race window is forced)."""
    batcher = MicroBatcher(env, max_batch_size=8, policy_timeout=10.0)
    # NOT started: we drive the lane by hand
    fut = batcher.submit_audit([("happy", pod_review("pre"))])
    # live work arrives
    live = batcher.submit("happy", pod_review("live"), RequestOrigin.VALIDATE)
    batcher._maybe_dispatch_audit()
    deadline = time.perf_counter() + 5
    while batcher.audit_lane_depth() == 0 and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert batcher.audit_lane_depth() == 1  # re-queued, not dispatched
    assert batcher.stats_snapshot()["audit_preemptions"] == 1
    assert not fut.done()
    # once the live lane drains (started loop), both complete
    batcher.start()
    assert live.result(timeout=30).allowed is True
    assert fut.result(timeout=30)[0].allowed is True
    batcher.shutdown()


def test_audit_slack_gate_blocks_on_breaker_and_tight_budget(env):
    class BreakerOpen:
        breaker_all_open = True

        def __getattr__(self, name):
            return getattr(env, name)

    batcher = MicroBatcher(BreakerOpen(), max_batch_size=8)
    assert batcher._audit_slack_ok(8) is False
    # slack keys on the HARD request-deadline budget (the soft latency
    # budget defends itself via the host-side router instead)
    batcher2 = MicroBatcher(
        env, max_batch_size=8, request_timeout_ms=100.0,
    )
    from policy_server_tpu.evaluation.environment import bucket_size

    batcher2._dev_rtt[bucket_size(8)] = 0.5  # 500 ms RTT >> 100 ms budget
    assert batcher2._audit_slack_ok(8) is False
    batcher2._dev_rtt[bucket_size(8)] = 0.001
    assert batcher2._audit_slack_ok(8) is True
    # the hold estimate scales with the AUDIT batch size, not the live
    # bucket alone: 8 ms/chunk x 64 rows / 8-row bucket = 64 ms > 50 ms
    batcher2._dev_rtt[bucket_size(8)] = 0.008
    assert batcher2._audit_slack_ok(8) is True
    assert batcher2._audit_slack_ok(64) is False
    # no deadline propagation configured: audit always has slack when idle
    batcher3 = MicroBatcher(env, max_batch_size=8, request_timeout_ms=0.0)
    batcher3._dev_rtt[bucket_size(8)] = 0.5
    assert batcher3._audit_slack_ok(8) is True


def test_audit_lane_rejects_on_shutdown(env):
    batcher = MicroBatcher(env, max_batch_size=8)
    fut = batcher.submit_audit([("happy", pod_review("s1"))])
    batcher.shutdown()
    with pytest.raises(RuntimeError, match="audit lane closed"):
        fut.result(timeout=5)
    with pytest.raises(RuntimeError, match="audit lane closed"):
        batcher.submit_audit([("happy", pod_review("s2"))]).result(timeout=5)


def test_preemption_proof_live_deadlines_met_under_saturating_sweep(env):
    """THE acceptance property: with the audit lane saturated (far more
    queued audit rows than the device can absorb), injected live
    requests still meet their deadline — a live batch never waits behind
    more than the single in-flight audit dispatch."""
    batcher = MicroBatcher(
        env, max_batch_size=16, policy_timeout=5.0,
        host_fastpath_threshold=0,  # live rides the device path too
    ).start()
    try:
        batcher.warmup()
        # saturate: 40 audit batches x 64 unique rows, far beyond what
        # dispatches during the test
        for b in range(40):
            batcher.submit_audit([
                ("priv", pod_review(f"audit-{b}-{i}", privileged=bool(i % 2)))
                for i in range(64)
            ])
        latencies: list[float] = []
        for wave in range(10):
            t0 = time.perf_counter()
            futs = [
                batcher.submit(
                    "priv", pod_review(f"live-{wave}-{i}", privileged=False),
                    RequestOrigin.VALIDATE,
                )
                for i in range(8)
            ]
            for f in futs:
                resp = f.result(timeout=10)
                assert resp.allowed is True, resp.status
                if resp.status is not None:
                    assert resp.status.message != DEADLINE_MESSAGE
            latencies.append(time.perf_counter() - t0)
            time.sleep(0.05)  # idle gap: the audit lane may claim it
        snap = batcher.stats_snapshot()
        # audit throughput rode the idle gaps...
        assert snap["audit_batches_dispatched"] >= 1
        # ...while every live wave stayed far inside the 5 s deadline
        # (one in-flight audit dispatch of 64 rows bounds the wait)
        assert max(latencies) < 4.0, latencies
        assert snap["deadline_abandoned_batches"] == 0
    finally:
        batcher.shutdown()


# ---------------------------------------------------------------------------
# the scanner
# ---------------------------------------------------------------------------


def make_scanner(env, batcher, lifecycle=None, **kw):
    state = SimpleNamespace(
        evaluation_environment=env, batcher=batcher, lifecycle=lifecycle
    )
    snapshot = SnapshotStore()
    reports = PolicyReportStore()
    kw.setdefault("mode", "interval")
    kw.setdefault("interval_seconds", 30.0)
    scanner = AuditScanner(
        state=state, snapshot=snapshot, reports=reports, **kw
    )
    return scanner


def test_scanner_full_and_dirty_sweeps(env):
    batcher = MicroBatcher(env, max_batch_size=8, policy_timeout=10.0).start()
    scanner = make_scanner(env, batcher, batch_size=4)
    try:
        scanner.snapshot.observe([
            pod_review("a", privileged=True), pod_review("b"),
        ])
        # full sweep: 2 resources x 2 policies = 4 rows
        assert scanner.sweep(full=True) == 4
        body = scanner.report_payload()
        assert body["summary"]["results"] == 4
        assert body["summary"]["resources"] == 2
        # "a" is privileged: priv denies it, happy allows everything
        by = {(r["name"], r["policy_id"]): r for r in body["reports"]}
        assert by[("a", "priv")]["allowed"] is False
        assert by[("a", "happy")]["allowed"] is True
        assert by[("b", "priv")]["allowed"] is True
        assert all(r["epoch"] == 0 for r in body["reports"])
        # nothing dirty: a dirty sweep scans nothing
        assert scanner.sweep(full=False) == 0
        # touch one object: the dirty sweep re-judges only it
        scanner.snapshot.observe([pod_review("b", privileged=True)])
        assert scanner.sweep(full=False) == 2
        body = scanner.report_payload()
        by = {(r["name"], r["policy_id"]): r for r in body["reports"]}
        assert by[("b", "priv")]["allowed"] is False  # superseded object
        stats = scanner.stats()
        assert stats["full_sweeps"] == 1
        assert stats["dirty_sweeps"] == 2
        assert stats["rows_scanned"] == 6
        assert stats["freshness_seconds"] >= 0
    finally:
        batcher.shutdown()


def test_scanner_rows_scanned_accounts_whole_run_across_epochs(env):
    """PROFILE r13 caveat 3 (soak-artifact accounting): ``rows_scanned``
    is the WHOLE-RUN total across policy epochs, and
    ``rows_scanned_by_epoch`` decomposes it — a run whose last event is
    an epoch flip reports every epoch's audit volume, not only the
    post-promote sweep's."""
    lifecycle = SimpleNamespace(current_epoch=0)
    batcher = MicroBatcher(env, max_batch_size=8, policy_timeout=10.0).start()
    scanner = make_scanner(env, batcher, lifecycle=lifecycle, batch_size=4)
    try:
        scanner.snapshot.observe([
            pod_review("a", privileged=True), pod_review("b"),
        ])
        assert scanner.sweep(full=True) == 4  # epoch 0
        # promote: the post-promote full sweep re-judges everything
        # under the new epoch's set
        lifecycle.current_epoch = 1
        scanner.on_promote(1)
        assert scanner.sweep(full=True) == 4  # epoch 1
        stats = scanner.stats()
        assert stats["rows_scanned"] == 8  # whole run, both epochs
        assert stats["rows_scanned_by_epoch"] == {"0": 4, "1": 4}
        assert (
            sum(stats["rows_scanned_by_epoch"].values())
            == stats["rows_scanned"]
        )
    finally:
        batcher.shutdown()


def test_scanner_pauses_while_breaker_open(env):
    class BreakerOpen:
        breaker_all_open = True

        def __getattr__(self, name):
            return getattr(env, name)

    batcher = MicroBatcher(env, max_batch_size=8).start()
    scanner = make_scanner(BreakerOpen(), batcher)
    try:
        scanner.snapshot.observe([pod_review("a")])
        assert scanner.sweep(full=True) == 0
        assert scanner.stats()["paused_sweeps"] == 1
        assert scanner.report_payload()["summary"]["results"] == 0
    finally:
        batcher.shutdown()


def test_scanner_fault_aborts_then_resumes(env):
    """An armed ``audit.sweep`` fault aborts the sweep (error counted,
    unscanned keys re-marked dirty); the next sweep — fault cleared —
    judges the full corpus. The scanner never wedges."""
    batcher = MicroBatcher(env, max_batch_size=8, policy_timeout=10.0).start()
    scanner = make_scanner(env, batcher)
    try:
        scanner.snapshot.observe([pod_review("a"), pod_review("b")])
        with failpoints.active(
            "audit.sweep",
            lambda: (_ for _ in ()).throw(
                failpoints.FailpointError("injected sweep fault")
            ),
            count=1,
        ):
            with pytest.raises(failpoints.FailpointError):
                scanner.sweep(full=True)
        assert failpoints.fired_count("audit.sweep") == 1
        # fault cleared: the retry judges everything
        assert scanner.sweep(full=True) == 4
        assert scanner.report_payload()["summary"]["results"] == 4
    finally:
        batcher.shutdown()


def test_scanner_mid_sweep_batcher_shutdown_remarks_dirty(env):
    """A mid-sweep epoch retirement (the batcher shuts down under the
    scanner) aborts the sweep and re-marks unscanned keys dirty so the
    post-promote sweep picks them back up on the new epoch."""
    batcher = MicroBatcher(env, max_batch_size=8, policy_timeout=10.0)
    # not started, then shut down: submit_audit rejects like a retiring
    # epoch's batcher would
    batcher.shutdown()
    scanner = make_scanner(env, batcher, job_timeout_seconds=5.0)
    scanner.snapshot.observe([pod_review("a"), pod_review("b")])
    with pytest.raises(RuntimeError):
        scanner.sweep(full=True)
    # both resources back on the dirty set
    assert scanner.snapshot.stats()["dirty"] == 2
    # a healthy epoch finishes the job from the dirty set alone
    batcher2 = MicroBatcher(env, max_batch_size=8, policy_timeout=10.0).start()
    scanner.state.batcher = batcher2
    try:
        assert scanner.sweep(full=False) == 4
    finally:
        batcher2.shutdown()


# ---------------------------------------------------------------------------
# end to end: real server, HTTP surface, epoch coherence, audit-vs-validate
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def audit_server():
    import requests as _rq  # noqa: F401 — fail fast if missing

    from test_server import ServerHandle, make_config

    metrics_mod.reset_metrics_for_tests()
    policies = {
        "pod-privileged": parse_policy_entry(
            "pod-privileged", {"module": "builtin://pod-privileged"}
        ),
        # mutating policy, allowedToMutate UNSET (False) in protect mode:
        # the constraint FLIPS the verdict on /validate but must not on
        # /audit (reference handlers.rs:69-90)
        "caps-mutator": parse_policy_entry(
            "caps-mutator",
            {
                "module": "builtin://psp-capabilities",
                "settings": {
                    "allowed_capabilities": ["*"],
                    "required_drop_capabilities": ["NET_ADMIN"],
                },
            },
        ),
    }
    config = make_config(
        policies=policies,
        policy_timeout_seconds=5.0,
        audit_mode="interval",
        # cadence far beyond the test: sweeps are driven by hand or by
        # the lifecycle hooks, never by the timer
        audit_interval_seconds=60.0,
        audit_batch_size=8,
    )
    handle = ServerHandle(config)
    yield handle
    handle.stop()
    metrics_mod.reset_metrics_for_tests()


def _wait_until(predicate, timeout=15.0, step=0.05):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if predicate():
            return True
        time.sleep(step)
    return False


def test_audit_skips_constraints_validate_applies_them(audit_server):
    """Satellite pin: the SAME mutating review through both endpoints —
    /validate (protect mode, not allowed to mutate) flips the verdict to
    a rejection with the patch stripped; /audit reports the RAW verdict,
    patch intact (service.rs:108-116, handlers.rs:69-90)."""
    import requests as rq

    from test_server import pod_review_body

    body = pod_review_body(False)
    r = rq.post(
        audit_server.url("/validate/caps-mutator"), json=body, timeout=30
    )
    assert r.status_code == 200
    resp = r.json()["response"]
    assert resp["allowed"] is False
    assert "patch" not in resp
    assert "not allow mutations" in resp["status"]["message"]

    r = rq.post(
        audit_server.url("/audit/caps-mutator"), json=body, timeout=30
    )
    assert r.status_code == 200
    resp = r.json()["response"]
    assert resp["allowed"] is True
    patch = json.loads(base64.b64decode(resp["patch"]))
    assert any(
        op["path"].endswith("/capabilities/drop")
        and op["value"] == ["NET_ADMIN"]
        for op in patch
    )
    assert resp["patchType"] == "JSONPatch"


def test_dirty_tracking_sweep_and_reports_endpoints(audit_server):
    import requests as rq

    from test_server import pod_review_body

    scanner = audit_server.server.state.audit
    assert scanner is not None
    # served /validate traffic lands in the snapshot (dirty-set tracker);
    # audit-origin traffic must NOT feed the snapshot
    doc = pod_review_body(True)
    doc["request"]["namespace"] = "ns-a"
    doc["request"]["object"]["metadata"]["namespace"] = "ns-a"
    r = rq.post(
        audit_server.url("/validate/pod-privileged"), json=doc, timeout=30
    )
    assert r.status_code == 200
    before = scanner.snapshot.stats()["resources"]
    r = rq.post(
        audit_server.url("/audit/pod-privileged"), json=doc, timeout=30
    )
    assert r.status_code == 200
    assert _wait_until(
        lambda: scanner.snapshot.stats()["resources"] == before
    )
    assert before >= 1

    scanner.sweep(full=True)
    r = rq.get(audit_server.url("/audit/reports"), timeout=10)
    assert r.status_code == 200
    body = r.json()
    assert body["summary"]["results"] >= 2  # >=1 resource x 2 policies
    assert body["scanner"]["full_sweeps"] >= 1
    assert body["scanner"]["freshness_seconds"] >= 0
    rows = {
        (x["namespace"], x["policy_id"]): x for x in body["reports"]
    }
    # the privileged pod in ns-a: denied by pod-privileged (raw verdict)
    assert rows[("ns-a", "pod-privileged")]["allowed"] is False
    assert rows[("ns-a", "caps-mutator")]["mutated"] is True

    # namespace-scoped listing filters
    r = rq.get(audit_server.url("/audit/reports/ns-a"), timeout=10)
    assert r.status_code == 200
    assert all(x["namespace"] == "ns-a" for x in r.json()["reports"])
    r = rq.get(audit_server.url("/audit/reports/no-such-ns"), timeout=10)
    assert r.json()["summary"]["results"] == 0
    # the readiness port serves the same listing (always the main
    # process — prefork workers only proxy the POST surface)
    r = rq.get(audit_server.readiness_url("/audit/reports"), timeout=10)
    assert r.status_code == 200
    assert r.json()["summary"]["results"] >= 2


def test_epoch_coherence_promote_rescans_rollback_stales(audit_server):
    """Acceptance: reports carry the epoch generation; a promote
    triggers a full re-scan stamped with the new epoch; a rollback marks
    the rolled-back epoch's reports stale and re-scans under the revived
    epoch."""
    import requests as rq

    from test_server import pod_review_body

    scanner = audit_server.server.state.audit
    lifecycle = audit_server.server.lifecycle
    assert lifecycle is not None
    # baseline: traffic + a by-hand full sweep stamped with epoch 0
    r = rq.post(
        audit_server.url("/validate/pod-privileged"),
        json=pod_review_body(False), timeout=30,
    )
    assert r.status_code == 200
    scanner.sweep(full=True)
    epoch0 = lifecycle.current_epoch
    body = scanner.report_payload()
    assert body["summary"]["results"] >= 2
    assert all(x["epoch"] == epoch0 for x in body["reports"])

    # PROMOTE: the post-promote hook queues a full re-scan on the
    # scanner thread; rows re-stamp with the new epoch
    sweeps_before = scanner.stats()["full_sweeps"]
    with lifecycle._swap_lock:
        current_policies = dict(lifecycle._current.policies)
    assert lifecycle.reload(policies=current_policies) == "promoted"
    epoch1 = lifecycle.current_epoch
    assert epoch1 == epoch0 + 1
    assert _wait_until(
        lambda: scanner.stats()["full_sweeps"] > sweeps_before
        and all(
            x["epoch"] == epoch1 for x in scanner.report_payload()["reports"]
        ),
        timeout=30,
    ), scanner.report_payload()["reports"]

    # ROLLBACK: hold the sweep lock so the stale marking (synchronous,
    # inside rollback()) is observable before the queued post-rollback
    # re-scan overwrites it
    with scanner._sweep_lock:
        assert lifecycle.rollback() == "rolled-back"
        assert lifecycle.current_epoch == epoch0
        body = scanner.report_payload()
        stale = [x for x in body["reports"] if x["stale"]]
        assert stale and all(x["epoch"] == epoch1 for x in stale)
        assert body["summary"]["stale"] == len(stale)
    # lock released: the queued post-rollback full sweep re-judges
    # everything under the revived epoch and clears the staleness
    assert _wait_until(
        lambda: all(
            x["epoch"] == epoch0 and not x["stale"]
            for x in scanner.report_payload()["reports"]
        ),
        timeout=30,
    ), scanner.report_payload()["reports"]


def test_reports_endpoint_404_when_audit_off():
    import requests as rq

    from test_server import ServerHandle, make_config

    config = make_config(
        policies={
            "pod-privileged": parse_policy_entry(
                "pod-privileged", {"module": "builtin://pod-privileged"}
            ),
        },
        policy_timeout_seconds=5.0,
        warmup_at_boot=False,
        policy_reload_mode="off",
    )
    handle = ServerHandle(config)
    try:
        assert handle.server.state.audit is None
        r = rq.get(handle.url("/audit/reports"), timeout=10)
        assert r.status_code == 404
        assert "audit scanner is disabled" in r.json()["message"]
    finally:
        handle.stop()


# ---------------------------------------------------------------------------
# review-hardening regressions: deferred full sweeps, report GC, lane cancel
# ---------------------------------------------------------------------------


def test_paused_full_sweep_keeps_its_pending_claim(env):
    """A full sweep skipped by the breaker pause (or failed outright)
    must stay pending — in on-promote mode nothing else would ever
    re-trigger it, and the new epoch would never re-judge the cluster."""

    class BreakerOpen:
        breaker_all_open = True

        def __getattr__(self, name):
            return getattr(env, name)

    batcher = MicroBatcher(env, max_batch_size=8).start()
    scanner = make_scanner(BreakerOpen(), batcher, mode="on-promote")
    try:
        with scanner._lock:
            scanner._full_pending = False  # as _loop does before sweeping
        assert scanner.sweep(full=True) == 0  # paused, not run
        with scanner._lock:
            assert scanner._full_pending is True  # claim restored
        # same for a faulted sweep
        with scanner._lock:
            scanner._full_pending = False
        with failpoints.active(
            "audit.sweep",
            lambda: (_ for _ in ()).throw(
                failpoints.FailpointError("injected")
            ),
            count=1,
        ):
            with pytest.raises(failpoints.FailpointError):
                scanner.sweep(full=True)
        with scanner._lock:
            assert scanner._full_pending is True
    finally:
        batcher.shutdown()


def test_reports_pruned_for_deleted_and_evicted_resources(env):
    """Report rows must not outlive their resource: a DELETE prunes on
    the next sweep, and a completed full sweep garbage-collects rows
    for resources/policies no longer in the inventory."""
    batcher = MicroBatcher(env, max_batch_size=8, policy_timeout=10.0).start()
    scanner = make_scanner(env, batcher)
    try:
        scanner.snapshot.observe([pod_review("a"), pod_review("b")])
        scanner.sweep(full=True)
        assert scanner.report_payload()["summary"]["resources"] == 2
        # DELETE of "a": its rows prune on the next (dirty) sweep
        scanner.snapshot.observe([pod_review("a", operation="DELETE")])
        scanner.sweep(full=False)
        body = scanner.report_payload()
        assert body["summary"]["resources"] == 1
        assert all(r["name"] == "b" for r in body["reports"])
        # stale policy rows GC on a full sweep: forge a row for a policy
        # the serving set does not carry
        scanner.reports.put([
            scanner.reports.row_from_result(
                "/v1/Pod/default/b", "removed-policy", pod_review("b"),
                AdmissionResponse(uid="u", allowed=True), epoch=0,
            )
        ])
        scanner.sweep(full=True)
        assert all(
            r["policy_id"] in ("priv", "happy")
            for r in scanner.report_payload()["reports"]
        )
    finally:
        batcher.shutdown()


def test_cancel_audit_removes_queued_job(env):
    batcher = MicroBatcher(env, max_batch_size=8)  # not started: job queues
    fut = batcher.submit_audit([("happy", pod_review("c"))])
    assert batcher.audit_lane_depth() == 1
    assert batcher.cancel_audit(fut) is True
    assert batcher.audit_lane_depth() == 0
    with pytest.raises(RuntimeError, match="cancelled"):
        fut.result(timeout=5)
    # cancelling an unknown/already-gone future is a no-op
    assert batcher.cancel_audit(fut) is False
    batcher.shutdown()


def test_sweep_job_timeout_cancels_lane_job_and_remarks_dirty(env):
    """The overload shape: the lane never gets an idle slot, the sweep
    times out — the stale job must leave the lane (no duplicate pileup)
    and the resources go back on the dirty set."""
    batcher = MicroBatcher(env, max_batch_size=8)  # loop not running:
    # submitted audit jobs never dispatch, like a saturated live lane
    scanner = make_scanner(env, batcher, job_timeout_seconds=0.3)
    scanner.snapshot.observe([pod_review("a")])
    with pytest.raises(RuntimeError, match="timed out"):
        scanner.sweep(full=True)
    assert batcher.audit_lane_depth() == 0  # cancelled, not lingering
    assert scanner.snapshot.stats()["dirty"] == 1
    with scanner._lock:
        assert scanner._full_pending is True
    batcher.shutdown()


def test_on_promote_mode_drains_deletions_between_sweeps(env):
    """on-promote mode may not sweep for days: the cadence loop must
    still drain observed DELETEs every tick — pruning the deleted
    objects' report rows and bounding the pending-deletion set."""
    batcher = MicroBatcher(env, max_batch_size=8, policy_timeout=10.0).start()
    scanner = make_scanner(env, batcher, mode="on-promote")
    try:
        scanner.snapshot.observe([pod_review("a"), pod_review("b")])
        scanner.sweep(full=True)
        assert scanner.report_payload()["summary"]["resources"] == 2
        with scanner._lock:
            scanner._full_pending = False  # no sweep will run
        scanner.start()
        scanner.snapshot.observe([pod_review("a", operation="DELETE")])
        assert _wait_until(
            lambda: scanner.report_payload()["summary"]["resources"] == 1
            and not scanner.snapshot.take_deletions()
        ), scanner.report_payload()["summary"]
        # no sweep ran: the prune happened on the cadence tick alone
        assert scanner.stats()["full_sweeps"] == 1
        assert scanner.stats()["dirty_sweeps"] == 0
    finally:
        scanner.shutdown()
        batcher.shutdown()


# ---------------------------------------------------------------------------
# Multi-tenant scoping (round 16, tenancy.py): the audit scanner serves
# the DEFAULT tenant only — a named tenant's validate traffic must never
# feed the default snapshot store (or its report rows).
# ---------------------------------------------------------------------------


def test_tenant_traffic_never_feeds_the_audit_snapshot(env):
    """server.py wires named-tenant batchers with audit_tracker=None:
    the snapshot store (and therefore every report row derived from it)
    stays scoped to objects admitted through the DEFAULT tenant."""
    store = SnapshotStore(max_bytes=10 * 1024 * 1024)
    default_batcher = MicroBatcher(
        env, max_batch_size=8, policy_timeout=10.0, audit_tracker=store,
    ).start()
    tenant_batcher = MicroBatcher(
        env, max_batch_size=8, policy_timeout=10.0, audit_tracker=None,
        tenant="ten-a",
    ).start()
    try:
        default_batcher.submit(
            "priv", pod_review("from-default"), RequestOrigin.VALIDATE
        ).result(timeout=30)
        tenant_batcher.submit(
            "priv", pod_review("from-tenant-a"), RequestOrigin.VALIDATE
        ).result(timeout=30)
        keys = [k for k, _ in store.collect()]
        assert any("from-default" in k for k in keys)
        assert not any("from-tenant-a" in k for k in keys)
        assert len(store) == 1
    finally:
        default_batcher.shutdown()
        tenant_batcher.shutdown()
