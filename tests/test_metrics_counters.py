"""Round-6 serving counters are operator-visible (VERDICT r5 weak #4):
two-tier dedup, verdict-cache hit/miss, host-fastpath, budget routing,
and the host-pipeline decomposition must appear — with correct values —
on the Prometheus pull endpoint (/metrics) AND survive the OTLP
conversion that the metrics pusher uses, after a REAL served batch."""

from __future__ import annotations

import json
import time

import pytest
import requests

from policy_server_tpu.config.config import Config, TlsConfig
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.telemetry import metrics as metrics_mod

from conftest import build_admission_review_dict
from test_server import ServerHandle


def _review_body(uid: str, privileged: bool) -> bytes:
    doc = build_admission_review_dict()
    doc["request"]["uid"] = uid
    doc["request"]["object"] = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": "p", "namespace": "default"},
        "spec": {
            "containers": [
                {"name": "c", "image": "nginx",
                 "securityContext": {"privileged": privileged}}
            ]
        },
    }
    return json.dumps(doc).encode()


@pytest.fixture(scope="module")
def server():
    metrics_mod.reset_metrics_for_tests()
    config = Config(
        addr="127.0.0.1",
        port=0,
        readiness_probe_port=0,
        tls_config=TlsConfig(),
        policies={
            "pod-privileged": parse_policy_entry(
                "pod-privileged", {"module": "builtin://pod-privileged"}
            ),
            # gives the schema a string column (the image, under its
            # predicates): what the encoder's mirror is about
            "latest-tag": parse_policy_entry(
                "latest-tag", {"module": "builtin://disallow-latest-tag"}
            ),
        },
        policy_timeout_seconds=30.0,
        max_batch_size=8,
        batch_timeout_ms=1.0,
        # 0 forces the DEVICE path so the encode/dedup/dispatch counters
        # all move (the host fast-path would bypass the native pipeline)
        host_fastpath_threshold=0,
        # and no budget routing: on a loaded machine the device's round
        # trip outruns the 50 ms budget and the batcher answers host-side
        latency_budget_ms=0,
        warmup_at_boot=True,
    )
    handle = ServerHandle(config)
    yield handle
    handle.stop()
    metrics_mod.reset_metrics_for_tests()


def _scrape(server) -> dict[str, float]:
    r = requests.get(server.readiness_url("/metrics"), timeout=10)
    assert r.status_code == 200
    out: dict[str, float] = {}
    for line in r.text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name, _, value = line.rpartition(" ")
        name = name.split("{")[0].strip()
        try:
            out[name] = float(value)
        except ValueError:
            continue
    return out


def test_dedup_and_pipeline_counters_after_served_batch(server):
    url = server.url("/validate/pod-privileged")
    headers = {"Content-Type": "application/json"}
    # 1) cold: unique payload → full encode + dispatch (all misses)
    r = requests.post(url, data=_review_body("u-1", False),
                      headers=headers, timeout=30)
    assert r.status_code == 200
    # 2) exact replay (same uid, same payload): a webhook call is live, so
    # no blob tier (its uid is new on every real call) → ROW-tier hit
    r = requests.post(url, data=_review_body("u-1", False),
                      headers=headers, timeout=30)
    assert r.status_code == 200
    # 3) fresh uid, same pod spec → ROW-tier hit post-encode
    r = requests.post(url, data=_review_body("u-2", False),
                      headers=headers, timeout=30)
    assert r.status_code == 200
    time.sleep(0.1)  # let phase-3 bookkeeping settle

    env = server.server.environment
    dedup = env.dedup_stats
    profile = env.host_profile
    assert dedup["blob_cache_hits"] == dedup["blob_cache_misses"] == 0
    assert dedup["blob_bypassed_rows"] == 3
    assert dedup["cache_hits"] >= 2

    m = _scrape(server)
    # two-tier dedup counters, values matching the environment's own
    assert m["policy_server_dedup_blob_hits_total"] == dedup["blob_cache_hits"]
    assert (
        m["policy_server_dedup_blob_misses_total"]
        == dedup["blob_cache_misses"]
    )
    assert (
        m["policy_server_dedup_blob_bypassed_rows_total"]
        == dedup["blob_bypassed_rows"]
    )
    assert m["policy_server_verdict_cache_hits_total"] == dedup["cache_hits"]
    assert (
        m["policy_server_verdict_cache_misses_total"]
        == dedup["cache_misses"]
    )
    assert m["policy_server_batch_dedup_hits_total"] == dedup["batch_dup_hits"]
    assert (
        m["policy_server_verdict_cache_bytes"]
        == dedup["cache_bytes"] + dedup["blob_cache_bytes"]
    )
    # host-pipeline decomposition: encode ran for all three (no blob
    # tier before it); dispatch shipped at least one row
    assert m["policy_server_host_encode_rows_total"] == profile["encode_rows"]
    assert profile["encode_rows"] >= 3
    assert m["policy_server_dispatched_rows_total"] == profile["dispatched_rows"]
    assert profile["dispatched_rows"] >= 1
    assert m["policy_server_host_encode_seconds_total"] > 0
    assert m["policy_server_host_bookkeeping_seconds_total"] > 0
    assert m["policy_server_dispatch_wait_seconds_total"] > 0
    # routing counters exist (0 is fine — no budget pressure here)
    assert "policy_server_budget_routed_batches_total" in m
    assert "policy_server_host_fastpath_batches_total" in m
    # PR 40: the small batches a full pipeline sent to the device; none
    # here, three requests one after another never fill four slots
    assert m["policy_server_host_fastpath_declined_batches_total"] == 0
    # round-7 resilience surface: shedding / deadline drops / breaker /
    # degraded answers / fetch retries all scrape (zero on a healthy
    # server — the chaos suite moves them)
    assert m["policy_server_shed_requests_total"] == 0
    assert m["policy_server_expired_dropped_rows_total"] == 0
    assert m["policy_server_degraded_responses_total"] == 0
    assert m["policy_server_breaker_open_shards"] == 0
    assert "policy_server_breaker_trips_total" in m
    assert "policy_server_breaker_recoveries_total" in m
    assert "policy_server_breaker_short_circuited_requests_total" in m
    assert "policy_server_fetch_retry_attempts_total" in m
    assert "policy_server_fetch_retry_giveups_total" in m
    # round-9 policy-lifecycle surface: reload counters + epoch gauge
    # scrape (zero on a boot set; the lifecycle chaos tests move them)
    assert m["policy_server_policy_reloads_total"] == 0
    assert m["policy_server_policy_reload_failures_total"] == 0
    assert m["policy_server_policy_reload_rollbacks_total"] == 0
    assert m["policy_server_policy_epoch"] == 0
    assert "policy_server_reload_canary_replays_total" in m
    assert "policy_server_reload_canary_divergences_total" in m
    # round-10 audit surface: the families export on EVERY deployment
    # (zero with --audit-mode off, this server's state — the audit suite
    # moves them); freshness reads -1 before any full sweep
    assert m["policy_server_audit_rows_scanned_total"] == 0
    assert m["policy_server_audit_batches_dispatched_total"] == 0
    assert m["policy_server_audit_preemptions_total"] == 0
    assert m["policy_server_audit_lane_depth"] == 0
    assert m["policy_server_audit_report_freshness_seconds"] == -1
    assert m["policy_server_audit_reports_resident"] == 0
    assert m["policy_server_audit_reports_stale"] == 0
    assert m["policy_server_audit_snapshot_resources"] == 0
    assert m["policy_server_audit_snapshot_bytes"] == 0
    assert "policy_server_audit_full_sweeps_total" in m
    assert "policy_server_audit_dirty_sweeps_total" in m
    assert "policy_server_audit_sweep_errors_total" in m
    assert "policy_server_audit_paused_sweeps_total" in m


def test_launch_h2d_arrays_on_the_pull_endpoint(server):
    """One host array a launch (the columnar wire buffer), counted where
    the benchmark reads it: the family's value is the environment's own
    count, and every launch that shipped anything added exactly one."""
    env = server.server.environment
    before = env.host_profile
    r = requests.post(
        server.url("/validate/pod-privileged"),
        data=_review_body("u-h2d", True),  # a pod shape not served yet
        headers={"Content-Type": "application/json"}, timeout=30,
    )
    assert r.status_code == 200
    time.sleep(0.1)
    profile = env.host_profile
    launches = profile["dispatched_chunks"] - before["dispatched_chunks"]
    assert launches >= 1
    assert (
        profile["launch_h2d_arrays"] - before["launch_h2d_arrays"] == launches
    )
    m = _scrape(server)
    assert (
        m[metrics_mod.LAUNCH_H2D_ARRAYS + "_total"]
        == profile["launch_h2d_arrays"]
    )


def test_device_fragment_rows_on_the_pull_endpoint(server):
    """A dispatched row of a fragment-eligible target is answered from its
    template, counted where the benchmark reads it: one more for such a
    request the device answered, none for a tier's hit (that is
    ``fragment_hits``), and the family's value is the environment's own
    count."""
    env = server.server.environment
    assert env._frag_eligible(env._fast_target("pod-privileged"))

    def post(uid: str) -> tuple[int, int, int]:
        before = env.host_profile
        hits = env.dedup_stats["fragment_hits"]
        r = requests.post(
            server.url("/validate/pod-privileged"),
            data=_review_body(uid, True),
            headers={"Content-Type": "application/json"}, timeout=30,
        )
        assert r.status_code == 200
        assert r.json()["response"]["uid"] == uid
        time.sleep(0.1)
        after = env.host_profile
        return (
            after["dispatched_rows"] - before["dispatched_rows"],
            after["device_fragment_rows"] - before["device_fragment_rows"],
            env.dedup_stats["fragment_hits"] - hits,
        )

    env.reset_verdict_cache()
    assert post("u-frag-1") == (1, 1, 0)  # the device's row, spliced
    assert post("u-frag-2") == (0, 0, 1)  # the row tier's hit, spliced
    m = _scrape(server)
    assert (
        m[metrics_mod.DEVICE_FRAGMENT_ROWS]
        == env.host_profile["device_fragment_rows"]
        >= 1
    )


def test_launch_native_wire_on_the_pull_endpoint(server):
    """The launches whose wire buffer the encode call wrote, counted
    where the benchmark reads them: one more for a launch of a settled
    shape whose strings are all known, none for a launch that fell back
    (a string the encoder's mirror has not seen), and the family's value
    is the environment's own count."""
    env = server.server.environment

    def post(uid: str, image: str) -> tuple[int, int]:
        doc = json.loads(_review_body(uid, False))
        doc["request"]["object"]["spec"]["containers"][0]["image"] = image
        # every tier forgets: the same pod shape reaches the device again
        env.reset_verdict_cache()
        before = env.host_profile
        r = requests.post(
            server.url("/validate/latest-tag"), data=json.dumps(doc),
            headers={"Content-Type": "application/json"}, timeout=30,
        )
        assert r.status_code == 200
        deadline = time.monotonic() + 120
        while env.plane_programs_pending:  # a grown set compiles off-path
            assert time.monotonic() < deadline
            time.sleep(0.05)
        after = env.host_profile
        return (
            after["dispatched_chunks"] - before["dispatched_chunks"],
            after["launch_native_wire"] - before["launch_native_wire"],
        )

    assert post("u-wire-1", "registry.example/wire:1") == (1, 0)  # cold
    assert post("u-wire-2", "registry.example/wire:1")[0] == 1    # settles
    assert post("u-wire-3", "registry.example/wire:1") == (1, 1)
    assert post("u-wire-4", "registry.example/wire:2") == (1, 0)  # cold
    assert post("u-wire-5", "registry.example/wire:2") == (1, 1)
    m = _scrape(server)
    assert (
        m[metrics_mod.LAUNCH_NATIVE_WIRE + "_total"]
        == env.host_profile["launch_native_wire"]
        >= 2
    )


def test_the_dashboard_shows_native_wire_beside_h2d_arrays():
    from pathlib import Path

    dashboard = json.loads(
        (Path(__file__).parent.parent / "kubewarden-dashboard.json")
        .read_text()
    )
    panels = [
        [t["expr"] for t in p.get("targets", [])]
        for p in dashboard["panels"]
    ]
    launch = 'policy_server_phase_latency_seconds_count{phase="launch"}'
    both = [
        exprs for exprs in panels
        if any(metrics_mod.LAUNCH_H2D_ARRAYS + "_total" in e for e in exprs)
    ]
    assert len(both) == 1
    assert any(
        metrics_mod.LAUNCH_NATIVE_WIRE + "_total" in e and launch in e
        for e in both[0]
    )


def test_encode_python_strings_on_the_pull_endpoint(server):
    """The native encoder's mirror of the intern table, counted where the
    benchmark reads it: a string it has not seen is Python's once (the
    counter moves, the mirror grows), and the same string in a later
    request is the native call's (rows move, the counter does not)."""
    env = server.server.environment

    def post(uid: str, image: str) -> dict:
        doc = json.loads(_review_body(uid, False))
        doc["request"]["object"]["spec"]["containers"][0]["image"] = image
        r = requests.post(
            server.url("/validate/pod-privileged"), data=json.dumps(doc),
            headers={"Content-Type": "application/json"}, timeout=30,
        )
        assert r.status_code == 200
        time.sleep(0.1)
        return env.host_profile

    before = env.host_profile
    first = post("u-mirror-1", "registry.example/never-seen:1")
    again = post("u-mirror-2", "registry.example/never-seen:1")
    assert first["encode_rows"] > before["encode_rows"]
    assert again["encode_rows"] > first["encode_rows"]
    # met once, resolved in Python once, published once
    assert first["encode_python_strings"] > before["encode_python_strings"]
    assert (
        first["encode_mirror_entries"] == before["encode_mirror_entries"] + 1
    )
    assert again["encode_python_strings"] == first["encode_python_strings"]
    assert again["encode_mirror_entries"] == first["encode_mirror_entries"]
    m = _scrape(server)
    assert (
        m[metrics_mod.HOST_ENCODE_PYTHON_STRINGS]
        == again["encode_python_strings"]
    )
    assert (
        m[metrics_mod.HOST_ENCODE_MIRROR_ENTRIES]
        == again["encode_mirror_entries"]
    )


def test_counters_survive_otlp_conversion(server):
    """The OTLP pusher converts the SAME registry (one source of truth);
    the round-6 instruments must come through as monotonic sums/gauges."""
    pb = pytest.importorskip("policy_server_tpu.telemetry.otlp")
    from policy_server_tpu.telemetry import default_registry

    registry = default_registry().registry
    now = time.time_ns()
    metrics = pb.prometheus_to_otlp(registry, now - 10**9, now)
    names = {m.name for m in metrics}
    for expected in (
        metrics_mod.DEDUP_BLOB_HITS,
        metrics_mod.VERDICT_CACHE_HITS,
        metrics_mod.BATCH_DEDUP_HITS,
        metrics_mod.HOST_ENCODE_SECONDS,
        metrics_mod.DISPATCH_WAIT_SECONDS,
        metrics_mod.DISPATCHED_ROWS,
        metrics_mod.VERDICT_CACHE_BYTES,
        metrics_mod.POLICY_RELOADS,
        metrics_mod.POLICY_RELOAD_ROLLBACKS,
        metrics_mod.RELOAD_CANARY_REPLAYS,
        metrics_mod.POLICY_EPOCH,
        metrics_mod.AUDIT_ROWS_SCANNED,
        metrics_mod.AUDIT_PREEMPTIONS,
        metrics_mod.AUDIT_REPORT_FRESHNESS,
        metrics_mod.AUDIT_SNAPSHOT_BYTES,
    ):
        assert any(expected in n for n in names), (expected, names)
