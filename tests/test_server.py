"""HTTP integration tests — the analog of tests/integration_test.rs: a real
PolicyServer bound to port 0 (parallel-safe, tests/common/mod.rs:135-140),
driven over real sockets with `requests`. Covers accept/reject, groups with
causes, 404/422 mapping, raw validation + JSONPatch mutation, audit,
monitor mode, timeout protection, readiness, metrics, and pprof."""

from __future__ import annotations

import asyncio
import base64
import json
import threading
import time

import pytest
import requests

from policy_server_tpu.config.config import Config, TlsConfig
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.server import PolicyServer
from policy_server_tpu.telemetry import metrics as metrics_mod

from conftest import build_admission_review_dict


class ServerHandle:
    """Runs a PolicyServer inside a private event loop thread."""

    def __init__(self, config: Config):
        self.server = PolicyServer.new_from_config(config)
        self.loop = asyncio.new_event_loop()
        self._started = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()
        assert self._started.wait(timeout=60), "server failed to start"

    def _run(self) -> None:
        asyncio.set_event_loop(self.loop)
        self.loop.run_until_complete(self.server.start())
        self._started.set()
        self.loop.run_forever()

    def stop(self) -> None:
        async def _shutdown():
            await self.server.stop()
            self.loop.stop()

        asyncio.run_coroutine_threadsafe(_shutdown(), self.loop)
        self.thread.join(timeout=10)

    def url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.server.api_port}{path}"

    def readiness_url(self, path: str) -> str:
        return f"http://127.0.0.1:{self.server.readiness_port}{path}"


def make_config(**overrides) -> Config:
    policies = {
        "pod-privileged": parse_policy_entry(
            "pod-privileged", {"module": "builtin://pod-privileged"}
        ),
        "pod-privileged-monitor": parse_policy_entry(
            "pod-privileged-monitor",
            {"module": "builtin://pod-privileged", "policyMode": "monitor"},
        ),
        "raw-mutation": parse_policy_entry(
            "raw-mutation",
            {"module": "builtin://raw-mutation", "allowedToMutate": True},
        ),
        "sleeping": parse_policy_entry(
            "sleeping",
            {"module": "builtin://sleeping", "settings": {"sleep_ms": 1500}},
        ),
        "group": parse_policy_entry(
            "group",
            {
                "expression": "happy() && priv()",
                "message": "group rejected the request",
                "policies": {
                    "happy": {"module": "builtin://always-happy"},
                    "priv": {"module": "builtin://pod-privileged"},
                },
            },
        ),
    }
    defaults = dict(
        addr="127.0.0.1",
        port=0,
        readiness_probe_port=0,
        tls_config=TlsConfig(),
        policies=policies,
        policy_timeout_seconds=0.5,
        max_batch_size=8,
        batch_timeout_ms=1.0,
        enable_pprof=True,
        # Warmup is required with a tight deadline: the dispatch watchdog
        # bounds device execution, so an un-warmed bucket's compile stall
        # is (correctly) rejected as "execution deadline exceeded".
        warmup_at_boot=True,
    )
    defaults.update(overrides)
    return Config(**defaults)


@pytest.fixture(scope="module")
def server():
    metrics_mod.reset_metrics_for_tests()
    handle = ServerHandle(make_config())
    yield handle
    handle.stop()


def pod_review_body(privileged: bool) -> dict:
    doc = build_admission_review_dict()
    doc["request"]["object"] = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": "p", "namespace": "default"},
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
    return doc


def test_validate_accept_and_reject(server):
    r = requests.post(
        server.url("/validate/pod-privileged"), json=pod_review_body(False),
        timeout=30,
    )
    assert r.status_code == 200
    body = r.json()
    assert body["apiVersion"] == "admission.k8s.io/v1"
    assert body["kind"] == "AdmissionReview"
    assert body["response"]["allowed"] is True
    assert body["response"]["uid"] == "hello"

    r = requests.post(
        server.url("/validate/pod-privileged"), json=pod_review_body(True),
        timeout=30,
    )
    assert r.status_code == 200
    resp = r.json()["response"]
    assert resp["allowed"] is False
    assert resp["status"]["message"] == "Privileged container is not allowed"


def test_validate_policy_group_with_causes(server):
    r = requests.post(
        server.url("/validate/group"), json=pod_review_body(True), timeout=30
    )
    assert r.status_code == 200
    resp = r.json()["response"]
    assert resp["allowed"] is False
    assert resp["status"]["message"] == "group rejected the request"
    causes = resp["status"]["details"]["causes"]
    assert causes == [
        {
            "field": "spec.policies.priv",
            "message": "Privileged container is not allowed",
        }
    ]

    r = requests.post(
        server.url("/validate/group"), json=pod_review_body(False), timeout=30
    )
    assert r.json()["response"]["allowed"] is True


def test_unknown_policy_404(server):
    r = requests.post(
        server.url("/validate/does-not-exist"), json=pod_review_body(False),
        timeout=30,
    )
    assert r.status_code == 404
    assert "does-not-exist" in r.json()["message"]
    assert r.json()["status"] == 404


def test_malformed_body_422(server):
    r = requests.post(
        server.url("/validate/pod-privileged"),
        data=b"this is not json",
        headers={"Content-Type": "application/json"},
        timeout=30,
    )
    assert r.status_code == 422

    r = requests.post(
        server.url("/validate/pod-privileged"), json={"no_request": 1},
        timeout=30,
    )
    assert r.status_code == 422


def test_validate_raw_mutation(server):
    r = requests.post(
        server.url("/validate_raw/raw-mutation"),
        json={"request": {"uid": "raw-1", "user": "alice"}},
        timeout=30,
    )
    assert r.status_code == 200
    resp = r.json()["response"]
    assert resp["allowed"] is True
    patch = json.loads(base64.b64decode(resp["patch"]))
    assert patch == [{"op": "add", "path": "/validated", "value": True}]
    assert resp["patchType"] == "JSONPatch"

    r = requests.post(
        server.url("/validate_raw/raw-mutation"),
        json={"request": {"uid": "raw-2", "forbidden": True}},
        timeout=30,
    )
    resp = r.json()["response"]
    assert resp["allowed"] is False
    assert resp["status"]["message"] == "the request is forbidden"


def test_audit_reports_raw_verdict(server):
    r = requests.post(
        server.url("/audit/pod-privileged-monitor"), json=pod_review_body(True),
        timeout=30,
    )
    assert r.status_code == 200
    assert r.json()["response"]["allowed"] is False


def test_monitor_mode_allows_via_http(server):
    r = requests.post(
        server.url("/validate/pod-privileged-monitor"),
        json=pod_review_body(True),
        timeout=30,
    )
    assert r.status_code == 200
    assert r.json()["response"]["allowed"] is True


def test_timeout_protection(server):
    """integration_test.rs:367-423: the sleeping policy exceeds the 0.5 s
    deadline → in-band 500 'execution deadline exceeded'."""
    r = requests.post(
        server.url("/validate/sleeping"), json=pod_review_body(False),
        timeout=30,
    )
    assert r.status_code == 200
    resp = r.json()["response"]
    assert resp["allowed"] is False
    assert resp["status"]["message"] == "execution deadline exceeded"
    assert resp["status"]["code"] == 500


def test_readiness_and_metrics(server):
    r = requests.get(server.readiness_url("/readiness"), timeout=10)
    assert r.status_code == 200
    r = requests.get(server.readiness_url("/metrics"), timeout=10)
    assert r.status_code == 200
    assert "kubewarden_policy_evaluations_total" in r.text
    # serving-runtime introspection gauges ride the same exposition
    assert "policy_server_batches_dispatched_total" in r.text
    assert "policy_server_queue_depth" in r.text
    assert "policy_server_oracle_fallbacks_total" in r.text


def test_debug_timeline_serves_live_trace(server):
    """GET /debug/timeline (round 18): a Perfetto-loadable Chrome trace
    for live traffic — batch phase slices, metadata track names, and
    the exemplar table — on the readiness port AND the python-frontend
    API port; the per-phase histogram rides /metrics."""
    doc = build_admission_review_dict()
    for _ in range(8):
        requests.post(
            server.url("/validate/pod-privileged"), json=doc, timeout=10
        )
    for url in (
        server.readiness_url("/debug/timeline"),
        server.url("/debug/timeline"),
    ):
        r = requests.get(url, timeout=10)
        assert r.status_code == 200
        trace = r.json()
        slices = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert slices, "no phase slices for a live burst"
        phases = {e["name"] for e in slices}
        assert {"queue_wait", "form", "dispatch", "deliver"} <= phases
        assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in slices)
        assert isinstance(trace["exemplars"], list)
    m = requests.get(server.readiness_url("/metrics"), timeout=10).text
    assert "policy_server_phase_latency_seconds_bucket" in m
    assert "policy_server_flight_recorder_events_total" in m
    assert "policy_server_tail_exemplar_latency_seconds" in m


def test_debug_timeline_returns_only_the_interval_asked_for(server):
    """?since_ns=&until_ns= (PR 27): only events that overlap the
    interval, on the clock of the events' own ``ts``."""
    doc = build_admission_review_dict()
    requests.post(server.url("/validate/pod-privileged"), json=doc, timeout=10)
    t_mid = time.perf_counter_ns()
    time.sleep(0.01)
    requests.post(server.url("/validate/pod-privileged"), json=doc, timeout=10)
    url = server.readiness_url("/debug/timeline")

    def slices(query: str) -> list:
        r = requests.get(url + query, timeout=10)
        assert r.status_code == 200
        return [e for e in r.json()["traceEvents"] if e["ph"] == "X"]

    everything = slices("")
    late = slices(f"?since_ns={t_mid}")
    early = slices(f"?until_ns={t_mid}")
    assert late and early
    assert len(late) < len(everything) and len(early) < len(everything)
    assert all((e["ts"] + e["dur"]) * 1e3 >= t_mid - 1 for e in late)
    assert all(e["ts"] * 1e3 <= t_mid + 1 for e in early)
    assert slices(f"?since_ns={t_mid}&until_ns={t_mid - 10**9}") == []
    assert "batch" in late[0]["args"]
    r = requests.get(url + "?since_ns=yesterday", timeout=10)
    assert r.status_code == 422


def test_collector_and_encode_cpu_families_ride_metrics(server):
    """PR 27: the recorder's gc hook and encode's CPU clock on /metrics."""
    import gc

    def families() -> dict:
        text = requests.get(server.readiness_url("/metrics"), timeout=10).text
        return {
            line.rsplit(" ", 1)[0]: float(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith(("policy_server_gc_", "policy_server_host_encode_"))
        }

    before = families()
    gc.collect()
    after = families()
    for g in "012":
        assert f'policy_server_gc_passes_total{{generation="{g}"}}' in after
        assert f'policy_server_gc_pause_seconds_total{{generation="{g}"}}' in after
    full = '{generation="2"}'
    assert after["policy_server_gc_passes_total" + full] >= \
        before["policy_server_gc_passes_total" + full] + 1
    assert after["policy_server_gc_pause_seconds_total" + full] > \
        before["policy_server_gc_pause_seconds_total" + full]
    assert after["policy_server_host_encode_cpu_seconds_total"] <= \
        after["policy_server_host_encode_seconds_total"] + 1e-6


def test_pprof_trace_returns_an_xplane(server):
    """GET /debug/pprof/trace (PR 27): the process's jax.profiler trace as
    an .xplane.pb, on the API port and, beside /debug/timeline, on the
    readiness port; single-flight; seconds bounded."""
    from jax.profiler import ProfileData

    for url in (server.url("/debug/pprof/trace"),
                server.readiness_url("/debug/pprof/trace")):
        r = requests.get(url + "?seconds=0.2", timeout=60)
        assert r.status_code == 200
        planes = [p.name for p in
                  ProfileData.from_serialized_xspace(r.content).planes]
        assert any(name.startswith("/host:") for name in planes), planes
    for bad in ("?seconds=0", "?seconds=3600", "?seconds=soon"):
        r = requests.get(server.url("/debug/pprof/trace") + bad, timeout=10)
        assert r.status_code == 422, bad
    from policy_server_tpu.api import profiling

    assert profiling._trace_lock.acquire(blocking=False)
    try:  # a trace is being taken: the next caller is told so, at once
        r = requests.get(server.url("/debug/pprof/trace?seconds=0.2"),
                         timeout=10)
        assert r.status_code == 409
    finally:
        profiling._trace_lock.release()


def test_pprof_endpoints(server):
    r = requests.get(server.url("/debug/pprof/cpu?interval=0.05"), timeout=30)
    assert r.status_code == 200 and len(r.content) > 0
    r = requests.get(server.url("/debug/pprof/heap"), timeout=30)
    assert r.status_code == 200
    doc = r.json()
    assert "devices" in doc and len(doc["devices"]) >= 1


# ---------------------------------------------------------------------------
# Policy lifecycle over HTTP (round 9): admin auth, SIGHUP, readiness,
# worker-respawn epoch coherence
# ---------------------------------------------------------------------------


def test_admin_endpoints_disabled_without_token(server):
    """The lifecycle manager is wired (default --policy-reload-mode auto)
    but no --reload-admin-token is configured: every admin endpoint is a
    403, token or not."""
    for path in ("/policies/reload", "/policies/promote",
                 "/policies/rollback"):
        r = requests.post(server.readiness_url(path), timeout=10)
        assert r.status_code == 403, path
        r = requests.post(
            server.readiness_url(path),
            headers={"Authorization": "Bearer guess"}, timeout=10,
        )
        assert r.status_code == 403, path


def test_sighup_drives_policy_reload(server):
    """The SIGHUP contract: one handler (reload_signal) drives the policy
    reload (and the cert reload when TLS is on). The reload runs in the
    background; readiness stays 200 on last-good throughout, and the
    epoch advances on promotion."""
    lifecycle = server.server.lifecycle
    assert lifecycle is not None
    before = lifecycle.stats()["reloads"]
    server.server.reload_signal()
    deadline = time.time() + 120
    while time.time() < deadline:
        if lifecycle.stats()["reloads"] > before:
            break
        r = requests.get(server.readiness_url("/readiness"), timeout=10)
        assert r.status_code == 200  # last-good stays ready mid-reload
        time.sleep(0.2)
    stats = lifecycle.stats()
    assert stats["reloads"] == before + 1
    assert stats["reload_failures"] == 0
    # the promoted epoch serves the same set bit-exactly
    r = requests.post(
        server.url("/validate/pod-privileged"), json=pod_review_body(True),
        timeout=30,
    )
    assert r.status_code == 200
    assert r.json()["response"]["allowed"] is False


def test_run_async_signal_registration_safe_off_main_thread():
    """run_async registers SIGTERM/SIGINT/SIGHUP through the event loop;
    on a non-main thread that raises, and the guard must swallow it —
    the server serves anyway (admin endpoint + watcher still drive
    reloads)."""
    import asyncio as aio

    server = PolicyServer.new_from_config(
        make_config(policies={
            "pod-privileged": parse_policy_entry(
                "pod-privileged", {"module": "builtin://pod-privileged"}
            ),
        })
    )
    loop = aio.new_event_loop()
    task_box: dict = {}

    def run() -> None:
        aio.set_event_loop(loop)
        task_box["task"] = loop.create_task(server.run_async())
        try:
            loop.run_until_complete(task_box["task"])
        except aio.CancelledError:
            pass

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    try:
        deadline = time.time() + 60
        while time.time() < deadline and server.api_port is None:
            time.sleep(0.05)
        assert server.api_port is not None, "server failed to start"
        r = requests.post(
            f"http://127.0.0.1:{server.api_port}/validate/pod-privileged",
            json=pod_review_body(False), timeout=30,
        )
        assert r.status_code == 200
    finally:
        loop.call_soon_threadsafe(task_box["task"].cancel)
        thread.join(timeout=30)
    assert not thread.is_alive(), "run_async did not stop after cancel"


def test_worker_respawn_serves_promoted_epoch():
    """Satellite: a prefork frontend worker that dies and respawns
    mid-swap must come back serving the PROMOTED epoch, never the
    retired one — workers are stateless (they bridge to the evaluation
    process, whose epoch pointer the reload flips), and the respawned
    worker must inherit that. Also covers the authenticated admin
    reload endpoint (202 + bearer token)."""
    policies = {
        "pod-privileged": parse_policy_entry(
            "pod-privileged", {"module": "builtin://pod-privileged"}
        ),
    }
    handle = ServerHandle(make_config(
        policies=policies,
        http_workers=3,
        policy_timeout_seconds=5.0,
        reload_admin_token="resp-token",
    ))
    try:
        # wait for the worker processes to bind the shared port
        deadline = time.time() + 30
        while (
            time.time() < deadline
            and len(handle.server._worker_procs) < 2
        ):
            time.sleep(0.05)
        assert len(handle.server._worker_procs) == 2

        # promote a new epoch that ADDS a policy, via the authenticated
        # admin endpoint (the HTTP trigger is async: poll the epoch)
        new_policies = dict(policies)
        new_policies["happy"] = parse_policy_entry(
            "happy", {"module": "builtin://always-happy"}
        )
        lifecycle = handle.server.lifecycle
        # kill a worker, then promote while its slot is respawning — the
        # respawn must come back on the promoted epoch
        victim = handle.server._worker_procs[0]
        victim.kill()
        r = requests.post(
            handle.readiness_url("/policies/reload"),
            headers={"Authorization": "Bearer resp-token"}, timeout=10,
        )
        assert r.status_code == 202  # trigger accepted (coalesced reload)
        # drive the actual swap deterministically with the new set
        assert lifecycle.reload(policies=new_policies) == "promoted"
        assert lifecycle.stats()["epoch"] >= 1

        # wait until the killed slot respawned (supervise interval 2 s)
        deadline = time.time() + 30
        while time.time() < deadline:
            procs = handle.server._worker_procs
            if all(p is not None and p.poll() is None for p in procs):
                break
            time.sleep(0.1)
        procs = handle.server._worker_procs
        assert all(p is not None and p.poll() is None for p in procs), (
            "worker was not respawned"
        )

        # every process behind the SO_REUSEPORT pool — the survivor, the
        # main process, and the RESPAWNED worker — must serve the
        # promoted epoch: the new policy answers on every connection
        for i in range(20):
            r = requests.post(
                handle.url("/validate/happy"), json=pod_review_body(False),
                timeout=30,
            )
            assert r.status_code == 200, (i, r.status_code, r.text)
            assert r.json()["response"]["allowed"] is True
        # and the retired epoch's set still answers bit-exactly too
        r = requests.post(
            handle.url("/validate/pod-privileged"),
            json=pod_review_body(True), timeout=30,
        )
        assert r.json()["response"]["allowed"] is False
    finally:
        handle.stop()
