"""Overload & failure resilience chaos suite (``make chaos``).

Fault injection comes from policy_server_tpu.failpoints — the same sites
production code carries (device fetch, batch encode, registry HTTP, cert
reload) — so every scenario here exercises the REAL serving path, not a
mock of it. The contract under test, end to end:

* load shedding: admission rejects (429 + Retry-After) when the queue's
  estimated wait exceeds the propagated request deadline;
* no dead work: rows whose deadline passed while queued are dropped
  BEFORE encode/dispatch (504 in-band, counted, encoder untouched);
* device circuit breaker: repeated dispatch faults / watchdog trips trip
  a shard to the bit-exact host-oracle fallback (correct verdicts, no
  hangs), half-open probes recover it when the fault clears;
* --degraded-mode: a fully-tripped breaker serves monitor-mode verdicts
  or in-band 503s instead of evaluating;
* fetch retry: transient registry 5xx/timeouts retry with capped
  backoff + jitter; deterministic failures do not;
* shutdown under load: graceful drain with hung in-flight batches plus
  queued requests completes within the drain deadline, shedding the
  remainder with 503 — never hanging.
"""

from __future__ import annotations

import threading
import time

import pytest

from policy_server_tpu import failpoints
from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.evaluation.environment import (
    EvaluationEnvironmentBuilder,
    bucket_size,
)
from policy_server_tpu.models import AdmissionReviewRequest, ValidateRequest
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.resilience import CircuitBreaker, retry_with_backoff
from policy_server_tpu.runtime.batcher import (
    DEADLINE_MESSAGE,
    DEGRADED_MESSAGE,
    EXPIRED_MESSAGE,
    MicroBatcher,
    ShedError,
)
from policy_server_tpu.telemetry import metrics as metrics_mod

from conftest import build_admission_review_dict


@pytest.fixture(autouse=True)
def clean_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


@pytest.fixture(autouse=True)
def fresh_metrics():
    metrics_mod.reset_metrics_for_tests()
    yield
    metrics_mod.reset_metrics_for_tests()


def review(namespace: str | None = None) -> ValidateRequest:
    doc = build_admission_review_dict()
    if namespace is not None:
        doc["request"]["namespace"] = namespace
    return ValidateRequest.from_admission(
        AdmissionReviewRequest.from_dict(doc).request
    )


def make_env(**breaker_overrides):
    breaker_config = dict(
        failure_threshold=2, window_seconds=10.0, cooldown_seconds=0.3
    )
    breaker_config.update(breaker_overrides)
    # verdict cache OFF: a cache hit would answer a half-open probe's
    # batch without touching the device, leaving the probe outcome-less
    # (recovery then waits for a cache-missing row — correct but slow,
    # and nondeterministic in a test)
    return EvaluationEnvironmentBuilder(
        backend="jax", breaker_config=breaker_config, verdict_cache_size=0
    ).build(
        {
            "ns": parse_policy_entry(
                "ns",
                {
                    "module": "builtin://namespace-validate",
                    "settings": {"denied_namespaces": ["blocked"]},
                },
            )
        }
    )


# ---------------------------------------------------------------------------
# Circuit breaker unit behavior
# ---------------------------------------------------------------------------


def test_breaker_state_machine():
    clock = {"t": 0.0}
    b = CircuitBreaker(
        failure_threshold=3, window_seconds=5.0, cooldown_seconds=2.0,
        clock=lambda: clock["t"],
    )
    assert b.allow_device()
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"  # under threshold
    # failures outside the window age out
    clock["t"] = 6.0
    b.record_failure()
    assert b.state == "closed"
    b.record_failure()
    b.record_failure()
    assert b.state == "open" and b.trips == 1
    assert not b.allow_device()
    assert b.short_circuits == 1
    # cooldown elapses → half-open admits ONE probe, denies the second
    clock["t"] = 8.5
    assert b.allow_device()
    assert b.state == "half_open" and b.probes == 1
    assert not b.allow_device()
    # probe failure → straight back to open, fresh cooldown
    b.record_failure()
    assert b.state == "open" and b.trips == 2
    clock["t"] = 11.0
    assert b.allow_device()
    b.record_success()
    assert b.state == "closed" and b.recoveries == 1
    # late failures from abandoned work while open change nothing
    b.record_failure()
    b.record_failure()
    b.record_failure()
    assert b.state == "open"
    b.record_success()  # late success from abandoned work: no-op
    assert b.state == "open"


# ---------------------------------------------------------------------------
# Breaker on the environment's dispatch path (fault injection)
# ---------------------------------------------------------------------------


def test_breaker_trips_to_oracle_and_recovers():
    """Injected dispatch faults trip the environment's breaker; tripped
    traffic serves CORRECT verdicts from the host oracle; clearing the
    fault + cooldown recovers via a half-open probe."""
    env = make_env()
    try:
        env.warmup((1, 4))
        allowed = [("ns", review())]
        denied = [("ns", review(namespace="blocked"))]

        failpoints.configure("device.fetch=raise:injected-dispatch-fault")
        for _ in range(2):
            with pytest.raises(failpoints.FailpointError):
                env.validate_batch(allowed)
        stats = env.breaker_stats
        assert stats["trips"] == 1 and stats["open_shards"] == 1

        # tripped: host oracle answers, bit-exact — and the still-armed
        # failpoint proves the device path is never touched
        out = env.validate_batch(allowed + denied)
        assert out[0].allowed is True
        assert out[1].allowed is False
        assert env.breaker_stats["short_circuited_requests"] >= 2

        # fault clears → cooldown → half-open probe → recovery
        failpoints.clear()
        time.sleep(0.35)
        out = env.validate_batch(allowed)
        assert out[0].allowed is True
        stats = env.breaker_stats
        assert stats["recoveries"] == 1 and stats["open_shards"] == 0
        assert stats["probes"] >= 1
    finally:
        env.close()


def test_breaker_hung_shard_watchdog_trips_degrades_and_recovers():
    """The acceptance scenario end to end: a HUNG device shard (fetch
    never returns) is bounded by the dispatch watchdog, N trips open the
    breaker, traffic degrades to the oracle path (correct verdicts, no
    request ever hangs), and the shard recovers via a half-open probe
    once the fault clears — all visible in the exported counters."""
    env = make_env(cooldown_seconds=0.5)
    env.warmup((1, 4))
    release = threading.Event()
    # first two fetches hang (bounded by release's own timeout so the
    # abandoned daemon threads unwedge after the test)
    failpoints.set_failpoint(
        "device.fetch", lambda: release.wait(timeout=30), count=2
    )
    batcher = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=0.4,
        host_fastpath_threshold=0, latency_budget_ms=0,
    ).start()
    try:
        for expected_trips in (0, 1):
            t0 = time.perf_counter()
            resp = batcher.submit(
                "ns", review(), RequestOrigin.VALIDATE
            ).result(timeout=5)
            assert resp.status.code == 500
            assert DEADLINE_MESSAGE in resp.status.message
            assert time.perf_counter() - t0 < 3.0  # watchdog, not the hang
            assert env.breaker_stats["trips"] == expected_trips

        assert env.breaker_stats["open_shards"] == 1
        # degraded-but-correct: the oracle path answers instantly while
        # the breaker is open; a denied namespace still denies
        t0 = time.perf_counter()
        ok = batcher.submit("ns", review(), RequestOrigin.VALIDATE)
        bad = batcher.submit(
            "ns", review(namespace="blocked"), RequestOrigin.VALIDATE
        )
        assert ok.result(timeout=5).allowed is True
        assert bad.result(timeout=5).allowed is False
        assert time.perf_counter() - t0 < 2.0

        # fault cleared (count exhausted) → probe recovers the shard
        time.sleep(0.6)
        resp = batcher.submit(
            "ns", review(), RequestOrigin.VALIDATE
        ).result(timeout=10)
        assert resp.allowed is True
        stats = env.breaker_stats
        assert stats["recoveries"] == 1 and stats["open_shards"] == 0
    finally:
        release.set()
        batcher.shutdown()
        env.close()


def test_degraded_mode_reject_and_monitor():
    """Tripped-everything behavior per --degraded-mode: 'reject' answers
    in-band 503s, 'monitor' serves accept-all monitor verdicts; the
    default 'oracle' path (previous tests) keeps real verdicts."""
    env = make_env(failure_threshold=1, cooldown_seconds=60.0)
    env.warmup((1,))
    env.breaker.record_failure()  # trip: stays open for the whole test
    assert env.breaker_all_open

    batcher = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=2.0,
        host_fastpath_threshold=0, latency_budget_ms=0,
        degraded_mode="reject",
    ).start()
    try:
        resp = batcher.submit(
            "ns", review(), RequestOrigin.VALIDATE
        ).result(timeout=5)
        assert resp.allowed is False
        assert resp.status.code == 503
        assert DEGRADED_MESSAGE in resp.status.message
        assert batcher.degraded_responses == 1
    finally:
        batcher.shutdown()

    monitor = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=2.0,
        host_fastpath_threshold=0, latency_budget_ms=0,
        degraded_mode="monitor",
    ).start()
    try:
        resp = monitor.submit(
            "ns", review(namespace="blocked"), RequestOrigin.VALIDATE
        ).result(timeout=5)
        assert resp.allowed is True  # monitor mode: accept, log, count
        assert resp.status is None
        assert monitor.degraded_responses == 1
    finally:
        monitor.shutdown()
        env.close()


def test_degraded_mode_recovers_after_fault_clears():
    """The degraded gate must not wedge: once the cooldown makes a probe
    due, breaker_all_open flips false, the batch proceeds to the normal
    dispatch path, allow_device() runs the half-open probe, and a
    healthy device closes the breaker — real verdicts resume (a gate
    keyed on raw open-ness would serve monitor verdicts forever)."""
    env = make_env(failure_threshold=1, cooldown_seconds=0.3)
    env.warmup((1,))
    env.breaker.record_failure()  # trip
    assert env.breaker_all_open
    batcher = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=2.0,
        host_fastpath_threshold=0, latency_budget_ms=0,
        degraded_mode="monitor",
    ).start()
    try:
        # while cooling: monitor-mode accept-all (even a denied namespace)
        resp = batcher.submit(
            "ns", review(namespace="blocked"), RequestOrigin.VALIDATE
        ).result(timeout=5)
        assert resp.allowed is True and resp.status is None
        assert batcher.degraded_responses == 1

        time.sleep(0.35)  # cooldown elapses → probe due → gate opens
        resp = batcher.submit(
            "ns", review(namespace="blocked"), RequestOrigin.VALIDATE
        ).result(timeout=10)
        assert resp.allowed is False  # REAL verdict again
        stats = env.breaker_stats
        assert stats["recoveries"] == 1 and stats["open_shards"] == 0
    finally:
        batcher.shutdown()
        env.close()


def test_queue_aged_expiry_does_not_trip_breaker():
    """A watchdog abandonment caused by QUEUE AGE (items near their
    evaluation deadline before dispatch even starts) must not mark the
    device breaker: the device is healthy, the queue is the problem, and
    tripping would flip overload onto the slower host path."""
    env = make_env(failure_threshold=1, cooldown_seconds=60.0)
    env.warmup((1, 8))
    failpoints.set_failpoint("device.fetch", lambda: time.sleep(0.5))
    batcher = MicroBatcher(  # not started: items age in the queue first
        env, max_batch_size=8, batch_timeout_ms=1.0, policy_timeout=1.0,
        host_fastpath_threshold=0, latency_budget_ms=0,
    )
    try:
        futs = [
            batcher.submit("ns", review(), RequestOrigin.VALIDATE)
            for _ in range(3)
        ]
        time.sleep(0.7)  # ~0.3s of deadline left when dispatch starts
        batcher.start()
        for fut in futs:
            resp = fut.result(timeout=5)
            assert DEADLINE_MESSAGE in resp.status.message
        # the watchdog DID abandon the batch...
        assert batcher.deadline_abandoned_batches >= 1
        # ...but the short device wait is not attributed as a hang
        # (threshold-1 breaker: one false mark would trip it)
        assert env.breaker_stats["open_shards"] == 0
        assert env.breaker_stats["trips"] == 0
    finally:
        batcher.shutdown()
        env.close()


# ---------------------------------------------------------------------------
# Load shedding + deadline propagation
# ---------------------------------------------------------------------------


def test_admission_sheds_when_estimated_wait_exceeds_budget():
    """With a measured device RTT on record and a deep queue, a request
    whose deadline cannot be met is rejected at ADMISSION with ShedError
    (→ HTTP 429 + Retry-After) instead of queueing doomed work."""
    env = make_env()
    batcher = MicroBatcher(  # deliberately NOT started: the queue holds
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=5.0,
        request_timeout_ms=50.0,
    )
    try:
        # teach the estimator a slow device: 1 s per max-size batch
        batcher._dev_rtt[bucket_size(4)] = 1.0
        fut = batcher.submit("ns", review(), RequestOrigin.VALIDATE)
        with pytest.raises(ShedError) as exc:
            batcher.submit("ns", review(), RequestOrigin.VALIDATE)
        assert exc.value.retry_after_seconds > 0.05
        assert batcher.shed_requests == 1
        assert not fut.done()  # the admitted request is still queued
    finally:
        batcher.shutdown()
    # shutdown resolved the admitted-but-unserved request in-band
    assert fut.result(timeout=1).status.code == 503


def test_expired_rows_dropped_pre_encode_no_dead_work():
    """Rows whose propagated deadline passed while queued are dropped
    BEFORE encode/dispatch: counted, answered 504 in-band, and the
    encoder never sees them; fresh traffic on the same batcher is
    unaffected (no dead work, no contamination)."""
    env = make_env()
    env.warmup((1, 8))
    batcher = MicroBatcher(  # not started yet: requests age in the queue
        env, max_batch_size=8, batch_timeout_ms=1.0, policy_timeout=5.0,
        request_timeout_ms=100.0,
        # device path only: the encoder-rows assertions below are the
        # whole point, and the host fast-path would bypass the encoder
        host_fastpath_threshold=0, latency_budget_ms=0,
    )
    try:
        futs = [
            batcher.submit("ns", review(), RequestOrigin.VALIDATE)
            for _ in range(5)
        ]
        time.sleep(0.25)  # every deadline (100 ms) is now past
        encode_rows_before = env.host_profile["encode_rows"]
        batcher.start()
        for fut in futs:
            resp = fut.result(timeout=5)
            assert resp.status.code == 504
            assert EXPIRED_MESSAGE in resp.status.message
        assert batcher.expired_dropped == 5
        # pre-encode is the whole point: the encoder saw none of them
        assert env.host_profile["encode_rows"] == encode_rows_before

        # the unexpired stream is unaffected
        resp = batcher.submit(
            "ns", review(), RequestOrigin.VALIDATE
        ).result(timeout=10)
        assert resp.allowed is True
        assert env.host_profile["encode_rows"] > encode_rows_before
    finally:
        batcher.shutdown()
        env.close()


def test_request_timeout_disabled_keeps_legacy_behavior():
    """request_timeout_ms=0 (or unset) disables deadlines and shedding:
    no ShedError, no expired drops — the pre-round-7 contract."""
    env = make_env()
    env.warmup((1,))
    batcher = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=5.0,
    )
    try:
        batcher._dev_rtt[bucket_size(4)] = 100.0  # absurdly slow device
        batcher.start()
        resp = batcher.submit(
            "ns", review(), RequestOrigin.VALIDATE
        ).result(timeout=10)
        assert resp.allowed is True
        assert batcher.shed_requests == 0
        assert batcher.expired_dropped == 0
    finally:
        batcher.shutdown()
        env.close()


def test_shed_error_maps_to_http_429_with_retry_after():
    """The HTTP contract for shedding: 429, a Retry-After header, and
    retry_after_seconds in the body (the body copy is what prefork
    workers use to reconstruct the header across the bridge frame)."""
    import asyncio
    import json

    from policy_server_tpu.api import handlers
    from policy_server_tpu.runtime import frontend

    class FakeBatcher:
        async def submit_async(self, *args):
            raise ShedError(2.3)

    resp = asyncio.run(
        handlers._evaluate(
            FakeBatcher(), "ns", review(), RequestOrigin.VALIDATE
        )
    )
    assert resp.status == 429
    assert resp.headers["Retry-After"] == "3"  # ceil(2.3)
    body = json.loads(resp.body)
    assert body["retry_after_seconds"] == 3
    # worker-side header reconstruction from the bridge frame's body
    assert frontend._shed_headers(429, resp.body) == {"Retry-After": "3"}
    assert frontend._shed_headers(200, b"{}") is None


# ---------------------------------------------------------------------------
# Encoder fault containment
# ---------------------------------------------------------------------------


def test_encoder_fault_is_contained_and_next_request_serves():
    """An injected encoder error fails its own batch in-band (the future
    raises; the HTTP layer maps it to a JSON 500) and the NEXT request
    is served normally — one poisoned batch never wedges the pipeline."""
    env = make_env()
    env.warmup((1,))
    batcher = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=2.0,
        host_fastpath_threshold=0, latency_budget_ms=0,
    ).start()
    try:
        failpoints.configure("encode.batch=raise:injected-encoder-fault*1")
        fut = batcher.submit("ns", review(), RequestOrigin.VALIDATE)
        with pytest.raises(failpoints.FailpointError):
            fut.result(timeout=5)
        assert failpoints.fired_count("encode.batch") == 1
        resp = batcher.submit(
            "ns", review(), RequestOrigin.VALIDATE
        ).result(timeout=10)
        assert resp.allowed is True
    finally:
        batcher.shutdown()
        env.close()


# ---------------------------------------------------------------------------
# Fetch retry / backoff
# ---------------------------------------------------------------------------


class _Resp:
    def __init__(self, code: int, content: bytes = b"x"):
        self.status_code = code
        self.content = content


def test_fetch_retries_transient_5xx_then_succeeds(monkeypatch):
    from policy_server_tpu.fetch import downloader as dl

    calls = {"n": 0}

    def fake_get(url, **kw):
        calls["n"] += 1
        return _Resp(503) if calls["n"] < 3 else _Resp(200, b"payload")

    monkeypatch.setattr(dl.requests, "get", fake_get)
    sleeps: list[float] = []
    d = dl.Downloader(
        retry_attempts=4, retry_base_seconds=0.01, retry_cap_seconds=0.05,
        retry_sleep=sleeps.append,
    )
    before = dl.retry_stats()["attempts"]
    out = d._http_get("https://registry.example/p.wasm", "registry.example")
    assert out == b"payload"
    assert calls["n"] == 3 and len(sleeps) == 2
    assert all(0 <= s <= 0.05 for s in sleeps)  # capped, jittered
    assert dl.retry_stats()["attempts"] == before + 2


def test_fetch_retry_budget_exhausts_with_fetch_error(monkeypatch):
    from policy_server_tpu.fetch import downloader as dl

    calls = {"n": 0}
    monkeypatch.setattr(
        dl.requests, "get",
        lambda url, **kw: (calls.__setitem__("n", calls["n"] + 1), _Resp(503))[1],
    )
    d = dl.Downloader(
        retry_attempts=2, retry_base_seconds=0.0, retry_sleep=lambda s: None
    )
    with pytest.raises(dl.FetchError, match="HTTP 503"):
        d._http_get("https://registry.example/p.wasm", "registry.example")
    assert calls["n"] == 2
    assert dl.retry_stats()["giveups"] >= 1


def test_fetch_deterministic_failures_do_not_retry(monkeypatch):
    from policy_server_tpu.fetch import downloader as dl

    calls = {"n": 0}
    monkeypatch.setattr(
        dl.requests, "get",
        lambda url, **kw: (calls.__setitem__("n", calls["n"] + 1), _Resp(404))[1],
    )
    d = dl.Downloader(retry_attempts=4, retry_sleep=lambda s: None)
    with pytest.raises(dl.FetchError, match="HTTP 404"):
        d._http_get("https://registry.example/p.wasm", "registry.example")
    assert calls["n"] == 1  # a 404 is deterministic: one attempt only


def test_fetch_failpoint_injected_5xx_retries(monkeypatch):
    """The chaos-harness shape: a failpoint injects registry faults for
    the first two attempts; the retry policy rides them out."""
    from policy_server_tpu.fetch import downloader as dl

    monkeypatch.setattr(dl.requests, "get", lambda url, **kw: _Resp(200, b"ok"))
    failpoints.configure("fetch.http=raise:injected-registry-5xx*2")
    d = dl.Downloader(
        retry_attempts=4, retry_base_seconds=0.0, retry_sleep=lambda s: None
    )
    assert d._http_get("https://r.example/p.wasm", "r.example") == b"ok"
    assert failpoints.fired_count("fetch.http") == 2


def test_retry_with_backoff_respects_cap():
    delays: list[float] = []
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] < 5:
            raise ValueError("transient")
        return "done"

    out = retry_with_backoff(
        flaky, is_retryable=lambda e: isinstance(e, ValueError),
        attempts=5, base_seconds=0.5, cap_seconds=1.0, sleep=delays.append,
    )
    assert out == "done"
    assert len(delays) == 4
    assert all(0 <= d <= 1.0 for d in delays)  # cap binds the tail


# ---------------------------------------------------------------------------
# Cert-reload corruption containment
# ---------------------------------------------------------------------------


def test_cert_reload_corruption_keeps_last_good_identity(tmp_path):
    """An injected corruption during identity reload must keep the
    last-good certificate serving (the reference's failed-reload rule,
    certs.rs:86-161)."""
    pytest.importorskip("cryptography")
    import test_tls

    from policy_server_tpu import certs as certs_mod
    from policy_server_tpu.config.config import TlsConfig

    key, cert = test_tls.make_cert("localhost", is_ca=False)
    cert_file, key_file = test_tls.write_pem(tmp_path, "srv", key, cert)
    ctx = certs_mod.ReloadableTlsContext(
        TlsConfig(cert_file=str(cert_file), key_file=str(key_file))
    )
    reloads_before = ctx.reloads
    failpoints.configure("certs.reload=raise:injected-corrupt-pem")
    with pytest.raises(failpoints.FailpointError):
        ctx._reload_identity()
    assert ctx.reloads == reloads_before  # nothing swapped
    failpoints.clear()
    ctx._reload_identity()  # clean reload still works
    assert ctx.reloads == reloads_before + 1


# ---------------------------------------------------------------------------
# Shutdown under load (satellite): drain without hanging
# ---------------------------------------------------------------------------


def test_shutdown_under_load_drains_and_sheds_without_hanging():
    """Graceful drain with hung in-flight batches plus queued requests:
    every future resolves in-band (watchdog 500s for the hung batch,
    503s for the queued remainder) and shutdown() returns within the
    drain deadline — it never waits for the wedged device call."""
    env = make_env(failure_threshold=100)  # breaker out of the picture
    env.warmup((1, 2))
    release = threading.Event()
    failpoints.set_failpoint("device.fetch", lambda: release.wait(timeout=30))
    batcher = MicroBatcher(
        env, max_batch_size=2, batch_timeout_ms=1.0, policy_timeout=0.5,
        queue_capacity=4, host_fastpath_threshold=0, latency_budget_ms=0,
    ).start()
    try:
        futs = [
            batcher.submit("ns", review(), RequestOrigin.VALIDATE)
            for _ in range(6)
        ]
        time.sleep(0.1)  # let the first batches reach the hung device
        t0 = time.perf_counter()
        batcher.shutdown()
        elapsed = time.perf_counter() - t0
        assert elapsed < 15.0, f"shutdown took {elapsed:.1f}s"
        for fut in futs:
            resp = fut.result(timeout=1)  # resolved — nothing hangs
            assert resp.allowed is False
            assert resp.status.code in (429, 500, 503)
    finally:
        release.set()
        env.close()


def test_shutdown_under_load_through_real_server():
    """Server-level drain: stop() with in-flight HTTP requests against a
    hung device completes inside its own deadline (bridge wait_closed
    and batcher drain both bounded) and in-flight requests get answers,
    not resets."""
    import requests as rq

    from test_server import ServerHandle, make_config, pod_review_body

    handle = ServerHandle(make_config(policy_timeout_seconds=0.5))
    release = threading.Event()
    results: list = []
    try:
        failpoints.set_failpoint(
            "device.fetch", lambda: release.wait(timeout=30)
        )

        def fire():
            try:
                r = rq.post(
                    handle.url("/validate/pod-privileged"),
                    json=pod_review_body(False), timeout=10,
                )
                results.append(r.status_code)
            except Exception as e:  # noqa: BLE001 — recorded for assert
                results.append(e)

        threads = [threading.Thread(target=fire) for _ in range(4)]
        for t in threads:
            t.start()
        # wait until every request actually REACHED the batcher (a fixed
        # sleep raced slow client-thread scheduling on a loaded box: a
        # thread still connecting when stop() closed the listener got
        # connection-refused, which is not the property under test)
        deadline = time.monotonic() + 10
        while (
            handle.server.batcher.stats_snapshot()["requests_dispatched"] < 4
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
    finally:
        t0 = time.perf_counter()
        handle.stop()
        stop_elapsed = time.perf_counter() - t0
        release.set()
    assert stop_elapsed < 12.0, f"server stop took {stop_elapsed:.1f}s"
    for t in threads:
        t.join(timeout=5)
    # every in-flight request got an HTTP answer (watchdog 500-in-200 or
    # a shutdown 503-in-200) — none hung past stop
    assert len(results) == 4
    assert all(isinstance(code, int) for code in results), results


# ---------------------------------------------------------------------------
# Policy hot reload under load (round 9): zero drops, bit-exact, and a
# bad push never serves (lifecycle.py; failpoints reload.*)
# ---------------------------------------------------------------------------


def _lifecycle_config():
    from policy_server_tpu.models.policy import parse_policy_entry as ppe
    from test_server import make_config

    policies = {
        "pod-privileged": ppe(
            "pod-privileged", {"module": "builtin://pod-privileged"}
        ),
    }
    return make_config(
        policies=policies,
        policy_timeout_seconds=5.0,
        max_batch_size=4,
        reload_admin_token="chaos-token",
    ), policies


def test_hot_reload_under_load_zero_drops_bit_exact():
    """The acceptance scenario: sustained traffic across >=3 back-to-back
    hot reloads with ZERO non-2xx responses and bit-exact verdicts (a
    privileged pod always denies, an unprivileged one always allows —
    through every swap), the epoch gauge advancing each promotion, and a
    subsequent bad-policy push (injected compile fault, then a canary
    fault) leaving last-good serving with the rollback counter
    incremented."""
    import requests as rq

    from policy_server_tpu.models.policy import parse_policy_entry as ppe
    from test_server import ServerHandle, pod_review_body

    config, policies = _lifecycle_config()
    handle = ServerHandle(config)
    lifecycle = handle.server.lifecycle
    stop = threading.Event()
    results: list[tuple[int, bool | None, bool]] = []
    errors: list[Exception] = []

    def traffic(worker: int) -> None:
        i = 0
        while not stop.is_set():
            privileged = (i + worker) % 2 == 0
            i += 1
            try:
                r = rq.post(
                    handle.url("/validate/pod-privileged"),
                    json=pod_review_body(privileged), timeout=30,
                )
                allowed = (
                    r.json()["response"]["allowed"]
                    if r.status_code == 200 else None
                )
                results.append((r.status_code, allowed, privileged))
            except Exception as e:  # noqa: BLE001 — recorded for assert
                errors.append(e)
                return

    threads = [
        threading.Thread(target=traffic, args=(w,), daemon=True)
        for w in range(2)
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)  # traffic flowing before the first swap

        # three back-to-back reloads under load, alternating the set so
        # every swap is a REAL rebuild (policy added / removed / added)
        extra = dict(policies)
        extra["happy"] = ppe("happy", {"module": "builtin://always-happy"})
        for reload_no, policy_set in enumerate(
            (extra, policies, extra), start=1
        ):
            assert lifecycle.reload(policies=policy_set) == "promoted"
            assert lifecycle.stats()["epoch"] == reload_no
            time.sleep(0.2)  # traffic rides the fresh epoch between swaps

        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, f"transport-level failures under reload: {errors}"
        assert len(results) > 20, "traffic generator barely ran"
        # ZERO dropped/erroneous responses across every swap...
        non_2xx = [r for r in results if r[0] != 200]
        assert not non_2xx, f"non-2xx under reload: {non_2xx[:5]}"
        # ...and every verdict bit-exact wrt the policy semantics
        for status, allowed, privileged in results:
            assert allowed == (not privileged), (status, allowed, privileged)

        stats = lifecycle.stats()
        assert stats["reloads"] == 3
        assert stats["reload_failures"] == 0 and stats["rollbacks"] == 0

        # -- bad-policy pushes: compile fault, then canary fault ----------
        from policy_server_tpu.lifecycle import ReloadRejected

        failpoints.configure("reload.compile=raise:injected-bad-compile*1")
        with pytest.raises(ReloadRejected):
            lifecycle.reload(policies=extra)
        assert failpoints.fired_count("reload.compile") == 1

        failpoints.configure("reload.canary=raise:injected-canary-fault*1")
        with pytest.raises(ReloadRejected):
            lifecycle.reload(policies=extra)
        assert failpoints.fired_count("reload.canary") == 1

        failpoints.configure("reload.fetch=raise:injected-fetch-fault*1")
        with pytest.raises(ReloadRejected):
            lifecycle.reload(policies=extra)
        assert failpoints.fired_count("reload.fetch") == 1

        stats = lifecycle.stats()
        assert stats["rollbacks"] == 3 and stats["reload_failures"] == 3
        assert stats["epoch"] == 3  # last-good: the third promoted epoch

        # last-good keeps serving bit-exactly after every rejection
        r = rq.post(
            handle.url("/validate/pod-privileged"),
            json=pod_review_body(True), timeout=30,
        )
        assert r.status_code == 200
        assert r.json()["response"]["allowed"] is False
        r = rq.post(
            handle.url("/validate/happy"),
            json=pod_review_body(False), timeout=30,
        )
        assert r.status_code == 200  # the promoted epoch's added policy
        assert r.json()["response"]["allowed"] is True
    finally:
        stop.set()
        handle.stop()


def test_reload_counters_reach_metrics_endpoint():
    """All reload counters + the epoch gauge are operator-visible on the
    Prometheus pull endpoint after real promotions and rejections."""
    import requests as rq

    from policy_server_tpu.models.policy import parse_policy_entry as ppe
    from policy_server_tpu.lifecycle import ReloadRejected
    from test_server import ServerHandle

    config, policies = _lifecycle_config()
    handle = ServerHandle(config)
    try:
        lifecycle = handle.server.lifecycle
        extra = dict(policies)
        extra["happy"] = ppe("happy", {"module": "builtin://always-happy"})
        assert lifecycle.reload(policies=extra) == "promoted"
        failpoints.configure("reload.compile=raise:injected*1")
        with pytest.raises(ReloadRejected):
            lifecycle.reload(policies=policies)

        r = rq.get(handle.readiness_url("/metrics"), timeout=10)
        assert r.status_code == 200
        metrics: dict[str, float] = {}
        for line in r.text.splitlines():
            if line.startswith("#") or not line.strip():
                continue
            name, _, value = line.rpartition(" ")
            try:
                metrics[name.split("{")[0].strip()] = float(value)
            except ValueError:
                continue
        assert metrics["policy_server_policy_reloads_total"] == 1
        assert metrics["policy_server_policy_reload_failures_total"] == 1
        assert metrics["policy_server_policy_reload_rollbacks_total"] == 1
        assert metrics["policy_server_policy_epoch"] == 1
        assert metrics["policy_server_reload_canary_replays_total"] > 0
        assert "policy_server_reload_canary_divergences_total" in metrics
    finally:
        handle.stop()


def test_audit_scanner_chaos_under_load_reload_and_sweep_fault():
    """Round-10 chaos acceptance: the background audit scanner running
    under sustained live traffic, through a mid-sweep policy reload AND
    an armed ``audit.sweep`` fault — zero live non-2xx, bit-exact live
    verdicts, the scanner resumes sweeping after the fault clears, and
    post-reload reports are stamped with the promoted epoch. Runs under
    the locksan gate via ``make chaos`` (0 inversions)."""
    import requests as rq

    from policy_server_tpu.models.policy import parse_policy_entry as ppe
    from test_server import ServerHandle, pod_review_body

    config, policies = _lifecycle_config()
    config.audit_mode = "interval"
    config.audit_interval_seconds = 0.2
    config.audit_batch_size = 16
    handle = ServerHandle(config)
    scanner = handle.server.state.audit
    assert scanner is not None
    stop = threading.Event()
    results: list[tuple[int, bool | None, bool]] = []
    errors: list[Exception] = []

    def traffic(worker: int) -> None:
        i = 0
        while not stop.is_set():
            privileged = (i + worker) % 2 == 0
            i += 1
            try:
                r = rq.post(
                    handle.url("/validate/pod-privileged"),
                    json=pod_review_body(privileged), timeout=30,
                )
                allowed = (
                    r.json()["response"]["allowed"]
                    if r.status_code == 200 else None
                )
                results.append((r.status_code, allowed, privileged))
            except Exception as e:  # noqa: BLE001 — recorded for assert
                errors.append(e)
                return

    threads = [
        threading.Thread(target=traffic, args=(w,), daemon=True)
        for w in range(2)
    ]

    def wait_until(predicate, timeout=30.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if predicate():
                return True
            time.sleep(0.05)
        return False

    try:
        for t in threads:
            t.start()
        # the dirty-set tracker sees the served objects and the cadence
        # sweeps them while traffic flows
        assert wait_until(lambda: scanner.stats()["rows_scanned"] > 0)

        # armed sweep fault: the next 2 sweeps abort loudly...
        failpoints.configure("audit.sweep=raise:injected-sweep-fault*2")
        assert wait_until(lambda: scanner.stats()["sweep_errors"] >= 2)
        assert failpoints.fired_count("audit.sweep") >= 2
        # ...and the scanner RESUMES once the fault exhausts
        resumed_from = scanner.stats()["rows_scanned"]
        rq.post(
            handle.url("/validate/pod-privileged"),
            json=pod_review_body(False), timeout=30,
        )  # dirty the snapshot so the next sweep has work
        assert wait_until(
            lambda: scanner.stats()["rows_scanned"] > resumed_from
        )

        # mid-sweep policy reload: promote a rebuilt set while the
        # cadence keeps sweeping; the post-promote full re-scan stamps
        # reports with the new epoch
        lifecycle = handle.server.lifecycle
        extra = dict(policies)
        extra["happy"] = ppe("happy", {"module": "builtin://always-happy"})
        assert lifecycle.reload(policies=extra) == "promoted"
        assert wait_until(
            lambda: (
                lambda body: bool(body["reports"]) and all(
                    x["epoch"] == 1 and not x["stale"]
                    for x in body["reports"]
                )
            )(scanner.report_payload())
        ), scanner.report_payload()["summary"]

        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, f"transport failures under audit chaos: {errors}"
        assert len(results) > 20, "traffic generator barely ran"
        non_2xx = [r for r in results if r[0] != 200]
        assert not non_2xx, f"live non-2xx with scanner armed: {non_2xx[:5]}"
        for status, allowed, privileged in results:
            assert allowed == (not privileged), (status, allowed, privileged)
        # preemption discipline held: audit work flowed on idle slots
        snap = handle.server.batcher.stats_snapshot()
        final = scanner.stats()
        assert final["rows_scanned"] > 0
        assert final["sweep_errors"] >= 2
        assert snap["audit_batches_dispatched"] >= 1
    finally:
        stop.set()
        failpoints.reset()
        handle.stop()


# ---------------------------------------------------------------------------
# Native frontend chaos (round 11): the GIL-free C++ framing path under
# shutdown-under-load, SIGHUP hot reload, and armed device failpoints
# ---------------------------------------------------------------------------


def _native_or_skip():
    nf = pytest.importorskip("policy_server_tpu.runtime.native_frontend")
    if not nf.native_available():
        pytest.skip("httpfront.cpp failed to build (no g++?)")
    return nf


def test_native_shutdown_under_load_resolves_every_inflight():
    """stop() with in-flight requests parked on a hung device behind the
    NATIVE frontend: every accepted request gets an HTTP answer (watchdog
    500-in-200 or shutdown 503-in-200) before the native loops stop —
    no resets, no hangs, and stop() stays inside its own deadline."""
    import requests as rq

    from test_server import ServerHandle, make_config, pod_review_body

    _native_or_skip()
    handle = ServerHandle(
        make_config(frontend="native", policy_timeout_seconds=0.5)
    )
    assert handle.server._native_frontend is not None
    release = threading.Event()
    results: list = []
    try:
        failpoints.set_failpoint(
            "device.fetch", lambda: release.wait(timeout=30)
        )

        def fire():
            try:
                r = rq.post(
                    handle.url("/validate/pod-privileged"),
                    json=pod_review_body(False), timeout=10,
                )
                results.append(r.status_code)
            except Exception as e:  # noqa: BLE001 — recorded for assert
                results.append(e)

        threads = [threading.Thread(target=fire) for _ in range(4)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 10
        while (
            handle.server.batcher.stats_snapshot()["requests_dispatched"] < 4
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
    finally:
        t0 = time.perf_counter()
        handle.stop()
        stop_elapsed = time.perf_counter() - t0
        release.set()
    assert stop_elapsed < 12.0, f"server stop took {stop_elapsed:.1f}s"
    for t in threads:
        t.join(timeout=5)
    assert len(results) == 4
    assert all(isinstance(code, int) for code in results), results


def test_native_sighup_reload_under_load_zero_non_2xx():
    """Sustained traffic through the native frontend across a SIGHUP-
    triggered policy hot reload: zero non-2xx, bit-exact verdicts through
    the epoch flip (the reload machinery swaps state.batcher under the
    drainer's feet — BatcherSink must follow the epoch pointer)."""
    import requests as rq

    from test_server import ServerHandle, pod_review_body

    _native_or_skip()
    config, _policies = _lifecycle_config()
    config.frontend = "native"
    handle = ServerHandle(config)
    assert handle.server._native_frontend is not None
    lifecycle = handle.server.lifecycle
    stop = threading.Event()
    results: list[tuple[int, bool | None, bool]] = []
    errors: list[Exception] = []

    def traffic(worker: int) -> None:
        i = 0
        while not stop.is_set():
            privileged = (i + worker) % 2 == 0
            i += 1
            try:
                r = rq.post(
                    handle.url("/validate/pod-privileged"),
                    json=pod_review_body(privileged), timeout=30,
                )
                allowed = (
                    r.json()["response"]["allowed"]
                    if r.status_code == 200 else None
                )
                results.append((r.status_code, allowed, privileged))
            except Exception as e:  # noqa: BLE001 — recorded for assert
                errors.append(e)
                return

    threads = [
        threading.Thread(target=traffic, args=(w,), daemon=True)
        for w in range(2)
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)
        before = lifecycle.stats()["reloads"]
        # the SIGHUP contract entry point (server.reload_signal), not a
        # raw kill(): ServerHandle's loop thread can't take signals
        handle.server.reload_signal()
        deadline = time.monotonic() + 60
        while (
            lifecycle.stats()["reloads"] == before
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        assert lifecycle.stats()["reloads"] > before, "reload never promoted"
        time.sleep(0.3)  # traffic THROUGH the promoted epoch
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        handle.stop()
    assert not errors, errors
    assert len(results) > 20
    non_2xx = [r for r in results if r[0] != 200]
    assert not non_2xx, f"non-2xx during native SIGHUP reload: {non_2xx[:5]}"
    for _code, allowed, privileged in results:
        assert allowed is (not privileged)  # bit-exact through the flip


def test_native_armed_failpoint_breaker_degrades_to_oracle():
    """An armed raising device failpoint behind the native frontend:
    the breaker trips, traffic degrades to the bit-exact host oracle —
    every HTTP answer stays 200 with the correct verdict."""
    import requests as rq

    from test_server import ServerHandle, make_config, pod_review_body

    _native_or_skip()
    handle = ServerHandle(
        make_config(
            frontend="native",
            policy_timeout_seconds=5.0,
            breaker_failure_threshold=2,
            breaker_window_seconds=10.0,
            breaker_cooldown_seconds=30.0,
            verdict_cache_size=0,
            host_fastpath_threshold=0,
            latency_budget_ms=0.0,
        )
    )
    assert handle.server._native_frontend is not None
    try:
        def boom():
            raise RuntimeError("injected device fault")

        failpoints.set_failpoint("device.fetch", boom)
        statuses = []
        for privileged in (True, False) * 6:
            r = rq.post(
                handle.url("/validate/pod-privileged"),
                json=pod_review_body(privileged), timeout=30,
            )
            statuses.append(r.status_code)
            if r.status_code == 200:
                body = r.json()["response"]
                # in-band faults (pre-trip) reject with 5xx status codes;
                # post-trip oracle answers carry the true verdict
                if not (body.get("status") or {}).get("code"):
                    assert body["allowed"] is (not privileged)
        # the breaker tripped and the oracle served: the tail of the
        # stream must be clean 200s with true verdicts
        tail = statuses[-6:]
        assert tail == [200] * 6, statuses
        breaker = handle.server.environment.breaker_stats
        assert breaker["trips"] >= 1
        assert handle.server._native_frontend.stats()["http_requests"] >= 12
    finally:
        handle.stop()


# ---------------------------------------------------------------------------
# Round 13 — soak-era chaos: frontend intake fault, watch-stream fault,
# and the burst-level shed Retry-After contract
# ---------------------------------------------------------------------------


def test_submit_many_shed_retry_after_derives_from_ewma():
    """Burst-level shedding (submit_many) must stamp Retry-After from
    the measured EWMA queue wait — the SAME estimate the admission
    check used — not a constant: a deeper/slower queue must advertise a
    proportionally longer retry."""
    env = make_env()
    batcher = MicroBatcher(  # deliberately NOT started: queue holds
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=5.0,
        request_timeout_ms=50.0,
    )
    try:
        # one admitted row so the queue has depth (depth 0 never sheds)
        batcher.submit("ns", review(), RequestOrigin.VALIDATE)

        def shed_burst() -> float:
            est = batcher.estimated_wait()
            futures = batcher.submit_many(
                [("ns", review()) for _ in range(3)],
                RequestOrigin.VALIDATE,
            )
            retries = set()
            for fut in futures:
                with pytest.raises(ShedError) as exc:
                    fut.result(timeout=1)
                retries.add(exc.value.retry_after_seconds)
            assert len(retries) == 1  # one estimate for the whole burst
            retry = retries.pop()
            # the stamp IS the estimate (modulo the clamp floor)
            assert retry == pytest.approx(max(0.001, est), rel=0.25)
            return retry

        batcher._dev_rtt[bucket_size(4)] = 2.0
        slow = shed_burst()
        batcher._dev_rtt[bucket_size(4)] = 8.0
        slower = shed_burst()
        # 4x the device RTT → ~4x the advertised retry: EWMA-derived,
        # provably not a constant
        assert slower == pytest.approx(slow * 4.0, rel=0.25)
        assert batcher.shed_requests == 6
    finally:
        batcher.shutdown()


def test_native_frontend_accept_fault_answers_500_and_recovers():
    """An armed frontend.accept fault: the poisoned poll burst answers
    every request with an in-band 500 (never strands the HTTP caller),
    the drainer survives, and the very next request serves normally."""
    import requests as rq

    from test_server import ServerHandle, make_config, pod_review_body

    _native_or_skip()
    handle = ServerHandle(make_config(frontend="native"))
    assert handle.server._native_frontend is not None
    try:
        failpoints.configure("frontend.accept=raise:intake-fault*1")
        r = rq.post(
            handle.url("/validate/pod-privileged"),
            json=pod_review_body(False),
            headers={"Connection": "close"}, timeout=30,
        )
        assert r.status_code == 500
        assert r.json() == {
            "message": "Something went wrong", "status": 500
        }
        assert failpoints.fired_count("frontend.accept") == 1
        # next burst is clean: the drainer kept running
        r = rq.post(
            handle.url("/validate/pod-privileged"),
            json=pod_review_body(True),
            headers={"Connection": "close"}, timeout=30,
        )
        assert r.status_code == 200
        assert r.json()["response"]["allowed"] is False
    finally:
        handle.stop()


def test_watch_feed_stream_fault_resyncs_and_recovers():
    """An armed watch.stream fault: the kind's stream connect raises,
    the feed backs off and recovers through a counted full re-LIST
    resync — the snapshot store still converges to cluster truth and
    later churn applies through the repaired stream."""
    from policy_server_tpu.audit import SnapshotStore, WatchFeed
    from tools.soak.cluster import SyntheticCluster

    cluster = SyntheticCluster(seed=3)
    cluster.populate(120)
    store = SnapshotStore()
    feed = WatchFeed(cluster, cluster.kinds, store, refresh_seconds=0.5)
    # one raise per kind: every stream's FIRST connect faults, the
    # retry path must re-LIST and carry on
    failpoints.configure(
        f"watch.stream=raise:injected-watch-fault*{len(cluster.kinds)}"
    )
    try:
        feed.start()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and (
            len(store) < 120
            or feed.stats()["resyncs"] < 1
        ):
            time.sleep(0.05)
        assert len(store) == 120
        stats = feed.stats()
        assert failpoints.fired_count("watch.stream") >= 1
        assert stats["resyncs"] >= 1
        assert stats["resync_reasons"].get("error", 0) >= 1
        # the repaired streams keep delivering
        cluster.churn(80)
        deadline = time.monotonic() + 20
        while (
            time.monotonic() < deadline
            and cluster.object_count() != len(store)
        ):
            time.sleep(0.05)
        assert cluster.object_count() == len(store)
        assert feed.stats()["events_applied"] > 0
    finally:
        feed.stop()
        cluster.stop()


# ---------------------------------------------------------------------------
# Round 14: chaos under the fused-SPMD (data × policy) mesh program
# ---------------------------------------------------------------------------


def test_mesh_breaker_trips_to_oracle_and_recovers():
    """The round-7 breaker contract holds under the fused mesh program:
    injected dispatch faults on the ONE (data × policy) SPMD program trip
    its breaker, tripped traffic serves bit-exact verdicts from the host
    oracle (the still-armed failpoint proves the mesh program is never
    touched while open), and a half-open probe recovers it — through the
    lax.switch + all-gather path, not the single-device program."""
    from policy_server_tpu.config.config import MeshSpec
    from policy_server_tpu.parallel import make_mesh

    env = EvaluationEnvironmentBuilder(
        backend="jax",
        breaker_config=dict(
            failure_threshold=2, window_seconds=10.0, cooldown_seconds=0.3
        ),
        # cache off: a hit would answer the half-open probe without
        # touching the device (same rationale as make_env above)
        verdict_cache_size=0,
    ).build(
        {
            "ns": parse_policy_entry(
                "ns",
                {
                    "module": "builtin://namespace-validate",
                    "settings": {"denied_namespaces": ["blocked"]},
                },
            ),
            "priv": parse_policy_entry(
                "priv", {"module": "builtin://pod-privileged"}
            ),
        }
    )
    env.attach_mesh(make_mesh(MeshSpec.parse("data:4,policy:2")))
    assert env._mesh_block is not None  # policy axis really sharded
    try:
        env.warmup((4,))
        allowed = [("ns", review())]
        denied = [("ns", review(namespace="blocked"))]

        failpoints.configure("device.fetch=raise:injected-mesh-fault")
        for _ in range(2):
            with pytest.raises(failpoints.FailpointError):
                env.validate_batch(allowed)
        stats = env.breaker_stats
        assert stats["trips"] == 1 and stats["open_shards"] == 1

        out = env.validate_batch(allowed + denied)
        assert out[0].allowed is True
        assert out[1].allowed is False
        assert env.breaker_stats["short_circuited_requests"] >= 2

        failpoints.clear()
        time.sleep(0.35)
        out = env.validate_batch(allowed)
        assert out[0].allowed is True
        stats = env.breaker_stats
        assert stats["recoveries"] == 1 and stats["open_shards"] == 0
    finally:
        env.close()


def test_mesh_sighup_reload_under_load_zero_non_2xx():
    """SIGHUP epoch flip while the serving program is the fused SPMD
    mesh program: sustained traffic across the promoted flip sees ZERO
    non-2xx and bit-exact verdicts, and the newly promoted epoch serves
    through a freshly attached fused mesh program (the program swap is
    mesh → mesh, never a fallback to single-device or threaded MPMD)."""
    import requests as rq

    from policy_server_tpu.config.config import MeshSpec
    from policy_server_tpu.models.policy import parse_policy_entry as ppe
    from policy_server_tpu.parallel import PolicyShardedEvaluator
    from test_server import ServerHandle, make_config, pod_review_body

    policies = {
        "pod-privileged": ppe(
            "pod-privileged", {"module": "builtin://pod-privileged"}
        ),
        "latest": ppe("latest", {"module": "builtin://disallow-latest-tag"}),
    }
    config = make_config(
        policies=policies,
        policy_timeout_seconds=5.0,
        max_batch_size=4,
        reload_admin_token="chaos-token",
        mesh=MeshSpec.parse("data:4,policy:2"),
    )
    handle = ServerHandle(config)
    lifecycle = handle.server.lifecycle
    boot_env = handle.server.environment
    assert not isinstance(boot_env, PolicyShardedEvaluator)
    assert boot_env._mesh_block is not None
    stop = threading.Event()
    results: list[tuple[int, bool | None, bool]] = []
    errors: list[Exception] = []

    def traffic(worker: int) -> None:
        i = 0
        while not stop.is_set():
            privileged = (i + worker) % 2 == 0
            i += 1
            try:
                r = rq.post(
                    handle.url("/validate/pod-privileged"),
                    json=pod_review_body(privileged), timeout=30,
                )
                allowed = (
                    r.json()["response"]["allowed"]
                    if r.status_code == 200 else None
                )
                results.append((r.status_code, allowed, privileged))
            except Exception as e:  # noqa: BLE001 — recorded for assert
                errors.append(e)
                return

    threads = [
        threading.Thread(target=traffic, args=(w,), daemon=True)
        for w in range(2)
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)
        before = lifecycle.stats()["reloads"]
        handle.server.reload_signal()
        deadline = time.monotonic() + 120
        while (
            lifecycle.stats()["reloads"] == before
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        assert lifecycle.stats()["reloads"] > before, "reload never promoted"
        time.sleep(0.3)  # traffic THROUGH the promoted epoch
        promoted_env = handle.server.environment
        assert promoted_env is not boot_env
        assert not isinstance(promoted_env, PolicyShardedEvaluator)
        assert promoted_env._mesh_block is not None  # mesh → mesh swap
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        handle.stop()
    assert not errors, errors
    assert len(results) > 10
    non_2xx = [r for r in results if r[0] != 200]
    assert not non_2xx, f"non-2xx during mesh SIGHUP reload: {non_2xx[:5]}"
    for _code, allowed, privileged in results:
        assert allowed is (not privileged)  # bit-exact through the flip


# ---------------------------------------------------------------------------
# Multi-tenant fault containment (round 16, tenancy.py): a fault scoped
# to one tenant trips/rolls back THAT tenant only — other tenants see
# zero non-2xx, bit-exact verdicts, and no oracle fallbacks.
# ---------------------------------------------------------------------------


def test_tenant_scoped_device_fault_trips_one_tenant_only():
    """An armed device.fetch fault scoped to tenant A trips A's breaker
    (A degrades to its bit-exact host oracle); tenant B — concurrently
    serving through the SAME fair scheduler on the same host — never
    sees the fault: zero errors, correct verdicts, breaker closed, no
    oracle short-circuits."""
    from policy_server_tpu.runtime.scheduler import FairDispatchScheduler

    env_a = make_env()
    env_b = make_env()
    sched = FairDispatchScheduler(max_concurrent=2)
    batchers = {}
    for name, env in (("ten-a", env_a), ("ten-b", env_b)):
        env.warmup((1, 4))
        batchers[name] = MicroBatcher(
            env, max_batch_size=4, batch_timeout_ms=1.0,
            policy_timeout=5.0, host_fastpath_threshold=0,
            latency_budget_ms=0, scheduler=sched, tenant=name,
        ).start()
    try:
        failpoints.set_failpoint(
            "device.fetch",
            lambda: (_ for _ in ()).throw(
                failpoints.FailpointError("injected device fault")
            ),
            scope="ten-a",
        )
        b_results: list = []
        b_errors: list = []
        stop = threading.Event()

        def b_traffic():
            i = 0
            while not stop.is_set():
                blocked = i % 2 == 0
                i += 1
                try:
                    resp = batchers["ten-b"].submit(
                        "ns",
                        review(namespace="blocked" if blocked else None),
                        RequestOrigin.VALIDATE,
                    ).result(timeout=10)
                    b_results.append((resp.allowed, blocked))
                except Exception as e:  # noqa: BLE001 — asserted below
                    b_errors.append(e)
                    return

        bt = threading.Thread(target=b_traffic, daemon=True)
        bt.start()

        # A's first two dispatches fault -> breaker trips; then the
        # bit-exact host oracle answers A's traffic correctly
        for _ in range(2):
            with pytest.raises(failpoints.FailpointError):
                batchers["ten-a"].submit(
                    "ns", review(), RequestOrigin.VALIDATE
                ).result(timeout=10)
        assert env_a.breaker_stats["trips"] == 1
        ok = batchers["ten-a"].submit(
            "ns", review(), RequestOrigin.VALIDATE
        ).result(timeout=10)
        bad = batchers["ten-a"].submit(
            "ns", review(namespace="blocked"), RequestOrigin.VALIDATE
        ).result(timeout=10)
        assert ok.allowed is True and bad.allowed is False
        assert env_a.breaker_stats["short_circuited_requests"] >= 2

        time.sleep(0.3)  # let B serve through the whole fault window
        stop.set()
        bt.join(timeout=10)

        # containment: B never saw the fault
        assert not b_errors
        assert len(b_results) >= 5
        assert all(allowed is (not blocked) for allowed, blocked in b_results)
        b_stats = env_b.breaker_stats
        assert b_stats["trips"] == 0
        assert b_stats["open_shards"] == 0
        assert b_stats["short_circuited_requests"] == 0
        assert (getattr(env_b, "oracle_fallbacks", 0) or 0) == 0
    finally:
        for b in batchers.values():
            b.shutdown()
        env_a.close()
        env_b.close()


def test_tenant_admission_fault_contained_to_its_tenant():
    """An armed tenant.admission fault scoped to tenant A answers A's
    submissions with an in-band error; tenant B's admission (its OWN
    quota object) keeps admitting."""
    from policy_server_tpu.tenancy import TenantAdmission

    env = make_env(failure_threshold=100)
    env.warmup((1, 4))
    adm_a = TenantAdmission("ten-a", rows_per_second=1000.0)
    adm_b = TenantAdmission("ten-b", rows_per_second=1000.0)
    batcher_a = MicroBatcher(
        env, max_batch_size=4, policy_timeout=5.0, admission=adm_a,
        tenant="ten-a",
    ).start()
    batcher_b = MicroBatcher(
        env, max_batch_size=4, policy_timeout=5.0, admission=adm_b,
        tenant="ten-b",
    ).start()
    try:
        failpoints.set_failpoint(
            "tenant.admission",
            lambda: (_ for _ in ()).throw(
                failpoints.FailpointError("admission layer down")
            ),
            scope="ten-a",
        )
        with pytest.raises(failpoints.FailpointError):
            batcher_a.submit("ns", review(), RequestOrigin.VALIDATE)
        resp = batcher_b.submit(
            "ns", review(), RequestOrigin.VALIDATE
        ).result(timeout=10)
        assert resp.allowed is True
        assert adm_b.stats()["admitted_rows"] == 1
        assert adm_a.stats()["admitted_rows"] == 0
        # in-flight accounting drained for B
        assert adm_b.stats()["inflight"] == 0
    finally:
        batcher_a.shutdown()
        batcher_b.shutdown()
        env.close()


def test_tenant_reload_fault_contained_across_sighup_fanout():
    """The SIGHUP fan-out (reload_all) with a tenant.reload fault scoped
    to tenant A: A's pipeline rejects at the fetch stage and keeps
    serving last-good; tenant B and the default tenant promote their
    epochs — under sustained tenant-B traffic with zero non-2xx and
    bit-exact verdicts through the flips."""
    import requests as rq

    from test_server import ServerHandle, pod_review_body
    from test_tenancy import _tenant_config

    import tempfile
    from pathlib import Path

    tmp_dir = Path(tempfile.mkdtemp(prefix="tenant-chaos-"))
    handle = ServerHandle(_tenant_config(tmp_dir))
    mgr = handle.server.state.tenants
    stop = threading.Event()
    results: list[tuple[int, bool | None, bool]] = []
    errors: list[Exception] = []

    def b_traffic(worker: int) -> None:
        i = 0
        while not stop.is_set():
            privileged = (i + worker) % 2 == 0
            i += 1
            try:
                r = rq.post(
                    handle.url("/validate/ten-b/common"),
                    json=pod_review_body(privileged), timeout=30,
                )
                allowed = (
                    r.json()["response"]["allowed"]
                    if r.status_code == 200 else None
                )
                results.append((r.status_code, allowed, privileged))
            except Exception as e:  # noqa: BLE001 — recorded for assert
                errors.append(e)
                return

    threads = [
        threading.Thread(target=b_traffic, args=(w,), daemon=True)
        for w in range(2)
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)

        failpoints.set_failpoint(
            "tenant.reload",
            lambda: (_ for _ in ()).throw(
                failpoints.FailpointError("tenant manifest unreadable")
            ),
            scope="ten-a",
        )
        started = mgr.reload_all("chaos-sighup")
        assert started >= 3  # default + ten-a + ten-b (+ ten-q)

        # wait for every tenant's pipeline to settle
        deadline = time.monotonic() + 120
        lcs = {
            name: mgr.get(name).state.lifecycle
            for name in ("ten-a", "ten-b")
        }
        lcs["default"] = handle.server.lifecycle
        while time.monotonic() < deadline:
            if not any(lc.reload_in_flight() for lc in lcs.values()):
                break
            time.sleep(0.2)

        a_stats = lcs["ten-a"].stats()
        assert a_stats["epoch"] == 0, "faulted tenant must NOT promote"
        assert a_stats["reload_failures"] == 1
        assert a_stats["rollbacks"] == 1
        assert lcs["ten-b"].stats()["epoch"] == 1
        assert lcs["default"].stats()["epoch"] == 1

        # A keeps serving last-good
        r = rq.post(
            handle.url("/validate/ten-a/only-a"),
            json=pod_review_body(True), timeout=30,
        )
        assert r.status_code == 200
        assert r.json()["response"]["allowed"] is False

        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors
        assert len(results) >= 10
        non_2xx = [s for s, _a, _p in results if s != 200]
        assert non_2xx == [], f"tenant B saw non-2xx: {non_2xx[:5]}"
        assert all(
            allowed is (not privileged) for _s, allowed, privileged in results
        )
    finally:
        stop.set()
        handle.stop()


# ---------------------------------------------------------------------------
# Round 17 — crash tolerance: the state store under chaos load
# ---------------------------------------------------------------------------


def test_statestore_armed_reload_under_load_then_warm_reboot(tmp_path):
    """The state store under the chaos contract (and the lock-order
    sanitizer, via make chaos): sustained traffic across a hot reload
    with ``--state-dir`` armed — zero non-2xx, the last-good manifest
    following every promotion — then a stop + warm re-boot with the
    registry failpoint armed: the manifest pin carries over, verdicts
    stay bit-exact, and the fsck pass quarantines a deliberately
    bit-flipped journal on a THIRD boot instead of crashing it."""
    import requests as rq

    from policy_server_tpu import failpoints
    from policy_server_tpu.statestore import StateStore
    from test_server import ServerHandle, make_config, pod_review_body

    policies_path = tmp_path / "policies.yml"
    policies_path.write_text(
        "pod-privileged:\n  module: builtin://pod-privileged\n"
    )

    from policy_server_tpu.config.config import read_policies_file

    def build_config():
        return make_config(
            policies=read_policies_file(policies_path),
            policies_path=str(policies_path),
            policy_timeout_seconds=5.0,
            max_batch_size=4,
            state_dir=str(tmp_path / "state"),
            selfheal_interval_seconds=0.2,
        )

    handle = ServerHandle(build_config())
    stop = threading.Event()
    results: list[int] = []
    errors: list[Exception] = []

    def client():
        body = pod_review_body(False)
        while not stop.is_set():
            try:
                r = rq.post(
                    handle.url("/validate/pod-privileged"),
                    json=body, timeout=30,
                )
                results.append(r.status_code)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(2)]
    try:
        for t in threads:
            t.start()
        store = handle.server.state.statestore
        assert store is not None
        assert store.last_good_manifest()["outcome"] == "boot"
        # promote a reload mid-traffic: the manifest must follow
        policies_path.write_text(
            "pod-privileged:\n  module: builtin://pod-privileged\n"
            "happy:\n  module: builtin://always-happy\n"
        )
        assert handle.server.lifecycle.request_reload("chaos")
        deadline = time.time() + 60
        while time.time() < deadline:
            m = store.last_good_manifest()
            if m["outcome"] == "promoted" and m["epoch"] >= 1:
                break
            time.sleep(0.1)
        m = store.last_good_manifest()
        assert m["outcome"] == "promoted" and "happy" in m["policy_ids"]
        # the self-heal watchdog ran under load without reviving anything
        assert handle.server.state.supervisor.stats()[
            "batcher_revives"
        ] == 0
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors
        assert results and all(s == 200 for s in results)
        r = rq.post(
            handle.url("/validate/pod-privileged"),
            json=pod_review_body(True), timeout=30,
        )
        pre_denied = r.json()["response"]["allowed"]
        assert pre_denied is False
    finally:
        stop.set()
        handle.stop()

    # warm re-boot with the registry failpoint armed: builtin policies
    # need no fetch, and the manifest pin must carry the epoch forward
    with failpoints.active(
        "fetch.http",
        lambda: (_ for _ in ()).throw(
            failpoints.FailpointError("registry outage")
        ),
    ):
        handle2 = ServerHandle(build_config())
    try:
        report = handle2.server.state.boot_report
        assert report["warm"] is True
        assert report["manifest_epoch"] >= 1
        r = rq.post(
            handle2.url("/validate/pod-privileged"),
            json=pod_review_body(True), timeout=30,
        )
        assert r.status_code == 200
        assert r.json()["response"]["allowed"] is False
    finally:
        handle2.stop()

    # bit-flip the manifests journal: the THIRD boot must fsck-
    # quarantine it and come up clean-cold, never crash
    journal = tmp_path / "state" / StateStore.MANIFESTS_JOURNAL
    data = bytearray(journal.read_bytes())
    data[8] ^= 0xFF
    journal.write_bytes(bytes(data))
    handle3 = ServerHandle(build_config())
    try:
        assert handle3.server.state.boot_report[
            "fsck_quarantined"
        ] >= 1
        r = rq.post(
            handle3.url("/validate/pod-privileged"),
            json=pod_review_body(False), timeout=30,
        )
        assert r.status_code == 200
    finally:
        handle3.stop()


# ---------------------------------------------------------------------------
# Serving shards (round 22, runtime/shards.py): chaos at server level
# ---------------------------------------------------------------------------


def test_sharded_sighup_flip_under_load_zero_non_2xx():
    """SIGHUP epoch flip with --serving-shards 2 under sustained load:
    the reload builds a whole NEW router (fresh sibling environments
    from the candidate policy set) and the lifecycle flips the one
    state.batcher pointer — all M shards swap atomically, verdicts stay
    bit-exact through the flip, zero non-2xx."""
    import requests as rq

    from policy_server_tpu.runtime.shards import ShardRouter
    from test_server import ServerHandle, pod_review_body

    config, _policies = _lifecycle_config()
    config.serving_shards = 2
    config.shard_heartbeat_seconds = 0.2
    handle = ServerHandle(config)
    lifecycle = handle.server.lifecycle
    router_before = handle.server.state.batcher
    assert isinstance(router_before, ShardRouter)
    assert router_before.serving_shards == 2
    stop = threading.Event()
    results: list[tuple[int, bool | None, bool]] = []
    errors: list[Exception] = []

    def traffic(worker: int) -> None:
        i = 0
        while not stop.is_set():
            privileged = (i + worker) % 2 == 0
            i += 1
            try:
                r = rq.post(
                    handle.url("/validate/pod-privileged"),
                    json=pod_review_body(privileged), timeout=30,
                )
                allowed = (
                    r.json()["response"]["allowed"]
                    if r.status_code == 200 else None
                )
                results.append((r.status_code, allowed, privileged))
            except Exception as e:  # noqa: BLE001 — recorded for assert
                errors.append(e)
                return

    threads = [
        threading.Thread(target=traffic, args=(w,), daemon=True)
        for w in range(2)
    ]
    try:
        for t in threads:
            t.start()
        time.sleep(0.3)
        before = lifecycle.stats()["reloads"]
        handle.server.reload_signal()
        deadline = time.monotonic() + 60
        while (
            lifecycle.stats()["reloads"] == before
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        assert lifecycle.stats()["reloads"] > before, "reload never promoted"
        time.sleep(0.3)  # traffic THROUGH the promoted epoch
        router_after = handle.server.state.batcher
        # the flip swapped in a NEW router, still M=2 — epoch atomicity
        # is the single pointer store, not M per-shard swaps
        assert isinstance(router_after, ShardRouter)
        assert router_after is not router_before
        assert router_after.serving_shards == 2
        assert all(h["healthy"] for h in router_after.shard_health())
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        handle.stop()
    assert not errors, errors
    assert len(results) > 20
    non_2xx = [r for r in results if r[0] != 200]
    assert not non_2xx, f"non-2xx during sharded SIGHUP flip: {non_2xx[:5]}"
    for _code, allowed, privileged in results:
        assert allowed is (not privileged)  # bit-exact through the flip


def test_shard_dispatch_fault_fences_revives_and_recovers():
    """An armed shard.dispatch fault at server level: shard 0's dispatch
    loop dies, the router's heartbeat fences it within one beat and
    warm-revives it in place — traffic through the window answers 200
    with correct verdicts (fenced rows re-route to the live sibling),
    and the fence/respawn counters reach the metrics endpoint."""
    import requests as rq

    from test_server import ServerHandle, make_config, pod_review_body

    handle = ServerHandle(
        make_config(serving_shards=2, shard_heartbeat_seconds=0.2)
    )
    try:
        router = handle.server.batcher
        assert router.serving_shards == 2

        def die():
            raise RuntimeError("injected shard death")

        failpoints.set_failpoint(
            "shard.dispatch", die, count=1, scope="shard-0"
        )
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if router._shards[0].batcher.dispatch_wedged():
                break
            time.sleep(0.02)
        failpoints.clear("shard.dispatch")

        # traffic through the fence window: every answer 200, correct
        for i in range(12):
            privileged = i % 2 == 0
            r = rq.post(
                handle.url("/validate/pod-privileged"),
                json=pod_review_body(privileged), timeout=30,
            )
            assert r.status_code == 200, (i, r.status_code)
            assert r.json()["response"]["allowed"] is (not privileged)

        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            stats = router.stats_snapshot()
            if stats["shard_fences"] >= 1 and stats["shard_respawns"] >= 1:
                break
            time.sleep(0.05)
        stats = router.stats_snapshot()
        assert stats["shard_fences"] >= 1, stats
        assert stats["shard_respawns"] >= 1, stats
        assert all(
            h["healthy"] and h["dispatch_alive"]
            for h in router.shard_health()
        )
        # operator-visible on /metrics
        m = rq.get(handle.readiness_url("/metrics"), timeout=10).text
        metrics: dict[str, float] = {}
        for line in m.splitlines():
            if line.startswith("#") or not line.strip():
                continue
            name, _, value = line.rpartition(" ")
            try:
                metrics[name.split("{")[0].strip()] = float(value)
            except ValueError:
                continue
        assert metrics["policy_server_shards_serving"] == 2
        assert metrics["policy_server_shard_fences_total"] >= 1
        assert metrics["policy_server_shard_respawns_total"] >= 1
    finally:
        handle.stop()


def test_sharded_pipelined_connection_reroutes_in_order():
    """Pipelined requests on ONE native connection while a shard dies
    mid-stream: fenced rows re-route (provably not-yet-dispatched — the
    fence drain holds the queue mutex) and every response comes back
    IN ORDER with the verdict of its positional request — the
    never-double-answered, never-desynced contract at the wire level."""
    import json as json_mod
    import socket

    from test_server import ServerHandle, make_config, pod_review_body

    _native_or_skip()
    handle = ServerHandle(
        make_config(
            frontend="native", serving_shards=2,
            shard_heartbeat_seconds=0.2,
        )
    )
    conn = None
    try:
        router = handle.server.batcher

        def die():
            raise RuntimeError("injected shard death")

        failpoints.set_failpoint(
            "shard.dispatch", die, count=1, scope="shard-0"
        )
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if router._shards[0].batcher.dispatch_wedged():
                break
            time.sleep(0.02)
        failpoints.clear("shard.dispatch")
        assert router._shards[0].batcher.dispatch_wedged()

        # one keep-alive connection, N pipelined POSTs back-to-back —
        # bursts land on BOTH shards (the dead one still enqueues until
        # the heartbeat fences it)
        n = 12
        wire = b""
        for i in range(n):
            body = json_mod.dumps(pod_review_body(i % 2 == 0)).encode()
            wire += (
                b"POST /validate/pod-privileged HTTP/1.1\r\n"
                b"Host: t\r\nContent-Type: application/json\r\n"
                b"Content-Length: " + str(len(body)).encode() + b"\r\n"
                b"\r\n" + body
            )
        conn = socket.create_connection(
            ("127.0.0.1", handle.server.api_port), timeout=30
        )
        conn.sendall(wire)

        buf = b""
        statuses: list[int] = []
        verdicts: list[bool | None] = []
        for _ in range(n):
            while b"\r\n\r\n" not in buf:
                chunk = conn.recv(65536)
                assert chunk, "peer closed mid-pipeline"
                buf += chunk
            head, buf = buf.split(b"\r\n\r\n", 1)
            lines = head.decode("latin-1").split("\r\n")
            status = int(lines[0].split(" ", 2)[1])
            clen = 0
            for ln in lines[1:]:
                k, _, v = ln.partition(":")
                if k.strip().lower() == "content-length":
                    clen = int(v.strip())
            while len(buf) < clen:
                chunk = conn.recv(65536)
                assert chunk, "peer closed mid-body"
                buf += chunk
            body, buf = buf[:clen], buf[clen:]
            statuses.append(status)
            verdicts.append(
                json_mod.loads(body)["response"]["allowed"]
                if status == 200 else None
            )
        # every pipelined slot answered 200 IN ORDER with the verdict of
        # ITS OWN request — a re-route that desynced positional
        # attribution would flip a verdict parity here
        assert statuses == [200] * n, statuses
        for i, allowed in enumerate(verdicts):
            assert allowed is (i % 2 != 0), (i, verdicts)
        stats = router.stats_snapshot()
        assert stats["shard_fences"] >= 1, stats
    finally:
        if conn is not None:
            conn.close()
        handle.stop()


def test_shard_heartbeat_probe_fault_fences_then_self_heals():
    """An armed shard.heartbeat probe fault at server level (the FP04
    chaos surface for the site): the router's next beat fences the
    probed shard WITHOUT a respawn (its dispatch loop is alive — a
    probe fault is evidence of sickness, not a wedge), traffic keeps
    answering 200 off the sibling, and the beat after the fault clears
    re-marks the shard healthy."""
    import requests as rq

    from test_server import ServerHandle, make_config, pod_review_body

    handle = ServerHandle(
        make_config(serving_shards=2, shard_heartbeat_seconds=0.2)
    )
    try:
        router = handle.server.batcher

        def sick():
            raise RuntimeError("injected probe fault")

        failpoints.set_failpoint(
            "shard.heartbeat", sick, count=1, scope="shard-1"
        )
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            stats = router.stats_snapshot()
            if stats["shard_heartbeat_faults"] >= 1:
                break
            time.sleep(0.02)
        stats = router.stats_snapshot()
        assert stats["shard_heartbeat_faults"] >= 1, stats
        assert stats["shard_fences"] >= 1, stats

        # traffic through the fenced window still answers correctly
        for i in range(8):
            privileged = i % 2 == 0
            r = rq.post(
                handle.url("/validate/pod-privileged"),
                json=pod_review_body(privileged), timeout=30,
            )
            assert r.status_code == 200, (i, r.status_code)
            assert r.json()["response"]["allowed"] is (not privileged)

        # fault was count=1: the next clean beat self-heals the shard
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if all(h["healthy"] for h in router.shard_health()):
                break
            time.sleep(0.05)
        health = router.shard_health()
        assert all(h["healthy"] and h["dispatch_alive"] for h in health), (
            health
        )
        # probe faults never respawn — the dispatch loop was never dead
        assert router.stats_snapshot()["shard_respawns"] == 0
    finally:
        handle.stop()
