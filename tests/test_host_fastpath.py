"""Host latency fast-path tests (round-4 VERDICT item 1).

The serving layer answers small micro-batches with the targeted host oracle
instead of a device dispatch — the batched analog of the reference's
per-request sync path (src/api/handlers.rs:256-286). These tests pin the
two load-bearing properties:

1. bit-exactness: ``validate_batch(prefer_host=True)`` must produce
   responses identical to the device path for every verdict shape
   (accept, reject, group causes, mutation);
2. routing: the MicroBatcher takes the fast-path for a batch at or under
   the threshold while the pipeline has a slot to spare (PR 40: a small
   batch that meets a full pipeline is throughput traffic and rides the
   device), and never when disabled.
"""

from __future__ import annotations

import threading
import time

import pytest

from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.evaluation.environment import EvaluationEnvironmentBuilder
from policy_server_tpu.models import AdmissionReviewRequest, ValidateRequest
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.runtime.batcher import MicroBatcher
from policy_server_tpu.telemetry import metrics as metrics_mod

from conftest import build_admission_review_dict


@pytest.fixture(autouse=True)
def fresh_metrics():
    metrics_mod.reset_metrics_for_tests()
    yield
    metrics_mod.reset_metrics_for_tests()


def pod_review(namespace: str, privileged: bool) -> ValidateRequest:
    doc = build_admission_review_dict()
    doc["request"]["namespace"] = namespace
    doc["request"]["object"] = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": "p", "namespace": namespace},
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


POLICIES = {
    "priv": {"module": "builtin://pod-privileged"},
    "ns": {
        "module": "builtin://namespace-validate",
        "settings": {"denied_namespaces": ["blocked"]},
    },
    "grp": {
        "expression": "happy() || priv()",
        "message": "group denied",
        "policies": {
            "happy": {"module": "builtin://always-unhappy"},
            "priv": {"module": "builtin://pod-privileged"},
        },
    },
}


@pytest.fixture(scope="module")
def env():
    return EvaluationEnvironmentBuilder(backend="jax").build(
        {n: parse_policy_entry(n, e) for n, e in POLICIES.items()}
    )


def host_route_answers(env) -> int:
    """Answers the host route has given: one answer, one source (PR 35), so
    a request a dedup tier answers on the host path is the tier's hit and
    not a ``host_fastpath_requests``; the route's answers are the three."""
    dedup = env.dedup_stats
    return (env.host_fastpath_requests + dedup["cache_hits"]
            + dedup["blob_cache_hits"])


def corpus() -> list[tuple[str, ValidateRequest]]:
    reqs = [
        pod_review("default", False),
        pod_review("default", True),
        pod_review("blocked", False),
        pod_review("blocked", True),
    ]
    return [(pid, r) for pid in ("priv", "ns", "grp") for r in reqs]


def test_fastpath_bit_exact_vs_device(env):
    """prefer_host responses must be byte-identical to device responses —
    the serving fast-path inherits the differential suite's guarantee."""
    items = corpus()
    device = env.validate_batch(items)
    before, rows = host_route_answers(env), env.host_profile["dispatched_rows"]
    host = env.validate_batch(items, prefer_host=True)
    # every host-route answer counted once, by the oracle or by the tier
    # the device pass filled; nothing reached the device
    assert host_route_answers(env) - before == len(items)
    assert env.host_profile["dispatched_rows"] == rows
    for (pid, _), d, h in zip(items, device, host):
        assert not isinstance(d, Exception), (pid, d)
        assert not isinstance(h, Exception), (pid, h)
        assert d.to_dict() == h.to_dict(), pid
    # the corpus exercises both verdicts and a group-cause rejection
    verdicts = {r.allowed for r in device if not isinstance(r, Exception)}
    assert verdicts == {True, False}


def test_fastpath_handles_unknown_policy(env):
    from policy_server_tpu.evaluation.errors import PolicyNotFoundError

    (res,) = env.validate_batch(
        [("missing", pod_review("default", False))], prefer_host=True
    )
    assert isinstance(res, PolicyNotFoundError)


def _mk_batcher(env, threshold, **kw):
    return MicroBatcher(
        env,
        max_batch_size=kw.pop("max_batch_size", 32),
        batch_timeout_ms=kw.pop("batch_timeout_ms", 1.0),
        policy_timeout=kw.pop("policy_timeout", 5.0),
        host_fastpath_threshold=threshold,
    ).start()


def test_batcher_small_batch_takes_fastpath(env):
    before = host_route_answers(env)
    b = _mk_batcher(env, threshold=64)
    try:
        res = b.evaluate("priv", pod_review("default", True), RequestOrigin.VALIDATE)
        assert res.allowed is False
        res = b.evaluate("grp", pod_review("default", False), RequestOrigin.VALIDATE)
        assert res.allowed is True
        assert b.host_fastpath_batches >= 2
        assert host_route_answers(env) - before == 2
    finally:
        b.shutdown()


def test_batcher_large_batch_uses_device(env):
    """A batch above the threshold must ride the device path."""
    before = env.host_fastpath_requests
    b = _mk_batcher(env, threshold=2, max_batch_size=16, batch_timeout_ms=200.0)
    try:
        gate = threading.Barrier(9)
        futures = []

        def submit():
            gate.wait()
            futures.append(
                b.submit("priv", pod_review("default", False), RequestOrigin.VALIDATE)
            )

        threads = [threading.Thread(target=submit) for _ in range(8)]
        for t in threads:
            t.start()
        gate.wait()
        for t in threads:
            t.join()
        for f in futures:
            assert f.result(timeout=10).allowed is True
        # 8 concurrent submissions with a 200ms window form batches > 2:
        # at least one batch must have gone to the device
        assert env.host_fastpath_requests - before < 8
    finally:
        b.shutdown()


def test_batcher_fastpath_disabled(env):
    before = env.host_fastpath_requests
    b = _mk_batcher(env, threshold=0)
    try:
        res = b.evaluate("ns", pod_review("blocked", False), RequestOrigin.VALIDATE)
        assert res.allowed is False
        assert b.host_fastpath_batches == 0
        assert env.host_fastpath_requests == before
    finally:
        b.shutdown()


def test_batcher_fastpath_with_timeout_disabled(env):
    """policy_timeout=None (unbounded execution) still takes the fast-path."""
    b = _mk_batcher(env, threshold=64, policy_timeout=None)
    try:
        res = b.evaluate("priv", pod_review("default", True), RequestOrigin.VALIDATE)
        assert res.allowed is False
        assert b.host_fastpath_batches >= 1
    finally:
        b.shutdown()


def test_fastpath_bounded_by_watchdog(env):
    """A slow host evaluation (e.g. a wasm member whose fuel outlasts the
    wall-clock budget) must still resolve in-band at policy_timeout — the
    fast-path runs under the same dispatch watchdog as the device path."""
    import time

    from policy_server_tpu.runtime.batcher import DEADLINE_MESSAGE

    real = env.validate_batch

    def slow_validate_batch(items, run_hooks=True, prefer_host=False):
        time.sleep(2.0)  # simulated runaway host-side evaluation
        return real(items, run_hooks=run_hooks, prefer_host=prefer_host)

    env.validate_batch = slow_validate_batch
    b = _mk_batcher(env, threshold=64, policy_timeout=0.4)
    try:
        t0 = time.perf_counter()
        resp = b.evaluate(
            "priv", pod_review("default", False), RequestOrigin.VALIDATE
        )
        assert time.perf_counter() - t0 < 1.5
        assert resp.allowed is False
        assert resp.status.code == 500
        assert DEADLINE_MESSAGE in resp.status.message
        assert b.host_fastpath_batches >= 1  # it WAS the fast-path
    finally:
        env.validate_batch = real
        b.shutdown()


# -- the occupancy rule (PR 40): room in the pipeline, not rows in the batch ----


class _Routes:
    """Stands between a batcher and its two evaluators: notes the route
    each batch took and, while ``gate`` is clear, holds the batch there,
    so that it keeps its pipeline slot for as long as a test wants."""

    def __init__(self, b: MicroBatcher, env, monkeypatch):
        self.gate = threading.Event()
        self.gate.set()
        self.took: list[str] = []
        device, host = b._fused_validate, env.validate_batch

        def fused_validate(pairs):
            self.took.append("device")
            self.gate.wait(30)
            return device(pairs)

        def validate_batch(items, **kw):
            if kw.get("prefer_host"):
                self.took.append("host")
                self.gate.wait(30)
            return host(items, **kw)

        monkeypatch.setattr(b, "_fused_validate", fused_validate)
        monkeypatch.setattr(env, "validate_batch", validate_batch)


def _small_batch() -> list:
    from concurrent.futures import Future

    from policy_server_tpu.runtime.batcher import _Pending

    return [
        _Pending("priv", pod_review("default", privileged),
                 RequestOrigin.VALIDATE, Future())
        for privileged in (False, True)
    ]


def _wait_for(condition, timeout: float = 10.0) -> None:
    until = time.perf_counter() + timeout
    while not condition():
        assert time.perf_counter() < until, "timed out"
        time.sleep(0.005)


def _answered(batch: list) -> None:
    allowed = [p.future.result(timeout=30).allowed for p in batch]
    assert allowed == [True, False]


@pytest.fixture
def pipeline(env, monkeypatch):
    """A batcher whose dispatch loop is the test: batches are handed to
    the four-slot pipeline one by one (``_launch_batch``)."""
    b = MicroBatcher(env, max_batch_size=8, policy_timeout=30.0,
                     host_fastpath_threshold=4)
    routes = _Routes(b, env, monkeypatch)
    yield b, routes
    routes.gate.set()
    b.shutdown()


def test_a_small_batch_that_fills_the_pipeline_rides_the_device(pipeline):
    b, routes = pipeline
    routes.gate.clear()
    batches = [_small_batch() for _ in range(4)]
    for batch in batches[:3]:
        b._launch_batch(batch)
    _wait_for(lambda: len(routes.took) == 3)
    # three found a slot to spare beyond their own: the host's
    assert routes.took == ["host"] * 3 and b._batches_inflight == 3
    assert b.host_fastpath_declined_batches == 0
    b._launch_batch(batches[3])  # every other slot is held
    _wait_for(lambda: len(routes.took) == 4)
    assert routes.took[3] == "device"
    assert b.host_fastpath_declined_batches == 1
    assert b.host_fastpath_batches == 3
    routes.gate.set()
    for batch in batches:
        _answered(batch)
    # the same batch once the slots have drained: the host's again
    _wait_for(lambda: b._batches_inflight == 0)
    again = _small_batch()
    b._launch_batch(again)
    _answered(again)
    assert routes.took[4] == "host"
    stats = b.stats_snapshot()
    assert stats["host_fastpath_batches"] == 4
    assert stats["host_fastpath_declined_batches"] == 1


def test_a_small_batch_that_waited_for_a_slot_rides_the_device(pipeline):
    b, routes = pipeline
    routes.gate.clear()
    batches = [_small_batch() for _ in range(5)]
    for batch in batches[:4]:
        b._launch_batch(batch)
    _wait_for(lambda: len(routes.took) == 4)
    waiting = threading.Thread(target=b._launch_batch, args=(batches[4],))
    waiting.start()  # the loop's thread, blocked on the semaphore
    waiting.join(timeout=0.2)
    assert waiting.is_alive() and b._batches_inflight == 4
    routes.gate.set()
    waiting.join(timeout=30)
    for batch in batches:
        _answered(batch)
    # whatever had drained by the time it got its slot, it had queued
    # behind a full pipeline: throughput traffic
    assert routes.took == ["host"] * 3 + ["device"] * 2
    assert b.host_fastpath_declined_batches == 2
    _wait_for(lambda: b._batches_inflight == 0)


def test_a_batch_that_raises_gives_its_slot_back(pipeline, monkeypatch):
    b, routes = pipeline

    def broken(batch, has_room):
        raise RuntimeError("planted")

    monkeypatch.setattr(b, "_dispatch", broken)
    batches = [_small_batch() for _ in range(6)]  # more than the slots
    for batch in batches:
        b._launch_batch(batch)
        with pytest.raises(RuntimeError, match="planted"):
            batch[0].future.result(timeout=30)
    _wait_for(lambda: b._batches_inflight == 0)
    monkeypatch.undo()  # the evaluators and _dispatch as they were
    lone = _small_batch()
    b._launch_batch(lone)
    _answered(lone)
    assert b.host_fastpath_batches == 1  # the route was not switched off
    assert b.host_fastpath_declined_batches == 0


def test_a_batch_the_watchdog_abandons_gives_its_slot_back(env, monkeypatch):
    from policy_server_tpu.runtime.batcher import DEADLINE_MESSAGE

    b = MicroBatcher(env, max_batch_size=8, policy_timeout=0.3,
                     host_fastpath_threshold=4)
    routes = _Routes(b, env, monkeypatch)
    try:
        routes.gate.clear()
        batches = [_small_batch() for _ in range(4)]
        for batch in batches:
            b._launch_batch(batch)
        for batch in batches:
            for p in batch:
                response = p.future.result(timeout=30)
                assert response.status.code == 500
                assert DEADLINE_MESSAGE in response.status.message
        # the evaluators still hold their pool threads; the slots are back
        _wait_for(lambda: b._batches_inflight == 0)
        assert b.deadline_abandoned_batches == 4
        assert routes.took == ["host"] * 3 + ["device"]
        routes.gate.set()
        lone = _small_batch()
        b._launch_batch(lone)
        _answered(lone)
        assert routes.took[4] == "host"
    finally:
        routes.gate.set()
        b.shutdown()


def test_threshold_zero_never_counts_a_decline(env, monkeypatch):
    b = MicroBatcher(env, max_batch_size=8, policy_timeout=30.0,
                     host_fastpath_threshold=0)
    routes = _Routes(b, env, monkeypatch)
    try:
        routes.gate.clear()
        batches = [_small_batch() for _ in range(4)]
        for batch in batches:
            b._launch_batch(batch)
        _wait_for(lambda: len(routes.took) == 4)
        routes.gate.set()
        for batch in batches:
            _answered(batch)
        assert routes.took == ["device"] * 4
        assert b.host_fastpath_batches == 0
        assert b.host_fastpath_declined_batches == 0
    finally:
        routes.gate.set()
        b.shutdown()


def test_a_declined_batch_is_not_the_budget_tiers(env, monkeypatch):
    """The budget tier keeps the batches it had, those over the threshold:
    a small batch the full pipeline sent to the device goes there, even
    with a device estimate that blows the budget (``declined`` counts
    batches that rode the device, and ``budget_routed_batches`` stays
    the batches over the threshold)."""
    from policy_server_tpu.runtime.batcher import bucket_size

    b = MicroBatcher(env, max_batch_size=8, policy_timeout=None,
                     host_fastpath_threshold=4, latency_budget_ms=100.0)
    routes = _Routes(b, env, monkeypatch)
    try:
        b._dev_rtt[bucket_size(2)] = 10.0  # the device as slow as can be
        declined = _small_batch()
        b._dispatch(declined, has_room=False)
        _answered(declined)
        assert b.host_fastpath_declined_batches == 1
        assert b.budget_routed_batches == 0 and b.host_fastpath_batches == 0
        lone = _small_batch()
        b._dispatch(lone, has_room=True)
        _answered(lone)
        assert b.host_fastpath_batches == 1 and b.budget_routed_batches == 0
        assert routes.took == ["device", "host"]
    finally:
        b.shutdown()


def test_the_inflight_count_under_contention(env):
    """The loop's thread adds to the count and four batch workers take
    from it: after 300 small batches pushed through a live loop by more
    submitters than cores, with the interpreter switching threads forty
    times as often, no update is lost (the count is back at 0) and every
    batch was counted once, by the route it took."""
    import sys

    b = _mk_batcher(env, threshold=64, max_batch_size=4, batch_timeout_ms=0.0)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(interval / 40)
    try:
        def submitter():
            for _ in range(25):
                b.evaluate("priv", pod_review("default", False),
                           RequestOrigin.VALIDATE)

        threads = [threading.Thread(target=submitter) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        _wait_for(lambda: b._batches_inflight == 0)
        stats = b.stats_snapshot()
        assert stats["requests_dispatched"] == 300
        assert (stats["host_fastpath_batches"]
                + stats["host_fastpath_declined_batches"]
                == stats["batches_dispatched"])
        # every slot is back: four more can be taken at once, no fifth
        assert all(b._inflight.acquire(blocking=False) for _ in range(4))
        assert not b._inflight.acquire(blocking=False)
        for _ in range(4):
            b._inflight.release()
    finally:
        sys.setswitchinterval(interval)
        b.shutdown()


def test_sharded_evaluator_forwards_prefer_host():
    """PolicyShardedEvaluator forwards the fast-path to its shards."""
    import jax
    from policy_server_tpu.config.config import MeshSpec
    from policy_server_tpu.parallel import mesh as mesh_mod
    from policy_server_tpu.parallel.policy_sharded import PolicyShardedEvaluator

    devices = jax.devices()[:2]
    mesh = mesh_mod.make_mesh(MeshSpec.parse("data:1,policy:2"), devices)
    sharded = PolicyShardedEvaluator(
        {n: parse_policy_entry(n, e) for n, e in POLICIES.items() if n != "grp"},
        mesh,
    )
    assert sharded.supports_host_fastpath
    items = [(pid, pod_review("default", True)) for pid in ("priv", "ns")]
    device = sharded.validate_batch(items)
    before = sum(host_route_answers(e) for e in sharded._routing.shards)
    host = sharded.validate_batch(items, prefer_host=True)
    assert sum(host_route_answers(e)
               for e in sharded._routing.shards) - before == 2
    for d, h in zip(device, host):
        assert d.to_dict() == h.to_dict()
