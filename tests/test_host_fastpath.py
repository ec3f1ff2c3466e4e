"""Host latency fast-path tests (round-4 VERDICT item 1).

The serving layer answers small micro-batches with the targeted host oracle
instead of a device dispatch — the batched analog of the reference's
per-request sync path (src/api/handlers.rs:256-286). These tests pin the
two load-bearing properties:

1. bit-exactness: ``validate_batch(prefer_host=True)`` must produce
   responses identical to the device path for every verdict shape
   (accept, reject, group causes, mutation);
2. routing: the MicroBatcher takes the fast-path exactly when batch
   occupancy is at or below the threshold, and never when disabled.
"""

from __future__ import annotations

import threading

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
