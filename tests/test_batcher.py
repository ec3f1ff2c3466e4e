"""Micro-batcher tests: batched verdicts match the single-request path,
mixed-policy batching, deadline protection (the reference's sleeping-policy
timeout tests, tests/integration_test.rs:367-423), and overload behavior."""

from __future__ import annotations

import threading
import time

import pytest

from policy_server_tpu.api.service import RequestOrigin, evaluate
from policy_server_tpu.evaluation.environment import (
    EvaluationEnvironmentBuilder,
    bucket_size,
)
from policy_server_tpu.models import (
    AdmissionResponse,
    AdmissionReviewRequest,
    ValidateRequest,
)
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.runtime.batcher import DEADLINE_MESSAGE, MicroBatcher
from policy_server_tpu.telemetry import flightrec
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


@pytest.fixture(scope="module")
def env():
    policies = {
        "priv": parse_policy_entry("priv", {"module": "builtin://pod-privileged"}),
        "ns": parse_policy_entry(
            "ns",
            {
                "module": "builtin://namespace-validate",
                "settings": {"denied_namespaces": ["blocked"]},
            },
        ),
        "grp": parse_policy_entry(
            "grp",
            {
                "expression": "happy() || priv()",
                "message": "group denied",
                "policies": {
                    "happy": {"module": "builtin://always-happy"},
                    "priv": {"module": "builtin://pod-privileged"},
                },
            },
        ),
    }
    return EvaluationEnvironmentBuilder(backend="jax").build(policies)


def test_warmup_rtt_seed_normalized_by_warmup_dispatches():
    """ADVICE r5 #4: the warmup RTT seed divides by the environment's own
    per-warmup dispatch count (schemas × shards for the sharded
    evaluator), not by a schemas attribute the evaluator may not expose —
    the old code overestimated per-dispatch RTT by shards×schemas and
    biased early routing host-side."""
    import time as _time

    class FakeShardedEnv:
        """Duck-typed evaluator: warmup costs a fixed wall per dispatch,
        exposes warmup_dispatches like PolicyShardedEvaluator (no
        ``schemas`` attribute, like the real sharded evaluator)."""

        supports_host_fastpath = True
        warmup_dispatches = 6  # e.g. 3 shards × 2 schemas
        PER_DISPATCH_S = 0.01

        def warmup(self, batch_sizes=(1,)):
            _time.sleep(self.PER_DISPATCH_S * self.warmup_dispatches)

    env = FakeShardedEnv()
    batcher = MicroBatcher(
        env, max_batch_size=2, latency_budget_ms=50.0, policy_timeout=2.0
    )
    batcher.warmup()
    for bucket, rtt in batcher._dev_rtt.items():
        # the seed must approximate ONE dispatch (~10 ms), not the whole
        # shards×schemas warmup sweep (~60 ms)
        assert rtt < 3 * env.PER_DISPATCH_S, (bucket, rtt)
        assert rtt > 0


def test_sharded_evaluator_exposes_warmup_dispatches():
    from policy_server_tpu.evaluation.environment import (
        EvaluationEnvironment,
    )

    # one fused environment: one dispatch per schema per warmup call
    assert EvaluationEnvironment.warmup_dispatches.fget is not None


def test_bucket_size():
    assert [bucket_size(n) for n in (1, 2, 3, 5, 8, 9, 128)] == [
        1, 2, 4, 8, 8, 16, 128,
    ]


def test_batched_matches_single_path(env):
    batcher = MicroBatcher(env, host_fastpath_threshold=0, max_batch_size=16, batch_timeout_ms=5.0).start()
    try:
        cases = [
            ("priv", pod_review("default", True)),
            ("priv", pod_review("default", False)),
            ("ns", pod_review("blocked", False)),
            ("ns", pod_review("ok", False)),
            ("grp", pod_review("default", True)),
            ("grp", pod_review("default", False)),
        ]
        futures = [
            batcher.submit(pid, req, RequestOrigin.VALIDATE) for pid, req in cases
        ]
        batched = [f.result(timeout=30) for f in futures]
        single = [
            evaluate(env, pid, req, RequestOrigin.VALIDATE) for pid, req in cases
        ]
        for b, s in zip(batched, single):
            assert b.to_dict() == s.to_dict()
        # requests for different policies coalesced into few dispatches
        assert batcher.batches_dispatched <= 2
    finally:
        batcher.shutdown()


def test_concurrent_submissions_form_batches(env):
    batcher = MicroBatcher(env, host_fastpath_threshold=0, max_batch_size=32, batch_timeout_ms=20.0).start()
    try:
        results = [None] * 24
        def worker(i: int) -> None:
            req = pod_review("default", i % 2 == 0)
            results[i] = batcher.evaluate("priv", req, RequestOrigin.VALIDATE, timeout=30)
        threads = [threading.Thread(target=worker, args=(i,)) for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, resp in enumerate(results):
            assert resp.allowed == (i % 2 != 0)
        assert batcher.requests_dispatched == 24
        assert batcher.batches_dispatched < 24  # actually batched
    finally:
        batcher.shutdown()


def test_deadline_protection_sleeping_policy():
    """integration_test.rs:367-423: 100 ms sleep passes, long sleep exceeds
    the deadline and rejects in-band with 'execution deadline exceeded'."""
    env = EvaluationEnvironmentBuilder(backend="jax").build(
        {
            "sleep-ok": parse_policy_entry(
                "sleep-ok",
                {"module": "builtin://sleeping", "settings": {"sleep_ms": 100}},
            ),
            "sleep-long": parse_policy_entry(
                "sleep-long",
                {"module": "builtin://sleeping", "settings": {"sleep_ms": 4000}},
            ),
        }
    )
    # warm the fused program OUTSIDE the deadline: this test times the
    # sleeping HOOK against the deadline, and on a loaded CPU box a cold
    # first-dispatch compile alone can (correctly, but irrelevantly here)
    # blow the 0.5 s budget — it flaked ~1-in-3 under the full suite
    env.warmup((1, 4))
    batcher = MicroBatcher(
        env, host_fastpath_threshold=0,
        max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=0.5
    ).start()
    try:
        ok = batcher.evaluate(
            "sleep-ok", pod_review("default", False), RequestOrigin.VALIDATE,
            timeout=30,
        )
        assert ok.allowed
        slow = batcher.evaluate(
            "sleep-long", pod_review("default", False), RequestOrigin.VALIDATE,
            timeout=30,
        )
        assert not slow.allowed
        assert slow.status.message == DEADLINE_MESSAGE
        assert slow.status.code == 500
    finally:
        batcher.shutdown()


def test_unknown_policy_raises_through_future(env):
    batcher = MicroBatcher(env, host_fastpath_threshold=0, max_batch_size=4, batch_timeout_ms=1.0).start()
    try:
        from policy_server_tpu.evaluation.errors import PolicyNotFoundError

        fut = batcher.submit(
            "missing", pod_review("default", False), RequestOrigin.VALIDATE
        )
        with pytest.raises(PolicyNotFoundError):
            fut.result(timeout=30)
    finally:
        batcher.shutdown()


def test_overload_waits_then_rejects_in_band(env):
    """Queue-full behavior is a bounded WAIT (the reference waits on its
    semaphore, handlers.rs:262-266), then an in-band 429 — not an instant
    fast-reject that would fail closed on absorbable bursts."""
    import time as time_mod

    batcher = MicroBatcher(
        env, host_fastpath_threshold=0,
        max_batch_size=1, batch_timeout_ms=0.0,
        queue_capacity=1, policy_timeout=0.3,
    )
    # not started: the queue fills immediately
    first = batcher.submit("priv", pod_review("d", False), RequestOrigin.VALIDATE)
    t0 = time_mod.perf_counter()
    second = batcher.submit("priv", pod_review("d", False), RequestOrigin.VALIDATE)
    waited = time_mod.perf_counter() - t0
    assert waited >= 0.25, f"rejected without waiting ({waited:.3f}s)"
    assert not first.done()
    resp = second.result(timeout=1)
    assert not resp.allowed and resp.status.code == 429
    batcher.shutdown()


def test_overload_burst_absorbed_when_space_frees(env):
    """A submit that hits a momentarily-full queue succeeds once the
    dispatcher drains it (no spurious 429)."""
    batcher = MicroBatcher(
        env, host_fastpath_threshold=0,
        max_batch_size=1, batch_timeout_ms=0.0,
        queue_capacity=1, policy_timeout=2.0,
    )
    first = batcher.submit("priv", pod_review("d", False), RequestOrigin.VALIDATE)
    import threading as threading_mod

    started = threading_mod.Timer(0.05, batcher.start)
    started.start()
    # queue is full; the dispatcher starts 50ms in and drains it
    second = batcher.submit("priv", pod_review("d", True), RequestOrigin.VALIDATE)
    try:
        assert first.result(timeout=30).allowed is True
        assert second.result(timeout=30).allowed is False  # privileged
    finally:
        started.join()
        batcher.shutdown()


def test_submit_async_waits_without_blocking_loop(env):
    """submit_async polls for space on the event loop; a full queue that
    never drains resolves to 429 at the deadline."""
    import asyncio

    batcher = MicroBatcher(
        env, host_fastpath_threshold=0,
        max_batch_size=1, batch_timeout_ms=0.0,
        queue_capacity=1, policy_timeout=0.2,
    )
    batcher.submit("priv", pod_review("d", False), RequestOrigin.VALIDATE)

    async def go():
        fut = await batcher.submit_async(
            "priv", pod_review("d", False), RequestOrigin.VALIDATE
        )
        return await fut

    resp = asyncio.run(go())
    assert not resp.allowed and resp.status.code == 429
    batcher.shutdown()


def test_shutdown_does_not_close_shared_environment(env):
    """Regression (round-2 VERDICT weak #1): the batcher borrows its
    environment; shutting one batcher down must leave the env — and any
    other batcher sharing it — fully functional."""
    a = MicroBatcher(env, host_fastpath_threshold=0, max_batch_size=4, batch_timeout_ms=1.0).start()
    b = MicroBatcher(env, host_fastpath_threshold=0, max_batch_size=4, batch_timeout_ms=1.0).start()
    try:
        assert a.evaluate(
            "priv", pod_review("d", False), RequestOrigin.VALIDATE, timeout=30
        ).allowed
    finally:
        a.shutdown()
    # direct env path still works after a's shutdown
    (direct,) = env.validate_batch([("priv", pod_review("d", True))])
    assert direct.allowed is False
    # and so does the surviving batcher
    try:
        assert b.evaluate(
            "priv", pod_review("d", False), RequestOrigin.VALIDATE, timeout=30
        ).allowed
    finally:
        b.shutdown()


def test_closed_environment_fails_loudly():
    """A closed environment raises RuntimeError('environment closed') at the
    dispatch entry instead of AttributeError deep in the batch path."""
    owned = EvaluationEnvironmentBuilder(backend="jax").build(
        {"priv": parse_policy_entry("priv", {"module": "builtin://pod-privileged"})}
    )
    (ok,) = owned.validate_batch([("priv", pod_review("d", False))])
    assert ok.allowed
    owned.close()
    owned.close()  # idempotent
    with pytest.raises(RuntimeError, match="environment closed"):
        owned.validate_batch([("priv", pod_review("d", False))])


def test_shutdown_resolves_overload_waiters(env):
    """Regression (round-2 ADVICE medium): submit_async waiters parked on a
    full queue must all resolve during shutdown — none may strand an
    unresolved future after the drain empties the queue."""
    import asyncio

    batcher = MicroBatcher(
        env, host_fastpath_threshold=0,
        max_batch_size=1, batch_timeout_ms=0.0,
        queue_capacity=1, policy_timeout=None,  # unbounded waiters
    )
    # not started: queue fills and stays full
    batcher.submit("priv", pod_review("d", False), RequestOrigin.VALIDATE)

    async def go():
        futs = [
            await batcher.submit_async(
                "priv", pod_review("d", False), RequestOrigin.VALIDATE
            )
            for _ in range(12)  # > overload pool width of 8
        ]
        await asyncio.get_running_loop().run_in_executor(None, batcher.shutdown)
        return await asyncio.gather(*futs)

    responses = asyncio.run(asyncio.wait_for(go(), timeout=30))
    assert len(responses) == 12
    for r in responses:
        assert not r.allowed and r.status.code == 503
    # post-shutdown submissions reject immediately instead of hanging
    late = batcher.submit("priv", pod_review("d", False), RequestOrigin.VALIDATE)
    assert late.result(timeout=1).status.code == 503


def test_budget_routing_keeps_latency_under_budget(env):
    """Deadline-aware routing (VERDICT r4 #2): when the measured device
    round-trip would blow a request's latency budget and the host path
    would not, the batch is answered host-side. Mixed load against an
    artificially slow device: after the router learns the device RTT, no
    request the host path could serve waits past its budget."""
    import time

    SLOW_DEVICE_S = 0.25
    BUDGET_S = 0.10

    class SlowDeviceEnv:
        """Env proxy: device dispatches cost SLOW_DEVICE_S; the host
        fast-path answers at real host speed."""

        def __init__(self, inner):
            self._inner = inner
            self.device_batches = 0
            self.host_batches = 0

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def validate_batch(self, items, run_hooks=True, prefer_host=False,
                           live=False):
            if prefer_host:
                self.host_batches += 1
                return self._inner.validate_batch(
                    items, run_hooks=run_hooks, prefer_host=True, live=live
                )
            self.device_batches += 1
            time.sleep(SLOW_DEVICE_S)
            return self._inner.validate_batch(
                items, run_hooks=run_hooks, live=live
            )

        def validate_batch_finish(self, handle):
            # the split (double-buffered) pipeline blocks on device
            # results here — the simulated slowness must cover it too
            self.device_batches += 1
            time.sleep(SLOW_DEVICE_S)
            return self._inner.validate_batch_finish(handle)

    slow = SlowDeviceEnv(env)
    batcher = MicroBatcher(
        slow,
        max_batch_size=16,
        batch_timeout_ms=0.0,
        policy_timeout=5.0,
        host_fastpath_threshold=0,  # isolate the BUDGET tier from the
        latency_budget_ms=BUDGET_S * 1e3,  # occupancy tier
    ).start()
    try:
        # learning phase: the first dispatches go device-side (the seed
        # estimate comes from warmup, which this test skipped) and teach
        # the router the real RTT
        for _ in range(3):
            batcher.evaluate(
                "priv", pod_review("d", False), RequestOrigin.VALIDATE
            )
        assert slow.device_batches > 0

        # steady state: every batch must now route host-side and finish
        # inside the budget (generous 2x allowance for scheduling jitter)
        lats = []
        for _ in range(20):
            t0 = time.perf_counter()
            r = batcher.evaluate(
                "priv", pod_review("d", True), RequestOrigin.VALIDATE
            )
            lats.append(time.perf_counter() - t0)
            assert not r.allowed  # privileged pod still denied correctly
        assert batcher.budget_routed_batches > 0
        assert max(lats) < 2 * BUDGET_S, (
            f"request waited {max(lats):.3f}s past its "
            f"{BUDGET_S}s budget: {lats}"
        )
    finally:
        batcher.shutdown()


def test_budget_routing_reprobes_after_decay(env):
    """The stored device estimate decays on every budget bypass, so a
    once-slow device is eventually re-probed instead of being pinned
    host-side forever. Drives _dispatch directly for determinism."""
    from concurrent.futures import Future

    from policy_server_tpu.runtime.batcher import _Pending

    batcher = MicroBatcher(
        env,
        max_batch_size=8,
        host_fastpath_threshold=0,
        latency_budget_ms=100.0,
        policy_timeout=None,  # inline dispatch path
    )
    bucket = bucket_size(2)
    batcher._dev_rtt[bucket] = 10.0  # pretend the device measured terrible
    for _ in range(5):
        batch = [
            _Pending(
                "priv", pod_review("d", False), RequestOrigin.VALIDATE,
                Future(),
            ),
            _Pending(
                "priv", pod_review("d", True), RequestOrigin.VALIDATE,
                Future(),
            ),
        ]
        now = time.perf_counter()
        batcher._dispatch(batch, True, (now, now, now))
        assert batch[0].future.result(timeout=5).allowed
        assert not batch[1].future.result(timeout=5).allowed
    assert batcher.budget_routed_batches == 5
    # each bypass decayed the estimate toward an eventual device re-probe
    assert batcher._dev_rtt[bucket] == pytest.approx(10.0 * 0.98**5)
    batcher.shutdown()


def test_rtt_estimator_discards_compile_bearing_samples():
    """Round-14 regression: a dispatch whose window traced a NEW columnar
    plane structure paid a one-time XLA compile — seconds for a mesh
    program — and feeding that one sample into the device-RTT EWMA made
    the budget router send every later batch host-side for the rest of
    the run. _observe_dispatch must discard samples whose window
    advanced the environment's plane_program_compiles counter."""

    class CompilingEnv:
        supports_host_fastpath = True
        plane_program_compiles = 0

    cenv = CompilingEnv()
    batcher = MicroBatcher(
        cenv, max_batch_size=8, latency_budget_ms=100.0, policy_timeout=2.0
    )
    bucket = bucket_size(4)
    batcher._dev_rtt[bucket] = 0.005  # compile-free warmup seed
    # window saw a compile: the 3 s reading is a trace+compile stall,
    # not the steady-state device cost — discarded
    snapshot = cenv.plane_program_compiles
    cenv.plane_program_compiles += 1
    batcher._observe_dispatch(False, bucket, 4, 3.0, compiles_before=snapshot)
    assert batcher._dev_rtt[bucket] == pytest.approx(0.005)
    # compile-free window: the sample feeds the EWMA normally
    batcher._observe_dispatch(
        False, bucket, 4, 0.009,
        compiles_before=cenv.plane_program_compiles,
    )
    assert batcher._dev_rtt[bucket] == pytest.approx(
        0.7 * 0.005 + 0.3 * 0.009
    )
    # a watchdog-abandoned (lower-bound) sample is discarded too when
    # its window compiled — the program exists now; the stall won't recur
    snapshot = cenv.plane_program_compiles
    cenv.plane_program_compiles += 1
    batcher._observe_dispatch(
        False, bucket, 4, 60.0, lower_bound=True, compiles_before=snapshot
    )
    assert batcher._dev_rtt[bucket] < 1.0
    # environments WITHOUT the counter (host oracle, older shims) keep
    # the pre-round-14 behavior: getattr defaults to 0 == compiles_before
    # and every sample feeds in
    class CounterlessEnv:
        supports_host_fastpath = True

    legacy = MicroBatcher(
        CounterlessEnv(), max_batch_size=8, latency_budget_ms=100.0,
        policy_timeout=2.0,
    )
    legacy._dev_rtt[bucket] = 0.005
    legacy._observe_dispatch(False, bucket, 4, 0.02, compiles_before=0)
    assert legacy._dev_rtt[bucket] == pytest.approx(
        0.7 * 0.005 + 0.3 * 0.02
    )


# ---------------------------------------------------------------------------
# fragment fast lane (round 19: pre-serialized cache hits)
# ---------------------------------------------------------------------------


def test_fragment_lane_serves_hits_with_metrics(env):
    """Warm replays through the fused batcher pipeline answer as
    fragment hits: the counter moves, sink verdicts stay correct for
    allowed AND denied shapes, the futures path still yields full
    AdmissionResponses, and every row's evaluation metric is recorded
    (the memoized-metric lane must not drop counts)."""
    import threading as _threading
    import time

    from policy_server_tpu.api import service as service_mod

    batcher = MicroBatcher(
        env,
        max_batch_size=8,
        batch_timeout_ms=1.0,
        policy_timeout=5.0,
        host_fastpath_threshold=0,
        latency_budget_ms=0,
    ).start()

    class Sink:
        def __init__(self):
            self.got = {}
            self.lock = _threading.Lock()

        def deliver_many(self, items):
            with self.lock:
                for token, resp, exc in items:
                    self.got[token] = (resp, exc)

    try:
        items = [
            ("priv", pod_review("default", privileged=False)),
            ("priv", pod_review("default", privileged=True)),
            ("ns", pod_review("blocked", privileged=False)),
        ] * 4
        frag_before = env.dedup_stats["fragment_hits"]
        # wave 1 populates the row tier (misses), wave 2 hits
        for _wave in range(2):
            sink = Sink()
            batcher.submit_many(
                items, RequestOrigin.VALIDATE, sink=sink,
                tokens=list(range(len(items))),
            )
            deadline = time.perf_counter() + 30
            while time.perf_counter() < deadline:
                with sink.lock:
                    if len(sink.got) == len(items):
                        break
                time.sleep(0.005)
            assert len(sink.got) == len(items)
        assert env.dedup_stats["fragment_hits"] > frag_before
        # hit-wave verdicts: correct allowed/denied split with the
        # denial's status intact
        for i, (pid, _req) in enumerate(items):
            resp, exc = sink.got[i]
            assert exc is None
            if pid == "priv" and i % 3 == 1:
                assert resp.allowed is False
                assert resp.status.code == 400
            elif pid == "ns":
                assert resp.allowed is False
            else:
                assert resp.allowed is True
        # futures path converts fragments back to AdmissionResponse
        fut = batcher.submit(
            "priv", pod_review("default", privileged=True),
            RequestOrigin.VALIDATE,
        )
        resp = fut.result(timeout=30)
        assert type(resp).__name__ == "AdmissionResponse"
        assert resp.allowed is False
        # metrics recorded for every delivered row (memoized lane incl.)
        reg = service_mod._registry()
        total = reg.counter_value(metrics_mod.EVALUATIONS_TOTAL)
        assert total >= 2 * len(items) + 1
    finally:
        batcher.shutdown()


# ---------------------------------------------------------------------------
# where a batch waits: queue_wait's four parts on the flight recorder
# ---------------------------------------------------------------------------

QUEUE_PARTS = ("queue_take", "queue_fill", "slot_wait", "batch_pickup")


class _GatedEnv:
    """The duck-typed surface the batcher touches. ``validate_batch``
    holds every batch, and with it the batch's pipeline slot, until
    ``gate`` is set; ``held`` counts the batches it holds."""

    supports_host_fastpath = False
    always_accept_namespace = None

    def __init__(self):
        self.gate = threading.Event()
        self.held = 0
        self._lock = threading.Lock()

    def validate_batch(self, items, run_hooks=True, prefer_host=False,
                       audit=False, live=False):
        if not audit:
            with self._lock:
                self.held += 1
            self.gate.wait(30)
        return [AdmissionResponse(uid=req.uid(), allowed=True)
                for _pid, req in items]

    def pre_eval_hooks_of(self, target):
        return []

    def _lookup_top_level(self, pid):
        return object()

    def should_always_accept_requests_made_inside_of_namespace(self, ns):
        return False

    def get_policy_mode(self, pid):
        from policy_server_tpu.models.policy import PolicyMode

        return PolicyMode.PROTECT

    def get_policy_allowed_to_mutate(self, pid):
        return False


def _ring_by_batch(rec) -> dict[int, dict[str, tuple[int, int]]]:
    out: dict[int, dict[str, tuple[int, int]]] = {}
    for e in rec.snapshot():
        if e["kind"] == "batch" and e["batch"] >= 0:
            out.setdefault(e["batch"], {})[e["phase"]] = (
                e["start_ns"], e["end_ns"])
    return out


def _wait_until(condition, timeout: float = 10.0) -> None:
    until = time.perf_counter() + timeout
    while not condition():
        assert time.perf_counter() < until, "timed out"
        time.sleep(0.005)


def _fifth_batch_behind_four() -> list[dict[str, tuple[int, int]]]:
    """Five one-row batches through a live loop: the first four each find
    a slot and hold it in the environment, the fifth is taken and filled
    and then waits for a slot until the gate opens. → the five batches'
    ring intervals, in the order they were formed."""
    env = _GatedEnv()
    rec = flightrec.install(flightrec.FlightRecorder(capacity=4096))
    b = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=30.0,
        host_fastpath_threshold=0, latency_budget_ms=0,
    ).start()
    try:
        futs = []
        for k in range(4):
            futs.append(b.submit("priv", pod_review("default", False),
                                 RequestOrigin.VALIDATE))
            _wait_until(lambda: env.held == k + 1)
        futs.append(b.submit("priv", pod_review("default", False),
                             RequestOrigin.VALIDATE))
        _wait_until(lambda: b._queue.qsize() == 0)
        time.sleep(0.05)  # the loop's thread, blocked on the semaphore
        assert env.held == 4
        env.gate.set()
        for f in futs:
            assert f.result(timeout=30).allowed
    finally:
        env.gate.set()
        b.shutdown()
        flightrec.install(None)
    batches = [phs for _bid, phs in sorted(_ring_by_batch(rec).items())]
    assert len(batches) == 5
    return batches


def test_queue_waits_four_parts_abut_and_cover_it_to_the_nanosecond():
    for phs in _fifth_batch_behind_four():
        start, end = phs["queue_wait"]
        at = start
        for part in QUEUE_PARTS:
            s, e = phs[part]
            assert s == at and e >= s, (part, phs)
            at = e
        assert at == end


def test_a_batch_that_found_a_slot_free_waited_for_none():
    batches = _fifth_batch_behind_four()
    for phs in batches[:4]:
        s, e = phs["slot_wait"]
        assert e - s == 0
        assert phs["queue_fill"][1] - phs["queue_take"][1] > 0


def test_the_fifth_batch_waits_for_a_slot_while_four_are_held():
    fifth = _fifth_batch_behind_four()[4]
    s, e = fifth["slot_wait"]
    assert e - s >= 40_000_000  # at least the 50 ms the four were held
    # and it was most of the fifth batch's wait
    start, end = fifth["queue_wait"]
    assert e - s > (end - start) / 2


def test_an_audit_job_records_none_of_queue_waits_parts():
    env = _GatedEnv()
    env.gate.set()
    rec = flightrec.install(flightrec.FlightRecorder(capacity=4096))
    b = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=30.0,
        host_fastpath_threshold=0, latency_budget_ms=0,
    ).start()
    try:
        pairs = [("priv", pod_review("default", False)) for _ in range(6)]
        assert len(b.submit_audit(pairs).result(timeout=30)) == 6
        live = b.submit("priv", pod_review("default", False),
                        RequestOrigin.VALIDATE)
        assert live.result(timeout=30).allowed
    finally:
        b.shutdown()
        flightrec.install(None)
    lane = [phs for phs in _ring_by_batch(rec).values()
            if "audit_dispatch" in phs]
    assert len(lane) == 1
    assert not {"queue_wait", *QUEUE_PARTS} & set(lane[0])
    # the live batch beside it has all four
    (served,) = [phs for phs in _ring_by_batch(rec).values()
                 if "queue_wait" in phs]
    assert set(QUEUE_PARTS) <= set(served)


def _one_batch_through(env) -> tuple[list[dict], object]:
    """One request through a live loop over ``env`` → the ring's batch
    events and the request's future."""
    rec = flightrec.install(flightrec.FlightRecorder(capacity=4096))
    b = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=30.0,
        host_fastpath_threshold=0, latency_budget_ms=0,
    ).start()
    try:
        fut = b.submit("priv", pod_review("default", False),
                       RequestOrigin.VALIDATE)
        _wait_until(fut.done)
    finally:
        b.shutdown()
        flightrec.install(None)
    return [e for e in rec.snapshot() if e["kind"] == "batch"], fut


def test_the_parts_are_stamped_once_the_batch_is_answered():
    """They cost the batch's requests nothing: the four ring writes come
    after the batch's delivery."""
    env = _GatedEnv()
    env.gate.set()
    events, fut = _one_batch_through(env)
    assert fut.result().allowed
    seq = {e["phase"]: e["seq"] for e in events}
    assert seq["queue_wait"] < seq["form"] < seq["deliver"]
    assert all(seq[part] > seq["deliver"] for part in QUEUE_PARTS)


class _RaisingEnv(_GatedEnv):
    def validate_batch(self, items, **_kw):
        raise RuntimeError("planted")


def test_a_batch_whose_evaluation_raised_records_all_four():
    events, fut = _one_batch_through(_RaisingEnv())
    with pytest.raises(RuntimeError, match="planted"):
        fut.result()
    phs = {e["phase"]: (e["start_ns"], e["end_ns"]) for e in events}
    start, end = phs["queue_wait"]
    assert phs["queue_take"][0] == start and phs["batch_pickup"][1] == end
    assert set(QUEUE_PARTS) <= set(phs)
