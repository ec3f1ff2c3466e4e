"""Dispatch-watchdog tests: device execution (compile stall, transport
hang) is bounded by the per-request deadline, mirroring the reference's
mid-execution epoch interrupt (src/lib.rs:176-190, "execution deadline
exceeded" in tests/integration_test.rs:417). No request future may outlive
``policy_timeout`` unresolved, and a wedged device call must not take the
dispatch loop down with it."""

from __future__ import annotations

import threading
import time

import pytest

from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.evaluation.environment import EvaluationEnvironmentBuilder
from policy_server_tpu.models import AdmissionReviewRequest, ValidateRequest
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.runtime.batcher import DEADLINE_MESSAGE, MicroBatcher
from policy_server_tpu.telemetry import metrics as metrics_mod

from conftest import build_admission_review_dict


@pytest.fixture(autouse=True)
def fresh_metrics():
    metrics_mod.reset_metrics_for_tests()
    yield
    metrics_mod.reset_metrics_for_tests()


def review() -> ValidateRequest:
    return ValidateRequest.from_admission(
        AdmissionReviewRequest.from_dict(build_admission_review_dict()).request
    )


def wedge_device_half(env, gate_fn):
    """Wrap whichever callable the batcher's device path will block on —
    validate_batch_finish on the split (double-buffered) native pipeline,
    validate_batch otherwise — so a simulated hang/stall lands exactly
    where a real device wait would. Returns an undo callable."""
    if env.native_encoding:
        real = env.validate_batch_finish

        def wrapped(handle):
            gate_fn()
            return real(handle)

        env.validate_batch_finish = wrapped
        return lambda: setattr(env, "validate_batch_finish", real)
    real = env.validate_batch

    def wrapped(items, run_hooks=True, live=False):
        gate_fn()
        return real(items, run_hooks=run_hooks, live=live)

    env.validate_batch = wrapped
    return lambda: setattr(env, "validate_batch", real)


@pytest.fixture()
def env():
    policies = {
        "ns": parse_policy_entry(
            "ns",
            {
                "module": "builtin://namespace-validate",
                "settings": {"denied_namespaces": ["blocked"]},
            },
        ),
    }
    return EvaluationEnvironmentBuilder(backend="jax").build(policies)


def test_hung_device_call_rejects_in_band_and_loop_survives(env):
    """A transport hang (device results never arriving) must resolve every
    waiting future with the deadline rejection within ~policy_timeout, and
    the NEXT batch must still be served (the hang wedges one device-pool
    worker, not the dispatch loop)."""
    release = threading.Event()
    hang_once = {"armed": True}

    def gate():
        if hang_once["armed"]:
            hang_once["armed"] = False
            release.wait(timeout=30)  # simulated hung device_get

    undo = wedge_device_half(env, gate)
    batcher = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=0.5,
        host_fastpath_threshold=0,  # these tests exercise the DEVICE path
        latency_budget_ms=0,  # keep the budget router from bypassing it
    ).start()
    try:
        t0 = time.perf_counter()
        fut = batcher.submit("ns", review(), RequestOrigin.VALIDATE)
        resp = fut.result(timeout=5)  # watchdog, not the hang, bounds this
        elapsed = time.perf_counter() - t0
        assert resp.allowed is False
        assert resp.status.code == 500
        assert DEADLINE_MESSAGE in resp.status.message
        assert elapsed < 3.0
        assert batcher.deadline_abandoned_batches == 1
        # loop is alive: a second submission dispatches on a fresh worker
        fut2 = batcher.submit("ns", review(), RequestOrigin.VALIDATE)
        assert fut2.result(timeout=10).allowed is True
    finally:
        release.set()
        batcher.shutdown()
        undo()


def test_cold_bucket_compile_stall_bounded_then_fast(env):
    """A compile stall on a cold (schema × batch) bucket: the first request
    is deadline-rejected in-band while compilation finishes in the
    background; once warm, the same bucket serves within the deadline."""
    stall = {"first": True}

    def gate():
        if stall["first"]:
            stall["first"] = False
            time.sleep(1.2)  # simulated cold-bucket XLA compile

    # a compile stall surfaces in the HOST half (the jit dispatch runs in
    # validate_batch_begin) — wedge that half on the split pipeline so
    # this test proves the encode-stage watchdog too
    if env.native_encoding:
        real_begin = env.validate_batch_begin

        def stalling_begin(items, run_hooks=True, live=False):
            gate()
            return real_begin(items, run_hooks=run_hooks, live=live)

        env.validate_batch_begin = stalling_begin
        undo = lambda: setattr(env, "validate_batch_begin", real_begin)  # noqa: E731
    else:
        undo = wedge_device_half(env, gate)
    batcher = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=0.4,
        host_fastpath_threshold=0,
        latency_budget_ms=0,
    ).start()
    try:
        cold = batcher.submit("ns", review(), RequestOrigin.VALIDATE)
        resp = cold.result(timeout=5)
        assert resp.status.code == 500
        assert DEADLINE_MESSAGE in resp.status.message
        time.sleep(1.3)  # let the background compile finish
        warm = batcher.submit("ns", review(), RequestOrigin.VALIDATE)
        assert warm.result(timeout=10).allowed is True
    finally:
        batcher.shutdown()
        undo()


def test_timeout_disabled_keeps_unbounded_execution(env):
    """``--policy-timeout 0`` disables the deadline (src/cli.rs:164-169):
    a slow device call then completes normally instead of being cut."""
    real = env.validate_batch

    def slow_validate_batch(items, run_hooks=True, live=False):
        time.sleep(0.3)
        return real(items, run_hooks=run_hooks, live=live)

    env.validate_batch = slow_validate_batch
    batcher = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0, policy_timeout=None,
        host_fastpath_threshold=0,
        latency_budget_ms=0,
    ).start()
    try:
        fut = batcher.submit("ns", review(), RequestOrigin.VALIDATE)
        assert fut.result(timeout=10).allowed is True
        assert batcher.deadline_abandoned_batches == 0
    finally:
        batcher.shutdown()
        env.validate_batch = real


def test_partial_expiry_late_items_still_served(env):
    """Items with later deadlines stay live after earlier items expire:
    the watchdog rejects progressively, not batch-at-once."""
    release = threading.Event()
    entered = threading.Event()
    calls = {"n": 0}

    def gate():
        calls["n"] += 1
        if calls["n"] == 1:
            entered.set()
            release.wait(timeout=30)

    undo = wedge_device_half(env, gate)
    # max_batch_size=1 → each submission is its own batch; the first wedges
    # one device worker, the second runs concurrently on another.
    batcher = MicroBatcher(
        env, max_batch_size=1, batch_timeout_ms=0.1, policy_timeout=0.6,
        host_fastpath_threshold=0,
        latency_budget_ms=0,
    ).start()
    try:
        doomed = batcher.submit("ns", review(), RequestOrigin.VALIDATE)
        # wait until doomed's device half is provably the wedged one —
        # submitting both back-to-back would race which batch's device
        # half reaches the gate first (wider window under the split
        # pipeline, whose host half does real encode work)
        assert entered.wait(timeout=5), "doomed batch never reached device"
        ok = batcher.submit("ns", review(), RequestOrigin.VALIDATE)
        assert ok.result(timeout=10).allowed is True
        resp = doomed.result(timeout=5)
        assert resp.status.code == 500
        assert DEADLINE_MESSAGE in resp.status.message
    finally:
        release.set()
        batcher.shutdown()
        undo()
