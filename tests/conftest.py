"""Test configuration.

Forces the JAX CPU backend with 8 virtual devices so mesh/sharding tests run
without TPU hardware — the stand-in for a v5e-8, mirroring how the reference
uses in-process port-0 servers to stand in for a deployment (SURVEY.md §4.2).
Must run before the first ``import jax`` anywhere in the test session.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# graftcheck lock-order sanitizer ("tsan-lite"): when armed (make chaos
# sets GRAFTCHECK_LOCKSAN=1), every threading.Lock the package creates is
# wrapped to record per-thread acquisition order; the session-scoped
# fixture below errors the run on any inversion. Must install BEFORE any
# package module constructs a lock. Zero-cost (never imported) when off.
_LOCKSAN = os.environ.get("GRAFTCHECK_LOCKSAN", "") not in ("", "0")
if _LOCKSAN:
    from policy_server_tpu import locksan

    locksan.install()

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _locksan_gate():
    """When the lock-order sanitizer is armed, FAIL the run on any
    lock-order inversion (a teardown assert in a session fixture errors
    the run without touching individual tests). Long holds are reported
    (pytest_terminal_summary below — fixture stdout is fd-captured and
    would never be shown) but do not fail — chaos tests inject sleeps on
    purpose; the invariant the gate enforces is acquisition ORDER."""
    yield
    if not _LOCKSAN:
        return
    from policy_server_tpu import locksan

    rep = locksan.report()
    assert not rep["inversions"], (
        "graftcheck locksan: lock-order inversion(s) detected: "
        f"{rep['inversions']}\n" + locksan.format_report(rep)
    )


def pytest_terminal_summary(terminalreporter):
    """Locksan statistics (acquisitions, order edges, inversions, long
    holds) on every armed run — the terminal reporter is the only
    channel pytest's fd-level capture does not swallow."""
    if not _LOCKSAN:
        return
    from policy_server_tpu import locksan

    terminalreporter.write_line("")
    for line in locksan.format_report().splitlines():
        terminalreporter.write_line(line)


@pytest.fixture
def admission_review_request():
    """Canned AdmissionReviewRequest (reference src/test_utils.rs:3-37:
    a Deployment 'nginx-deployment' scale UPDATE)."""
    from policy_server_tpu.models import AdmissionReviewRequest

    return AdmissionReviewRequest.from_dict(build_admission_review_dict())


def build_admission_review_dict() -> dict:
    return {
        "apiVersion": "admission.k8s.io/v1",
        "kind": "AdmissionReview",
        "request": {
            "uid": "hello",
            "kind": {"group": "autoscaling", "version": "v1", "kind": "Scale"},
            "resource": {"group": "apps", "version": "v1", "resource": "deployments"},
            "subResource": "scale",
            "requestKind": {"group": "autoscaling", "version": "v1", "kind": "Scale"},
            "requestResource": {
                "group": "apps",
                "version": "v1",
                "resource": "deployments",
            },
            "requestSubResource": "scale",
            "name": "my-deployment",
            "namespace": "my-namespace",
            "operation": "UPDATE",
            "userInfo": {
                "username": "admin",
                "uid": "014fbff9a07c",
                "groups": ["system:masters", "system:authenticated"],
            },
            "object": {
                "apiVersion": "autoscaling/v1",
                "kind": "Scale",
                "metadata": {"name": "my-deployment", "namespace": "my-namespace"},
                "spec": {"replicas": 2},
            },
            "oldObject": None,
            "dryRun": False,
            "options": None,
        },
    }


@pytest.fixture(scope="session")
def reference_gatekeeper_fixtures():
    """Upstream-compiled Gatekeeper wasm test policies (the reference's
    embedded fixtures). Skip when the reference snapshot isn't present —
    the repo's own WAT-authored wasm policies cover the hermetic case."""
    from pathlib import Path

    base = Path("/root/reference/tests/data")
    happy = base / "gatekeeper_always_happy_policy.wasm"
    unhappy = base / "gatekeeper_always_unhappy_policy.wasm"
    if not (happy.exists() and unhappy.exists()):
        pytest.skip("upstream gatekeeper wasm fixtures not available")
    return happy.read_bytes(), unhappy.read_bytes()
