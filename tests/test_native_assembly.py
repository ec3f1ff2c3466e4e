"""Differential corpus for batch-granular native response assembly
(round 19): every response byte the C++ serializer emits must equal the
Python responder's ``json.dumps(envelope.to_dict())`` — across the
builtin family catalog (mutation patches included), the constraint-skip
(audit-origin) path, cache-hit fragment templates, and a synthetic sweep
of every natively-classified field shape. The corpus renders through
``httpfront_render_verdict`` — the SAME parse+emit path production's
bulk completion fill uses — so what passes here is what serving sends.
"""

from __future__ import annotations

import json

import pytest

from policy_server_tpu.api import service
from policy_server_tpu.evaluation.environment import (
    EvaluationEnvironmentBuilder,
    fragment_responses,
)
from policy_server_tpu.models import (
    AdmissionResponse,
    AdmissionReviewResponse,
    FragTemplate,
    FragVerdict,
    RawReviewResponse,
    StatusCause,
    StatusDetails,
    ValidationStatus,
)
from policy_server_tpu.runtime import native_frontend as nf

from test_predicate_opt import FAMILY_CATALOG, _catalog_entries, _catalog_items

pytestmark = pytest.mark.skipif(
    not nf.native_available(), reason="native frontend unavailable"
)


def _python_bytes(r, raw_shape: bool = False) -> bytes:
    env = RawReviewResponse(r) if raw_shape else AdmissionReviewResponse(r)
    return json.dumps(env.to_dict()).encode()


def _native_bytes(r, raw_shape: bool = False) -> bytes | None:
    rec = (
        nf.pack_frag_record(1, r, raw_shape)
        if type(r) is FragVerdict
        else nf.pack_verdict_record(1, r, raw_shape)
    )
    if rec is None:
        return None
    out = nf.render_verdict_bytes(rec)
    assert out is not None, "packable record must render"
    return out


@pytest.fixture(scope="module")
def catalog_env():
    env = EvaluationEnvironmentBuilder(backend="jax").build(
        _catalog_entries()
    )
    yield env
    env.close()


@pytest.mark.parametrize("seed", [31, 77])
def test_family_catalog_responses_byte_exact(catalog_env, seed):
    """Raw verdicts across the family catalog — mutators' patches ride
    natively now — render byte-identical to the Python responder in
    both envelopes."""
    items = _catalog_items(40, seed)
    catalog_env.reset_verdict_cache()
    results = catalog_env.validate_batch(items)
    rendered = 0
    for (pid, _req), r in zip(items, results):
        if isinstance(r, Exception):
            continue
        for raw_shape in (False, True):
            got = _native_bytes(r, raw_shape)
            assert got is not None, (pid, r.to_dict())
            assert got == _python_bytes(r, raw_shape), (pid, r.to_dict())
        rendered += 1
    assert rendered > len(FAMILY_CATALOG)  # the sweep is not vacuous


def test_mutation_and_constraint_skip_paths(catalog_env):
    """The audit-vs-validate constraint fork on a mutating policy pinned
    not-allowed-to-mutate: /validate flips to reject+strip, /audit keeps
    allowed+patch — BOTH post-constraint responses must render natively
    byte-exact."""
    items = [
        item for item in _catalog_items(40, 5)
        if item[0] == "psp-capabilities"
    ]
    assert items, "catalog must carry the mutating family"
    catalog_env.reset_verdict_cache()
    results = catalog_env.validate_batch(items)
    saw_patch = False
    for (pid, req), vanilla in zip(items, results):
        if isinstance(vanilla, Exception):
            continue
        saw_patch = saw_patch or vanilla.patch is not None
        for origin in (
            service.RequestOrigin.VALIDATE,
            service.RequestOrigin.AUDIT,
        ):
            resp = service.post_evaluate(
                catalog_env, pid, req, origin, vanilla, 0.0, now=0.0
            )
            got = _native_bytes(resp)
            assert got is not None, (origin, resp.to_dict())
            assert got == _python_bytes(resp), (origin, resp.to_dict())
    assert saw_patch, "the mutation path never produced a patch"


def test_fragment_templates_byte_exact(catalog_env):
    """Cache-hit fragments (the blob/row-tier fast lane): the spliced
    uid+template record must render exactly what the Python responder
    would emit for the reconstructed response."""
    items = _catalog_items(30, 13)
    catalog_env.reset_verdict_cache()
    catalog_env.validate_batch(items)  # populate the blob tier
    with fragment_responses():
        results = catalog_env.validate_batch(items)
    frags = [r for r in results if type(r) is FragVerdict]
    assert frags, "warm catalog replay must serve fragments"
    assert any(not f.allowed for f in frags), "denial fragments too"
    for f in frags:
        for raw_shape in (False, True):
            got = _native_bytes(f, raw_shape)
            assert got is not None
            assert got == _python_bytes(f.to_response(), raw_shape)


# -- synthetic field-shape sweep (the classification's edge cases) ----------

_SYNTHETIC = [
    AdmissionResponse(uid="u", allowed=True),
    AdmissionResponse(uid="", allowed=False),
    AdmissionResponse.reject("u", "internal server error: boom", 500),
    AdmissionResponse(
        uid="u", allowed=False,
        status=ValidationStatus(message="m", code=400),
    ),
    AdmissionResponse(
        uid="u", allowed=False,
        status=ValidationStatus(message=None, code=403, reason="Forbidden"),
    ),
    AdmissionResponse(uid="u", allowed=False, status=ValidationStatus()),
    AdmissionResponse(
        uid="u", allowed=False,
        status=ValidationStatus(
            message="grp", code=400,
            details=StatusDetails(
                causes=(
                    StatusCause(field="spec.policies.a", message="bad"),
                    StatusCause(field=None, message="only-message"),
                    StatusCause(field="only-field", message=None),
                    StatusCause(),
                )
            ),
        ),
    ),
    AdmissionResponse(
        uid="u", allowed=False,
        status=ValidationStatus(
            message="empty causes", details=StatusDetails(causes=())
        ),
    ),
    AdmissionResponse(
        uid="u", allowed=True, patch_type="JSONPatch",
        patch="W3sib3AiOiAicmVwbGFjZSIsICJwYXRoIjogIiJ9XQ==",
    ),
    AdmissionResponse(uid="u", allowed=True, warnings=["w1", "w2"]),
    AdmissionResponse(uid="u", allowed=True, warnings=[]),
    AdmissionResponse(
        uid='q"uote\\back\n\t\x01\x7f', allowed=False,
        status=ValidationStatus(message="ünïcode \U0001f389 \u2028\x00", code=0),
    ),
    AdmissionResponse(
        uid="astral-𝔘𝔫𝔦", allowed=True, warnings=["wärn 🎉", ""],
    ),
]


@pytest.mark.parametrize("idx", range(len(_SYNTHETIC)))
@pytest.mark.parametrize("raw_shape", [False, True])
def test_synthetic_shapes_byte_exact(idx, raw_shape):
    r = _SYNTHETIC[idx]
    got = _native_bytes(r, raw_shape)
    assert got is not None, r.to_dict()
    assert got == _python_bytes(r, raw_shape), r.to_dict()


def test_python_only_shapes_decline_native():
    """The classified Python-only tail must refuse to pack — the oracle
    renders it (auditAnnotations, incoherent patchType, surrogates,
    negative codes colliding with the wire sentinel)."""
    declines = [
        AdmissionResponse(
            uid="u", allowed=True, audit_annotations={"k": "v"}
        ),
        AdmissionResponse(uid="u", allowed=True, patch_type="JSONPatch"),
        AdmissionResponse(uid="u", allowed=True, patch="cGF0Y2g="),
        AdmissionResponse(uid="\udcff-surrogate", allowed=True),
        AdmissionResponse(
            uid="u", allowed=False,
            status=ValidationStatus(message="m", code=-7),
        ),
        AdmissionResponse(uid="u", allowed=True, warnings=["w"] * 256),
    ]
    for r in declines:
        assert nf.pack_verdict_record(1, r, False) is None, r.to_dict()


def test_classification_is_total_over_the_model():
    """RS01's runtime twin: every AdmissionResponse / ValidationStatus
    field is classified native or python-only — a new model field
    without a classification fails here before it fails make check."""
    resp_fields = set(AdmissionResponse.__dataclass_fields__)
    assert resp_fields == (
        set(nf.NATIVE_RESPONSE_FIELDS) | set(nf.PYTHON_ONLY_RESPONSE_FIELDS)
    )
    status_fields = set(ValidationStatus.__dataclass_fields__)
    assert status_fields == (
        set(nf.NATIVE_STATUS_FIELDS) | set(nf.PYTHON_ONLY_STATUS_FIELDS)
    )


def test_malformed_records_answer_minus_one_not_crash():
    """The native emitter is exported for arbitrary test input: length
    fields that wrap signed sentinels (warning len >= 2^31) or giant
    cause counts must answer None (C -1), never crash the process.

    Round 21: the cases live in tools/fuzz_native.py's shared
    verdict_record_corpus() — the same seeds the structure-aware fuzzer
    mutates under ``make sanitize``, so the unit test and the fuzzer can
    never drift apart."""
    from tools.fuzz_native import verdict_record_corpus

    corpus = verdict_record_corpus()
    # the promoted round-19 regressions must still be in the corpus
    assert {n for n, _, e in corpus if e == "reject"} >= {
        "r19-warnlen-topbit", "r19-warnlen-oversize",
        "r19-causes-giant", "r19-truncated",
    }
    for name, record, expect in corpus:
        rendered = nf.render_verdict_bytes(record)
        if expect == "reject":
            assert rendered is None, name
        else:
            assert rendered is not None, name
    # a model-packed record still renders after all that
    ok = nf.pack_verdict_record(1, AdmissionResponse(uid="u", allowed=True), False)
    assert nf.render_verdict_bytes(ok) is not None


def test_surrogate_static_message_falls_back_to_python(catalog_env):
    """A fragment-eligible target whose STATIC message carries a lone
    surrogate (json can represent it, utf-8 cannot encode it) must mark
    itself python-only at template build — not fail the batch."""
    from unittest import mock

    env = catalog_env
    target = env._fast_target("pod-privileged")
    row = {"p:pod-privileged:allowed": False, "p:pod-privileged:rule": 0}
    bad = AdmissionResponse(
        uid="", allowed=False,
        status=ValidationStatus(message="\ud800bad", code=400),
    )
    env._frag_lanes.pop(id(target), None)
    with mock.patch.object(env, "_materialize_from_row", return_value=bad):
        assert env._frag_of(target, row) is None
    # memoized permanently ineligible for THIS verdict of this target, in
    # the target's memo (the row is left as it was put)
    (_own, memo), _packed = env._frag_lanes[id(target)]
    assert memo == {(False, 0): False}
    assert set(row) == {"p:pod-privileged:allowed", "p:pod-privileged:rule"}
    env._frag_lanes.pop(id(target))  # the catalog env is shared
    # and the per-row Python renderer handles the shape fine
    assert nf.pack_verdict_record(1, bad, False) is None
    assert json.dumps(AdmissionReviewResponse(bad).to_dict())


def test_out_of_range_status_code_declines_native():
    """A policy-controlled code outside i32 (wasm host verdicts carry
    arbitrary ints) must take the Python renderer, not raise
    struct.error out of a future done-callback."""
    for code in (2**31, 2**40, -7):
        r = AdmissionResponse(
            uid="u", allowed=False,
            status=ValidationStatus(message="m", code=code),
        )
        assert nf.pack_verdict_record(1, r, False) is None, code
        # the Python path serializes it fine
        assert json.dumps(AdmissionReviewResponse(r).to_dict())
    t = FragTemplate(False, 2**31, "m")
    assert nf.pack_frag_record(1, FragVerdict("u", t), False) is None
