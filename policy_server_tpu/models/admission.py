"""Kubernetes AdmissionReview data model.

Reference parity:
* ``AdmissionRequest`` — policy-evaluator's ``admission_request::AdmissionRequest``
  as used by the reference (src/api/handlers.rs:288-306, src/test_utils.rs:5-31).
* ``AdmissionResponse`` — policy-evaluator's ``admission_response::AdmissionResponse``
  (src/api/service.rs:60-68; src/evaluation/evaluation_environment.rs:979-1042).
* ``AdmissionReviewRequest`` / ``AdmissionReviewResponse`` —
  src/api/admission_review.rs:5-36 (response always ``admission.k8s.io/v1``).
* ``RawReviewRequest`` / ``RawReviewResponse`` — src/api/raw_review.rs:5-20.
* ``ValidateRequest`` — the enum wrapper over AdmissionRequest | raw JSON
  (SURVEY.md §2.2), carried down to the evaluation layer.

These are plain host-side types; the tensor codec (ops/codec.py) flattens them
for the device. JSON field names use Kubernetes camelCase on the wire and
snake_case in Python.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Mapping


def _drop_none(d: dict[str, Any]) -> dict[str, Any]:
    return {k: v for k, v in d.items() if v is not None}


@dataclass(frozen=True)
class GroupVersionKind:
    """K8s GroupVersionKind (AdmissionRequest.kind / requestKind)."""

    group: str = ""
    version: str = ""
    kind: str = ""

    @classmethod
    def from_dict(cls, d: Mapping[str, Any] | None) -> "GroupVersionKind | None":
        if d is None:
            return None
        return cls(
            group=d.get("group", "") or "",
            version=d.get("version", "") or "",
            kind=d.get("kind", "") or "",
        )

    def to_dict(self) -> dict[str, Any]:
        return {"group": self.group, "version": self.version, "kind": self.kind}


@dataclass(frozen=True)
class GroupVersionResource:
    """K8s GroupVersionResource (AdmissionRequest.resource / requestResource)."""

    group: str = ""
    version: str = ""
    resource: str = ""

    @classmethod
    def from_dict(cls, d: Mapping[str, Any] | None) -> "GroupVersionResource | None":
        if d is None:
            return None
        return cls(
            group=d.get("group", "") or "",
            version=d.get("version", "") or "",
            resource=d.get("resource", "") or "",
        )

    def to_dict(self) -> dict[str, Any]:
        return {
            "group": self.group,
            "version": self.version,
            "resource": self.resource,
        }


@dataclass
class AdmissionRequest:
    """The ``request`` field of an AdmissionReview.

    Field set mirrors the reference's span-population and test fixture usage
    (src/api/handlers.rs:288-306, src/test_utils.rs:5-31).
    """

    uid: str = ""
    kind: GroupVersionKind = field(default_factory=GroupVersionKind)
    resource: GroupVersionResource = field(default_factory=GroupVersionResource)
    sub_resource: str | None = None
    request_kind: GroupVersionKind | None = None
    request_resource: GroupVersionResource | None = None
    request_sub_resource: str | None = None
    name: str | None = None
    namespace: str | None = None
    operation: str = ""
    user_info: dict[str, Any] = field(default_factory=dict)
    object: Any = None
    old_object: Any = None
    dry_run: bool | None = None
    options: Any = None

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "AdmissionRequest":
        if not isinstance(d, Mapping):
            raise ValueError("AdmissionReview.request must be an object")
        uid = d.get("uid")
        if not isinstance(uid, str) or not uid:
            raise ValueError("AdmissionReview.request.uid is required")
        return cls(
            uid=uid,
            kind=GroupVersionKind.from_dict(d.get("kind")) or GroupVersionKind(),
            resource=GroupVersionResource.from_dict(d.get("resource"))
            or GroupVersionResource(),
            sub_resource=d.get("subResource"),
            request_kind=GroupVersionKind.from_dict(d.get("requestKind")),
            request_resource=GroupVersionResource.from_dict(d.get("requestResource")),
            request_sub_resource=d.get("requestSubResource"),
            name=d.get("name"),
            namespace=d.get("namespace"),
            operation=d.get("operation", "") or "",
            user_info=dict(d.get("userInfo") or {}),
            object=d.get("object"),
            old_object=d.get("oldObject"),
            dry_run=d.get("dryRun"),
            options=d.get("options"),
        )

    def to_dict(self) -> dict[str, Any]:
        return _drop_none(
            {
                "uid": self.uid,
                "kind": self.kind.to_dict(),
                "resource": self.resource.to_dict(),
                "subResource": self.sub_resource,
                "requestKind": self.request_kind.to_dict() if self.request_kind else None,
                "requestResource": self.request_resource.to_dict()
                if self.request_resource
                else None,
                "requestSubResource": self.request_sub_resource,
                "name": self.name,
                "namespace": self.namespace,
                "operation": self.operation,
                "userInfo": self.user_info or None,
                "object": self.object,
                "oldObject": self.old_object,
                "dryRun": self.dry_run,
                "options": self.options,
            }
        )


@dataclass(frozen=True)
class StatusCause:
    """One cause inside status.details.causes (group denials carry
    field=``spec.policies.<member>``, reference
    evaluation_environment.rs:984-994)."""

    field: str | None = None
    message: str | None = None

    def to_dict(self) -> dict[str, Any]:
        return _drop_none({"field": self.field, "message": self.message})


@dataclass(frozen=True)
class StatusDetails:
    causes: tuple[StatusCause, ...] = ()

    def to_dict(self) -> dict[str, Any]:
        return {"causes": [c.to_dict() for c in self.causes]}


@dataclass(frozen=True)
class ValidationStatus:
    """AdmissionResponse.status."""

    message: str | None = None
    code: int | None = None
    reason: str | None = None
    details: StatusDetails | None = None

    def to_dict(self) -> dict[str, Any]:
        return _drop_none(
            {
                "message": self.message,
                "code": self.code,
                "reason": self.reason,
                "details": self.details.to_dict() if self.details else None,
            }
        )


JSON_PATCH = "JSONPatch"


@dataclass
class AdmissionResponse:
    """Verdict model, incl. JSONPatch mutation.

    Reference: policy-evaluator ``admission_response::AdmissionResponse`` as
    used at src/api/service.rs:60-68,86-90 and
    src/evaluation/evaluation_environment.rs:979-1042. ``patch`` is
    base64-encoded JSONPatch, ``patch_type`` is always ``"JSONPatch"`` when a
    patch is present.
    """

    uid: str = ""
    allowed: bool = False
    patch_type: str | None = None
    patch: str | None = None
    status: ValidationStatus | None = None
    audit_annotations: dict[str, str] | None = None
    warnings: list[str] | None = None

    @classmethod
    def reject(cls, uid: str, message: str, code: int) -> "AdmissionResponse":
        """Reference: AdmissionResponse::reject (service.rs:86-90)."""
        return cls(
            uid=uid,
            allowed=False,
            status=ValidationStatus(message=message, code=code),
        )

    @classmethod
    def reject_internal_server_error(cls, uid: str, message: str) -> "AdmissionResponse":
        return cls.reject(uid, f"internal server error: {message}", 500)

    def to_dict(self) -> dict[str, Any]:
        return _drop_none(
            {
                "uid": self.uid,
                "allowed": self.allowed,
                "patchType": self.patch_type,
                "patch": self.patch,
                "status": self.status.to_dict() if self.status else None,
                "auditAnnotations": self.audit_annotations,
                "warnings": self.warnings,
            }
        )

    def copy(self) -> "AdmissionResponse":
        return AdmissionResponse(
            uid=self.uid,
            allowed=self.allowed,
            patch_type=self.patch_type,
            patch=self.patch,
            status=self.status,
            audit_annotations=dict(self.audit_annotations)
            if self.audit_annotations is not None
            else None,
            warnings=list(self.warnings) if self.warnings is not None else None,
        )


class FragTemplate:
    """The uid-independent part of a cached verdict's response,
    pre-computed ONCE per target and verdict (environment._frag_of keys
    it by the target's own outputs in the cached row) so an
    all-cache-hit batch never re-runs response materialization
    (round 19: the flight recorder measured blob-tier cache-hit
    materialization at ~61 µs/row — almost all of it per-row
    AdmissionResponse/ValidationStatus construction).

    Only fragment-ELIGIBLE targets get templates
    (environment._frag_eligible): protect-mode, no mutator, no wasm,
    static rule messages — exactly the shapes whose response is a pure
    function of (target, output row) plus the request uid, and whose
    post_evaluate constraints are provably the identity. ``msg_b`` and
    ``causes_b`` carry the utf-8 bytes the native bulk serializer
    splices, so the common path re-encodes nothing per row."""

    __slots__ = (
        "allowed", "code", "message", "msg_b", "causes", "causes_b",
        "status", "native_tail",
    )

    def __init__(
        self,
        allowed: bool,
        code: "int | None" = None,
        message: "str | None" = None,
        causes: "tuple | None" = None,
    ) -> None:
        self.allowed = allowed
        self.code = code
        self.message = message
        self.msg_b = message.encode() if message is not None else None
        # ((field, message), ...) for group denials' status.details
        self.causes = causes
        self.causes_b = (
            tuple(
                (
                    f.encode() if f is not None else None,
                    m.encode() if m is not None else None,
                )
                for f, m in causes
            )
            if causes is not None
            else None
        )
        # the shared ValidationStatus every hit reuses (immutable)
        if allowed:
            self.status = None
        else:
            details = (
                StatusDetails(
                    causes=tuple(
                        StatusCause(field=f, message=m) for f, m in causes
                    )
                )
                if causes is not None
                else None
            )
            self.status = ValidationStatus(
                message=message, code=code, details=details
            )
        # opaque per-template cache of the native bulk record's fixed
        # tail (filled by runtime/native_frontend.pack_frag_record on
        # the first native delivery; GIL-atomic store, identical values)
        self.native_tail = None

    def to_response(self, uid: str) -> "AdmissionResponse":
        """Rebuild the full AdmissionResponse (futures/aiohttp callers;
        the native sink path never needs it)."""
        return AdmissionResponse(
            uid=uid, allowed=self.allowed, status=self.status
        )


class FragVerdict:
    """One output row's verdict: the request uid plus a shared
    FragTemplate. This is what the environment answers an eligible
    target's row with, a tier's hit or a dispatched slot (under
    environment.fragment_responses()), instead of a materialized
    AdmissionResponse; the batcher's phase 3 recognizes it
    — metrics from the template fields, constraints skipped (eligibility
    proved them identity) — and the native completion sink splices
    uid + template bytes straight into the bulk verdict record."""

    __slots__ = ("uid", "tmpl")

    # read-compatible with AdmissionResponse for sink consumers that
    # introspect the delivered verdict (fragment eligibility means these
    # are structurally absent)
    patch = None
    patch_type = None
    audit_annotations = None
    warnings = None

    def __init__(self, uid: str, tmpl: FragTemplate) -> None:
        self.uid = uid
        self.tmpl = tmpl

    @property
    def allowed(self) -> bool:
        return self.tmpl.allowed

    @property
    def status(self) -> "ValidationStatus | None":
        return self.tmpl.status

    def to_response(self) -> "AdmissionResponse":
        return self.tmpl.to_response(self.uid)

    def to_dict(self) -> dict[str, Any]:
        return self.to_response().to_dict()


API_VERSION = "admission.k8s.io/v1"
ADMISSION_REVIEW_KIND = "AdmissionReview"


@dataclass
class AdmissionReviewRequest:
    """Incoming AdmissionReview envelope (src/api/admission_review.rs:5-20).

    ``kind``/``apiVersion`` are optional on input (the reference models them
    as Option<String>); only ``request`` is required.
    """

    request: AdmissionRequest
    kind: str | None = None
    api_version: str | None = None

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "AdmissionReviewRequest":
        if not isinstance(d, Mapping) or "request" not in d:
            raise ValueError("AdmissionReview must contain a `request` field")
        return cls(
            request=AdmissionRequest.from_dict(d["request"]),
            kind=d.get("kind"),
            api_version=d.get("apiVersion"),
        )


@dataclass
class AdmissionReviewResponse:
    """Outgoing AdmissionReview envelope — always ``admission.k8s.io/v1``
    (src/api/admission_review.rs:22-36)."""

    response: AdmissionResponse

    def to_dict(self) -> dict[str, Any]:
        return {
            "apiVersion": API_VERSION,
            "kind": ADMISSION_REVIEW_KIND,
            "response": self.response.to_dict(),
        }


@dataclass
class RawReviewRequest:
    """Non-Kubernetes raw JSON validation request (src/api/raw_review.rs:5-11)."""

    request: Any

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "RawReviewRequest":
        if not isinstance(d, Mapping) or "request" not in d:
            raise ValueError("raw review must contain a `request` field")
        return cls(request=d["request"])


@dataclass
class RawReviewResponse:
    """src/api/raw_review.rs:13-20."""

    response: AdmissionResponse

    def to_dict(self) -> dict[str, Any]:
        return {"response": self.response.to_dict()}


class ValidateRequest:
    """Wrapper over AdmissionRequest | raw JSON (SURVEY.md §2.2
    ``ValidateRequest``), the unit handed to the evaluation layer.

    ``.uid()`` mirrors the reference usage at src/api/service.rs:61 and
    src/api/handlers.rs:81,165 (raw requests synthesize/extract a uid from the
    JSON body's ``uid`` key when present, else empty string).
    """

    __slots__ = ("admission_request", "raw", "_payload_cache", "_payload_json")

    def __init__(
        self,
        admission_request: AdmissionRequest | None = None,
        raw: Any = None,
    ) -> None:
        if (admission_request is None) == (raw is None):
            raise ValueError(
                "ValidateRequest is either an AdmissionRequest or a raw value"
            )
        self.admission_request = admission_request
        self.raw = raw
        self._payload_cache: Any = None
        self._payload_json: bytes | None = None

    @classmethod
    def from_admission(cls, req: AdmissionRequest) -> "ValidateRequest":
        return cls(admission_request=req)

    @classmethod
    def from_raw(cls, value: Any) -> "ValidateRequest":
        return cls(raw=value)

    @property
    def is_raw(self) -> bool:
        return self.admission_request is None

    def uid(self) -> str:
        if self.admission_request is not None:
            return self.admission_request.uid
        if isinstance(self.raw, Mapping):
            uid = self.raw.get("uid")
            if isinstance(uid, str):
                return uid
        return ""

    def payload(self) -> Any:
        """The JSON value policies inspect: the full request dict for
        admission requests, the raw value otherwise. Memoized — the batcher
        and the evaluation layers call this repeatedly on the hot path."""
        if self.admission_request is not None:
            if self._payload_cache is None:
                self._payload_cache = self.admission_request.to_dict()
            return self._payload_cache
        return self.raw

    def payload_json(self) -> bytes:
        """The payload as compact JSON bytes (memoized) — the native
        encoder's input (ops/fastenc.py)."""
        if self._payload_json is None:
            self._payload_json = json.dumps(
                self.payload(), separators=(",", ":")
            ).encode()
        return self._payload_json
