"""HTTP handlers.

Reference parity: src/api/handlers.rs —
* POST ``/validate/{policy_id}``     → validate_handler (handlers.rs:120-141)
* POST ``/validate_raw/{policy_id}`` → validate_raw_handler (143-174)
* POST ``/audit/{policy_id}``        → audit_handler (69-90)
* GET  ``/readiness``                → readiness_handler (176-178)
* GET  ``/debug/pprof/cpu|heap|trace`` → pprof handlers (193-254)
* error mapping: PolicyNotFound → 404, everything else → 500
  "Something went wrong" (321-342); malformed JSON body → 422 ApiError
  (JsonExtractor, 30-39).

Request spans carry the reference's field set (request_uid, host, policy_id,
resource identifiers, allowed/mutated/response_*, handlers.rs:46-67 and
288-319). Evaluation itself goes through the micro-batcher: the await on the
batcher future is the analog of `acquire_semaphore_and_evaluate`'s
semaphore + spawn_blocking hop (handlers.rs:256-286)."""

from __future__ import annotations

import asyncio
import json

from aiohttp import web

from policy_server_tpu.api import profiling
from policy_server_tpu.api.api_error import (
    api_error,
    json_body_error,
    something_went_wrong,
)
from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.api.state import ApiServerState
from policy_server_tpu.evaluation.errors import (
    EvaluationError,
    PolicyNotFoundError,
)
from policy_server_tpu.runtime.batcher import ShedError
from policy_server_tpu.models import (
    AdmissionResponse,
    AdmissionReviewRequest,
    AdmissionReviewResponse,
    RawReviewRequest,
    RawReviewResponse,
    ValidateRequest,
)
from policy_server_tpu.telemetry import default_registry
from policy_server_tpu.telemetry.tracing import logger, span

STATE_KEY = web.AppKey("state", ApiServerState)

# one request-body cap for EVERY process that can accept the API socket
# (in-process app and prefork workers must agree or limits go
# nondeterministic behind SO_REUSEPORT)
MAX_BODY_BYTES = 8 * 1024**2


class BodyError(Exception):
    """Malformed request body; ``message`` carries the 422 text."""

    def __init__(self, message: str):
        super().__init__(message)
        self.message = message


def parse_admission_review_bytes(body: bytes) -> AdmissionReviewRequest:
    """The ONE parse+error contract for admission review bodies, shared by
    the in-process handlers, the prefork workers, and the evaluation
    bridge (a 422 body must not depend on which process parsed it)."""
    try:
        return AdmissionReviewRequest.from_dict(json.loads(body))
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise BodyError(f"Failed to parse the request body as JSON: {e}") from e
    except (KeyError, TypeError, ValueError, AttributeError) as e:
        raise BodyError(f"Failed to deserialize the JSON body: {e}") from e


def _span_fields_from_admission(review: AdmissionReviewRequest) -> dict:
    """populate_span_with_admission_request_data (handlers.rs:288-306)."""
    req = review.request
    fields = {
        "request_uid": req.uid,
        "name": req.name,
        "namespace": req.namespace,
        "operation": req.operation,
        "subresource": req.sub_resource,
    }
    if req.kind:
        fields.update(
            kind_group=req.kind.group, kind_version=req.kind.version,
            kind=req.kind.kind,
        )
    if req.resource:
        fields.update(
            resource_group=req.resource.group,
            resource_version=req.resource.version,
            resource=req.resource.resource,
        )
    return {k: v for k, v in fields.items() if v not in (None, "")}


def _record_response(fields: dict, response: AdmissionResponse) -> None:
    """populate_span_with_policy_evaluation_results (handlers.rs:308-319)."""
    fields["allowed"] = response.allowed
    fields["mutated"] = response.patch is not None
    if response.status:
        if response.status.code is not None:
            fields["response_code"] = response.status.code
        if response.status.message:
            fields["response_message"] = response.status.message


def _tenant_span_field(request: web.Request) -> dict:
    """``{"tenant": name}`` for tenant-routed requests; empty for the
    default routes so their span/log lines stay byte-identical."""
    name = request.match_info.get("tenant")
    return {} if name is None else {"tenant": name}


def _incoming_trace(request: web.Request):
    """The W3C ``traceparent`` parent of this request, when the span
    pipeline is installed (round 18): webhook-originated traces then
    correlate end-to-end instead of starting fresh roots. None keeps
    the historical fresh-root behavior (and skips the header parse
    entirely when no tracer exists)."""
    from policy_server_tpu.telemetry import otlp

    if otlp.tracer() is None:
        return None
    return otlp.parse_traceparent(request.headers.get("traceparent"))


def _tenant_state(state: ApiServerState, request: web.Request):
    """Resolve the serving tenant from the request path (round 16,
    tenancy.py): un-prefixed routes keep the default epoch pointer (the
    state itself — every existing URL unchanged); ``{tenant}`` routes
    resolve through the tenant registry. Returns ``(state_like, None)``
    or ``(None, 404 response)`` for an unknown tenant."""
    name = request.match_info.get("tenant")
    if name is None:
        return state, None
    from policy_server_tpu.tenancy import (
        lookup_tenant,
        unknown_tenant_message,
    )

    tenant = lookup_tenant(state, name)
    if tenant is None:
        return None, api_error(404, unknown_tenant_message(name))
    return tenant.state, None


async def _evaluate(
    batcher,
    policy_id: str,
    request: ValidateRequest,
    origin: RequestOrigin,
) -> AdmissionResponse | web.Response:
    """Dispatch through the batcher; map EvaluationError → ApiError
    responses (handlers.rs:321-342)."""
    try:
        # submit_async returns a loop-bound asyncio future; whole batches
        # deliver with one loop wakeup (runtime/batcher.py _DeliveryBatch)
        future = await batcher.submit_async(policy_id, request, origin)
        return await future
    except ShedError as e:
        # admission-time load shed (429) or shard fence (503, FencedError
        # subclass): either way the row cannot be answered with a verdict
        # now, and an HTTP error with Retry-After beats evaluating work
        # the API server will time out anyway. Status and message come
        # off the exception class so both surfaces stay byte-identical
        # with the native frontend's _shed_body.
        import math as _math

        retry_after = max(1, _math.ceil(e.retry_after_seconds))
        return web.json_response(
            {
                "message": getattr(
                    e, "message", "policy server overloaded; retry later"
                ),
                "retry_after_seconds": retry_after,
            },
            status=getattr(e, "http_status", 429),
            headers={"Retry-After": str(retry_after)},
        )
    except PolicyNotFoundError as e:
        return api_error(404, str(e))
    except EvaluationError as e:
        logger.error("Evaluation error: %s", e)
        return something_went_wrong()
    except Exception as e:  # noqa: BLE001 — keep the JSON error contract
        logger.error("Evaluation error: %s", e)
        return something_went_wrong()


async def _read_admission_review(
    request: web.Request,
) -> AdmissionReviewRequest | web.Response:
    try:
        return parse_admission_review_bytes(await request.read())
    except BodyError as e:
        return json_body_error(e.message)


async def validate_handler(request: web.Request) -> web.Response:
    state = request.app[STATE_KEY]
    policy_id = request.match_info["policy_id"]
    tstate, denied = _tenant_state(state, request)
    if denied is not None:
        return denied
    review = await _read_admission_review(request)
    if isinstance(review, web.Response):
        return review
    with span(
        "validation", parent_ctx=_incoming_trace(request),
        host=state.hostname, policy_id=policy_id,
        **_tenant_span_field(request),
        **_span_fields_from_admission(review),
    ) as fields:
        result = await _evaluate(
            tstate.batcher, policy_id,
            ValidateRequest.from_admission(review.request),
            RequestOrigin.VALIDATE,
        )
        if isinstance(result, web.Response):
            return result
        _record_response(fields, result)
        return web.json_response(AdmissionReviewResponse(result).to_dict())


async def audit_handler(request: web.Request) -> web.Response:
    state = request.app[STATE_KEY]
    policy_id = request.match_info["policy_id"]
    tstate, denied = _tenant_state(state, request)
    if denied is not None:
        return denied
    review = await _read_admission_review(request)
    if isinstance(review, web.Response):
        return review
    with span(
        "audit", parent_ctx=_incoming_trace(request),
        host=state.hostname, policy_id=policy_id,
        **_tenant_span_field(request),
        **_span_fields_from_admission(review),
    ) as fields:
        result = await _evaluate(
            tstate.batcher, policy_id,
            ValidateRequest.from_admission(review.request),
            RequestOrigin.AUDIT,
        )
        if isinstance(result, web.Response):
            return result
        _record_response(fields, result)
        return web.json_response(AdmissionReviewResponse(result).to_dict())


def _audit_reports_etag(state: ApiServerState) -> str:
    """The GET /audit/reports validator: snapshot generation (what the
    cluster looks like) + serving epoch (which policy set judged it) +
    report-store version (what the sweeps actually wrote). Any change an
    unchanged-ETag response could hide bumps one of the three."""
    scanner = state.audit
    generation = scanner.snapshot.stats().get("generation", 0)
    epoch = (
        state.lifecycle.current_epoch if state.lifecycle is not None else 0
    )
    return f'"audit-{generation}-{epoch}-{scanner.reports.version()}"'


async def audit_reports_handler(request: web.Request) -> web.Response:
    """GET /audit/reports[/{namespace}] — the background audit scanner's
    PolicyReport-style output (round 10): per-resource × per-policy raw
    verdicts stamped with the policy epoch that produced them, plus
    summary counters and scanner freshness. 404 when --audit-mode off.
    Round 23: carries an ETag and honors If-None-Match with 304, so
    pollers that have not migrated to /audit/stream stop re-serializing
    unchanged full reports."""
    state = request.app[STATE_KEY]
    if state.audit is None:
        return api_error(404, "the background audit scanner is disabled")
    namespace = request.match_info.get("namespace")
    etag = _audit_reports_etag(state)
    if request.headers.get("If-None-Match") == etag:
        return web.Response(status=304, headers={"ETag": etag})
    return web.json_response(
        state.audit.report_payload(namespace), headers={"ETag": etag}
    )


async def audit_stream_handler(request: web.Request) -> web.StreamResponse:
    """GET /audit/stream[?cursor=N] — the verdict matrix's watch-style
    changelog as chunked JSON lines (round 23). Each line carries a
    monotonic ``matrixVersion``; a client that disconnects resumes with
    ``?cursor=<last seen>`` and replays exactly the missed entries, or
    gets a RESYNC marker + full state when the ring no longer covers the
    cursor. A slow consumer overflows its own bounded queue and is
    dropped with a counted close — the sweep applier never blocks on a
    client. 404 without --audit-matrix; 503 over the client cap."""
    state = request.app[STATE_KEY]
    matrix = state.audit_matrix
    if matrix is None:
        return api_error(404, "the verdict matrix is disabled")
    if matrix.stream_clients() >= state.audit_stream_max_clients:
        return api_error(
            503,
            f"audit stream client cap reached "
            f"({state.audit_stream_max_clients}); retry later",
        )
    cursor: int | None = None
    raw_cursor = request.query.get("cursor")
    if raw_cursor is not None:
        try:
            cursor = int(raw_cursor)
        except ValueError:
            return api_error(422, f"invalid cursor {raw_cursor!r}")
    resp = web.StreamResponse(
        status=200,
        headers={
            "Content-Type": "application/x-ndjson",
            "Cache-Control": "no-cache",
        },
    )
    resp.enable_chunked_encoding()
    await resp.prepare(request)
    sub = matrix.subscribe(cursor)
    try:
        while True:
            entries, dead = matrix.drain(sub)
            for entry in entries:
                await resp.write(
                    json.dumps(entry, separators=(",", ":")).encode()
                    + b"\n"
                )
            if dead:
                # the queue overflowed while we were writing: tell the
                # client honestly (it reconnects with its cursor) and
                # close — a silent gap would corrupt its matrix view
                await resp.write(
                    json.dumps(
                        {
                            "type": "OVERFLOW",
                            "matrixVersion": matrix.version,
                        },
                        separators=(",", ":"),
                    ).encode() + b"\n"
                )
                break
            await asyncio.sleep(0.1)
    except (ConnectionResetError, asyncio.CancelledError):
        pass  # client went away — the cursor contract covers its return
    finally:
        matrix.unsubscribe(sub)
    return resp


async def validate_raw_handler(request: web.Request) -> web.Response:
    state = request.app[STATE_KEY]
    policy_id = request.match_info["policy_id"]
    tstate, denied = _tenant_state(state, request)
    if denied is not None:
        return denied
    try:
        body = json.loads(await request.read())
        raw_review = RawReviewRequest.from_dict(body)
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        return json_body_error(f"Failed to parse the request body as JSON: {e}")
    except (KeyError, TypeError, ValueError) as e:
        return json_body_error(f"Failed to deserialize the JSON body: {e}")
    with span(
        "validation_raw", parent_ctx=_incoming_trace(request),
        host=state.hostname, policy_id=policy_id,
        **_tenant_span_field(request),
    ) as fields:
        result = await _evaluate(
            tstate.batcher, policy_id,
            ValidateRequest.from_raw(raw_review.request),
            RequestOrigin.VALIDATE,
        )
        if isinstance(result, web.Response):
            return result
        _record_response(fields, result)
        return web.json_response(RawReviewResponse(result).to_dict())


async def readiness_handler(request: web.Request) -> web.Response:
    """Honest readiness (round 9): 503 until the first policy epoch is
    compiled+warmed, 200 on last-good during a background reload, 503
    when every shard's breaker is open under --degraded-mode reject
    (ApiServerState.readiness holds the verdict logic; multi-tenant
    deployments aggregate — 503 only when EVERY tenant is degraded)."""
    status, text = request.app[STATE_KEY].readiness()
    return web.Response(status=status, text=text)


async def readiness_tenant_handler(request: web.Request) -> web.Response:
    """GET /readiness/{tenant} (round 16): ONE tenant's honest verdict —
    503 until that tenant's first epoch is compiled+warmed, or while its
    breakers are all open under a per-tenant --degraded-mode reject.
    404 for unknown tenants (and for every name when no tenants
    manifest is configured)."""
    state = request.app[STATE_KEY]
    name = request.match_info["tenant"]
    from policy_server_tpu.tenancy import (
        lookup_tenant,
        unknown_tenant_message,
    )

    tenant = lookup_tenant(state, name)
    if tenant is None:
        return api_error(404, unknown_tenant_message(name))
    status, text = tenant.readiness()
    return web.Response(status=status, text=text)


# -- policy-lifecycle admin endpoints (lifecycle.py) ------------------------


def _admin_gate(state: ApiServerState, request: web.Request) -> web.Response | None:
    """Auth for the /policies/* admin endpoints: a bearer token must be
    CONFIGURED (--reload-admin-token) and presented. Serving on the
    plaintext readiness port keeps the surface cluster-internal like
    /metrics; the token keeps it operator-only."""
    if state.lifecycle is None:
        return api_error(404, "policy hot reload is disabled")
    if not state.admin_token:
        return api_error(
            403,
            "policy admin endpoints disabled: --reload-admin-token is "
            "not configured",
        )
    header = request.headers.get("Authorization", "")
    import hmac

    expected = f"Bearer {state.admin_token}"
    if not hmac.compare_digest(header, expected):
        return api_error(401, "invalid or missing bearer token")
    return None


async def policies_reload_handler(request: web.Request) -> web.Response:
    state = request.app[STATE_KEY]
    denied = _admin_gate(state, request)
    if denied is not None:
        return denied
    started = state.lifecycle.request_reload("admin-endpoint")
    body = {
        "status": "reload started" if started else
        "reload already in progress",
        "epoch": state.lifecycle.current_epoch,
    }
    # last shadow-canary cluster what-if (round 23): the verdict flips
    # the PREVIOUS candidate would have caused — this reload's own diff
    # lands once its canary runs (poll this endpoint or the matrix)
    whatif = state.lifecycle.stats().get("whatif")
    if whatif is not None:
        body["whatif"] = whatif
    return web.json_response(body, status=202)


async def _lifecycle_action(
    request: web.Request, action: str
) -> web.Response:
    """Shared body for the synchronous promote/rollback endpoints."""
    from policy_server_tpu.lifecycle import ReloadRejected

    state = request.app[STATE_KEY]
    denied = _admin_gate(state, request)
    if denied is not None:
        return denied
    fn = getattr(state.lifecycle, action)
    try:
        # promote/rollback build + start a batcher: off the event loop
        outcome = await asyncio.get_running_loop().run_in_executor(None, fn)
    except ReloadRejected as e:
        return api_error(409, str(e))
    except Exception as e:  # noqa: BLE001 — keep the JSON error contract
        logger.error("policy %s failed: %s", action, e)
        return something_went_wrong()
    body = {"status": outcome, "epoch": state.lifecycle.current_epoch}
    whatif = state.lifecycle.stats().get("whatif")
    if whatif is not None:
        body["whatif"] = whatif
    return web.json_response(body)


async def policies_rollback_handler(request: web.Request) -> web.Response:
    return await _lifecycle_action(request, "rollback")


async def policies_promote_handler(request: web.Request) -> web.Response:
    return await _lifecycle_action(request, "promote_staged")


async def metrics_handler(request: web.Request) -> web.Response:
    """Prometheus exposition (this build's pull-based replacement for the
    reference's OTLP push, see telemetry/metrics.py). Serving-runtime
    introspection (dispatch counts, watchdog abandonments, queue depth,
    oracle fallbacks) rides the same registry via the runtime-stats
    collector the server attaches at bootstrap."""
    return web.Response(
        body=default_registry().exposition(),
        content_type="text/plain",
        charset="utf-8",
    )


async def timeline_handler(request: web.Request) -> web.Response:
    """GET /debug/timeline (round 18): the flight recorder's ring as
    Chrome/Perfetto trace JSON — batch phase tracks, native-frontend
    burst aggregates, sampled-row tracks, plus the current tail
    exemplars and ring accounting under ``otherData``. Load the body in
    https://ui.perfetto.dev or chrome://tracing. ``?since_ns=&until_ns=``
    (both optional, CLOCK_MONOTONIC ns, the clock of the events' ``ts``)
    return only the events that overlap that interval, so that reading
    a few seconds of a busy server does not render the whole ring. 404
    when --flight-recorder off. Served on the readiness port (always the
    main process, cluster-internal like /metrics) and on the
    python-frontend API port."""
    from policy_server_tpu.telemetry import flightrec

    rec = flightrec.recorder()
    if rec is None:
        return api_error(404, "the flight recorder is disabled")
    try:
        since_ns, until_ns = (
            int(request.query[key]) if key in request.query else None
            for key in ("since_ns", "until_ns")
        )
    except ValueError:
        return json_body_error("invalid 'since_ns'/'until_ns' query parameter")
    # snapshot + JSON render walk the ring: off the event loop
    body = await asyncio.get_running_loop().run_in_executor(
        None, rec.chrome_trace_json, since_ns, until_ns
    )
    return web.Response(body=body, content_type="application/json")


async def pprof_cpu_handler(request: web.Request) -> web.Response:
    """GET /debug/pprof/cpu?interval= (handlers.rs:193-223). Interval is
    seconds (default 30, profiling.rs:48-51); runs off the event loop."""
    try:
        interval = float(
            request.query.get("interval", profiling.DEFAULT_PROFILING_INTERVAL)
        )
        frequency = int(
            request.query.get("frequency", profiling.DEFAULT_PROFILING_FREQUENCY)
        )
    except ValueError:
        return json_body_error("invalid 'interval'/'frequency' query parameter")
    try:
        profile = await asyncio.get_running_loop().run_in_executor(
            None, profiling.start_one_cpu_profile, interval, frequency
        )
    except profiling.ProfileInProgress as e:
        return api_error(409, str(e))
    except Exception as e:  # noqa: BLE001
        logger.error("pprof error: %s", e)
        return something_went_wrong()
    return web.Response(
        body=profile.text.encode(),
        content_type="application/octet-stream",
        headers={"Content-Disposition": 'attachment; filename="cpu.pprof.txt"'},
    )


async def pprof_heap_handler(request: web.Request) -> web.Response:
    """GET /debug/pprof/heap (handlers.rs:227-254): host allocations +
    device HBM stats."""
    try:
        body = await asyncio.get_running_loop().run_in_executor(
            None, profiling.heap_profile
        )
    except Exception as e:  # noqa: BLE001
        logger.error("pprof error: %s", e)
        return something_went_wrong()
    return web.Response(body=body, content_type="application/json")


async def pprof_trace_handler(request: web.Request) -> web.Response:
    """GET /debug/pprof/trace?seconds= : a JAX/XLA trace of this process
    (device events and the ``ps:launch`` anchors, profiling.py
    take_device_trace) as an ``.xplane.pb``; single-flight, off the event
    loop. Its host events carry ``perf_counter_ns``, the clock of
    ``/debug/timeline``."""
    try:
        seconds = float(
            request.query.get("seconds", profiling.DEFAULT_TRACE_SECONDS)
        )
    except ValueError:
        return json_body_error("invalid 'seconds' query parameter")
    if not 0 < seconds <= profiling.MAX_TRACE_SECONDS:
        return json_body_error(
            f"'seconds' must be over 0 and at most "
            f"{profiling.MAX_TRACE_SECONDS:g}"
        )
    try:
        body = await asyncio.get_running_loop().run_in_executor(
            None, profiling.take_device_trace, seconds
        )
    except profiling.ProfileInProgress as e:
        return api_error(409, str(e))
    except Exception as e:  # noqa: BLE001
        logger.error("pprof error: %s", e)
        return something_went_wrong()
    return web.Response(
        body=body,
        content_type="application/octet-stream",
        headers={
            "Content-Disposition": 'attachment; filename="trace.xplane.pb"'
        },
    )


def build_router(state: ApiServerState) -> web.Application:
    """The API application (reference router wiring, src/lib.rs:205-225)."""
    app = web.Application(client_max_size=MAX_BODY_BYTES)
    app[STATE_KEY] = state
    app.router.add_post("/validate/{policy_id}", validate_handler)
    app.router.add_post("/validate_raw/{policy_id}", validate_raw_handler)
    # literal /audit/reports routes BEFORE the /audit/{policy_id}
    # wildcard so the report listing wins path resolution
    app.router.add_get("/audit/reports", audit_reports_handler)
    app.router.add_get("/audit/reports/{namespace}", audit_reports_handler)
    # verdict-matrix changelog stream (round 23) — literal, same
    # wildcard-shadowing rule as /audit/reports ('stream' is reserved)
    app.router.add_get("/audit/stream", audit_stream_handler)
    app.router.add_post("/audit/{policy_id}", audit_handler)
    # tenant-routed evaluation surface (round 16, tenancy.py): the
    # tenant rides the path; the un-prefixed routes above stay the
    # reserved default tenant. 'reports' is a reserved tenant name, so
    # the literal audit routes can never be shadowed.
    app.router.add_post(
        "/validate/{tenant}/{policy_id}", validate_handler
    )
    app.router.add_post(
        "/validate_raw/{tenant}/{policy_id}", validate_raw_handler
    )
    app.router.add_post("/audit/{tenant}/{policy_id}", audit_handler)
    if state.enable_pprof:
        app.router.add_get("/debug/pprof/cpu", pprof_cpu_handler)
        app.router.add_get("/debug/pprof/heap", pprof_heap_handler)
        app.router.add_get("/debug/pprof/trace", pprof_trace_handler)
    # flight-recorder timeline (round 18): also on the API port for the
    # python frontend (the native frontend serves only the evaluation
    # POSTs; the readiness-port copy below is the always-there surface)
    app.router.add_get("/debug/timeline", timeline_handler)
    return app


def build_readiness_router(state: ApiServerState) -> web.Application:
    """The plaintext readiness application (lib.rs:225, cli.rs:71-76) —
    also exposes /metrics (Prometheus pull)."""
    app = web.Application()
    app[STATE_KEY] = state
    app.router.add_get("/readiness", readiness_handler)
    # per-tenant honest readiness (round 16): 503 until THAT tenant's
    # first epoch is warmed / while it is degraded-rejecting
    app.router.add_get("/readiness/{tenant}", readiness_tenant_handler)
    app.router.add_get("/metrics", metrics_handler)
    # policy-lifecycle admin surface (bearer-token gated; 404 when the
    # lifecycle manager is absent, 403 when no token is configured)
    app.router.add_post("/policies/reload", policies_reload_handler)
    app.router.add_post("/policies/promote", policies_promote_handler)
    app.router.add_post("/policies/rollback", policies_rollback_handler)
    # audit reports ALSO on the readiness port: always served by the
    # main process (prefork workers only proxy the validate/audit POST
    # surface), cluster-internal like /metrics
    app.router.add_get("/audit/reports", audit_reports_handler)
    app.router.add_get("/audit/reports/{namespace}", audit_reports_handler)
    # verdict-matrix changelog stream: also on the readiness port (the
    # main process owns the matrix; prefork workers only proxy POSTs)
    app.router.add_get("/audit/stream", audit_stream_handler)
    # flight-recorder timeline (round 18): the main-process ring is the
    # one with the batcher/device phases, and the readiness port is
    # always served by the main process — the canonical surface
    app.router.add_get("/debug/timeline", timeline_handler)
    if state.enable_pprof:
        # the device trace beside the timeline it lines up with: the
        # main process holds the chip, under either frontend
        app.router.add_get("/debug/pprof/trace", pprof_trace_handler)
    return app
