"""Prefork HTTP frontend — scaling past the one-event-loop framing wall.

The Python asyncio HTTP layer saturated ≈1.3k requests/s per process on
the 2-core CPU box this was built on, far below the batcher behind it. The
reference's answer to frontend limits is replicas behind a Service
(README.md:21-26); this module is the in-box equivalent:

* ``--http-workers N`` (N>1) spawns N lightweight worker PROCESSES that
  bind the SAME API port with ``SO_REUSEPORT`` (kernel load-balances
  accepted connections) and run the full request handling — HTTP framing,
  JSON parse/422 mapping, span logging, response serialization;
* each worker forwards ``(origin, policy_id, request-json)`` over a
  length-prefixed unix-socket frame to the ONE evaluation process that
  owns the device, and relays the ``(status, body)`` answer;
* the evaluation process keeps everything stateful: the environment, the
  micro-batcher, metrics (scraped from its readiness port), OTLP.

Workers import no JAX — boot is milliseconds, memory is a few tens of
MB, and a worker crash loses nothing but its in-flight sockets.

Frame wire format (little-endian):

    request:  u32 frame_len | u64 req_id | u8 origin | u16 policy_id_len
              | policy_id utf-8 | payload json bytes
    response: u32 frame_len | u64 req_id | u16 http_status | body bytes

``origin``: 0 = validate, 1 = validate_raw, 2 = audit."""

from __future__ import annotations

import asyncio
import json
import re
import struct
from typing import Any, Mapping

from policy_server_tpu.models import GroupVersionKind

_REQ_HEADER = struct.Struct("<QBH")
_PARSED_EXTRA = struct.Struct("<I")  # header-json length, parsed frames only
_RESP_HEADER = struct.Struct("<QH")
_LEN = struct.Struct("<I")

ORIGIN_VALIDATE, ORIGIN_RAW, ORIGIN_AUDIT = 0, 1, 2
# worker-parsed frames: the WORKER validated/parsed the AdmissionReview and
# ships (header json, payload json bytes); the evaluation process builds a
# zero-parse WireValidateRequest — the whole point of the prefork split
ORIGIN_VALIDATE_PARSED, ORIGIN_AUDIT_PARSED = 3, 4

MAX_FRAME = 32 * 1024 * 1024  # bridge frames (body + header + framing)


def _shed_headers(status: int, payload: bytes) -> dict | None:
    """Reconstruct the Retry-After header on the worker side of the
    bridge: load-shed 429s and shard-fence 503s carry
    ``retry_after_seconds`` in the JSON body (the frame format has no
    header channel), and the HTTP answer a worker serves must match the
    in-process one."""
    if status not in (429, 503):
        return None
    try:
        retry_after = json.loads(payload).get("retry_after_seconds")
    except (ValueError, AttributeError):
        return None
    if not retry_after:
        return None
    return {"Retry-After": str(retry_after)}


async def _read_frame(reader: asyncio.StreamReader) -> bytes | None:
    try:
        raw_len = await reader.readexactly(_LEN.size)
    except (asyncio.IncompleteReadError, ConnectionResetError):
        return None
    (length,) = _LEN.unpack(raw_len)
    if length > MAX_FRAME:
        raise ValueError(f"frame of {length} bytes exceeds the limit")
    try:
        return await reader.readexactly(length)
    except (asyncio.IncompleteReadError, ConnectionResetError):
        return None


def _write_frame(writer: asyncio.StreamWriter, payload: bytes) -> None:
    writer.write(_LEN.pack(len(payload)) + payload)


# ---------------------------------------------------------------------------
# Zero-parse wire request (evaluation-process side of parsed frames)
# ---------------------------------------------------------------------------


class _WireKind:
    __slots__ = ("kind",)

    def __init__(self, kind: str):
        self.kind = kind


# The head of the canonical payload both parsed-frame producers write
# (csrc/httpfront.cpp canon_admission_review, AdmissionRequest.to_dict):
# uid, kind, resource, then the optional sub-resource / request-kind keys,
# then name. Group 1-3: the review's kind; group 4: its name, None when
# the review carries none. A payload laid out any other way does not
# match and is parsed whole.
_JSTR = rb'"[^"\\]*(?:\\.[^"\\]*)*"'
_JGVK = (
    rb'\{"group":' + _JSTR + rb',"version":' + _JSTR + rb',"%s":' + _JSTR
    + rb"\}"
)
_IDENTITY = re.compile(
    rb'\{"uid":' + _JSTR
    + rb',"kind":\{"group":(' + _JSTR + rb'),"version":(' + _JSTR
    + rb'),"kind":(' + _JSTR + rb")\}"
    + rb',"resource":' + _JGVK % b"resource"
    + rb'(?:,"subResource":' + _JSTR + rb")?"
    + rb'(?:,"requestKind":' + _JGVK % b"kind" + rb")?"
    + rb'(?:,"requestResource":' + _JGVK % b"resource" + rb")?"
    + rb'(?:,"requestSubResource":' + _JSTR + rb")?"
    + rb'(?:,"name":(' + _JSTR + rb'))?,"(?:namespace|operation)":'
)


def _unquote(literal: bytes) -> str:
    """A JSON string literal's value; only one with an escape in it costs
    a parse."""
    if b"\\" in literal:
        return json.loads(literal)
    return literal[1:-1].decode()


# the kinds met so far, by their three literals as the payload spells
# them: a cluster has a few hundred, so this stays small
_KINDS: dict[tuple, GroupVersionKind] = {}


def _identity_of(payload: bytes) -> tuple[GroupVersionKind, Any]:
    """(kind, name) of the object a canonical request payload targets,
    read off the payload's head without parsing the object."""
    m = _IDENTITY.match(payload)
    if m is None:
        d = json.loads(payload)
        return (
            GroupVersionKind.from_dict(d.get("kind")) or GroupVersionKind(),
            d.get("name"),
        )
    spelled = m.group(1, 2, 3)
    kind = _KINDS.get(spelled)
    if kind is None:
        kind = GroupVersionKind(*map(_unquote, spelled))
        if len(_KINDS) < 4096:
            _KINDS[spelled] = kind
    name = m.group(4)
    return kind, None if name is None else _unquote(name)


class _WireAdmission:
    """The slice of AdmissionRequest the service layer reads (namespace
    shortcut + metric labels) off the header; everything else lives in
    the payload bytes. ``kind`` and ``name`` — the object's identity,
    which the audit snapshot store keys on — are read from the payload's
    head on first use."""

    __slots__ = (
        "uid", "namespace", "operation", "request_kind", "_payload",
        "_identity",
    )

    def __init__(self, header: Mapping[str, Any], payload: bytes):
        self.uid = str(header.get("uid") or "")
        self.namespace = header.get("namespace")
        self.operation = header.get("operation")
        kind = header.get("kind")
        self.request_kind = _WireKind(str(kind)) if kind else None
        self._payload = payload
        self._identity = None

    def _ident(self) -> tuple[GroupVersionKind, Any]:
        if self._identity is None:
            self._identity = _identity_of(self._payload)
        return self._identity

    @property
    def kind(self) -> GroupVersionKind:
        return self._ident()[0]

    @property
    def name(self) -> Any:
        return self._ident()[1]


class WireValidateRequest:
    """ValidateRequest-compatible object whose payload stays as the wire
    JSON bytes: the native encoder consumes ``payload_json()`` directly
    (no Python parse on the evaluation side); ``payload()`` materializes
    lazily only for host-side consumers (oracle, hooks, rule-message
    callables, mutators)."""

    __slots__ = ("admission_request", "_payload_bytes", "_payload_cache")

    is_raw = False
    raw = None

    def __init__(self, header: Mapping[str, Any], payload_bytes: bytes):
        self.admission_request = _WireAdmission(header, payload_bytes)
        self._payload_bytes = payload_bytes
        self._payload_cache = None

    def uid(self) -> str:
        return self.admission_request.uid

    def payload(self) -> Any:
        if self._payload_cache is None:
            self._payload_cache = json.loads(self._payload_bytes)
        return self._payload_cache

    def payload_json(self) -> bytes:
        return self._payload_bytes

    def freeze(self) -> tuple:
        """This request as a tuple of bytes, strings and None: what a
        store that keeps tens of thousands of them holds in its place
        (audit/snapshot.py). The collector stops tracking such a tuple
        the first time it meets it, where this object and its header
        would be walked by every full pass for as long as they live."""
        adm = self.admission_request
        kind = adm.request_kind
        return (self._payload_bytes, adm.uid, adm.namespace, adm.operation,
                None if kind is None else kind.kind)

    @classmethod
    def thaw(cls, frozen: tuple) -> "WireValidateRequest":
        payload, uid, namespace, operation, kind = frozen
        return cls({"uid": uid, "namespace": namespace,
                    "operation": operation, "kind": kind}, payload)


# ---------------------------------------------------------------------------
# Evaluation-process side: the bridge
# ---------------------------------------------------------------------------


class EvaluationBridge:
    """Unix-socket server inside the evaluation process: decodes request
    frames, drives the same evaluation path as the in-process handlers,
    answers with (status, body) frames. One task per frame — ordering
    across a connection is NOT preserved (req_id correlates)."""

    def __init__(self, state: Any, socket_path: str):
        self.state = state
        self.socket_path = socket_path
        self._server: asyncio.AbstractServer | None = None
        self._connections: set[asyncio.StreamWriter] = set()

    async def start(self) -> None:
        self._server = await asyncio.start_unix_server(
            self._serve_connection, path=self.socket_path
        )

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
        # close established connections BEFORE wait_closed(): workers
        # detect the bridge's death through EOF (their read loops fail
        # in-flight requests fast and reconnect later), and Python 3.12's
        # wait_closed() blocks until connection handlers finish — a live
        # _serve_connection parked in a read would deadlock the stop
        for writer in list(self._connections):
            writer.close()
        self._connections.clear()
        if self._server is not None:
            await self._server.wait_closed()
            self._server = None

    async def _serve_connection(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        lock = asyncio.Lock()  # frame writes must not interleave
        tasks: set[asyncio.Task] = set()
        self._connections.add(writer)
        try:
            while True:
                frame = await _read_frame(reader)
                if frame is None:
                    break
                task = asyncio.ensure_future(
                    self._handle_frame(frame, writer, lock)
                )
                tasks.add(task)
                task.add_done_callback(tasks.discard)
        finally:
            self._connections.discard(writer)
            for t in tasks:
                t.cancel()
            writer.close()

    async def _handle_frame(
        self, frame: bytes, writer: asyncio.StreamWriter, lock: asyncio.Lock
    ) -> None:
        # req_id first: once we have it, EVERY failure mode must still
        # answer the worker (an unanswered frame hangs an HTTP request);
        # a frame too short to even carry the header closes the connection,
        # which triggers the worker's fail-all-in-flight path
        try:
            req_id, origin_code, pid_len = _REQ_HEADER.unpack_from(frame)
        except struct.error:
            from policy_server_tpu.telemetry.tracing import logger

            logger.error("malformed bridge frame (%d bytes); closing", len(frame))
            writer.close()
            return
        try:
            offset = _REQ_HEADER.size
            policy_id = frame[offset : offset + pid_len].decode()
            rest = frame[offset + pid_len :]
            if origin_code in (ORIGIN_VALIDATE_PARSED, ORIGIN_AUDIT_PARSED):
                (hlen,) = _PARSED_EXTRA.unpack_from(rest)
                header = json.loads(
                    rest[_PARSED_EXTRA.size : _PARSED_EXTRA.size + hlen]
                )
                payload = rest[_PARSED_EXTRA.size + hlen :]
                status, response_body = await self._evaluate_parsed(
                    origin_code, policy_id, header, payload
                )
            else:
                status, response_body = await self._evaluate(
                    origin_code, policy_id, rest
                )
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — same contract as the
            # in-process handlers: every failure maps to a JSON 500
            from policy_server_tpu.telemetry.tracing import logger

            logger.error("bridge frame handling failed: %s", e)
            status = 500
            response_body = json.dumps(
                {"message": "Something went wrong"}
            ).encode()
        async with lock:
            _write_frame(
                writer, _RESP_HEADER.pack(req_id, status) + response_body
            )
            await writer.drain()

    def _route_tenant(self, policy_id: str):
        """Tenant routing over the bridge (round 16, tenancy.py): the
        worker forwards tenant-routed paths as ``"tenant/policy"`` in
        the policy-id field; the shared registry helper resolves to
        THAT tenant's batcher. Returns ``(batcher, bare_policy_id,
        None)`` or ``(None, _, 404 body)`` with the same body the
        in-process aiohttp router answers."""
        from policy_server_tpu.api.api_error import api_error_body
        from policy_server_tpu.tenancy import (
            resolve_tenant_batcher,
            unknown_tenant_message,
        )

        batcher, pid, unknown = resolve_tenant_batcher(
            self.state, policy_id
        )
        if batcher is None:
            return None, pid, api_error_body(
                404, unknown_tenant_message(unknown)
            )
        return batcher, pid, None

    async def _evaluate_parsed(
        self,
        origin_code: int,
        policy_id: str,
        header: Mapping[str, Any],
        payload: bytes,
    ) -> tuple[int, bytes]:
        from policy_server_tpu.api import handlers
        from policy_server_tpu.api.service import RequestOrigin
        from policy_server_tpu.models import AdmissionReviewResponse

        batcher, policy_id, not_found = self._route_tenant(policy_id)
        if batcher is None:
            return 404, not_found
        request = WireValidateRequest(header, payload)
        origin = (
            RequestOrigin.AUDIT
            if origin_code == ORIGIN_AUDIT_PARSED
            else RequestOrigin.VALIDATE
        )
        result = await handlers._evaluate(  # noqa: SLF001 — same package
            batcher, policy_id, request, origin
        )
        if hasattr(result, "status") and hasattr(result, "body"):
            return result.status, result.body or b""  # mapped error
        body_out = json.dumps(AdmissionReviewResponse(result).to_dict())
        return 200, body_out.encode()

    async def _evaluate(
        self, origin_code: int, policy_id: str, body: bytes
    ) -> tuple[int, bytes]:
        # mirror api/handlers semantics exactly — same parse errors, same
        # error mapping, same span-less core (the WORKER owns the span)
        from policy_server_tpu.api import handlers
        from policy_server_tpu.api.api_error import json_body_error
        from policy_server_tpu.api.handlers import (
            BodyError,
            parse_admission_review_bytes,
        )
        from policy_server_tpu.api.service import RequestOrigin
        from policy_server_tpu.models import (
            AdmissionReviewResponse,
            RawReviewRequest,
            RawReviewResponse,
            ValidateRequest,
        )

        try:
            if origin_code == ORIGIN_RAW:
                raw_review = RawReviewRequest.from_dict(json.loads(body))
                request = ValidateRequest.from_raw(raw_review.request)
            else:
                review = parse_admission_review_bytes(body)
                request = ValidateRequest.from_admission(review.request)
        except BodyError as e:
            resp = json_body_error(e.message)
            return resp.status, resp.body or b""
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            resp = json_body_error(
                f"Failed to parse the request body as JSON: {e}"
            )
            return resp.status, resp.body or b""
        except (KeyError, TypeError, ValueError, AttributeError) as e:
            resp = json_body_error(
                f"Failed to deserialize the JSON body: {e}"
            )
            return resp.status, resp.body or b""

        # raw requests evaluate under the VALIDATE origin like the native
        # handler (validate_raw_handler); AUDIT reports the raw verdict
        origin = (
            RequestOrigin.AUDIT
            if origin_code == ORIGIN_AUDIT
            else RequestOrigin.VALIDATE
        )
        batcher, policy_id, not_found = self._route_tenant(policy_id)
        if batcher is None:
            return 404, not_found
        result = await handlers._evaluate(  # noqa: SLF001 — same package
            batcher, policy_id, request, origin
        )
        if hasattr(result, "status") and hasattr(result, "body"):
            return result.status, result.body or b""  # mapped error
        if origin_code == ORIGIN_RAW:
            body_out = json.dumps(RawReviewResponse(result).to_dict())
        else:
            body_out = json.dumps(AdmissionReviewResponse(result).to_dict())
        return 200, body_out.encode()


# ---------------------------------------------------------------------------
# Worker-process side
# ---------------------------------------------------------------------------


class BridgeClient:
    """Multiplexing client over one unix-socket connection."""

    def __init__(self, socket_path: str):
        self.socket_path = socket_path
        self._writer: asyncio.StreamWriter | None = None
        # pending futures are SCOPED PER CONNECTION: a stale read loop from
        # a previous connection must never fail fresh requests riding the
        # new one
        self._pending: dict[int, asyncio.Future] = {}
        self._next_id = 0
        self._lock = asyncio.Lock()
        self._read_task: asyncio.Task | None = None  # strong ref: the loop
        # holds only weak refs and a collected reader would hang every
        # in-flight request
        self._dead = True

    async def connect(self) -> None:
        if self._read_task is not None:
            # a previous connection's loop may still be parked in a read;
            # cancel it so it cannot race the new connection
            self._read_task.cancel()
            self._read_task = None
        reader, writer = await asyncio.open_unix_connection(self.socket_path)
        self._writer = writer
        pending: dict[int, asyncio.Future] = {}
        self._pending = pending
        self._dead = False
        self._read_task = asyncio.ensure_future(
            self._read_loop(reader, pending)
        )

    async def _read_loop(
        self, reader: asyncio.StreamReader, pending: dict[int, asyncio.Future]
    ) -> None:
        """Reader bound to ONE connection: both the stream and the pending
        map are locals, so a superseded loop can only touch its own."""
        try:
            while True:
                frame = await _read_frame(reader)
                if frame is None:
                    break
                req_id, status = _RESP_HEADER.unpack_from(frame)
                fut = pending.pop(req_id, None)
                if fut is not None and not fut.done():
                    fut.set_result((status, frame[_RESP_HEADER.size :]))
        finally:
            # ANY exit — clean close, oversized frame, decode error — must
            # fail THIS connection's in-flight requests; leaving futures
            # pending would hang their HTTP requests
            if pending is self._pending:
                self._dead = True
            for fut in pending.values():
                if not fut.done():
                    fut.set_exception(
                        ConnectionError("evaluation bridge closed")
                    )
            pending.clear()

    async def _ensure_connected(self) -> None:
        if self._dead or self._writer is None or self._writer.is_closing():
            await self.connect()

    async def _call(
        self, origin_code: int, policy_id: str, tail: bytes
    ) -> tuple[int, bytes]:
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        pid = policy_id.encode()
        async with self._lock:
            await self._ensure_connected()
            self._next_id += 1
            req_id = self._next_id
            self._pending[req_id] = fut
            _write_frame(
                self._writer,
                _REQ_HEADER.pack(req_id, origin_code, len(pid)) + pid + tail,
            )
            await self._writer.drain()
        return await fut

    async def call(
        self, origin_code: int, policy_id: str, body: bytes
    ) -> tuple[int, bytes]:
        return await self._call(origin_code, policy_id, body)

    async def call_parsed(
        self,
        origin_code: int,
        policy_id: str,
        header: bytes,
        payload: bytes,
    ) -> tuple[int, bytes]:
        return await self._call(
            origin_code,
            policy_id,
            _PARSED_EXTRA.pack(len(header)) + header + payload,
        )


def build_worker_app(bridge: BridgeClient, hostname: str):
    """The worker's aiohttp app: the three evaluation endpoints with the
    reference span fields; everything stateful proxies to the bridge."""
    from aiohttp import web

    from policy_server_tpu.telemetry.tracing import span

    def make_admission_handler(parsed_origin: int, span_name: str):
        """validate/audit: the WORKER parses and validates the review
        (422s never cross the bridge) and ships a parsed frame the
        evaluation process consumes without re-parsing. Parse/422 mapping
        and span fields come from api/handlers — one contract regardless
        of which process accepted the socket."""
        from policy_server_tpu.api.api_error import json_body_error
        from policy_server_tpu.api.handlers import (
            BodyError,
            _span_fields_from_admission,
            parse_admission_review_bytes,
        )

        async def handler(request: web.Request) -> web.Response:
            policy_id = _wire_policy_id(request)
            body = await request.read()
            try:
                review = parse_admission_review_bytes(body)
            except BodyError as e:
                return json_body_error(e.message)
            adm = review.request
            with span(
                span_name, host=hostname, policy_id=policy_id,
                **_span_fields_from_admission(review),
            ) as fields:
                header = json.dumps(
                    {
                        "uid": adm.uid,
                        "namespace": adm.namespace,
                        "operation": adm.operation,
                        "kind": adm.request_kind.kind
                        if adm.request_kind
                        else None,
                    }
                ).encode()
                # to_dict(), NOT the raw body slice: the payload root must
                # be byte-identical to the in-process path (from_dict may
                # normalize fields, and Exists() semantics depend on it)
                payload_bytes = json.dumps(
                    adm.to_dict(), separators=(",", ":")
                ).encode()
                try:
                    status, payload = await bridge.call_parsed(
                        parsed_origin, policy_id, header, payload_bytes
                    )
                except ConnectionError:
                    return web.json_response(
                        {"message": "evaluation backend unavailable"},
                        status=503,
                    )
                fields["response_code"] = status
                return web.Response(
                    status=status,
                    body=payload,
                    content_type="application/json",
                    headers=_shed_headers(status, payload),
                )

        return handler

    async def raw_handler(request: web.Request) -> web.Response:
        policy_id = _wire_policy_id(request)
        body = await request.read()
        with span(
            "validation_raw", host=hostname, policy_id=policy_id
        ) as fields:
            try:
                status, payload = await bridge.call(
                    ORIGIN_RAW, policy_id, body
                )
            except ConnectionError:
                return web.json_response(
                    {"message": "evaluation backend unavailable"}, status=503
                )
            fields["response_code"] = status
            return web.Response(
                status=status, body=payload, content_type="application/json",
                headers=_shed_headers(status, payload),
            )

    from policy_server_tpu.api.handlers import MAX_BODY_BYTES

    app = web.Application(client_max_size=MAX_BODY_BYTES)
    app.router.add_post(
        "/validate/{policy_id}",
        make_admission_handler(ORIGIN_VALIDATE_PARSED, "validation"),
    )
    app.router.add_post("/validate_raw/{policy_id}", raw_handler)
    app.router.add_post(
        "/audit/{policy_id}",
        make_admission_handler(ORIGIN_AUDIT_PARSED, "audit"),
    )
    # tenant-routed surface (round 16): the tenant travels to the
    # evaluation process inside the policy-id field ("tenant/policy");
    # the bridge resolves it to that tenant's batcher and answers
    # unknown tenants with the in-process 404 body
    validate_h = make_admission_handler(ORIGIN_VALIDATE_PARSED, "validation")
    audit_h = make_admission_handler(ORIGIN_AUDIT_PARSED, "audit")
    app.router.add_post("/validate/{tenant}/{policy_id}", validate_h)
    app.router.add_post("/validate_raw/{tenant}/{policy_id}", raw_handler)
    app.router.add_post("/audit/{tenant}/{policy_id}", audit_h)
    return app


def _wire_policy_id(request: web.Request) -> str:
    """The policy-id field as it crosses the bridge: tenant-routed
    paths encode as ``"tenant/policy"`` (split again on the evaluation
    side), un-prefixed paths stay the bare id."""
    policy_id = request.match_info["policy_id"]
    tenant = request.match_info.get("tenant")
    return policy_id if tenant is None else f"{tenant}/{policy_id}"


async def worker_main(
    socket_path: str, addr: str, port: int, hostname: str,
    frontend: str = "python",
) -> None:
    bridge = BridgeClient(socket_path)
    await bridge.connect()
    if frontend == "native":
        # the worker as a THIN owner of a native event loop: HTTP framing
        # + AdmissionReview parsing run GIL-free (csrc/httpfront.cpp);
        # this asyncio loop only forwards parsed frames over the bridge
        from policy_server_tpu.api.handlers import MAX_BODY_BYTES
        from policy_server_tpu.runtime import native_frontend as nf

        if not nf.native_available():
            # asked for by flag: an error (the respawn breaker then
            # reports the slot), never a quiet aiohttp worker
            raise RuntimeError(
                "--frontend native: csrc/httpfront.cpp failed to build "
                f"or load: {nf.load_error()}"
            )
        sock = None
        try:
            sock = nf.make_listen_socket(addr, port)
            front = nf.NativeFrontend(
                sock,
                nf.BridgeSink(bridge, asyncio.get_running_loop()),
                max_body=MAX_BODY_BYTES,
            )
            front.start()
            try:
                while True:  # serve until the parent terminates us
                    await asyncio.sleep(3600)
            finally:
                front.stop_accepting()
                front.shutdown()
            return
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — soft-dep fallback
            import contextlib

            from policy_server_tpu.telemetry.tracing import logger

            if sock is not None:
                # a leaked SO_REUSEPORT listener would keep receiving a
                # share of connections that nothing ever accepts
                with contextlib.suppress(OSError):
                    sock.close()
            logger.warning(
                "native HTTP frontend unavailable in worker (%s); "
                "falling back to the Python frontend", e,
            )
    from aiohttp import web

    app = build_worker_app(bridge, hostname)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, addr, port, reuse_port=True)
    await site.start()
    while True:  # serve until the parent terminates us
        await asyncio.sleep(3600)


def main() -> int:
    """Worker-process entry: python -m policy_server_tpu.runtime.frontend"""
    import argparse

    from policy_server_tpu.telemetry import setup_tracing

    parser = argparse.ArgumentParser()
    parser.add_argument("--socket", required=True)
    parser.add_argument("--addr", required=True)
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--hostname", default="worker")
    parser.add_argument("--log-level", default="info")
    parser.add_argument("--log-fmt", default="text")
    parser.add_argument(
        "--frontend", default="python", choices=["python", "native"]
    )
    args = parser.parse_args()
    setup_tracing(args.log_level, args.log_fmt)
    try:
        asyncio.run(
            worker_main(
                args.socket, args.addr, args.port, args.hostname,
                args.frontend,
            )
        )
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
