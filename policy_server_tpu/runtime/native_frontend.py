"""ctypes bindings + runtime owner for the native HTTP front-end
(csrc/httpfront.cpp).

The native front-end moves HTTP framing off the Python event loop: epoll
event loops on native threads accept connections, parse HTTP/1.1
(keep-alive, chunked bodies, pipelining), canonicalize AdmissionReview JSON
into the exact compact bytes ``json.dumps(AdmissionRequest.to_dict(),
separators=(",", ":"))`` would produce, and serialize responses — all
GIL-free. Python's per-request work shrinks to: pop a parsed record from a
lock-free ring, submit it to the MicroBatcher, and complete the request
when the batch verdict lands. Round 19 grew verdict serialization into
full batch-granular native response assembly: patches, warnings, and
complete status objects (message/code/reason/details.causes tables) pack
into v2 records (pack_verdict_record — the ONE packing path) and render
in C++ byte-exactly; cache-hit fragments splice uid + pre-encoded
template bytes (pack_frag_record). Only the classified Python-only tail
(audit annotations, surrogate strings) is rendered by Python — the
per-row oracle; graftcheck RS01/RS02 pin the classification and the
emitter's key order to models/admission.py.

Build model mirrors ops/fastenc.py: compiled on demand with g++ into
``build/`` under a name that hashes its source and flags
(utils/nativebuild.py). ``--frontend native`` asks for this library, so a
failed build or load is a boot error there; the Python (aiohttp)
frontend stays the correctness oracle for the differential framing
corpus (tests/test_native_frontend.py), not a silent fallback.

Two sinks consume parsed records:

* :class:`BatcherSink` — the evaluation process: records feed the
  MicroBatcher directly (``submit_nowait``), responses complete through
  the batcher futures' done-callbacks on the dispatch threads.
* :class:`BridgeSink` — a prefork worker (runtime/frontend.py): the
  worker becomes a thin owner of a native event loop, forwarding parsed
  frames over the unix-socket evaluation bridge.
"""

from __future__ import annotations

import ctypes
import json
import math
import socket
import struct
import threading
import time
from pathlib import Path
from typing import Any

from policy_server_tpu import failpoints
from policy_server_tpu.models import FragVerdict
from policy_server_tpu.telemetry import flightrec
from policy_server_tpu.telemetry.tracing import logger
from policy_server_tpu.utils.nativebuild import (
    REPO_ROOT,
    NativeBuildError,
    build_shared_library,
)

_SRC = REPO_ROOT / "csrc" / "httpfront.cpp"

# default request-body cap for DIRECT construction (tests, embedding).
# The server and prefork workers pass api.handlers.MAX_BODY_BYTES
# explicitly (server._start_native_frontend asserts the two agree) so
# the 413 thresholds cannot drift apart behind SO_REUSEPORT; the
# constant is not imported here to keep this module aiohttp-free.
MAX_BODY_BYTES = 8 * 1024**2

# record kinds (csrc/httpfront.cpp)
K_VALIDATE, K_AUDIT, K_RAW, K_VALIDATE_FB, K_AUDIT_FB = 0, 1, 2, 3, 4

# u32 total | u64 req_id | u8 kind | u8 flags | u16 policy/uid/ns/op/gvk/tp
# | u32 payload_len | i64 t_first/t_parse/t_push (flight-recorder stamps
# on CLOCK_MONOTONIC — the clock perf_counter_ns reads on Linux)
_REC = struct.Struct("<IQBB6HI3q")

_STAT_NAMES = (
    "connections_accepted",
    "http_requests",
    "requests_parsed_native",
    "parse_fallbacks",
    "responses_native_serialized",
    "responses_python_serialized",
    "ring_full_rejections",
    "bad_requests",
    "route_misses",
    "oversized_rejections",
    "bytes_in",
    "bytes_out",
    "framing_ns",
    "inflight",
    "midbody_disconnects",
    "idle_timeout_closes",
    "conn_cap_rejections",
    # TLS termination (round 20) — order pinned to the C++ stats enum
    "tls_connections",
    "tls_handshakes_ok",
    "tls_handshakes_failed",
    "tls_handshake_timeouts",
    "tls_handshake_disconnects",
    "tls_handshakes_fail_injected",
    "tls_clean_closes",
)

# buffer we hand httpfront_stats, passed as its cap argument (the C side
# writes min(cap, STAT_N) slots, so the two constants may drift safely);
# only the first len(_STAT_NAMES) slots are named, the rest are headroom
_STAT_SLOTS = 24

_lib_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_pylib: ctypes.PyDLL | None = None
# why the library is unavailable (None: not tried yet, or loaded)
_lib_error: str | None = None  # guarded-by: _lib_lock


def _build_library() -> Path:
    return build_shared_library(_SRC, ["-pthread"], libs=["-ldl"])


def _load() -> ctypes.CDLL | None:
    """The loaded library, or None when it cannot be built or loaded
    (:func:`load_error` says why; one attempt per process). Whoever ASKED
    for the native front-end turns None into an error
    (server._start_native_frontend)."""
    global _lib, _pylib, _lib_error
    with _lib_lock:
        if _lib is not None or _lib_error is not None:
            return _lib
        try:
            path = _build_library()
            lib = ctypes.CDLL(str(path))
            # completion calls are pure memory ops (lock-free stack push,
            # no syscalls): binding them through PyDLL keeps the GIL held
            # for the ~1.5us call instead of paying a release/reacquire
            # bounce per request — under 4 concurrent delivery threads
            # that bounce dominated the serving profile
            pylib = ctypes.PyDLL(str(path))
        except (NativeBuildError, OSError) as e:
            _lib_error = str(e)
            return None
        lib.httpfront_create.restype = ctypes.c_void_p
        lib.httpfront_create.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_int,
        ]
        lib.httpfront_configure.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64,
        ]
        lib.httpfront_set_static.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
        ]
        lib.httpfront_start.restype = ctypes.c_int
        lib.httpfront_start.argtypes = [ctypes.c_void_p]
        lib.httpfront_stop_accepting.argtypes = [ctypes.c_void_p]
        lib.httpfront_stop.argtypes = [ctypes.c_void_p]
        lib.httpfront_destroy.argtypes = [ctypes.c_void_p]
        lib.httpfront_poll.restype = ctypes.c_int64
        lib.httpfront_poll.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
        ]
        pylib.httpfront_complete.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int, ctypes.c_char_p,
            ctypes.c_int64, ctypes.c_int,
        ]
        pylib.httpfront_complete_verdict_bulk.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_int64,
        ]
        pylib.httpfront_render_verdict.restype = ctypes.c_int64
        pylib.httpfront_render_verdict.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_int64,
        ]
        pylib.httpfront_outstanding.restype = ctypes.c_int64
        pylib.httpfront_outstanding.argtypes = [ctypes.c_void_p]
        pylib.httpfront_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ]
        # TLS termination (round 20): the OpenSSL binding is resolved at
        # RUNTIME inside the .so (dlopen) — these entry points exist even
        # when libssl does not, and tls_available() reports which case
        # this process is in
        lib.httpfront_tls_available.restype = ctypes.c_int
        lib.httpfront_tls_error.restype = ctypes.c_char_p
        lib.httpfront_ktls_supported.restype = ctypes.c_int
        lib.httpfront_tls_ctx_create.restype = ctypes.c_void_p
        lib.httpfront_tls_ctx_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_int64, ctypes.c_char_p, ctypes.c_int64,
        ]
        lib.httpfront_tls_ctx_free.argtypes = [ctypes.c_void_p]
        lib.httpfront_set_tls.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.httpfront_tls_configure.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.httpfront_tls_fail_handshakes.argtypes = [
            ctypes.c_void_p, ctypes.c_long,
        ]
        _lib = lib
        _pylib = pylib
        return _lib


def native_available() -> bool:
    return _load() is not None


def load_error() -> str:
    """Why :func:`native_available` is False."""
    _load()
    with _lib_lock:
        return _lib_error or ""


def tls_available() -> bool:
    """True when the native frontend can terminate TLS: the extension
    loaded AND its runtime dlopen of libssl/libcrypto resolved every
    needed symbol. False demands the LOUD aiohttp-TLS fallback."""
    if _load() is None:
        return False
    return bool(_lib.httpfront_tls_available())


def tls_error() -> str:
    """Why native TLS is unavailable (or the last ctx-build error)."""
    if _load() is None:
        return "native frontend unavailable (httpfront.cpp failed to build/load)"
    return (_lib.httpfront_tls_error() or b"").decode("utf-8", "replace")


def ktls_supported() -> bool:
    """Capability probe for kernel-TLS offload after the userspace
    handshake (needs an OpenSSL 3.x kTLS build). A plain answer — the
    caller logs it; nothing silently downgrades either way."""
    return _load() is not None and bool(_lib.httpfront_ktls_supported())


def tls_ctx_create(
    cert_pem: bytes, key_pem: bytes, ca_pem: bytes | None = None
) -> int:
    """Build one native SSL_CTX generation from PEM bytes (certs.py's
    last-good identity snapshot; ``ca_pem`` turns on mTLS with
    CPython-CERT_REQUIRED semantics). Returns an opaque handle; raises
    RuntimeError with the native error string on failure."""
    if _load() is None:
        raise RuntimeError(tls_error())
    handle = _lib.httpfront_tls_ctx_create(
        cert_pem, len(cert_pem), key_pem, len(key_pem),
        ca_pem, len(ca_pem) if ca_pem else 0,
    )
    if not handle:
        raise RuntimeError(f"native TLS context build failed: {tls_error()}")
    return handle


def tls_ctx_free(handle: int) -> None:
    if _lib is not None and handle:
        _lib.httpfront_tls_ctx_free(handle)


def render_verdict_bytes(record: bytes) -> bytes | None:
    """Render one packed v2 verdict record through the SAME native
    emitter serving uses (httpfront_render_verdict) — the differential
    corpus' entry point, so the byte-exactness it proves is the
    byte-exactness production emits. None when the native library is
    unavailable or the record is malformed."""
    if _load() is None:
        return None
    # worst-case py_escape expansion is 6x (\uXXXX per char) plus the
    # fixed envelope
    cap = len(record) * 6 + 8192
    out = ctypes.create_string_buffer(cap)
    n = _pylib.httpfront_render_verdict(record, len(record), out, cap)
    if n < 0:
        return None
    return ctypes.string_at(out, n)


def server_header() -> str:
    """The Server header the aiohttp frontend sends — the native frontend
    emits the same string so the two are byte-identical behind
    SO_REUSEPORT (only the Date value differs)."""
    try:
        from aiohttp.http import SERVER_SOFTWARE

        return SERVER_SOFTWARE
    except ImportError:  # aiohttp-less deployment: still serve
        return "policy-server-tpu"


def make_listen_socket(addr: str, port: int, backlog: int = 1024) -> socket.socket:
    """Bound+listening non-blocking socket with SO_REUSEPORT, so the main
    process and prefork workers can all own native event loops on the one
    API port (the kernel load-balances accepted connections)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
    s.bind((addr, port))
    s.listen(backlog)
    s.setblocking(False)
    return s


class NativeFrontend:
    """Owns one native httpfront instance: the listen socket, the event
    loop threads, the drainer thread, and the completion calls."""

    _POLL_TIMEOUT_MS = 200

    # connection-abuse hardening defaults (soak round 13): idle matches
    # aiohttp's 75 s keep-alive; the read timeout bounds one request's
    # ARRIVAL (header+body), which is what defeats slowloris drips; the
    # connection cap answers an in-band 503 over it (0 = uncapped)
    IDLE_TIMEOUT_MS = 75_000
    READ_TIMEOUT_MS = 30_000
    MAX_CONNECTIONS = 0

    def __init__(
        self,
        sock: socket.socket,
        sink: Any,
        *,
        loops: int = 1,
        max_body: int = MAX_BODY_BYTES,
        ring_bits: int = 12,
        idle_timeout_ms: int | None = None,
        read_timeout_ms: int | None = None,
        max_connections: int | None = None,
    ):
        lib = _load()
        if lib is None:
            raise RuntimeError(
                "native frontend unavailable (csrc/httpfront.cpp failed to "
                "build or load)"
            )
        self._lib = lib
        self._pylib = _pylib  # GIL-holding bindings for the hot non-blocking calls
        self._sock = sock
        self._sink = sink
        self._max_body = max_body
        # poll buffer must hold the largest single record (a fallback
        # record carries the whole raw body)
        self._poll_cap = max_body + 64 * 1024
        self._lock = threading.Lock()
        handle = lib.httpfront_create(
            sock.fileno(), int(loops), int(max_body),
            server_header().encode(), int(ring_bits),
        )
        if not handle:
            raise RuntimeError("httpfront_create failed")
        lib.httpfront_configure(
            handle,
            self.IDLE_TIMEOUT_MS if idle_timeout_ms is None
            else int(idle_timeout_ms),
            self.READ_TIMEOUT_MS if read_timeout_ms is None
            else int(read_timeout_ms),
            self.MAX_CONNECTIONS if max_connections is None
            else int(max_connections),
        )
        self._handle = handle  # guarded-by: _lock
        self._closed = False  # guarded-by: _lock
        self._drainer: threading.Thread | None = None
        self._set_statics(handle)

    # -- static response parity (aiohttp shapes, probed + pinned by the
    #    differential corpus) --------------------------------------------

    def _set_statics(self, handle) -> None:
        text = b"text/plain; charset=utf-8"
        js = b"application/json; charset=utf-8"

        def set_static(slot, status, ct, body, extra=b""):
            self._lib.httpfront_set_static(
                handle, slot, status, ct, body, len(body), extra
            )

        set_static(0, 404, text, b"404: Not Found")
        set_static(1, 405, text, b"405: Method Not Allowed", b"Allow: POST\r\n")
        set_static(
            2, 413, text,
            (
                f"Maximum request body size {self._max_body} exceeded, "
                "actual body size %lld"
            ).encode(),
        )
        set_static(
            3, 503, js,
            json.dumps({"message": "evaluation backend unavailable"}).encode(),
        )
        set_static(4, 400, text, b"Bad Request")

    # -- lifecycle --------------------------------------------------------

    def start(self) -> "NativeFrontend":
        with self._lock:
            handle = self._handle
        rc = self._lib.httpfront_start(handle)
        if rc != 0:
            raise RuntimeError("httpfront_start failed")
        self._drainer = threading.Thread(
            target=self._drain_loop, name="httpfront-drain", daemon=True
        )
        self._drainer.start()
        return self

    def stop_accepting(self) -> None:
        with self._lock:
            if self._closed or not self._handle:
                return
            self._lib.httpfront_stop_accepting(self._handle)

    # -- TLS termination (round 20) ---------------------------------------

    def set_tls(self, ctx_handle: int | None) -> None:
        """Swap the SSL_CTX generation NEW accepts handshake under (the
        native side takes its own reference — the caller's handle stays
        valid until its tls_ctx_free). Established connections drain on
        the generation they pinned at accept. None disables TLS for new
        connections."""
        with self._lock:
            if self._closed or not self._handle:
                return
            self._lib.httpfront_set_tls(self._handle, ctx_handle or None)

    def configure_tls(self, handshake_timeout_ms: int) -> None:
        """Handshake-arrival deadline, measured from ACCEPT and never
        refreshed by arriving bytes — the TLS-layer slowloris clock
        (0 disables)."""
        with self._lock:
            if self._closed or not self._handle:
                return
            self._lib.httpfront_tls_configure(
                self._handle, int(handshake_timeout_ms)
            )

    def fail_tls_handshakes(self, n: int) -> None:
        """`tls.handshake` failpoint backend: fail the next ``n``
        handshakes (n>0), every handshake (-1), or disarm (0)."""
        with self._lock:
            if self._closed or not self._handle:
                return
            self._lib.httpfront_tls_fail_handshakes(self._handle, int(n))

    # -- self-heal surface (round 17, supervision.SelfHealWatchdog) --------

    def drainer_wedged(self) -> bool:
        """True when the drain thread DIED while the frontend is still
        serving: the native loops keep framing requests into the rings,
        but nothing moves them to the batcher — every accepted request
        rots until its webhook timeout."""
        with self._lock:
            closed = self._closed
        t = self._drainer
        return not closed and t is not None and not t.is_alive()

    def revive_drainer(self) -> bool:
        """Rebuild a dead drain thread (the watchdog's repair action) —
        the SPSC ring's single-consumer contract holds because the old
        consumer is provably dead before the new one starts."""
        if not self.drainer_wedged():
            return False
        self._drainer = threading.Thread(
            target=self._drain_loop, name="httpfront-drain-revived",
            daemon=True,
        )
        self._drainer.start()
        return True

    def shutdown(self, timeout: float = 10.0) -> None:
        """Stop serving: wait for every in-flight request's completion to
        flush (the batcher/bridge shutdown resolved their futures before
        this is called), then stop the loops and free the instance."""
        import time as _time

        with self._lock:
            if self._closed:
                return
            handle = self._handle
        deadline = _time.monotonic() + timeout
        while (
            _time.monotonic() < deadline
            and self._pylib.httpfront_outstanding(handle) > 0
        ):
            _time.sleep(0.02)
        self._lib.httpfront_stop(handle)
        drainer_alive = False
        if self._drainer is not None:
            self._drainer.join(timeout=10)
            drainer_alive = self._drainer.is_alive()
            self._drainer = None
        with self._lock:
            self._closed = True
            self._handle = None
        if drainer_alive:
            # the drainer is wedged inside its sink (e.g. a slow Python
            # parse of a huge fallback body): destroying the instance it
            # will poll next would be a use-after-free — leak it instead
            logger.warning(
                "native frontend drainer did not exit within the stop "
                "deadline; leaking the native instance rather than "
                "freeing it under the thread"
            )
        else:
            self._lib.httpfront_destroy(handle)
        try:
            self._sock.close()
        except OSError:
            pass

    # -- completions (any thread) ----------------------------------------

    def complete(
        self, req_id: int, status: int, body: bytes, retry_after: int = 0
    ) -> None:
        with self._lock:
            if self._closed or not self._handle:
                return  # response raced shutdown: the socket is gone anyway
            self._pylib.httpfront_complete(
                self._handle, req_id, status, body, len(body),
                int(retry_after),
            )

    def complete_verdict_bulk(self, records: list[bytes]) -> None:
        """Batch-granular completion fill: ``records`` is a list of
        pre-packed v2 verdict records (pack_verdict_record /
        pack_frag_record) — ONE frontend-lock acquisition and ONE native
        call push every verdict of a dispatched batch onto the MPSC
        completion stack, and the C++ side renders the full response
        shape (patches, warnings, status tables) per record."""
        buf = b"".join(records)
        with self._lock:
            if self._closed or not self._handle:
                return
            self._pylib.httpfront_complete_verdict_bulk(
                self._handle, buf, len(buf), len(records)
            )

    def complete_verdict_rec(self, record: bytes) -> None:
        """One packed v2 verdict record (the per-request legacy path)."""
        with self._lock:
            if self._closed or not self._handle:
                return
            self._pylib.httpfront_complete_verdict_bulk(
                self._handle, record, len(record), 1
            )

    # -- introspection ----------------------------------------------------

    def stats(self) -> dict[str, int]:
        out = (ctypes.c_int64 * _STAT_SLOTS)()
        with self._lock:
            if self._closed or not self._handle:
                return {name: 0 for name in _STAT_NAMES}
            self._pylib.httpfront_stats(
                self._handle,
                ctypes.cast(out, ctypes.POINTER(ctypes.c_int64)),
                _STAT_SLOTS,
            )
        return {name: int(out[i]) for i, name in enumerate(_STAT_NAMES)}

    # -- the drainer ------------------------------------------------------

    @staticmethod
    def _record_burst_phases(burst: list[tuple]) -> None:
        """Flight-recorder native phases for one drained poll burst,
        from the CLOCK_MONOTONIC stamps httpfront carried across the
        SPSC ring: accept (first byte → fully received), parse
        (received → canonicalized + pushed), ring-cross (pushed →
        drained here). Burst AGGREGATES — min start to max end across
        the burst's records, one event per phase per burst, so the
        always-on cost is one clock read per drain cycle."""
        rec = flightrec.recorder()
        if rec is None:
            return
        t_drain = time.perf_counter_ns()
        rows = len(burst)
        # t_first is 0 for requests that arrived in a single read (the
        # arrival window never opened) — substitute the parse stamp so
        # the accept aggregate stays on the timeline's timebase
        firsts = [r[9] if r[9] else r[10] for r in burst]
        parses = [r[10] for r in burst]
        pushes = [r[11] for r in burst]
        rec.record_phase(
            flightrec.PH_NATIVE_ACCEPT, min(firsts), max(parses), rows=rows
        )
        rec.record_phase(
            flightrec.PH_NATIVE_PARSE, min(parses), max(pushes), rows=rows
        )
        rec.record_phase(
            flightrec.PH_RING_CROSS, min(pushes), t_drain, rows=rows
        )

    def _drain_loop(self) -> None:
        buf = ctypes.create_string_buffer(self._poll_cap)
        lib = self._lib
        with self._lock:
            # the handle outlives this thread by construction: shutdown()
            # stops the loops and joins the drainer BEFORE destroy
            handle = self._handle
        sink = self._sink
        unpack_from = _REC.unpack_from
        rec_size = _REC.size
        while True:
            n = lib.httpfront_poll(
                handle, buf, self._poll_cap, self._POLL_TIMEOUT_MS
            )
            if n < 0:
                return  # stopped and fully drained
            if n == 0:
                continue
            # string_at copies exactly n bytes — buf.raw[:n] would copy
            # the full poll buffer (max_body-sized) per drain cycle
            data = ctypes.string_at(buf, n)
            off = 0
            burst: list[tuple] = []
            while off < n:
                (
                    total, req_id, kind, flags, plen, ulen, nslen, oplen,
                    glen, tplen, paylen, t_first, t_parse, t_push,
                ) = unpack_from(data, off)
                p = off + rec_size
                policy = data[p : p + plen].decode()
                p += plen
                uid = data[p : p + ulen].decode()
                p += ulen
                ns = data[p : p + nslen].decode() if flags & 1 else None
                p += nslen
                op = data[p : p + oplen].decode()
                p += oplen
                gvk = data[p : p + glen].decode()
                p += glen
                # errors="replace": the C++ side gates the header to
                # printable ASCII, but a client-controlled field must
                # NEVER be able to kill the drain thread with a strict-
                # decode raise (replaced chars fail traceparent parsing
                # → fresh root, which is the malformed contract)
                tp = (
                    data[p : p + tplen].decode(errors="replace")
                    if tplen else ""
                )
                p += tplen
                payload = data[p : p + paylen]
                off += total
                burst.append(
                    (
                        req_id, kind, policy, uid, ns, op, gvk, payload,
                        tp, t_first, t_parse, t_push,
                    )
                )
            if burst:
                self._record_burst_phases(burst)
            # chaos site: a fault at frontend intake (drainer dies mid-
            # handoff / sink wiring broken) must answer every request of
            # the burst in-band, never strand them — fired per BURST,
            # not per record (hot-path discipline)
            try:
                failpoints.fire("frontend.accept")
            except Exception as e:  # noqa: BLE001 — injected intake fault
                logger.error("native frontend intake fault: %s", e)
                body = json.dumps(
                    {"message": "Something went wrong", "status": 500}
                ).encode()
                for rec in burst:
                    self.complete(rec[0], 500, body)
                continue
            # array-at-a-time handoff (round 12): the whole poll burst
            # crosses into the sink in ONE call — the BatcherSink turns
            # it into one submit_many instead of a ring-pop →
            # submit_nowait hop per request. Sinks without a burst
            # surface (BridgeSink, embedders) get the per-record calls.
            handle_burst = getattr(sink, "handle_burst", None)
            if handle_burst is not None:
                try:
                    handle_burst(self, burst)
                except Exception as e:  # noqa: BLE001 — a broken burst
                    # must answer every request, not hang them
                    logger.error("native frontend sink failed: %s", e)
                    body = json.dumps(
                        {"message": "Something went wrong", "status": 500}
                    ).encode()
                    for rec in burst:
                        self.complete(rec[0], 500, body)
                continue
            for (
                req_id, kind, policy, uid, ns, op, gvk, payload,
                _tp, _tf, _tpr, _tpu,
            ) in burst:
                try:
                    sink.handle(
                        self, req_id, kind, policy, uid, ns, op, gvk, payload
                    )
                except Exception as e:  # noqa: BLE001 — a broken record
                    # must answer, not hang its HTTP request
                    logger.error("native frontend sink failed: %s", e)
                    self.complete(
                        req_id, 500,
                        json.dumps(
                            {"message": "Something went wrong", "status": 500}
                        ).encode(),
                    )


class NativeTlsManager:
    """Glue between certs.py's last-good identity machinery and the
    native frontend's TLS termination (round 20).

    * builds SSL_CTX generations from ``ReloadableTlsContext``
      SNAPSHOTS — the validated bytes the aiohttp contexts serve, never
      files on disk mid-rotation;
    * registers a reload listener so SIGHUP/digest rotation atomically
      swaps the generation NEW connections handshake under, while
      established connections drain on the one they pinned at accept; a
      failed native rebuild keeps the previous generation serving
      (counted and logged, mirroring certs.py's keep-last-good rule);
    * bridges the ``tls.handshake`` failpoint: a short poll loop fires
      the pure-Python site and arms/disarms the native refuse-handshakes
      knob, so chaos and soak can fault the TLS accept path without the
      C++ side knowing what a failpoint is.
    """

    HANDSHAKE_TIMEOUT_MS = 10_000
    _FAILPOINT_POLL_SECONDS = 0.25

    def __init__(
        self,
        frontend: NativeFrontend,
        reloadable,
        *,
        handshake_timeout_ms: int | None = None,
    ):
        self._frontend = frontend
        self._reloadable = reloadable
        self._lock = threading.Lock()
        self._ctx_handle: int | None = None  # guarded-by: _lock
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._fail_armed = False
        self.generations = 0  # successful installs (guarded-by: _lock)
        self.failed_swaps = 0  # guarded-by: _lock
        frontend.configure_tls(
            self.HANDSHAKE_TIMEOUT_MS
            if handshake_timeout_ms is None
            else int(handshake_timeout_ms)
        )
        self._install_current()  # raises when the identity will not build
        reloadable.add_reload_listener(self._on_reload)
        self._thread = threading.Thread(
            target=self._failpoint_loop, name="native-tls-failpoints",
            daemon=True,
        )
        self._thread.start()

    def _install_current(self) -> None:
        cert_pem, key_pem = self._reloadable.identity_snapshot()
        ca = self._reloadable.client_ca_snapshot()
        handle = tls_ctx_create(
            cert_pem, key_pem, ca.encode() if ca else None
        )
        self._frontend.set_tls(handle)
        with self._lock:
            old, self._ctx_handle = self._ctx_handle, handle
            self.generations += 1
        if old:
            tls_ctx_free(old)

    def _on_reload(self) -> None:
        if self._stop.is_set():
            # the reloadable outlives this manager (its watcher thread
            # is daemon-global); a post-stop rotation must not rebuild
            # contexts for torn-down loops
            return
        try:
            self._install_current()
            logger.info(
                "native TLS generation rotated (generation %d): new "
                "connections handshake under the new identity, "
                "established connections drain on the old one",
                self.generations,
            )
        except Exception as e:  # noqa: BLE001 — keep last-good serving
            with self._lock:
                self.failed_swaps += 1
            logger.error(
                "native TLS generation rebuild failed; the previous "
                "identity keeps serving: %s", e,
            )

    def _failpoint_loop(self) -> None:
        while not self._stop.wait(self._FAILPOINT_POLL_SECONDS):
            self.poll_failpoint_once()

    def poll_failpoint_once(self) -> None:
        """One ``tls.handshake`` failpoint evaluation (the loop body,
        and the deterministic entry tests drive directly): an armed
        raising site makes the native loops refuse EVERY new handshake
        until the site disarms; disarming restores service."""
        try:
            failpoints.fire("tls.handshake")
            armed = False
        except Exception:  # noqa: BLE001 — any raise means "refuse"
            armed = True
        if armed != self._fail_armed:
            self._fail_armed = armed
            self._frontend.fail_tls_handshakes(-1 if armed else 0)

    def snapshot(self) -> dict:
        """Rotation/identity introspection for runtime metrics."""
        reloads, reload_failures = self._reloadable.counters()
        with self._lock:
            generations = self.generations
            failed_swaps = self.failed_swaps
        return {
            "generations": generations,
            "failed_swaps": failed_swaps,
            "reloads": reloads,
            "reload_failures": reload_failures,
            "cert_expiry_epoch": self._reloadable.identity_not_after(),
            "ktls": ktls_supported(),
        }

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        with self._lock:
            handle, self._ctx_handle = self._ctx_handle, None
        if handle:
            tls_ctx_free(handle)


_SHED_MESSAGE = "policy server overloaded; retry later"


def _shed_body(
    retry_after: int,
    message: str = _SHED_MESSAGE,
) -> bytes:
    # byte parity with api/handlers._evaluate's shed json_response; the
    # message parameter carries FencedError's 503 text (shard fenced)
    return json.dumps(
        {
            "message": message,
            "retry_after_seconds": retry_after,
        }
    ).encode()


def _api_error_body(status: int, message: str) -> bytes:
    # byte parity with api/api_error.api_error — one shared builder
    from policy_server_tpu.api.api_error import api_error_body

    return api_error_body(status, message)


# -- native response assembly: the one source of truth (round 19) -----------
# Classification of every AdmissionResponse / ValidationStatus field into
# natively-serialized vs Python-rendered. graftcheck RS01 checks this
# partition is TOTAL over models/admission.py's to_dict keys (a new model
# field without a classification fails `make check`), and RS02 checks the
# C++ emitter's literal key order against to_dict's. pack_verdict_record
# below is the ONE packing path serving, tests, and the differential
# corpus share.
NATIVE_RESPONSE_FIELDS = frozenset(
    {"uid", "allowed", "patch_type", "patch", "status", "warnings"}
)
PYTHON_ONLY_RESPONSE_FIELDS = frozenset({"audit_annotations"})
NATIVE_STATUS_FIELDS = frozenset({"message", "code", "reason", "details"})
PYTHON_ONLY_STATUS_FIELDS: frozenset = frozenset()

# v2 bulk verdict record header (csrc/httpfront.cpp
# parse_verdict_record documents the full layout):
#   u64 req_id | u8 allowed | u8 raw_shape | u8 flags | u8 n_warnings |
#   i32 code | i32 uid_len | i32 msg_len | i32 patch_len |
#   i32 reason_len | i32 n_causes
# then uid | msg | patch | reason | warnings (u32 len + bytes each) |
# causes (i32 field_len | i32 msg_len | field | msg each). -1 lengths =
# absent; flags bit0 = status present, bit1 = warnings list present.
_BULK_REC = struct.Struct("<QBBBBiiiiii")
_WARN_LEN = struct.Struct("<I")
_CAUSE_LEN = struct.Struct("<ii")
# the record's leading u64 alone — the in-band error path recovers
# req_ids from records whose bulk fill failed as a unit
_REC_REQ_ID = struct.Struct("<Q")
# status codes ride an i32 with -1 as the absent sentinel: anything
# outside [0, 2^31) must take the Python renderer (json has no such
# bound; struct.pack would raise, not truncate)
_CODE_MAX = 0x7FFFFFFF


def _pack_causes(causes_b) -> bytes:
    """The (field_len | msg_len | field | msg) cause tail — ONE wire
    encoding shared by pack_verdict_record and pack_frag_record."""
    parts = []
    for fb, mb in causes_b:
        parts.append(
            _CAUSE_LEN.pack(
                -1 if fb is None else len(fb), -1 if mb is None else len(mb)
            )
        )
        if fb is not None:
            parts.append(fb)
        if mb is not None:
            parts.append(mb)
    return b"".join(parts)


def pack_verdict_record(req_id: int, r: Any, raw_shape: bool) -> bytes | None:
    """Pack one AdmissionResponse-shaped verdict into the v2 record the
    native serializer renders byte-exactly. Returns None when the shape
    needs the Python renderer: audit annotations (the classified
    python-only field), a patchType without a patch (or a non-JSONPatch
    type), negative status codes, >255 warnings, or strings json can
    serialize but utf-8 cannot encode (surrogates)."""
    if r.audit_annotations is not None:
        return None
    patch = r.patch
    if (patch is None) != (r.patch_type is None) or (
        r.patch_type is not None and r.patch_type != "JSONPatch"
    ):
        return None
    st = r.status
    warnings = r.warnings
    try:
        uid_b = r.uid.encode()
        msg_b = reason_b = None
        code = -1
        n_causes = -1
        causes_b: tuple = ()
        flags = 0
        if st is not None:
            flags |= 1
            if st.message is not None:
                msg_b = st.message.encode()
            if st.code is not None:
                if not 0 <= st.code <= _CODE_MAX:
                    # -1 is the absent sentinel and the wire is i32;
                    # wasm host verdicts carry policy-controlled codes
                    return None
                code = int(st.code)
            if st.reason is not None:
                reason_b = st.reason.encode()
            if st.details is not None:
                causes_b = tuple(
                    (
                        c.field.encode() if c.field is not None else None,
                        c.message.encode() if c.message is not None else None,
                    )
                    for c in st.details.causes
                )
                n_causes = len(causes_b)
        patch_b = patch.encode() if patch is not None else None
        warn_b = None
        if warnings is not None:
            if len(warnings) > 255:
                return None
            flags |= 2
            warn_b = [w.encode() for w in warnings]
    except (UnicodeEncodeError, AttributeError):
        return None
    parts = [
        _BULK_REC.pack(
            req_id, 1 if r.allowed else 0, 1 if raw_shape else 0,
            flags, len(warn_b) if warn_b is not None else 0, code,
            len(uid_b),
            -1 if msg_b is None else len(msg_b),
            -1 if patch_b is None else len(patch_b),
            -1 if reason_b is None else len(reason_b),
            n_causes,
        ),
        uid_b,
    ]
    if msg_b is not None:
        parts.append(msg_b)
    if patch_b is not None:
        parts.append(patch_b)
    if reason_b is not None:
        parts.append(reason_b)
    if warn_b:
        for w in warn_b:
            parts.append(_WARN_LEN.pack(len(w)))
            parts.append(w)
    if causes_b:
        parts.append(_pack_causes(causes_b))
    return b"".join(parts)


def pack_frag_record(
    req_id: int, frag: Any, raw_shape: bool
) -> bytes | None:
    """pack_verdict_record's fragment lane: a FragVerdict's template
    already carries pre-encoded message/cause bytes, so its row packs
    as one header + uid + the template's memoized tail — no per-row
    string encoding beyond the uid. The tail is cached on the template
    (native_tail) the first time it ships."""
    t = frag.tmpl
    try:
        uid_b = frag.uid.encode()
    except UnicodeEncodeError:
        return None
    tail = t.native_tail
    if tail is None:
        if t.code is not None and not 0 <= t.code <= _CODE_MAX:
            return None  # outside the i32 wire range: Python renders
        n_causes = -1 if t.causes_b is None else len(t.causes_b)
        tail = (
            t.allowed,
            0 if t.status is None else 1,  # flags: status present
            -1 if t.code is None else int(t.code),
            t.msg_b,
            n_causes,
            _pack_causes(t.causes_b or ()),
        )
        t.native_tail = tail
    allowed, flags, code, msg_b, n_causes, causes_tail = tail
    header = _BULK_REC.pack(
        req_id, 1 if allowed else 0, 1 if raw_shape else 0,
        flags, 0, code, len(uid_b),
        -1 if msg_b is None else len(msg_b),
        -1, -1, n_causes,
    )
    if msg_b is None:
        return b"".join((header, uid_b, causes_tail))
    return b"".join((header, uid_b, msg_b, causes_tail))


class BatcherSink:
    """Evaluation-process sink: parsed records feed the MicroBatcher
    array-at-a-time (``submit_many``, one call per poll burst); verdicts
    come back batch-granular through :meth:`deliver_many` — one
    frontend-lock acquisition and one native bulk completion call per
    dispatched batch."""

    def __init__(self, state: Any):
        self.state = state  # ApiServerState: epoch flips rebind .batcher
        # the sink's token → the completion route: (frontend, req_id,
        # raw_shape). The frontend rides in the token (not on self) so an
        # epoch flip or multi-frontend embedding can never cross wires.

    def _route(self, policy_id: str):
        """Tenant routing (round 16, tenancy.py): a two-segment id
        ("tenant/policy" — the C++ router passes it through verbatim)
        resolves through the shared registry helper to THAT tenant's
        batcher; bare ids keep the default epoch pointer. Returns
        ``(batcher, bare_policy_id, None)`` or ``(None, _, 404 body)``
        — the 404 text is shared with the aiohttp router so both
        frontends answer unknown tenants byte-identically. Hot-path
        discipline: this runs per RECORD of every poll burst, so the
        single-tenant common case is one substring test."""
        if "/" not in policy_id:
            return self.state.batcher, policy_id, None
        from policy_server_tpu.tenancy import (
            resolve_tenant_batcher,
            unknown_tenant_message,
        )

        batcher, pid, unknown = resolve_tenant_batcher(
            self.state, policy_id
        )
        if batcher is None:
            return None, pid, _api_error_body(
                404, unknown_tenant_message(unknown)
            )
        return batcher, pid, None

    def handle_burst(
        self, frontend: NativeFrontend, burst: list[tuple]
    ) -> None:
        """One poll burst → at most one submit_many per (tenant batcher,
        origin) group; fallback records (Python parse oracle, raw
        shapes) keep their per-record path — they are the rare tail by
        construction."""
        from policy_server_tpu.api.service import RequestOrigin
        from policy_server_tpu.runtime.frontend import WireValidateRequest
        from policy_server_tpu.telemetry import otlp

        rec = flightrec.recorder()
        t_admit = time.perf_counter_ns() if rec is not None else 0
        # parse incoming W3C traceparent headers only when a span
        # pipeline exists to parent to (--log-fmt otlp); the common
        # deployment skips the per-record parse entirely
        tp_enabled = otlp.tracer() is not None
        # (id(batcher), origin) → [batcher, origin, items, tokens, ctxs]
        # — one bulk admission per serving batcher per burst; the
        # single-tenant common case degenerates to the historical
        # one-group-per-origin
        groups: dict = {}
        for (
            req_id, kind, policy_id, uid, ns, op, gvk, payload,
            tp, _tf, _tpr, _tpu,
        ) in burst:
            if kind in (K_VALIDATE, K_AUDIT):
                batcher, pid, not_found = self._route(policy_id)
                if batcher is None:
                    frontend.complete(req_id, 404, not_found)
                    continue
                header = {
                    "uid": uid,
                    "namespace": ns,
                    "operation": op,
                    "kind": gvk or None,
                }
                request: Any = WireValidateRequest(header, payload)
                origin = (
                    RequestOrigin.AUDIT if kind == K_AUDIT
                    else RequestOrigin.VALIDATE
                )
                g = groups.setdefault(
                    (id(batcher), origin), [batcher, origin, [], [], []]
                )
                g[2].append((pid, request))
                g[3].append((frontend, req_id, False))
                g[4].append(
                    otlp.parse_traceparent(tp)
                    if tp_enabled and tp else None
                )
            else:
                try:
                    self._handle_fallback(
                        frontend, req_id, kind, policy_id, payload
                    )
                except Exception as e:  # noqa: BLE001 — a broken record
                    # must answer, not hang its HTTP request
                    logger.error("native frontend record failed: %s", e)
                    frontend.complete(
                        req_id, 500,
                        _api_error_body(500, "Something went wrong"),
                    )
        # per-submission containment: a failure admitting one group must
        # answer only ITS records — another group may already be
        # submitted (double-completing admitted rows would race their
        # real verdicts), and fallback records above already answered
        for batcher, origin, g_items, g_tokens, g_ctxs in groups.values():
            try:
                batcher.submit_many(
                    g_items, origin, sink=self, tokens=g_tokens,
                    trace_ctxs=(
                        g_ctxs if any(c is not None for c in g_ctxs)
                        else None
                    ),
                )
            except Exception as e:  # noqa: BLE001 — answer, don't hang
                logger.error("bulk submission failed: %s", e)
                body = _api_error_body(500, "Something went wrong")
                for _fe, req_id, _raw in g_tokens:
                    frontend.complete(req_id, 500, body)
        if rec is not None and groups:
            rec.record_phase(
                flightrec.PH_ADMIT, t_admit, time.perf_counter_ns(),
                rows=sum(len(g[2]) for g in groups.values()),
            )

    def _handle_fallback(
        self, frontend, req_id, kind, policy_id, payload
    ) -> None:
        from policy_server_tpu.api.service import RequestOrigin
        from policy_server_tpu.models import ValidateRequest

        raw_shape = False
        if kind in (K_VALIDATE_FB, K_AUDIT_FB):
            # the native parser declined (float, dup key, bad syntax, …):
            # Python is the parse oracle, 422 bodies are bit-exact
            from policy_server_tpu.api.handlers import (
                BodyError,
                parse_admission_review_bytes,
            )

            try:
                review = parse_admission_review_bytes(payload)
            except BodyError as e:
                frontend.complete(
                    req_id, 422, _api_error_body(422, e.message)
                )
                return
            request = ValidateRequest.from_admission(review.request)
            origin = (
                RequestOrigin.AUDIT if kind == K_AUDIT_FB
                else RequestOrigin.VALIDATE
            )
        else:  # K_RAW — mirror the bridge's raw-path parse errors exactly
            from policy_server_tpu.models import RawReviewRequest

            raw_shape = True
            try:
                raw_review = RawReviewRequest.from_dict(json.loads(payload))
                request = ValidateRequest.from_raw(raw_review.request)
            except (json.JSONDecodeError, UnicodeDecodeError) as e:
                frontend.complete(
                    req_id, 422,
                    _api_error_body(
                        422, f"Failed to parse the request body as JSON: {e}"
                    ),
                )
                return
            except (KeyError, TypeError, ValueError, AttributeError) as e:
                frontend.complete(
                    req_id, 422,
                    _api_error_body(
                        422, f"Failed to deserialize the JSON body: {e}"
                    ),
                )
                return
            origin = RequestOrigin.VALIDATE
        self._submit(frontend, req_id, policy_id, request, origin, raw_shape)

    def _submit(
        self, frontend, req_id, policy_id, request, origin, raw_shape
    ) -> None:
        from policy_server_tpu.runtime.batcher import ShedError

        batcher, policy_id, not_found = self._route(policy_id)
        if batcher is None:
            frontend.complete(req_id, 404, not_found)
            return
        try:
            fut = batcher.submit_nowait(policy_id, request, origin)
        except ShedError as e:
            retry = max(1, math.ceil(e.retry_after_seconds))
            status = getattr(e, "http_status", 429)
            msg = getattr(e, "message", _SHED_MESSAGE)
            frontend.complete(
                req_id, status, _shed_body(retry, msg), retry
            )
            return
        fut.add_done_callback(
            lambda f: _deliver(frontend, req_id, raw_shape, f)
        )

    # -- batch-granular completion (runtime/batcher.py CompletionSink) ----

    def deliver_many(self, completions: list[tuple]) -> None:
        """One call per dispatched batch: the common verdict shape packs
        into ONE native bulk fill; errors, sheds, and exotic shapes take
        their per-record paths (the rare tail). Every record is
        individually guarded — one broken response must answer 500, not
        strand the rest of the batch's HTTP callers."""
        bulk_by_frontend: dict = {}
        for token, response, exc in completions:
            frontend, req_id, raw_shape = token
            try:
                self._deliver_one(
                    bulk_by_frontend, frontend, req_id, raw_shape,
                    response, exc,
                )
            except Exception as e:  # noqa: BLE001 — answer, don't hang
                logger.error("completion delivery failed: %s", e)
                try:
                    frontend.complete(
                        req_id, 500,
                        _api_error_body(500, "Something went wrong"),
                    )
                except Exception:  # noqa: BLE001 — frontend gone
                    pass
        rec = flightrec.recorder()
        t_ser = (
            time.perf_counter_ns()
            if rec is not None and bulk_by_frontend else 0
        )
        for frontend, records in bulk_by_frontend.items():
            try:
                frontend.complete_verdict_bulk(records)
            except Exception as e:  # noqa: BLE001 — last resort: the
                # packed fill failed as a unit; answer each in-band
                # (req_id is the v2 record's leading u64)
                logger.error("bulk completion fill failed: %s", e)
                for record in records:
                    try:
                        frontend.complete(
                            _REC_REQ_ID.unpack_from(record)[0], 500,
                            _api_error_body(500, "Something went wrong"),
                        )
                    except Exception:  # noqa: BLE001
                        pass
        if t_ser:
            # the verdict handoff + native serialize enqueue window (the
            # event-loop thread renders the bytes asynchronously; the
            # C++ framing_ns counter carries that side)
            rec.record_phase(
                flightrec.PH_NATIVE_SERIALIZE, t_ser,
                time.perf_counter_ns(),
                rows=sum(len(r) for r in bulk_by_frontend.values()),
            )

    def _deliver_one(
        self, bulk_by_frontend, frontend, req_id, raw_shape, response, exc
    ) -> None:
        if exc is not None:
            self._deliver_exc(frontend, req_id, exc)
            return
        r = response
        # v2 native assembly (round 19): cache-hit fragments splice
        # uid + template bytes; full AdmissionResponses — patches,
        # warnings, status tables included — pack once and render in
        # C++. None = the classified Python-only tail (annotations,
        # surrogates).
        rec = (
            pack_frag_record(req_id, r, raw_shape)
            if type(r) is FragVerdict
            else pack_verdict_record(req_id, r, raw_shape)
        )
        if rec is not None:
            bulk_by_frontend.setdefault(frontend, []).append(rec)
            return
        from policy_server_tpu.models import (
            AdmissionReviewResponse,
            RawReviewResponse,
        )

        env = RawReviewResponse(r) if raw_shape else AdmissionReviewResponse(r)
        frontend.complete(req_id, 200, json.dumps(env.to_dict()).encode())

    @staticmethod
    def _deliver_exc(frontend, req_id: int, exc: BaseException) -> None:
        from policy_server_tpu.evaluation.errors import PolicyNotFoundError
        from policy_server_tpu.runtime.batcher import ShedError

        if isinstance(exc, ShedError):
            retry = max(1, math.ceil(exc.retry_after_seconds))
            status = getattr(exc, "http_status", 429)
            msg = getattr(exc, "message", _SHED_MESSAGE)
            frontend.complete(
                req_id, status, _shed_body(retry, msg), retry
            )
        elif isinstance(exc, PolicyNotFoundError):
            frontend.complete(req_id, 404, _api_error_body(404, str(exc)))
        else:
            logger.error("Evaluation error: %s", exc)
            frontend.complete(
                req_id, 500, _api_error_body(500, "Something went wrong")
            )


def _deliver(frontend: NativeFrontend, req_id: int, raw_shape: bool, fut) -> None:
    """Map a resolved batcher future to the HTTP answer — the native
    analog of api/handlers._evaluate's error mapping. Runs as a future
    done-callback: ANY escape would strand the HTTP request until the
    caller's webhook timeout, so the whole body is guarded."""
    from policy_server_tpu.evaluation.errors import PolicyNotFoundError

    try:
        exc = fut.exception()
        if exc is not None:
            if isinstance(exc, PolicyNotFoundError):
                frontend.complete(
                    req_id, 404, _api_error_body(404, str(exc))
                )
            else:
                logger.error("Evaluation error: %s", exc)
                frontend.complete(
                    req_id, 500, _api_error_body(500, "Something went wrong")
                )
            return
        r = fut.result()
        rec = (
            pack_frag_record(req_id, r, raw_shape)
            if type(r) is FragVerdict
            else pack_verdict_record(req_id, r, raw_shape)
        )
        if rec is not None:
            frontend.complete_verdict_rec(rec)
            return
        from policy_server_tpu.models import (
            AdmissionReviewResponse,
            RawReviewResponse,
        )

        env = RawReviewResponse(r) if raw_shape else AdmissionReviewResponse(r)
        frontend.complete(req_id, 200, json.dumps(env.to_dict()).encode())
    except Exception as e:  # noqa: BLE001 — answer, never hang
        logger.error("verdict delivery failed: %s", e)
        try:
            frontend.complete(
                req_id, 500, _api_error_body(500, "Something went wrong")
            )
        except Exception:  # noqa: BLE001 — frontend gone
            pass


class BridgeSink:
    """Prefork-worker sink: the worker owns a native event loop and
    forwards parsed frames over the unix-socket evaluation bridge. The
    bridge client is asyncio; the drainer hops onto the worker's loop via
    run_coroutine_threadsafe (frame forwarding is cheap — the HTTP
    framing this worker used to spend its loop on is already done)."""

    def __init__(self, bridge: Any, loop: Any):
        self.bridge = bridge
        self.loop = loop

    def handle(
        self,
        frontend: NativeFrontend,
        req_id: int,
        kind: int,
        policy_id: str,
        uid: str,
        ns: str | None,
        op: str,
        gvk: str,
        payload: bytes,
    ) -> None:
        import asyncio

        coro = self._forward(
            frontend, req_id, kind, policy_id, uid, ns, op, gvk, payload
        )
        asyncio.run_coroutine_threadsafe(coro, self.loop)

    async def _forward(
        self, frontend, req_id, kind, policy_id, uid, ns, op, gvk, payload
    ) -> None:
        from policy_server_tpu.runtime import frontend as fr

        try:
            if kind in (K_VALIDATE, K_AUDIT):
                header = json.dumps(
                    {
                        "uid": uid,
                        "namespace": ns,
                        "operation": op,
                        "kind": gvk or None,
                    }
                ).encode()
                status, body = await self.bridge.call_parsed(
                    fr.ORIGIN_AUDIT_PARSED if kind == K_AUDIT
                    else fr.ORIGIN_VALIDATE_PARSED,
                    policy_id, header, payload,
                )
            elif kind in (K_VALIDATE_FB, K_AUDIT_FB):
                # worker-side parse (422s never cross the bridge), then the
                # canonical to_dict() payload — same as the aiohttp worker
                from policy_server_tpu.api.handlers import (
                    BodyError,
                    parse_admission_review_bytes,
                )

                try:
                    review = parse_admission_review_bytes(payload)
                except BodyError as e:
                    frontend.complete(
                        req_id, 422, _api_error_body(422, e.message)
                    )
                    return
                adm = review.request
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
                payload_bytes = json.dumps(
                    adm.to_dict(), separators=(",", ":")
                ).encode()
                status, body = await self.bridge.call_parsed(
                    fr.ORIGIN_AUDIT_PARSED if kind == K_AUDIT_FB
                    else fr.ORIGIN_VALIDATE_PARSED,
                    policy_id, header, payload_bytes,
                )
            else:  # K_RAW
                status, body = await self.bridge.call(
                    fr.ORIGIN_RAW, policy_id, payload
                )
        except ConnectionError:
            frontend.complete(
                req_id, 503,
                json.dumps(
                    {"message": "evaluation backend unavailable"}
                ).encode(),
            )
            return
        except Exception as e:  # noqa: BLE001 — same contract as the
            # aiohttp worker: every failure maps to a JSON 500
            logger.error("bridge forward failed: %s", e)
            frontend.complete(
                req_id, 500, _api_error_body(500, "Something went wrong")
            )
            return
        retry_after = 0
        if status == 429:
            headers = fr._shed_headers(status, body)  # noqa: SLF001
            if headers:
                retry_after = int(headers["Retry-After"])
        frontend.complete(req_id, status, body, retry_after)
