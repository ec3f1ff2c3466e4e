"""Native HTTP front-end tests (csrc/httpfront.cpp +
runtime/native_frontend.py).

The core is the DIFFERENTIAL FRAMING CORPUS: the same raw byte streams —
valid, malformed, oversized, chunked, keep-alive, pipelined, unicode,
float-bearing, duplicate-keyed, mid-body-disconnected — replayed against
two live servers that differ ONLY in ``--frontend``; status lines, headers
(incl. Retry-After; the Date value is the one excluded volatile), and body
bytes must match exactly. The Python (aiohttp) frontend is the correctness
oracle; the native frontend earns its throughput by being
indistinguishable from it.

Also covered: an extension that cannot build/load is a boot error under
``--frontend native``, never a quiet Python front-end."""

from __future__ import annotations

import asyncio
import json
import socket
import time

import pytest
import requests

from policy_server_tpu.server import PolicyServer
from test_server import ServerHandle, make_config, pod_review_body

nf = pytest.importorskip(
    "policy_server_tpu.runtime.native_frontend",
    reason="native frontend module unavailable",
)

pytestmark = pytest.mark.skipif(
    not nf.native_available(),
    reason="httpfront.cpp failed to build (no g++?)",
)


@pytest.fixture(scope="module")
def pair():
    """One policy set, two frontends: (python_handle, native_handle)."""
    from policy_server_tpu.telemetry import metrics as metrics_mod

    metrics_mod.reset_metrics_for_tests()
    py = ServerHandle(make_config(frontend="python"))
    nat = ServerHandle(make_config(frontend="native"))
    assert nat.server._native_frontend is not None, (
        "native frontend did not come up despite native_available()"
    )
    yield py, nat
    nat.stop()
    py.stop()


# -- raw-socket helpers ------------------------------------------------------


def send_raw(port: int, data: bytes, timeout: float = 15.0) -> bytes:
    s = socket.create_connection(("127.0.0.1", port))
    try:
        s.sendall(data)
        s.settimeout(timeout)
        out = b""
        try:
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                out += chunk
        except socket.timeout:
            pass
        return out
    finally:
        s.close()


def parse_responses(stream: bytes) -> list[tuple[str, dict, bytes]]:
    """Split a byte stream into (status_line, headers, body) responses.
    100-continue interim responses are kept as body-less entries."""
    out = []
    rest = stream
    while rest:
        head_end = rest.find(b"\r\n\r\n")
        if head_end < 0:
            out.append(("<trailing-garbage>", {}, rest))
            break
        head = rest[:head_end].decode("latin-1")
        rest = rest[head_end + 4 :]
        lines = head.split("\r\n")
        status_line = lines[0]
        headers: dict[str, str] = {}
        for ln in lines[1:]:
            k, _, v = ln.partition(":")
            headers[k.strip().lower()] = v.strip()
        if status_line.endswith("100 Continue"):
            out.append((status_line, headers, b""))
            continue
        n = int(headers.get("content-length", "0"))
        out.append((status_line, headers, rest[:n]))
        rest = rest[n:]
    return out


def normalize(parsed, drop=("date",)):
    return [
        (status, {k: v for k, v in hdrs.items() if k not in drop}, body)
        for status, hdrs, body in parsed
    ]


def assert_identical(pair, payload: bytes, n_responses: int | None = None):
    py, nat = pair
    a = normalize(parse_responses(send_raw(py.server.api_port, payload)))
    b = normalize(parse_responses(send_raw(nat.server.api_port, payload)))
    assert a == b, (
        f"frontends diverged for {payload[:120]!r}...\n"
        f"python: {a}\nnative: {b}"
    )
    if n_responses is not None:
        assert len(a) == n_responses
    return a


def post_bytes(
    path: str, body: bytes, close: bool = True, extra: str = ""
) -> bytes:
    head = (
        f"POST {path} HTTP/1.1\r\nHost: t\r\n"
        f"Content-Type: application/json\r\n"
        f"Content-Length: {len(body)}\r\n{extra}"
    )
    if close:
        head += "Connection: close\r\n"
    return head.encode() + b"\r\n" + body


def review(obj=None, **request_overrides) -> bytes:
    doc = pod_review_body(False)
    if obj is not None:
        doc["request"]["object"] = obj
    doc["request"].update(request_overrides)
    return json.dumps(doc).encode()


# -- the differential corpus -------------------------------------------------


def test_valid_verdicts_bit_exact(pair):
    for privileged in (True, False):
        body = json.dumps(pod_review_body(privileged)).encode()
        (status, _h, resp) = assert_identical(
            pair, post_bytes("/validate/pod-privileged", body), 1
        )[0]
        assert status == "HTTP/1.1 200 OK"
        assert json.loads(resp)["response"]["allowed"] is (not privileged)


def test_keep_alive_and_pipelining(pair):
    one = post_bytes(
        "/validate/pod-privileged",
        json.dumps(pod_review_body(False)).encode(),
        close=False,
    )
    two = post_bytes(
        "/validate/pod-privileged",
        json.dumps(pod_review_body(True)).encode(),
    )
    resps = assert_identical(pair, one + two, 2)
    assert all(s == "HTTP/1.1 200 OK" for s, _h, _b in resps)
    # keep-alive first response carries no Connection header; the closer does
    assert "connection" not in resps[0][1]
    assert resps[1][1].get("connection") == "close"


def test_malformed_and_undeserializable_bodies(pair):
    cases = [
        b"not json at all",
        b"{",
        b'{"request": "not an object"}',
        b'{"nope": 1}',                      # missing request
        b'{"request": {"operation": "CREATE"}}',  # missing uid
        b'{"request": {"uid": ""}}',        # empty uid
        b'{"request": {"uid": 42}}',        # non-string uid
        b'{"request": {"uid": "u", "kind": "Pod"}}',  # non-object kind
        json.dumps({"request": {"uid": "u"}, "extra": [1, {"a": None}]}).encode(),
    ]
    for body in cases:
        (status, _h, resp) = assert_identical(
            pair, post_bytes("/validate/pod-privileged", body), 1
        )[0]
        if body == cases[-1]:
            assert status == "HTTP/1.1 200 OK"
        else:
            assert status == "HTTP/1.1 422 Unprocessable Entity", resp


def test_routing_404_405(pair):
    a = assert_identical(
        pair, post_bytes("/no/such/route", b"{}"), 1
    )
    assert a[0][0] == "HTTP/1.1 404 Not Found"
    a = assert_identical(
        pair,
        b"GET /validate/pod-privileged HTTP/1.1\r\nHost: t\r\n"
        b"Connection: close\r\n\r\n",
        1,
    )
    assert a[0][0] == "HTTP/1.1 405 Method Not Allowed"
    assert a[0][1]["allow"] == "POST"
    a = assert_identical(
        pair,
        post_bytes("/validate/nope", json.dumps(pod_review_body(False)).encode()),
        1,
    )
    assert a[0][0] == "HTTP/1.1 404 Not Found"  # PolicyNotFound, JSON body
    assert json.loads(a[0][2])["status"] == 404


def test_oversized_bodies(pair):
    """413 parity, modulo the trailing byte count: aiohttp reports the
    bytes it had read when the cap tripped — a transport-chunking
    artifact that varies run to run — while the native frontend reports
    the full (deterministic) body size. Status line, headers, and the
    message prefix must match; the native number must be exact."""
    import re

    def mask(resps):
        return [
            (s, h, re.sub(rb"actual body size \d+", b"actual body size N", b))
            for s, h, b in resps
        ]

    py, nat = pair
    cases = []
    big = review(obj={"filler": "x" * (9 * 1024 * 1024)})
    cases.append((post_bytes("/validate/pod-privileged", big), len(big)))
    payload = b"y" * (9 * 1024 * 1024)
    chunked = (
        b"POST /validate/pod-privileged HTTP/1.1\r\nHost: t\r\n"
        b"Transfer-Encoding: chunked\r\nConnection: close\r\n\r\n"
        + hex(len(payload))[2:].encode() + b"\r\n" + payload + b"\r\n0\r\n\r\n"
    )
    cases.append((chunked, len(payload)))
    for wire, total in cases:
        a = normalize(parse_responses(send_raw(py.server.api_port, wire)))
        b = normalize(parse_responses(send_raw(nat.server.api_port, wire)))
        # content-length differs only through the masked digits
        for resps in (a, b):
            for _s, h, _b in resps:
                h.pop("content-length", None)
        assert mask(a) == mask(b), f"python: {a}\nnative: {b}"
        assert a[0][0] == "HTTP/1.1 413 Request Entity Too Large"
        assert b[0][2] == (
            f"Maximum request body size 8388608 exceeded, actual body "
            f"size {total}"
        ).encode()


def test_chunked_valid_body(pair):
    body = json.dumps(pod_review_body(True)).encode()
    mid = len(body) // 2
    chunked = (
        b"POST /validate/pod-privileged HTTP/1.1\r\nHost: t\r\n"
        b"Transfer-Encoding: chunked\r\nConnection: close\r\n\r\n"
        + hex(mid)[2:].encode() + b"\r\n" + body[:mid] + b"\r\n"
        + hex(len(body) - mid)[2:].encode() + b"\r\n" + body[mid:]
        + b"\r\n0\r\n\r\n"
    )
    a = assert_identical(pair, chunked, 1)
    assert a[0][0] == "HTTP/1.1 200 OK"
    assert json.loads(a[0][2])["response"]["allowed"] is False


def test_expect_100_continue(pair):
    body = json.dumps(pod_review_body(False)).encode()
    a = assert_identical(
        pair,
        post_bytes(
            "/validate/pod-privileged", body,
            extra="Expect: 100-continue\r\n",
        ),
        2,
    )
    assert a[0][0].endswith("100 Continue")
    assert a[1][0] == "HTTP/1.1 200 OK"


def test_canonicalization_parity_unicode_and_shapes(pair):
    """Payload shapes that stress the native canonicalizer: non-ASCII
    (ensure_ascii escaping), astral plane, null-dropping, requestKind
    normalization, unknown request keys, empty userInfo."""
    doc = {
        "apiVersion": "admission.k8s.io/v1",
        "kind": "AdmissionReview",
        "request": {
            "unknownKey": {"deep": [1, 2, {"x": "y"}]},
            "uid": "uid-üñí-😀",
            "operation": "CREATE",
            "name": None,
            "namespace": "späce",
            "requestKind": {"version": "v1", "kind": "Pod", "junk": 1},
            "userInfo": {},
            "dryRun": False,
            "object": {
                "metadata": {
                    "labels": {"app": "ünïcode- -😀", "tab": "a\tb"},
                    "annotations": {"empty": "", "ctl": "\x01\x7f"},
                },
                "spec": {
                    "containers": [
                        {"name": "c", "securityContext": {"privileged": True}}
                    ]
                },
            },
        },
    }
    a = assert_identical(
        pair,
        post_bytes("/validate/pod-privileged", json.dumps(doc).encode()),
        1,
    )
    assert a[0][0] == "HTTP/1.1 200 OK"
    assert json.loads(a[0][2])["response"]["allowed"] is False
    assert json.loads(a[0][2])["response"]["uid"] == "uid-üñí-😀"


def test_python_fallback_shapes_still_bit_exact(pair):
    """Constructs the native parser deliberately declines (floats,
    duplicate keys, deep nesting, NaN) must round-trip through the
    Python parse oracle with identical answers."""
    float_doc = review(obj={"spec": {"weight": 0.25, "big": 1e30}})
    dup = (
        b'{"request": {"uid": "u1", "object": {"a": 1, "a": 2}, '
        b'"operation": "CREATE"}}'
    )
    deep_obj: dict = {"leaf": 1}
    for _ in range(120):
        deep_obj = {"n": deep_obj}
    deep = review(obj=deep_obj)
    nan = b'{"request": {"uid": "u2", "object": {"v": NaN}}}'
    for body in (float_doc, dup, deep, nan):
        a = assert_identical(
            pair, post_bytes("/validate/pod-privileged", body), 1
        )
        assert a[0][0] == "HTTP/1.1 200 OK", a[0][2]


def test_canonical_expansion_overflow_falls_back(pair):
    """ensure_ascii escaping can expand multibyte UTF-8 ~3x: a body that
    fits the 8 MiB cap but whose CANONICAL form would not must ship the
    raw body to the Python oracle (bounded record) instead of producing
    an oversized record that could wedge the submission ring."""
    emoji_mb = "😀" * (1024 * 1024)  # 4 MiB of raw UTF-8 → ~12 MiB escaped
    doc = json.loads(review())
    doc["request"]["object"] = {"notes": emoji_mb}
    # ensure_ascii=False: the WIRE carries compact UTF-8; only the
    # canonicalizer's ensure_ascii output would blow past the cap
    body = json.dumps(doc, ensure_ascii=False).encode()
    assert len(body) < 8 * 1024**2
    _py, nat = pair
    fallbacks_before = nat.server._native_frontend.stats()["parse_fallbacks"]
    a = assert_identical(
        pair, post_bytes("/validate/pod-privileged", body), 1
    )
    assert a[0][0] == "HTTP/1.1 200 OK"
    assert (
        nat.server._native_frontend.stats()["parse_fallbacks"]
        > fallbacks_before
    )


def test_validate_raw_and_audit_parity(pair):
    raw_bad = b"steak"
    a = assert_identical(
        pair, post_bytes("/validate_raw/raw-mutation", raw_bad), 1
    )
    assert a[0][0] == "HTTP/1.1 422 Unprocessable Entity"

    raw_ok = json.dumps({"request": {"uid": "raw-1", "user": "x"}}).encode()
    a = assert_identical(
        pair, post_bytes("/validate_raw/raw-mutation", raw_ok), 1
    )
    assert a[0][0] == "HTTP/1.1 200 OK"
    assert "response" in json.loads(a[0][2])

    audit_body = json.dumps(pod_review_body(True)).encode()
    a = assert_identical(
        pair, post_bytes("/audit/pod-privileged", audit_body), 1
    )
    assert a[0][0] == "HTTP/1.1 200 OK"
    assert json.loads(a[0][2])["response"]["allowed"] is False


def test_mid_body_disconnect_leaves_server_serving(pair):
    """A client dying mid-body gets no response from either frontend, and
    neither server may be degraded by it."""
    py, nat = pair
    for handle in (py, nat):
        s = socket.create_connection(("127.0.0.1", handle.server.api_port))
        s.sendall(
            b"POST /validate/pod-privileged HTTP/1.1\r\nHost: t\r\n"
            b"Content-Length: 5000\r\n\r\npartial-body-then-gone"
        )
        s.close()
    time.sleep(0.2)
    body = json.dumps(pod_review_body(False)).encode()
    a = assert_identical(
        pair, post_bytes("/validate/pod-privileged", body), 1
    )
    assert a[0][0] == "HTTP/1.1 200 OK"


def test_malformed_request_line_status_parity(pair):
    """Framing garbage: both answer 400 (bodies differ — aiohttp embeds
    the offending bytes — so this case compares status codes only)."""
    py, nat = pair
    for handle in (py, nat):
        out = send_raw(handle.server.api_port, b"BLARGH\r\n\r\n")
        assert b" 400 " in out.split(b"\r\n", 1)[0], out[:100]


def test_smuggling_vectors_rejected_with_400(pair):
    """Duplicate Content-Length and Content-Length+chunked are request-
    smuggling vectors: both frontends must refuse to frame them (status
    parity; aiohttp's llhttp rejects with 400)."""
    py, nat = pair
    vectors = [
        b"POST /validate/pod-privileged HTTP/1.1\r\nHost: t\r\n"
        b"Content-Length: 2\r\nContent-Length: 5\r\n"
        b"Connection: close\r\n\r\n{}",
        b"POST /validate/pod-privileged HTTP/1.1\r\nHost: t\r\n"
        b"Content-Length: 7\r\nTransfer-Encoding: chunked\r\n"
        b"Connection: close\r\n\r\n2\r\n{}\r\n0\r\n\r\n",
    ]
    for wire in vectors:
        for handle in (py, nat):
            out = send_raw(handle.server.api_port, wire)
            assert b" 400 " in out.split(b"\r\n", 1)[0], (wire[:60], out[:120])


def test_shed_429_carries_retry_after_natively():
    """ShedError at admission must answer HTTP 429 + Retry-After from the
    native completion path (header parity with api/handlers)."""
    import concurrent.futures

    from policy_server_tpu.telemetry import metrics as metrics_mod

    metrics_mod.reset_metrics_for_tests()
    from policy_server_tpu.evaluation.environment import bucket_size

    handle = ServerHandle(
        make_config(
            frontend="native",
            request_timeout_ms=100.0,
            max_batch_size=2,
            batch_timeout_ms=5.0,
            policy_timeout_seconds=30.0,
        )
    )
    try:
        # teach the estimator a pathologically slow device (the unit-test
        # pattern from test_resilience): any nonzero queue depth now
        # exceeds the 100 ms budget, so concurrent arrivals shed
        handle.server.batcher._dev_rtt[bucket_size(2)] = 50.0
        url = handle.url("/validate/pod-privileged")
        body = pod_review_body(False)

        def one():
            try:
                r = requests.post(
                    url, json=body,
                    headers={"Connection": "close"}, timeout=60,
                )
                return r.status_code, r.headers.get("Retry-After")
            except requests.RequestException:
                return None, None

        with concurrent.futures.ThreadPoolExecutor(64) as pool:
            results = list(pool.map(lambda _i: one(), range(128)))
        sheds = [ra for code, ra in results if code == 429]
        assert sheds, f"no shed 429s at this load: {results[:10]}"
        assert all(ra is not None and int(ra) >= 1 for ra in sheds)
    finally:
        handle.stop()


# -- graceful degradation ----------------------------------------------------


def test_unavailable_extension_is_a_boot_error(monkeypatch):
    """--frontend native asks for the extension: when it cannot be built
    or loaded the server refuses to start and says why — it does not
    quietly frame in Python and answer the same 200s."""
    from policy_server_tpu.runtime import native_frontend as mod
    from policy_server_tpu.telemetry import metrics as metrics_mod

    metrics_mod.reset_metrics_for_tests()
    monkeypatch.setattr(mod, "_lib", None)
    monkeypatch.setattr(mod, "_lib_error", "g++: injected build failure")
    server = PolicyServer.new_from_config(make_config(frontend="native"))

    async def boot():
        try:
            await server.start()
        finally:
            await server.stop()

    with pytest.raises(RuntimeError, match="injected build failure"):
        asyncio.run(boot())


def test_prefork_workers_own_native_loops():
    """--http-workers with --frontend native: each prefork worker becomes
    a thin owner of its own native event loop (SO_REUSEPORT), forwarding
    parsed frames over the evaluation bridge — verdicts must be
    indistinguishable across whichever process accepts the socket."""
    from policy_server_tpu.telemetry import metrics as metrics_mod

    metrics_mod.reset_metrics_for_tests()
    handle = ServerHandle(make_config(http_workers=3, frontend="native"))
    try:
        deadline = time.time() + 30
        while time.time() < deadline and len(handle.server._worker_procs) < 2:
            time.sleep(0.1)
        time.sleep(1.5)  # workers binding their native listeners
        assert handle.server._native_frontend is not None  # main process
        url = handle.url("/validate/pod-privileged")
        for i in range(12):  # fresh connections → kernel spreads processes
            r = requests.post(
                url, json=pod_review_body(i % 2 == 0),
                headers={"Connection": "close"}, timeout=60,
            )
            assert r.status_code == 200
            assert r.json()["response"]["allowed"] is (i % 2 != 0)
        # parse errors stay bit-exact through worker loops too
        r = requests.post(
            url, data=b"junk",
            headers={"Content-Type": "application/json",
                     "Connection": "close"},
            timeout=60,
        )
        assert r.status_code == 422
    finally:
        handle.stop()


def test_native_counters_reach_metrics_endpoint(pair):
    """The framing counters must be visible on /metrics with their
    declared (graftcheck-checked) family names."""
    _py, nat = pair
    requests.post(
        nat.url("/validate/pod-privileged"),
        json=pod_review_body(False),
        timeout=60,
    )
    text = requests.get(nat.readiness_url("/metrics"), timeout=30).text
    assert "policy_server_native_http_requests_total" in text
    assert "policy_server_native_framing_seconds_total" in text
    assert "policy_server_queue_wait_seconds_total" in text
    stats = nat.server._native_frontend.stats()
    assert stats["http_requests"] > 0
    assert stats["requests_parsed_native"] > 0


# -- round 13: drainer backpressure + connection-abuse hardening -------------


class _GatedSink:
    """Burst sink that blocks until released, then answers 200s — the
    deterministic way to wedge the drainer so the SPSC ring fills."""

    def __init__(self):
        import threading

        self.gate = threading.Event()

    def handle_burst(self, frontend, burst):
        self.gate.wait(timeout=30)
        for rec in burst:
            frontend.complete(rec[0], 200, b'{"ok": true}')


def _mini_frontend(sink, **kw):
    sock = nf.make_listen_socket("127.0.0.1", 0)
    port = sock.getsockname()[1]
    front = nf.NativeFrontend(sock, sink, **kw).start()
    return front, port


def test_ring_full_answers_inband_503_not_stall():
    """With the drainer wedged, a flood past the submission ring's
    capacity must answer in-band 503s (counted) from the epoll loop —
    never stall it — and the wedge's release must complete every
    admitted request."""
    sink = _GatedSink()
    front, port = _mini_frontend(sink, ring_bits=8)  # 256-slot ring
    try:
        s = socket.create_connection(("127.0.0.1", port))
        one = post_bytes("/validate/p", b"{}", close=False)
        s.sendall(one)  # latches the drainer into the blocked sink
        time.sleep(0.3)
        flood = b"".join(
            post_bytes("/validate/p", b"{}", close=False)
            for _ in range(600)
        )
        s.sendall(flood)
        deadline = time.time() + 10
        while (
            time.time() < deadline
            and front.stats()["ring_full_rejections"] == 0
        ):
            time.sleep(0.05)
        assert front.stats()["ring_full_rejections"] > 0, (
            "flood never overran the 256-slot ring"
        )
        sink.gate.set()
        # every request answers: 200 (drained) or 503 (ring-full)
        s.settimeout(20)
        stream = b""
        try:
            while stream.count(b"HTTP/1.1 ") < 601:
                chunk = s.recv(1 << 20)
                if not chunk:
                    break
                stream += chunk
        except socket.timeout:
            pass
        resps = parse_responses(stream)
        assert len(resps) == 601, len(resps)
        codes = [st.split(" ")[1] for st, _h, _b in resps]
        # compare against the counter AFTER every response is in: a
        # snapshot taken while the flood is still hitting the full ring
        # undercounts the rejections that land between snapshot and
        # gate-release (observed 212 counted vs 344 final in CI)
        rejected = front.stats()["ring_full_rejections"]
        assert codes.count("503") == rejected
        assert codes.count("200") == 601 - rejected
        s.close()
    finally:
        sink.gate.set()
        front.shutdown(timeout=5)


class _EchoSink:
    def handle_burst(self, frontend, burst):
        for rec in burst:
            frontend.complete(rec[0], 200, b'{"ok": true}')


def test_read_timeout_reaps_slowloris_and_idle_conns():
    """A request dripping forever (slowloris) must be reaped by the
    read timeout; a silent keep-alive conn by the idle timeout — both
    counted, with served conns untouched in between."""
    front, port = _mini_frontend(
        _EchoSink(), read_timeout_ms=1000, idle_timeout_ms=2500
    )
    try:
        # slowloris: header never completes
        slow = socket.create_connection(("127.0.0.1", port))
        slow.sendall(b"POST /validate/p HTTP/1.1\r\n")
        # idle: one served request, then silence
        idle = socket.create_connection(("127.0.0.1", port))
        idle.sendall(post_bytes("/validate/p", b"{}", close=False))
        idle.settimeout(10)
        assert b" 200 " in idle.recv(65536)

        def reaped(sock_, drip):
            deadline = time.time() + 8
            while time.time() < deadline:
                try:
                    if drip:
                        sock_.sendall(b"X")
                    sock_.settimeout(0.3)
                    try:
                        if sock_.recv(4096) == b"":
                            return True
                    except socket.timeout:
                        pass
                except OSError:
                    return True
                time.sleep(0.2)
            return False

        assert reaped(slow, drip=True), "slowloris conn never reaped"
        assert reaped(idle, drip=False), "idle conn never reaped"
        assert front.stats()["idle_timeout_closes"] >= 2
        # the port still serves
        ok = socket.create_connection(("127.0.0.1", port))
        ok.sendall(post_bytes("/validate/p", b"{}"))
        ok.settimeout(10)
        assert b" 200 " in ok.recv(65536)
        ok.close()
    finally:
        front.shutdown(timeout=5)


def test_continuous_pipelining_outlives_read_timeout():
    """The read-timeout clock is per REQUEST arrival, not per buffer
    drain: a healthy client pipelining back-to-back requests for longer
    than the read timeout (its buffer often holding a partial tail)
    must never be reaped mid-stream — each completed request resets the
    clock (regression: the clock used to clear only when the input
    buffer drained to a clean boundary)."""
    front, port = _mini_frontend(
        _EchoSink(), read_timeout_ms=700, idle_timeout_ms=60_000
    )
    try:
        s = socket.create_connection(("127.0.0.1", port))
        s.settimeout(10)
        one = post_bytes("/validate/p", b"{}", close=False)
        head, tail = one[: len(one) // 2], one[len(one) // 2:]
        # every burst ENDS with a partial request, so the server's input
        # buffer never drains to a clean boundary for the whole run —
        # the old clock (cleared only on a drained buffer) starts once
        # and reaps this healthy conn at 700 ms
        s.sendall(one + head)
        sent = 1
        stream = b""
        deadline = time.time() + 2.5  # ~3.5x the read timeout
        while time.time() < deadline:
            while stream.count(b"HTTP/1.1 ") < sent:
                chunk = s.recv(1 << 16)
                assert chunk, (
                    "server closed a continuously pipelining conn "
                    f"after {stream.count(b'HTTP/1.1 ')} of {sent} "
                    "responses"
                )
                stream += chunk
            s.sendall(tail + one + head)  # completes 2, leaves 1 partial
            sent += 2
            time.sleep(0.05)
        s.sendall(tail)  # finish the last partial
        while stream.count(b"HTTP/1.1 ") < sent:
            chunk = s.recv(1 << 16)
            assert chunk, "server closed the conn on the final drain"
            stream += chunk
        resps = parse_responses(stream)
        assert len(resps) == sent and sent >= 20
        assert all(" 200 " in st for st, _h, _b in resps)
        assert front.stats()["idle_timeout_closes"] == 0
        s.close()
    finally:
        front.shutdown(timeout=5)


def test_connection_cap_rejects_inband_503():
    """Accepts over --native-max-connections answer an in-band 503 +
    Retry-After and close (counted) instead of silently dropping."""
    front, port = _mini_frontend(_EchoSink(), max_connections=2)
    try:
        held = [
            socket.create_connection(("127.0.0.1", port))
            for _ in range(2)
        ]
        time.sleep(0.3)  # both registered by the event loop
        over = socket.create_connection(("127.0.0.1", port))
        over.settimeout(10)
        data = b""
        while True:
            try:
                chunk = over.recv(65536)
            except socket.timeout:
                break
            if not chunk:
                break
            data += chunk
        assert b" 503 " in data.split(b"\r\n", 1)[0], data[:120]
        assert b"connection limit reached" in data
        assert b"retry-after" in data.lower()
        assert front.stats()["conn_cap_rejections"] == 1
        over.close()
        # capacity frees as held conns close
        held[0].close()
        time.sleep(1.2)
        again = socket.create_connection(("127.0.0.1", port))
        again.sendall(post_bytes("/validate/p", b"{}"))
        again.settimeout(10)
        assert b" 200 " in again.recv(65536)
        again.close()
        held[1].close()
    finally:
        front.shutdown(timeout=5)


def test_record_timestamps_and_traceparent_cross_the_ring():
    """Round 18: every record carries CLOCK_MONOTONIC stamps (received,
    canonicalized+pushed) plus the verbatim traceparent header; the
    drainer records the native accept/parse/ring-cross phase aggregates
    on the flight recorder."""
    import threading as _threading
    import time as _time

    from policy_server_tpu.telemetry import flightrec

    class _CaptureSink:
        def __init__(self):
            self.bursts = []
            self.got = _threading.Event()

        def handle_burst(self, frontend, burst):
            self.bursts.append(list(burst))
            for rec in burst:
                frontend.complete(rec[0], 200, b'{"ok": true}')
            self.got.set()

    rec = flightrec.install(flightrec.FlightRecorder(capacity=1024))
    sink = _CaptureSink()
    front, port = _mini_frontend(sink)
    try:
        tp = "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01"
        body = review()
        t_before = _time.perf_counter_ns()
        req = (
            b"POST /validate/priv HTTP/1.1\r\nHost: x\r\n"
            + f"traceparent: {tp}\r\n".encode()
            + f"Content-Length: {len(body)}\r\n\r\n".encode()
            + body
        )
        send_raw(port, req)
        assert sink.got.wait(timeout=15)
        t_after = _time.perf_counter_ns()
    finally:
        front.shutdown()
        flightrec.install(None)
    (burst,) = sink.bursts
    (record,) = burst
    # tuple: (req_id, kind, policy, uid, ns, op, gvk, payload, tp,
    #         t_first, t_parse, t_push)
    assert record[8] == tp
    _tf, t_parse, t_push = record[9], record[10], record[11]
    assert t_before < t_parse <= t_push < t_after
    phases = {e["phase"] for e in rec.snapshot()}
    assert {
        flightrec.PH_NATIVE_ACCEPT,
        flightrec.PH_NATIVE_PARSE,
        flightrec.PH_RING_CROSS,
    } <= phases
    for e in rec.snapshot():
        assert e["end_ns"] >= e["start_ns"]


def test_obs_text_traceparent_never_kills_the_drainer():
    """Post-review regression: HTTP/1.1 field values legally carry
    obs-text bytes 0x80-0xFF; a traceparent full of them must be
    dropped at the C++ header gate (and the Python decode is
    errors='replace' as defense in depth) — never a strict-decode
    raise that kills the drain thread and strands the burst."""
    import threading as _threading

    class _CaptureSink:
        def __init__(self):
            self.records = []
            self.got = _threading.Event()

        def handle_burst(self, frontend, burst):
            self.records.extend(burst)
            for rec in burst:
                frontend.complete(rec[0], 200, b'{"ok": true}')
            if len(self.records) >= 2:
                self.got.set()

    sink = _CaptureSink()
    front, port = _mini_frontend(sink)
    try:
        body = review()
        bad = (
            b"POST /validate/priv HTTP/1.1\r\nHost: x\r\n"
            b"traceparent: \xff\xfe\x80garbage\r\n"
            + f"Content-Length: {len(body)}\r\n\r\n".encode()
            + body
        )
        resp = send_raw(port, bad)
        assert b"200" in resp.split(b"\r\n", 1)[0]
        # the drainer survived: a SECOND request still drains and answers
        ok = (
            b"POST /validate/priv HTTP/1.1\r\nHost: x\r\n"
            + f"Content-Length: {len(body)}\r\n\r\n".encode()
            + body
        )
        resp = send_raw(port, ok)
        assert b"200" in resp.split(b"\r\n", 1)[0]
        assert sink.got.wait(timeout=15)
    finally:
        front.shutdown()
    # the obs-text header never crossed the ring
    assert sink.records[0][8] == ""
