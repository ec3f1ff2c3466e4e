"""The soak harness: full serving stack + trace replay + fault storm +
live watch feed + SLO artifact.

Runs the REAL server in-process (the same bootstrap `python -m
policy_server_tpu` uses — native frontend by default over real sockets,
prefork optional) inside a private event-loop thread, then drives it
with:

* paced client threads replaying the seeded scenario trace over
  keep-alive, pipelined raw sockets (statuses + latencies recorded per
  expectation class);
* an abuse driver executing the trace's connection-abuse waves
  (slowloris drips against the native read timeout, pipelined malformed
  floods, mid-body disconnects — and, under ``--tls``, the
  handshake-abuse waves: ClientHello drips into the handshake deadline,
  mid-handshake disconnect floods, wrong-CA bursts);
* a churn thread mutating the :class:`SyntheticCluster` that feeds the
  audit snapshot store through the live :class:`WatchFeed`;
* the :class:`FaultStorm` applying seeded mid-soak faults (SIGHUP
  reload, poisoned reload, breaker trip, audit/watch/frontend
  failpoints, stream closes, worker kills).

When the engine owns the main thread (``python -m tools.soak``) the
SIGHUP is a REAL signal through a registered handler. The run ends with
the SLO gate and a ``BENCH_soak_<tag>.json`` artifact; exit code 1 on a
gate failure (``make soak-smoke`` is CI-gating).
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import signal
import socket
import ssl as ssl_mod
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

from tools.soak import scenarios
from tools.soak.cluster import SyntheticCluster
from tools.soak.faults import FaultStorm
from tools.soak.slo import SLORecorder, write_artifact

_REPO_ROOT = Path(__file__).resolve().parent.parent.parent

_POLICIES_YAML = """\
pod-privileged:
  module: builtin://pod-privileged
pod-privileged-monitor:
  module: builtin://pod-privileged
  policyMode: monitor
raw-mutation:
  module: builtin://raw-mutation
  allowedToMutate: true
soak-group:
  expression: happy() && priv()
  message: group rejected the request
  policies:
    happy:
      module: builtin://always-happy
    priv:
      module: builtin://pod-privileged
"""


@dataclass
class SoakSettings:
    seed: int = 42
    duration: float = 45.0
    clients: int = 4
    pipeline: int = 4
    target_rps: float = 300.0
    n_trace_items: int = 4000
    objects: int = 20_000
    churn_ops_per_second: float = 400.0
    window_seconds: float = 5.0
    p99_budget_ms: float = 750.0
    frontend: str = "native"
    http_workers: int = 1
    read_timeout_seconds: float = 5.0  # native slowloris bound
    audit_interval_seconds: float = 5.0
    artifact: str | None = None
    tag: str = "r13"
    preset: str = "custom"
    # policy-churn storm (round 15): scheduled policies.yml rewrites
    # under load — the reload digest watch detects each one and the
    # predicate optimizer re-runs for every candidate epoch. 0 disables.
    policy_rewrites: int = 0
    # tenancy mix (round 16, tenancy.py): N tenants on the manifest —
    # ten-0 runs an UNPACED overload storm against a tight admission
    # quota (it must shed 429s, never queue into shared capacity) while
    # ten-1..N-1 are paced victims whose p99 must hold the soak budget;
    # every mid-soak SIGHUP reloads EVERY tenant's epoch independently.
    # 0/1 disables (single-tenant soak, the pre-round-16 shape).
    tenants: int = 0
    tenant_storm_quota_rps: float = 50.0
    tenant_victim_rps: float = 30.0  # total across victim tenants
    # TLS soak (round 20): boot the server with a generated identity
    # and run EVERY client/abuse surface over TLS — the native frontend
    # terminates the handshakes on its own loops, and the trace gains
    # the handshake-abuse waves (tls_slowloris, tls_midhandshake,
    # tls_wrong_ca) plus a windowed tls.handshake failpoint outage in
    # the fault storm. Requires the openssl CLI for cert minting.
    tls: bool = False
    # restart storm (round 17, statestore.py): N mid-soak server
    # restarts — stop, then re-boot the SAME config with the registry
    # failpoint armed; the warm boot must come from the state store
    # (gate `restart_storm_survived`: warm-boot-used + bit-exact
    # pre/post-restart probe verdicts + zero unexplained after ready).
    # The in-process engine cannot SIGKILL itself, so the crash model
    # is what the state store actually guarantees: nothing beyond the
    # crash-consistent periodic spill and the promotion-time manifests
    # is carried across (make restart-drill does the real SIGKILL).
    restarts: int = 0
    # serving shards (round 22, runtime/shards.py): M host-local
    # serving stacks behind the health/EWMA router. > 1 adds the
    # shard_kill storm event (one dispatch loop dies mid-service; the
    # heartbeat must fence, disposition the queue, and warm-revive) and
    # the `shard_kill_survived` gate check. 1 = router bypassed, the
    # pre-round-22 shape.
    serving_shards: int = 1

    @classmethod
    def smoke(cls, **over) -> "SoakSettings":
        """The CI mini-soak (make soak-smoke). The p99 budget is
        above the single-tenant 750 ms calibration because every SIGHUP
        now fans out N+1 CONCURRENT reload pipelines (default + each
        tenant, round 16) whose candidate compiles contend for the
        2-core box's GIL mid-soak — observed whole-soak p99 ≈390-760 ms
        run-to-run with the tenancy mix on. Round 17 stretched the
        smoke window (20→45 s) to fit ONE mid-soak restart event before
        the late reload."""
        base = dict(
            duration=45.0, clients=3, target_rps=220.0,
            n_trace_items=2500, objects=20_000,
            churn_ops_per_second=300.0, window_seconds=2.5,
            preset="smoke", tag="r13_smoke", policy_rewrites=2,
            tenants=2, p99_budget_ms=950.0, restarts=1,
            serving_shards=2,
        )
        base.update(over)
        return cls(**base)

    @classmethod
    def full(cls, **over) -> "SoakSettings":
        """The cluster-scale soak: 100k+ watched objects, prefork
        workers in the kill rotation, a longer storm, a 4-tenant mix,
        a 2-cycle restart storm."""
        base = dict(
            duration=300.0, clients=6, target_rps=700.0,
            n_trace_items=20_000, objects=120_000,
            churn_ops_per_second=800.0, window_seconds=10.0,
            http_workers=2, preset="full", tag="r13_full",
            # 4-tenant mix: every SIGHUP runs 5 concurrent reload
            # pipelines (see smoke's budget note)
            policy_rewrites=5, tenants=4, p99_budget_ms=950.0,
            restarts=2, serving_shards=2,
        )
        base.update(over)
        return cls(**base)


class _ServerThread:
    """PolicyServer inside a private event loop (test_server.ServerHandle
    shape, re-owned here so the soak tool has no tests/ dependency)."""

    def __init__(self, config):
        from policy_server_tpu.server import PolicyServer

        self.server = PolicyServer.new_from_config(config)
        self.loop = asyncio.new_event_loop()
        self._started = threading.Event()
        self._boot_error: BaseException | None = None
        self.thread = threading.Thread(
            target=self._run, name="soak-server", daemon=True
        )
        self.thread.start()
        if not self._started.wait(timeout=180):
            raise RuntimeError("soak server failed to start (timeout)")
        if self._boot_error is not None:
            raise RuntimeError(
                "soak server failed to start"
            ) from self._boot_error

    def _run(self) -> None:
        asyncio.set_event_loop(self.loop)
        try:
            self.loop.run_until_complete(self.server.start())
        except BaseException as e:  # noqa: BLE001 — a boot failure must
            # surface as the constructor's exception, not a daemon-thread
            # stderr line followed by a causeless 3-minute timeout
            self._boot_error = e
            self._started.set()
            return
        self._started.set()
        self.loop.run_forever()

    def stop(self) -> None:
        async def _shutdown():
            await self.server.stop()
            self.loop.stop()

        asyncio.run_coroutine_threadsafe(_shutdown(), self.loop)
        self.thread.join(timeout=30)


@dataclass
class SoakEngine:
    settings: SoakSettings
    log: list[str] = field(default_factory=list)

    # the TLS soak tightens the native handshake deadline (default 10 s)
    # so the tls_slowloris wave proves the reap inside the soak window
    _TLS_HANDSHAKE_TIMEOUT = 5.0
    # class-level defaults: run() overwrites these when --tls mints an
    # identity, but engine surfaces (_conn, _await_routing_ready) must
    # work on a partially-built engine too (the handover regression test
    # drives them without run())
    _client_ssl = None
    _tls_config = None
    tls_native = False

    @staticmethod
    def _phase_attribution() -> dict | None:
        """The flight recorder's wall-vs-summed-phases reconciliation
        over whatever its ring currently holds (the soak's own recent
        traffic) — recorded into the artifact at gate time so the
        unattributed-residual number trends round-over-round. None when
        the recorder is disabled."""
        from policy_server_tpu.telemetry import flightrec

        rec = flightrec.recorder()
        if rec is None:
            return None
        try:
            return rec.attribution()
        except Exception:  # noqa: BLE001 — accounting must not fail soaks
            return None

    def _say(self, msg: str) -> None:
        line = f"[soak +{time.monotonic() - self._t0:6.1f}s] {msg}"
        self.log.append(line)
        print(line, flush=True)

    # -- bring-up ----------------------------------------------------------

    def _build_config(self, policies_path: Path, tenants_path=None,
                      state_dir: Path | None = None):
        from policy_server_tpu.config.config import (
            Config,
            TlsConfig,
            read_policies_file,
        )

        tenants = None
        if tenants_path is not None:
            from policy_server_tpu.tenancy import read_tenants_file

            tenants = read_tenants_file(tenants_path)
        s = self.settings
        return Config(
            # durable state (round 17): the restart storm's warm boots
            # ride the state store + the persistent XLA compile cache
            # (placed by `python -m tools.soak` for its own process);
            # the spill cadence is shortened so a mid-soak restart
            # resumes a fresh audit inventory
            state_dir=str(state_dir) if state_dir is not None else None,
            state_audit_spill_seconds=5.0,
            tenants_path=(
                str(tenants_path) if tenants_path is not None else None
            ),
            tenants=tenants,
            addr="127.0.0.1",
            port=0,
            readiness_probe_port=0,
            # the TLS soak's identity (a restart-storm reboot re-reads
            # the same cert paths, like a real pod remount)
            tls_config=getattr(self, "_tls_config", None) or TlsConfig(),
            policies=read_policies_file(policies_path),
            policies_path=str(policies_path),
            policy_timeout_seconds=5.0,
            max_batch_size=16,
            batch_timeout_ms=2.0,
            request_timeout_ms=2000.0,
            frontend=s.frontend,
            http_workers=s.http_workers,
            serving_shards=s.serving_shards,
            native_tls="auto",
            native_tls_handshake_timeout_seconds=(
                self._TLS_HANDSHAKE_TIMEOUT
            ),
            policy_reload_mode="auto",
            reload_canary_requests=16,
            audit_mode="interval",
            audit_interval_seconds=s.audit_interval_seconds,
            audit_batch_size=256,
            # round 23: the persistent (object × policy) verdict matrix
            # rides every soak — promotions must take the column-diff
            # path and the matrix must converge to store parity (the
            # verdict_matrix_converged gate); the spill cadence matches
            # the snapshot's so a mid-soak restart resumes both
            audit_matrix=True,
            audit_matrix_spill_seconds=5.0,
            native_read_timeout_seconds=s.read_timeout_seconds,
            native_idle_timeout_seconds=75.0,
            native_max_connections=4096,
            enable_pprof=False,
        )

    # -- traffic -----------------------------------------------------------

    def _client_loop(
        self, idx: int, items: list, stop: threading.Event
    ) -> None:
        s = self.settings
        rec = self.recorder
        rng = random.Random(s.seed * 1000 + idx)
        order = list(range(len(items)))
        rng.shuffle(order)
        per_client = max(1.0, s.target_rps / s.clients)
        burst_sleep = s.pipeline / per_client
        pos = 0
        sock_ = None
        while not stop.is_set():
            if self._restart_in_progress:
                # handover hold (round 19): a pipelined burst straddling
                # the reboot is how the r18 flake happened — a burst's
                # conn died mid-read and the positional response
                # attribution desynced (an unknown-policy slot read its
                # neighbor's 200, a midbody probe read an in-flight
                # 500). Probes and traffic HOLD until routing is
                # re-established, and the conn is dropped so nothing
                # spans the handover.
                if sock_ is not None:
                    sock_.close()
                    sock_ = None
                stop.wait(0.1)
                continue
            t_burst = time.perf_counter()
            burst = [
                items[order[(pos + i) % len(order)]]
                for i in range(s.pipeline)
            ]
            pos = (pos + s.pipeline) % len(order)
            try:
                if sock_ is None:
                    sock_ = self._conn()
                payload = b"".join(
                    self._wire(it.path, it.body) for it in burst
                )
                sock_.sendall(payload)
                for it in burst:
                    status, _hdrs, _body = sock_.read_response()
                    rec.record(
                        status,
                        (time.perf_counter() - t_burst) * 1000.0,
                        it.expect,
                        detail=f"{it.scenario} {it.path}",
                    )
            except Exception as e:  # noqa: BLE001 — conn died: the
                # responses we did not read are unobservable; a server
                # that closed on us mid-burst outside an abuse wave
                # shows up via the requests we re-issue, so just
                # reconnect (drops counted by the artifact's totals gap)
                if not stop.is_set():
                    rec.record(599, 0.0, "ok", detail=f"conn: {e}")
                if sock_ is not None:
                    sock_.close()
                sock_ = None
                # brief backoff: a dead port (mid-restart downtime)
                # must not turn reconnects into a busy loop that starves
                # the rebooting server of CPU
                stop.wait(0.05)
                continue
            elapsed = time.perf_counter() - t_burst
            if elapsed < burst_sleep:
                time.sleep(burst_sleep - elapsed)
        if sock_ is not None:
            sock_.close()

    def _conn(self, timeout: float = 30.0) -> "_HttpConn":
        """One client connection — TLS-wrapped when the soak is."""
        return _HttpConn(
            self.api_port, timeout=timeout, ssl_ctx=self._client_ssl
        )

    def _abuse_sock(self, timeout: float) -> socket.socket:
        """A raw connection for post-handshake abuse (slowloris drips,
        malformed floods, mid-body disconnects): under TLS the abuse
        bytes flow through a COMPLETED handshake, so the plaintext abuse
        coverage carries over to the TLS surface unchanged."""
        c = socket.create_connection(
            ("127.0.0.1", self.api_port), timeout=timeout
        )
        if self._client_ssl is not None:
            c = self._client_ssl.wrap_socket(c)
        return c

    @staticmethod
    def _wire(path: str, body: bytes) -> bytes:
        return (
            f"POST {path} HTTP/1.1\r\nHost: soak\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n"
        ).encode() + body

    # -- tenancy mix (round 16) --------------------------------------------

    _TENANT_POLICIES_YAML = (
        "pod-privileged:\n  module: builtin://pod-privileged\n"
    )

    @staticmethod
    def _tenant_review_body() -> bytes:
        return json.dumps({
            "apiVersion": "admission.k8s.io/v1",
            "kind": "AdmissionReview",
            "request": {
                "uid": "soak-tenant",
                "kind": {"group": "", "version": "v1", "kind": "Pod"},
                "resource": {
                    "group": "", "version": "v1", "resource": "pods",
                },
                "name": "t", "namespace": "default",
                "operation": "CREATE",
                "userInfo": {"username": "soak"},
                "object": {
                    "apiVersion": "v1", "kind": "Pod",
                    "metadata": {"name": "t", "namespace": "default"},
                    "spec": {"containers": [
                        {"name": "c", "image": "nginx"},
                    ]},
                },
            },
        }, separators=(",", ":")).encode()

    def _write_tenants(self, tmp: Path) -> tuple[Path, list[str]]:
        """tenants.yml + the shared tiny per-tenant policies file:
        ten-0 is the storm tenant (tight token-bucket quota), the rest
        are victims with a 2x fair-dispatch weight."""
        s = self.settings
        names = [f"ten-{i}" for i in range(s.tenants)]
        (tmp / "tenant-policies.yml").write_text(
            self._TENANT_POLICIES_YAML, encoding="utf-8"
        )
        lines = ["tenants:"]
        for i, name in enumerate(names):
            lines += [f"  {name}:", "    policies: tenant-policies.yml"]
            if i == 0:
                lines += [
                    f"    quota-rows-per-second: {s.tenant_storm_quota_rps:g}",
                    f"    quota-burst: {max(8.0, s.tenant_storm_quota_rps / 2):g}",
                    "    weight: 1.0",
                ]
            else:
                lines += ["    weight: 2.0"]
        path = tmp / "tenants.yml"
        path.write_text("\n".join(lines) + "\n", encoding="utf-8")
        return path, names

    def _tenant_storm_loop(
        self, tenant: str, stop: threading.Event, stats: dict
    ) -> None:
        """UNPACED flood of tenant-0 far past its quota: the admission
        bucket must shed 429s at the front door (legal, counted) — the
        victims' p99 is the isolation judge."""
        body = self._tenant_review_body()
        wire = self._wire(f"/validate/{tenant}/pod-privileged", body)
        conn = None
        while not stop.is_set():
            try:
                if conn is None:
                    conn = self._conn()
                conn.sendall(wire * 8)
                for _ in range(8):
                    status, _h, _b = conn.read_response()
                    from tools.soak import slo as slo_mod

                    cls = self.recorder.classify(status, "ok")
                    with self._tenant_lock:
                        stats["requests"] += 1
                        if cls == slo_mod.SHED:
                            stats["sheds"] += 1
                        elif cls == slo_mod.UNEXPLAINED:
                            stats["errors"] += 1
                    self.recorder.record(
                        status, 0.0, "ok", detail=f"tenant-storm {tenant}"
                    )
            except Exception:  # noqa: BLE001 — reconnect and continue
                if conn is not None:
                    conn.close()
                conn = None
                stop.wait(0.05)
                continue
            stop.wait(0.005)  # ~1.6k req/s ceiling: a storm, not a DoS
        if conn is not None:
            conn.close()

    def _tenant_victim_loop(
        self, tenant: str, rps: float, stop: threading.Event, stats: dict
    ) -> None:
        """Paced victim traffic whose per-request latency is recorded —
        the tenancy gate requires its p99 inside the soak budget while
        the storm tenant floods."""
        body = self._tenant_review_body()
        wire = self._wire(f"/validate/{tenant}/pod-privileged", body)
        period = 1.0 / max(1.0, rps)
        conn = None
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                if conn is None:
                    conn = self._conn()
                conn.sendall(wire)
                status, _h, _b = conn.read_response()
                latency_ms = (time.perf_counter() - t0) * 1000.0
                # the recorder's classifier owns the fault-window logic:
                # a 5xx inside a DECLARED fault window (frontend burst
                # fault, worker kill) is explained — loudly counted, but
                # not an isolation breach
                from tools.soak import slo as slo_mod

                cls = self.recorder.classify(status, "ok")
                with self._tenant_lock:
                    stats["requests"] += 1
                    if cls == slo_mod.OK:
                        stats["latencies_ms"].append(latency_ms)
                    elif cls == slo_mod.SHED:
                        stats["sheds"] += 1
                    elif cls == slo_mod.UNEXPLAINED:
                        stats["errors"] += 1
                self.recorder.record(
                    status, latency_ms, "ok",
                    detail=f"tenant-victim {tenant}",
                )
            except Exception:  # noqa: BLE001 — reconnect and continue
                if conn is not None:
                    conn.close()
                conn = None
                stop.wait(0.05)
                continue
            elapsed = time.perf_counter() - t0
            if elapsed < period:
                stop.wait(period - elapsed)
        if conn is not None:
            conn.close()

    # -- abuse driver ------------------------------------------------------

    def _abuse_loop(
        self, waves: list, stop: threading.Event, t0: float
    ) -> None:
        s = self.settings
        if not waves:
            return
        # spread waves over the middle of the soak
        spacing = s.duration * 0.8 / (len(waves) + 1)
        for i, wave in enumerate(waves):
            due = t0 + s.duration * 0.1 + spacing * (i + 1)
            while not stop.is_set() and time.monotonic() < due:
                stop.wait(0.2)
            if stop.is_set():
                return
            while self._restart_in_progress and not stop.is_set():
                # an abuse wave against a mid-reboot server proves only
                # that a down server is down; wait for the swap
                stop.wait(0.2)
            while (
                time.monotonic() < getattr(self.storm, "tls_outage_until", 0.0)
                and not stop.is_set()
            ):
                # same logic for an injected TLS accept outage: a wave
                # that cannot even handshake measures the fault, not
                # the abuse-hardening it came to test
                stop.wait(0.2)
            if stop.is_set():
                return
            try:
                result = self._run_wave(wave)
            except Exception as e:  # noqa: BLE001 — an abuse wave must
                # never kill the soak; record the failure
                result = {"kind": wave.kind, "passed": False,
                          "error": str(e)}
            result["t"] = round(time.monotonic() - t0, 1)
            self.recorder.record_abuse(result)
            self._say(f"abuse wave {result}")

    def _run_wave(self, wave) -> dict:
        if wave.kind == "slowloris":
            return self._wave_slowloris(wave)
        if wave.kind == "malformed_flood":
            return self._wave_malformed(wave)
        if wave.kind == "tls_slowloris":
            return self._wave_tls_slowloris(wave)
        if wave.kind == "tls_midhandshake":
            return self._wave_tls_midhandshake(wave)
        if wave.kind == "tls_wrong_ca":
            return self._wave_tls_wrong_ca(wave)
        return self._wave_midbody(wave)

    def _wave_slowloris(self, wave) -> dict:
        if not self.native_active:
            return {
                "kind": "slowloris", "passed": None,
                "note": "skipped: python frontend has no read timeout",
            }
        budget = self.settings.read_timeout_seconds + 6.0
        conns = []
        for _ in range(wave.conns):
            c = self._abuse_sock(budget)
            c.sendall(b"POST /validate/pod-privileged HTTP/1.1\r\n")
            conns.append(c)
        deadline = time.monotonic() + budget
        open_conns = list(conns)
        closed = 0
        # drip ALL conns concurrently each interval (sequential drips
        # would serialize N read-timeout waits past the soak window)
        while open_conns and time.monotonic() < deadline:
            time.sleep(max(0.1, wave.param))
            still = []
            for c in open_conns:
                try:
                    c.sendall(b"X")  # one more header byte: never done
                    c.setblocking(False)
                    try:
                        if c.recv(4096) == b"":
                            closed += 1
                            continue
                    except (BlockingIOError, ssl_mod.SSLWantReadError):
                        pass  # SSLWantReadError: the TLS-soak variant
                        # of "no bytes yet" on a nonblocking socket
                    finally:
                        c.setblocking(True)
                    still.append(c)
                except OSError:
                    closed += 1
            open_conns = still
        for c in conns:
            try:
                c.close()
            except OSError:
                pass
        return {
            "kind": "slowloris", "conns": wave.conns, "closed": closed,
            "passed": closed == wave.conns,
        }

    def _wave_malformed(self, wave) -> dict:
        got_400 = 0
        for _ in range(wave.conns):
            c = self._abuse_sock(15)
            try:
                flood = b"".join(
                    b"BLARGH nonsense\r\nGarbage: yes\r\n\r\n"
                    for _ in range(int(wave.param))
                )
                c.sendall(flood)
                c.settimeout(10)
                data = b""
                try:
                    while True:
                        chunk = c.recv(65536)
                        if not chunk:
                            break
                        data += chunk
                except socket.timeout:
                    pass
                if b" 400 " in data.split(b"\r\n", 1)[0]:
                    got_400 += 1
            finally:
                try:
                    c.close()
                except OSError:
                    pass
        return {
            "kind": "malformed_flood", "conns": wave.conns,
            "answered_400": got_400, "passed": got_400 == wave.conns,
        }

    def _wave_midbody(self, wave) -> dict:
        for _ in range(wave.conns):
            c = self._abuse_sock(15)
            c.sendall(
                b"POST /validate/pod-privileged HTTP/1.1\r\nHost: s\r\n"
                b"Content-Length: 50000\r\n\r\npartial-then-gone"
            )
            c.close()
        # the server must still answer cleanly right after — but never
        # mid-handover (a restart beginning during the disconnect loop
        # above must not turn this probe into a coin flip)
        self._await_handover()
        probe = scenarios.build_trace(1, 4).items[0]
        conn = self._conn()
        try:
            conn.sendall(self._wire(probe.path, probe.body))
            status, _h, _b = conn.read_response()
        finally:
            conn.close()
        ok = status in (200, 429, 504)
        return {
            "kind": "midbody_disconnect", "conns": wave.conns,
            "probe_status": status, "passed": ok,
        }

    # -- TLS handshake-abuse waves (round 20) ------------------------------

    def _tls_stat(self, name: str) -> int:
        front = self.server.state.native_frontend
        return front.stats().get(name, 0) if front is not None else 0

    def _wave_tls_slowloris(self, wave) -> dict:
        """Drip a ClientHello one byte at a time: the handshake deadline
        is anchored at accept and drips never refresh it, so every conn
        must be reaped within the (tightened) handshake timeout."""
        if not self.tls_native:
            return {
                "kind": "tls_slowloris", "passed": None,
                "note": "skipped: TLS not natively terminated "
                "(aiohttp has no handshake deadline)",
            }
        budget = self._TLS_HANDSHAKE_TIMEOUT + 6.0
        timeouts_before = self._tls_stat("tls_handshake_timeouts")
        # a plausible ClientHello prefix, never completed
        hello = b"\x16\x03\x01\x00\xc8\x01\x00\x00\xc4\x03\x03" + b"\x00" * 64
        conns = []
        for _ in range(wave.conns):
            c = socket.create_connection(
                ("127.0.0.1", self.api_port), timeout=budget
            )
            conns.append(c)
        deadline = time.monotonic() + budget
        open_conns = list(conns)
        pos = 0
        closed = 0
        while open_conns and time.monotonic() < deadline:
            time.sleep(max(0.1, wave.param))
            still = []
            for c in open_conns:
                try:
                    c.sendall(hello[pos % len(hello):][:1])
                    c.setblocking(False)
                    try:
                        if c.recv(4096) == b"":
                            closed += 1
                            continue
                    except BlockingIOError:
                        pass
                    finally:
                        c.setblocking(True)
                    still.append(c)
                except OSError:
                    closed += 1
            pos += 1
            open_conns = still
        for c in conns:
            try:
                c.close()
            except OSError:
                pass
        reaped = self._tls_stat("tls_handshake_timeouts") - timeouts_before
        return {
            "kind": "tls_slowloris", "conns": wave.conns,
            "closed": closed, "reaped_as_timeout": reaped,
            "passed": closed == wave.conns and reaped >= wave.conns,
        }

    def _wave_tls_midhandshake(self, wave) -> dict:
        """A flood of connections dropped mid-handshake: the loops must
        count and reap every one, and serving must be untouched."""
        before = self._tls_stat("tls_handshake_disconnects")
        for _ in range(wave.conns):
            c = socket.create_connection(
                ("127.0.0.1", self.api_port), timeout=15
            )
            c.sendall(b"\x16\x03\x01\x00\xc8\x01\x00")  # fragment
            c.close()
        self._await_handover()
        probe = scenarios.build_trace(1, 4).items[0]
        conn = self._conn()
        try:
            conn.sendall(self._wire(probe.path, probe.body))
            status, _h, _b = conn.read_response()
        finally:
            conn.close()
        counted = None
        if self.tls_native:
            # the reap is event-driven (EPOLLHUP/read-0) — give the
            # loops a moment to observe the last close
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                counted = (
                    self._tls_stat("tls_handshake_disconnects") - before
                )
                if counted >= wave.conns:
                    break
                time.sleep(0.1)
        ok = status in (200, 429, 504) and (
            counted is None or counted >= wave.conns
        )
        return {
            "kind": "tls_midhandshake", "conns": wave.conns,
            "counted_disconnects": counted, "probe_status": status,
            "passed": ok,
        }

    def _wave_tls_wrong_ca(self, wave) -> dict:
        """Clients that verify the server against the WRONG trust root:
        each aborts its handshake with an alert the server must absorb
        as a counted failure — and keep serving everyone else."""
        from tools import tlsgen

        import tempfile

        before = self._tls_stat("tls_handshakes_failed")
        with tempfile.TemporaryDirectory() as td:
            ca, _cakey = tlsgen.make_ca(td, cn="wrong-ca")
            ctx = ssl_mod.create_default_context(cafile=str(ca))
            ctx.check_hostname = False
            rejected = 0
            for _ in range(wave.conns):
                try:
                    c = ctx.wrap_socket(
                        socket.create_connection(
                            ("127.0.0.1", self.api_port), timeout=15
                        )
                    )
                    c.close()
                except (ssl_mod.SSLError, OSError):
                    rejected += 1
        probe = scenarios.build_trace(1, 4).items[0]
        conn = self._conn()
        try:
            conn.sendall(self._wire(probe.path, probe.body))
            status, _h, _b = conn.read_response()
        finally:
            conn.close()
        failed = None
        if self.tls_native:
            failed = self._tls_stat("tls_handshakes_failed") - before
        ok = (
            rejected == wave.conns
            and status in (200, 429, 504)
            and (failed is None or failed >= wave.conns)
        )
        return {
            "kind": "tls_wrong_ca", "conns": wave.conns,
            "rejected": rejected, "counted_failures": failed,
            "probe_status": status, "passed": ok,
        }

    # -- churn -------------------------------------------------------------

    def _churn_loop(self, stop: threading.Event) -> None:
        s = self.settings
        tick = 0.25
        per_tick = max(1, int(s.churn_ops_per_second * tick))
        while not stop.wait(tick):
            self.cluster.churn(per_tick)

    def _policy_churn_loop(
        self,
        rewrites: list,
        policies_path: Path,
        stop: threading.Event,
        t0: float,
    ) -> None:
        """Write each scheduled policies.yml rewrite at its offset; the
        lifecycle digest watcher (1 s poll) picks it up and kicks a
        background reload while the trace keeps flowing."""
        for rw in rewrites:
            while not stop.is_set():
                delay = t0 + rw.at - time.monotonic()
                if delay <= 0:
                    break
                stop.wait(min(delay, 0.2))
            if stop.is_set():
                return
            # atomic replace: the lifecycle's digest poll must never
            # read a truncated half-written file (a garbage candidate
            # would reject and the rewrite's reload silently vanish)
            tmp_path = policies_path.with_suffix(".yml.tmp")
            tmp_path.write_text(rw.yaml_text, encoding="utf-8")
            os.replace(tmp_path, policies_path)
            self._policy_rewrites_applied.append(
                {"at": round(time.monotonic() - t0, 1), "note": rw.note,
                 "marker": rw.marker}
            )
            self._say(f"policies.yml rewritten ({rw.note})")

    # -- restart storm (round 17) ------------------------------------------

    def _await_handover(self, timeout: float = 600.0) -> None:
        """Hold until any in-flight restart handover completes — wave
        probes must observe either the OLD serving server or the NEW
        ready one, never the window between them (round 19: the
        deterministic-handover contract; the r18 restart-storm flake was
        exactly a probe landing inside that window)."""
        deadline = time.monotonic() + timeout
        while self._restart_in_progress and time.monotonic() < deadline:
            time.sleep(0.2)

    def _await_routing_ready(self, server, timeout: float = 120.0) -> bool:
        """Routing re-established on the NEW server: the in-process
        readiness verdict answers 200 AND one canary probe (the first
        restart-probe corpus item, expectation-OK by construction)
        round-trips the real HTTP stack with a definitive in-band
        answer. Only then do the held probes/clients resume."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                if server.state.readiness()[0] != 200:
                    time.sleep(0.1)
                    continue
            except Exception:  # noqa: BLE001 — state mid-build
                time.sleep(0.1)
                continue
            try:
                canary = self._probe(self._restart_probes[:1])
                if canary and canary[0][1] in (200, 429):
                    return True
            except OSError:
                pass  # listener not accepting yet
            time.sleep(0.1)
        return False

    def _probe(self, probes: list) -> list:
        """Serve the fixed probe corpus and return (path, status, body)
        triples — the bit-exactness witness across a restart."""
        out = []
        conn = self._conn()
        try:
            for it in probes:
                conn.sendall(self._wire(it.path, it.body))
                status, _h, body = conn.read_response()
                out.append((it.path, status, body))
        finally:
            conn.close()
        return out

    def _do_restart(self, idx: int, t0: float) -> None:
        """One restart cycle: probe → stop → re-boot the same config
        with the registry failpoint armed → rebind traffic/feed/storm to
        the new server → probe again. The fault window opens generously
        (reboot length is compile-bound) and is CLOSED the moment the
        post-restart probe answers, so post-ready errors stay visible."""
        from policy_server_tpu import failpoints
        from policy_server_tpu.audit import WatchFeed

        self.recorder.note_fault_window("server_restart", duration=600.0)
        self._restart_in_progress = True
        pre = self._probe(self._restart_probes)
        down_at = time.monotonic()
        self._say(f"restart {idx}: stopping server (pre-probe recorded)")
        self.feed.stop()  # spills its final cursor/inventory state
        feed_stopped = time.monotonic()
        self.handle.stop()
        stopped = time.monotonic()
        # the registry outage: any network fetch during the reboot
        # raises — the warm boot must come entirely from the state store
        failpoints.configure(
            "fetch.http=raise:soak-restart-registry-outage"
        )
        try:
            handle = _ServerThread(
                self._build_config(*self._config_paths)
            )
        finally:
            failpoints.configure("fetch.http=off")
        booted = time.monotonic()
        server = handle.server
        self.handle = handle
        self.server = server
        self.api_port = server.api_port
        self.native_active = server._native_frontend is not None
        self.tls_native = server._native_tls is not None
        self.recorder.soak_state = server.state
        self.storm.server = server
        # rebuild the live feed on the NEW server's snapshot store,
        # RESUMING from the spilled cursors (the cluster object survives
        # the restart — it IS the cluster)
        statestore = server.state.statestore
        resume = (
            statestore.load_audit_spill() if statestore is not None
            else None
        )
        feed = WatchFeed(
            self.cluster,
            self.cluster.kinds,
            server.state.audit.snapshot,
            refresh_seconds=5.0,
            max_queue_events=65536,
            statestore=statestore,
            spill_interval_seconds=5.0,
            resume_rvs=(resume or {}).get("rvs"),
            resume_fed=(resume or {}).get("fed"),
        ).start()
        server.state.audit_watch = feed
        server.state.audit.watch_feed = feed
        self.feed = feed
        # deterministic handover (round 19): the post-restart probe —
        # and every held client/wave — resumes only after routing is
        # provably re-established (readiness 200 + a canary round-trip)
        routing_ready = self._await_routing_ready(server)
        post = self._probe(self._restart_probes)
        self.recorder.close_fault_window("server_restart")
        self._restart_in_progress = False
        report = dict(server.state.boot_report or {})
        event = {
            "routing_ready_before_probes": routing_ready,
            "at": round(down_at - t0, 1),
            "down_s": round(time.monotonic() - down_at, 1),
            "feed_stop_s": round(feed_stopped - down_at, 1),
            "server_stop_s": round(stopped - feed_stopped, 1),
            "boot_s": round(booted - stopped, 1),
            "warm_boot_used": bool(report.get("warm")),
            "verdicts_bit_exact": pre == post,
            "audit_rows_restored": report.get("audit_rows_restored", 0),
            "resumed_kinds": len((resume or {}).get("rvs") or {}),
            "boot_report": report,
        }
        self._restarts_done.append(event)
        self._say(
            f"restart {idx} complete: warm={event['warm_boot_used']} "
            f"bit_exact={event['verdicts_bit_exact']} "
            f"down={event['down_s']}s "
            f"rows_restored={event['audit_rows_restored']}"
        )

    def _restart_loop(self, stop: threading.Event, t0: float) -> None:
        s = self.settings
        # a single restart goes LATE-middle (0.6): after the pinned mid
        # sighup / device-fault windows, so their interactions are not
        # swallowed by the downtime; a multi-restart storm spreads from
        # 0.30 (the full preset's window is long enough to serve real
        # traffic between cycles)
        if s.restarts == 1:
            offsets = [0.60 * s.duration]
        else:
            offsets = [
                (0.30 + 0.25 * i) * s.duration for i in range(s.restarts)
            ]
        for i, off in enumerate(offsets):
            while not stop.is_set() and time.monotonic() < t0 + off:
                stop.wait(0.2)
            if stop.is_set():
                return
            try:
                self._do_restart(i, t0)
            except Exception as e:  # noqa: BLE001 — a failed restart is
                # a FAILED GATE, never a crashed soak
                self._restart_in_progress = False
                self.recorder.close_fault_window("server_restart")
                self._restarts_done.append({
                    "at": round(time.monotonic() - t0, 1),
                    "error": str(e)[:300],
                    "warm_boot_used": False,
                    "verdicts_bit_exact": False,
                })
                self._say(f"restart {i} FAILED: {e}")

    # -- the run -----------------------------------------------------------

    def run(self) -> int:
        import tempfile

        from policy_server_tpu.audit import WatchFeed

        s = self.settings
        self._t0 = time.monotonic()
        rng = random.Random(s.seed)
        self._say(
            f"soak preset={s.preset} seed={s.seed} duration={s.duration}s "
            f"clients={s.clients} target_rps={s.target_rps} "
            f"objects={s.objects}"
        )
        trace = scenarios.build_trace(s.seed, s.n_trace_items, tls=s.tls)
        self._say(
            f"trace built: {len(trace.items)} items, "
            f"{len(trace.abuse)} abuse waves"
        )
        tmp = tempfile.mkdtemp(prefix="policy-server-soak-")
        policies_path = Path(tmp) / "policies.yml"
        policies_path.write_text(_POLICIES_YAML, encoding="utf-8")
        # TLS soak: mint the serving identity and the client context
        # BEFORE _build_config reads self._tls_config
        self._tls_config = None
        self._client_ssl = None
        if s.tls:
            from policy_server_tpu.config.config import TlsConfig
            from tools import tlsgen

            if not tlsgen.openssl_available():
                raise RuntimeError(
                    "--tls soak needs the openssl CLI to mint certs"
                )
            cert, key = tlsgen.self_signed_identity(
                Path(tmp) / "tls", cn="localhost"
            )
            self._tls_config = TlsConfig(
                cert_file=str(cert), key_file=str(key)
            )
            ctx = ssl_mod.create_default_context()
            ctx.check_hostname = False
            ctx.verify_mode = ssl_mod.CERT_NONE
            self._client_ssl = ctx
            self._say(f"TLS soak: identity minted at {cert}")
        tenants_path = None
        tenant_names: list[str] = []
        if s.tenants >= 2:
            tenants_path, tenant_names = self._write_tenants(Path(tmp))
            self._say(
                f"tenancy mix: {s.tenants} tenants (storm={tenant_names[0]} "
                f"quota={s.tenant_storm_quota_rps:g} rows/s, victims="
                f"{tenant_names[1:]})"
            )
        state_dir = Path(tmp) / "state" if s.restarts else None
        config = self._build_config(
            policies_path, tenants_path, state_dir=state_dir
        )
        # the restart storm re-builds the config from the SAME paths so
        # a reboot re-reads whatever policies.yml says by then — exactly
        # what a real process restart does (the churn storm may have
        # rewritten it while the server was down)
        self._config_paths = (policies_path, tenants_path, state_dir)

        handle = _ServerThread(config)
        server = handle.server
        self.handle = handle
        self.server = server
        self.api_port = server.api_port
        self.native_active = server._native_frontend is not None
        self.tls_native = server._native_tls is not None
        if s.frontend == "native" and not self.native_active:
            self._say(
                "NOTE: native frontend unavailable — soaking the python "
                "frontend (recorded in the artifact)"
            )
        if s.tls and not self.tls_native:
            self._say(
                "NOTE: TLS terminating on the aiohttp frontend (no "
                "native TLS) — handshake-abuse waves degrade to "
                "availability checks (recorded in the artifact)"
            )
        self._say(
            f"server up on :{self.api_port} native={self.native_active}"
            + (f" tls_native={self.tls_native}" if s.tls else "")
        )

        # SIGHUP: a REAL signal when we own the main thread (the handler
        # reads THROUGH self.server so it follows restart-storm swaps)
        sighup_registered = False
        if (
            hasattr(signal, "SIGHUP")
            and threading.current_thread() is threading.main_thread()
        ):
            signal.signal(
                signal.SIGHUP, lambda *_a: self.server.reload_signal()
            )
            sighup_registered = True

        # synthetic cluster → live watch feed → audit snapshot store
        self.cluster = SyntheticCluster(seed=s.seed)
        self.cluster.populate(s.objects)
        self._say(f"synthetic cluster populated: {self.cluster.object_count()} objects")
        feed = WatchFeed(
            self.cluster,
            self.cluster.kinds,
            server.state.audit.snapshot,
            refresh_seconds=5.0,
            max_queue_events=65536,
            statestore=server.state.statestore,
            spill_interval_seconds=(
                config.state_audit_spill_seconds
            ),
        ).start()
        server.state.audit_watch = feed
        server.state.audit.watch_feed = feed
        self.feed = feed

        self.recorder = SLORecorder(
            window_seconds=s.window_seconds, soak_state=server.state
        )

        storm = FaultStorm.schedule(
            rng, s.duration, server, self.cluster,
            sighup_registered=sighup_registered,
            workers=s.http_workers > 1,
            # the injected TLS accept outage needs the failpoint-polling
            # native manager; without it the armed site never refuses
            tls=s.tls and self.tls_native,
            shards=s.serving_shards > 1,
        )
        storm.recorder = self.recorder
        self.storm = storm
        self._restart_in_progress = False
        storm.hold = lambda: self._restart_in_progress
        # restart-storm probe corpus: fixed, expectation-OK trace items
        # whose responses must be BIT-EXACT across every restart
        self._restart_probes = [
            it for it in trace.items if it.expect == "ok"
        ][:4]
        self._restarts_done: list[dict] = []

        # policy-churn storm (round 15): seeded policies.yml rewrites
        # under load — the digest watch reloads each one, and the
        # predicate optimizer re-runs for every candidate epoch
        policy_rewrites = scenarios.policy_churn_storm(
            rng, s.duration, _POLICIES_YAML, rewrites=s.policy_rewrites
        )
        self._policy_rewrites_applied: list[dict] = []

        stop = threading.Event()
        threads = [
            threading.Thread(
                target=self._client_loop, args=(i, trace.items, stop),
                name=f"soak-client-{i}", daemon=True,
            )
            for i in range(s.clients)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        churner = threading.Thread(
            target=self._churn_loop, args=(stop,), name="soak-churn",
            daemon=True,
        )
        churner.start()
        policy_churner = threading.Thread(
            target=self._policy_churn_loop,
            args=(policy_rewrites, policies_path, stop, t0),
            name="soak-policy-churn", daemon=True,
        )
        policy_churner.start()
        abuser = threading.Thread(
            target=self._abuse_loop, args=(trace.abuse, stop, t0),
            name="soak-abuse", daemon=True,
        )
        abuser.start()
        # tenancy mix: one unpaced storm tenant + paced victims
        self._tenant_lock = threading.Lock()
        tenant_stats: dict[str, dict] = {}
        tenant_threads: list[threading.Thread] = []
        if tenant_names:
            storm_name = tenant_names[0]
            tenant_stats[storm_name] = {
                "role": "storm", "requests": 0, "sheds": 0, "errors": 0,
            }
            tenant_threads.append(threading.Thread(
                target=self._tenant_storm_loop,
                args=(storm_name, stop, tenant_stats[storm_name]),
                name="soak-tenant-storm", daemon=True,
            ))
            victims = tenant_names[1:]
            per_victim = s.tenant_victim_rps / max(1, len(victims))
            for name in victims:
                tenant_stats[name] = {
                    "role": "victim", "requests": 0, "sheds": 0,
                    "errors": 0, "latencies_ms": [],
                }
                tenant_threads.append(threading.Thread(
                    target=self._tenant_victim_loop,
                    args=(name, per_victim, stop, tenant_stats[name]),
                    name=f"soak-tenant-{name}", daemon=True,
                ))
            for t in tenant_threads:
                t.start()
        restarter = None
        if s.restarts:
            restarter = threading.Thread(
                target=self._restart_loop, args=(stop, t0),
                name="soak-restart", daemon=True,
            )
            restarter.start()
        storm.start(t0)
        self._say("traffic + churn + storm running")

        end = t0 + s.duration
        while time.monotonic() < end:
            time.sleep(min(2.0, max(0.1, end - time.monotonic())))
        stop.set()
        if restarter is not None:
            # a restart mid-flight finishes its swap before collection
            # (compile-bound; collection must not race a half-swapped
            # server)
            restarter.join(timeout=240)
        server = self.server  # the restart storm may have swapped it
        for t in threads:
            t.join(timeout=30)
        for t in tenant_threads:
            t.join(timeout=30)
        churner.join(timeout=5)
        policy_churner.join(timeout=5)
        abuser.join(timeout=10)
        storm.stop()
        self.recorder.finish()
        self._say("soak traffic done; collecting")

        # the storm's late reload may still be compiling its candidate:
        # give it a bounded drain so the promoted-flip gate check judges
        # a settled lifecycle, not a race with the collection point. The
        # policy-churn gate needs more than "no reload in flight": the
        # LAST rewrite's marker policy must actually be serving (its
        # digest-watch trigger may still be pending a poll tick when
        # the drain starts, and coalesced triggers re-detect next tick)
        churn_marker = (
            self._policy_rewrites_applied[-1]["marker"]
            if self._policy_rewrites_applied else None
        )
        churn_landed = False
        if server.lifecycle is not None:
            drain_end = time.monotonic() + 60.0
            while time.monotonic() < drain_end:
                if server.lifecycle.reload_in_flight():
                    time.sleep(0.25)
                    continue
                if churn_marker is None:
                    break
                env_now = server.state.evaluation_environment
                if churn_marker in env_now.policy_ids():
                    churn_landed = True
                    break
                time.sleep(0.3)  # watcher poll is 1 s; wait a tick

        # drain the NAMED tenants' in-flight reloads too: the per-tenant
        # SIGHUP fan-out gate judges settled lifecycles
        tenant_mix = None
        if tenant_names:
            mgr = server.state.tenants
            drain_end = time.monotonic() + 60.0
            while time.monotonic() < drain_end:
                busy = [
                    n for n in tenant_names
                    if (lc := mgr.get(n).state.lifecycle) is not None
                    and lc.reload_in_flight()
                ]
                if not busy:
                    break
                time.sleep(0.25)
            from tools.bench.common import pct

            victim_lat = sorted(
                v
                for st in tenant_stats.values()
                if st["role"] == "victim"
                for v in st["latencies_ms"]
            )
            reloads_per_tenant = {}
            for n in tenant_names:
                lc = mgr.get(n).state.lifecycle
                reloads_per_tenant[n] = (
                    lc.stats()["reloads"] if lc is not None else 0
                )
            storm_st = tenant_stats[tenant_names[0]]
            tenant_mix = {
                "tenants": len(tenant_names),
                "storm_tenant": tenant_names[0],
                "storm_requests": storm_st["requests"],
                "storm_sheds": storm_st["sheds"],
                "storm_shed_rate": round(
                    storm_st["sheds"] / max(1, storm_st["requests"]), 4
                ),
                "victim_requests": sum(
                    st["requests"] for st in tenant_stats.values()
                    if st["role"] == "victim"
                ),
                # OK-classified responses only — the gate requires this
                # to be nonzero so an all-shed victim outage can never
                # pass on a vacuous p99 of 0.0
                "victim_ok": len(victim_lat),
                "victim_p50_ms": round(pct(victim_lat, 0.50), 2),
                "victim_p99_ms": round(pct(victim_lat, 0.99), 2),
                "victim_unexplained": sum(
                    st["errors"] for st in tenant_stats.values()
                    if st["role"] == "victim"
                ),
                "reloads_per_tenant": reloads_per_tenant,
            }
            self._say(f"tenancy mix {json.dumps(tenant_mix)}")

        lifecycle_stats = (
            server.lifecycle.stats() if server.lifecycle else {}
        )
        # collected BEFORE the gate: the shard_kill_survived check reads
        # the router's fence/respawn receipts out of this snapshot —
        # PREFERRING the statestore's durable incident log, because the
        # in-memory counters belong to the CURRENT router and reset to
        # zero whenever a reload epoch or the restart storm rebuilds it
        # (the smoke preset does both after its shard_kill wave)
        batcher_stats = server.batcher.stats_snapshot()
        shard_kills = [
            e for e in storm.events if e.kind == "shard_kill"
        ]
        shard_log = (
            server.state.statestore.shard_events()
            if server.state.statestore is not None else []
        )
        logged_respawns = sum(
            1 for e in shard_log if e.get("reason") == "warm-respawn"
        )
        logged_fences = len(shard_log) - logged_respawns
        shard_fences = max(
            logged_fences, batcher_stats.get("shard_fences", 0)
        )
        shard_respawns = max(
            logged_respawns, batcher_stats.get("shard_respawns", 0)
        )
        shard_rerouted = max(
            sum(e.get("rows_rerouted", 0) for e in shard_log),
            batcher_stats.get("shard_reroutes", 0),
        )
        shard_fenced_rows = max(
            sum(e.get("rows_fenced", 0) for e in shard_log),
            batcher_stats.get("shard_fenced_rows", 0),
        )
        # verdict-matrix convergence (round 23): one drain dirty sweep
        # claims whatever the tail of the churn dirtied after the last
        # cadence tick, then the matrix must hold a COMPLETE verdict row
        # for every resident snapshot row, and the mid-soak promotions
        # must have taken the column-diff path (clean rows re-judged
        # only under changed columns — column_sweep_rows counts them)
        matrix_gate = None
        matrix_obj = server.state.audit_matrix
        if matrix_obj is not None:
            try:
                server.state.audit.sweep(full=False)
            except Exception as e:  # noqa: BLE001 — gate reads the counters
                self._say(f"matrix drain sweep failed: {e!r}")
            mstats = matrix_obj.stats()
            matrix_rows, matrix_rows_complete = matrix_obj.coverage()
            matrix_gate = {
                "snapshot_rows": server.state.audit.snapshot.stats()[
                    "resources"
                ],
                "matrix_rows": matrix_rows,
                "rows_complete": matrix_rows_complete,
                "column_sweep_rows": mstats["column_sweep_rows"],
                "row_sweep_rows": mstats["row_sweep_rows"],
                "cells_resident": mstats["cells_resident"],
                "columns": mstats["columns"],
                "dirty_columns": mstats["dirty_columns"],
                "matrix_version": mstats["matrix_version"],
                "changelog_emits": mstats["changelog_emits"],
                "rows_evicted": mstats["rows_evicted"],
                "columns_invalidated": mstats["columns_invalidated"],
                "spills": mstats["spills"],
                "cells_restored": mstats["cells_restored"],
            }
            self._say(f"verdict matrix {json.dumps(matrix_gate)}")
        gate = self.recorder.gate(
            p99_budget_ms=s.p99_budget_ms,
            fault_events=storm.events,
            matrix=matrix_gate,
            promoted_reloads=(
                lifecycle_stats.get("reloads")
                if server.lifecycle is not None else None
            ),
            policy_rewrites=(
                {
                    "applied": len(self._policy_rewrites_applied),
                    "planned": s.policy_rewrites,
                    "landed": churn_landed,
                }
                if s.policy_rewrites else None
            ),
            tenant_mix=tenant_mix,
            restart_storm=(
                {"planned": s.restarts, "events": self._restarts_done}
                if s.restarts else None
            ),
            shard_storm=(
                {
                    "planned": len(shard_kills),
                    "applied": sum(
                        1 for e in shard_kills
                        if e.applied_at is not None
                        and not e.effect.startswith("APPLY FAILED")
                    ),
                    "shards": s.serving_shards,
                    "fences": shard_fences,
                    "respawns": shard_respawns,
                    "rerouted_rows": shard_rerouted,
                    "fenced_rows": shard_fenced_rows,
                }
                if s.serving_shards > 1 else None
            ),
        )
        feed_stats = self.feed.stats()
        scanner_stats = server.state.audit.stats()
        native_stats = (
            server.state.native_frontend.stats()
            if server.state.native_frontend is not None else {}
        )
        snapshot_stats = server.state.audit.snapshot.stats()

        artifact_path = s.artifact or str(
            _REPO_ROOT / f"BENCH_soak_{s.tag}.json"
        )
        write_artifact(
            artifact_path,
            meta={
                "preset": s.preset,
                "seed": s.seed,
                "duration_seconds": s.duration,
                "clients": s.clients,
                "target_rps": s.target_rps,
                "trace_items": len(trace.items),
                "cluster_objects": self.cluster.object_count(),
                "churn_ops": self.cluster.churn_ops,
                "frontend": "native" if self.native_active else "python",
                "serving_shards": s.serving_shards,
                "sighup_real_signal": sighup_registered,
                # where TLS terminated: "native" (the acceptance shape),
                # "aiohttp" (fallback — TLS on, native termination off),
                # or "off" (plaintext soak)
                "tls": (
                    ("native" if self.tls_native else "aiohttp")
                    if s.tls else "off"
                ),
            },
            windows=self.recorder.windows(),
            faults=[
                {
                    "at": round(e.at, 1), "kind": e.kind,
                    "applied_at": (
                        round(e.applied_at, 1)
                        if e.applied_at is not None else None
                    ),
                    "effect": e.effect,
                }
                for e in storm.events
            ],
            gate=gate,
            extra={
                "watch_feed": feed_stats,
                "scanner": scanner_stats,
                "snapshot": snapshot_stats,
                # the convergence facts the verdict_matrix_converged
                # gate judged (round 23); None with the matrix off
                "matrix": matrix_gate,
                # flight-recorder phase attribution over the soak's own
                # traffic (round 18): the same wall-vs-summed-phases
                # reconciliation `make phase-report` gates, computed at
                # soak-gate time so the residual trends with every soak
                # artifact. None when the recorder is off.
                "phase_attribution": self._phase_attribution(),
                "batcher": {
                    k: batcher_stats[k]
                    for k in (
                        "requests_dispatched", "shed_requests",
                        "expired_dropped", "audit_batches_dispatched",
                        "audit_preemptions", "bulk_submits",
                    )
                },
                # the router's fence/respawn receipts (round 22) plus
                # per-shard terminal health — None with serving_shards=1
                # (plain batcher, no router object). Run-cumulative
                # counts come from the durable incident log (the final
                # router's own counters only cover the last epoch)
                "shards": (
                    {
                        "health": server.batcher.shard_health(),
                        "shard_fences": shard_fences,
                        "shard_reroutes": shard_rerouted,
                        "shard_fenced_rows": shard_fenced_rows,
                        "shard_respawns": shard_respawns,
                        "shard_heartbeat_faults": batcher_stats.get(
                            "shard_heartbeat_faults", 0
                        ),
                        "incident_log": shard_log,
                    }
                    if hasattr(server.batcher, "shard_health") else None
                ),
                "lifecycle": lifecycle_stats,
                "native_frontend": native_stats,
                # the TLS soak's rotation/identity receipts (round 20):
                # SSL_CTX generations, reload counters, cert expiry —
                # None on plaintext soaks or aiohttp-TLS fallback
                "tls": (
                    server._native_tls.snapshot()
                    if server._native_tls is not None else None
                ),
                # the churn storm's receipts: rewrites written, and the
                # serving epoch's optimizer accounting at collection
                # (re-derived per candidate epoch — nonzero here proves
                # the pass survived the flips)
                "policy_churn": {
                    "planned": s.policy_rewrites,
                    "applied": self._policy_rewrites_applied,
                    "last_rewrite_landed": churn_landed,
                    "optimizer_stats": dict(
                        getattr(
                            server.state.evaluation_environment,
                            "optimizer_stats", None,
                        ) or {}
                    ),
                },
                # the tenancy-mix receipts (round 16): the noisy
                # neighbor's shed rate, the victims' p50/p99, and each
                # tenant's promoted-reload count across the SIGHUPs
                "tenancy": tenant_mix,
                # the restart storm's receipts (round 17): every cycle's
                # downtime, warm-boot flag, bit-exactness witness, and
                # the full boot reports + state-store accounting
                "restart_storm": {
                    "planned": s.restarts,
                    "events": self._restarts_done,
                    "statestore": (
                        server.state.statestore.stats()
                        if server.state.statestore is not None else None
                    ),
                },
            },
        )
        self._say(
            f"gate={'PASS' if gate['passed'] else 'FAIL'} "
            f"{json.dumps(gate['checks'])}"
        )
        self._say(
            f"totals={json.dumps({k: v for k, v in gate['totals'].items() if k not in ('unexplained_samples', 'abuse_waves')})}"
        )
        self._say(f"artifact: {artifact_path}")

        self.feed.stop()
        self.cluster.stop()
        self.handle.stop()
        if sighup_registered:
            signal.signal(signal.SIGHUP, signal.SIG_DFL)
        return 0 if gate["passed"] else 1


class _HttpConn:
    """One keep-alive client connection + its pipelined read-ahead
    buffer (socket objects do not accept ad-hoc attributes). With an
    ``ssl_ctx`` the connection handshakes before the first byte — the
    TLS soak's every request flows through the native termination."""

    def __init__(
        self,
        port: int,
        timeout: float = 30.0,
        ssl_ctx: "ssl_mod.SSLContext | None" = None,
    ):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=timeout)
        if ssl_ctx is not None:
            self.sock = ssl_ctx.wrap_socket(self.sock)
        self.pending = b""

    def sendall(self, data: bytes) -> None:
        self.sock.sendall(data)

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass

    def read_response(self) -> tuple[int, dict, bytes]:
        """Read exactly one HTTP response (Content-Length framing — both
        frontends always send it); over-reads stay buffered for the next
        call."""
        buf = self.pending
        self.pending = b""
        while b"\r\n\r\n" not in buf:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("peer closed mid-response")
            buf += chunk
        head, rest = buf.split(b"\r\n\r\n", 1)
        lines = head.decode("latin-1").split("\r\n")
        status = int(lines[0].split(" ", 2)[1])
        headers = {}
        for ln in lines[1:]:
            k, _, v = ln.partition(":")
            headers[k.strip().lower()] = v.strip()
        n = int(headers.get("content-length", "0"))
        while len(rest) < n:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("peer closed mid-body")
            rest += chunk
        body, self.pending = rest[:n], rest[n:]
        return status, headers, body
