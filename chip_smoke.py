#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served admission path runs
on the accelerator.

Drives the main path once, through the entry point a user would call
(``python -m policy_server_tpu``): HTTPS → native front-end → batcher →
native encode → device → deliver, with the flagship 32-policy set at its
full width and routing pinned to the device, against a reference server
(host-oracle backend, aiohttp front-end) given the same reviews. Every
response must be 200 and byte-identical to the reference's modulo the
``Date`` header, and the server's own counters must show that the device —
not a host fast path, the oracle fallback, the circuit breaker or a Python
front-end — answered.

This process is an HTTP client and a supervisor. It never initialises a JAX
backend: a chip belongs to one process, and that process is the server
under test. Without ``--platform cpu`` a missing accelerator is a failure,
never a fallback. A phase that fails raises; nothing is caught.

    python chip_smoke.py                   # one chip, --mesh auto; then
                                           # the audit stage (scanner on)
    python chip_smoke.py --chips 4         # data:4, then data:2,policy:2
    python chip_smoke.py --platform cpu --requests 256    # rehearsal

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import signal
import socket
import ssl
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# counters of the server under test that must stay 0 over the whole run:
# each is a way to answer a request correctly without the device
MUST_STAY_ZERO = (
    "policy_server_host_fastpath_requests",
    "policy_server_oracle_fallbacks",
    "policy_server_breaker_trips",
    "policy_server_breaker_short_circuited_requests",
    "policy_server_deadline_abandoned_batches",
)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(f"[chip_smoke] {message}", flush=True)


# -- children ----------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _die_with_parent() -> None:
    """preexec_fn: the child gets SIGTERM when this process dies, however
    it dies (PR_SET_PDEATHSIG) — no server outlives the smoke."""
    import ctypes

    ctypes.CDLL(None, use_errno=True).prctl(1, signal.SIGTERM)


class Server:
    """One ``python -m policy_server_tpu`` child."""

    def __init__(self, name: str, args: list[str], env: dict[str, str],
                 log_dir: Path) -> None:
        self.name = name
        self.api_port = _free_port()
        self.ready_port = _free_port()
        self.log_path = log_dir / f"{name}.log"
        self._log = open(self.log_path, "wb")
        self.spawned_at = time.monotonic()
        self.proc = subprocess.Popen(
            [
                sys.executable, "-m", "policy_server_tpu",
                "--addr", "127.0.0.1",
                "--port", str(self.api_port),
                "--readiness-probe-port", str(self.ready_port),
                *args,
            ],
            cwd=str(ROOT), env=env, stdout=self._log,
            stderr=subprocess.STDOUT, preexec_fn=_die_with_parent,
        )

    def wait_ready(self, timeout: float) -> float:
        """Poll /readiness until 200; → seconds from spawn to ready."""
        url = f"http://127.0.0.1:{self.ready_port}/readiness"
        deadline = self.spawned_at + timeout
        while time.monotonic() < deadline:
            rc = self.proc.poll()
            require(
                rc is None,
                f"server {self.name} exited rc={rc} before it was ready; "
                f"log tail:\n{self.log_tail()}",
            )
            try:
                with urllib.request.urlopen(url, timeout=5) as r:
                    if r.status == 200:
                        return time.monotonic() - self.spawned_at
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.25)
        raise SmokeFailure(
            f"server {self.name} not ready within {timeout:.0f}s; log "
            f"tail:\n{self.log_tail()}"
        )

    def metrics(self) -> dict[str, list[tuple[dict[str, str], float]]]:
        url = f"http://127.0.0.1:{self.ready_port}/metrics"
        with urllib.request.urlopen(url, timeout=30) as r:
            return parse_metrics(r.read().decode("utf-8"))

    def log_tail(self, n: int = 6000) -> str:
        self._log.flush()
        return self.log_path.read_bytes()[-n:].decode("utf-8", "replace")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self._log.close()


def parse_metrics(text: str) -> dict[str, list[tuple[dict[str, str], float]]]:
    """Prometheus text exposition → {sample name: [(labels, value)]}."""
    from prometheus_client.parser import text_string_to_metric_families

    out: dict[str, list[tuple[dict[str, str], float]]] = {}
    for family in text_string_to_metric_families(text):
        for sample in family.samples:
            out.setdefault(sample.name, []).append(
                (dict(sample.labels), sample.value)
            )
    return out


def scalar(samples: dict, family: str) -> float:
    """Value of an unlabelled family (counters expose as ``_total``)."""
    for name in (family, family + "_total"):
        if name in samples:
            return samples[name][0][1]
    raise SmokeFailure(f"/metrics exports no family {family}")


# -- traffic -----------------------------------------------------------------


def build_requests(n: int, seed: int, policy_ids: list[str]) -> list[bytes]:
    from policy_server_tpu.policies.flagship import synthetic_firehose

    out = []
    for i, review in enumerate(synthetic_firehose(n, seed=seed)):
        body = json.dumps(review, separators=(",", ":")).encode()
        pid = policy_ids[i % len(policy_ids)]
        out.append(
            (
                f"POST /validate/{pid} HTTP/1.1\r\nHost: chip-smoke\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n"
            ).encode() + body
        )
    return out


async def _read_response(reader: asyncio.StreamReader) -> tuple[bytes, bytes]:
    """→ (head without the Date line, body) of one HTTP/1.1 response."""
    head = await reader.readuntil(b"\r\n\r\n")
    length = 0
    kept = []
    for line in head[:-4].split(b"\r\n"):
        lower = line.lower()
        if lower.startswith(b"date:"):
            continue
        if lower.startswith(b"content-length:"):
            length = int(line.split(b":", 1)[1])
        kept.append(line)
    return b"\r\n".join(kept), await reader.readexactly(length)


async def _drive_connection(
    port: int, tls: ssl.SSLContext | None, requests: list[bytes],
    indices: list[int], depth: int, out: list,
) -> None:
    """One keep-alive connection holding ``depth`` requests in flight:
    responses come back in request order, and each one read admits the
    next request."""
    reader, writer = await asyncio.open_connection(
        "127.0.0.1", port, ssl=tls,
        server_hostname="localhost" if tls is not None else None,
    )
    try:
        sent = 0
        for _ in range(min(depth, len(indices))):
            writer.write(requests[indices[sent]])
            sent += 1
        await writer.drain()
        for idx in indices:
            out[idx] = await _read_response(reader)
            if sent < len(indices):
                writer.write(requests[indices[sent]])
                sent += 1
                await writer.drain()
    finally:
        writer.close()


async def _drive(port: int, tls: ssl.SSLContext | None,
                 requests: list[bytes], connections: int,
                 depth: int) -> list[tuple[bytes, bytes]]:
    out: list = [None] * len(requests)
    connections = min(connections, len(requests))
    await asyncio.gather(*(
        _drive_connection(
            port, tls, requests,
            list(range(c, len(requests), connections)), depth, out,
        )
        for c in range(connections)
    ))
    return out


def send_pass(server: Server, tls: ssl.SSLContext | None,
              requests: list[bytes], connections: int,
              depth: int) -> list[tuple[bytes, bytes]]:
    return asyncio.run(
        _drive(server.api_port, tls, requests, connections, depth)
    )


def check_responses(label: str, got: list, want: list) -> None:
    bad_status = [
        i for i, (head, _b) in enumerate(got)
        if not head.startswith(b"HTTP/1.1 200 ")
    ]
    require(
        not bad_status,
        f"{label}: {len(bad_status)} of {len(got)} responses are not 200; "
        f"first: #{bad_status[:1]} {got[bad_status[0]] if bad_status else ''}",
    )
    differ = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    require(
        not differ,
        f"{label}: {len(differ)} of {len(got)} responses differ from the "
        f"reference server's; first: #{differ[:1]}\n got: "
        f"{got[differ[0]] if differ else ''}\nwant: "
        f"{want[differ[0]] if differ else ''}",
    )


# -- one server under test ----------------------------------------------------


def run_case(
    args: argparse.Namespace, mesh: str | None, base_args: list[str],
    env: dict[str, str], log_dir: Path, tls: ssl.SSLContext,
    requests: list[bytes], reference: list[list], cache_dir: str,
) -> dict[str, str]:
    """Boot the server under test, send the warm pass and the checked
    pass, hold it to every check. → its device info labels."""
    name = "under-test" + (f"-{mesh.replace(':', '').replace(',', '-')}"
                           if mesh else "")
    server_args = list(base_args)
    if mesh:
        server_args += ["--mesh", mesh]
    say(f"case {name}: booting (readiness wait up to "
        f"{args.ready_timeout:.0f}s)")
    server = Server(name, server_args, env, log_dir)
    done = False
    try:
        ready_s = server.wait_ready(args.ready_timeout)
        boot = server.metrics()
        info, _one = boot["policy_server_device_info"][0]
        say(f"  platform: {info['platform']}")
        say(f"  device_kind: {info['device_kind']}")
        say(f"  device_count: {info['device_count']}  mesh: "
            f"{info['mesh'] or '(single device)'}  warm-up output spans "
            f"{info['output_devices']} device(s)")
        say(f"  versions: jax {info['jax']}, jaxlib {info['jaxlib']}, "
            f"libtpu {info['libtpu'] or '(none)'}")
        say(f"  time to ready: {ready_s:.1f}s from spawn "
            f"({scalar(boot, 'policy_server_boot_time_to_ready_seconds'):.1f}s "
            "inside the bootstrap)")
        say("  programs compiled at boot: "
            f"{scalar(boot, 'policy_server_xla_programs_compiled'):.0f} "
            "(persistent-cache hits: "
            f"{scalar(boot, 'policy_server_xla_compile_cache_hits'):.0f})")
        require(
            info["platform"] == args.platform,
            f"server runs on platform {info['platform']!r}, "
            f"not {args.platform!r}",
        )
        require(bool(info["device_kind"]), "server names no device_kind")
        require(
            f"compile_cache_dir={cache_dir} " in server.log_tail(1 << 20),
            f"the server's boot report does not place its compile cache "
            f"at {cache_dir}",
        )
        if args.chips is not None:
            require(
                int(info["device_count"]) == args.chips,
                f"server names {info['device_count']} device(s), "
                f"--chips says {args.chips}",
            )
        if mesh:
            want_mesh = {"data": "1", "policy": "1"}
            want_mesh.update(part.split(":") for part in mesh.split(","))
            got_mesh = dict(
                part.split(":") for part in info["mesh"].split(",") if part
            )
            require(
                got_mesh == want_mesh,
                f"server resolved mesh {info['mesh']!r}, not {mesh!r}",
            )
        require(
            info["output_devices"] == info["device_count"],
            f"a warm-up output spans {info['output_devices']} device(s) "
            f"of {info['device_count']}: the program is not on every chip",
        )

        sent = 0
        snaps = [boot]
        for p, label in enumerate(("warm pass", "checked pass")):
            got = send_pass(server, tls, requests, args.connections,
                            args.depth)
            sent += len(requests)
            check_responses(f"{name} {label}", got, reference[p])
            # plane programs still compiling off the serving path land
            # before the next pass, so that pass traces nothing new
            deadline = time.monotonic() + args.ready_timeout
            while True:
                snap = server.metrics()
                if scalar(snap, "policy_server_plane_programs_pending") == 0:
                    break
                require(
                    time.monotonic() < deadline,
                    "plane programs still compiling after "
                    f"{args.ready_timeout:.0f}s",
                )
                time.sleep(0.5)
            snaps.append(snap)
            say(f"  {label}: {len(requests)} requests, all 200, all "
                "identical to the reference")

        def delta(family: str, a: int, b: int) -> float:
            return scalar(snaps[b], family) - scalar(snaps[a], family)

        for p, label in ((1, "warm pass"), (2, "checked pass")):
            say(f"  {label}: plane_program_compiles "
                f"{delta('policy_server_plane_program_compiles', p - 1, p):.0f}"
                ", XLA programs compiled "
                f"{delta('policy_server_xla_programs_compiled', p - 1, p):.0f}"
                ", rows dispatched to the device "
                f"{delta('policy_server_dispatched_rows', p - 1, p):.0f}")
        final = snaps[2]
        for family in MUST_STAY_ZERO:
            require(
                scalar(final, family) == 0,
                f"{family} = {scalar(final, family):.0f}: requests were "
                "answered without the device",
            )
        for family in ("policy_server_dispatched_rows",
                       "policy_server_wire_rows",
                       "policy_server_host_encode_rows"):
            require(
                delta(family, 1, 2) > 0,
                f"{family} did not advance in the checked pass",
            )
        require(
            delta("policy_server_dispatched_rows", 1, 2) == len(requests),
            "rows dispatched to the device in the checked pass: "
            f"{delta('policy_server_dispatched_rows', 1, 2):.0f}, requests: "
            f"{len(requests)} — not every verdict came from the device",
        )
        require(
            delta("policy_server_host_encode_rows", 1, 2) >= len(requests),
            "the native encoder saw fewer rows than requests in the "
            "checked pass",
        )
        for family in ("policy_server_plane_program_compiles",
                       "policy_server_xla_programs_compiled"):
            require(
                delta(family, 1, 2) == 0,
                f"{family} advanced by {delta(family, 1, 2):.0f} in the "
                "checked pass: programs compiled at serve time",
            )
        native = scalar(final, "policy_server_native_http_requests")
        require(
            native == sent,
            f"native front-end framed {native:.0f} requests, "
            f"{sent} were sent: another front-end answered",
        )
        require(
            scalar(final, "policy_server_tls_handshakes_ok") > 0,
            "no TLS handshake completed on the native loops",
        )
        done = True
        return info
    finally:
        if not done:
            print(f"--- {name} log tail ---\n{server.log_tail()}",
                  file=sys.stderr, flush=True)
            if server.proc.poll() is None:
                last = server.metrics()
                for family in (*MUST_STAY_ZERO,
                               "policy_server_plane_program_compiles",
                               "policy_server_dispatched_rows"):
                    print(f"--- {family} = {scalar(last, family):.0f}",
                          file=sys.stderr, flush=True)
        server.stop()


AUDIT_REQUESTS = 1000  # reviews the audit stage serves


def run_audit_stage(
    args: argparse.Namespace, base_args: list[str], env: dict[str, str],
    log_dir: Path, tls: ssl.SSLContext, requests: list[bytes],
    reference: list, policy_count: int,
) -> None:
    """The compliance scanner beside live admission (PR 38): boot with
    ``--audit-mode interval``, serve 1,000 reviews, wait for the scanner to
    sweep what they left dirty, and hold the lane to its counts: every
    object the store holds has a report row of every policy, no sweep
    failed, and the audit rows were counted by the lane and not as
    requests the device answered."""
    requests, reference = requests[:AUDIT_REQUESTS], reference[:AUDIT_REQUESTS]
    say(f"stage audit: booting with --audit-mode interval, "
        f"{len(requests)} reviews")
    server = Server(
        "under-test-audit",
        [*base_args, "--audit-mode", "interval",
         "--audit-interval-seconds", "1"],
        env, log_dir,
    )
    done = False
    try:
        server.wait_ready(args.ready_timeout)
        boot = server.metrics()
        got = send_pass(server, tls, requests, args.connections, args.depth)
        check_responses("under-test-audit", got, reference)
        deadline = time.monotonic() + args.ready_timeout
        while True:
            snap = server.metrics()
            resources = scalar(snap, "policy_server_audit_snapshot_resources")
            resident = scalar(snap, "policy_server_audit_reports_resident")
            if (resources > 0 and resident == resources * policy_count
                    and scalar(snap, "policy_server_audit_lane_depth") == 0):
                break
            require(
                time.monotonic() < deadline,
                f"the scanner holds {resident:.0f} report rows for "
                f"{resources:.0f} objects x {policy_count} policies after "
                f"{args.ready_timeout:.0f}s",
            )
            time.sleep(0.5)

        def moved(family: str) -> float:
            return scalar(snap, family) - scalar(boot, family)

        say(f"  {resources:.0f} objects in the snapshot store, "
            f"{resident:.0f} report rows, "
            f"{moved('policy_server_audit_batches_dispatched'):.0f} audit "
            "batches, "
            f"{moved('policy_server_audit_preemptions'):.0f} handed back")
        require(
            moved("policy_server_audit_rows_dispatched") >= resident,
            "the lane counted "
            f"{moved('policy_server_audit_rows_dispatched'):.0f} rows, the "
            f"reports hold {resident:.0f}",
        )
        require(
            moved("policy_server_dispatched_rows") == len(requests),
            "rows dispatched for a caller: "
            f"{moved('policy_server_dispatched_rows'):.0f}, requests: "
            f"{len(requests)} - audit rows were counted as answers",
        )
        require(
            scalar(snap, "policy_server_audit_sweep_errors") == 0,
            "an audit sweep failed",
        )
        for family in MUST_STAY_ZERO:
            require(scalar(snap, family) == 0,
                    f"{family} = {scalar(snap, family):.0f} with the "
                    "scanner on")
        done = True
    finally:
        if not done:
            print(f"--- under-test-audit log tail ---\n{server.log_tail()}",
                  file=sys.stderr, flush=True)
        server.stop()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=8192,
                    help="reviews per pass (two passes are sent)")
    ap.add_argument("--platform", choices=["tpu", "cpu"], default="tpu",
                    help="'cpu' is an explicit rehearsal, never a fallback")
    ap.add_argument("--chips", type=int, default=None,
                    help="devices the server must name; 4 also runs the "
                         "data:4 and data:2,policy:2 meshes")
    ap.add_argument("--mesh", default=None,
                    help="one explicit --mesh for the server under test")
    ap.add_argument("--connections", type=int, default=64)
    ap.add_argument("--depth", type=int, default=12,
                    help="requests in flight per connection "
                         "(connections x depth >= 512)")
    ap.add_argument("--ready-timeout", type=float, default=900.0)
    ap.add_argument("--log-dir", default=None,
                    help="keep the servers' logs here")
    args = ap.parse_args(argv)
    rehearsal = args.platform == "cpu"

    # Belt for "one process per chip": should anything in this process
    # ever reach a JAX backend, it is the CPU one. The children get their
    # platform assigned explicitly below.
    os.environ["JAX_PLATFORMS"] = "cpu"

    from policy_server_tpu.policies.flagship import flagship_policy_specs
    from tools.tlsgen import self_signed_identity

    import yaml

    work = Path(tempfile.mkdtemp(prefix="chip-smoke-"))
    log_dir = Path(args.log_dir).resolve() if args.log_dir else work
    log_dir.mkdir(parents=True, exist_ok=True)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache"
    )
    populated = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    say(f"platform: {args.platform}" + ("  rehearsal: true" if rehearsal
                                        else ""))
    say(f"compile cache: {cache_dir} (populated on entry: "
        f"{str(populated).lower()})")

    specs = flagship_policy_specs()
    sig_store = next(
        (s["settings"]["signatureStore"] for s in specs.values()
         if "signatureStore" in s.get("settings", {})), None,
    )
    policies_path = work / "policies.yml"
    policies_path.write_text(yaml.safe_dump(specs), encoding="utf-8")
    cert, key = self_signed_identity(work)
    tls = ssl.create_default_context(cafile=str(cert))
    requests = build_requests(args.requests, args.seed, list(specs))
    say(f"traffic: synthetic_firehose({args.requests}, seed={args.seed}) "
        f"over {len(specs)} policy ids in rotation, {args.connections} "
        f"connections x {args.depth} in flight")

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = args.platform
    if rehearsal and (args.chips or 1) > 1:
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}"
        ).strip()
    ref_env = dict(os.environ)
    ref_env["JAX_PLATFORMS"] = "cpu"
    base_args = [
        "--policies", str(policies_path),
        "--frontend", "native",
        "--cert-file", str(cert), "--key-file", str(key),
        # routing pinned to the device: no host fast path, no latency
        # router, no verdict cache. Every other flag at its default.
        "--host-fastpath-threshold", "0",
        "--latency-budget-ms", "0",
        "--verdict-cache-size", "0",
    ]
    if args.mesh:
        meshes: list[str | None] = [args.mesh]
    elif args.chips == 4:
        meshes = ["data:4", "data:2,policy:2"]
    else:
        meshes = [None]

    ok = False
    ref_server = Server(
        "reference",
        ["--policies", str(policies_path),
         "--evaluation-backend", "oracle"],
        ref_env, log_dir,
    )
    try:
        ref_server.wait_ready(args.ready_timeout)
        # the reference is deterministic, but each pass asks it again: the
        # comparison is then between two live servers given the same bytes
        reference = [
            send_pass(ref_server, None, requests, 32, 2) for _ in range(2)
        ]
        info: dict[str, str] = {}
        for mesh in meshes:
            info = run_case(args, mesh, base_args, env, log_dir, tls,
                            requests, reference, cache_dir)
        if meshes == [None]:  # one chip: the scanner's lane has a stage
            run_audit_stage(args, base_args, env, log_dir, tls, requests,
                            reference[0], len(specs))
        ok = True
    finally:
        if not ok:
            print(f"--- reference log tail ---\n{ref_server.log_tail(2000)}",
                  file=sys.stderr, flush=True)
        ref_server.stop()
        if ok or log_dir != work:  # a failed run keeps its logs
            shutil.rmtree(work, ignore_errors=True)
        if sig_store:
            shutil.rmtree(sig_store, ignore_errors=True)

    result = {
        "ok": True,
        "device": {
            "platform": info["platform"],
            "kind": info["device_kind"],
            "count": int(info["device_count"]),
        },
    }
    if rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
