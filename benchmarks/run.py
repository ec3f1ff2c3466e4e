#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is a supervisor and an HTTP client's parent; it never
initialises a JAX backend, because a chip belongs to one process and that
process is the server under test. It reads the cell, its configuration,
its traffic mix and its layer metrics from their data files by the names in
``BENCHMARK.json``; makes policies file, signature store, TLS identity and
traffic from ``--seed``; boots the server under test through
``benchmarks/serve.py``; requires the platform and the chip count the cell
asks for (``--platform cpu`` is an explicit rehearsal and says so on an
earlier line — a missing chip is a failure, never a fallback); warms until
a whole pass compiled nothing (and, where the mix has a warm state, until
the server's background work stands where it says); drives the window;
frees the chip; and only then runs the plain reference over every answer of
the window. A configuration may
name its own plain reference and a mix its own generator
(``check_manifest.plugin``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number that decided ``correct``
beside its limit. The same numbers are the last lines of standard error.

``--control <fault>`` (not used by the driver) puts the reference, with one
guarantee of the configuration broken, in the program's place: the control
of the comparison, which has to come out as not correct.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import base64  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402
from pathlib import Path  # noqa: E402
from types import ModuleType  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import check_manifest  # noqa: E402
import host_spans  # noqa: E402
import reduce  # noqa: E402

COMPILE_COUNTERS = (
    "policy_server_xla_programs_compiled",
    "policy_server_plane_program_compiles",
)
# how often a warm state's pause is looked for through the warm traffic,
# each look reaching this far back into the previous one; and how long it
# may take, well inside a run's 360 s
PAUSE_POLL_S = 0.5
PAUSE_OVERLAP_NS = 200_000_000
PAUSE_WAIT_S = 150.0
# in-band answers under HTTP 200 that are a request failed, not a verdict
FAILED_CODES = (429, 500, 503, 504)
IMAGE_SIGNATURE_TYPE = "cosign container image signature"


class RunFailure(Exception):
    """The run cannot give a result (no chip, no server, no warm state)."""


def say(message: str) -> None:
    print(f"[benchmark] {message}", flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text(encoding="utf-8"))


# -- children -----------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _die_with_parent() -> None:
    """preexec_fn: the child gets SIGTERM when this process dies, however
    it dies (PR_SET_PDEATHSIG) — nothing outlives the run."""
    import ctypes

    ctypes.CDLL(None, use_errno=True).prctl(1, signal.SIGTERM)


class Server:
    """The one server child of a run (copied from chip_smoke.py's)."""

    def __init__(self, command: list[str], env: dict[str, str],
                 log_path: Path) -> None:
        self.api_port = _free_port()
        self.ready_port = _free_port()
        self.log_path = log_path
        self._log = open(log_path, "wb")
        self.spawned_at = time.monotonic()
        self.proc = subprocess.Popen(
            [*command, "--addr", "127.0.0.1", "--port", str(self.api_port),
             "--readiness-probe-port", str(self.ready_port)],
            cwd=str(ROOT), env=env, stdout=self._log,
            stderr=subprocess.STDOUT, preexec_fn=_die_with_parent,
        )

    def wait_ready(self, timeout: float) -> float:
        """Poll /readiness until 200; → seconds from spawn to ready."""
        url = f"http://127.0.0.1:{self.ready_port}/readiness"
        deadline = self.spawned_at + timeout
        while time.monotonic() < deadline:
            rc = self.proc.poll()
            if rc is not None:
                raise RunFailure(f"the server exited rc={rc} before it was "
                                 f"ready; log tail:\n{self.log_tail()}")
            try:
                with urllib.request.urlopen(url, timeout=5) as r:
                    if r.status == 200:
                        return time.monotonic() - self.spawned_at
            except (urllib.error.URLError, OSError):
                pass
            time.sleep(0.1)
        raise RunFailure(f"the server was not ready within {timeout:.0f}s; "
                         f"log tail:\n{self.log_tail()}")

    def metrics(self) -> reduce.Samples:
        url = f"http://127.0.0.1:{self.ready_port}/metrics"
        with urllib.request.urlopen(url, timeout=30) as r:
            return reduce.parse_metrics(r.read().decode("utf-8"))

    def log_tail(self, n: int = 4000) -> str:
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


class Clients:
    """The K client processes of a run (benchmarks/client.py)."""

    def __init__(self, spec: dict, count: int, log_path: Path) -> None:
        self._log = open(log_path, "wb")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.procs = []
        for k in range(count):
            proc = subprocess.Popen(
                [sys.executable, str(HERE / "client.py")], cwd=str(ROOT),
                env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=self._log, preexec_fn=_die_with_parent,
            )
            proc.stdin.write(
                json.dumps(dict(spec, k=k, K=count)).encode() + b"\n")
            proc.stdin.flush()
            self.procs.append(proc)

    def _tell(self, line: str) -> None:
        for proc in self.procs:
            proc.stdin.write(line.encode() + b"\n")
            proc.stdin.flush()

    def _answers(self, word: bytes) -> list[list[bytes]]:
        out = []
        for k, proc in enumerate(self.procs):
            line = proc.stdout.readline().split()
            if not line or line[0] != word:
                self._log.flush()
                raise RunFailure(
                    f"client {k} answered {line!r}, not {word!r}; its log: "
                    + Path(self._log.name).read_bytes()[-2000:].decode(
                        "utf-8", "replace"))
            out.append(line)
        return out

    def connect(self) -> None:
        """Once the server listens: wait for the pools, open the first
        connections."""
        self._answers(b"pooled")
        self._tell("connect")
        self._answers(b"ready")

    def warm(self, base: int, count: int) -> tuple[int, int]:
        self._tell(f"warm {base} {count}")
        answers = self._answers(b"warm")
        return (sum(int(a[1]) for a in answers),
                sum(int(a[2]) for a in answers))

    def start_window(self, base: int, t0: float, seconds: float,
                     rate: float | None = None) -> None:
        self._tell(f"window {base} {t0!r} {seconds!r}"
                   + (f" {rate!r}" if rate else ""))

    def results(self) -> tuple[list[tuple], int]:
        records, connections = [], 0
        for line, proc in zip(self._answers(b"result"), self.procs):
            part = pickle.loads(proc.stdout.read(int(line[1])))
            records.extend(part["records"])
            connections += part["connections"]
        return records, connections

    def stop(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    proc.stdin.write(b"quit\n")
                    proc.stdin.flush()
                except OSError:
                    pass
        for proc in self.procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)
        self._log.close()


class Pauses(threading.Thread):
    """Reads the flight recorder's ring every ``PAUSE_POLL_S`` and finds
    the first pause of a phase of the server's background work that began
    ``back_s`` before the watch started or later: ``quiet_s`` or more from
    the end of one of its stamps to the start of the next. ``began`` is
    when the pause began, on the ring's clock (``time.monotonic``); a pause
    is known once the phase is stamped again, so a long stamp is never
    taken for one. The ring answers a short interval with a few hundred
    events, where the exposition of ``/metrics`` is megabytes."""

    def __init__(self, server: Server, phase: str, quiet_s: float,
                 back_s: float) -> None:
        super().__init__(name="benchmark-pauses", daemon=True)
        self.server, self.phase, self.quiet_ns = server, phase, quiet_s * 1e9
        self.began: float | None = None
        self.error: Exception | None = None
        self._last_end: float | None = None  # of the phase's latest stamp
        self._since = time.monotonic_ns() - int(back_s * 1e9)
        self._over = threading.Event()

    def look(self) -> None:
        until = time.monotonic_ns()
        stamps = sorted(
            (start, end) for name, start, end, _batch in host_spans.ring(
                host_spans.fetch_timeline(self.server.ready_port,
                                          self._since, until))
            if name == self.phase)
        self._since = until - PAUSE_OVERLAP_NS  # a stamp written late
        for start, end in stamps:
            if (self._last_end is not None
                    and start - self._last_end >= self.quiet_ns):
                self.began = self._last_end / 1e9
                return
            self._last_end = max(self._last_end or end, end)

    def run(self) -> None:
        try:
            while self.began is None and not self._over.wait(PAUSE_POLL_S):
                self.look()
        except Exception as e:  # noqa: BLE001 - the supervisor reports it
            self.error = e

    def age(self) -> float | None:
        """Seconds since the first pause began; None before one was seen."""
        if self.error is not None:
            raise RunFailure(f"reading the ring: {self.error}")
        return None if self.began is None else time.monotonic() - self.began

    def stop(self) -> None:
        self._over.set()
        self.join()


class Sleeper(threading.Thread):
    """Sleeps 10 ms at a time through a window and keeps the longest it
    overslept. The supervisor does nothing else meanwhile, so what it
    oversleeps is the machine's doing (the chip machine is a VM that now
    and then stops whole for a second or more), not the server's or the
    generator's: a window's tail or rate that a stall made is told apart."""

    def __init__(self) -> None:
        super().__init__(name="benchmark-sleeper", daemon=True)
        self.worst_s = 0.0
        self._over = threading.Event()

    def run(self) -> None:
        while not self._over.is_set():
            t = time.monotonic()
            time.sleep(0.01)
            self.worst_s = max(self.worst_s, time.monotonic() - t - 0.01)

    def stop(self) -> float:
        self._over.set()
        self.join()
        return self.worst_s


# -- the inputs a run makes -----------------------------------------------------


def self_signed_identity(directory: Path) -> tuple[Path, Path]:
    """One self-signed server identity through the ``openssl`` CLI (copied
    from tools/tlsgen.py); → (cert, key)."""
    cert, key = directory / "server.pem", directory / "server-key.pem"
    proc = subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(cert), "-days", "2",
         "-subj", "/CN=localhost",
         "-addext", "subjectAltName=DNS:localhost,IP:127.0.0.1"],
        capture_output=True, text=True,
    )
    if proc.returncode != 0:
        raise RunFailure(f"openssl req failed: {proc.stderr.strip()[:500]}")
    return cert, key


def build_signature_store(signing: dict, directory: Path) -> str:
    """Sign the configuration's ``signed_images`` with its Ed25519 key into
    the store layout the deployment reads (one cosign-style bundle per
    image, named by the sha256 of its reference); → the public key's PEM."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )
    from cryptography.hazmat.primitives.serialization import (
        Encoding,
        PublicFormat,
    )

    key = Ed25519PrivateKey.from_private_bytes(
        bytes.fromhex(signing["ed25519_private_bytes_hex"]))
    directory.mkdir(parents=True, exist_ok=True)
    for image in signing["signed_images"]:
        payload = json.dumps({
            "critical": {
                "identity": {"docker-reference": image},
                "image": {
                    "docker-manifest-digest": signing["manifest_digest"]},
                "type": IMAGE_SIGNATURE_TYPE,
            },
            "optional": {},
        }, sort_keys=True, separators=(",", ":")).encode()
        bundle = {"signatures": [{
            "keyid": "",
            "payload": base64.b64encode(payload).decode(),
            "signature": base64.b64encode(key.sign(payload)).decode(),
        }]}
        name = hashlib.sha256(image.encode()).hexdigest() + ".sig.json"
        (directory / name).write_text(json.dumps(bundle), encoding="utf-8")
    return key.public_key().public_bytes(
        Encoding.PEM, PublicFormat.SubjectPublicKeyInfo).decode()


def fill(value, marks: dict[str, str]):
    """The configuration's policies with ``@SIGSTORE@`` / ``@PUBKEY@``
    replaced by this run's."""
    if isinstance(value, dict):
        return {k: fill(v, marks) for k, v in value.items()}
    if isinstance(value, list):
        return [fill(v, marks) for v in value]
    return marks.get(value, value) if isinstance(value, str) else value


# -- judging --------------------------------------------------------------------


def judge_answers(records: list[tuple], generator: ModuleType,
                  traffic: object, reference: ModuleType, policies: dict,
                  signed: set, head: list[str]) -> dict[str, int]:
    """Every answer of the window against the plain reference: the
    configuration's (``reference.py`` where it names none), over the
    requests of the mix's generator (``traffic.py`` for ``pod_reviews``)."""
    entries = [policies[pid] for pid in traffic.policy_ids]
    unanswered = inband = mismatched = 0
    first = None
    for n, _due, _sent, _done, raw in records:
        if raw is None:
            unanswered += 1
            continue
        request = traffic.reviews[traffic.shape_of(n)]["request"]
        want = reference.http_response(
            head, generator.uid_of(n),
            reference.review_response(entries[traffic.policy_of(n)],
                                      request, signed))
        if raw == want:
            continue
        mismatched += 1
        if first is None:
            first = (n, raw, want)
        if not raw.startswith(b"HTTP/1.1 200 "):
            inband += 1
        else:
            try:
                status = json.loads(raw.partition(b"\r\n\r\n")[2])[
                    "response"].get("status") or {}
                inband += status.get("code") in FAILED_CODES
            except (ValueError, KeyError, AttributeError):
                pass
    if first is not None:
        n, raw, want = first
        print(f"[benchmark] first answer that differs: request {n} "
              f"(policy {traffic.policy_ids[traffic.policy_of(n)]})\n"
              f"  got:  {raw[-600:]!r}\n  want: {want[-600:]!r}",
              file=sys.stderr, flush=True)
    return {"unanswered": unanswered, "failed_in_band": inband,
            "mismatched": mismatched}


def is_good(raw: bytes | None) -> bool:
    """A good answer, for the rate and the tails: HTTP 200 with no in-band
    failure code. Whether it says the right thing is ``correct``'s."""
    if raw is None or not raw.startswith(b"HTTP/1.1 200 "):
        return False
    at = raw.find(b'"code": ', -200)
    return at < 0 or int(raw[at + 8:at + 11]) not in FAILED_CODES


# -- one run ----------------------------------------------------------------------


class Rig:
    """A run's server under test and clients, booted, checked and warm."""

    def __init__(self, args: argparse.Namespace, work: Path) -> None:
        self.args, self.work = args, work
        self.children: list = []
        self.manifest = check_manifest.load(ROOT)
        self.cell = next((w for w in self.manifest["workloads"]
                          if w["name"] == args.workload), None)
        if self.cell is None:
            raise RunFailure(
                f"BENCHMARK.json has no workload {args.workload!r}")
        if not (ROOT / "policy_server_tpu").is_dir():
            raise RunFailure("no program here: this checkout holds no "
                             "policy_server_tpu/")
        self.entry = next(c for c in self.manifest["configs"]
                          if c["name"] == self.cell["config"])
        self.config = load_json(ROOT / self.entry["file"])
        self.mix = load_json(HERE / "traffic" / f"{self.cell['traffic']}.json")
        warm = HERE / "warm" / f"{self.cell['traffic']}.json"
        self.warm_state = load_json(warm) if warm.is_file() else {}
        self.generator = check_manifest.plugin(
            "generator", self.mix.get("generator"))
        self.reference = check_manifest.plugin(
            "reference", self.config.get("reference"))
        self.rehearsal = args.platform == "cpu"
        self.info: dict[str, str] = {}
        self.sent = 0  # request numbers used so far

    def boot(self) -> None:
        args, work, config = self.args, self.work, self.config
        chips = int(self.cell["chips"])
        if chips != int(config["chips"]):
            raise RunFailure(f"cell asks for {chips} chip(s), its "
                             f"configuration for {config['chips']}")
        # inputs, from the seed and the configuration's data
        pubkey = build_signature_store(config["signing"], work / "sigstore")
        self.policies = fill(config["policies"], {
            "@SIGSTORE@": str(work / "sigstore"), "@PUBKEY@": pubkey})
        self.policy_ids = list(self.policies)
        (work / "policies.yml").write_text(
            json.dumps(self.policies), encoding="utf-8")
        cert, key = self_signed_identity(work)
        self.control_dir = work / "control"
        self.control_dir.mkdir()

        env = dict(os.environ, JAX_PLATFORMS=args.platform)
        if self.rehearsal and chips > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}").strip()
        common = ["--policies", str(work / "policies.yml"),
                  "--cert-file", str(cert), "--key-file", str(key)]
        if args.control:
            command = [sys.executable, str(HERE / "control_server.py"),
                       "--config", str(ROOT / self.entry["file"]),
                       "--reference", self.reference.__file__,
                       "--break", args.control, *common]
        else:
            command = [sys.executable, str(HERE / "serve.py"),
                       str(self.control_dir), "--", *common,
                       *config["server_flags"]]
        self.server = Server(command, env, work / "server.log")
        self.children.append(self.server)
        self.clients = Clients({
            "port": self.server.api_port, "cafile": str(cert),
            "seed": args.seed, "mix": self.mix,
            "generator": self.generator.__file__,
            "policy_ids": self.policy_ids,
        }, int(self.mix["client_processes"]), work / "clients.log")
        self.children.append(self.clients)

        self.boot_s = self.server.wait_ready(args.ready_timeout)
        boot = self.server.metrics()
        if not args.control:
            info = self.info = boot["policy_server_device_info"][0][0]
            if info["platform"] != args.platform:
                raise RunFailure(f"the server runs on platform "
                                 f"{info['platform']!r}, not {args.platform!r}")
            if int(info["device_count"]) != chips:
                raise RunFailure(f"the server names {info['device_count']} "
                                 f"device(s), the cell asks for {chips}")
            if info["output_devices"] != info["device_count"]:
                raise RunFailure(
                    f"a warm-up output spans {info['output_devices']} "
                    f"device(s) of {info['device_count']}: the program is "
                    "not on every chip")
        self.clients.connect()
        self.ready_s = time.monotonic() - T_START
        self._warm(boot)

    def _warm(self, snap: reduce.Samples) -> None:
        """Passes of the cell's own traffic until one compiled nothing.

        A mix may have a warm state, ``warm/<mix>.json``, for a server whose
        background work comes in rounds with pauses between them: traffic
        then goes on after the passes until the window can open at the same
        point of a round (``_until_paused``). Every other mix warms as it
        always did."""
        args, count = self.args, int(self.mix["warm_requests"])
        for nth in range(args.warm_passes):
            self._send(count)
            if args.control:
                return
            deadline = time.monotonic() + args.ready_timeout
            while True:
                after = self.server.metrics()
                if reduce.sample(
                        after, "policy_server_plane_programs_pending") == 0:
                    break
                if time.monotonic() > deadline:
                    raise RunFailure("plane programs still compiling after "
                                     f"{args.ready_timeout:.0f}s")
                time.sleep(0.2)
            compiled = sum(
                reduce.delta(snap, after, c) for c in COMPILE_COUNTERS)
            snap = after
            if compiled == 0 and nth > 0:
                break
        else:
            raise RunFailure(f"{args.warm_passes} warm passes and the last "
                             "still compiled a program")
        if self.warm_state:
            self._until_paused(self.warm_state)

    def _until_paused(self, state: dict) -> None:
        """Chunks of ``chunk_requests`` warm requests until a pause of the
        ring phase ``pause_phase`` (``Pauses``) that began no earlier than
        ``open_after_pause_s`` before the passes ended began that long
        ago."""
        after_s = float(state["open_after_pause_s"])
        pauses = Pauses(self.server, state["pause_phase"],
                        float(state["pause_quiet_s"]), after_s)
        pauses.start()
        try:
            deadline = time.monotonic() + PAUSE_WAIT_S
            chunks = 0
            while (age := pauses.age()) is None or age < after_s:
                if time.monotonic() > deadline:
                    raise RunFailure(f"no pause of {pauses.phase} in "
                                     f"{PAUSE_WAIT_S:.0f}s of warm traffic")
                self._send(int(state["chunk_requests"]))
                chunks += 1
        finally:
            pauses.stop()
        say(f"warm: {self.sent} requests, {chunks} chunks after the passes; "
            f"{pauses.phase} paused {age:.2f} s ago")

    def _send(self, count: int) -> None:
        done, failed = self.clients.warm(self.sent, count)
        self.sent += count
        if failed:
            raise RunFailure(
                f"{failed} of {done} warm requests got no answer")

    def window(self, seconds: float, trace: bool = False,
               rate: float | None = None) -> dict:
        """Drive one window; → its records, clock and counter snapshots."""
        before = self.server.metrics()
        t0 = time.monotonic() + 0.2
        self.clients.start_window(self.sent, t0, seconds, rate)
        sleeper = Sleeper()
        sleeper.start()
        traced: dict = {}
        if trace and not self.args.control:
            traced = trace_window(self.server, self.control_dir, self.work,
                                  t0, seconds)
        records, connections = self.clients.results()
        stalled_s = sleeper.stop()
        base, self.sent = self.sent, max(
            (r[0] for r in records), default=self.sent) + 1
        after = self.server.metrics()
        if traced:
            traced["done"] = load_json(
                await_file(self.control_dir / "trace.done", 180))
            found = sorted(traced["dir"].glob("**/*.xplane.pb"))
            if not found:
                raise RunFailure("the trace left no .xplane.pb")
            traced["file"] = found[-1]
            # the flight recorder's ring for the traced interval, on the
            # clock the launcher started the trace by
            since = int(traced["started_at"] * 1e9)
            traced["timeline"] = host_spans.fetch_timeline(
                self.server.ready_port, since,
                since + int(traced["done"]["traced_s"] * 1e9))
        rec = {
            "due": np.array([r[1] for r in records], np.float64),
            "sent": np.array([r[2] for r in records], np.float64),
            "done": np.array([r[3] for r in records], np.float64),
            "good": np.array([is_good(r[4]) for r in records], bool),
        }
        stats = reduce.client_stats(
            rec, t0, seconds, float(self.mix.get("timeout_s", 10)))
        stats["connections"] = connections
        stats["host_stall_max_ms"] = stalled_s * 1e3
        # each client's latest request: a stall of one client is the
        # generator's, a stall of all at one instant the machine's
        late = np.nan_to_num(rec["sent"] - rec["due"])
        K = len(self.clients.procs)
        worst: dict[int, int] = {}  # client -> index of its latest request
        for i, r in enumerate(records):
            k = (r[0] - base) % K
            if k not in worst or late[i] > late[worst[k]]:
                worst[k] = i
        latest = ", ".join(
            f"{late[i] * 1e3:.1f} ms at +{rec['due'][i] - t0:.2f} s"
            for _k, i in sorted(worst.items()))
        tails = ", ".join(f"p{q} {stats[f'latency_p{q}_ms']:.3f}"
                          for q in reduce.TAILS)
        say(f"window: {len(records)} requests, {int(rec['good'].sum())} "
            f"good, {stats['reviews_per_s']:.1f} reviews/s, {tails} ms, "
            f"late p99 {stats['late_p99_ms']:.3f} ms, {connections} "
            f"connections, the supervisor overslept {stalled_s * 1e3:.1f} ms "
            f"at most; latest request of each client: {latest}")
        return {"records": records, "rec": rec, "stats": stats, "t0": t0,
                "before": before, "after": after, "traced": traced}

    def memory_peaks(self) -> list:
        (self.control_dir / "memory.req").write_text("", encoding="utf-8")
        return load_json(await_file(self.control_dir / "memory.json", 30))[
            "peak_bytes_in_use"]

    def stop(self) -> None:
        for child in reversed(self.children):
            child.stop()
        self.children = []


def run(args: argparse.Namespace, rig: Rig) -> dict:
    if rig.rehearsal:
        print(json.dumps({"rehearsal": True, "platform": "cpu"}), flush=True)
    rig.boot()
    setup_s = time.monotonic() + 0.2 - T_START
    say(f"set-up {setup_s:.1f}s: boot {rig.boot_s:.1f}s, clients and server "
        f"ready at {rig.ready_s:.1f}s, warm passes until {setup_s:.1f}s")
    win = rig.window(args.seconds, trace=bool(args.trace))
    setup_s = win["t0"] - T_START
    peaks = [] if args.control else rig.memory_peaks()
    rig.stop()  # the chip is free; the reference runs from here on

    manifest, cell, config = rig.manifest, rig.cell, rig.config
    records, rec, stats = win["records"], win["rec"], win["stats"]
    before, after, traced = win["before"], win["after"], win["traced"]
    traffic = rig.generator.Traffic(rig.mix, args.seed, rig.policy_ids)
    t_ref = time.monotonic()
    compared = {
        name: [value, 0] for name, value in judge_answers(
            records, rig.generator, traffic, rig.reference, rig.policies,
            set(config["signing"]["signed_images"]),
            config["response_head"]).items()
    }
    say(f"reference: {len(records)} answers in "
        f"{time.monotonic() - t_ref:.1f}s")
    answered = len(records) - compared["unanswered"][0]
    if not args.control:  # the control server claims no device, no counter
        say(f"{answered} answers; by source: " + json.dumps({
            source: n for source, n in reduce.answers_by_source(
                before, after).items() if n}))
        for name, value in reduce.held_to_its_sources(
                config, before, after, answered).items():
            compared[name] = [value, 0]
        compared["not_framed_natively"] = [abs(len(records) - reduce.delta(
            before, after, "policy_server_native_http_requests")), 0]
        compared["shed"] = [reduce.delta(
            before, after, "policy_server_shed_requests"), 0]
        compared["compiles_in_window"] = [sum(
            reduce.delta(before, after, c) for c in COMPILE_COUNTERS), 0]
    correct = bool(records) and all(v == lim for v, lim in compared.values())

    info = rig.info
    device = {
        "platform": info.get("platform", "none"),
        "kind": info.get("device_kind", "none"),
        "count": int(info.get("device_count", 0)),
        "memory_peak_bytes": max((p for p in peaks if p), default=None),
    }
    ctx = {"before": before, "after": after, "client": stats,
           "config": config}
    result: dict = {
        "correct": correct, "attempted": len(records),
        "failed": int((~rec["good"]).sum()),
    }
    if args.trace:
        if traced and not rig.rehearsal:
            result["breakdown"] = read_traced(traced, device, ctx, args.keep)
        wanted = manifest["per_layer"]
    else:
        wanted = manifest["end_to_end"]
    metrics = {}
    for m in wanted:
        if cell["name"] not in (m.get("workloads") or [cell["name"]]):
            continue
        if not args.trace:
            value = setup_s if m["name"] == "setup_s" else stats[m["name"]]
        else:
            value = reduce.read_layer_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device, compared=compared)
    if args.keep:
        keep_json(args.keep, "counters.json", moved_counters(before, after))
    return result


def moved_counters(before: reduce.Samples, after: reduce.Samples) -> dict:
    """Every counter's move over the window but the histograms' buckets,
    for PERF.md; ``before`` is indexed once (a scan a sample took minutes
    over the 25,000 bucket samples of the per-policy histogram)."""
    def key(name: str, labels: dict) -> str:
        return name + "".join(f"[{v}]" for v in labels.values())

    was = {key(name, labels): value for name, samples in before.items()
           for labels, value in samples}
    return {
        key(name, labels): value - was[key(name, labels)]
        for name, samples in after.items() if "_bucket" not in name
        for labels, value in samples
        if value != was.get(key(name, labels), value)}


def keep_json(directory: str, name: str, doc) -> None:
    Path(directory).mkdir(parents=True, exist_ok=True)
    (Path(directory) / name).write_text(
        json.dumps(doc, indent=1), encoding="utf-8")


def read_traced(traced: dict, device: dict, ctx: dict,
                keep: str | None = None) -> dict:
    """Read the traced part of a window: ``device`` gains ``busy_s`` and
    ``window_s``; ``ctx`` the trace, the counters around it, the ring's
    events for the interval and its gaps laid to host phases
    (``host_spans.attribute``, once); → the breakdown, the busiest device's
    gaps named where the program has ``ps:launch`` and ``/debug/timeline``
    and ``reduce.breakdown``'s where it has not."""
    trace = host_spans.load_trace(traced["file"])
    timeline = traced["timeline"]
    spans = host_spans.attribute(trace, timeline)
    busy = reduce.busy_seconds(trace)
    device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
    device["window_s"] = traced_s = traced["done"]["traced_s"]
    ctx.update(trace=trace, traced_s=traced_s,
               trace_before=traced["before"], trace_after=traced["after"],
               peaks=reduce.peaks_of(device["kind"]),
               timeline=timeline, spans=spans)
    if keep:
        keep_json(keep, "timeline.json", timeline)
        keep_json(keep, "spans.json", spans)
    if spans is not None:  # the clock, the link and idle seconds by part
        say("host spans: " + json.dumps(
            {k: v for k, v in spans.items() if k != "gaps"}))
    return host_spans.breakdown(trace, spans)


def await_file(path: Path, timeout: float) -> Path:
    deadline = time.monotonic() + timeout
    while not path.exists():
        if time.monotonic() > deadline:
            raise RunFailure(f"the launcher wrote no {path.name} within "
                             f"{timeout:.0f}s")
        time.sleep(0.02)
    return path


def trace_window(server: Server, control_dir: Path, work: Path, t0: float,
                 seconds: float) -> dict:
    """Have the launcher trace a few seconds in the middle of the window,
    and read the counters at both ends of the traced part."""
    length = min(3.0, seconds / 3.0)
    start = t0 + (seconds - length) / 2.0
    time.sleep(max(0.0, start - time.monotonic()))
    trace_dir = work / "trace"
    (control_dir / "trace.tmp").write_text(
        json.dumps({"dir": str(trace_dir), "seconds": length}),
        encoding="utf-8")
    os.replace(control_dir / "trace.tmp", control_dir / "trace.json")
    started = load_json(await_file(control_dir / "trace.started", 60))
    before = server.metrics()
    time.sleep(max(0.0, started["at"] + length - time.monotonic()))
    after = server.metrics()
    return {"dir": trace_dir, "before": before, "after": after,
            "started_at": started["at"]}


def parser(description: str) -> argparse.ArgumentParser:
    """The arguments of a run; ``sweep.py`` adds its own to them."""
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--platform", choices=["tpu", "cpu"], default="tpu",
                    help="'cpu' is an explicit rehearsal, never a fallback")
    ap.add_argument("--control", default=None, metavar="FAULT",
                    help="put the reference with this guarantee broken in "
                         "the program's place (benchmarks/control_server.py)")
    ap.add_argument("--ready-timeout", type=float, default=900.0)
    ap.add_argument("--warm-passes", type=int, default=8)
    ap.add_argument("--keep", default=None, metavar="DIR",
                    help="keep here the server's and clients' logs, every "
                         "counter's move over the window and, of a traced "
                         "run, the trace, the ring's events and the gaps")
    return ap


def main(argv: list[str] | None = None) -> int:
    args = parser(__doc__.split("\n\n")[0]).parse_args(argv)

    # Belt for "one process per chip": should anything in this process ever
    # reach a JAX backend, it is the CPU one.
    os.environ["JAX_PLATFORMS"] = "cpu"
    work = Path(tempfile.mkdtemp(prefix="benchmark-"))
    rig = None
    try:
        rig = Rig(args, work)
        result = run(args, rig)
    except (RunFailure, check_manifest.ManifestError) as e:
        print(f"[benchmark] FAILED: {e}", file=sys.stderr, flush=True)
        return 3
    finally:
        if rig is not None:
            rig.stop()
        if args.keep:
            keep = Path(args.keep)
            keep.mkdir(parents=True, exist_ok=True)
            for item in ("server.log", "clients.log"):
                if (work / item).exists():
                    shutil.copy(work / item, keep / item)
            for pb in work.glob("trace/**/*.xplane.pb"):
                shutil.copy(pb, keep / pb.name)
        shutil.rmtree(work, ignore_errors=True)
    for name, (value, limit) in result["compared"].items():
        print(f"[benchmark] compared {name}: {value} (limit {limit})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
