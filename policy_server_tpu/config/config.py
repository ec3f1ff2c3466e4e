"""Config: CLI args + env fallbacks → a validated ``Config`` struct.

Reference parity: src/config.rs —
* ``Config::from_args`` (config.rs:61-169): resolves addr/port, TLS, policy
  file paths, download dir, workers/pool_size, timeouts, feature flags.
* ``pool_size = --workers or num_cpus`` (config.rs:85-90).
* ``HOSTNAME`` from env for span fields (config.rs:24-27).
* OTLP client TLS config from OTEL_* env vars (config.rs:458-496).

TPU-native additions (no reference counterpart; SURVEY.md §7):
* ``evaluation_backend``: ``jax`` (batched TPU predicate programs) or
  ``oracle`` (host interpreter; the stand-in for the reference's wasmtime
  path and the differential-testing oracle).
* micro-batcher knobs (``max_batch_size``, ``batch_timeout_ms``) — the
  batched analog of the reference's Semaphore admission control
  (src/api/handlers.rs:256-286).
* device mesh spec (``mesh``) — e.g. ``data:8`` or ``data:4,policy:2`` —
  the scale-out axis that replaces the reference's replica-based scaling
  (SURVEY.md §2.3 last row).
"""

from __future__ import annotations

import os
import socket
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

import yaml

from policy_server_tpu.models.policy import (
    PolicyOrPolicyGroup,
    parse_policies,
)
from policy_server_tpu.config.sources import Sources, read_sources_file
from policy_server_tpu.config.verification import (
    VerificationConfig,
    read_verification_file,
)

LOG_LEVELS = ("trace", "debug", "info", "warn", "error")
LOG_FORMATS = ("text", "json", "otlp")
EVALUATION_BACKENDS = ("jax", "oracle")

DEFAULT_PORT = 3000
DEFAULT_READINESS_PORT = 8081


@dataclass(frozen=True)
class TlsConfig:
    """TLS material paths (src/config.rs TlsConfig; src/certs.rs:31).

    ``cert_file``/``key_file`` must be provided together; ``client_ca_file``
    (a list — multiple CAs supported, certs.rs:231-258) enables mTLS and
    requires TLS to be enabled.
    """

    cert_file: str | None = None
    key_file: str | None = None
    client_ca_file: tuple[str, ...] = ()

    @property
    def enabled(self) -> bool:
        return self.cert_file is not None

    @property
    def mtls_enabled(self) -> bool:
        return bool(self.client_ca_file)

    def validate(self) -> None:
        if (self.cert_file is None) != (self.key_file is None):
            raise ValueError(
                "both --cert-file and --key-file must be provided to enable TLS"
            )
        if self.client_ca_file and not self.enabled:
            raise ValueError("--client-ca-file requires --cert-file and --key-file")


@dataclass(frozen=True)
class MeshSpec:
    """Device-mesh request, e.g. ``data:8`` or ``data:4,policy:2``.

    Axis names: ``data`` shards the request batch dimension; ``policy``
    shards the loaded policy set (verdict bits all-gathered; SURVEY.md §5
    long-context row). ``auto`` sizes the data axis to ``len(jax.devices())``
    at boot.
    """

    axes: tuple[tuple[str, int], ...] = (("data", 0),)  # 0 = auto

    @classmethod
    def parse(cls, spec: str) -> "MeshSpec":
        if spec in ("auto", ""):
            return cls()
        axes: list[tuple[str, int]] = []
        for part in spec.split(","):
            name, _, size = part.partition(":")
            name = name.strip()
            if name not in ("data", "policy"):
                raise ValueError(f"unknown mesh axis {name!r} (expected data/policy)")
            try:
                n = int(size)
            except ValueError:
                raise ValueError(f"invalid mesh axis size in {part!r}") from None
            if n < 1:
                raise ValueError(f"mesh axis size must be >= 1: {part!r}")
            axes.append((name, n))
        if not axes:
            raise ValueError(f"invalid mesh spec {spec!r}")
        names = [a for a, _ in axes]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate mesh axis in {spec!r}")
        return cls(axes=tuple(axes))

    def data_size(self) -> int:
        return dict(self.axes).get("data", 1)

    def policy_size(self) -> int:
        return dict(self.axes).get("policy", 1)


def _default_pool_size() -> int:
    return os.cpu_count() or 1


_SIZE_SUFFIXES = {
    "k": 1024, "ki": 1024, "kb": 1000,
    "m": 1024**2, "mi": 1024**2, "mb": 1000**2,
    "g": 1024**3, "gi": 1024**3, "gb": 1000**3,
}


def parse_size(value) -> int:
    """Byte-size value: a plain integer, or an integer with a K/M/G
    (binary) or KB/MB/GB (decimal) suffix — ``--verdict-cache-size 64M``.
    Case-insensitive; a trailing 'i' (Ki/Mi/Gi) is the same binary unit."""
    if isinstance(value, int):
        return value
    s = str(value).strip().lower()
    for suffix in sorted(_SIZE_SUFFIXES, key=len, reverse=True):
        if s.endswith(suffix):
            return int(float(s[: -len(suffix)]) * _SIZE_SUFFIXES[suffix])
    return int(s)


@dataclass
class Config:
    """The resolved server configuration (reference Config, config.rs:29-52)."""

    addr: str = "0.0.0.0"
    port: int = DEFAULT_PORT
    readiness_probe_port: int = DEFAULT_READINESS_PORT
    tls_config: TlsConfig = field(default_factory=TlsConfig)
    policies: dict[str, PolicyOrPolicyGroup] = field(default_factory=dict)
    policies_download_dir: str = "."
    sources: Sources | None = None
    verification_config: VerificationConfig | None = None
    pool_size: int = field(default_factory=_default_pool_size)
    policy_timeout_seconds: float = 2.0  # cli.rs:164-169 default 2 s
    disable_timeout_protection: bool = False
    ignore_kubernetes_connection_failure: bool = False
    kube_insecure_skip_tls_verify: bool = False
    always_accept_admission_reviews_on_namespace: str | None = None
    continue_on_errors: bool = False
    enable_metrics: bool = False
    enable_pprof: bool = False
    log_level: str = "info"
    log_fmt: str = "text"
    log_no_color: bool = False
    daemon: bool = False
    daemon_pid_file: str = "policy-server.pid"
    daemon_stdout_file: str | None = None
    daemon_stderr_file: str | None = None
    docker_config_json_path: str | None = None
    sigstore_cache_dir: str = "sigstore-data"
    hostname: str = field(default_factory=socket.gethostname)
    # --- TPU-native additions -------------------------------------------
    evaluation_backend: str = "jax"
    max_batch_size: int = 128
    batch_timeout_ms: float = 1.0
    # latency fast-path: micro-batches ≤ this size are answered by the
    # bit-exact host oracle instead of paying a device round-trip
    host_fastpath_threshold: int = 64
    # bit-exact two-tier verdict cache / in-batch row dedup budget in
    # BYTES (round 6: was rows — split between the pre-encode blob tier
    # and the post-encode row tier, evaluation/verdict_cache.py; the CLI
    # accepts K/M/G[i] suffixes via parse_size). 0 disables.
    verdict_cache_size: int = 256 * 1024 * 1024
    # soft per-request latency target (ms) for deadline-aware routing:
    # a batch whose measured device RTT estimate would exceed the oldest
    # request's remaining budget is answered host-side; ≤0 disables
    latency_budget_ms: float = 50.0
    # propagated per-request deadline (the webhook timeoutSeconds model):
    # requests that cannot meet it are shed at admission (429 +
    # Retry-After) and rows already past it are dropped pre-encode;
    # 0 disables deadline propagation and shedding
    request_timeout_ms: float = 10000.0
    # device circuit breaker: N failures within the window trip a shard
    # to the host-oracle fallback; after the cooldown a half-open probe
    # decides recovery
    breaker_failure_threshold: int = 5
    breaker_window_seconds: float = 30.0
    breaker_cooldown_seconds: float = 5.0
    # what to serve while EVERY shard's breaker is tripped:
    # oracle (bit-exact host verdicts) | monitor (accept-all) | reject (503)
    degraded_mode: str = "oracle"
    # columnar device transport (round 12): ship encoded batches as
    # bit-packed / dictionary-narrowed column planes with all-zero
    # columns elided; False restores the row-packed transport
    columnar: bool = True
    # predicate-program optimizer (round 15, ops/optimizer.py):
    # cross-policy CSE + constant folding + dead-field/mask pruning
    # before lowering; False restores the naive per-policy lowering
    predicate_opt: bool = True
    # zero-downtime policy lifecycle (lifecycle.py): 'auto' promotes a
    # canaried candidate epoch automatically, 'manual' stages it for an
    # explicit POST /policies/promote, 'off' restores the frozen-at-boot
    # policy set (no watcher, no admin endpoints, no SIGHUP reload)
    policy_reload_mode: str = "auto"
    # shadow-canary replay budget: ring-buffer capacity of recently
    # served requests (plus one synthetic review per candidate policy)
    reload_canary_requests: int = 64
    # fraction of canary replays allowed to diverge from the host oracle
    # before the candidate epoch is rejected (0.0 = any divergence
    # rejects)
    reload_divergence_threshold: float = 0.0
    # bearer token for POST /policies/reload|promote|rollback on the
    # readiness port; None disables the admin endpoints
    reload_admin_token: str | None = None
    # the on-disk policies file backing hot reload (None when the config
    # was built programmatically — reloads then reuse the in-memory set)
    policies_path: str | None = None
    # multi-tenant serving (round 16, tenancy.py): the tenants manifest
    # path and its parsed form (tenancy.TenantManifest) — each named
    # tenant gets its own policies file, epoch lifecycle, admission
    # quota, deadline class, and breaker/degraded-mode; None keeps the
    # single-tenant topology bit-identical to round 15
    tenants_path: str | None = None
    tenants: Any = None
    # background audit scanner (audit/scanner.py): 'interval' sweeps the
    # dirty set on a cadence AND fully on every epoch promotion,
    # 'on-promote' sweeps fully on epoch flips only, 'off' disables the
    # scanner (the reference's external-companion model)
    audit_mode: str = "off"
    # dirty-sweep cadence for --audit-mode interval
    audit_interval_seconds: float = 30.0
    # rows per best-effort audit-lane batch
    audit_batch_size: int = 256
    # byte budget of the audit snapshot store (LRU-evicted beyond it)
    audit_max_snapshot_bytes: int = 64 * 1024 * 1024
    # optional YAML/JSON resources file seeding the snapshot store at
    # boot (the stand-in for the companion scanner's cluster LIST)
    audit_resources_file: str | None = None
    # record every object served through /validate in the snapshot
    # store (the batcher's dispatch path); off leaves the store to the
    # resources file and the watch feed
    audit_observe_admissions: bool = True
    # live-cluster watch feed (audit/watch_feed.py, round 13): list+watch
    # events populate the audit snapshot store directly, so the scanner
    # audits the LIVE cluster instead of only /validate traffic + a seed
    # file; requires --audit-mode != off
    audit_watch: bool = False
    # apiVersion/Kind list the watch feed follows
    audit_watch_resources: str = (
        "v1/Pod,v1/Namespace,apps/v1/Deployment,apps/v1/ReplicaSet,"
        "apps/v1/StatefulSet,apps/v1/DaemonSet"
    )
    # bounded watch-event queue between the per-kind watcher threads and
    # the snapshot applier; overflow drops the event (counted) and
    # forces a full re-LIST resync of that kind
    audit_watch_max_queue_events: int = 65536
    # persistent (object × policy) verdict matrix (round 23,
    # audit/matrix.py): sweeps evaluate only the dirty cross-product,
    # verdict changes stream on GET /audit/stream, columns spill through
    # the statestore for warm resume, and byte-identical /validate
    # UPDATEs answer from precomputed verdicts; requires the scanner
    audit_matrix: bool = False
    # concurrent GET /audit/stream clients (beyond it: in-band 503)
    audit_stream_max_clients: int = 64
    # matrix spill cadence (scanner-driven, rides the sweep tail)
    audit_matrix_spill_seconds: float = 30.0
    # stretch: evaluate a CANDIDATE epoch's changed columns against the
    # live snapshot during shadow canary and surface the cluster-wide
    # what-if diff on the reload status
    audit_matrix_whatif: bool = False
    # native-frontend connection-abuse hardening (csrc/httpfront.cpp,
    # round 13): idle keep-alive reap, per-request read (arrival)
    # timeout bounding slowloris drips, and the concurrent-connection
    # cap answering an in-band 503 over it (0 disables each)
    native_idle_timeout_seconds: float = 75.0
    native_read_timeout_seconds: float = 30.0
    native_max_connections: int = 0
    # native TLS termination (round 20): 'auto' terminates TLS on the
    # C++ epoll loops when --cert/--key are set and libssl loads
    # (hot-rotation swaps the SSL_CTX for new connections; established
    # ones drain on the old identity), falling back LOUDLY to the
    # aiohttp TLS frontend when libssl is unavailable; 'off' keeps
    # aiohttp terminating TLS even under --frontend native
    native_tls: str = "auto"
    # native TLS handshake-arrival bound: the full handshake must
    # COMPLETE within this window measured from accept — byte drips
    # never refresh it (slowloris at the TLS layer); 0 disables
    native_tls_handshake_timeout_seconds: float = 10.0
    # durable last-good state store (round 17, statestore.py): the
    # crash-tolerance directory holding the content-addressed policy
    # artifact cache, the per-tenant last-good epoch manifests, and the
    # audit snapshot spill — a warm boot loads pinned artifacts with
    # zero network, degrades loudly to last-good when fetch fails, and
    # resumes the audit watch instead of re-LISTing. None = amnesiac
    # restarts (pre-round-17 behavior)
    state_dir: str | None = None
    # audit-spill cadence: how often the watch feed spills its
    # resourceVersion cursors + snapshot inventory to the state dir
    state_audit_spill_seconds: float = 30.0
    # main-process self-heal watchdog (supervision.py): rebuild a wedged
    # batcher dispatch loop / native-frontend drainer instead of serving
    # zombies; the check cadence in seconds (0 disables)
    selfheal_interval_seconds: float = 5.0
    # host-local serving shards (round 22, runtime/shards.py): M full
    # serving stacks (each its own EvaluationEnvironment — verdict cache
    # + breaker — and MicroBatcher) behind a health/queue-depth router;
    # the promoted epoch artifacts and the XLA compilation cache are
    # shared read-only. 1 = the router is BYPASSED entirely and the
    # serving path is byte- and path-identical to previous rounds
    serving_shards: int = 1
    # shard heartbeat cadence: how often the router probes each shard's
    # dispatch loop; a wedged/dead shard is fenced within one interval
    # (queued rows re-routed to a sibling or answered 503+Retry-After)
    # and warm-revived in place without touching its siblings
    shard_heartbeat_seconds: float = 0.5
    # flight recorder (round 18, telemetry/flightrec.py): always-on
    # batch-granular phase timelines + per-phase histograms + tail
    # exemplars at <2% overhead; False disables the recorder AND the
    # GET /debug/timeline surface (the phase histogram family still
    # exports, empty)
    flight_recorder: bool = True
    # preallocated phase-event ring capacity (rounded up to a power of
    # two); at ~17 batch events per batch plus the native frontend's
    # burst events, the default holds the last ~12k batches
    recorder_ring_events: int = 262144
    # fraction of delivered rows that record per-row timeline segments
    # (deterministic 1-in-round(1/rate) stride, no RNG on the serving
    # path); 0 disables row sampling (batch events and exemplars remain)
    recorder_row_sample_rate: float = 0.01
    # prefork respawn breaker: consecutive fast crash-loop deaths after
    # which a worker slot stops respawning (readiness then reports the
    # degraded slot honestly)
    worker_respawn_giveup: int = 5
    mesh: MeshSpec = field(default_factory=MeshSpec)
    # how a >1 policy axis executes (round 14): 'fused' lowers the whole
    # policy set as ONE SPMD program over the (data x policy) mesh —
    # per-shard lax.switch branches + an all-gather collective replace
    # the thread pool's N host-side joins; 'threaded' keeps the legacy
    # thread-per-shard MPMD dispatcher (parallel/policy_sharded.py)
    mesh_dispatch: str = "fused"
    warmup_at_boot: bool = True
    # prefork HTTP frontend (runtime/frontend.py): worker processes
    # sharing the API port via SO_REUSEPORT; 1 = in-process serving
    http_workers: int = 1
    # HTTP framing implementation for the /validate|/validate_raw|/audit
    # POST surface: 'native' serves them from the GIL-free C++ epoll
    # front-end (csrc/httpfront.cpp; falls back to 'python' loudly when
    # the extension cannot build/load), 'python' keeps aiohttp framing —
    # the always-available fallback and differential correctness oracle
    frontend: str = "python"
    # context-aware snapshot freshness (see the staleness contract in
    # context/service.py): watch keeps snapshots event-fresh; the refresh
    # period bounds poll-mode staleness and watch-mode backoff/resync
    context_refresh_seconds: float = 30.0
    context_watch: bool = True
    # multi-host bring-up (SURVEY.md §7.2 step 10): when the coordinator is
    # set, bootstrap calls jax.distributed.initialize before mesh build so
    # the mesh spans every process's devices (ICI in-slice, DCN across)
    distributed_coordinator: str | None = None
    distributed_num_processes: int | None = None
    distributed_process_id: int | None = None

    def validate(self) -> None:
        self.tls_config.validate()
        if self.log_level not in LOG_LEVELS:
            raise ValueError(f"invalid log level {self.log_level!r}")
        if self.log_fmt not in LOG_FORMATS:
            raise ValueError(f"invalid log format {self.log_fmt!r}")
        if self.evaluation_backend not in EVALUATION_BACKENDS:
            raise ValueError(
                f"invalid evaluation backend {self.evaluation_backend!r} "
                f"(expected one of {EVALUATION_BACKENDS})"
            )
        if self.pool_size < 1:
            raise ValueError("--workers must be >= 1")
        if self.max_batch_size < 1:
            raise ValueError("--max-batch-size must be >= 1")
        if 0 < self.verdict_cache_size < 1024 * 1024:
            # round 6 changed --verdict-cache-size from ROWS to BYTES; a
            # pinned pre-round-6 value like 4096 would silently collapse
            # the cache to a couple of entries — warn loudly instead of
            # degrading quietly (a sub-MiB budget is never intentional;
            # use 0 to disable caching outright)
            import logging

            logging.getLogger("kubewarden-policy-server").warning(
                "--verdict-cache-size=%d bytes is below 1 MiB — the flag "
                "changed units from rows to bytes in round 6 (suffixes "
                "accepted: 64M, 256Mi); a value this small effectively "
                "disables cross-batch dedup",
                self.verdict_cache_size,
            )
        if not (0 <= self.port <= 65535) or not (0 <= self.readiness_probe_port <= 65535):
            raise ValueError("ports must be in [0, 65535]")
        if self.context_refresh_seconds <= 0:
            raise ValueError("--context-refresh-seconds must be > 0")
        if self.breaker_failure_threshold < 1:
            raise ValueError("--breaker-failure-threshold must be >= 1")
        if self.breaker_window_seconds <= 0:
            raise ValueError("--breaker-window-seconds must be > 0")
        if self.breaker_cooldown_seconds < 0:
            raise ValueError("--breaker-cooldown-seconds must be >= 0")
        if self.degraded_mode not in ("oracle", "monitor", "reject"):
            raise ValueError(
                f"invalid degraded mode {self.degraded_mode!r} "
                "(expected oracle, monitor, or reject)"
            )
        if self.http_workers < 1:
            raise ValueError("--http-workers must be >= 1")
        if self.frontend not in ("python", "native"):
            raise ValueError(
                f"invalid frontend {self.frontend!r} "
                "(expected python or native)"
            )
        if self.policy_reload_mode not in ("off", "auto", "manual"):
            raise ValueError(
                f"invalid policy reload mode {self.policy_reload_mode!r} "
                "(expected off, auto, or manual)"
            )
        if self.reload_canary_requests < 0:
            raise ValueError("--reload-canary-requests must be >= 0")
        if self.audit_mode not in ("off", "interval", "on-promote"):
            raise ValueError(
                f"invalid audit mode {self.audit_mode!r} "
                "(expected off, interval, or on-promote)"
            )
        if self.audit_interval_seconds <= 0:
            raise ValueError("--audit-interval-seconds must be > 0")
        if self.audit_batch_size < 1:
            raise ValueError("--audit-batch-size must be >= 1")
        if self.audit_max_snapshot_bytes < 0:
            raise ValueError("--audit-max-snapshot-bytes must be >= 0")
        if self.audit_watch:
            if self.audit_mode == "off":
                raise ValueError(
                    "--audit-watch requires the audit scanner "
                    "(--audit-mode interval or on-promote)"
                )
            from policy_server_tpu.audit.watch_feed import (
                parse_watch_resources,
            )

            if not parse_watch_resources(self.audit_watch_resources):
                raise ValueError(
                    "--audit-watch-resources must name at least one "
                    "apiVersion/Kind"
                )
        if self.audit_watch_max_queue_events < 1:
            raise ValueError(
                "--audit-watch-max-queue-events must be >= 1"
            )
        if self.audit_matrix and self.audit_mode == "off":
            raise ValueError(
                "--audit-matrix requires the audit scanner "
                "(--audit-mode interval or on-promote)"
            )
        if self.audit_stream_max_clients < 1:
            raise ValueError("--audit-stream-max-clients must be >= 1")
        if self.audit_matrix_spill_seconds <= 0:
            raise ValueError("--audit-matrix-spill-seconds must be > 0")
        if self.audit_matrix_whatif and not self.audit_matrix:
            raise ValueError(
                "--audit-matrix-whatif requires --audit-matrix"
            )
        if self.state_audit_spill_seconds <= 0:
            raise ValueError("--state-audit-spill-seconds must be > 0")
        if self.selfheal_interval_seconds < 0:
            raise ValueError("--selfheal-interval-seconds must be >= 0")
        if self.serving_shards < 1:
            raise ValueError("--serving-shards must be >= 1")
        if self.shard_heartbeat_seconds <= 0:
            raise ValueError("--shard-heartbeat-seconds must be > 0")
        if self.worker_respawn_giveup < 1:
            raise ValueError("--worker-respawn-giveup must be >= 1")
        if self.native_idle_timeout_seconds < 0:
            raise ValueError("--native-idle-timeout-seconds must be >= 0")
        if self.native_read_timeout_seconds < 0:
            raise ValueError("--native-read-timeout-seconds must be >= 0")
        if self.native_max_connections < 0:
            raise ValueError("--native-max-connections must be >= 0")
        if self.native_tls not in ("auto", "off"):
            raise ValueError(
                f"invalid native TLS mode {self.native_tls!r} "
                "(expected auto or off)"
            )
        if self.native_tls_handshake_timeout_seconds < 0:
            raise ValueError(
                "--native-tls-handshake-timeout-seconds must be >= 0"
            )
        if not (0.0 <= self.reload_divergence_threshold <= 1.0):
            raise ValueError(
                "--reload-divergence-threshold must be in [0, 1]"
            )
        if self.tenants is not None:
            from policy_server_tpu.tenancy import TenantManifest

            if not isinstance(self.tenants, TenantManifest):
                raise ValueError(
                    "config.tenants must be a tenancy.TenantManifest "
                    "(use read_tenants_file)"
                )
        if self.mesh_dispatch not in ("fused", "threaded"):
            raise ValueError(
                f"invalid mesh dispatch {self.mesh_dispatch!r} "
                "(expected 'fused' or 'threaded')"
            )
        if self.distributed_coordinator is None:
            if (
                self.distributed_num_processes is not None
                or self.distributed_process_id is not None
            ):
                raise ValueError(
                    "--distributed-num-processes/--distributed-process-id "
                    "require --distributed-coordinator"
                )
        else:
            if (self.distributed_num_processes is None) != (
                self.distributed_process_id is None
            ):
                raise ValueError(
                    "--distributed-num-processes and --distributed-process-id "
                    "must be set together"
                )
            if (
                self.distributed_num_processes is not None
                and not (
                    0
                    <= self.distributed_process_id
                    < self.distributed_num_processes
                )
            ):
                raise ValueError(
                    "--distributed-process-id must be in "
                    "[0, --distributed-num-processes)"
                )

    @property
    def policy_timeout(self) -> float | None:
        """Effective evaluation deadline in seconds, None when disabled
        (reference: --disable-timeout-protection, cli.rs:164-176)."""
        return None if self.disable_timeout_protection else self.policy_timeout_seconds

    @classmethod
    def from_args(cls, args: Any) -> "Config":
        """Build a Config from a parsed argparse namespace
        (reference Config::from_args, config.rs:61-169)."""
        policies_path = Path(args.policies)
        policies = read_policies_file(policies_path) if policies_path.exists() else {}
        if not policies_path.exists() and not getattr(args, "allow_missing_policies", False):
            raise FileNotFoundError(f"policies file not found: {policies_path}")

        sources = read_sources_file(args.sources_path) if args.sources_path else None
        verification = (
            read_verification_file(args.verification_path)
            if args.verification_path
            else None
        )

        tls = TlsConfig(
            cert_file=args.cert_file,
            key_file=args.key_file,
            client_ca_file=tuple(args.client_ca_file or ()),
        )

        cfg = cls(
            addr=args.addr,
            port=args.port,
            readiness_probe_port=args.readiness_probe_port,
            tls_config=tls,
            policies=policies,
            policies_download_dir=args.policies_download_dir,
            sources=sources,
            verification_config=verification,
            pool_size=args.workers if args.workers else _default_pool_size(),
            policy_timeout_seconds=float(args.policy_timeout),
            disable_timeout_protection=args.disable_timeout_protection,
            ignore_kubernetes_connection_failure=args.ignore_kubernetes_connection_failure,
            kube_insecure_skip_tls_verify=args.kube_insecure_skip_tls_verify,
            always_accept_admission_reviews_on_namespace=(
                args.always_accept_admission_reviews_on_namespace or None
            ),
            continue_on_errors=args.continue_on_errors,
            enable_metrics=args.enable_metrics,
            enable_pprof=args.enable_pprof,
            log_level=args.log_level,
            log_fmt=args.log_fmt,
            log_no_color=args.log_no_color,
            daemon=args.daemon,
            daemon_pid_file=args.daemon_pid_file,
            daemon_stdout_file=args.daemon_stdout_file,
            daemon_stderr_file=args.daemon_stderr_file,
            docker_config_json_path=args.docker_config_json_path,
            sigstore_cache_dir=args.sigstore_cache_dir,
            evaluation_backend=args.evaluation_backend,
            max_batch_size=args.max_batch_size,
            batch_timeout_ms=float(args.batch_timeout_ms),
            host_fastpath_threshold=int(args.host_fastpath_threshold),
            verdict_cache_size=parse_size(args.verdict_cache_size),
            latency_budget_ms=float(args.latency_budget_ms),
            request_timeout_ms=float(args.request_timeout_ms),
            breaker_failure_threshold=int(args.breaker_failure_threshold),
            breaker_window_seconds=float(args.breaker_window_seconds),
            breaker_cooldown_seconds=float(args.breaker_cooldown_seconds),
            columnar=args.columnar == "on",
            predicate_opt=args.predicate_opt == "on",
            degraded_mode=args.degraded_mode,
            policy_reload_mode=args.policy_reload_mode,
            reload_canary_requests=int(args.reload_canary_requests),
            reload_divergence_threshold=float(
                args.reload_divergence_threshold
            ),
            reload_admin_token=args.reload_admin_token or None,
            policies_path=str(policies_path) if policies_path.exists() else None,
            tenants_path=args.tenants or None,
            tenants=_read_tenants(args.tenants),
            audit_mode=args.audit_mode,
            audit_interval_seconds=float(args.audit_interval_seconds),
            audit_batch_size=int(args.audit_batch_size),
            audit_max_snapshot_bytes=parse_size(args.audit_max_snapshot_bytes),
            audit_resources_file=args.audit_resources_file or None,
            audit_observe_admissions=args.audit_observe_admissions == "on",
            audit_watch=args.audit_watch,
            audit_watch_resources=args.audit_watch_resources,
            audit_watch_max_queue_events=int(
                args.audit_watch_max_queue_events
            ),
            audit_matrix=args.audit_matrix,
            audit_stream_max_clients=int(args.audit_stream_max_clients),
            audit_matrix_spill_seconds=float(
                args.audit_matrix_spill_seconds
            ),
            audit_matrix_whatif=args.audit_matrix_whatif,
            native_idle_timeout_seconds=float(
                args.native_idle_timeout_seconds
            ),
            native_read_timeout_seconds=float(
                args.native_read_timeout_seconds
            ),
            native_max_connections=int(args.native_max_connections),
            native_tls=getattr(args, "native_tls", "auto"),
            native_tls_handshake_timeout_seconds=float(
                getattr(args, "native_tls_handshake_timeout_seconds", 10.0)
            ),
            state_dir=args.state_dir or None,
            state_audit_spill_seconds=float(args.state_audit_spill_seconds),
            selfheal_interval_seconds=float(args.selfheal_interval_seconds),
            serving_shards=int(getattr(args, "serving_shards", 1)),
            shard_heartbeat_seconds=float(
                getattr(args, "shard_heartbeat_seconds", 0.5)
            ),
            flight_recorder=args.flight_recorder == "on",
            recorder_ring_events=int(args.recorder_ring_events),
            recorder_row_sample_rate=float(args.recorder_row_sample_rate),
            worker_respawn_giveup=int(args.worker_respawn_giveup),
            mesh=MeshSpec.parse(args.mesh),
            mesh_dispatch=args.mesh_dispatch,
            warmup_at_boot=not args.no_warmup,
            http_workers=int(args.http_workers),
            frontend=args.frontend,
            context_refresh_seconds=float(args.context_refresh_seconds),
            context_watch=not args.context_no_watch,
            distributed_coordinator=args.distributed_coordinator,
            distributed_num_processes=args.distributed_num_processes,
            distributed_process_id=args.distributed_process_id,
        )
        cfg.validate()
        return cfg


def _read_tenants(path: str | None):
    """Parse the --tenants manifest (None passthrough)."""
    if not path:
        return None
    from policy_server_tpu.tenancy import read_tenants_file

    return read_tenants_file(path)


def read_policies_file(path: str | Path) -> dict[str, PolicyOrPolicyGroup]:
    """config.rs:449-453 + parse (config.rs:219-258)."""
    return read_policies_source(path)[0]


def read_policies_source(
    path: str | Path,
) -> tuple[dict[str, PolicyOrPolicyGroup], str]:
    """Read + parse a policies file, returning the parsed mapping AND
    the exact text it was parsed from — the durable-manifest path
    (round 17) persists the bytes that were actually compiled/canaried,
    never a re-read that could have changed underneath the reload."""
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    return parse_policies(yaml.safe_load(text)), text


def build_client_tls_config_from_env(prefix: str = "OTEL_EXPORTER_OTLP") -> dict[str, str]:
    """OTLP exporter TLS settings from env (config.rs:458-496):
    ``{prefix}_CERTIFICATE`` (CA), ``{prefix}_CLIENT_CERTIFICATE``,
    ``{prefix}_CLIENT_KEY``. Either all client vars set or none."""
    ca = os.environ.get(f"{prefix}_CERTIFICATE")
    cert = os.environ.get(f"{prefix}_CLIENT_CERTIFICATE")
    key = os.environ.get(f"{prefix}_CLIENT_KEY")
    out: dict[str, str] = {}
    if ca:
        out["ca_file"] = ca
    if (cert is None) != (key is None):
        raise ValueError(
            f"{prefix}_CLIENT_CERTIFICATE and {prefix}_CLIENT_KEY must be set together"
        )
    if cert and key:
        out["cert_file"] = cert
        out["key_file"] = key
    return out
