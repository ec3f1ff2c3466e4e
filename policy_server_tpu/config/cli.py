"""CLI definition with per-flag ``KUBEWARDEN_*`` env fallbacks.

Reference parity: src/cli.rs — every flag has an env-var fallback
(cli.rs:24-212); ``--long-version`` prints the builtins banner (cli.rs:7-21,
here: the predicate-IR op registry instead of OPA builtins); the ``docs``
subcommand regenerates the markdown CLI reference (src/main.rs:68,
cli-docs.md), and CI can diff it for freshness.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Any, Sequence

from policy_server_tpu.version import __version__

PROG = "policy-server-tpu"


def _env(name: str, default: Any = None) -> Any:
    return os.environ.get(name, default)


def _env_flag(name: str) -> bool:
    v = os.environ.get(name, "")
    return v.lower() in ("1", "true", "yes", "on")


# (flag, env, kwargs) — single source of truth for the parser and for docs.
def _flag_specs() -> list[tuple[str, str | None, dict[str, Any]]]:
    return [
        ("--addr", "KUBEWARDEN_BIND_ADDRESS",
         dict(default="0.0.0.0", metavar="BIND_ADDRESS",
              help="Bind against ADDRESS")),
        ("--port", "KUBEWARDEN_PORT",
         dict(type=int, default=3000, metavar="PORT",
              help="Listen on PORT")),
        ("--readiness-probe-port", "KUBEWARDEN_READINESS_PROBE_PORT",
         dict(type=int, default=8081, metavar="READINESS_PROBE_PORT",
              help="Expose the readiness endpoint on this (plaintext) port")),
        ("--policies", "KUBEWARDEN_POLICIES",
         dict(default="policies.yml", metavar="POLICIES_FILE",
              help="YAML file holding the policies to be loaded and their settings")),
        ("--policies-download-dir", "KUBEWARDEN_POLICIES_DOWNLOAD_DIR",
         dict(default=".", metavar="POLICIES_DOWNLOAD_DIR",
              help="Download path for the policies")),
        ("--sources-path", "KUBEWARDEN_SOURCES_PATH",
         dict(default=None, metavar="SOURCES_PATH",
              help="YAML file holding source information (registries, HTTP, "
                   "insecure sources, authorities)")),
        ("--verification-path", "KUBEWARDEN_VERIFICATION_CONFIG_PATH",
         dict(default=None, metavar="VERIFICATION_CONFIG_PATH",
              help="YAML file holding verification config information "
                   "(signatures, requirements)")),
        ("--sigstore-cache-dir", "KUBEWARDEN_SIGSTORE_CACHE_DIR",
         dict(default="sigstore-data", metavar="SIGSTORE_CACHE_DIR",
              help="Directory used to cache sigstore data")),
        ("--docker-config-json-path", "KUBEWARDEN_DOCKER_CONFIG_JSON_PATH",
         dict(default=None, metavar="DOCKER_CONFIG",
              help="Path to a Docker config.json-like file holding registry "
                   "authentication details")),
        ("--cert-file", "KUBEWARDEN_CERT_FILE",
         dict(default=None, metavar="CERT_FILE",
              help="Path to an X.509 certificate file for HTTPS")),
        ("--key-file", "KUBEWARDEN_KEY_FILE",
         dict(default=None, metavar="KEY_FILE",
              help="Path to an X.509 private key file for HTTPS")),
        ("--client-ca-file", "KUBEWARDEN_CLIENT_CA_FILE",
         dict(default=None, metavar="CLIENT_CA_FILE", action="append",
              help="Path to a CA certificate file that issued the client "
                   "certificates; required to enable mTLS (repeatable)")),
        ("--workers", "KUBEWARDEN_WORKERS",
         dict(type=int, default=None, metavar="WORKERS_NUMBER",
              help="Number of concurrent evaluation slots (default: number of CPUs); "
                   "bounds in-flight micro-batches in the TPU backend")),
        ("--policy-timeout", "KUBEWARDEN_POLICY_TIMEOUT",
         dict(type=float, default=2.0, metavar="MAXIMUM_EXECUTION_TIME_SECONDS",
              help="Interrupt policy evaluation after the given time")),
        ("--request-timeout-ms", "KUBEWARDEN_REQUEST_TIMEOUT_MS",
         dict(type=float, default=10000.0, metavar="MS",
              help="Propagated per-request deadline, aligned to the "
                   "admission webhook timeoutSeconds model (the API server "
                   "abandons a review after its timeout, so work past it is "
                   "waste). Requests whose estimated queue wait exceeds the "
                   "budget are shed at admission with 429 + Retry-After, "
                   "and rows already expired when their batch forms are "
                   "dropped before encode/dispatch. 0 disables deadline "
                   "propagation and load shedding")),
        ("--disable-timeout-protection", "KUBEWARDEN_DISABLE_TIMEOUT_PROTECTION",
         dict(action="store_true", help="Disable policy timeout protection")),
        ("--ignore-kubernetes-connection-failure",
         "KUBEWARDEN_IGNORE_KUBERNETES_CONNECTION_FAILURE",
         dict(action="store_true",
              help="Do not exit with an error if the Kubernetes connection fails; "
                   "context-aware policies will break")),
        ("--kube-insecure-skip-tls-verify",
         "KUBEWARDEN_KUBE_INSECURE_SKIP_TLS_VERIFY",
         dict(action="store_true",
              help="Skip TLS verification of the Kubernetes API server "
                   "(explicit opt-in; without it, a missing cluster CA falls "
                   "back to the system trust store)")),
        ("--always-accept-admission-reviews-on-namespace",
         "KUBEWARDEN_ALWAYS_ACCEPT_ADMISSION_REVIEWS_ON_NAMESPACE",
         dict(default=None, metavar="NAMESPACE",
              help="Always accept AdmissionReviews that target the given namespace")),
        ("--continue-on-errors", "KUBEWARDEN_CONTINUE_ON_ERRORS",
         dict(action="store_true", help=argparse.SUPPRESS)),  # hidden (cli.rs:207-211)
        ("--enable-metrics", "KUBEWARDEN_ENABLE_METRICS",
         dict(action="store_true", help="Enable OTLP metrics")),
        ("--enable-pprof", "KUBEWARDEN_ENABLE_PPROF",
         dict(action="store_true",
              help="Enable profiling endpoints: GET /debug/pprof/cpu and "
                   "/debug/pprof/heap on the python-frontend API port, and "
                   "GET /debug/pprof/trace?seconds=N (default 3, at most "
                   "60) there and on the readiness port: a jax.profiler "
                   "trace of the serving process as an .xplane.pb, one at "
                   "a time. Each launch of the fused program is in it as "
                   "a 'ps:launch' host event carrying its flight-recorder "
                   "batch id and a perf_counter_ns reading, the clock of "
                   "/debug/timeline, so the two line up")),
        ("--log-level", "KUBEWARDEN_LOG_LEVEL",
         dict(default="info", metavar="LOG_LEVEL",
              choices=["trace", "debug", "info", "warn", "error"],
              help="Log level (trace, debug, info, warn, error)")),
        ("--log-fmt", "KUBEWARDEN_LOG_FMT",
         dict(default="text", metavar="LOG_FMT", choices=["text", "json", "otlp"],
              help="Log output format (text, json, otlp)")),
        ("--log-no-color", "KUBEWARDEN_LOG_NO_COLOR",
         dict(action="store_true", help="Disable colored output for logs")),
        ("--daemon", "KUBEWARDEN_DAEMON",
         dict(action="store_true",
              help="If set, runs policy-server in detached mode as a daemon")),
        ("--daemon-pid-file", "KUBEWARDEN_DAEMON_PID_FILE",
         dict(default="policy-server.pid", metavar="DAEMON-PID-FILE",
              help="Path to the PID file, used only when running in daemon mode")),
        ("--daemon-stdout-file", "KUBEWARDEN_DAEMON_STDOUT_FILE",
         dict(default=None, metavar="DAEMON-STDOUT-FILE",
              help="Path to the file holding stdout, used only in daemon mode")),
        ("--daemon-stderr-file", "KUBEWARDEN_DAEMON_STDERR_FILE",
         dict(default=None, metavar="DAEMON-STDERR-FILE",
              help="Path to the file holding stderr, used only in daemon mode")),
        # --- TPU-native flags (no reference counterpart; SURVEY.md §7) ----
        ("--evaluation-backend", "KUBEWARDEN_EVALUATION_BACKEND",
         dict(default="jax", metavar="BACKEND", choices=["jax", "oracle"],
              help="Evaluation backend: 'jax' (batched TPU predicate programs) "
                   "or 'oracle' (host interpreter, the differential-test oracle)")),
        ("--max-batch-size", "KUBEWARDEN_MAX_BATCH_SIZE",
         dict(type=int, default=128, metavar="N",
              help="Maximum micro-batch size dispatched to the device")),
        ("--batch-timeout-ms", "KUBEWARDEN_BATCH_TIMEOUT_MS",
         dict(type=float, default=1.0, metavar="MS",
              help="Maximum time a request waits for its micro-batch to fill")),
        ("--host-fastpath-threshold", "KUBEWARDEN_HOST_FASTPATH_THRESHOLD",
         dict(type=int, default=64, metavar="N",
              help="Micro-batches with at most N requests, and only while "
                   "the batch pipeline has a free slot, are answered by "
                   "the bit-exact host oracle instead of a device dispatch "
                   "(latency fast-path; 0 disables). A batch that small "
                   "which waited for a pipeline slot or took the last one "
                   "is throughput traffic: it rides the device and counts "
                   "in policy_server_host_fastpath_declined_batches. The "
                   "verdict cache is "
                   "asked first there too, and every answer is counted by "
                   "one source: a cache hit by the cache's own hit counter, "
                   "a request the oracle evaluated by "
                   "policy_server_host_fastpath_requests")),
        ("--latency-budget-ms", "KUBEWARDEN_LATENCY_BUDGET_MS",
         dict(type=float, default=50.0, metavar="MS",
              help="Soft per-request latency target for deadline-aware "
                   "routing: when the measured device round-trip estimate "
                   "would exceed the oldest queued request's remaining "
                   "budget, the batch is answered by the bit-exact host "
                   "oracle instead (0 disables; distinct from "
                   "--policy-timeout, the hard in-band deadline). Such a "
                   "batch counts in policy_server_budget_routed_batches, "
                   "its requests as the host fast path's are counted")),
        ("--columnar", "KUBEWARDEN_COLUMNAR",
         dict(default="on", metavar="MODE", choices=["on", "off"],
              help="Columnar device transport (round 12): ship encoded "
                   "batches as bit-packed / dictionary-narrowed column "
                   "PLANES with all-zero columns elided (steady-state "
                   "traffic ships only delta columns; elided planes are "
                   "reconstructed from device-resident zero constants). "
                   "A launch ships one packed wire buffer; the column "
                   "indices stay on the device. 'off' restores the "
                   "row-packed transport. Multi-process meshes always "
                   "use the packed transport")),
        ("--predicate-opt", "KUBEWARDEN_PREDICATE_OPT",
         dict(default="on", metavar="MODE", choices=["on", "off"],
              help="Predicate-program optimizer (round 15): before "
                   "lowering, run cross-policy common-subexpression "
                   "elimination (identical field-gather + comparison "
                   "subtrees compute once via a shared let-binding "
                   "table), constant folding (whole policies folding to "
                   "a constant verdict drop out of the device program), "
                   "and dead-field pruning (fields no surviving "
                   "predicate reads lose their gather columns; validity "
                   "masks provably redundant at the zero-fill lose "
                   "their mask lanes). Purely structural — bit-exact vs "
                   "the unoptimized program and the host oracle. 'off' "
                   "restores the naive per-policy lowering")),
        ("--breaker-failure-threshold", "KUBEWARDEN_BREAKER_FAILURE_THRESHOLD",
         dict(type=int, default=5, metavar="N",
              help="Device circuit breaker: dispatch faults / watchdog "
                   "trips within the window that trip a shard OPEN (its "
                   "traffic then serves from the bit-exact host oracle "
                   "until a half-open probe succeeds)")),
        ("--breaker-window-seconds", "KUBEWARDEN_BREAKER_WINDOW_SECONDS",
         dict(type=float, default=30.0, metavar="SECONDS",
              help="Device circuit breaker: sliding window over which "
                   "failures accumulate toward the trip threshold")),
        ("--breaker-cooldown-seconds", "KUBEWARDEN_BREAKER_COOLDOWN_SECONDS",
         dict(type=float, default=5.0, metavar="SECONDS",
              help="Device circuit breaker: time a tripped shard stays "
                   "OPEN before a half-open recovery probe is admitted")),
        ("--degraded-mode", "KUBEWARDEN_DEGRADED_MODE",
         dict(default="oracle", metavar="MODE",
              choices=["oracle", "monitor", "reject"],
              help="What to serve while EVERY device shard's breaker is "
                   "tripped: 'oracle' keeps serving bit-exact host-oracle "
                   "verdicts (default), 'monitor' serves accept-all "
                   "monitor-mode verdicts (fail-open), 'reject' answers "
                   "in-band 503s (fail-closed)")),
        ("--verdict-cache-size", "KUBEWARDEN_VERDICT_CACHE_SIZE",
         dict(default="256Mi", metavar="BYTES",
              help="Byte budget of the bit-exact two-tier verdict cache "
                   "(accepts K/M/G[i] suffixes; was rows before round 6). "
                   "Split between a pre-encode blob tier (exact payload "
                   "replays skip encoding) and a post-encode row tier "
                   "(uid/name-varying duplicates collapse after encode): "
                   "identical (policy, payload) rows are answered without "
                   "re-dispatch (policy evaluation is a pure function of "
                   "the payload, so this is lossless; wasm-backed verdicts "
                   "are never cached). An entry is one (policy, payload) "
                   "or (policy, encoded row) key over the packed output row "
                   "the device returned, accounted at 256 bytes + key + row: "
                   "~1.4 KB with a 1 KB key, so each tier's half of the "
                   "default 256Mi holds ~95,000 entries "
                   "(policy_server_verdict_cache_put_bytes_total over "
                   "policy_server_verdict_cache_puts_total is the measured "
                   "size). Size it to hold the live (policy, admission "
                   "template) pairs; a tier that outgrows its half "
                   "evicts oldest-first, counted by "
                   "policy_server_verdict_cache_evictions_total{tier}. 0 "
                   "disables caching AND in-batch row dedup")),
        ("--policy-reload-mode", "KUBEWARDEN_POLICY_RELOAD_MODE",
         dict(default="auto", metavar="MODE",
              choices=["off", "auto", "manual"],
              help="Zero-downtime policy hot reload (epoch-based, "
                   "lifecycle.py): 'auto' fetches+compiles+warms a new "
                   "policy set in the background on SIGHUP / policies-file "
                   "change / POST /policies/reload, shadow-canaries it "
                   "against the host oracle, and promotes atomically only "
                   "on a clean canary (last-good keeps serving otherwise); "
                   "'manual' stages the validated candidate for an "
                   "explicit POST /policies/promote; 'off' freezes the "
                   "policy set at boot (pre-round-9 behavior)")),
        ("--reload-canary-requests", "KUBEWARDEN_RELOAD_CANARY_REQUESTS",
         dict(type=int, default=64, metavar="N",
              help="Shadow-canary replay budget: the candidate epoch "
                   "replays up to N recently served requests (a bounded "
                   "ring recorded at dispatch, plus one synthetic review "
                   "per candidate policy) and cross-checks every verdict "
                   "against the host oracle before promotion")),
        ("--reload-divergence-threshold",
         "KUBEWARDEN_RELOAD_DIVERGENCE_THRESHOLD",
         dict(type=float, default=0.0, metavar="FRACTION",
              help="Fraction of canary replays allowed to diverge from "
                   "the host oracle before the candidate policy set is "
                   "rejected (default 0.0: any divergence, trap, or "
                   "canary timeout keeps last-good serving and increments "
                   "the rollback counter)")),
        ("--audit-mode", "KUBEWARDEN_AUDIT_MODE",
         dict(default="off", metavar="MODE",
              choices=["off", "interval", "on-promote"],
              help="Background audit scanner (audit/scanner.py): "
                   "continuously re-scans a snapshot of cluster resources "
                   "(seeded from --audit-resources-file and from every "
                   "object served through /validate) through the live "
                   "policy epoch on the micro-batcher's best-effort lane: "
                   "an audit batch goes out only when the live queue came "
                   "up empty, one at a time, in live-sized slices; a batch "
                   "already out is not recalled, and it shares the "
                   "interpreter with the live threads. Not free: see "
                   "PERF.md (on one v5e chip, the flagship set: about two "
                   "fifths of a 32-caller closed loop's throughput, about a "
                   "tenth at saturation). "
                   "'interval' sweeps the dirty set on a cadence and "
                   "fully on every policy-epoch promotion; 'on-promote' "
                   "sweeps fully on epoch flips only; 'off' disables the "
                   "scanner and the GET /audit/reports endpoints")),
        ("--audit-interval-seconds", "KUBEWARDEN_AUDIT_INTERVAL_SECONDS",
         dict(type=float, default=30.0, metavar="SECONDS",
              help="Dirty-set sweep cadence for --audit-mode interval "
                   "(objects served through /validate since the last "
                   "sweep are re-judged)")),
        ("--audit-batch-size", "KUBEWARDEN_AUDIT_BATCH_SIZE",
         dict(type=int, default=256, metavar="N",
              help="Rows per best-effort audit-lane batch (at most one "
                   "audit dispatch is ever in flight; the environment "
                   "takes it in slices of at most --max-batch-size, so no "
                   "value compiles a program warm-up did not)")),
        ("--audit-max-snapshot-bytes", "KUBEWARDEN_AUDIT_MAX_SNAPSHOT_BYTES",
         dict(default="64Mi", metavar="BYTES",
              help="Byte budget of the audit snapshot store holding "
                   "cluster resources as pre-encoded admission rows "
                   "(accepts K/M/G[i] suffixes; least-recently-recorded "
                   "rows evict beyond it). It bounds coverage too: the "
                   "reports list resident resources only, one evicted "
                   "loses its report rows, and one evicted before a "
                   "sweep had judged it counts under "
                   "policy_server_audit_objects_unjudged_total — size "
                   "the budget to the cluster (64Mi holds ~76,000 pods), "
                   "not to the admission rate")),
        ("--audit-resources-file", "KUBEWARDEN_AUDIT_RESOURCES_FILE",
         dict(default=None, metavar="RESOURCES_FILE",
              help="YAML/JSON file of Kubernetes objects (a list or a "
                   "List document) seeding the audit snapshot store at "
                   "boot — the stand-in for the companion audit "
                   "scanner's cluster LIST")),
        ("--audit-observe-admissions",
         "KUBEWARDEN_AUDIT_OBSERVE_ADMISSIONS",
         dict(default="on", metavar="MODE", choices=["on", "off"],
              help="Record every object served through /validate in the "
                   "audit snapshot store, on the dispatch path of its "
                   "batch (~10 us a request on one v5e chip's host; "
                   "policy_server_audit_observe_seconds_total). 'off' "
                   "leaves the store to --audit-resources-file and "
                   "--audit-watch, as the reference's companion scanner "
                   "lists the cluster and never sees an admission, and "
                   "the live path pays nothing. A deployment whose only "
                   "source is its admissions names 'on': a build "
                   "without this flag (its native front-end fed the "
                   "store nothing) then refuses the command line "
                   "instead of sweeping an empty store")),
        ("--audit-watch", "KUBEWARDEN_AUDIT_WATCH",
         dict(action="store_true",
              help="Feed the audit snapshot store from the Kubernetes "
                   "list+watch stream (audit/watch_feed.py): "
                   "ADDED/MODIFIED events supersede, DELETED evicts and "
                   "prunes report rows, and the scanner then audits the "
                   "LIVE cluster instead of only /validate traffic and "
                   "the seed file. Streams resume from the last "
                   "resourceVersion on clean close; faults and "
                   "queue overflows force a counted full re-LIST "
                   "resync. Requires --audit-mode interval|on-promote")),
        ("--audit-watch-resources", "KUBEWARDEN_AUDIT_WATCH_RESOURCES",
         dict(default="v1/Pod,v1/Namespace,apps/v1/Deployment,"
                      "apps/v1/ReplicaSet,apps/v1/StatefulSet,"
                      "apps/v1/DaemonSet",
              metavar="KINDS",
              help="Comma-separated apiVersion/Kind list the audit "
                   "watch feed follows (e.g. 'v1/Pod,apps/v1/"
                   "Deployment')")),
        ("--audit-watch-max-queue-events",
         "KUBEWARDEN_AUDIT_WATCH_MAX_QUEUE_EVENTS",
         dict(type=int, default=65536, metavar="N",
              help="Bound of the watch-event queue between the per-kind "
                   "watcher threads and the snapshot applier; an "
                   "overflow drops the event (counted loudly) and "
                   "forces a full re-LIST resync of that kind, so a "
                   "drop can delay freshness but never corrupt the "
                   "inventory")),
        ("--audit-matrix", "KUBEWARDEN_AUDIT_MATRIX",
         dict(action="store_true",
              help="Maintain the persistent (object × policy) verdict "
                   "matrix (audit/matrix.py): sweeps evaluate only the "
                   "dirty cross-product (dirty rows × all columns + "
                   "clean rows × dirty columns — a promotion changing 2 "
                   "of 32 policies re-judges 2 columns, not the "
                   "cluster), verdict changes stream on GET "
                   "/audit/stream with a monotonic matrixVersion "
                   "cursor, columns spill through --state-dir for warm "
                   "resume, and a /validate UPDATE byte-identical to a "
                   "judged row answers from the precomputed verdict. "
                   "Requires --audit-mode interval|on-promote")),
        ("--audit-stream-max-clients",
         "KUBEWARDEN_AUDIT_STREAM_MAX_CLIENTS",
         dict(type=int, default=64, metavar="N",
              help="Cap on concurrent GET /audit/stream clients; beyond "
                   "it new subscribers get an in-band 503 (each client "
                   "holds a bounded changelog queue — a slow consumer "
                   "overflows its own queue and is dropped with a "
                   "counted close, never blocking the applier)")),
        ("--audit-matrix-spill-seconds",
         "KUBEWARDEN_AUDIT_MATRIX_SPILL_SECONDS",
         dict(type=float, default=30.0, metavar="SECONDS",
              help="Verdict-matrix spill cadence: how often the scanner "
                   "spills the matrix columns (epoch-fingerprint-keyed) "
                   "to --state-dir so a warm restart resumes compliance "
                   "without re-judging clean rows")),
        ("--audit-matrix-whatif", "KUBEWARDEN_AUDIT_MATRIX_WHATIF",
         dict(action="store_true",
              help="During a reload's shadow canary, also evaluate the "
                   "CANDIDATE epoch's changed columns against the live "
                   "audit snapshot and surface the cluster-wide what-if "
                   "verdict diff on the reload status — canarying over "
                   "the whole cluster, not just the request ring. "
                   "Requires --audit-matrix")),
        ("--native-idle-timeout-seconds",
         "KUBEWARDEN_NATIVE_IDLE_TIMEOUT_SECONDS",
         dict(type=float, default=75.0, metavar="SECONDS",
              help="Native frontend: close keep-alive connections idle "
                   "longer than this between requests (aiohttp "
                   "keepalive parity; 0 disables)")),
        ("--native-read-timeout-seconds",
         "KUBEWARDEN_NATIVE_READ_TIMEOUT_SECONDS",
         dict(type=float, default=30.0, metavar="SECONDS",
              help="Native frontend: a single request (header+body) "
                   "must ARRIVE in full within this bound or the "
                   "connection is closed — the slowloris defense "
                   "(drips refresh byte activity but never complete "
                   "the request; 0 disables)")),
        ("--native-max-connections", "KUBEWARDEN_NATIVE_MAX_CONNECTIONS",
         dict(type=int, default=0, metavar="N",
              help="Native frontend: cap on concurrent connections; "
                   "accepts over it answer an in-band 503 + "
                   "Retry-After and close (counted; 0 = uncapped)")),
        ("--native-tls", "KUBEWARDEN_NATIVE_TLS",
         dict(default="auto", metavar="MODE", choices=["auto", "off"],
              help="Native frontend TLS termination: 'auto' terminates "
                   "TLS on the C++ epoll loops when --cert/--key are "
                   "set and libssl loads — SIGHUP/digest hot-rotation "
                   "atomically swaps the SSL_CTX for NEW connections "
                   "while established ones drain on the old identity, "
                   "and a failed reload keeps last-good serving; when "
                   "libssl is missing the server falls back LOUDLY to "
                   "the aiohttp TLS frontend. 'off' keeps aiohttp "
                   "terminating TLS even under --frontend native")),
        ("--native-tls-handshake-timeout-seconds",
         "KUBEWARDEN_NATIVE_TLS_HANDSHAKE_TIMEOUT_SECONDS",
         dict(type=float, default=10.0, metavar="SECONDS",
              help="Native TLS: the full handshake must COMPLETE "
                   "within this window measured from accept — byte "
                   "drips never refresh it, so a TLS-layer slowloris "
                   "is reaped on schedule (0 disables)")),
        ("--tenants", "KUBEWARDEN_TENANTS",
         dict(default=None, metavar="TENANTS_FILE",
              help="Multi-tenant serving (round 16, tenancy.py): a YAML "
                   "manifest mapping tenant names to their own policies "
                   "files plus per-tenant knobs — weight (weighted-fair "
                   "dispatch share), quota-rows-per-second + quota-burst "
                   "(token-bucket admission; overflow answers 429 + "
                   "Retry-After), max-inflight (admitted-unresolved row "
                   "cap), request-timeout-ms (per-tenant deadline "
                   "class), and degraded-mode (per-tenant breaker "
                   "fallback). Each named tenant owns an independent "
                   "epoch lifecycle (reload/canary/rollback/digest "
                   "watch) over its policies file and is served at "
                   "POST /validate/{tenant}/{policy_id} (plus the "
                   "audit/raw variants and GET /readiness/{tenant}); "
                   "every un-prefixed URL stays the reserved 'default' "
                   "tenant, configured by --policies as before. A "
                   "top-level 'default:' entry applies quota/weight "
                   "knobs to the default tenant; "
                   "'max-concurrent-dispatches' caps the shared "
                   "weighted-fair dispatch scheduler. Unset = "
                   "single-tenant, bit-identical to the pre-tenancy "
                   "serving path")),
        ("--reload-admin-token", "KUBEWARDEN_RELOAD_ADMIN_TOKEN",
         dict(default=None, metavar="TOKEN",
              help="Bearer token authenticating the policy-lifecycle "
                   "admin endpoints (POST /policies/reload, /policies/"
                   "promote, /policies/rollback on the readiness port); "
                   "unset disables them")),
        ("--state-dir", "KUBEWARDEN_STATE_DIR",
         dict(default=None, metavar="DIR",
              help="Durable last-good state directory (round 17, "
                   "statestore.py): a crash-consistent store (atomic "
                   "tmp+fsync+rename writes, CRC-framed generation-"
                   "numbered journals) holding (a) a content-addressed "
                   "policy artifact cache shared by boot and hot-reload "
                   "fetch, (b) per-tenant last-good epoch manifests "
                   "persisted on every promotion/rollback so the "
                   "rollback pin survives restarts, and (c) the audit "
                   "snapshot spill (resourceVersion cursors + "
                   "inventory) so the watch feed RESUMES instead of "
                   "re-LISTing the cluster. A warm boot whose policies "
                   "config matches the last-good manifest loads pinned "
                   "artifacts from the cache with ZERO network fetches; "
                   "a failed fetch degrades loudly to last-good instead "
                   "of fail-closing. Corrupt or torn entries are "
                   "quarantined by the boot fsck pass, never fatal. "
                   "Compiled programs survive in the persistent XLA "
                   "cache (JAX_COMPILATION_CACHE_DIR, else "
                   "<checkout>/.jax_cache). Unset = amnesiac "
                   "restarts (every boot refetches and re-LISTs)")),
        ("--state-audit-spill-seconds", "KUBEWARDEN_STATE_AUDIT_SPILL_SECONDS",
         dict(type=float, default=30.0, metavar="SECONDS",
              help="Cadence of the audit snapshot spill into the state "
                   "dir (one atomic journal replace per tick; also "
                   "spilled on clean shutdown). Only with --state-dir "
                   "and --audit-watch")),
        ("--flight-recorder", "KUBEWARDEN_FLIGHT_RECORDER",
         dict(default="on", metavar="MODE", choices=["on", "off"],
              help="Always-on flight recorder (round 18, telemetry/"
                   "flightrec.py): a lock-free per-process ring of "
                   "nanosecond-stamped phase events covering the full "
                   "request lifecycle — native accept/parse/ring-cross "
                   "(stamped in the C++ frontend and carried across the "
                   "SPSC ring), batcher admission/queue-wait/formation, "
                   "encode, dispatch, device execute, fetch, deliver, "
                   "native verdict serialize, and the CPython collector's "
                   "full passes — at <2% overhead (one "
                   "clock read per phase boundary per BATCH; per-row "
                   "events only on sampled rows). Read surfaces: GET "
                   "/debug/timeline (Chrome/Perfetto trace JSON, on the "
                   "readiness port and the python-frontend API port; "
                   "?since_ns=&until_ns= on CLOCK_MONOTONIC keep only "
                   "the events that overlap that interval), "
                   "per-phase latency histograms + tail exemplars on "
                   "/metrics and OTLP, and the phase-attribution report "
                   "(make phase-report). 'off' disables the recorder "
                   "and the timeline endpoint")),
        ("--recorder-ring-events", "KUBEWARDEN_RECORDER_RING_EVENTS",
         dict(type=int, default=262144, metavar="N",
              help="Flight-recorder ring capacity in events (rounded up "
                   "to a power of two; ~17 batch events per dispatched "
                   "batch, so the default holds the last ~12k batches; "
                   "older events are overwritten, never blocked on)")),
        ("--recorder-row-sample-rate", "KUBEWARDEN_RECORDER_ROW_SAMPLE_RATE",
         dict(type=float, default=0.01, metavar="FRACTION",
              help="Fraction of delivered rows that record per-row "
                   "timeline segments on the flight recorder "
                   "(deterministic 1-in-round(1/FRACTION) stride — no "
                   "RNG on the serving path; 0 disables row sampling "
                   "while batch events and tail exemplars remain)")),
        ("--selfheal-interval-seconds", "KUBEWARDEN_SELFHEAL_INTERVAL_SECONDS",
         dict(type=float, default=5.0, metavar="SECONDS",
              help="Main-process self-heal watchdog cadence "
                   "(supervision.py): every tick it verifies the "
                   "batcher dispatch loops (every tenant's) and the "
                   "native frontend's drainer thread are alive, and "
                   "REBUILDS a wedged one instead of serving zombies "
                   "(counted on /metrics as "
                   "policy_server_selfheal_*_revives). 0 disables")),
        ("--serving-shards", "KUBEWARDEN_SERVING_SHARDS",
         dict(type=int, default=1, metavar="M",
              help="Host-local serving shards (runtime/shards.py): M "
                   "full serving stacks — each with its own evaluation "
                   "environment (verdict cache + breaker) and "
                   "micro-batcher, sharing the promoted epoch artifacts "
                   "and the XLA compilation cache read-only — behind a "
                   "health + queue-depth-EWMA router. A shard whose "
                   "dispatch loop wedges or dies is fenced within one "
                   "heartbeat interval (queued rows re-routed to a "
                   "sibling or answered 503 with Retry-After, never "
                   "double-answered) and warm-revived in place without "
                   "touching its siblings; SIGTERM drains shards in "
                   "sequence. 1 bypasses the router entirely — the "
                   "serving path is byte-identical to a routerless "
                   "build")),
        ("--shard-heartbeat-seconds", "KUBEWARDEN_SHARD_HEARTBEAT_SECONDS",
         dict(type=float, default=0.5, metavar="SECONDS",
              help="Shard router heartbeat cadence: each tick probes "
                   "every shard's dispatch loop, fences a wedged/dead "
                   "shard (draining its queued rows to the healthiest "
                   "sibling), and warm-revives it. Bounds the fencing "
                   "latency after a shard death. Ignored when "
                   "--serving-shards is 1")),
        ("--worker-respawn-giveup", "KUBEWARDEN_WORKER_RESPAWN_GIVEUP",
         dict(type=int, default=5, metavar="N",
              help="Prefork respawn breaker: a frontend worker slot "
                   "that crash-loops N consecutive times within the "
                   "crash window stops respawning (exponential backoff "
                   "applies before the cap); the remaining processes "
                   "keep serving and /readiness reports the degraded "
                   "slot honestly")),
        ("--mesh", "KUBEWARDEN_MESH",
         dict(default="auto", metavar="MESH_SPEC",
              help="Device mesh spec, e.g. 'auto', 'data:8', 'data:4,policy:2'")),
        ("--mesh-dispatch", "KUBEWARDEN_MESH_DISPATCH",
         dict(default="fused", metavar="MODE", choices=["fused", "threaded"],
              help="How a >1 policy axis executes (round 14): 'fused' "
                   "lowers the whole policy set as ONE SPMD program over "
                   "the (data x policy) mesh — each policy shard is a "
                   "lax.switch branch selected by its mesh position, "
                   "verdict blocks meet in an all-gather collective, and "
                   "XLA overlaps the cross-shard work (one device program "
                   "per batch); 'threaded' keeps the legacy "
                   "thread-per-shard MPMD dispatcher (one program per "
                   "policy shard, host-side thread joins) as a fallback")),
        ("--no-warmup", "KUBEWARDEN_NO_WARMUP",
         dict(action="store_true",
              help="Skip AOT compilation of the policy program at boot")),
        ("--http-workers", "KUBEWARDEN_HTTP_WORKERS",
         dict(type=int, default=1, metavar="N",
              help="HTTP frontend processes sharing the API port via "
                   "SO_REUSEPORT, forwarding to the evaluation process "
                   "over a unix socket (1 = serve in-process; raises the "
                   "~1.3k req/s per-event-loop framing ceiling)")),
        ("--frontend", "KUBEWARDEN_FRONTEND",
         dict(default="python", metavar="IMPL", choices=["python", "native"],
              help="HTTP framing implementation for the evaluation POST "
                   "surface (/validate, /validate_raw, /audit): 'native' "
                   "serves it from the GIL-free C++ epoll front-end "
                   "(csrc/httpfront.cpp) that parses AdmissionReviews "
                   "straight into packed batch rows and serializes "
                   "verdicts natively — breaking the ~1.3k rps/process "
                   "Python framing ceiling; 'python' keeps "
                   "aiohttp framing, the differential correctness oracle. "
                   "With 'native', the "
                   "API port serves ONLY the evaluation POSTs — "
                   "/audit/reports, /metrics, and the /policies/* admin "
                   "surface stay on the readiness port, and the pprof "
                   "endpoints require --frontend python; a native library "
                   "that fails to build or load is a boot error, never a "
                   "quiet Python front-end. Under --http-workers, the "
                   "policy_server_native_* /metrics families count the "
                   "main process's loop only (worker processes export "
                   "no metrics, matching the python prefork mode)")),
        ("--context-refresh-seconds", "KUBEWARDEN_CONTEXT_REFRESH_SECONDS",
         dict(type=float, default=30.0, metavar="SECONDS",
              help="Context-aware snapshot freshness: the re-LIST period in "
                   "poll mode; in watch mode (snapshots are event-fresh) "
                   "the error-backoff cap, with a full re-LIST resync every "
                   "10x this value (staleness contract: context/service.py)")),
        ("--context-no-watch", "KUBEWARDEN_CONTEXT_NO_WATCH",
         dict(action="store_true",
              help="Disable the Kubernetes watch stream for context-aware "
                   "snapshots and poll with periodic LISTs instead")),
        ("--distributed-coordinator", "KUBEWARDEN_DISTRIBUTED_COORDINATOR",
         dict(default=None, metavar="HOST:PORT",
              help="jax.distributed coordinator address for multi-host "
                   "serving; when set, bootstrap initializes the DCN "
                   "process group before building the device mesh "
                   "(SURVEY.md §7.2 step 10)")),
        ("--distributed-num-processes", "KUBEWARDEN_DISTRIBUTED_NUM_PROCESSES",
         dict(type=int, default=None, metavar="N",
              help="Total number of policy-server processes in the "
                   "multi-host group (requires --distributed-coordinator)")),
        ("--distributed-process-id", "KUBEWARDEN_DISTRIBUTED_PROCESS_ID",
         dict(type=int, default=None, metavar="ID",
              help="This process's rank in the multi-host group "
                   "(requires --distributed-coordinator)")),
    ]


def long_version() -> str:
    """``--long-version`` banner: version + the predicate-IR op registry +
    the OPA builtins host registry (reference prints the burrego builtins,
    cli.rs:7-21)."""
    from policy_server_tpu.ops.ir import registered_op_names
    from policy_server_tpu.wasm.builtins import get_builtins

    ops = "\n".join(f"  - {name}" for name in registered_op_names())
    builtins = "\n".join(f"  - {name}" for name in sorted(get_builtins()))
    return (
        f"{PROG} {__version__}\npredicate IR ops:\n{ops}\n\n"
        f"Open Policy Agent/Gatekeeper implemented builtins:\n{builtins}"
    )


def build_cli() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog=PROG,
        description=(
            "TPU-native Kubernetes admission policy server: micro-batched "
            "JAX/XLA policy evaluation with the capability surface of "
            "Kubewarden's policy-server."
        ),
    )
    parser.add_argument("--version", action="version", version=f"{PROG} {__version__}")
    parser.add_argument(
        "--long-version",
        action="store_true",
        help="Print version information and the predicate-IR op registry",
    )
    for flag, env, kwargs in _flag_specs():
        kwargs = dict(kwargs)
        if env is not None:
            if kwargs.get("action") == "store_true":
                kwargs["default"] = _env_flag(env)
            elif kwargs.get("action") == "append":
                env_val = _env(env)
                if env_val is not None:
                    kwargs["default"] = env_val.split(",")
            else:
                env_val = _env(env)
                if env_val is not None:
                    t = kwargs.get("type", str)
                    kwargs["default"] = t(env_val)
            if kwargs.get("help") and kwargs["help"] is not argparse.SUPPRESS:
                kwargs["help"] += f" [env: {env}]"
        parser.add_argument(flag, **kwargs)

    sub = parser.add_subparsers(dest="subcommand")
    docs = sub.add_parser(
        "docs", help="Generates the markdown documentation for the CLI"
    )
    docs.add_argument(
        "--output", "-o", required=True, metavar="FILE", help="path where to save the docs file"
    )
    return parser


def generate_docs() -> str:
    """Render the markdown CLI reference (reference cli-docs.md generated by
    the ``docs`` subcommand, main.rs:68)."""
    lines = [
        f"# Command-Line Help for `{PROG}`",
        "",
        f"This document contains the help content for the `{PROG}` command-line program.",
        "",
        f"## `{PROG}`",
        "",
        f"**Usage:** `{PROG} [OPTIONS] [COMMAND]`",
        "",
        "###### **Subcommands:**",
        "",
        "* `docs` — Generates the markdown documentation for the CLI",
        "",
        "###### **Options:**",
        "",
    ]
    for flag, env, kwargs in _flag_specs():
        help_text = kwargs.get("help")
        if help_text is argparse.SUPPRESS:
            continue
        metavar = kwargs.get("metavar")
        action = kwargs.get("action")
        head = flag if action in ("store_true",) else f"{flag} <{metavar}>"
        lines.append(f"* `{head}` — {help_text}")
        if env:
            lines.append(f"  [env: `{env}`]")
        default = kwargs.get("default")
        if default not in (None, False, []):
            lines.append("")
            lines.append(f"  Default value: `{default}`")
        choices = kwargs.get("choices")
        if choices:
            lines.append("")
            lines.append("  Possible values: " + ", ".join(f"`{c}`" for c in choices))
        lines.append("")
    return "\n".join(lines)


def main(argv: Sequence[str] | None = None) -> int:
    """Process entry (reference src/main.rs:15-65)."""
    parser = build_cli()
    args = parser.parse_args(argv)

    if args.long_version:
        print(long_version())
        return 0

    if args.subcommand == "docs":
        with open(args.output, "w", encoding="utf-8") as f:
            f.write(generate_docs())
        return 0

    from policy_server_tpu.server import run_server

    return run_server(args)


if __name__ == "__main__":
    sys.exit(main())
