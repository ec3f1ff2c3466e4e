"""Evaluation metrics — the reference's two instruments, identical names and
label schema (src/metrics.rs, src/metrics/policy_evaluations_total.rs:7-15,
src/metrics/policy_evaluations_latency.rs:9-21).

Reference exports via OTLP gRPC push (metrics.rs:14-29). This build exposes
a Prometheus pull endpoint instead (``GET /metrics`` on the readiness
server) — the OTLP metrics SDK is not part of the baked environment, and a
pull endpoint removes a collector hop from the TPU serving path. Instrument
names, label keys, and units are unchanged, so collector-side scrape configs
see the reference's schema.

Label structs mirror metrics.rs:
* ``PolicyEvaluation``   (metrics.rs:34-74)  — policy_name, policy_mode,
  resource_kind, resource_namespace?, resource_request_operation, accepted,
  mutated, request_origin, error_code?
* ``RawPolicyEvaluation`` (metrics.rs:77-102) — policy_name, policy_mode,
  accepted, mutated, error_code?  (no resource labels: raw requests are not
  Kubernetes resources)
* ``PolicyInitializationError`` (metrics.rs:105-120) — policy_name,
  initialization_error
"""

from __future__ import annotations

import collections
import threading
from dataclasses import dataclass
from typing import Any, Mapping

from policy_server_tpu.telemetry.flightrec import PH_GC

try:  # baked into the environment, but keep the import soft for vendoring
    import prometheus_client
    from prometheus_client import CollectorRegistry
except ImportError:  # pragma: no cover
    prometheus_client = None
    CollectorRegistry = None

METER_NAME = "kubewarden"  # metrics.rs:12
EVALUATIONS_TOTAL = "kubewarden_policy_evaluations_total"
LATENCY_MILLISECONDS = "kubewarden_policy_evaluation_latency_milliseconds"
INIT_ERRORS_TOTAL = "kubewarden_policy_initialization_errors_total"

# Serving-runtime instrument names (round 6): exported through the
# runtime-stats collector (attach_runtime_stats, server.py wires the
# provider), so they appear on BOTH the Prometheus pull endpoint
# (/metrics) and the OTLP push pipeline (otlp.prometheus_to_otlp walks
# the same registry). Kept here so server, dashboard, and tests agree on
# one spelling — graftcheck's observability checker (OB01) rejects any
# runtime_stats yield whose name is not one of these constants.
BATCHES_DISPATCHED = "policy_server_batches_dispatched"
REQUESTS_DISPATCHED = "policy_server_requests_dispatched"
DEADLINE_ABANDONED_BATCHES = "policy_server_deadline_abandoned_batches"
QUEUE_DEPTH = "policy_server_queue_depth"
ORACLE_FALLBACKS = "policy_server_oracle_fallbacks"
HOST_FASTPATH_BATCHES = "policy_server_host_fastpath_batches"
# batches at or under --host-fastpath-threshold that rode the device
# because the pipeline had no slot to spare (PR 40)
HOST_FASTPATH_DECLINED_BATCHES = (
    "policy_server_host_fastpath_declined_batches"
)
HOST_FASTPATH_REQUESTS = "policy_server_host_fastpath_requests"
DEDUP_BLOB_HITS = "policy_server_dedup_blob_hits"
DEDUP_BLOB_MISSES = "policy_server_dedup_blob_misses"
# live requests that went straight to the row tier: a webhook call's uid
# is new every time, so its blob could only miss (verdict_cache.py)
DEDUP_BLOB_BYPASSED_ROWS = "policy_server_dedup_blob_bypassed_rows"
VERDICT_CACHE_HITS = "policy_server_verdict_cache_hits"
VERDICT_CACHE_MISSES = "policy_server_verdict_cache_misses"
VERDICT_CACHE_BYTES = "policy_server_verdict_cache_bytes"
# entries the byte bound pushed out, by tier ("blob" | "row"): a hit rate
# says nothing of a tier that churns, this does
VERDICT_CACHE_EVICTIONS = "policy_server_verdict_cache_evictions_total"
# entries put and their accounted bytes, both tiers together: bytes over
# puts is what one entry costs the byte budget (PR 32)
VERDICT_CACHE_PUTS = "policy_server_verdict_cache_puts_total"
VERDICT_CACHE_PUT_BYTES = "policy_server_verdict_cache_put_bytes_total"
BATCH_DEDUP_HITS = "policy_server_batch_dedup_hits"
FRAGMENT_HITS = "policy_server_fragment_hits"
BUDGET_ROUTED_BATCHES = "policy_server_budget_routed_batches"
SHED_REQUESTS = "policy_server_shed_requests"
EXPIRED_DROPPED = "policy_server_expired_dropped_rows"
DEGRADED_RESPONSES = "policy_server_degraded_responses"
BREAKER_OPEN_SHARDS = "policy_server_breaker_open_shards"
BREAKER_TRIPS = "policy_server_breaker_trips"
BREAKER_RECOVERIES = "policy_server_breaker_recoveries"
BREAKER_PROBES = "policy_server_breaker_probes"
BREAKER_SHORT_CIRCUITED = "policy_server_breaker_short_circuited_requests"
FETCH_RETRY_ATTEMPTS = "policy_server_fetch_retry_attempts"
FETCH_RETRY_GIVEUPS = "policy_server_fetch_retry_giveups"
POLICY_RELOADS = "policy_server_policy_reloads"
POLICY_RELOAD_FAILURES = "policy_server_policy_reload_failures"
POLICY_RELOAD_ROLLBACKS = "policy_server_policy_reload_rollbacks"
RELOAD_CANARY_REPLAYS = "policy_server_reload_canary_replays"
RELOAD_CANARY_DIVERGENCES = "policy_server_reload_canary_divergences"
POLICY_EPOCH = "policy_server_policy_epoch"
# round 10 — background audit scanner (audit/) + the batcher's
# best-effort audit lane (runtime/batcher.py)
AUDIT_ROWS_SCANNED = "policy_server_audit_rows_scanned"
AUDIT_BATCHES_DISPATCHED = "policy_server_audit_batches_dispatched"
AUDIT_PREEMPTIONS = "policy_server_audit_preemptions"
AUDIT_LANE_DEPTH = "policy_server_audit_lane_depth"
AUDIT_FULL_SWEEPS = "policy_server_audit_full_sweeps"
AUDIT_DIRTY_SWEEPS = "policy_server_audit_dirty_sweeps"
AUDIT_SWEEP_ERRORS = "policy_server_audit_sweep_errors"
AUDIT_PAUSED_SWEEPS = "policy_server_audit_paused_sweeps"
AUDIT_REPORT_FRESHNESS = "policy_server_audit_report_freshness_seconds"
AUDIT_REPORTS_RESIDENT = "policy_server_audit_reports_resident"
AUDIT_REPORTS_STALE = "policy_server_audit_reports_stale"
AUDIT_SNAPSHOT_RESOURCES = "policy_server_audit_snapshot_resources"
AUDIT_SNAPSHOT_BYTES = "policy_server_audit_snapshot_bytes"
# PR 38 — what the scanner costs the live path, and the lane's own row
# count (an audit row answers nobody: it is counted here and under no
# answer source)
AUDIT_ROWS_DISPATCHED = "policy_server_audit_rows_dispatched"
# every lane row the environment judged, whoever produced it; of those, the
# rows a dedup tier answered (the lane's, no answer source: one unlabelled
# total, and the same by tier, "row" | "blob" | "duplicate")
AUDIT_ROWS_JUDGED = "policy_server_audit_rows_judged"
AUDIT_TIER_HITS = "policy_server_audit_tier_hits"
AUDIT_TIER_HITS_BY_TIER = "policy_server_audit_tier_hits_by_tier"
AUDIT_OBSERVE_SECONDS = "policy_server_audit_observe_seconds_total"
AUDIT_SNAPSHOT_EVICTIONS = "policy_server_audit_snapshot_evictions"
AUDIT_OBJECTS_UNJUDGED = "policy_server_audit_objects_unjudged"
# round 11 — native HTTP front-end (csrc/httpfront.cpp +
# runtime/native_frontend.py): GIL-free framing counters, plus the
# batcher queue-wait leg of the framing/queue/device decomposition
NATIVE_HTTP_REQUESTS = "policy_server_native_http_requests"
NATIVE_PARSE_FALLBACKS = "policy_server_native_parse_fallbacks"
NATIVE_RING_FULL = "policy_server_native_ring_full_rejections"
NATIVE_VERDICTS_SERIALIZED = "policy_server_native_serialized_verdicts"
NATIVE_PYTHON_SERIALIZED = "policy_server_native_python_serialized_responses"
NATIVE_FRAMING_SECONDS = "policy_server_native_framing_seconds_total"
NATIVE_INFLIGHT = "policy_server_native_inflight_requests"
QUEUE_WAIT_SECONDS = "policy_server_queue_wait_seconds_total"
HOST_ENCODE_SECONDS = "policy_server_host_encode_seconds_total"
HOST_ENCODE_ROWS = "policy_server_host_encode_rows_total"
# CPU time of the encoding thread over the same intervals as
# HOST_ENCODE_SECONDS (wall): the difference is time that thread was off
# a core, waiting for the GIL or descheduled
HOST_ENCODE_CPU_SECONDS = "policy_server_host_encode_cpu_seconds_total"
# the native encoder's mirror of the intern table (csrc/fastenc.cpp, PR
# 34): string leaves it had not seen, which Python resolved (per row
# against HOST_ENCODE_ROWS: ~0 once warm), and the strings it holds
HOST_ENCODE_PYTHON_STRINGS = "policy_server_host_encode_python_strings_total"
HOST_ENCODE_MIRROR_ENTRIES = "policy_server_host_encode_mirror_entries"
HOST_BOOKKEEPING_SECONDS = "policy_server_host_bookkeeping_seconds_total"
DISPATCH_WAIT_SECONDS = "policy_server_dispatch_wait_seconds_total"
DISPATCHED_ROWS = "policy_server_dispatched_rows_total"
# requests a dispatched slot answered as a pre-serialized fragment (an
# eligible target's row; FRAGMENT_HITS counts tier hits)
DEVICE_FRAGMENT_ROWS = "policy_server_device_fragment_rows_total"
# round 12 — array-at-a-time serving path + columnar device transport
# (runtime/batcher.py submit_many, evaluation/environment.py planes):
# bulk admission volume, wire bytes shipped vs the packed-transport
# equivalent, delta-column hit rate, donation, resident constants
BULK_SUBMITS = "policy_server_bulk_submits"
BULK_SUBMITTED_ROWS = "policy_server_bulk_submitted_rows"
WIRE_BYTES_SHIPPED = "policy_server_wire_bytes_shipped"
# host arrays launches of the columnar program handed to the device (one
# wire buffer a launch since PR 28; per launch against
# policy_server_phase_latency_seconds_count{phase="launch"})
LAUNCH_H2D_ARRAYS = "policy_server_launch_h2d_arrays"
# launches whose wire buffer the chunk's encode call had written
# (csrc/fastenc.cpp write_wire) and not numpy in the launch (PR 37; per
# launch against the same count)
LAUNCH_NATIVE_WIRE = "policy_server_launch_native_wire"
WIRE_BYTES_PACKED_EQUIV = "policy_server_wire_bytes_packed_equivalent"
WIRE_ROWS = "policy_server_wire_rows"
DELTA_COLS_SHIPPED = "policy_server_delta_columns_shipped"
DELTA_COLS_TOTAL = "policy_server_delta_columns_available"
RESIDENT_CONST_BYTES = "policy_server_device_resident_constant_bytes"
# round 13 — cluster-scale soak + live watch feed: the audit snapshot
# store's list+watch event accounting (audit/watch_feed.py), the native
# frontend's connection-abuse hardening counters (csrc/httpfront.cpp
# idle/read timeouts + connection cap), and the live soak-window SLO
# gauges an in-process soak (tools/soak) publishes through the state
WATCH_EVENTS_APPLIED = "policy_server_audit_watch_events_applied"
WATCH_EVENTS_DROPPED = "policy_server_audit_watch_events_dropped"
WATCH_RESYNCS = "policy_server_audit_watch_resyncs"
NATIVE_IDLE_CLOSES = "policy_server_native_idle_timeout_closes"
NATIVE_CONN_CAP_REJECTS = "policy_server_native_connection_cap_rejections"
SOAK_WINDOW_RPS = "policy_server_soak_window_rps"
SOAK_WINDOW_P99_MS = "policy_server_soak_window_p99_ms"
SOAK_WINDOW_SHED_RATE = "policy_server_soak_window_shed_rate"
# round 15 — predicate-program optimizer (ops/optimizer.py). Names
# follow policy_server_predicate_<OPTIMIZER_STAT_KEY> — graftcheck's
# OB07 enforces the stats-dict ↔ constant ↔ dashboard mapping stays
# total.
PREDICATE_SUBTREES_SHARED = "policy_server_predicate_subtrees_shared"
PREDICATE_POLICIES_FOLDED = "policy_server_predicate_policies_folded"
PREDICATE_RULES_FOLDED = "policy_server_predicate_rules_folded"
PREDICATE_FIELDS_PRUNED = "policy_server_predicate_fields_pruned"
PREDICATE_ROW_BYTES_SAVED = "policy_server_predicate_row_bytes_saved"
# round 16 — multi-tenant serving (tenancy.py + runtime/scheduler.py):
# tenant-labelled admission/quota/fair-dispatch/lifecycle families.
# These are the first LABELLED runtime-stats families: the yield's
# value is a [(label_values, value), ...] list and the 5th tuple
# element names the label schema (("tenant",)) — see
# _RuntimeStatsCollector. All empty (no samples) without a --tenants
# manifest, so the families still export and dashboard panels resolve.
TENANT_SHED_ROWS = "policy_server_tenant_shed_rows"
TENANT_ADMITTED_ROWS = "policy_server_tenant_admitted_rows"
TENANT_INFLIGHT_ROWS = "policy_server_tenant_inflight_rows"
TENANT_QUEUE_DEPTH = "policy_server_tenant_queue_depth"
TENANT_DISPATCH_GRANTS = "policy_server_tenant_dispatch_grants"
TENANT_DISPATCH_WAIT_SECONDS = (
    "policy_server_tenant_dispatch_wait_seconds_total"
)
TENANT_EPOCH = "policy_server_tenant_policy_epoch"
TENANT_ROLLBACKS = "policy_server_tenant_reload_rollbacks"
TENANT_READY = "policy_server_tenant_ready"
TENANTS_SERVING = "policy_server_tenants_serving"
# round 17 — crash-tolerant serving (statestore.py + supervision.py):
# boot shape (warm/cold + the time-to-ready MTTR gauge), the durable
# state store's cache/journal/fsck accounting, and the supervision
# counters (prefork respawn breaker + the self-heal watchdog). All zero
# without --state-dir / prefork workers — the families still export so
# dashboard panels resolve on every deployment.
BOOT_TIME_TO_READY = "policy_server_boot_time_to_ready_seconds"
BOOT_WARM = "policy_server_boot_warm"
BOOT_DEGRADED_SOURCES = "policy_server_boot_degraded_sources"
STATESTORE_ARTIFACTS = "policy_server_statestore_artifacts_resident"
STATESTORE_BYTES = "policy_server_statestore_bytes_resident"
STATESTORE_CACHE_HITS = "policy_server_statestore_artifact_cache_hits"
STATESTORE_CACHE_MISSES = "policy_server_statestore_artifact_cache_misses"
STATESTORE_MANIFESTS_PERSISTED = (
    "policy_server_statestore_manifests_persisted"
)
STATESTORE_JOURNAL_RECORDS = "policy_server_statestore_journal_records"
STATESTORE_FSCK_QUARANTINED = "policy_server_statestore_fsck_quarantined"
STATESTORE_AUDIT_SPILLS = "policy_server_statestore_audit_spills"
STATESTORE_AUDIT_ROWS_RESTORED = (
    "policy_server_statestore_audit_rows_restored"
)
WORKER_RESPAWNS = "policy_server_worker_respawns"
WORKER_RESPAWN_BACKOFF_SECONDS = (
    "policy_server_worker_respawn_backoff_seconds_total"
)
WORKER_SLOTS_GIVEN_UP = "policy_server_worker_slots_given_up"
SELFHEAL_BATCHER_REVIVES = "policy_server_selfheal_batcher_revives"
SELFHEAL_FRONTEND_REVIVES = "policy_server_selfheal_frontend_revives"
# round 18 — flight recorder (telemetry/flightrec.py): per-phase latency
# histogram (the first phase-granular instrument — until now only
# whole-request latency existed), the tail-exemplar table (slowest rows
# per window, labelled by their trace id so a p99 blip links to its
# /debug/timeline), and the recorder's own volume counters. The
# histogram registers directly as a prometheus instrument below; the
# exemplar family is the labelled-gauge runtime_stats pattern from
# round 16 (the sample set is rebuilt per scrape, so rotated-out
# exemplars disappear instead of lingering as stale series).
PHASE_LATENCY_SECONDS = "policy_server_phase_latency_seconds"
TAIL_EXEMPLAR_LATENCY_SECONDS = "policy_server_tail_exemplar_latency_seconds"
FLIGHT_RECORDER_EVENTS = "policy_server_flight_recorder_events"
FLIGHT_RECORDER_ROWS_SAMPLED = "policy_server_flight_recorder_rows_sampled"
# the recorder's collector hook (flightrec.FlightRecorder.on_gc): passes
# of the CPython collector and the time they held the interpreter, by
# generation; the full passes are also ``gc`` intervals of the ring
GC_PAUSE_SECONDS = "policy_server_gc_pause_seconds_total"
GC_PASSES = "policy_server_gc_passes_total"
# round 20 — native TLS termination (csrc/httpfront.cpp memory-BIO
# handshakes + runtime/native_frontend.NativeTlsManager + certs.py
# last-good identity machinery): cert-expiry horizon, handshake
# outcome accounting (ok / hard failure / arrival-timeout slowloris
# reap / mid-handshake disconnect / close_notify-clean closes), and
# the hot-rotation generation/reload counters. The expiry gauge and
# reload counters export under BOTH terminators (native and the
# aiohttp fallback — they read certs.py through the state); the
# handshake counters are native-frontend stats, zero under aiohttp
# termination or plaintext (families still export so dashboard panels
# resolve everywhere).
TLS_CERT_EXPIRY_SECONDS = "policy_server_tls_cert_expiry_seconds"
TLS_HANDSHAKES_OK = "policy_server_tls_handshakes_ok"
TLS_HANDSHAKES_FAILED = "policy_server_tls_handshakes_failed"
TLS_HANDSHAKE_TIMEOUTS = "policy_server_tls_handshake_timeouts"
TLS_HANDSHAKE_DISCONNECTS = "policy_server_tls_handshake_disconnects"
TLS_CLEAN_CLOSES = "policy_server_tls_clean_closes"
TLS_GENERATIONS = "policy_server_tls_generations"
TLS_RELOADS = "policy_server_tls_reloads"
TLS_RELOAD_FAILURES = "policy_server_tls_reload_failures"
TLS_NATIVE_TERMINATION = "policy_server_tls_native_termination"

# round 22 — host-local serving shards (runtime/shards.py): M full
# serving stacks behind a health + queue-depth-EWMA router. The shard
# count and the per-shard health/queue gauges (labelled by shard index)
# describe the plane; the fence/reroute/respawn counters account every
# fencing event's row disposition — rerouted rows answered verdicts on
# a sibling, fenced rows answered 503+Retry-After, and the two must
# explain every queued row a dead shard held. All zeros/singletons with
# --serving-shards 1 (families still export so panels resolve).
SHARDS_SERVING = "policy_server_shards_serving"
SHARD_HEALTHY = "policy_server_shard_healthy"
SHARD_QUEUE_DEPTH = "policy_server_shard_queue_depth"
SHARD_FENCES = "policy_server_shard_fences"
SHARD_REROUTED_ROWS = "policy_server_shard_rerouted_rows"
SHARD_FENCED_ROWS = "policy_server_shard_fenced_rows"
SHARD_RESPAWNS = "policy_server_shard_respawns"
SHARD_HEARTBEAT_FAULTS = "policy_server_shard_heartbeat_faults"

# round 23 — persistent (object × policy) verdict matrix (audit/
# matrix.py). Residency gauges describe the in-memory matrix; the sweep
# counters split re-judged rows by WHY they were re-judged (row dirtied
# by the watch feed vs column dirtied by an epoch promotion) so a
# promotion touching 2 of 32 policies shows 2 columns' worth of column
# rows, not a cluster-wide spike. Changelog/stream counters account the
# /audit/stream fan-out (drops are slow consumers evicted, never the
# applier blocking); lookup hits/misses are the admission fast path
# (a /validate UPDATE answered from a precomputed verdict). Spills and
# restored cells tie the matrix to the statestore journal. All families
# export as zero with --audit-matrix off so panels resolve.
MATRIX_ROWS_RESIDENT = "policy_server_audit_matrix_rows_resident"
MATRIX_CELLS_RESIDENT = "policy_server_audit_matrix_cells_resident"
MATRIX_COLUMNS = "policy_server_audit_matrix_columns"
MATRIX_DIRTY_COLUMNS = "policy_server_audit_matrix_dirty_columns"
MATRIX_VERSION = "policy_server_audit_matrix_version"
MATRIX_ROW_SWEEP_ROWS = "policy_server_audit_matrix_row_sweep_rows"
MATRIX_COLUMN_SWEEP_ROWS = "policy_server_audit_matrix_column_sweep_rows"
MATRIX_ROWS_EVICTED = "policy_server_audit_matrix_rows_evicted"
MATRIX_COLUMNS_INVALIDATED = (
    "policy_server_audit_matrix_columns_invalidated"
)
MATRIX_CHANGELOG_EMITS = "policy_server_audit_matrix_changelog_emits"
MATRIX_STREAM_CLIENTS = "policy_server_audit_matrix_stream_clients"
MATRIX_STREAM_DROPPED_CLIENTS = (
    "policy_server_audit_matrix_stream_dropped_clients"
)
MATRIX_LOOKUP_HITS = "policy_server_audit_matrix_lookup_hits"
MATRIX_LOOKUP_MISSES = "policy_server_audit_matrix_lookup_misses"
MATRIX_SPILLS = "policy_server_audit_matrix_spills"
MATRIX_CELLS_RESTORED = "policy_server_audit_matrix_cells_restored"

# What the program runs on and what the compiler did (chip bring-up): one
# info-style gauge (value 1) whose labels are the boot report's device
# facts as JAX reports them — the chip smoke and every benchmark line
# stamp their output from it — plus the process's XLA compile counts
# (runtime/compile_cache.py: real compiles vs persistent-cache hits) and
# the columnar plane structures traced / still compiling off the serving
# path (evaluation/environment.py).
DEVICE_INFO = "policy_server_device_info"
XLA_PROGRAMS_COMPILED = "policy_server_xla_programs_compiled"
XLA_COMPILE_CACHE_HITS = "policy_server_xla_compile_cache_hits"
PLANE_PROGRAM_COMPILES = "policy_server_plane_program_compiles"
PLANE_PROGRAMS_PENDING = "policy_server_plane_programs_pending"

# Prometheus requires a fixed label set per metric family; optional reference
# labels (resource_namespace, error_code) encode absence as "".
_EVAL_LABELS = (
    "policy_name",
    "policy_mode",
    "resource_kind",
    "resource_namespace",
    "resource_request_operation",
    "accepted",
    "mutated",
    "request_origin",
    "error_code",
)
_INIT_LABELS = ("policy_name", "initialization_error")

# Millisecond buckets sized for the <10ms p99 north star (BASELINE.md) with
# headroom up to the 2 s policy deadline.
_LATENCY_BUCKETS_MS = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
    100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
)

# Second buckets for the per-phase histogram (flight recorder): phases
# span ~10 µs (bookkeeping on a warm batch) to ~100 ms (a cold device
# dispatch), so the grid is log-spaced across five decades.
_PHASE_BUCKETS_S = (
    25e-6, 50e-6, 100e-6, 250e-6, 500e-6, 1e-3, 2.5e-3, 5e-3,
    10e-3, 25e-3, 50e-3, 100e-3, 250e-3, 1.0,
)


def _b(v: bool) -> str:
    return "true" if v else "false"


@dataclass(frozen=True)
class PolicyEvaluation:
    policy_name: str
    policy_mode: str
    resource_kind: str
    resource_namespace: str | None
    resource_request_operation: str
    accepted: bool
    mutated: bool
    request_origin: str
    error_code: int | None = None

    def labels(self) -> dict[str, str]:
        return {
            "policy_name": self.policy_name,
            "policy_mode": self.policy_mode,
            "resource_kind": self.resource_kind,
            "resource_namespace": self.resource_namespace or "",
            "resource_request_operation": self.resource_request_operation,
            "accepted": _b(self.accepted),
            "mutated": _b(self.mutated),
            "request_origin": self.request_origin,
            "error_code": "" if self.error_code is None else str(self.error_code),
        }


@dataclass(frozen=True)
class RawPolicyEvaluation:
    policy_name: str
    policy_mode: str
    accepted: bool
    mutated: bool
    error_code: int | None = None

    def labels(self) -> dict[str, str]:
        return {
            "policy_name": self.policy_name,
            "policy_mode": self.policy_mode,
            "resource_kind": "",
            "resource_namespace": "",
            "resource_request_operation": "",
            "accepted": _b(self.accepted),
            "mutated": _b(self.mutated),
            "request_origin": "validate_raw",
            "error_code": "" if self.error_code is None else str(self.error_code),
        }


@dataclass(frozen=True)
class PolicyInitializationError:
    policy_name: str
    initialization_error: str

    def labels(self) -> dict[str, str]:
        return {
            "policy_name": self.policy_name,
            "initialization_error": self.initialization_error,
        }


class _RuntimeStatsCollector:
    """Custom collector exposing serving-runtime introspection (batcher
    dispatch counts, watchdog abandonments, queue depth, oracle
    fallbacks) through the SAME registry as the reference instruments —
    no hand-assembled exposition text, no duplicate-family risk."""

    def __init__(self, owner: "MetricsRegistry"):
        self._owner = owner

    def collect(self):
        fn = self._owner._runtime_stats_fn
        if fn is None:
            return
        from prometheus_client.core import (
            CounterMetricFamily,
            GaugeMetricFamily,
        )

        for item in fn():
            name, kind, help_text, value = item[:4]
            cls = (
                CounterMetricFamily if kind == "counter" else GaugeMetricFamily
            )
            if len(item) > 4:
                # labelled family (round 16): value is a list of
                # (label_values_tuple, value) samples, item[4] names the
                # label schema — e.g. ("tenant",). The OTLP converter
                # walks the same registry, so labels flow through as
                # attributes unchanged.
                family = cls(name, help_text, labels=list(item[4]))
                for label_values, v in value:
                    family.add_metric([str(x) for x in label_values], v)
            else:
                family = cls(name, help_text, value=value)
            yield family


class MetricsRegistry:
    """Thread-safe metrics sink. Always aggregates in-process (snapshot API
    used by unit tests and the batcher's self-tuning); exposes Prometheus
    text format when prometheus_client is present."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: dict[tuple[str, tuple[tuple[str, str], ...]], float] = {}  # guarded-by: _lock
        # Bounded recent-sample window per label set (tests/self-tuning);
        # the Prometheus histogram carries the full aggregation.
        self._latencies: dict[  # guarded-by: _lock
            tuple[tuple[str, str], ...], collections.deque[float]
        ] = {}
        # label-set → (counter child, histogram child); dict assignment is
        # atomic under the GIL, racing builders produce identical children
        self._prom_children: dict[tuple, tuple] = {}  # graftcheck: lockfree — GIL-atomic dict ops; racing builders store identical children
        # metric dataclass → (sorted label key, children): the serving
        # path records TWO observations per request with the same frozen
        # dataclass — hashing it once replaces rebuilding + sorting the
        # 9-entry label dict on every call (measured ~2/3 of phase-3
        # post-processing time). Cardinality is bounded like the children
        # cache (policy set × verdict space).
        self._resolved: dict[object, tuple] = {}  # graftcheck: lockfree — same protocol as _prom_children
        # serving-runtime stats provider (attach_runtime_stats): yields
        # (name, kind, help, value) tuples scraped on collect — ONE
        # collector registered here, so re-attachment can never produce
        # duplicate metric families
        self._runtime_stats_fn = None
        if prometheus_client is not None:
            self.registry = CollectorRegistry()
            self.registry.register(_RuntimeStatsCollector(self))
            self._prom_total = prometheus_client.Counter(
                EVALUATIONS_TOTAL,
                "Number of policy evaluations",
                _EVAL_LABELS,
                registry=self.registry,
            )
            self._prom_latency = prometheus_client.Histogram(
                LATENCY_MILLISECONDS,
                "Policy evaluation latency in milliseconds",
                _EVAL_LABELS,
                buckets=_LATENCY_BUCKETS_MS,
                registry=self.registry,
            )
            self._prom_init_errors = prometheus_client.Counter(
                INIT_ERRORS_TOTAL,
                "Number of policies that failed to initialize",
                _INIT_LABELS,
                registry=self.registry,
            )
            # flight-recorder per-phase latency (round 18): batch-granular
            # phase durations labelled by lifecycle phase. Fed by
            # telemetry/flightrec.py through observe_phase; OTLP export
            # rides prometheus_to_otlp like every histogram here.
            self._prom_phase = prometheus_client.Histogram(
                PHASE_LATENCY_SECONDS,
                "Per-batch serving-phase latency in seconds "
                "(flight recorder)",
                ("phase",),
                buckets=_PHASE_BUCKETS_S,
                registry=self.registry,
            )
            # phase-name cardinality is the closed flightrec.PHASES set;
            # children cache like _prom_children (GIL-atomic dict ops)
            self._phase_children: dict[str, Any] = {  # graftcheck: lockfree — GIL-atomic dict ops; racing builders store identical children
                # the collector's child exists from the start: its
                # observations come from inside a gc callback, which can
                # fire while this thread holds the histogram's own lock
                # (labels(), a scrape's copy), where a first labels()
                # call would wait for that lock forever
                PH_GC: self._prom_phase.labels(phase=PH_GC),
            }
        else:  # pragma: no cover
            self.registry = None

    # -- recording (reference add_policy_evaluation / record_policy_latency,
    #    src/metrics/policy_evaluations_total.rs + _latency.rs) ------------

    def _children(self, key: tuple, labels: dict[str, str]) -> tuple:
        """Cached (counter_child, histogram_child) per label set:
        ``labels(**kw)`` re-resolves the child through prometheus_client's
        internal lock on every call — with the per-request metric pair that
        lookup showed up in the serving profile. Label cardinality is
        bounded (policy set × verdict space), so the cache is too."""
        hit = self._prom_children.get(key)
        if hit is None:
            hit = (
                self._prom_total.labels(**labels),
                self._prom_latency.labels(**labels),
            )
            self._prom_children[key] = hit
        return hit

    def _resolve(
        self, m: PolicyEvaluation | RawPolicyEvaluation
    ) -> tuple[tuple, tuple | None]:
        """(sorted label key, prometheus children) for a metric dataclass,
        computed once per distinct label combination."""
        ent = self._resolved.get(m)
        if ent is None:
            labels = m.labels()
            key = tuple(sorted(labels.items()))
            children = (
                self._children(key, labels)
                if self.registry is not None
                else None
            )
            ent = (key, children)
            self._resolved[m] = ent
        return ent

    def add_policy_evaluation(
        self, m: PolicyEvaluation | RawPolicyEvaluation
    ) -> None:
        key, children = self._resolve(m)
        with self._lock:
            self._counters[(EVALUATIONS_TOTAL, key)] = (
                self._counters.get((EVALUATIONS_TOTAL, key), 0) + 1
            )
        if children is not None:
            children[0].inc()

    def record_policy_latency(
        self, milliseconds: float, m: PolicyEvaluation | RawPolicyEvaluation
    ) -> None:
        key, children = self._resolve(m)
        with self._lock:
            self._latencies.setdefault(
                key, collections.deque(maxlen=4096)
            ).append(milliseconds)
        if children is not None:
            children[1].observe(milliseconds)

    def record_evaluations_batch(
        self,
        pairs: list[tuple[float, PolicyEvaluation | RawPolicyEvaluation]],
    ) -> None:
        """Batch form of add_policy_evaluation + record_policy_latency for
        the dispatch thread's phase 3: one lock acquisition and one
        counter increment per LABEL GROUP per batch instead of two locked
        updates per request (a serving batch is typically 1-3 groups —
        same policy, accept/reject split)."""
        groups: dict[object, list[float]] = {}
        for ms, m in pairs:
            groups.setdefault(m, []).append(ms)
        resolved = [(self._resolve(m), vals) for m, vals in groups.items()]
        with self._lock:
            for (key, _children), vals in resolved:
                self._counters[(EVALUATIONS_TOTAL, key)] = (
                    self._counters.get((EVALUATIONS_TOTAL, key), 0)
                    + len(vals)
                )
                self._latencies.setdefault(
                    key, collections.deque(maxlen=4096)
                ).extend(vals)
        for (_key, children), vals in resolved:
            if children is not None:
                children[0].inc(len(vals))
                observe = children[1].observe
                for v in vals:
                    observe(v)

    def add_policy_initialization_error(
        self, m: PolicyInitializationError
    ) -> None:
        labels = m.labels()
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._counters[(INIT_ERRORS_TOTAL, key)] = (
                self._counters.get((INIT_ERRORS_TOTAL, key), 0) + 1
            )
        if self.registry is not None:
            self._prom_init_errors.labels(**labels).inc()

    def observe_phase(self, phase: str, seconds: float) -> None:
        """One flight-recorder phase observation (the recorder's /metrics
        + OTLP funnel). Hot-path discipline: one dict get + one
        prometheus observe per BATCH per phase."""
        if self.registry is None:  # pragma: no cover
            return
        child = self._phase_children.get(phase)
        if child is None:
            child = self._prom_phase.labels(phase=phase)
            self._phase_children[phase] = child
        child.observe(seconds)

    def attach_runtime_stats(self, snapshot_fn) -> None:
        """Install (or replace) the serving-runtime stats provider:
        ``snapshot_fn() -> [(name, 'counter'|'gauge', help, value), ...]``.
        Called by the server at bootstrap with a closure over its batcher
        and evaluation environment."""
        self._runtime_stats_fn = snapshot_fn

    # -- exposition ---------------------------------------------------------

    def exposition(self) -> bytes:
        """Prometheus text format for the /metrics endpoint."""
        if self.registry is None:  # pragma: no cover
            return b""
        return prometheus_client.generate_latest(self.registry)

    # -- test/introspection surface ----------------------------------------

    def counter_value(
        self, name: str, match: Mapping[str, str] | None = None
    ) -> float:
        with self._lock:
            total = 0.0
            for (metric, key), v in self._counters.items():
                if metric != name:
                    continue
                labels = dict(key)
                if match and any(labels.get(k) != v2 for k, v2 in match.items()):
                    continue
                total += v
            return total

    def latency_samples(self, match: Mapping[str, str] | None = None) -> list[float]:
        with self._lock:
            out: list[float] = []
            for key, vals in self._latencies.items():
                labels = dict(key)
                if match and any(labels.get(k) != v for k, v in match.items()):
                    continue
                out.extend(vals)
            return out


_default: MetricsRegistry | None = None
_default_lock = threading.Lock()


def setup_metrics() -> MetricsRegistry:
    """Install (or return) the process-wide registry (metrics.rs:14-29)."""
    global _default
    with _default_lock:
        if _default is None:
            _default = MetricsRegistry()
        return _default


def default_registry() -> MetricsRegistry:
    return setup_metrics()


def reset_metrics_for_tests() -> None:
    global _default
    with _default_lock:
        _default = None


def add_policy_evaluation(m: PolicyEvaluation | RawPolicyEvaluation) -> None:
    default_registry().add_policy_evaluation(m)


def record_policy_latency(
    milliseconds: float, m: PolicyEvaluation | RawPolicyEvaluation
) -> None:
    default_registry().record_policy_latency(milliseconds, m)


def add_policy_initialization_error(m: PolicyInitializationError) -> None:
    default_registry().add_policy_initialization_error(m)
