"""Micro-batching scheduler — the TPU-native replacement for the reference's
request-level concurrency model.

Reference mapping (SURVEY.md §2.3):
* ``Semaphore::new(pool_size)`` + ``task::spawn_blocking`` per request
  (src/api/handlers.rs:256-286) → a bounded submission queue feeding a
  dispatch thread; backpressure = queue capacity instead of semaphore
  permits.
* wasmtime epoch-interruption deadline (src/lib.rs:176-190, default 2 s,
  src/cli.rs:164-169) → a per-request wall-clock deadline covering queue
  wait + host hooks + device dispatch; exceeded ⇒ in-band 500 rejection
  with the reference's message "execution deadline exceeded"
  (tests/integration_test.rs:417).
* per-request wasm instance (evaluation_environment.rs:76-84) → nothing to
  isolate: the fused program is a pure function, one dispatch serves the
  whole batch.

Scheduling policy: dispatch fires when ``max_batch_size`` requests are
waiting OR the oldest waiter has aged ``batch_timeout_ms`` — the classic
size-or-deadline micro-batch rule. Batch shapes are bucketed to powers of
two (environment.bucket_size) so XLA compiles a bounded set of programs,
all warmed at boot.

Slow host-side pre-eval hooks (the 'sleeping' builtin — the reference's
sleeping-policy latency fixture) run on a side thread pool with a bounded
wait so one pathological request cannot stall the batch: on timeout the
request is rejected in-band and the batch proceeds (the thread is left to
finish in the background, exactly like an epoch-interrupted wasm instance
being torn down).
"""

from __future__ import annotations

import asyncio
import collections
import math
import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import Any

from policy_server_tpu import failpoints
from policy_server_tpu.api import service
from policy_server_tpu.evaluation import environment
from policy_server_tpu.evaluation.environment import (
    EvaluationEnvironment,
    bucket_size,
)
from policy_server_tpu.evaluation.errors import PolicyInitializationError
from policy_server_tpu.evaluation.policy_id import PolicyID
from policy_server_tpu.models import (
    AdmissionResponse,
    FragVerdict,
    ValidateRequest,
)
from policy_server_tpu.telemetry import flightrec, otlp

DEADLINE_MESSAGE = "execution deadline exceeded"
# a request whose propagated deadline passed while it sat in the queue:
# the API server already timed out the webhook call, so its verdict is
# unobservable — drop it BEFORE paying encode/dispatch (no dead work)
EXPIRED_MESSAGE = "request deadline exceeded before evaluation"
DEGRADED_MESSAGE = "policy server degraded: device backend unavailable"


class ShedError(Exception):
    """Load-shed signal raised at ADMISSION (submit/submit_async) when the
    queue's estimated wait — from the batcher's measured device-RTT EWMA —
    already exceeds the request's deadline budget: evaluating it would be
    pure waste (the admission-webhook model: the API server enforces a
    hard ``timeoutSeconds`` per review). The HTTP layer maps this to
    ``http_status`` + Retry-After."""

    http_status = 429
    message = "policy server overloaded; retry later"

    def __init__(self, retry_after_seconds: float):
        super().__init__(self.message)
        self.retry_after_seconds = max(0.001, retry_after_seconds)


class FencedError(ShedError):
    """A fenced serving shard's answer for rows it can no longer serve
    (round 22, runtime/shards.py): the shard's dispatch loop died or
    wedged, the router drained its queue, and no healthy sibling had
    room — the row was provably never dispatched, so retrying is safe
    and correct. Maps to 503 + Retry-After (a server-side availability
    event, not client overload — the 429 trend lines must not absorb
    fencing)."""

    http_status = 503
    message = "serving shard fenced; retry later"


@dataclass
class _Pending:
    policy_id: str
    request: ValidateRequest
    origin: service.RequestOrigin
    # per-request completion. None for bulk-submitted rows delivered
    # through a CompletionSink (submit_many): those skip the Future's
    # per-request lock/condition entirely and fan out batch-granular —
    # one sink call per dispatched batch.
    future: Future | None
    enqueued_at: float = field(default_factory=time.perf_counter)
    # captured at submission on the handler's thread; worker threads parent
    # their child spans to it (trace-id propagation through the batcher)
    trace_ctx: "otlp.SpanContext | None" = field(
        default_factory=otlp.current_span_context
    )
    # asyncio-native completion (submit_async): results are mirrored into
    # this loop-bound future so event-loop callers await it directly —
    # and a whole batch delivers with ONE call_soon_threadsafe per loop
    # instead of one wakeup per request (the fan-out dominated the
    # round-3 serving profile)
    aio_loop: Any = None
    aio_future: Any = None
    # propagated request deadline (absolute perf_counter time): stamped at
    # submission from --request-timeout-ms; rows past it are dropped
    # before encode/dispatch instead of evaluating dead work
    deadline: float | None = None
    # batch-granular completion (submit_many): ``sink.deliver_many``
    # receives [(token, response, exc)] — one call per batch instead of
    # one future resolution per row
    sink: Any = None
    token: Any = None
    # tenant admission accounting (round 16): the TenantAdmission this
    # row was counted against, cleared by the FIRST resolution so the
    # in-flight cap releases exactly once per row; None when no quota
    # applies (every single-tenant deployment)
    quota_token: Any = None
    # shard-ownership token (round 22, runtime/shards.py): the
    # MicroBatcher currently responsible for resolving this row.
    # Stamped at every enqueue (under the queue mutex on the burst
    # path), cleared by fence_drain while it holds that mutex, and
    # re-stamped by the sibling's enqueue on re-route — exactly one
    # owner exists at any instant, so a fenced row can never be
    # double-answered.
    owner: Any = None


def _set_many(items: list) -> None:
    """Runs ON the target event loop: apply a batch of completions. Each
    item is individually guarded — a duplicate completion (resolve then a
    late _fail for the same pending) must not abort the rest of the
    batch's deliveries."""
    for fut, result, exc in items:
        try:
            if fut.cancelled():
                continue
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(result)
        except asyncio.InvalidStateError:
            pass  # already completed: first completion wins


class _DeliveryBatch:
    """Accumulates asyncio completions per target loop and sink
    completions per CompletionSink; flush() wakes each loop / calls each
    sink ONCE for the whole batch."""

    __slots__ = ("_by_loop", "_by_sink")

    def __init__(self) -> None:
        self._by_loop: dict = {}
        self._by_sink: dict = {}

    def add(self, p: "_Pending", result=None, exc=None) -> None:
        self._by_loop.setdefault(p.aio_loop, []).append(
            (p.aio_future, result, exc)
        )

    def add_sink(self, p: "_Pending", result=None, exc=None) -> None:
        self._by_sink.setdefault(p.sink, []).append(
            (p.token, result, exc)
        )

    def flush(self) -> None:
        for loop, items in self._by_loop.items():
            try:
                loop.call_soon_threadsafe(_set_many, items)
            except RuntimeError:  # loop closed: nothing awaits anymore
                pass
        self._by_loop.clear()
        for sink, items in self._by_sink.items():
            _deliver_sink(sink, items)
        self._by_sink.clear()


def _deliver_sink(sink, items: list) -> None:
    """One batch-granular completion call; a broken sink must never take
    down the dispatch path."""
    try:
        sink.deliver_many(items)
    except Exception:  # noqa: BLE001 — delivery is best-effort
        from policy_server_tpu.telemetry.tracing import logger

        logger.exception("completion sink failed; batch dropped on floor")


class _BatchRec:
    """One dispatched batch's flight-recorder context: the batch id and
    the phase boundary stamps the batcher reads anyway (formed_at,
    phase-1 end, dispatch window). Rows reuse these for their exemplar
    phase breakdowns, so the per-row cost stays one float compare +
    one counter tick (flightrec.row_flags)."""

    __slots__ = ("rec", "bid", "formed_at", "form_ns", "disp_ns")

    def __init__(self, rec, formed_at: float):
        self.rec = rec
        self.bid = rec.next_batch()
        self.formed_at = formed_at
        self.form_ns = 0  # phase-1 duration, stamped when PH_FORM records
        self.disp_ns = 0  # dispatch duration, stamped when PH_DISPATCH records

    def row_breakdown(self, enqueued_at: float) -> dict:
        return {
            flightrec.PH_QUEUE_WAIT: int(
                max(0.0, self.formed_at - enqueued_at) * 1e9
            ),
            flightrec.PH_FORM: self.form_ns,
            flightrec.PH_DISPATCH: self.disp_ns,
        }


class _AuditJob:
    """One best-effort audit-lane batch: ``pairs`` of (policy_id,
    request), resolved as a list of raw verdicts (constraints never
    applied — audit-origin semantics)."""

    __slots__ = ("pairs", "future")

    def __init__(self, pairs: list, future: Future):
        self.pairs = pairs
        self.future = future


class MicroBatcher:
    """Thread-safe evaluation front: ``submit()`` returns a Future resolved
    by the dispatch thread with a final AdmissionResponse (service-layer
    constraints and metrics applied) or an EvaluationError.

    Round 10 adds a second, BEST-EFFORT priority lane
    (:meth:`submit_audit`) for the background audit scanner: audit
    batches dispatch only when the live lane is empty and the measured
    device-RTT estimate fits inside the deadline slack, at most ONE
    audit dispatch is in flight at any moment, audit work runs on its
    own single-thread pool (never occupying the live lane's
    encode/dispatch double-buffer pools), and a popped-but-undispatched
    audit batch is re-queued the instant live work arrives — so live p99
    can degrade by at most one in-flight audit dispatch, ever."""

    def __init__(
        self,
        env: EvaluationEnvironment,
        max_batch_size: int = 128,
        batch_timeout_ms: float = 1.0,
        policy_timeout: float | None = 2.0,
        queue_capacity: int | None = None,
        host_fastpath_threshold: int = 64,
        latency_budget_ms: float = 50.0,
        request_timeout_ms: float = 0.0,
        degraded_mode: str = "oracle",
        shadow_recorder: Any = None,
        audit_tracker: Any = None,
        verdict_matrix: Any = None,
        admission: Any = None,
        scheduler: Any = None,
        tenant: str = "default",
    ) -> None:
        self.env = env
        # -- multi-tenant serving (round 16, tenancy.py) ------------------
        # admission: the tenant's TenantAdmission quota (token-bucket
        # rows/s + in-flight cap), consulted once per submit burst;
        # scheduler: the process-wide FairDispatchScheduler every tenant
        # batcher acquires a dispatch slot from (live > weighted shares >
        # audit); tenant: this batcher's tenant name — also the ambient
        # failpoint scope its evaluation threads carry so chaos can fault
        # ONE tenant. All None/"default" on single-tenant deployments:
        # the dispatch path is then bit-identical to round 15.
        self.admission = admission
        self.scheduler = scheduler
        self.tenant = tenant
        # shard failpoint scope (round 22, runtime/shards.py): set by
        # the ShardRouter to "shard-<i>" so a scoped shard.dispatch arm
        # kills ONE shard's dispatch thread; None (scope passthrough)
        # for unsharded batchers
        self.failpoint_scope: str | None = None
        # policy-lifecycle shadow recorder (lifecycle.ShadowRecorder):
        # every formed batch's (policy_id, request) pairs feed the
        # hot-reload canary's replay ring. None = disabled (no reload
        # machinery); one deque-extend per BATCH, never per request.
        self.shadow_recorder = shadow_recorder
        # audit dirty-set tracker (audit.SnapshotStore): every VALIDATE
        # request in a formed batch is recorded (keyed GVK+ns+name, later
        # admissions supersede) so the background scanner re-judges what
        # was actually admitted. Same one-call-per-batch discipline as
        # the shadow recorder. None = audit disabled.
        self.audit_tracker = audit_tracker
        # verdict matrix (round 23, audit/matrix.py): lookup admission —
        # a /validate UPDATE whose canonical payload is byte-identical
        # (uid normalized out) to the row the audit lane already judged,
        # for a column whose content fingerprint matches the serving
        # set, answers from the precomputed verdict as a pre-serialized
        # fragment BEFORE shed/quota/queue. Eligibility is the fragment
        # lane's own proof plus a hookless target, so the lookup verdict
        # and the full-evaluation verdict are the same bytes. None =
        # matrix off (the pre-round-23 submit paths, bit-identical).
        self.verdict_matrix = verdict_matrix
        self.max_batch_size = max(1, int(max_batch_size))
        self.batch_timeout = max(0.0, batch_timeout_ms) / 1e3
        self.policy_timeout = policy_timeout
        # Propagated request deadline (--request-timeout-ms; aligned to
        # the webhook timeoutSeconds model, distinct from policy_timeout
        # — the per-EVALUATION bound). ≤0 disables deadline propagation
        # and load shedding entirely (the pre-round-7 behavior).
        self.request_timeout = (
            request_timeout_ms / 1e3 if request_timeout_ms > 0 else None
        )
        # what to serve while the device breaker is fully tripped:
        # 'oracle' (default) = bit-exact host verdicts, 'monitor' =
        # accept-all monitor-mode verdicts, 'reject' = in-band 503s
        self.degraded_mode = degraded_mode
        # Deadline-aware routing: beyond the static
        # fast-path count, a batch is answered host-side whenever the
        # MEASURED device round-trip estimate would blow the oldest
        # item's latency budget and the host estimate would not. The
        # budget is a soft serving target (p99 goal), distinct from
        # policy_timeout (the hard in-band deadline). ≤0 disables.
        self.latency_budget = (
            None if latency_budget_ms <= 0 else latency_budget_ms / 1e3
        )
        # EWMA device dispatch RTT per batch bucket, seconds — learned
        # from real dispatches (seeded by timed warmup); decayed slightly
        # each time budget routing bypasses the device so a stale slow
        # estimate re-probes instead of pinning traffic host-side forever.
        self._dev_rtt: dict[int, float] = {}
        # EWMA host fast-path cost per row, seconds
        self._host_cost_per_row = 1e-4
        # Latency fast-path: a formed batch with ≤ this many runnable items
        # is answered by the environment's targeted host oracle (bit-exact
        # with the device program by the differential suite) instead of
        # paying a device round-trip — the batched analog of the
        # reference's per-request sync path (src/api/handlers.rs:256-286).
        # 0 disables. The count is an upper bound, not the test for low
        # occupancy: in a closed loop at saturation batches form at ~60
        # rows with every pipeline slot taken (their size is how fast the
        # loop drains, not how shallow the queue is), so the route is
        # taken only while the pipeline has a slot to spare
        # (_launch_batch, _evaluate_runnable).
        self.host_fastpath_threshold = max(0, int(host_fastpath_threshold))
        self._env_fastpath = bool(
            getattr(env, "supports_host_fastpath", False)
        )
        self._queue: queue.Queue[_Pending] = queue.Queue(
            maxsize=queue_capacity or self.max_batch_size * 8
        )
        self._stop = threading.Event()
        self._stopping = False
        self._thread: threading.Thread | None = None
        from policy_server_tpu.runtime.workers import DaemonExecutor

        self._overload_pool = DaemonExecutor(
            max_workers=8, thread_name_prefix="overload-wait"
        )
        # Pipeline pool: when a policy timeout is configured, the whole
        # fused encode→device→fetch chain (_fused_validate) runs here
        # under the dispatch watchdog instead of on the dispatch thread,
        # so a compile stall or a hung transport cannot wedge the
        # batching loop. Round 19 fused the former encode/device pool
        # pair into this one pool: a batch is ONE worker submission, and
        # cross-batch double-buffering comes from the pool width (batch
        # N+1 encodes on a second worker while batch N's fetch blocks on
        # the first). The width bounds leaked threads under a persistent
        # hang — once every worker is wedged, later batches never start
        # and their items resolve in-band via the same watchdog timeout,
        # which is exactly the reference's behavior when every
        # evaluation hits the epoch deadline (src/lib.rs:176-190).
        # Daemon threads (workers.py): a wedged call is abandoned at
        # exit, never joined.
        self._device_pool = DaemonExecutor(
            max_workers=4, thread_name_prefix="device-dispatch"
        )
        # Batch-pipeline pool: the dispatch loop only FORMS batches; each
        # batch's host phases + watchdog wait run here, so consecutive
        # batches overlap (encode of batch N+1 overlaps device time of
        # batch N) and one wedged batch never serializes its followers.
        # The semaphore matches the pool width so a formed batch starts
        # (and its watchdog arms) immediately — a batch is either running
        # with a live watchdog, or its requests are still in the submission
        # queue under the bounded-wait overload rules.
        self._batch_workers = 4
        self._batch_pool = DaemonExecutor(
            max_workers=self._batch_workers, thread_name_prefix="batch"
        )
        self._inflight = threading.BoundedSemaphore(self._batch_workers)
        # live batches holding a slot of _inflight (the semaphore's own
        # value is private): what the host fast-path reads as occupancy
        self._batches_inflight = 0  # guarded-by: _stats_lock
        # _dispatch runs on concurrent batch-pool workers: counter updates
        # must be locked (+= is a racy read-modify-write).
        self._stats_lock = threading.Lock()
        self.batches_dispatched = 0  # guarded-by: _stats_lock
        self.requests_dispatched = 0  # guarded-by: _stats_lock
        self.deadline_abandoned_batches = 0  # guarded-by: _stats_lock
        self.host_fastpath_batches = 0  # guarded-by: _stats_lock
        # batches at or under the threshold that went to the device
        # because the pipeline was full when they were handed to it
        self.host_fastpath_declined_batches = 0  # guarded-by: _stats_lock
        # batches routed host-side by the latency-budget check (a strict
        # subset of host_fastpath_batches)
        self.budget_routed_batches = 0  # guarded-by: _stats_lock
        # -- resilience counters (round 7; /metrics surface) --------------
        # requests shed at admission (429 + Retry-After)
        self.shed_requests = 0  # guarded-by: _stats_lock
        # already-expired rows dropped before encode/dispatch
        self.expired_dropped = 0  # guarded-by: _stats_lock
        # requests answered by the --degraded-mode policy while the
        # device breaker was fully tripped (monitor/reject modes only)
        self.degraded_responses = 0  # guarded-by: _stats_lock
        # cumulative ns spent between submission and batch formation —
        # the queue leg of the framing-vs-queue-vs-device decomposition
        # the bench http lines report (round 11)
        self.queue_wait_ns = 0  # guarded-by: _stats_lock
        # -- bulk submission (round 12) -----------------------------------
        # submit_many calls and the rows they carried (avg burst size =
        # rows / calls — the array-at-a-time admission metric)
        self.bulk_submits = 0  # guarded-by: _stats_lock
        self.bulk_submitted_rows = 0  # guarded-by: _stats_lock
        # -- phase-1 memos (immutable post-boot registry; an epoch flip
        # builds a NEW batcher, so staleness is impossible) ---------------
        # policy ids whose PolicyID.parse is known-good (pre_evaluate's
        # only per-row work when no always-accept namespace is configured)
        self._preparsed_ok: set[str] = set()  # graftcheck: lockfree — GIL-atomic set add; racing adders insert the same id
        # policy id -> True when the target has NO pre-eval hooks (the
        # common case: the whole hook machinery is skipped per batch)
        self._hookless: dict[str, bool] = {}  # graftcheck: lockfree — GIL-atomic dict ops; racing builders store identical values
        # fragment-lane metric memo (round 19): label-tuple -> built
        # metric dataclass, replacing per-row dataclass construction on
        # the cache-hit fast lane (bounded; see _metric_of)
        self._metric_memo: dict[tuple, Any] = {}  # graftcheck: lockfree — GIL-atomic dict ops; racing builders store identical values
        # -- audit lane counters (round 10; /metrics surface) -------------
        # best-effort audit batches actually dispatched
        self.audit_batches_dispatched = 0  # guarded-by: _stats_lock
        # rows those batches carried
        self.audit_rows_dispatched = 0  # guarded-by: _stats_lock
        # -- lookup-admission counters (round 23; /metrics surface) -------
        # requests answered from the verdict matrix without dispatch
        self.matrix_lookup_hits = 0  # guarded-by: _stats_lock
        # eligible requests the matrix could not answer (no cell, stale
        # column fingerprint, payload drift, ineligible template)
        self.matrix_lookup_misses = 0  # guarded-by: _stats_lock
        # wall time of audit_tracker.observe on the dispatch path: what
        # feeding the scanner's snapshot costs every live batch
        self.audit_observe_ns = 0  # guarded-by: _stats_lock
        self._audit_observe_failed = False  # the failure was logged once
        # audit batches popped for dispatch but re-queued because live
        # work arrived first (the preemption contract in action)
        self.audit_preemptions = 0  # guarded-by: _stats_lock
        # -- the best-effort audit lane -----------------------------------
        # Jobs wait in a deque (appendleft on preemption so a re-queued
        # batch keeps its place at the head); dispatch happens on a
        # DEDICATED single-thread pool so audit work can never occupy a
        # live batch-pipeline/encode/device pool slot, and the pool width
        # (1) IS the one-in-flight cap.
        self._audit_lock = threading.Lock()
        self._audit_jobs: collections.deque[_AuditJob] = (
            collections.deque()
        )  # guarded-by: _audit_lock
        self._audit_inflight = False  # guarded-by: _audit_lock
        self._audit_pool = DaemonExecutor(
            max_workers=1, thread_name_prefix="audit-dispatch"
        )

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "MicroBatcher":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="micro-batcher", daemon=True
            )
            self._thread.start()
        return self

    # -- self-heal surface (round 17, supervision.SelfHealWatchdog) --------

    def dispatch_wedged(self) -> bool:
        """True when the dispatch loop thread DIED outside shutdown — a
        zombie batcher: submissions still enqueue, nothing ever forms a
        batch, every request times out while readiness answers 200."""
        t = self._thread
        return (
            t is not None
            and not t.is_alive()
            and not self._stopping
            and not self._stop.is_set()
        )

    def revive_dispatch(self) -> bool:
        """Rebuild a dead dispatch loop (the self-heal watchdog's repair
        action): queued work is still in the submission queue, the pools
        are still up — only the forming loop needs a fresh thread.
        Returns False when there is nothing to revive (alive, never
        started, or shutting down)."""
        if not self.dispatch_wedged():
            return False
        self._thread = threading.Thread(
            target=self._loop, name="micro-batcher-revived", daemon=True
        )
        self._thread.start()
        return True

    def shutdown(self) -> None:
        """Stop the dispatch thread and resolve every queued/waiting future.

        The batcher BORROWS its environment — it never closes it. The owner
        (the server that built it, or a test fixture) calls
        ``environment.close()`` at its own teardown; two batchers may share
        one environment, and shutting one down must not disable the other.
        """
        # Reject new submissions and wake overload waiters into the reject
        # path BEFORE draining, so a waiter whose put succeeds after the
        # drain below cannot strand an unresolved future.
        self._stopping = True
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        # In-flight batches finish resolving their futures (bounded by the
        # watchdog when a policy timeout is configured).
        self._batch_pool.shutdown(wait=True)
        # Drain: requests still queued must not leave their futures
        # unresolved (handlers await them).
        self._drain_rejecting()
        # Overload waiters sleep in bounded slices (_put_waiting), so every
        # one observes _stopping within a slice and rejects itself — even
        # when the queue is still full (waiter count can exceed capacity).
        # Joining the pool guarantees each waiter either rejected or
        # enqueued; the second drain resolves anything enqueued post-drain.
        self._overload_pool.shutdown(wait=True)
        self._drain_rejecting()
        # wait=False: a wedged device call must not block shutdown — its
        # futures were already resolved by the watchdog.
        self._device_pool.shutdown(wait=False)
        # audit lane: queued jobs reject (the scanner catches and re-marks
        # its keys dirty); an in-flight dispatch is abandoned, never joined
        self._drain_audit_rejecting()
        self._audit_pool.shutdown(wait=False)

    def _drain_rejecting(self) -> None:
        while True:
            try:
                p = self._queue.get_nowait()
            except queue.Empty:
                break
            self._resolve(
                p,
                AdmissionResponse.reject(
                    p.request.uid(), "policy server shutting down", 503
                ),
            )

    def fence_drain(self) -> list[_Pending]:
        """Atomically remove every not-yet-dispatched row from the
        submission queue (the shard router's fencing action, round 22).
        A row still queued is provably owned by NO batch worker — its
        future/sink has never been touched — so the router may re-route
        it to a sibling shard (preserving its deadline, trace context,
        and tenant quota token: no re-admission, the eventual resolution
        releases the quota exactly once) or answer it 503+Retry-After,
        without any double-answer window. Rows already popped by the
        dispatch loop resolve through their batch worker as usual (the
        batch pools survive a dead dispatch thread).

        ``unfinished_tasks`` is deliberately left alone (nothing joins
        on this queue); full-queue overload waiters are woken so they
        observe the freed space."""
        q = self._queue
        with q.mutex:
            taken = list(q.queue)
            q.queue.clear()
            for p in taken:
                p.owner = None  # ownership passes to the fencing router
            q.not_full.notify_all()
        return taken

    def queue_depth(self) -> int:
        """Requests currently waiting for batch formation (introspection
        for the /metrics runtime gauges)."""
        return self._queue.qsize()

    def stats_snapshot(self) -> dict[str, int]:
        """Every _stats_lock-guarded counter under ONE lock acquisition —
        the /metrics scrape's consistent view (a bare attribute read from
        another module would be the dirty cross-module read the
        guarded-by annotations forbid; graftcheck is module-scoped, so
        this method is how the contract survives the module boundary)."""
        with self._stats_lock:
            return {
                "batches_dispatched": self.batches_dispatched,
                "requests_dispatched": self.requests_dispatched,
                "deadline_abandoned_batches": self.deadline_abandoned_batches,
                "host_fastpath_batches": self.host_fastpath_batches,
                "host_fastpath_declined_batches": (
                    self.host_fastpath_declined_batches
                ),
                "budget_routed_batches": self.budget_routed_batches,
                "shed_requests": self.shed_requests,
                "expired_dropped": self.expired_dropped,
                "degraded_responses": self.degraded_responses,
                "queue_wait_ns": self.queue_wait_ns,
                "bulk_submits": self.bulk_submits,
                "bulk_submitted_rows": self.bulk_submitted_rows,
                "audit_batches_dispatched": self.audit_batches_dispatched,
                "audit_rows_dispatched": self.audit_rows_dispatched,
                "audit_preemptions": self.audit_preemptions,
                "audit_observe_ns": self.audit_observe_ns,
                "matrix_lookup_hits": self.matrix_lookup_hits,
                "matrix_lookup_misses": self.matrix_lookup_misses,
            }

    def estimated_wait(self) -> float:
        """Rough seconds until a request enqueued NOW would dispatch:
        queue depth in batches × the measured device-RTT EWMA for the
        serving bucket, divided by the batch-pipeline width. This is the
        load-shedding admission signal — deliberately cheap (two dict
        reads, no locks) and deliberately pessimism-free: shedding on an
        inflated estimate would turn a clearable burst into 429s."""
        depth = self._queue.qsize()
        if depth <= 0:
            return 0.0
        bucket = bucket_size(self.max_batch_size)
        rtt = self._dev_rtt.get(bucket)
        if rtt is None:
            # no device measurement yet (cold boot / host-only traffic):
            # fall back to the host-cost estimate for a full batch
            rtt = self._host_cost_per_row * self.max_batch_size
        batches = math.ceil(depth / self.max_batch_size)
        return batches * rtt / self._batch_workers

    def _shed_check(self, pending: "_Pending") -> None:
        """Admission-time load shedding: raise ShedError when the queue's
        estimated wait already exceeds this request's deadline budget.
        No-op unless a request timeout is configured."""
        if pending.deadline is None:
            return
        est = self.estimated_wait()
        if est > pending.deadline - time.perf_counter():
            with self._stats_lock:
                self.shed_requests += 1
            raise ShedError(est)

    def _admit_quota(self, pendings: list["_Pending"]) -> None:
        """Tenant admission (round 16): count the burst against the
        tenant's token bucket + in-flight cap; a denial raises ShedError
        (HTTP 429 + Retry-After) and counts into BOTH the tenant-
        labelled admission counters and this batcher's shed counter.
        No-op without an admission quota (single-tenant deployments)."""
        adm = self.admission
        if adm is None:
            return
        try:
            adm.admit(len(pendings))
        except ShedError:
            with self._stats_lock:
                self.shed_requests += len(pendings)
            raise
        for p in pendings:
            p.quota_token = adm

    @staticmethod
    def _release_quota(p: "_Pending") -> None:
        """Release one admitted row's in-flight claim exactly once (the
        first resolution clears the token; TenantAdmission floors at
        zero so the rare shutdown double-resolve stays harmless)."""
        tok = p.quota_token
        if tok is not None:
            p.quota_token = None
            tok.release(1)

    def _scoped(self, fn, *args, **kwargs):
        """Run ``fn`` under this batcher's tenant failpoint scope —
        evaluation work crosses to pool threads, and tenant-scoped chaos
        (failpoints.scope) must travel with it."""
        with failpoints.scope(self.tenant):
            return fn(*args, **kwargs)

    def _scoped_rec(self, bid: int, fn, *args, **kwargs):
        """_scoped plus the flight-recorder batch scope: the
        environment's phase events (encode, fetch, bookkeeping) must
        attribute to the submitting batch across the encode/device pool
        boundary, exactly like tenant-scoped chaos."""
        with failpoints.scope(self.tenant), flightrec.batch_scope(bid):
            return fn(*args, **kwargs)

    def _scoped_rec_timed(self, bid: int, fn, *args, **kwargs):
        """_scoped_rec returning ``(result, start_ns, end_ns)`` — the
        worker-side stamps let the submitting batch worker measure the
        POOL HANDOFF gaps (submit → worker pickup, work end → future
        wake) as the flight recorder's ``handoff`` phase. Round 18's
        first phase-report runs found exactly this gap as the dominant
        unattributed dispatch time on the sandboxed kernel (condition-
        variable wakes ride the GIL switch interval)."""
        with failpoints.scope(self.tenant), flightrec.batch_scope(bid):
            t0 = time.perf_counter_ns()
            out = fn(*args, **kwargs)
            return out, t0, time.perf_counter_ns()

    def warmup(self) -> None:
        """Compile every batch bucket at boot (reference precompiles all
        policies via rayon at boot, src/lib.rs:287-307) and seed the
        device-RTT estimator: each bucket warms twice, the second —
        compile-free — run is the routing baseline (a compile-inclusive
        seed would misroute everything host-side until corrected)."""
        sizes = []
        b = 1
        while b < self.max_batch_size:
            sizes.append(b)
            b <<= 1
        sizes.append(bucket_size(self.max_batch_size))
        self.env.warmup(tuple(sizes))
        if self.latency_budget is not None or self.request_timeout is not None:
            # one warmup((b,)) call dispatches once per shape schema, per
            # SHARD (PolicyShardedEvaluator warms every shard
            # sequentially) — a serving batch dispatches exactly once, so
            # divide by the environment's own accounting. The old code
            # read len(env.schemas), which the sharded evaluator does not
            # expose, overestimating per-dispatch RTT by shards×schemas
            # and biasing early routing host-side (ADVICE r5 #4).
            per_warmup = max(
                1, int(getattr(self.env, "warmup_dispatches", 0) or 0)
            )
            for b in sizes:
                t0 = time.perf_counter()
                self.env.warmup((b,))
                self._dev_rtt[bucket_size(b)] = (
                    time.perf_counter() - t0
                ) / per_warmup

    # -- submission --------------------------------------------------------

    def _try_matrix(self, p: "_Pending") -> bool:
        """Lookup admission (round 23): answer this request from the
        verdict matrix when every soundness gate holds — VALIDATE origin,
        UPDATE operation (a CREATE/DELETE changes the inventory by
        definition), no always-accept namespace short-circuit, a hookless
        target (pre-eval hooks see request context a precomputed verdict
        never saw), and the matrix's own gates (payload byte-identity
        with the judged row, current column fingerprint, fragment
        eligibility). A hit resolves the pending in-band as a FragVerdict
        — same completion shape as the round-19 cache-hit lane — before
        shed/quota/queue ever see it. Returns False untouched on any
        miss (the caller proceeds down the normal path)."""
        matrix = self.verdict_matrix
        if matrix is None or p.origin is not service.RequestOrigin.VALIDATE:
            return False
        adm = p.request.admission_request
        if adm is None or (adm.operation or "").upper() != "UPDATE":
            return False
        if getattr(self.env, "always_accept_namespace", None) is not None:
            return False
        if self._target_hookless(p.policy_id) is not True:
            return False
        tmpl = matrix.lookup(p.policy_id, p.request, self.env)
        if not tmpl:
            with self._stats_lock:
                self.matrix_lookup_misses += 1
            return False
        done_at = time.perf_counter()
        with self._stats_lock:
            self.matrix_lookup_hits += 1
        try:
            service._registry().record_evaluations_batch(  # noqa: SLF001
                [((done_at - p.enqueued_at) * 1e3, self._metric_of(p, tmpl))]
            )
        except Exception:  # noqa: BLE001 — metrics must not fail serving
            pass
        verdict = FragVerdict(p.request.uid(), tmpl)
        # NOT recorded to the audit tracker: the payload is byte-identical
        # to the inventory row the verdict came from — re-observing would
        # dirty the row and re-judge what the hit just proved current
        self._resolve(
            p, verdict if p.sink is not None else verdict.to_response()
        )
        return True

    def submit(
        self,
        policy_id: str,
        request: ValidateRequest,
        origin: service.RequestOrigin,
    ) -> Future:
        """Enqueue one evaluation; Future resolves to AdmissionResponse or
        raises EvaluationError. A full queue WAITS for space — the analog of
        the reference waiting on its semaphore (handlers.rs:262-266) — but
        bounded by the policy timeout, so a burst is absorbed and only
        sustained overload degrades, with a clear in-band 429. With a
        request timeout configured, admission may instead raise ShedError
        when the estimated wait already exceeds the deadline budget."""
        pending = _Pending(policy_id, request, origin, Future())
        if self.request_timeout is not None:
            pending.deadline = pending.enqueued_at + self.request_timeout
        if self._stopping:
            self._reject_stopping(pending)
            return pending.future
        if self._try_matrix(pending):
            return pending.future
        self._shed_check(pending)
        self._admit_quota([pending])
        self._put_waiting(pending)
        return pending.future

    # Overload waiters sleep in bounded slices so every blocked enqueue
    # observes shutdown within one slice — an unbounded queue.put can block
    # past the drain (capacity < waiter count) and deadlock shutdown's
    # pool join while stranding its future.
    _WAIT_SLICE_SECONDS = 0.05

    def _put_waiting(self, pending: _Pending) -> bool:
        """Blocking enqueue honoring overload semantics: waits for queue
        space up to the request's remaining deadline (unbounded when the
        policy timeout is disabled — reference parity with waiting on the
        semaphore, handlers.rs:262-266), but always observing ``_stopping``.
        Returns True when enqueued; False when resolved in-band (429/503)."""
        while True:
            if self._stopping:
                self._reject_stopping(pending)
                return False
            bounds = []
            if self.policy_timeout is not None:
                bounds.append(
                    pending.enqueued_at + self.policy_timeout
                )
            if pending.deadline is not None:
                # waiting past the propagated request deadline is dead
                # work — the webhook caller already gave up
                bounds.append(pending.deadline)
            if not bounds:
                wait = self._WAIT_SLICE_SECONDS
            else:
                now = time.perf_counter()
                remaining = min(bounds) - now
                if remaining <= 0:
                    # same failure mode, same answer: a wait that ran out
                    # the PROPAGATED deadline is an expired drop (504,
                    # counted), not a generic overload 429 — the caller's
                    # webhook timed out either way, and the expired-drop
                    # counter must see every pre-dispatch deadline death
                    if (
                        pending.deadline is not None
                        and now >= pending.deadline
                    ):
                        self._reject_expired(pending)
                    else:
                        self._reject_overloaded(pending)
                    return False
                wait = min(self._WAIT_SLICE_SECONDS, remaining)
            try:
                self._queue.put(pending, timeout=wait)
                pending.owner = self  # shard-ownership token (round 22)
            except queue.Full:
                continue
            # Close the stranding window: shutdown may have completed BOTH
            # of its drains between our _stopping check and this put — the
            # item would then sit in a never-again-drained queue. Re-check
            # and self-drain; duplicate rejection is harmless (_resolve
            # tolerates already-done futures, sink delivery double-sends
            # at worst a late 503 the frontend drops).
            if self._stopping and (
                pending.future is None or not pending.future.done()
            ):
                self._drain_rejecting()
            return True

    def submit_nowait(
        self,
        policy_id: str,
        request: ValidateRequest,
        origin: service.RequestOrigin,
    ) -> Future:
        """submit() for callers that must never block (the native
        frontend's drainer thread): sheds exactly like submit(), but a
        full queue parks the bounded overload wait on the batcher's own
        executor and returns the Future immediately — the caller's
        done-callback sees the verdict, the bounded-wait 429, or the
        shutdown 503."""
        pending = _Pending(policy_id, request, origin, Future())
        if self.request_timeout is not None:
            pending.deadline = pending.enqueued_at + self.request_timeout
        if self._stopping:
            self._reject_stopping(pending)
            return pending.future
        if self._try_matrix(pending):
            return pending.future
        self._shed_check(pending)
        self._admit_quota([pending])
        try:
            self._queue.put_nowait(pending)
            pending.owner = self  # shard-ownership token (round 22)
            # same stranding window as _put_waiting: shutdown may have
            # finished both drains between the check above and this put
            if self._stopping and not pending.future.done():
                self._drain_rejecting()
            return pending.future
        except queue.Full:
            pass
        try:
            self._overload_pool.submit(self._put_waiting, pending)
        except RuntimeError:  # pool already shut down (stop race)
            self._reject_stopping(pending)
        return pending.future

    def submit_many(
        self,
        items: list[tuple[str, ValidateRequest]],
        origin: service.RequestOrigin,
        sink: Any = None,
        tokens: list | None = None,
        trace_ctxs: list | None = None,
    ) -> list[Future] | None:
        """Array-at-a-time admission (round 12): enqueue a whole burst
        with ONE deadline stamp, ONE shed estimate, and ONE queue-lock
        acquisition instead of per-row submit_nowait calls — the
        ring-pop → submit hop was the dominant per-request Python in the
        round-11 profile.

        Two completion modes:

        * ``sink=None`` — returns one Future per item (submit_nowait
          parity; a shed burst resolves every future with ShedError
          instead of raising, since a bulk call cannot raise per row).
        * ``sink`` + ``tokens`` — batch-granular completion:
          ``sink.deliver_many([(token, response, exc), ...])`` fires once
          per dispatched batch (the native frontend's MPSC fill becomes
          one call per batch). No Futures are allocated at all.

        Deadline/shed semantics match submit_nowait: every row is
        stamped with the same admission instant, so the burst sheds or
        admits as a unit; rows that outlive their deadline in the queue
        still drop pre-encode per row.

        ``trace_ctxs`` (round 18): an optional parallel list of
        per-row ``otlp.SpanContext`` parents — the native frontend
        propagates incoming W3C ``traceparent`` headers through here so
        webhook-originated traces correlate end-to-end. Rows with None
        keep the burst's ambient context (usually none on the native
        path)."""
        now = time.perf_counter()
        deadline = (
            now + self.request_timeout
            if self.request_timeout is not None
            else None
        )
        trace_ctx = otlp.current_span_context()
        pendings: list[_Pending] = []
        futures: list[Future] | None = [] if sink is None else None
        for i, (policy_id, request) in enumerate(items):
            p = _Pending(
                policy_id, request, origin,
                Future() if sink is None else None,
                enqueued_at=now,
                trace_ctx=(
                    trace_ctxs[i] if trace_ctxs is not None
                    and trace_ctxs[i] is not None else trace_ctx
                ),
            )
            p.deadline = deadline
            if sink is not None:
                p.sink = sink
                p.token = tokens[i]
            else:
                futures.append(p.future)
            pendings.append(p)
        with self._stats_lock:
            self.bulk_submits += 1
            self.bulk_submitted_rows += len(pendings)
        if self._stopping:
            for p in pendings:
                self._reject_stopping(p)
            return futures
        if self.verdict_matrix is not None:
            # lookup admission per row BEFORE the burst-level shed/quota:
            # a hit resolves in-band and must not consume queue space or
            # tenant quota for work that will never dispatch
            pendings = [p for p in pendings if not self._try_matrix(p)]
            if not pendings:
                return futures
        if deadline is not None:
            est = self.estimated_wait()
            if est > self.request_timeout:
                with self._stats_lock:
                    self.shed_requests += len(pendings)
                err = ShedError(est)
                for p in pendings:
                    self._fail(p, err)
                return futures
        if self.admission is not None:
            try:
                self._admit_quota(pendings)
            except ShedError as err:
                # a bulk call cannot raise per row: resolve the whole
                # burst with the same 429 the per-row path raises
                for p in pendings:
                    self._fail(p, err)
                return futures
        overflow = self._put_burst(pendings)
        # same stranding window as submit_nowait: shutdown may have
        # finished both drains between the check above and the burst put
        if self._stopping:
            self._drain_rejecting()
        for p in overflow:
            try:
                self._overload_pool.submit(self._put_waiting, p)
            except RuntimeError:  # pool already shut down (stop race)
                self._reject_stopping(p)
        return futures

    def _put_burst(self, pendings: list[_Pending]) -> list[_Pending]:
        """Enqueue as many rows as fit under ONE acquisition of the
        queue's internal mutex (the documented stdlib internals: the same
        deque/condition ``queue.Queue.put`` uses, minus the per-item lock
        round-trips). Returns the rows that did not fit — the caller
        parks them on the bounded overload wait."""
        q = self._queue
        with q.mutex:
            space = (
                q.maxsize - len(q.queue) if q.maxsize > 0 else len(pendings)
            )
            take = pendings[: max(0, space)]
            if take:
                for p in take:
                    p.owner = self  # ownership stamped under the mutex
                q.queue.extend(take)
                q.unfinished_tasks += len(take)
                # one consumer (the dispatch loop): a single notify wakes
                # it and it drains greedily
                q.not_empty.notify()
        return pendings[len(take):]

    async def submit_async(
        self,
        policy_id: str,
        request: ValidateRequest,
        origin: service.RequestOrigin,
    ) -> Future:
        """submit() for event-loop callers: never blocks the loop. The fast
        path is a lock-free put; a full queue parks the wait on the
        batcher's OWN overload executor (not the loop's shared default
        executor — overload waits must never starve unrelated
        run_in_executor users) and returns the Future IMMEDIATELY — the
        caller awaits the future, which delivers the verdict, the 429
        after the bounded wait, or the 503 at shutdown. Waiters sleep in
        bounded slices (_put_waiting) so they observe shutdown; admission
        under sustained overload is therefore approximately oldest-first
        (a waiter re-entering after a slice can be leapfrogged within one
        slice window), not strictly FIFO — the trade accepted for a
        shutdown that can never strand a blocked waiter. Thread count is
        bounded by the pool width."""
        loop = asyncio.get_running_loop()
        pending = _Pending(policy_id, request, origin, Future())
        pending.aio_loop = loop
        pending.aio_future = loop.create_future()
        if self.request_timeout is not None:
            pending.deadline = pending.enqueued_at + self.request_timeout
        if self._stopping:
            self._reject_stopping(pending)
            return pending.aio_future
        if self._try_matrix(pending):
            return pending.aio_future
        self._shed_check(pending)
        self._admit_quota([pending])
        try:
            self._queue.put_nowait(pending)
            # same stranding window as the sync path (_put_waiting):
            # shutdown may have finished both drains between the _stopping
            # check above and this put — self-drain if so.
            if self._stopping and not pending.future.done():
                self._drain_rejecting()
            return pending.aio_future
        except queue.Full:
            pass
        try:
            self._overload_pool.submit(self._put_waiting, pending)
        except RuntimeError:  # pool already shut down (stop race)
            self._reject_stopping(pending)
        return pending.aio_future

    def _reject_overloaded(self, pending: _Pending) -> None:
        self._resolve(
            pending,
            AdmissionResponse.reject(
                pending.request.uid(), "policy server overloaded", 429
            ),
        )

    def _reject_stopping(self, pending: _Pending) -> None:
        self._resolve(
            pending,
            AdmissionResponse.reject(
                pending.request.uid(), "policy server shutting down", 503
            ),
        )

    def evaluate(
        self,
        policy_id: str,
        request: ValidateRequest,
        origin: service.RequestOrigin,
        timeout: float | None = None,
    ) -> AdmissionResponse:
        """Blocking convenience wrapper around submit()."""
        return self.submit(policy_id, request, origin).result(timeout=timeout)

    # -- best-effort audit lane (round 10) ---------------------------------

    def submit_audit(self, pairs: list) -> Future:
        """Enqueue one audit batch on the best-effort lane. The Future
        resolves to ``validate_batch``-shaped results (raw verdicts /
        per-item Exceptions) once an idle slot dispatches it — which may
        be arbitrarily later under sustained live load; the lane offers
        NO latency promise, that is the point. Raises nothing: a
        stopping batcher rejects via the future."""
        future: Future = Future()
        job = _AuditJob(list(pairs), future)
        if self._stopping:
            future.set_exception(
                RuntimeError("batcher shutting down; audit lane closed")
            )
            return future
        with self._audit_lock:
            self._audit_jobs.append(job)
        # close the stranding window: shutdown may have drained the lane
        # between the check above and the append — self-drain if so (the
        # same discipline as _put_waiting on the live lane)
        if self._stopping:
            self._drain_audit_rejecting()
        return future

    def audit_lane_depth(self) -> int:
        """Audit batches waiting for an idle slot (the /metrics gauge)."""
        with self._audit_lock:
            return len(self._audit_jobs)

    def cancel_audit(self, future: Future) -> bool:
        """Remove a not-yet-dispatched audit job from the lane — the
        scanner abandons a job it timed out waiting on, and without
        this removal every retry would stack a duplicate job that later
        burns an idle dispatch on results nobody reads. Returns False
        when the job is gone (already dispatched or drained); the one
        in-flight dispatch it may be burning is the bounded waste the
        lane already accepts."""
        with self._audit_lock:
            for job in self._audit_jobs:
                if job.future is future:
                    self._audit_jobs.remove(job)
                    break
            else:
                return False
        try:
            future.set_exception(
                RuntimeError("audit job cancelled by its submitter")
            )
        except Exception:  # noqa: BLE001 — already-done race
            pass
        return True

    def _audit_slack_ok(self, audit_rows: int) -> bool:
        """True when dispatching one audit batch of ``audit_rows`` NOW
        cannot break a live request that arrives right after: the live
        lane is already empty (caller checked, so the EWMA queue-wait
        estimate is zero), the device breaker is not fully open (open
        shards pause audit instead of burning oracle capacity), and the
        estimated device hold time OF THAT BATCH — the per-bucket RTT
        EWMA scaled by how many max-size chunks the audit rows span,
        since --audit-batch-size may exceed the live batch size — fits
        inside half the propagated request-deadline budget, so a live
        batch formed behind the single in-flight audit dispatch still
        admits and meets its deadline. The SOFT latency budget
        deliberately does not gate here: a live batch that forms while
        an audit dispatch holds the device is re-routed host-side by the
        latency-budget router, so the p99 target defends itself."""
        if getattr(self.env, "breaker_all_open", False):
            return False
        if self.request_timeout is None:
            return True
        bucket = bucket_size(self.max_batch_size)
        rtt = self._dev_rtt.get(bucket)
        if rtt is None:
            # no device measurement yet: the first audit dispatch IS the
            # measurement (warmup normally seeds this before serving)
            return True
        hold_est = rtt * max(1, math.ceil(audit_rows / bucket))
        return hold_est <= 0.5 * self.request_timeout

    def _maybe_dispatch_audit(self) -> None:
        """Called by the dispatch loop ONLY when the live queue came up
        empty: admit at most one audit batch onto the (width-1) audit
        pool, and only while a pipeline worker is free. Slack is evaluated
        before taking the lane lock — it reads the environment's breaker
        state, and lock-order discipline keeps _audit_lock innermost."""
        if self._stopping:
            return
        with self._audit_lock:
            if self._audit_inflight or not self._audit_jobs:
                return
            head_rows = len(self._audit_jobs[0].pairs)
        if not self._inflight.acquire(blocking=False):
            # the queue is empty because every pipeline worker holds a
            # live batch, not because the load let up: an audit job now
            # could only take the interpreter from them. On the chip at
            # saturation the lane sent ~200 jobs in a 20 s window through
            # exactly these moments, each ~47 ms on the wall, a quarter
            # of all rows dispatched (PR 38). Only this thread, the
            # dispatch loop, ever acquires, so the probe takes no slot
            # from a live batch.
            return
        self._inflight.release()
        if not self._audit_slack_ok(head_rows):
            return
        with self._audit_lock:
            if self._audit_inflight or not self._audit_jobs:
                return
            job = self._audit_jobs.popleft()
            self._audit_inflight = True
        try:
            self._audit_pool.submit(self._run_audit_job, job)
        except RuntimeError:  # pool shut down (stop race)
            with self._audit_lock:
                self._audit_inflight = False
            try:
                job.future.set_exception(
                    RuntimeError("batcher shutting down; audit lane closed")
                )
            except Exception:  # noqa: BLE001 — already-done race
                pass

    def _run_audit_job(self, job: _AuditJob) -> None:
        try:
            # preemption: live work arrived between the pop and this
            # worker starting — the audit batch goes BACK to the head of
            # the lane and the live batch proceeds unimpeded
            if self._queue.qsize() > 0 and not self._stopping:
                with self._stats_lock:
                    self.audit_preemptions += 1
                with self._audit_lock:
                    self._audit_jobs.appendleft(job)
                return
            if self._stopping:
                job.future.set_exception(
                    RuntimeError("batcher shutting down; audit lane closed")
                )
                return
            sched = self.scheduler
            granted = False
            if sched is not None:
                # multi-tenant (round 16): audit also yields CROSS-tenant
                # — the AUDIT priority class is granted only behind every
                # live waiter; a bounded wait re-queues at the lane head
                # (counted as a preemption) instead of camping on a slot
                from policy_server_tpu.runtime import scheduler as _fair

                granted = sched.acquire(
                    self.tenant, _fair.AUDIT, timeout=0.5,
                    should_abort=lambda: self._stopping,
                )
                if not granted:
                    if self._stopping:
                        job.future.set_exception(
                            RuntimeError(
                                "batcher shutting down; audit lane closed"
                            )
                        )
                        return
                    with self._stats_lock:
                        self.audit_preemptions += 1
                    with self._audit_lock:
                        self._audit_jobs.appendleft(job)
                    return
            try:
                try:
                    results = self._dispatch_audit(job.pairs)
                except Exception as e:  # noqa: BLE001 — the job carries it
                    job.future.set_exception(e)
                    return
                with self._stats_lock:
                    self.audit_batches_dispatched += 1
                    self.audit_rows_dispatched += len(job.pairs)
                job.future.set_result(results)
            finally:
                if granted:
                    sched.release(self.tenant)
        finally:
            with self._audit_lock:
                self._audit_inflight = False

    def _dispatch_audit(self, pairs: list) -> list:
        """One audit batch through the environment: raw verdicts
        (audit-origin semantics: constraints never applied); audit=True —
        its rows are the lane's to count (audit_rows_dispatched), not a
        live answer's. Pre-evaluation hooks run as they do for a live
        request: a hook is not only latency, the signature policy's is
        what verifies an image, and a row judged without it reports every
        image no live request had verified yet as unsigned (PR 38: the
        reports differed from the plain reference exactly there).

        It goes a live-sized slice at a time, as _audit_slack_ok prices
        it: --audit-batch-size may exceed --max-batch-size, and a launch
        wider than any live batch is a program warm-up never compiled —
        it would compile inside a dispatch, with live traffic behind it.
        The whole job is one ring phase under a batch id of its own, so
        the environment's phases inside it (encode, launch, fetch) have
        a batch to belong to."""
        rec = flightrec.recorder()
        bid = rec.next_batch() if rec is not None else -1
        t0 = time.perf_counter_ns()
        results: list = []
        step = self.max_batch_size
        with flightrec.batch_scope(bid):
            for at in range(0, len(pairs), step):
                results.extend(self._scoped(
                    self.env.validate_batch, pairs[at : at + step],
                    audit=True,
                ))
        if rec is not None:
            rec.record_phase(
                flightrec.PH_AUDIT_DISPATCH, t0, time.perf_counter_ns(),
                rows=len(pairs), batch=bid,
            )
        return results

    def _drain_audit_rejecting(self) -> None:
        while True:
            with self._audit_lock:
                if not self._audit_jobs:
                    return
                job = self._audit_jobs.popleft()
            try:
                job.future.set_exception(
                    RuntimeError("batcher shutting down; audit lane closed")
                )
            except Exception:  # noqa: BLE001 — already-done race
                pass

    # -- dispatch loop -----------------------------------------------------

    def _loop(self) -> None:
        while not self._stop.is_set():
            batch: list[_Pending] = []
            try:
                # shard-death chaos site (round 22): fired BEFORE any
                # queue pop, so an injected raise kills this dispatch
                # thread holding zero rows — the clean wedge the shard
                # router's heartbeat fences and warm-revives. Fired
                # under the shard's failpoint scope (set by the router)
                # so chaos can kill ONE specific shard; scope(None) is
                # a passthrough for unsharded batchers.
                with failpoints.scope(self.failpoint_scope):
                    failpoints.fire("shard.dispatch")
                # live lane MOMENTARILY empty: this — and only this — is
                # when the best-effort audit lane may claim an idle slot.
                # Checked at the loop top (not just on get-timeout):
                # under steady load the queue drains to zero between
                # bursts for milliseconds at a time, and those gaps ARE
                # the idle capacity audit rides; a 50 ms fully-quiet
                # window would never occur. The audit dispatch runs on
                # its own pool, so the live get below is not delayed.
                if self._queue.qsize() == 0:
                    self._maybe_dispatch_audit()
                try:
                    first = self._queue.get(timeout=0.05)
                except queue.Empty:
                    continue
                batch.append(first)
                # Backlog drains immediately — the batch-timeout window
                # only bounds ADDED latency when load is light; it must
                # never shrink batches when the queue is already deep
                # (that collapses throughput to batch-of-one under
                # pressure).
                deadline = first.enqueued_at + self.batch_timeout
                while len(batch) < self.max_batch_size:
                    try:
                        batch.append(self._queue.get_nowait())
                        continue
                    except queue.Empty:
                        pass
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    try:
                        batch.append(self._queue.get(timeout=remaining))
                    except queue.Empty:
                        break
                self._launch_batch(batch)
            except BaseException:
                # the dispatch thread is dying (real mid-iteration bug
                # or armed shard.dispatch fault): rows already popped
                # into ``batch`` are owned by NO batch worker and would
                # strand unresolved — answer each 503+Retry-After first
                # so every submitted row still resolves exactly once,
                # then re-raise so dispatch_wedged() sees a dead thread
                # and the self-heal/shard-fencing machinery engages.
                for p in batch:
                    try:
                        self._fail(p, FencedError(0.5))
                    except Exception:  # noqa: BLE001 — best-effort drain
                        pass
                raise

    def _launch_batch(self, batch: list[_Pending]) -> None:
        """Hand a formed batch to the pipeline pool (bounded in-flight)."""
        # a batch that has to wait for a slot is throughput traffic
        waited = not self._inflight.acquire(blocking=False)
        if waited:
            while not self._inflight.acquire(timeout=0.05):
                if self._stopping or self._stop.is_set():
                    for p in batch:
                        self._reject_stopping(p)
                    return
        with self._stats_lock:
            self._batches_inflight += 1
            # room: it did not wait, and a slot is free beyond its own
            has_room = (
                not waited and self._batches_inflight < self._batch_workers
            )
        try:
            self._batch_pool.submit(self._process_batch, batch, has_room)
        except RuntimeError:  # pool shut down (stop race)
            self._release_slot()
            for p in batch:
                self._reject_stopping(p)

    def _release_slot(self) -> None:
        with self._stats_lock:
            self._batches_inflight -= 1
        self._inflight.release()

    def _process_batch(self, batch: list[_Pending], has_room: bool) -> None:
        try:
            # the tenant failpoint scope rides the batch worker thread
            # (tenant-scoped chaos, failpoints.scope)
            self._scoped(self._dispatch, batch, has_room)
        except Exception as e:  # noqa: BLE001 — last-resort guard
            for p in batch:
                self._fail(p, e)
        finally:
            self._release_slot()

    # -- batch evaluation --------------------------------------------------

    def _remaining(self, p: _Pending) -> float | None:
        if self.policy_timeout is None:
            return None
        return self.policy_timeout - (time.perf_counter() - p.enqueued_at)

    def _resolve(
        self,
        p: _Pending,
        response: AdmissionResponse,
        delivery: _DeliveryBatch | None = None,
    ) -> None:
        """Complete a future, tolerating a concurrent client-side cancel
        (the webhook caller timing out mid-batch must never take down the
        dispatch thread). Sink rows (submit_many) accumulate into the
        delivery batch instead — one sink call per batch."""
        self._release_quota(p)
        if p.sink is not None:
            if delivery is not None:
                delivery.add_sink(p, response, None)
            else:
                _deliver_sink(p.sink, [(p.token, response, None)])
            return
        try:
            p.future.set_result(response)
        except Exception:  # cancelled/already-done race
            pass
        self._mirror(p, response, None, delivery)

    def _fail(
        self,
        p: _Pending,
        exc: BaseException,
        delivery: _DeliveryBatch | None = None,
    ) -> None:
        self._release_quota(p)
        if p.sink is not None:
            if delivery is not None:
                delivery.add_sink(p, None, exc)
            else:
                _deliver_sink(p.sink, [(p.token, None, exc)])
            return
        try:
            p.future.set_exception(exc)
        except Exception:
            pass
        self._mirror(p, None, exc, delivery)

    @staticmethod
    def _mirror(
        p: _Pending,
        result,
        exc,
        delivery: _DeliveryBatch | None,
    ) -> None:
        if p.aio_future is None:
            return
        if delivery is not None:
            delivery.add(p, result, exc)
            return
        try:
            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        item = [(p.aio_future, result, exc)]
        if running is p.aio_loop:
            _set_many(item)  # already on the loop: set inline
            return
        try:
            p.aio_loop.call_soon_threadsafe(_set_many, item)
        except RuntimeError:  # loop closed
            pass

    def _reject_expired(
        self, p: _Pending, delivery: _DeliveryBatch | None = None
    ) -> None:
        """Drop an already-expired row BEFORE encode/dispatch (no dead
        work): the propagated deadline passed while it queued, so the
        webhook caller is gone — answer 504 in-band and count it."""
        with self._stats_lock:
            self.expired_dropped += 1
        self._resolve(
            p,
            AdmissionResponse.reject(p.request.uid(), EXPIRED_MESSAGE, 504),
            delivery,
        )

    def _serve_degraded(self, runnable: list[_Pending]) -> None:
        """The tripped-everything answer per --degraded-mode: 'monitor'
        serves accept-all monitor-style verdicts (fail-open, logged),
        'reject' serves in-band 503s (fail-closed). The default 'oracle'
        never reaches here — the environment routes host-side itself."""
        from policy_server_tpu.telemetry.tracing import logger

        with self._stats_lock:
            self.degraded_responses += len(runnable)
        logger.warning(
            "device breaker fully open: serving %d request(s) in "
            "degraded mode %r", len(runnable), self.degraded_mode,
        )
        delivery = _DeliveryBatch()
        for p in runnable:
            if self.degraded_mode == "reject":
                self._resolve(
                    p,
                    AdmissionResponse.reject(
                        p.request.uid(), DEGRADED_MESSAGE, 503
                    ),
                    delivery,
                )
            else:  # monitor: accept, no status — service.rs monitor shape
                self._resolve(
                    p,
                    AdmissionResponse(uid=p.request.uid(), allowed=True),
                    delivery,
                )
        delivery.flush()

    def _record_device_failure(
        self, batch: list[_Pending], waited: float
    ) -> None:
        """Report a watchdog abandonment to the environment's circuit
        breaker(s) — the failure mode exceptions cannot see (the device
        call HUNG). The sharded evaluator routes the report to the shards
        owning the batch's policies.

        ``waited`` is how long the device call was actually outstanding
        before abandonment. A batch formed from queue-aged items can
        expire moments after dispatch on a perfectly healthy device —
        that is a QUEUEING failure, and attributing it to the breaker
        would flip overloaded-but-healthy shards onto the slower host
        path and deepen the overload. Only a wait consuming a meaningful
        share of the evaluation deadline reads as a device hang."""
        if (
            self.policy_timeout is not None
            and waited < 0.5 * self.policy_timeout
        ):
            return
        rec = getattr(self.env, "record_dispatch_failure", None)
        if rec is None:
            return
        try:
            rec([p.policy_id for p in batch])
        except Exception:  # noqa: BLE001 — accounting must not fail batches
            pass

    def _reject_deadline(
        self, p: _Pending, delivery: _DeliveryBatch | None = None
    ) -> None:
        self._resolve(
            p,
            AdmissionResponse.reject(p.request.uid(), DEADLINE_MESSAGE, 500),
            delivery,
        )
        otlp.emit_span(
            "policy_evaluation",
            p.trace_ctx,
            None,
            {"policy_id": p.policy_id},
            error=DEADLINE_MESSAGE,
        )

    def _dispatch(
        self, batch: list[_Pending], has_room: bool = True
    ) -> None:
        """One formed batch, phases 1-3. ``has_room``: the pipeline had a
        slot to spare when the batch was handed to it (_launch_batch)."""
        formed_at = time.perf_counter()
        with self._stats_lock:
            self.batches_dispatched += 1
            self.requests_dispatched += len(batch)
            self.queue_wait_ns += int(
                sum(formed_at - p.enqueued_at for p in batch) * 1e9
            )
        # flight recorder (round 18): one _BatchRec per dispatched batch;
        # every phase boundary below reuses a clock read the batcher
        # already pays (formed_at, dispatch_start, done_at), so the
        # always-on cost is array stores + one histogram observe per
        # phase per BATCH
        rec = flightrec.recorder()
        brec = None
        if rec is not None:
            brec = _BatchRec(rec, formed_at)
            rec.record_phase(
                flightrec.PH_QUEUE_WAIT,
                int(min(p.enqueued_at for p in batch) * 1e9),
                int(formed_at * 1e9),
                rows=len(batch), batch=brec.bid,
            )
        if self.shadow_recorder is not None:
            try:
                self.shadow_recorder.observe(
                    [(p.policy_id, p.request) for p in batch]
                )
            except Exception:  # noqa: BLE001 — recording must not fail
                pass  # the batch (canary corpus just stays smaller)
        if self.audit_tracker is not None:
            t_observe = time.perf_counter_ns()
            try:
                # dirty-set tracking for the background audit scanner:
                # only objects ADMITTED through /validate belong in the
                # cluster snapshot (audit-origin replays must not feed
                # themselves back in)
                self.audit_tracker.observe(
                    [
                        p.request for p in batch
                        if p.origin is service.RequestOrigin.VALIDATE
                    ]
                )
            except Exception:  # noqa: BLE001 — tracking must not fail
                # the batch (the scan corpus just stays smaller) — but
                # say so once: until PR 38 every native-path batch
                # failed here in silence and the snapshot stayed empty
                if not self._audit_observe_failed:
                    self._audit_observe_failed = True
                    from policy_server_tpu.telemetry.tracing import logger

                    logger.exception(
                        "audit snapshot tracking failed; the scanner "
                        "will not see these objects"
                    )
            with self._stats_lock:
                self.audit_observe_ns += time.perf_counter_ns() - t_observe

        # Phase 1 (host): pre-evaluation — id parse, namespace shortcut,
        # bounded pre-eval hooks. Items that short-circuit or fail resolve
        # here and drop out of the device batch. Round 12: the loop is
        # vectorized over the burst — ONE perf_counter read for every
        # deadline check, pre_evaluate memoized per policy id (its only
        # per-row work is the id-format parse unless an always-accept
        # namespace is configured), and the hook machinery skipped
        # entirely for hookless targets (the common case). Early
        # completions batch into one delivery flush instead of one
        # wakeup per row.
        aa_ns = getattr(self.env, "always_accept_namespace", None)
        preparsed = self._preparsed_ok
        hookless = self._hookless
        delivery = _DeliveryBatch()
        runnable: list[_Pending] = []
        # one clock read for the whole batch, refreshed after every
        # hook-running row (hooks are the only phase-1 work that can
        # block long enough to stale the snapshot) — rows that expired
        # during formation still drop, without a per-row syscall
        now = time.perf_counter()
        for p in batch:
            if p.future is not None and p.future.cancelled():
                continue
            # no dead work: a row whose propagated deadline passed while
            # queued is dropped HERE, before any encode/dispatch spend
            if p.deadline is not None and now >= p.deadline:
                self._reject_expired(p, delivery)
                continue
            pid = p.policy_id
            no_hooks = hookless.get(pid)
            known = no_hooks is not None
            if not known:
                no_hooks = self._target_hookless(pid)
                if no_hooks is None:
                    no_hooks = True  # unknown id: fails in validate_batch
                else:
                    # memos are bounded to REGISTRY-KNOWN ids only — a
                    # stream of distinct unknown ids must not grow them
                    hookless[pid] = no_hooks
                    known = True
            if aa_ns is not None or pid not in preparsed:
                try:
                    short = service.pre_evaluate(
                        self.env, pid, p.request, p.origin, p.enqueued_at
                    )
                except Exception as e:  # EvaluationError → HTTP error mapper
                    self._fail(p, e, delivery)
                    continue
                if short is not None:
                    self._resolve(p, short, delivery)
                    continue
                if aa_ns is None and known:
                    preparsed.add(pid)
            if no_hooks:
                if (
                    self.policy_timeout is not None
                    and now - p.enqueued_at >= self.policy_timeout
                ):
                    self._reject_deadline(p, delivery)
                    continue
            else:
                try:
                    if not self._run_hooks_with_deadline(p):
                        continue  # deadline rejection already delivered
                except Exception as e:  # noqa: BLE001 — per-item
                    # isolation: a payload that breaks its own hook setup
                    # must not fail the whole batch
                    self._fail(p, e, delivery)
                    continue
                # hooks block: re-read the clock for this and later rows
                now = time.perf_counter()
                if (
                    self.policy_timeout is not None
                    and now - p.enqueued_at >= self.policy_timeout
                ):
                    self._reject_deadline(p, delivery)
                    continue
            runnable.append(p)
        delivery.flush()
        if brec is not None:
            phase1_end = time.perf_counter()
            brec.form_ns = int((phase1_end - formed_at) * 1e9)
            brec.rec.record_phase(
                flightrec.PH_FORM, int(formed_at * 1e9),
                int(phase1_end * 1e9), rows=len(batch), batch=brec.bid,
            )
        if not runnable:
            return
        sched = self.scheduler
        if sched is None:
            # single-tenant: no slot gate — the round-15 path, unchanged
            self._evaluate_runnable(runnable, brec, has_room)
            return
        from policy_server_tpu.runtime import scheduler as _fair

        # Weighted-fair dispatch slot (live class, round 16): a tenant
        # past its share waits HERE, burning its own requests' deadline
        # budget while other tenants' batches keep flowing — the
        # noisy-neighbor containment point for shared device/CPU time.
        if not sched.acquire(
            self.tenant, _fair.LIVE,
            should_abort=lambda: self._stopping,
        ):
            for p in runnable:
                self._reject_stopping(p)
            return
        try:
            self._evaluate_runnable(runnable, brec, has_room)
        finally:
            sched.release(self.tenant)

    def _evaluate_runnable(
        self,
        runnable: list[_Pending],
        brec: "_BatchRec | None" = None,
        has_room: bool = True,
    ) -> None:
        """Phases 2-3 for a formed batch's runnable rows: degraded-mode
        gate, host/device dispatch under the watchdog, service-layer
        post-processing. Split from :meth:`_dispatch` so the round-16
        fair scheduler brackets exactly the shared evaluation work."""
        # Degraded-mode gate: with every shard's breaker open and a
        # non-default policy, answer per --degraded-mode instead of
        # evaluating (the default 'oracle' keeps evaluating — the
        # environment itself short-circuits to the host oracle).
        if self.degraded_mode != "oracle" and getattr(
            self.env, "breaker_all_open", False
        ):
            self._serve_degraded(runnable)
            return

        # Phase 2 (device): one fused dispatch for every runnable item.
        # Hooks already ran in phase 1 under the deadline, so skip them here.
        # A batch-level failure (device error, OOM on a new bucket) must fail
        # THESE futures, never the dispatch thread. With a policy timeout
        # configured, the call runs on the device pool under the dispatch
        # watchdog (below): device execution — compile stall on a cold
        # (schema × batch) bucket, a hung device call — is
        # bounded by the per-request deadline just like queue wait and host
        # hooks, matching the reference's mid-execution epoch interrupt
        # (src/lib.rs:176-190, tests/integration_test.rs:417).
        pairs = [(p.policy_id, p.request) for p in runnable]
        # Latency fast-path decision, two tiers:
        # 1. occupancy: a small batch handed to a pipeline with a slot to
        #    spare is latency-critical, not throughput traffic, so answer
        #    it on the host. One that waited for a slot or took the last
        #    is throughput traffic whatever its size (a saturated closed
        #    loop forms ~60-row batches, PR 40): the host route is Python
        #    under the GIL, ~5 x the device route's wall time a request
        #    there, so it rides the device and counts as declined;
        # 2. budget: for larger batches (every batch at threshold 0: what
        #    this tier saw before tier 1 could decline), compare the
        #    MEASURED device round-trip estimate against the oldest
        #    item's remaining latency budget; when the device would blow
        #    the budget and the host estimate would not, route host-side.
        #    The stored estimate decays on every bypass so a stale slow
        #    reading re-probes the device instead of pinning traffic.
        n = len(runnable)
        bucket = bucket_size(n)
        small = self._env_fastpath and 0 < n <= self.host_fastpath_threshold
        use_host = small and has_room
        if (
            not small
            and self._env_fastpath
            and self.latency_budget is not None
            and n > 0
        ):
            est = self._dev_rtt.get(bucket)
            if est is not None:
                oldest = min(p.enqueued_at for p in runnable)
                remaining_budget = self.latency_budget - (
                    time.perf_counter() - oldest
                )
                host_est = self._host_cost_per_row * n
                # route host-side only when the host can actually MEET the
                # budget the device would blow. A batch whose budget is
                # already gone (deep queue under sustained load) stays on
                # the device — the host oracle cannot un-blow it, and
                # flipping the firehose to the scalar host path would
                # collapse throughput and deepen the queue further.
                if host_est <= remaining_budget < est:
                    use_host = True
                    self._dev_rtt[bucket] = est * 0.98
                    with self._stats_lock:
                        self.budget_routed_batches += 1
        if use_host:
            with self._stats_lock:
                self.host_fastpath_batches += 1
        elif small:
            with self._stats_lock:
                self.host_fastpath_declined_batches += 1
        # RTT samples whose dispatch window traced a NEW columnar plane
        # structure paid a one-time XLA compile (seconds on a multi-device
        # mesh) — snapshot the environment's compile counter so
        # _observe_dispatch can discard them, the warmup rule ("the
        # second, compile-free run is the routing baseline") applied at
        # serve time. One poisoned EWMA sample would otherwise route the
        # firehose host-side for the rest of the run.
        compiles_before = getattr(self.env, "plane_program_compiles", 0)
        rec_bid = brec.bid if brec is not None else -1
        dispatch_start_ns = time.time_ns()
        dispatch_start = time.perf_counter()
        if self.policy_timeout is None:
            # reference parity: timeout disabled ⇒ unbounded execution,
            # run inline (host fast-path or device alike)
            try:
                results = (
                    self._scoped_rec(
                        rec_bid, self.env.validate_batch,
                        pairs, run_hooks=False, prefer_host=True,
                    )
                    if use_host
                    else self._scoped_rec(
                        rec_bid, self._fused_validate, pairs,
                    )
                )
            except Exception as e:  # noqa: BLE001
                for p in runnable:
                    self._fail(p, e)
                return
            live = runnable
        else:
            # EVERY stage runs under the dispatch watchdog: the host
            # fast-path is µs for IR rows, but a batch may carry
            # host-executed wasm rows (fuel bounds instructions, not
            # wall-clock) or slow context providers — no request future
            # may outlive policy_timeout unresolved, whichever path
            # served it.
            #
            # Fused pipeline (round 19): ONE worker submission runs the
            # whole encode→device→fetch chain (_fused_validate chains
            # validate_batch_begin + validate_batch_finish on one
            # pipeline thread), and this batch worker parks on ONE
            # batch-granular completion instead of hopping the encode
            # and device pools with a future-wake at each boundary —
            # the round-18 flight recorder measured those pool
            # crossings as the single largest host cost (``handoff``,
            # ~82 µs/row on a 2-core CPU box). Cross-batch
            # overlap is preserved by the pool width: batch N+1's
            # encode runs on a second pipeline worker while batch N's
            # fetch blocks on the first. Both halves stay under the
            # dispatch watchdog, so deadline semantics are unchanged: a
            # hung encode, compile stall, or transport hang all resolve
            # in-band at the per-request deadline.
            live = runnable
            # pool-handoff gaps (submit → worker pickup, work end →
            # future wake): one pair per batch now — the measured cost
            # of the single remaining pool crossing
            handoffs: list | None = [] if brec is not None else None
            t_submit = time.perf_counter_ns() if handoffs is not None else 0
            if use_host:
                dev_future = self._device_pool.submit(
                    self._scoped_rec_timed, rec_bid,
                    self.env.validate_batch,
                    pairs,
                    run_hooks=False,
                    prefer_host=True,
                )
            else:
                dev_future = self._device_pool.submit(
                    self._scoped_rec_timed, rec_bid,
                    self._fused_validate, pairs,
                )
            try:
                wrapped, live = self._watchdog_wait(dev_future, live)
            except Exception as e:  # noqa: BLE001 — validate_batch raised
                for p in live:
                    self._fail(p, e)
                return
            results = None
            if wrapped is not None:
                results, t_start, t_end = wrapped
                if handoffs is not None:
                    handoffs.append((t_submit, t_start))
                    handoffs.append((t_end, time.perf_counter_ns()))
            if results is None:
                # the elapsed time is a LOWER bound on this bucket's RTT —
                # teach the router the device is slow right now
                if not use_host:
                    # a watchdog abandonment is the breaker's hang signal
                    # (attributed only when the device wait was long)
                    self._record_device_failure(
                        runnable, time.perf_counter() - dispatch_start
                    )
                self._observe_dispatch(
                    use_host, bucket, n,
                    time.perf_counter() - dispatch_start, lower_bound=True,
                    compiles_before=compiles_before,
                )
                return  # every item deadline-rejected; device work abandoned
        done_at = time.perf_counter()
        self._observe_dispatch(
            use_host, bucket, n, done_at - dispatch_start,
            compiles_before=compiles_before,
        )
        if brec is not None:
            # done_at doubles as the dispatch end AND phase 3's shared
            # clock read — no extra syscall for the recorder
            brec.disp_ns = int((done_at - dispatch_start) * 1e9)
            brec.rec.record_phase(
                flightrec.PH_DISPATCH, int(dispatch_start * 1e9),
                int(done_at * 1e9), rows=n, batch=brec.bid,
            )
            if self.policy_timeout is not None:
                # the pool-handoff gaps collected around the single
                # fused pipeline submission (ONE textual record site —
                # OB08)
                for h0, h1 in handoffs:
                    if h1 > h0:
                        brec.rec.record_phase(
                            flightrec.PH_HANDOFF, h0, h1, rows=n,
                            batch=brec.bid,
                        )

        # Phase 3 (host): service-layer constraints + metrics per item.
        # Items the watchdog already rejected are skipped — their verdicts
        # arrived too late to be observable and must not double-count
        # metrics. Round 12: ONE clock read covers every latency sample,
        # spans are emitted only when a trace context exists (the native
        # bulk path has none), and completions fan out batch-granular —
        # one sink call / one loop wakeup per batch.
        live_ids = {id(p) for p in live}
        delivery = _DeliveryBatch()
        metrics_sink: list = []
        hit_rows = 0  # cache-hit (FragVerdict) rows — mix attribution
        for p, result in zip(runnable, results):
            if id(p) not in live_ids:
                continue
            try:
                if type(result) is FragVerdict:
                    hit_rows += 1
                    # pre-serialized cache-hit lane (round 19): fragment
                    # eligibility proved the service-layer constraints
                    # are the identity on this shape, so post_evaluate's
                    # per-row object work collapses to one memoized
                    # metric append; the native sink splices the
                    # template bytes without ever building an
                    # AdmissionResponse
                    tmpl = result.tmpl
                    metrics_sink.append(
                        (
                            (done_at - p.enqueued_at) * 1e3,
                            self._metric_of(p, tmpl),
                        )
                    )
                    self._resolve(
                        p,
                        result if p.sink is not None
                        else result.to_response(),
                        delivery,
                    )
                    if p.trace_ctx is not None:
                        otlp.emit_span(
                            "policy_evaluation",
                            p.trace_ctx,
                            dispatch_start_ns,
                            {
                                "policy_id": p.policy_id,
                                "batch_size": len(runnable),
                                "allowed": tmpl.allowed,
                            },
                        )
                    continue
                if isinstance(result, PolicyInitializationError):
                    self._resolve(
                        p,
                        service.handle_initialization_error(p.request, result),
                        delivery,
                    )
                    continue
                if isinstance(result, Exception):
                    self._fail(p, result, delivery)
                    continue
                # No further deadline check: the watchdog guaranteed this
                # item's verdict arrived inside its deadline, and discarding
                # completed work protects nothing.
                response = service.post_evaluate(
                    self.env, p.policy_id, p.request, p.origin,
                    result, p.enqueued_at, metrics_sink=metrics_sink,
                    now=done_at,
                )
                self._resolve(p, response, delivery)
                if p.trace_ctx is not None:
                    otlp.emit_span(
                        "policy_evaluation",
                        p.trace_ctx,
                        dispatch_start_ns,
                        {
                            "policy_id": p.policy_id,
                            "batch_size": len(runnable),
                            "allowed": response.allowed,
                        },
                    )
            except Exception as e:  # noqa: BLE001 — never kill the loop
                self._fail(p, e, delivery)
        # ONE wakeup per client loop / ONE sink call for the whole batch
        delivery.flush()
        if metrics_sink:
            service._registry().record_evaluations_batch(metrics_sink)
        if brec is not None:
            brec.rec.record_phase(
                flightrec.PH_DELIVER, int(done_at * 1e9),
                time.perf_counter_ns(), rows=len(live), batch=brec.bid,
            )
            # hit/miss mix marker (round 22): one event per batch tags
            # how many delivered rows rode the pre-serialized cache-hit
            # lane, so attribution() can split every phase interval into
            # hit-batch vs miss-batch groups — the decomposition that
            # localizes the ~3.5x miss-path gap (make phase-report)
            brec.rec.record_batch_mix(brec.bid, hit_rows, len(live))
            if live:
                # per-row recorder work is BATCH-granular by design (the
                # <=2% overhead contract): one exemplar offer — the
                # batch's oldest live row is its slowest, since every
                # row shares done_at — and one stride reservation for
                # the sampled-row timeline segments
                done_ns = int(done_at * 1e9)
                oldest = min(live, key=lambda q: q.enqueued_at)
                brec.rec.offer_exemplar(
                    oldest.request.uid(), oldest.policy_id,
                    int(oldest.enqueued_at * 1e9), done_ns,
                    brec.row_breakdown(oldest.enqueued_at),
                )
                for i in brec.rec.sample_indices(len(live)):
                    p = live[i]
                    brec.rec.record_row(
                        p.request.uid(), p.policy_id,
                        int(p.enqueued_at * 1e9), done_ns, brec.bid,
                        brec.row_breakdown(p.enqueued_at),
                        flightrec.FlightRecorder.ROW_SAMPLED,
                    )

    def _metric_of(self, p: "_Pending", tmpl) -> Any:
        """Memoized metric dataclass for the fragment lane: a small
        label-tuple key + dict get replaces per-row frozen-dataclass
        construction (part of the measured ``deliver`` cost). Fragment
        verdicts carry no patch, so mutated is always
        False and error_code is the template's code. Bounded at 4096
        entries — real traffic's label diversity is tiny; a hostile
        high-cardinality stream falls back to plain construction."""
        req = p.request
        if req.is_raw:
            key = (
                p.policy_id, p.origin, tmpl.allowed, tmpl.code, True,
                None, None, None,
            )
        else:
            adm = req.admission_request
            key = (
                p.policy_id, p.origin, tmpl.allowed, tmpl.code, False,
                adm.request_kind.kind if adm.request_kind else "",
                adm.namespace, adm.operation,
            )
        memo = self._metric_memo
        m = memo.get(key)
        if m is None:
            m = service._evaluation_metric(  # noqa: SLF001 — same package
                self.env, p.policy_id, req, p.origin,
                accepted=tmpl.allowed, mutated=False,
                error_code=tmpl.code,
            )
            if len(memo) < 4096:
                memo[key] = m
        return m

    def _fused_validate(self, pairs: list) -> list:
        """The encode→device→fetch chain as ONE unit of pool work: the
        native pipeline's host half (validate_batch_begin) and device
        half (validate_batch_finish) run back-to-back on the SAME
        pipeline thread — no pool hop, no future-wake between them —
        and the cache-hit fast lane is armed (fragment_responses) so
        blob/row-tier hits come back as pre-serialized FragVerdicts
        instead of per-row AdmissionResponse construction. Environments
        without the native split (oracle backend, sharded evaluators,
        tripped breakers declining the pipeline) fall through to plain
        validate_batch with identical semantics."""
        with environment.fragment_responses():
            begin_fn = getattr(self.env, "validate_batch_begin", None)
            if begin_fn is not None and getattr(
                self.env, "native_encoding", False
            ):
                handle = begin_fn(pairs, run_hooks=False)
                if handle is not None:
                    return self.env.validate_batch_finish(handle)
            return self.env.validate_batch(pairs, run_hooks=False)

    def _observe_dispatch(
        self,
        use_host: bool,
        bucket: int,
        n: int,
        dur: float,
        lower_bound: bool = False,
        compiles_before: int | None = None,
    ) -> None:
        """Feed the routing estimators with a measured dispatch. Racy
        float writes from concurrent batch workers are benign (last EWMA
        step wins). The estimators serve BOTH the latency-budget router
        and the load-shedding admission check (estimated_wait), so they
        stay live when either knob is on."""
        if (
            self.latency_budget is None and self.request_timeout is None
        ) or n <= 0:
            return
        if use_host:
            if lower_bound:
                # a watchdog-truncated host batch (hung wasm row) is not a
                # cost measurement — feeding it in would inflate host_est
                # and suppress legitimate routing long after the hang
                return
            self._host_cost_per_row = (
                0.7 * self._host_cost_per_row + 0.3 * dur / n
            )
            return
        if compiles_before is not None and (
            getattr(self.env, "plane_program_compiles", 0) > compiles_before
        ):
            # the dispatch window traced a new columnar plane structure:
            # dur includes a one-time XLA compile, not the steady-state
            # device cost — discard the sample (a concurrent worker's
            # compile landing in our window skips a valid sample instead,
            # which is benign: the next compile-free dispatch feeds in)
            return
        est = self._dev_rtt.get(bucket)
        if lower_bound:
            # a watchdog-abandoned dispatch only bounds the RTT from below
            self._dev_rtt[bucket] = max(est or 0.0, dur)
        else:
            self._dev_rtt[bucket] = (
                dur if est is None else 0.7 * est + 0.3 * dur
            )

    def _watchdog_wait(
        self, dev_future: Future, runnable: list[_Pending]
    ) -> tuple[list | None, list[_Pending]]:
        """Dispatch watchdog: wait for the device batch, but never past any
        item's deadline. Items whose deadline passes while the device call
        is still running resolve in-band with "execution deadline exceeded"
        (500) — the batched analog of the reference interrupting a running
        wasm instance at its epoch deadline (src/lib.rs:176-190,
        src/cli.rs:164-169). Returns ``(results, live_items)``; when every
        item expired, returns ``(None, [])`` and leaves the device work to
        finish (and be discarded) in the background, so no request future
        can outlive ``policy_timeout`` unresolved."""
        from concurrent.futures import TimeoutError as FutureTimeout

        live = list(runnable)
        while True:
            next_deadline = min(
                p.enqueued_at + self.policy_timeout for p in live
            )
            wait = max(0.0, next_deadline - time.perf_counter())
            try:
                return dev_future.result(timeout=wait), live
            except FutureTimeout:
                now = time.perf_counter()
                expired = [
                    p for p in live
                    if now >= p.enqueued_at + self.policy_timeout
                ]
                delivery = _DeliveryBatch()
                for p in expired:
                    self._reject_deadline(p, delivery)
                delivery.flush()
                if expired:
                    live = [
                        p for p in live
                        if now < p.enqueued_at + self.policy_timeout
                    ]
                if not live:
                    with self._stats_lock:
                        self.deadline_abandoned_batches += 1
                    dev_future.add_done_callback(self._discard_late_batch)
                    return None, []

    @staticmethod
    def _discard_late_batch(dev_future: Future) -> None:
        """Completion sink for an abandoned device batch: surface the error
        (if any) in logs, never raise."""
        from policy_server_tpu.telemetry.tracing import logger

        exc = dev_future.exception()
        if exc is not None:
            logger.warning("abandoned device batch failed late: %s", exc)
        else:
            logger.info(
                "abandoned device batch completed after deadline; "
                "verdicts discarded"
            )

    def _target_hookless(self, policy_id: str) -> bool | None:
        """True/False when the policy id resolves to a registry target
        (memoizable: the registry is immutable post-boot and an epoch
        flip builds a fresh batcher); None for ids the registry does not
        know — those must NOT be memoized, or a client streaming
        ever-distinct unknown ids would grow the caches without bound
        (their real 404/500 surfaces in validate_batch)."""
        try:
            target = self.env._lookup_top_level(  # noqa: SLF001 — same package
                PolicyID.parse(policy_id)
            )
        except Exception:  # noqa: BLE001 — resolved later with semantics
            return None
        return not self.env.pre_eval_hooks_of(target)

    def _run_hooks_with_deadline(self, p: _Pending) -> bool:
        """Run the target's pre-eval hooks (latency-fault fixtures) off the
        dispatch thread, waiting at most the request's remaining deadline.
        Returns False when the request was rejected for deadline excess."""
        try:
            target = self.env._lookup_top_level(  # noqa: SLF001 — same package
                PolicyID.parse(p.policy_id)
            )
        except Exception:
            # lookup errors surface in validate_batch with full semantics
            return True
        hooks = self.env.pre_eval_hooks_of(target)
        if not hooks:
            return True
        # payload_for, not payload(): hook-observable input is identical on
        # the batcher and direct-validate paths (incl. __context__ snapshot)
        payload = self.env.payload_for(target, p.request)
        # Warm fast path: a hook may advertise (via .skip_if) that it would
        # do no blocking work for this payload — e.g. the image-signature
        # verifier with every image cached. All hooks skippable ⇒ no
        # thread, no handoff; production hooks stay off the hot path.
        if all(
            getattr(h, "skip_if", None) is not None and h.skip_if(payload)
            for h in hooks
        ):
            return True
        remaining = self._remaining(p)
        # One daemon thread per hook run (not a fixed pool): a timed-out
        # hook leaks only its own thread until it finishes — it can never
        # clog a shared pool and starve other requests' hooks.
        done = threading.Event()
        box: dict[str, BaseException] = {}

        def runner() -> None:
            try:
                for h in hooks:
                    h(payload)
            except BaseException as e:  # noqa: BLE001
                box["error"] = e
            finally:
                done.set()

        threading.Thread(
            target=runner, name="pre-eval-hook", daemon=True
        ).start()
        if not done.wait(timeout=remaining):
            self._reject_deadline(p)
            return False
        if "error" in box:
            self._fail(p, box["error"])
            return False
        return True
