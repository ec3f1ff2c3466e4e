"""Background audit scanner — continuous cluster re-scan on idle device
capacity.

The reference's audit story is an external companion (Kubewarden's
audit-scanner Deployment) that periodically LISTs cluster resources and
replays them through ``POST /audit/{policy_id}``, emitting PolicyReports
— a policy set promoted today says nothing about resources admitted
under yesterday's set until the companion gets around to them. Here the
scan lives in-process and rides the serving stack's idle slots: BENCH_r05
shows the device path transport/host-bound between admission bursts, so
a background sweep is nearly free *provided live traffic strictly
preempts it*. That discipline is the micro-batcher's best-effort audit
lane (:meth:`MicroBatcher.submit_audit`): audit batches dispatch only
when the live lane is empty with RTT slack, at most one audit dispatch
is ever in flight, and a queued audit batch is re-queued (preempted) the
moment live work arrives.

Sweep cadences:

* **full sweep** — the whole snapshot store through the live epoch's
  evaluation environment; runs at scanner start, on every policy-epoch
  PROMOTION (lifecycle post-promote hook: the new set must re-judge
  everything admitted under the old one), and after a ROLLBACK (whose
  first effect is marking the rolled-back epoch's reports stale).
* **dirty sweep** — only objects served through ``/validate`` since the
  last sweep, on the ``--audit-interval-seconds`` cadence
  (``--audit-mode interval``; ``on-promote`` skips the cadence and
  sweeps only on epoch flips).

Results land in the :class:`~policy_server_tpu.audit.reports.
PolicyReportStore` stamped with the epoch generation that produced them.
Audit rows are RAW verdicts (RequestOrigin::Audit semantics —
``validation_response_with_constraints`` never applies, reference
handlers.rs:69-90), and they share the epoch's verdict cache with live
traffic, so re-scanning unchanged objects is mostly cache hits.

Degradation: while the device breaker is fully open the scanner PAUSES
(skipped sweeps are counted) instead of burning host-oracle capacity the
degraded live path needs. A mid-sweep policy reload retires the old
epoch's batcher; the in-flight audit job then fails, the sweep aborts
re-marking its unscanned keys dirty, and the post-promote hook's full
sweep picks everything up on the new epoch.

Chaos site: ``audit.sweep`` fires at the head of every sweep.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Any

from policy_server_tpu import failpoints
from policy_server_tpu.audit.reports import PolicyReportStore
from policy_server_tpu.audit.snapshot import SnapshotStore
from policy_server_tpu.telemetry.tracing import logger

AUDIT_MODES = ("off", "interval", "on-promote")


class AuditScanner:
    """The background sweeper (see module docstring). Owns a daemon
    thread; sweeps are serialized by ``_sweep_lock`` so a test-driven
    synchronous :meth:`sweep` never races the cadence thread."""

    def __init__(
        self,
        *,
        state: Any,
        snapshot: SnapshotStore,
        reports: PolicyReportStore,
        mode: str = "interval",
        interval_seconds: float = 30.0,
        batch_size: int = 256,
        job_timeout_seconds: float = 60.0,
        matrix: Any = None,
    ) -> None:
        if mode not in AUDIT_MODES:
            raise ValueError(f"invalid audit mode {mode!r}")
        self.state = state
        self.snapshot = snapshot
        self.reports = reports
        # optional verdict matrix (audit/matrix.py): when armed, sweeps
        # evaluate the dirty CROSS-PRODUCT (dirty-rows × all-columns +
        # clean-rows × dirty-columns) and feed results to the matrix
        # next to the report store; epoch hooks diff policy-content
        # fingerprints instead of requesting whole-cluster re-judges
        self.matrix = matrix
        self.mode = mode
        self.interval = max(0.05, float(interval_seconds))
        self.batch_size = max(1, int(batch_size))
        # bound on one audit-lane dispatch (queue wait behind live bursts
        # + device time); a sweep that cannot land a batch inside it
        # aborts and retries on the next cadence tick
        self.job_timeout = float(job_timeout_seconds)
        # optional live-cluster feed (audit/watch_feed.WatchFeed): set by
        # the server under --audit-watch so sweep payloads and stats
        # carry the feed's freshness accounting next to the scanner's
        self.watch_feed: Any = None
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None
        # serializes whole sweeps (cadence thread vs. test/bench callers)
        self._sweep_lock = threading.Lock()
        self._lock = threading.Lock()
        self._full_pending = True  # guarded-by: _lock — first sweep is full
        # a matrix column-diff promotion requests a DIRTY sweep; this
        # flag lets on-promote mode run it without a cadence tick
        self._kick_pending = False  # guarded-by: _lock
        self._full_sweeps = 0  # guarded-by: _lock
        self._dirty_sweeps = 0  # guarded-by: _lock
        self._sweep_errors = 0  # guarded-by: _lock
        self._paused_sweeps = 0  # guarded-by: _lock
        self._rows_scanned = 0  # guarded-by: _lock
        # objects a sweep had collected that left the store before all
        # their rows were judged (the byte budget, or a DELETE)
        self._objects_skipped = 0  # guarded-by: _lock
        # whole-run accounting, segmented by the policy epoch whose set
        # judged the rows (PROFILE r13 caveat 3: one total alone reads
        # ambiguously after an epoch flip — the soak artifact needs the
        # run's full audit volume AND the per-epoch decomposition)
        self._rows_by_epoch: dict[int, int] = {}  # guarded-by: _lock
        self._last_full_sweep: float | None = None  # guarded-by: _lock

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "AuditScanner":
        if self._thread is None:
            # the pending boot sweep runs on the first loop pass, not an
            # interval later (freshness gauge live from the start)
            self._wake.set()
            self._thread = threading.Thread(
                target=self._loop, name="audit-scanner", daemon=True
            )
            self._thread.start()
        return self

    def shutdown(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if self.matrix is not None:
            # final durable spill so the next boot resumes compliance
            # from the freshest verdicts, not the last cadence tick's
            self.matrix.maybe_spill(force=True)

    # -- triggers ----------------------------------------------------------

    def request_full_sweep(self, reason: str) -> None:
        with self._lock:
            self._full_pending = True
        self._wake.set()
        logger.info("audit full sweep requested (%s)", reason)

    def skip_boot_full_sweep(self) -> None:
        """Warm-boot downgrade: a successful matrix restore proved the
        covered rows current under the serving column fingerprints, so
        the pending boot FULL sweep becomes a dirty sweep of whatever
        the restore could not validate (zero re-judge of clean rows —
        the restart drill asserts this)."""
        with self._lock:
            self._full_pending = False
            self._kick_pending = True

    def request_dirty_sweep(self, reason: str) -> None:
        """Kick one dirty sweep out of cadence (matrix column-diff
        promotions: only the changed columns need re-judging, so a full
        sweep would throw away exactly the work the matrix preserved)."""
        with self._lock:
            self._kick_pending = True
        self._wake.set()
        logger.info("audit dirty sweep requested (%s)", reason)

    def _matrix_columns_sync(self, epoch: int) -> "dict | None":
        """Diff the SERVING policy set's content fingerprints into the
        matrix columns. Returns the diff, or None when the matrix is off
        or the environment cannot supply its source policies (then the
        caller falls back to the pre-matrix full-sweep contract)."""
        matrix = self.matrix
        if matrix is None:
            return None
        env = self.state.evaluation_environment
        policies = (
            getattr(env, "source_policies", None)
            if env is not None else None
        )
        if not policies:
            return None
        return matrix.set_columns(policies, epoch)

    def on_promote(self, epoch: int) -> None:
        """Lifecycle post-promote hook. Matrix off: the newly serving
        policy set must re-judge every resource admitted under the
        previous one (full sweep). Matrix on: diff column fingerprints —
        a promotion that changes 2 of 32 policies dirties 2 columns and
        kicks a dirty sweep; an unchanged-content promotion re-stamps
        cells and re-judges NOTHING."""
        diff = self._matrix_columns_sync(epoch)
        if diff is None:
            self.request_full_sweep(f"epoch-{epoch}-promoted")
            return
        if diff["dirty"] or diff["removed"]:
            self.request_dirty_sweep(
                f"epoch-{epoch}-promoted: {len(diff['dirty'])} column(s) "
                f"dirty, {len(diff['removed'])} removed"
            )

    def on_rollback(self, stale_epoch: int, serving_epoch: int) -> None:
        """Lifecycle rollback hook: the rolled-back epoch's verdicts no
        longer describe a policy set anyone serves — mark them stale,
        then re-scan under the revived epoch. The matrix diffs columns
        first (a rollback to byte-identical policy content keeps its
        cells valid; the full sweep's re-judge then re-stamps without
        emission), but the REPORT rows need the revived epoch's stamp,
        so the full-sweep contract stays."""
        marked = self.reports.mark_epoch_stale(stale_epoch)
        logger.warning(
            "audit reports from rolled-back policy epoch %d marked stale "
            "(%d rows); full re-scan under epoch %d queued",
            stale_epoch, marked, serving_epoch,
        )
        self._matrix_columns_sync(serving_epoch)
        self.request_full_sweep(f"epoch-{stale_epoch}-rolled-back")

    # -- the cadence loop --------------------------------------------------

    def _loop(self) -> None:
        while not self._stop.is_set():
            # interval mode ticks on the cadence; on-promote mode sleeps
            # until a hook kicks it (short timeout only to observe stop)
            timeout = self.interval if self.mode == "interval" else 0.5
            self._wake.wait(timeout)
            self._wake.clear()
            if self._stop.is_set():
                return
            # drain observed DELETEs every tick, even when no sweep runs
            # (on-promote mode may not sweep for days; without this the
            # pending-deletion set grows unbounded under cluster churn
            # and deleted objects' report rows keep reading as current)
            self._prune_deletions()
            with self._lock:
                full = self._full_pending
                self._full_pending = False
                kick = self._kick_pending
                self._kick_pending = False
            if not full and not kick and self.mode != "interval":
                continue
            try:
                self.sweep(full=full)
            except Exception as e:  # noqa: BLE001 — the scanner must
                # survive any sweep failure (mid-sweep reload, injected
                # fault) and resume on the next trigger; sweep() already
                # re-pended the full-sweep claim
                with self._lock:
                    self._sweep_errors += 1
                logger.error("audit sweep failed (will retry): %s", e)

    # -- sweeping ----------------------------------------------------------

    def sweep(self, full: bool = True) -> int:
        """Run one sweep synchronously; returns resources×policies rows
        scanned. Public for tests and the bench harness. A full sweep
        that fails for ANY reason (injected fault, mid-sweep epoch
        retirement, job timeout) keeps its pending claim so the next
        trigger retries it."""
        with self._sweep_lock:
            try:
                return self._run_sweep(full)
            except BaseException:
                self._defer_full(full)
                raise

    def _prune_deletions(self) -> None:
        """Drain DELETE-evicted snapshot keys and drop their report rows
        (and matrix rows — each emits a DELETE changelog entry) in one
        bulk pass; called every cadence tick and at sweep heads."""
        deleted = self.snapshot.take_deletions()
        # an object the byte budget pushed out is gone for the scanner
        # too: rows of it would read as the posture of something no sweep
        # will visit again (they used to stay until the next FULL sweep,
        # which only a promotion asks for). The matrix keeps its own
        # bound (retain): nothing was deleted from the cluster.
        self.reports.drop_resources(deleted | self.snapshot.take_evictions())
        if self.matrix is not None and deleted:
            self.matrix.evict_rows(deleted)

    def _defer_full(self, full: bool) -> None:
        """A full sweep that could not run keeps its claim: without this
        a promotion landing while the breaker is open would silently
        never re-judge the cluster under the new set (on-promote mode
        has no cadence to catch it later)."""
        if not full:
            return
        with self._lock:
            self._full_pending = True

    def _run_sweep(self, full: bool) -> int:
        # holds: _sweep_lock
        failpoints.fire("audit.sweep")
        env = self.state.evaluation_environment
        batcher = self.state.batcher
        if env is None or batcher is None:
            self._defer_full(full)
            return 0
        if getattr(env, "breaker_all_open", False):
            # open shards pause audit instead of burning the oracle
            # capacity degraded live traffic is leaning on; the pending
            # full sweep survives the pause
            with self._lock:
                self._paused_sweeps += 1
            self._defer_full(full)
            return 0
        lifecycle = getattr(self.state, "lifecycle", None)
        epoch = lifecycle.current_epoch if lifecycle is not None else 0
        # deletions observed since the last sweep prune their report
        # rows (a deleted object's verdicts must not read as current
        # cluster posture); one bulk pass, not per-key scans
        self._prune_deletions()
        matrix = self.matrix
        if matrix is not None and not matrix.has_columns():
            # standalone harnesses (bench, tests) that never fire a
            # lifecycle hook still get columns before the first record
            self._matrix_columns_sync(epoch)
        # each object as the store keeps it; request_of gives the request
        items = self.snapshot.collect(dirty_only=not full)
        request_of = self.snapshot.request_of
        policy_ids = list(env.policy_ids())
        # the sweep's rows are the cross product items x policy_ids, row r
        # being (items[r // P], policy_ids[r % P]): kept as that rule and
        # not as a list, and an object's request made when a job reaches
        # it. A sweep of 40,000 dirty objects under 32 policies is 1.3
        # million (key, policy, request) tuples that would live as long
        # as the sweep does, and every full pass of the collector walks
        # them with the GIL held while live requests wait (PR 38, on the
        # chip: 4.5 s of collector pauses in a 20 s window as a list,
        # 1.2-1.3 s as a rule, with the store's entries frozen)
        n_policies = len(policy_ids)
        n_product = len(items) * n_policies
        extra: list = []  # (key, policy, request) rows beyond the product
        dirty_cols: set[str] = set()
        if matrix is not None:
            # the dirty CROSS-PRODUCT: dirty-rows × ALL columns (above)
            # plus clean-rows × dirty-columns. A full sweep already
            # covers every cell, so it just claims (and thereby clears)
            # the dirty-column set.
            dirty_cols = matrix.take_dirty_columns()
            if dirty_cols and not full:
                dirty_keys = {key for key, _stored in items}
                cols = [pid for pid in policy_ids if pid in dirty_cols]
                extra = [
                    (key, pid, request)
                    for key, request in self.snapshot.rows_snapshot()
                    if key not in dirty_keys
                    for pid in cols
                ]
            matrix.note_sweep(row_rows=n_product, column_rows=len(extra))
        n_rows = n_product + len(extra)

        def beyond(start: int, stop: int) -> list:
            return extra[max(start, n_product) - n_product
                         : max(stop, n_product) - n_product]

        def keys_of(start: int, stop: int) -> set[str]:
            """Whose rows [start, stop) are; no request is made."""
            per = max(n_policies, 1)
            keys = {items[i][0] for i in range(
                start // per, -(-min(stop, n_product) // per))}
            keys.update(row[0] for row in beyond(start, stop))
            return keys

        def rows_from(start: int, stop: int, but: set[str]) -> list:
            out = []
            at, request = -1, None
            for r in range(start, min(stop, n_product)):
                key, stored = items[r // n_policies]
                if key in but:
                    continue
                if r // n_policies != at:  # one request an object a job
                    at, request = r // n_policies, request_of(stored)
                out.append((key, policy_ids[r % n_policies], request))
            out.extend(row for row in beyond(start, stop) if row[0] not in but)
            return out

        scanned = 0  # rows judged
        done = 0  # rows dealt with: judged, or skipped
        skipped: set[str] = set()
        try:
            for start in range(0, n_rows, self.batch_size):
                if self._stop.is_set():
                    raise RuntimeError("audit scanner shutting down")
                upto = min(start + self.batch_size, n_rows)
                keys = keys_of(start, upto)
                gone = keys - self.snapshot.holds(keys)
                if gone:
                    # collected by this sweep and since pushed out of the
                    # store (a sweep under load can outlast the byte
                    # budget's turnover): a row judged now would describe
                    # an object no later sweep visits, and would take a
                    # lane job from one that is still there
                    with self._lock:
                        self._objects_skipped += len(gone - skipped)
                    skipped |= gone
                chunk = rows_from(start, upto, gone)
                if not chunk:
                    done = upto
                    continue
                future = batcher.submit_audit(
                    [(pid, request) for _key, pid, request in chunk]
                )
                try:
                    results = future.result(timeout=self.job_timeout)
                except FutureTimeout:
                    # abandon the job IN THE LANE too — without this,
                    # overload-era retries would pile duplicate jobs
                    # into the deque and later burn idle dispatches on
                    # results nobody reads
                    batcher.cancel_audit(future)
                    raise RuntimeError(
                        f"audit batch timed out after "
                        f"{self.job_timeout:.0f}s waiting for an idle "
                        "slot"
                    ) from None
                report_rows = [
                    self.reports.row_from_result(
                        key, pid, request, result, epoch
                    )
                    for (key, pid, request), result in zip(chunk, results)
                ]
                self.reports.put(report_rows)
                if matrix is not None:
                    matrix.record_rows(
                        [
                            (key, pid, request, result)
                            for (key, pid, request), result in zip(
                                chunk, results
                            )
                        ],
                        epoch,
                    )
                scanned += len(chunk)
                done = upto
                with self._lock:
                    self._rows_scanned += len(chunk)
                    self._rows_by_epoch[epoch] = (
                        self._rows_by_epoch.get(epoch, 0) + len(chunk)
                    )
        except BaseException:
            # abort: un-judged resources go back on the dirty set so the
            # next sweep (e.g. the post-promote full sweep after a
            # mid-sweep reload killed our batcher) picks them up
            self.snapshot.remark_dirty(
                keys_of(done, n_rows)
            )
            if matrix is not None and dirty_cols:
                # the claimed columns were not (fully) re-judged; give
                # them back so the next sweep picks them up (re-judging
                # an already-landed cell merely re-stamps, never emits)
                matrix.remark_columns_dirty(dirty_cols)
            raise
        if full:
            # a completed full sweep covered the ENTIRE inventory: any
            # report row it did not refresh describes an evicted/deleted
            # resource or a policy the serving set no longer has — prune
            # (this is what keeps the report store bounded by snapshot
            # size x policy-set size)
            swept = keys_of(0, n_rows)
            self.reports.retain(swept, set(policy_ids))
            if matrix is not None:
                matrix.retain(swept, set(policy_ids))
        with self._lock:
            if full:
                self._full_sweeps += 1
                self._last_full_sweep = time.monotonic()
            else:
                self._dirty_sweeps += 1
        if matrix is not None:
            # durability rides the sweep tail on the spill cadence (and
            # never the serving path); the scanner drives this — not the
            # watch feed — so a drill without a kube API still spills
            matrix.maybe_spill()
        return scanned

    # -- introspection -----------------------------------------------------

    def freshness_seconds(self) -> float:
        """Seconds since the last COMPLETED full sweep; -1 before the
        first one lands (the dashboard's report-freshness gauge)."""
        with self._lock:
            last = self._last_full_sweep
        if last is None:
            return -1.0
        return time.monotonic() - last

    def report_payload(self, namespace: str | None = None) -> dict[str, Any]:
        """The GET /audit/reports body: report rows + summary, plus the
        scanner's own freshness/cadence facts."""
        body = self.reports.payload(namespace)
        with self._lock:
            body["scanner"] = {
                "mode": self.mode,
                "full_sweeps": self._full_sweeps,
                "dirty_sweeps": self._dirty_sweeps,
                "sweep_errors": self._sweep_errors,
                "paused_sweeps": self._paused_sweeps,
                "rows_scanned": self._rows_scanned,
                "objects_skipped": self._objects_skipped,
            }
        body["scanner"]["freshness_seconds"] = self.freshness_seconds()
        body["scanner"]["snapshot"] = self.snapshot.stats()
        if self.watch_feed is not None:
            body["scanner"]["watch_feed"] = self.watch_feed.stats()
        if self.matrix is not None:
            body["scanner"]["matrix"] = self.matrix.stats()
        return body

    def stats(self) -> dict[str, Any]:
        """One locked snapshot for runtime_stats (/metrics + OTLP).
        ``rows_scanned`` is the WHOLE-RUN total across every policy
        epoch; ``rows_scanned_by_epoch`` decomposes it (string epoch
        keys, JSON-artifact friendly) so a soak whose last event was an
        epoch flip still reports the run's full audit volume next to the
        post-promote sweep's share."""
        with self._lock:
            out: dict[str, Any] = {
                "full_sweeps": self._full_sweeps,
                "dirty_sweeps": self._dirty_sweeps,
                "sweep_errors": self._sweep_errors,
                "paused_sweeps": self._paused_sweeps,
                "rows_scanned": self._rows_scanned,
                "rows_scanned_by_epoch": {
                    str(e): n
                    for e, n in sorted(self._rows_by_epoch.items())
                },
            }
            skipped = self._objects_skipped
        out["freshness_seconds"] = self.freshness_seconds()
        if self.watch_feed is not None:
            wstats = self.watch_feed.stats()
            out["watch_events_applied"] = wstats["events_applied"]
            out["watch_events_dropped"] = wstats["events_dropped"]
            out["watch_resyncs"] = wstats["resyncs"]
        else:
            out["watch_events_applied"] = 0
            out["watch_events_dropped"] = 0
            out["watch_resyncs"] = 0
        rstats = self.reports.stats()
        out["reports_resident"] = rstats["resident"]
        out["reports_stale"] = rstats["stale"]
        sstats = self.snapshot.stats()
        out["snapshot_resources"] = sstats["resources"]
        out["snapshot_bytes"] = sstats["bytes"]
        out["snapshot_evictions"] = sstats["evicted"]
        # of those, the ones no sweep had finished with: never collected,
        # or skipped by the sweep that had (it counts a DELETE's too)
        out["objects_unjudged"] = sstats["evicted_dirty"] + skipped
        if self.matrix is not None:
            out["matrix"] = self.matrix.stats()
        return out
