"""graftcheck suite tests: golden fixtures per checker (every seeded
violation flagged, every clean fixture silent), the 3-lock ABC/BCA
cycle detector, the lock-order sanitizer's runtime graph, the baseline
mechanics, and the repo itself passing the gate. Plus the round-8
concurrency-fix regression tests (one per fix)."""

from __future__ import annotations

import sys
import threading
import time
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tools.graftcheck import concurrency, failpoint_drift, observability, tracepurity  # noqa: E402
from tools.graftcheck.base import Finding, apply_baseline, load_baseline  # noqa: E402

FIXTURES = Path(__file__).parent / "graftcheck_fixtures"


def rules_of(findings: list[Finding]) -> set[str]:
    return {f.rule for f in findings}


def symbols_of(findings: list[Finding], rule: str) -> set[str]:
    return {f.symbol for f in findings if f.rule == rule}


# ---------------------------------------------------------------------------
# Checker 1 — concurrency
# ---------------------------------------------------------------------------


def test_guarded_by_violation_fixture_flagged():
    findings = concurrency.check(FIXTURES / "gb_violation", "pkg")
    assert rules_of(findings) == {"GB01"}
    syms = symbols_of(findings, "GB01")
    assert "Counter.racy_read:value" in syms
    assert "Counter.racy_check_then_set:value" in syms
    # annotated MODULE GLOBALS are enforced too, not just attributes
    assert "racy_global_read:_registry" in syms
    assert not any("register:" in s for s in syms)  # locked writer clean
    # the lockfree-annotated attribute is never flagged
    assert not any("snapshot" in s for s in syms)


def test_guarded_by_clean_fixture_passes():
    assert concurrency.check(FIXTURES / "gb_clean", "pkg") == []


def test_lock_order_abc_bca_cycle_flagged():
    findings = concurrency.check(FIXTURES / "lo_cycle_abc", "pkg")
    cycles = [f for f in findings if f.rule == "LO01"]
    assert len(cycles) == 1
    # all three locks participate in the reported cycle
    msg = cycles[0].message
    for lock in ("_a", "_b", "_c"):
        assert f"Router.{lock}" in msg


def test_lock_order_clean_fixture_passes():
    assert concurrency.check(FIXTURES / "lo_clean", "pkg") == []


# ---------------------------------------------------------------------------
# Checker 2 — trace purity
# ---------------------------------------------------------------------------


def test_trace_purity_violations_flagged():
    findings = tracepurity.check(FIXTURES / "tp_violation", "pkg")
    rules = rules_of(findings)
    assert {"TP01", "TP02", "TP03"} <= rules
    # TP01 fires in the helper REACHED from the jit root, not just the root
    assert any(
        f.rule == "TP01" and "_impure_helper" in f.symbol for f in findings
    )
    assert any(
        f.rule == "TP03" and "sneaky_fetch" in f.symbol for f in findings
    )


def test_trace_purity_clean_fixture_passes():
    assert tracepurity.check(FIXTURES / "tp_clean", "pkg") == []


# ---------------------------------------------------------------------------
# Checker 3 — observability
# ---------------------------------------------------------------------------


def test_observability_fixture_flags_every_seeded_drift():
    findings = observability.check(
        FIXTURES / "obs",
        metrics_path="metrics_fix.py",
        server_path="server_fix.py",
        dashboard_path="dash.json",
        environment_path="env_fix.py",
    )
    rules = rules_of(findings)
    assert {"OB01", "OB02", "OB03", "OB04", "OB05", "OB06", "OB07"} <= rules
    # both OB01 shapes: a literal name AND a computed-name expression
    assert any(
        f.rule == "OB01" and "fixture_literal" in f.symbol for f in findings
    )
    assert any(
        f.rule == "OB01" and "computed" in f.symbol for f in findings
    )
    assert any(
        f.rule == "OB03" and "DEAD_METRIC" in f.symbol for f in findings
    )
    assert any(
        f.rule == "OB04" and "fixture_depth" in f.symbol for f in findings
    )
    assert any(
        f.rule == "OB05" and "ghost" in f.symbol for f in findings
    )
    assert any(
        f.rule == "OB06" and "policy_mode" in f.symbol for f in findings
    )
    # OB07: uncovered stats keys flagged, the covered one not
    ob07 = [f for f in findings if f.rule == "OB07"]
    assert any("phantom_stat" in f.symbol for f in ob07)
    assert not any("covered_stat" in f.symbol for f in ob07)


def test_observability_repo_mapping_is_total():
    """Acceptance: the live counter<->OTLP<->dashboard mapping has no
    unexported increments, no dead instruments, no dead panels."""
    assert observability.check(REPO_ROOT) == []


def test_ob08_phase_violation_fixture_flagged():
    """OB08 (round 18): an unstamped phase, a double-stamped phase, and
    a histogram family with no dashboard panel are all flagged; the
    once-stamped phase is not."""
    findings = observability.check(
        FIXTURES / "obs_phase_violation",
        metrics_path="metrics_fix.py",
        server_path="server_fix.py",
        dashboard_path="dash.json",
        flightrec_path="flightrec_fix.py",
        package_path="pkg",
    )
    ob08 = [f for f in findings if f.rule == "OB08"]
    assert any("phase:unstamped:gamma" == f.symbol for f in ob08)
    assert any("phase:multi:beta" == f.symbol for f in ob08)
    assert any(
        "histogram:policy_server_fixture_phase_seconds" == f.symbol
        for f in ob08
    )
    assert not any("alpha" in f.symbol for f in ob08)


def test_ob08_phase_clean_fixture_passes():
    findings = observability.check(
        FIXTURES / "obs_phase_clean",
        metrics_path="metrics_fix.py",
        server_path="server_fix.py",
        dashboard_path="dash.json",
        flightrec_path="flightrec_fix.py",
        package_path="pkg",
    )
    assert [f for f in findings if f.rule == "OB08"] == []


# ---------------------------------------------------------------------------
# Checker 4 — failpoint drift
# ---------------------------------------------------------------------------


def test_failpoint_drift_fixture_flagged():
    findings = failpoint_drift.check(
        FIXTURES / "fp_drift",
        package="pkg",
        tests_dir="tests",
        failpoints_rel="does/not/exist.py",
    )
    assert rules_of(findings) == {"FP01", "FP02", "FP04"}
    assert symbols_of(findings, "FP01") == {"armed:site.phantom"}
    assert symbols_of(findings, "FP02") == {"fired:site.unarmed"}
    # site.armed is armed ONLY by a plain unit-test file; site.chaosed
    # is armed from a test_resilience* file and stays FP04-clean
    assert symbols_of(findings, "FP04") == {"unchaosed:site.armed"}


def test_failpoint_repo_sites_all_armed_and_documented():
    assert failpoint_drift.check(REPO_ROOT) == []


# ---------------------------------------------------------------------------
# Checker — state-dir write discipline (FS01, round 17)
# ---------------------------------------------------------------------------


def test_statestore_fs_violation_fixture_flagged():
    from tools.graftcheck import statestore_fs

    findings = statestore_fs.check(FIXTURES / "fs_violation", "pkg")
    assert rules_of(findings) == {"FS01"}
    by_file = {(f.path, f.line) for f in findings}
    # the three raw writes in the statestore module outside the
    # annotated helper: open("wb"), Path.write_text, os.rename
    assert ("pkg/statestore.py", 15) in by_file
    assert ("pkg/statestore.py", 20) in by_file
    assert ("pkg/statestore.py", 24) in by_file
    # the package-wide rule: another module writing into the state dir
    assert ("pkg/other.py", 6) in by_file
    # the annotated helper's own writes and plain reads are clean, and
    # other modules' non-state-dir writes are not this checker's business
    assert len(findings) == 4


def test_statestore_fs_clean_fixture_passes():
    from tools.graftcheck import statestore_fs

    assert statestore_fs.check(FIXTURES / "fs_clean", "pkg") == []


def test_statestore_fs_repo_clean():
    """FS01 over the real tree: every state-dir write goes through the
    atomic helper (baseline stays empty)."""
    from tools.graftcheck import statestore_fs

    assert statestore_fs.check(REPO_ROOT) == []


# ---------------------------------------------------------------------------
# Baseline mechanics
# ---------------------------------------------------------------------------


def test_baseline_suppresses_and_reports_stale(tmp_path):
    f = Finding("concurrency", "GB01", "a.py", 3, "C.m:x", "boom")
    baseline = {f.fingerprint: "known dirty read", "GB01:gone.py:C.m:y": "stale"}
    res = apply_baseline([f], baseline)
    assert res.new == []
    assert [s[0] for s in res.suppressed] == [f]
    assert res.stale == ["GB01:gone.py:C.m:y"]
    # fingerprints are line-number-free: moving the finding keeps the match
    f2 = Finding("concurrency", "GB01", "a.py", 99, "C.m:x", "boom")
    assert f2.fingerprint == f.fingerprint


def test_repo_concurrency_and_tracepurity_clean():
    """The round-8 audit fixed or annotated everything the suite finds in
    the current tree, so the checkers run clean with an EMPTY baseline."""
    assert concurrency.check(REPO_ROOT) == []
    assert tracepurity.check(REPO_ROOT) == []
    assert load_baseline(REPO_ROOT / "tools/graftcheck/baseline.json") == {}


# ---------------------------------------------------------------------------
# Checker 5 — lock-order sanitizer (runtime)
# ---------------------------------------------------------------------------


def _fresh_locksan():
    from policy_server_tpu import locksan

    if locksan.installed():
        # an armed session (make chaos) owns the global state; these
        # synthetic-graph tests would pollute its report
        pytest.skip("locksan armed session: synthetic graph tests skipped")
    return locksan


def test_locksan_detects_abc_bca_inversion():
    locksan = _fresh_locksan()
    locksan.reset()
    a = locksan.SanLock(threading.Lock(), "fix.py:1", False)
    b = locksan.SanLock(threading.Lock(), "fix.py:2", False)
    c = locksan.SanLock(threading.Lock(), "fix.py:3", False)
    with a, b:
        pass
    with b, c:
        pass
    with c, a:  # closes the 3-cycle
        pass
    rep = locksan.report()
    assert rep["inversions"] == [["fix.py:1", "fix.py:2", "fix.py:3"]]
    assert rep["acquisitions"] == 6
    # the first-seen stacks are attached for the report
    assert rep["inversion_stacks"]
    locksan.reset()


def test_locksan_consistent_order_is_clean_and_same_site_ignored():
    locksan = _fresh_locksan()
    locksan.reset()
    a = locksan.SanLock(threading.Lock(), "fix.py:1", False)
    b = locksan.SanLock(threading.Lock(), "fix.py:2", False)
    b2 = locksan.SanLock(threading.Lock(), "fix.py:2", False)
    with a, b:
        pass
    with b, b2:  # same creation site: hand-over-hand, no edge
        pass
    rep = locksan.report()
    assert rep["inversions"] == []
    assert rep["edges"] == [("fix.py:1", "fix.py:2")]
    locksan.reset()


def test_locksan_long_hold_reported():
    locksan = _fresh_locksan()
    locksan.reset()
    old = locksan.HOLD_THRESHOLD_MS
    locksan.HOLD_THRESHOLD_MS = 5.0
    try:
        lk = locksan.SanLock(threading.Lock(), "fix.py:9", False)
        with lk:
            time.sleep(0.02)
        rep = locksan.report()
        assert rep["long_holds"] and rep["long_holds"][0][0] == "fix.py:9"
        assert rep["inversions"] == []  # long holds report, never fail
    finally:
        locksan.HOLD_THRESHOLD_MS = old
        locksan.reset()


def test_locksan_install_instruments_package_locks_only():
    locksan = _fresh_locksan()
    locksan.install()
    try:
        from policy_server_tpu.resilience import CircuitBreaker

        breaker = CircuitBreaker()
        assert type(breaker._lock).__name__ == "SanLock"
        # non-package construction sites keep native locks
        assert type(threading.Lock()).__name__ != "SanLock"
        breaker.record_failure()
        assert breaker.state  # instrumented lock drives the real breaker
    finally:
        locksan.uninstall()
        locksan.reset()


# ---------------------------------------------------------------------------
# Round-8 concurrency-fix regressions (one per fix)
# ---------------------------------------------------------------------------


def test_verdict_cache_len_and_bytes_consistent_under_concurrent_puts():
    """Fix: __len__/bytes_used read _data/_bytes under _lock (they raced
    _put_locked's pop/reinsert+eviction before round 8)."""
    from policy_server_tpu.evaluation.verdict_cache import VerdictCache

    cache = VerdictCache(capacity_bytes=64 * 1024)
    stop = threading.Event()
    errors: list[BaseException] = []

    def writer(tag: str):
        i = 0
        try:
            while not stop.is_set():
                cache.put_many(
                    [((tag, i, j), {"v": j, "w": j + 1}) for j in range(16)]
                )
                i += 1
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                n = len(cache)
                used = cache.bytes_used
                assert n >= 0 and used >= 0
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [
        threading.Thread(target=writer, args=("a",)),
        threading.Thread(target=writer, args=("b",)),
        threading.Thread(target=reader),
        threading.Thread(target=reader),
    ]
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    assert not errors
    # post-quiescence invariant: accounted bytes match the entries (a
    # row is immutable once put, so its cost is a function of key and row)
    from policy_server_tpu.evaluation.verdict_cache import entry_cost

    with cache._lock:
        assert cache._bytes == sum(
            entry_cost(key, row) for key, row in cache._data.items()
        )
    assert cache.bytes_used <= cache.capacity_bytes


def test_otlp_span_drop_counter_exact_under_concurrent_on_end():
    """Fix: BatchSpanProcessor.dropped += was an unlocked read-modify-write
    racing every request thread; with the lock the count is exact. Every
    on_end either queues the span or counts a drop, and queued spans are
    either exported or still resident — so dropped must equal
    total - exported - queued EXACTLY; a lost update breaks the identity."""
    from policy_server_tpu.telemetry import otlp

    class _CountingExporter:
        def __init__(self):
            self.exported = 0
            self._lock = threading.Lock()

        def export_spans(self, spans):
            with self._lock:
                self.exported += len(spans)
            return True

    exporter = _CountingExporter()
    proc = otlp.BatchSpanProcessor(
        exporter, interval_seconds=3600, max_batch=4, max_queue=4
    )
    try:
        span = otlp.SpanData("s", b"t" * 16, b"s" * 8, b"", 0, 1)
        n_threads, per_thread = 8, 200
        total = n_threads * per_thread
        barrier = threading.Barrier(n_threads)

        def spam():
            barrier.wait()
            for _ in range(per_thread):
                proc.on_end(span)

        threads = [threading.Thread(target=spam) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        # settle: the flusher may be mid-drain; wait for the accounting
        # to go stable before asserting exactness
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            before = (proc.dropped, exporter.exported, proc._queue.qsize())
            time.sleep(0.05)
            after = (proc.dropped, exporter.exported, proc._queue.qsize())
            if before == after:
                break
        assert proc.dropped + exporter.exported + proc._queue.qsize() == total
        assert proc.dropped > 0  # the 4-deep queue must have overflowed
    finally:
        proc.shutdown()


def test_breaker_stats_consistent_under_concurrent_short_circuits():
    """Fix: breaker_stats/dedup_stats read their _fallback_lock-guarded
    counters under the lock (dirty reads before round 8)."""
    from policy_server_tpu.resilience import CircuitBreaker

    breaker = CircuitBreaker(failure_threshold=1, cooldown_seconds=60.0)
    breaker.record_failure()  # trip it
    assert breaker.state == "open"
    results: list[dict] = []
    errors: list[BaseException] = []

    def hammer():
        try:
            for _ in range(500):
                breaker.allow_device()
                results.append(breaker.stats())
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not errors
    for s in results:
        assert s["open"] == 1 and s["trips"] == 1
    # per-call denials were counted exactly (lock-guarded increment)
    assert breaker.short_circuits == 4 * 500


# ---------------------------------------------------------------------------
# Checker — native/Python response-shape totality (RS01/RS02, round 19)
# ---------------------------------------------------------------------------


def test_respshape_violation_fixture_flagged():
    """RS01: an unclassified to_dict field AND a stale classification
    entry; RS02: emitter key order diverging from to_dict."""
    from tools.graftcheck import respshape

    findings = respshape.check(
        FIXTURES / "rs_violation",
        models_path="models_fix.py",
        frontend_path="frontend_fix.py",
        csrc_path="csrc_fix.cpp",
    )
    syms = {f.symbol for f in findings}
    assert "unclassified:AdmissionResponse.priority" in syms
    assert "stale:AdmissionResponse.patch" in syms
    # the fixture's C++ emits code before message
    assert "order:ValidationStatus.code" in syms


def test_respshape_clean_fixture_passes():
    from tools.graftcheck import respshape

    assert respshape.check(
        FIXTURES / "rs_clean",
        models_path="models_fix.py",
        frontend_path="frontend_fix.py",
        csrc_path="csrc_fix.cpp",
    ) == []


def test_respshape_repo_classification_is_total():
    """Acceptance: the live native serializer's field classification is
    total over the response models and the C++ emitter's key order
    matches to_dict's."""
    from tools.graftcheck import respshape

    assert respshape.check(REPO_ROOT) == []


# ---------------------------------------------------------------------------
# Checkers 8/9 — native ABI drift + wire-parser bounds (round 21)
# ---------------------------------------------------------------------------


def test_native_abi_drift_fixture_flags_every_seeded_violation():
    from tools.graftcheck import native_abi

    d = FIXTURES / "na_drift"
    findings = native_abi.check(
        d, csrc_paths=[d / "csrc_fix.cpp"], py_paths=[d / "binding_fix.py"]
    )
    assert rules_of(findings) == {"NA01", "NA02", "NA03"}
    # NA01: phantom binding, incompatible argtype, missing 64-bit restype
    assert symbols_of(findings, "NA01") == {
        "nat_missing", "nat_poll:arg2", "nat_poll:restype",
    }
    # NA02: drifted anchored layout + unanchored packed struct
    assert symbols_of(findings, "NA02") == {"abi:NatHdr", "abi:Orphan"}
    # NA03: inline wire-format literal
    assert symbols_of(findings, "NA03") == {"inline-fmt:<I"}


def test_native_abi_clean_fixture_passes():
    """Struct-mode AND offsets-mode anchors resolve with zero findings
    when both sides agree."""
    from tools.graftcheck import native_abi

    c = FIXTURES / "na_clean"
    assert native_abi.check(
        c, csrc_paths=[c / "csrc_fix.cpp"], py_paths=[c / "binding_fix.py"]
    ) == []


def test_native_bounds_violation_fixture_flags_every_seeded_violation():
    from tools.graftcheck import native_bounds

    v = FIXTURES / "nw_violation"
    findings = native_bounds.check(v, csrc_paths=[v / "csrc_fix.cpp"])
    assert rules_of(findings) == {"NW01", "NW02", "NW03"}
    assert symbols_of(findings, "NW01") == {"parse_rec:n:resize"}
    assert symbols_of(findings, "NW02") == {"banned:strcpy"}
    assert symbols_of(findings, "NW03") == {"header_len:narrow:out.size()"}


def test_native_bounds_clean_fixture_passes():
    """Range checks, the take() lambda idiom, snprintf, a dominating
    size check, and the bounds-ok escape all clear the lint."""
    from tools.graftcheck import native_bounds

    c = FIXTURES / "nw_clean"
    assert native_bounds.check(c, csrc_paths=[c / "csrc_fix.cpp"]) == []


def test_native_checkers_repo_clean_and_armed():
    """Acceptance: both native checkers run clean on the live tree with
    an EMPTY baseline, and the bounds lint is armed (NW00 would fire if
    csrc/httpfront.cpp lost its wire-input annotations)."""
    from tools.graftcheck import native_abi, native_bounds

    assert native_abi.check(REPO_ROOT) == []
    assert native_bounds.check(REPO_ROOT) == []
    assert load_baseline(REPO_ROOT / "tools/graftcheck/baseline.json") == {}


def test_native_abi_stale_baseline_fails():
    """A baseline entry naming a fixed NA finding is reported stale —
    the suppression cannot outlive the bug."""
    from tools.graftcheck import native_abi

    d = FIXTURES / "na_drift"
    findings = native_abi.check(
        d, csrc_paths=[d / "csrc_fix.cpp"], py_paths=[d / "binding_fix.py"]
    )
    baseline = {
        "NA01:binding_fix.py:nat_missing": "known, tracked",
        "NA02:gone.cpp:abi:Retired": "fixed two rounds ago",
    }
    res = apply_baseline(findings, baseline)
    assert res.stale == ["NA02:gone.cpp:abi:Retired"]
    suppressed = {s[0].symbol for s in res.suppressed}
    assert suppressed == {"nat_missing"}
    assert {f.symbol for f in res.new} == {
        "nat_poll:arg2", "nat_poll:restype",
        "abi:NatHdr", "abi:Orphan", "inline-fmt:<I",
    }
