"""Flight recorder (round 18): ring-wraparound correctness, begin/end
pairing under batch failure paths (shed / pre-encode 504 / device-raise),
the recorder-on-vs-off overhead contract on the batcher serving path,
timeline-export schema validation, exemplar-window semantics, and the
phase-attribution residual math."""

from __future__ import annotations

import json
import time

import pytest

from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.evaluation.environment import (
    EvaluationEnvironmentBuilder,
)
from policy_server_tpu.models import AdmissionResponse, ValidateRequest
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.runtime.batcher import MicroBatcher, ShedError
from policy_server_tpu.telemetry import flightrec
from policy_server_tpu.telemetry.flightrec import (
    PH_DELIVER,
    PH_DISPATCH,
    PH_FORM,
    PH_QUEUE_WAIT,
    PHASES,
    FlightRecorder,
)

from conftest import build_admission_review_dict


@pytest.fixture(autouse=True)
def no_global_recorder():
    """Every test installs its own recorder; never leak one."""
    yield
    flightrec.install(None)


def _review(name: str = "p") -> ValidateRequest:
    from policy_server_tpu.models import AdmissionReviewRequest

    doc = build_admission_review_dict()
    doc["request"]["object"] = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {"containers": [{"name": "c", "image": "nginx"}]},
    }
    return ValidateRequest.from_admission(
        AdmissionReviewRequest.from_dict(doc).request
    )


@pytest.fixture(scope="module")
def env():
    policies = {
        "priv": parse_policy_entry(
            "priv", {"module": "builtin://pod-privileged"}
        ),
    }
    e = EvaluationEnvironmentBuilder(backend="jax").build(policies)
    yield e
    e.close()


# ---------------------------------------------------------------------------
# ring mechanics
# ---------------------------------------------------------------------------


def test_ring_wraparound_keeps_last_capacity_events():
    rec = FlightRecorder(capacity=16)
    for i in range(100):
        rec.record_phase(PH_FORM, i * 10, i * 10 + 5, rows=1, batch=i)
    assert rec.events_recorded() == 100
    snap = rec.snapshot()
    assert len(snap) == 16
    # the survivors are exactly the LAST 16 writes, oldest first
    assert [e["seq"] for e in snap] == list(range(84, 100))
    assert [e["batch"] for e in snap] == list(range(84, 100))
    for e in snap:
        assert e["end_ns"] - e["start_ns"] == 5


def test_capacity_rounds_up_to_power_of_two():
    rec = FlightRecorder(capacity=100)
    assert rec._cap == 128


def test_events_are_well_formed_and_ordered():
    rec = FlightRecorder(capacity=64)
    bid = rec.next_batch()
    t = time.perf_counter_ns()
    rec.record_phase(PH_QUEUE_WAIT, t, t + 100, rows=4, batch=bid)
    rec.record_phase(PH_FORM, t + 100, t + 200, rows=4, batch=bid)
    snap = rec.snapshot()
    assert [e["phase"] for e in snap] == [PH_QUEUE_WAIT, PH_FORM]
    assert all(e["kind"] == "batch" for e in snap)
    assert all(e["end_ns"] >= e["start_ns"] for e in snap)


# ---------------------------------------------------------------------------
# serving-path pairing: healthy, shed, expired, device-raise
# ---------------------------------------------------------------------------


class _StubEnvBase:
    """The duck-typed surface the batcher + service halves touch."""

    supports_host_fastpath = False
    always_accept_namespace = None

    def pre_eval_hooks_of(self, target):
        return []

    def _lookup_top_level(self, pid):
        return object()

    def should_always_accept_requests_made_inside_of_namespace(self, ns):
        return False

    def get_policy_mode(self, pid):
        from policy_server_tpu.models.policy import PolicyMode

        return PolicyMode.PROTECT

    def get_policy_allowed_to_mutate(self, pid):
        return False


def _batches_by_id(rec: FlightRecorder) -> dict[int, set[str]]:
    out: dict[int, set[str]] = {}
    for e in rec.snapshot():
        if e["kind"] == "batch" and e["batch"] >= 0:
            out.setdefault(e["batch"], set()).add(e["phase"])
    return out


def test_healthy_batch_records_core_phases(env):
    rec = flightrec.install(FlightRecorder(capacity=4096))
    b = MicroBatcher(
        env, max_batch_size=8, batch_timeout_ms=1.0, policy_timeout=10.0,
        host_fastpath_threshold=0,
    ).start()
    try:
        futs = [
            b.submit("priv", _review(f"p{i}"), RequestOrigin.VALIDATE)
            for i in range(8)
        ]
        for f in futs:
            assert f.result(timeout=15).uid
    finally:
        b.shutdown()
    batches = _batches_by_id(rec)
    assert batches, "no batch events recorded"
    for phases in batches.values():
        # every dispatched batch pairs form+dispatch+deliver around its
        # queue_wait; no dispatch may appear without its form
        assert PH_QUEUE_WAIT in phases and PH_FORM in phases
        if PH_DISPATCH in phases:
            assert PH_DELIVER in phases
    att = rec.attribution()
    assert att["batches_complete"] >= 1
    assert att["rows"] >= 8


def test_shed_burst_records_no_partial_batches(env):
    rec = flightrec.install(FlightRecorder(capacity=1024))
    # dispatch loop NOT started: the queue backs up, and a poisoned RTT
    # estimate makes admission shed everything that follows
    b = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0,
        policy_timeout=10.0, request_timeout_ms=50.0, queue_capacity=8,
    )
    try:
        b._dev_rtt[4] = 10.0
        filler = b.submit_nowait(
            "priv", _review("fill"), RequestOrigin.VALIDATE
        )
        with pytest.raises(ShedError):
            b.submit("priv", _review(), RequestOrigin.VALIDATE)
        futs = b.submit_many(
            [("priv", _review(f"s{i}")) for i in range(4)],
            RequestOrigin.VALIDATE,
        )
        for f in futs:
            with pytest.raises(ShedError):
                f.result(timeout=5)
    finally:
        b.shutdown()
    assert filler.result(timeout=5).status.code == 503  # shutdown drain
    # shed rows never formed a batch: the ring holds no batch events at
    # all (nothing dangles half-open)
    assert _batches_by_id(rec) == {}


def test_expired_rows_record_form_without_dispatch(env):
    """Rows whose deadline passes in the queue drop pre-encode (504):
    their batch records queue_wait+form but NO dispatch/deliver — and
    the attribution report simply excludes the incomplete batch."""
    rec = flightrec.install(FlightRecorder(capacity=1024))
    b = MicroBatcher(
        env, max_batch_size=4, batch_timeout_ms=1.0,
        policy_timeout=10.0, request_timeout_ms=30.0,
        host_fastpath_threshold=0,
    )
    # submit BEFORE starting the dispatch loop, then let the deadline
    # lapse: formation happens after expiry
    futs = [
        b.submit_nowait("priv", _review(f"e{i}"), RequestOrigin.VALIDATE)
        for i in range(4)
    ]
    time.sleep(0.08)
    b.start()
    try:
        for f in futs:
            r = f.result(timeout=10)
            assert r.status.code == 504
    finally:
        b.shutdown()
    batches = _batches_by_id(rec)
    assert batches, "expired batch should still record its host phases"
    for phases in batches.values():
        assert PH_FORM in phases
        assert PH_DISPATCH not in phases and PH_DELIVER not in phases
    assert rec.attribution()["batches_complete"] == 0


def test_device_raise_leaves_no_dispatch_event():
    """A validate_batch raise fails the rows in-band; the batch's
    dispatch window never records (excluded from attribution) and no
    later phase dangles."""

    class RaisingEnv(_StubEnvBase):
        def validate_batch(self, items, run_hooks=True, prefer_host=False,
                           live=False):
            raise RuntimeError("device fault")

    rec = flightrec.install(FlightRecorder(capacity=256))
    b = MicroBatcher(
        RaisingEnv(), max_batch_size=4, batch_timeout_ms=1.0,
        policy_timeout=5.0, host_fastpath_threshold=0,
    ).start()
    try:
        futs = [
            b.submit_nowait(
                "priv", _review(f"r{i}"), RequestOrigin.VALIDATE
            )
            for i in range(4)
        ]
        for f in futs:
            with pytest.raises(RuntimeError):
                f.result(timeout=10)
    finally:
        b.shutdown()
    for phases in _batches_by_id(rec).values():
        assert PH_DISPATCH not in phases and PH_DELIVER not in phases


# ---------------------------------------------------------------------------
# overhead A/B (the <=2% contract, asserted loosely against CI noise —
# the honest number rides the batcher_serving_path bench line)
# ---------------------------------------------------------------------------


def test_recorder_overhead_on_serving_path():
    class EchoEnv(_StubEnvBase):
        def validate_batch(self, items, run_hooks=True, prefer_host=False,
                           live=False):
            return [
                AdmissionResponse(uid=req.uid(), allowed=True)
                for _pid, req in items
            ]

    def drive(n: int) -> float:
        b = MicroBatcher(
            EchoEnv(), max_batch_size=128, batch_timeout_ms=2.0,
            policy_timeout=30.0, host_fastpath_threshold=0,
        ).start()
        try:
            reqs = [_review(f"o{i % 64}") for i in range(256)]
            items = [("priv", reqs[i % 256]) for i in range(n)]
            t0 = time.perf_counter()
            futs = []
            for c in range(0, n, 128):
                futs.extend(
                    b.submit_many(items[c : c + 128], RequestOrigin.VALIDATE)
                )
            for f in futs:
                f.result(timeout=30)
            return time.perf_counter() - t0
        finally:
            b.shutdown()

    n = 6000
    drive(n)  # warm both paths' allocators
    rec = flightrec.install(FlightRecorder(capacity=65536))
    t_on = min(drive(n) for _ in range(2))
    flightrec.install(None)
    events = rec.events_recorded()
    assert events > 0, "recorder saw no events while on"
    # the <=2% contract is judged DETERMINISTICALLY (the wall-clock A/B
    # on a contended CI box flakes on scheduler noise alone — observed;
    # the honest macro A/B lives on the batcher_serving_path bench
    # line): events the recorder actually wrote during the ON drive,
    # costed at the measured per-event price, must stay far under the
    # drive's wall. A recorder accidentally doing per-BATCH work per
    # ROW inflates `events` ~100x and fails this loudly.
    probe = FlightRecorder(capacity=4096)
    t0 = time.perf_counter()
    for i in range(2000):
        probe.record_phase(PH_DISPATCH, i, i + 100, rows=128, batch=i)
    per_event_s = (time.perf_counter() - t0) / 2000
    modeled = events * per_event_s / t_on
    assert modeled < 0.05, (
        f"modeled recorder overhead {modeled:.1%} "
        f"({events} events x {per_event_s * 1e6:.2f}us / {t_on:.2f}s)"
    )


# ---------------------------------------------------------------------------
# timeline export schema
# ---------------------------------------------------------------------------


def test_chrome_trace_schema(env):
    rec = flightrec.install(FlightRecorder(capacity=4096, row_sample_rate=1.0))
    b = MicroBatcher(
        env, max_batch_size=8, batch_timeout_ms=1.0, policy_timeout=10.0,
        host_fastpath_threshold=0,
    ).start()
    try:
        futs = [
            b.submit("priv", _review(f"t{i}"), RequestOrigin.VALIDATE)
            for i in range(8)
        ]
        for f in futs:
            f.result(timeout=15)
    finally:
        b.shutdown()
    doc = json.loads(rec.chrome_trace_json())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    slices = [e for e in events if e["ph"] == "X"]
    assert metas and slices
    names = {e["name"] for e in metas}
    assert {"process_name", "thread_name"} <= names
    for e in slices:
        assert e["name"] in PHASES
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["dur"] >= 0
        assert e["pid"] in (1, 2)
        assert isinstance(e["tid"], int)
        assert "rows" in e["args"] and "batch" in e["args"]
    # row_sample_rate=1.0: sampled-row slices present with uids
    rows = [e for e in slices if e["pid"] == 2]
    assert rows and all(e["args"].get("uid") for e in rows)
    assert doc["otherData"]["events_recorded"] == rec.events_recorded()
    assert isinstance(doc["exemplars"], list) and doc["exemplars"]
    ex = doc["exemplars"][0]
    assert {"trace_id", "policy_id", "latency_seconds",
            "slowest_phase", "phase_breakdown_us"} <= set(ex)


# ---------------------------------------------------------------------------
# exemplar reservoir
# ---------------------------------------------------------------------------


def test_exemplars_keep_slowest_n_with_trace_ids():
    rec = FlightRecorder(capacity=64, exemplar_slots=4)
    t0 = time.perf_counter_ns()
    for i in range(32):
        lat_ns = (i + 1) * 1_000_000
        rec.observe_row(
            f"uid-{i}", "pol", t0, t0 + lat_ns, 1,
            {PH_DISPATCH: lat_ns},
        )
    ex = rec.exemplars()
    assert len(ex) == 4
    assert [e["trace_id"] for e in ex] == [
        "uid-31", "uid-30", "uid-29", "uid-28"
    ]
    assert all(e["slowest_phase"] == PH_DISPATCH for e in ex)
    # the fast path: a row under the floor never takes the lock
    assert rec.row_flags(0.0000001) & FlightRecorder.ROW_EXEMPLAR == 0


def test_exemplar_table_unfreezes_after_spike_window():
    """Post-review regression: a transient spike (boot compiles) fills
    the window and raises the floor; once the window expires, later
    FAST rows must still rotate it (offer-path expiry check) instead of
    serving the stale spike forever — and an idle read rotates too."""
    rec = FlightRecorder(
        capacity=64, exemplar_slots=2, exemplar_window_seconds=0.01
    )
    # done stamps sit at NOW (enqueued in the past): the exemplar
    # window clock keys off completion time
    t0 = time.perf_counter_ns()
    rec.offer_exemplar("spike-a", "pol", t0 - 100_000_000, t0, {})
    rec.offer_exemplar("spike-b", "pol", t0 - 90_000_000, t0, {})
    assert rec._ex_floor > 0
    time.sleep(0.03)
    # a fast row WELL below the spike floor, offered after expiry:
    # the offer must ROTATE (spikes demote to the previous window,
    # floor resets, the fast row enters the new current window) —
    # before the fix the floor gate dropped it and nothing ever rotated
    t1 = time.perf_counter_ns()
    rec.offer_exemplar("fast", "pol", t1 - 1_000_000, t1, {})
    with rec._ex_lock:
        assert [e[1] for e in rec._ex_current] == ["fast"]
        assert rec._ex_floor == 0.0
    # two more idle windows: reads alone age the spike rows out
    time.sleep(0.03)
    rec.exemplars()
    time.sleep(0.03)
    ids = {e["trace_id"] for e in rec.exemplars()}
    assert "spike-a" not in ids and "spike-b" not in ids


def test_exemplars_dedup_duplicate_label_sets():
    """Post-review regression: the uid is client-supplied, and the same
    uid surviving in both the current and previous windows must not
    yield two exemplar entries with identical label tuples — the
    /metrics family would then emit duplicate series and prometheus
    rejects the ENTIRE scrape."""
    rec = FlightRecorder(
        capacity=64, exemplar_slots=4, exemplar_window_seconds=0.01
    )
    t = time.perf_counter_ns()
    rec.offer_exemplar(
        "dup-uid", "pol", t - 50_000_000, t, {PH_DISPATCH: 50_000_000}
    )
    time.sleep(0.03)
    t = time.perf_counter_ns()
    rec.offer_exemplar(
        "dup-uid", "pol", t - 40_000_000, t, {PH_DISPATCH: 40_000_000}
    )
    ex = rec.exemplars()
    assert len(ex) == 1
    # the slowest instance won the dedup
    assert ex[0]["latency_seconds"] == pytest.approx(0.05)


def test_exemplar_window_rotation():
    rec = FlightRecorder(
        capacity=64, exemplar_slots=2, exemplar_window_seconds=0.0
    )
    t0 = time.perf_counter_ns()
    rec.observe_row("old-slow", "pol", t0, t0 + 50_000_000, 1, {})
    # window 0s: the next observation rotates current → previous
    rec.observe_row("new-fast", "pol", t0, t0 + 1_000_000, 1, {})
    ids = {e["trace_id"] for e in rec.exemplars()}
    assert ids == {"old-slow", "new-fast"}  # previous window still visible


# ---------------------------------------------------------------------------
# attribution math
# ---------------------------------------------------------------------------


def test_attribution_residual_math():
    rec = FlightRecorder(capacity=256)
    bid = rec.next_batch()
    # wall 1000ns for 10 rows: form 100, dispatch 800 (600 explained by
    # encode+fetch), deliver 100 → residual = 200 (dispatch gap)
    rec.record_phase(PH_QUEUE_WAIT, 0, 1000, rows=10, batch=bid)
    rec.record_phase(PH_FORM, 1000, 1100, rows=10, batch=bid)
    rec.record_phase(PH_DISPATCH, 1100, 1900, rows=10, batch=bid)
    rec.record_phase(flightrec.PH_ENCODE, 1100, 1500, rows=10, batch=bid)
    rec.record_phase(flightrec.PH_FETCH, 1500, 1700, rows=10, batch=bid)
    rec.record_phase(PH_DELIVER, 1900, 2000, rows=10, batch=bid)
    att = rec.attribution()
    assert att["batches_complete"] == 1
    assert att["rows"] == 10
    assert att["wall_us_per_row"] == pytest.approx(0.1)  # 1000ns/10rows
    assert att["residual_us_per_row"] == pytest.approx(0.02)  # 200ns/10
    assert att["residual_fraction_of_wall"] == pytest.approx(0.2)
    # device_execute never adds to attribution (it nests under fetch)
    rec.record_phase(
        flightrec.PH_DEVICE_EXECUTE, 1500, 1700, rows=10, batch=bid
    )
    assert rec.attribution()["residual_us_per_row"] == pytest.approx(0.02)


def test_attribution_since_cursor_excludes_old_batches():
    rec = FlightRecorder(capacity=256)
    b1 = rec.next_batch()
    rec.record_phase(PH_FORM, 0, 100, rows=1, batch=b1)
    rec.record_phase(PH_DISPATCH, 100, 200, rows=1, batch=b1)
    rec.record_phase(PH_DELIVER, 200, 300, rows=1, batch=b1)
    cursor = rec.events_recorded()
    b2 = rec.next_batch()
    rec.record_phase(PH_FORM, 0, 100, rows=5, batch=b2)
    rec.record_phase(PH_DISPATCH, 100, 200, rows=5, batch=b2)
    rec.record_phase(PH_DELIVER, 200, 300, rows=5, batch=b2)
    att = rec.attribution(since=cursor)
    assert att["batches_complete"] == 1
    assert att["rows"] == 5


# ---------------------------------------------------------------------------
# the host route's phase (PR 35)
# ---------------------------------------------------------------------------


def _host_route_batches(env, rec: FlightRecorder, rows: int) -> list[dict]:
    """Batches of ``rows`` under the documented default threshold of 64,
    so the router answers each on the host; → each batch's ring events."""
    b = MicroBatcher(
        env, max_batch_size=128, batch_timeout_ms=1.0, policy_timeout=10.0,
        host_fastpath_threshold=64, latency_budget_ms=50.0,
    ).start()
    try:
        for burst in range(4):
            futs = b.submit_many(
                [("priv", _review(f"host-{burst}-{i}")) for i in range(rows)],
                RequestOrigin.VALIDATE,
            )
            for f in futs:
                assert f.result(timeout=15).uid
        assert b.host_fastpath_batches == 4
    finally:
        b.shutdown()
    batches: dict[int, dict] = {}
    for e in rec.snapshot():
        if e["kind"] == "batch" and e["batch"] >= 0:
            batches.setdefault(e["batch"], {}).setdefault(
                e["phase"], []).append(e)
    return [phs for phs in batches.values() if PH_DISPATCH in phs]


def test_host_eval_is_a_phase_with_one_stamp_site_and_a_panel():
    from pathlib import Path

    from tools.graftcheck import observability as ob

    root = Path(__file__).resolve().parent.parent
    assert flightrec.PH_HOST_EVAL == "host_eval" in PHASES
    assert flightrec.PH_HOST_EVAL in flightrec._DISPATCH_NESTED
    consts, members = ob._flightrec_phases(
        root / "policy_server_tpu" / "telemetry" / "flightrec.py")
    assert "PH_HOST_EVAL" in members
    sites = ob._phase_record_sites(root / "policy_server_tpu", consts)
    (site,) = sites["host_eval"]
    assert site[0].endswith("evaluation/environment.py")
    dashboard = json.loads((root / "kubewarden-dashboard.json").read_text())
    exprs = [t["expr"] for p in dashboard["panels"] for t in p["targets"]]
    assert any('phase_latency_seconds_sum{phase="host_eval"}' in e
               for e in exprs)


def test_a_host_path_batch_stamps_host_eval_once_and_reaches_metrics(env):
    from policy_server_tpu.telemetry import metrics as metrics_mod

    metrics_mod.reset_metrics_for_tests()
    try:
        registry = metrics_mod.default_registry()
        rec = flightrec.install(
            FlightRecorder(capacity=4096, registry=registry))
        batches = _host_route_batches(env, rec, rows=12)
        assert len(batches) == 4
        for phs in batches:
            (stamp,) = phs[flightrec.PH_HOST_EVAL]  # one clock pair a batch
            assert stamp["rows"] == 12
            # no device phase: the batch never left the host
            assert not {"encode", "launch", "fetch"} & set(phs)
        line = next(
            ln for ln in registry.exposition().decode().splitlines()
            if ln.startswith(
                'policy_server_phase_latency_seconds_count{phase="host_eval"}'))
        assert float(line.split()[-1]) == 4
    finally:
        flightrec.install(None)
        metrics_mod.reset_metrics_for_tests()


def test_a_host_path_batchs_dispatch_is_attributed(env):
    """The nested phases of a host-path batch (``host_eval`` and the two
    hand-offs) sum to its ``dispatch`` window within the residual the
    phase report tolerates (a quarter of the wall); without the phase
    the whole evaluation read as unattributed dispatch time."""
    rec = flightrec.install(FlightRecorder(capacity=4096))
    batches = _host_route_batches(env, rec, rows=48)

    def dur(phs: dict, name: str) -> int:
        return sum(e["end_ns"] - e["start_ns"] for e in phs.get(name, ()))

    dispatch = sum(dur(phs, PH_DISPATCH) for phs in batches)
    nested = sum(dur(phs, p) for phs in batches
                 for p in flightrec._DISPATCH_NESTED)
    host_eval = sum(dur(phs, flightrec.PH_HOST_EVAL) for phs in batches)
    assert 0 < nested <= dispatch
    assert dispatch - nested < 0.25 * dispatch
    assert host_eval > 0.5 * dispatch  # and it is most of the window
    att = rec.attribution()
    assert att["phase_us_per_row"]["host_eval"] > 0
    assert att["residual_fraction_of_wall"] < 0.25
