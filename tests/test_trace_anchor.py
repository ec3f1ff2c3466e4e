"""PR 27: the flight recorder on the device trace's clock.

* a ``jax.profiler`` trace of a few served batches holds one ``ps:launch``
  host event per dispatched batch, carrying ``batch``, ``rows`` and
  ``perf_counter_ns``, and the offset they give lays each launch inside its
  batch's ``dispatch`` interval of the ring (read with the benchmark's own
  reader, benchmarks/host_spans.py);
* the collector is a ring phase and two counters;
* encode's CPU time moves with, and never exceeds, its wall time;
* ``snapshot`` / ``chrome_trace`` keep only what overlaps an interval;
* the fused programs' names match the benchmark's patterns whatever the
  methods are called.
"""

from __future__ import annotations

import fnmatch
import gc
import glob
import importlib
import json
import sys
import time
from pathlib import Path

import jax
import pytest

from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.evaluation import environment as environment_mod
from policy_server_tpu.evaluation.environment import (
    EvaluationEnvironmentBuilder,
)
from policy_server_tpu.models import AdmissionReviewRequest, ValidateRequest
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.parallel import make_mesh
from policy_server_tpu.parallel.mesh import MeshSpec
from policy_server_tpu.runtime.batcher import MicroBatcher
from policy_server_tpu.telemetry import flightrec
from policy_server_tpu.telemetry.flightrec import (
    PH_DISPATCH,
    PH_ENCODE,
    PH_GC,
    PH_LAUNCH,
    FlightRecorder,
)

from conftest import build_admission_review_dict

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmarks"
PATTERNS = json.loads(
    (BENCH / "layer_metrics" / "predicate_roofline.json").read_text()
)["module_patterns"]


@pytest.fixture(autouse=True)
def no_global_recorder():
    yield
    flightrec.install(None)


@pytest.fixture(scope="module")
def host_spans():
    """The benchmark's reader, imported as the benchmark does (its
    directory on sys.path only while it loads)."""
    sys.path.insert(0, str(BENCH))
    try:
        return importlib.import_module("host_spans")
    finally:
        sys.path.remove(str(BENCH))


def _review(name: str, privileged: bool = False) -> ValidateRequest:
    doc = build_admission_review_dict()
    doc["request"]["object"] = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": name, "namespace": "default"},
        "spec": {"containers": [{
            "name": "c", "image": "nginx",
            "securityContext": {"privileged": privileged},
        }]},
    }
    return ValidateRequest.from_admission(
        AdmissionReviewRequest.from_dict(doc).request
    )


def _policies() -> dict:
    return {
        "priv": parse_policy_entry(
            "priv", {"module": "builtin://pod-privileged"}
        )
    }


@pytest.fixture(scope="module")
def env():
    # no verdict cache: every batch reaches the device program
    e = EvaluationEnvironmentBuilder(
        backend="jax", verdict_cache_size=0
    ).build(_policies())
    yield e
    e.close()


def _burst(b: MicroBatcher, tag: str, n: int = 8) -> None:
    futs = [
        b.submit("priv", _review(f"{tag}-{i}", i % 2 == 0),
                 RequestOrigin.VALIDATE)
        for i in range(n)
    ]
    for f in futs:
        assert f.result(timeout=60).uid


def test_a_trace_holds_one_launch_per_batch_on_the_rings_clock(
    env, host_spans, tmp_path
):
    rec = flightrec.install(FlightRecorder(capacity=4096))
    b = MicroBatcher(
        env, max_batch_size=8, batch_timeout_ms=1.0, policy_timeout=30.0,
        # routing pinned to the device, as the benchmark's cells pin it:
        # on a loaded box the latency router would answer host-side
        host_fastpath_threshold=0, latency_budget_ms=0,
    ).start()
    try:
        _burst(b, "warm")
        _burst(b, "warm2")
        options = jax.profiler.ProfileOptions()  # the launcher's settings
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        since_ns = time.perf_counter_ns()
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for k in range(3):
                _burst(b, f"traced{k}")
        finally:
            jax.profiler.stop_trace()
        until_ns = time.perf_counter_ns()
    finally:
        b.shutdown()
    pb = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[-1]
    trace = host_spans.load_trace(Path(pb))
    launches = trace["launches"]
    dispatched = {
        e["batch"]: e for e in rec.snapshot(since_ns, until_ns)
        if e["kind"] == "batch" and e["phase"] == PH_DISPATCH
        and e["start_ns"] >= since_ns
    }
    assert len(dispatched) >= 3
    # one launch per dispatched batch, each with its three stats
    assert sorted(l[2] for l in launches) == sorted(dispatched)
    assert all(l[3] == dispatched[l[2]]["rows"] for l in launches)
    assert all(since_ns <= l[4] <= until_ns for l in launches)
    timing = host_spans.clock(trace)
    assert timing["launches"] == len(launches)
    assert timing["offset_range_us"] < 5000  # a CPU box under test load
    launched = {
        e["batch"]: e for e in rec.snapshot(since_ns, until_ns)
        if e["kind"] == "batch" and e["phase"] == PH_LAUNCH
    }
    for start, dur, batch, _rows, _reading, enqueue in launches:
        # on the ring's clock the event lies inside its batch's dispatch
        # window, and inside the launch phase that times the same call
        begin = start + timing["offset_ns"]
        for outer in (dispatched[batch], launched[batch]):
            assert outer["start_ns"] <= begin
            assert begin + dur <= outer["end_ns"] + 1000  # exit, then stamp
        assert start <= enqueue <= start + dur
    # the ring as /debug/timeline gives it reads back the same intervals
    ring = host_spans.ring(rec.chrome_trace(since_ns, until_ns))
    assert {
        (batch, round(s), round(e)) for name, s, e, batch in ring
        if name == PH_DISPATCH and batch in dispatched
    } == {
        (bid, e["start_ns"], e["end_ns"]) for bid, e in dispatched.items()
    }


def test_a_full_collector_pass_is_a_ring_interval_and_two_counters():
    rec = flightrec.install(FlightRecorder(capacity=64))
    assert rec.on_gc in gc.callbacks
    before = rec.gc_stats()
    t0 = time.perf_counter_ns()
    gc.collect()
    t1 = time.perf_counter_ns()
    after = rec.gc_stats()
    passes = [e for e in rec.snapshot() if e["phase"] == PH_GC]
    assert len(passes) == 1
    (ev,) = passes
    assert ev["batch"] == -1 and t0 <= ev["start_ns"] <= ev["end_ns"] <= t1
    assert after["passes"][2] == before["passes"][2] + 1
    assert after["pause_ns"][2] - before["pause_ns"][2] == \
        ev["end_ns"] - ev["start_ns"]
    # a young pass is counted and, being short, leaves no interval
    gc.collect(0)
    assert rec.gc_stats()["passes"][0] == after["passes"][0] + 1
    assert len([e for e in rec.snapshot() if e["phase"] == PH_GC]) == 1
    # the hook leaves with its recorder
    flightrec.install(None)
    assert rec.on_gc not in gc.callbacks
    gc.collect()
    assert rec.gc_stats()["passes"][2] == after["passes"][2]


def test_a_young_pass_that_held_the_interpreter_long_is_an_interval():
    rec = FlightRecorder(capacity=64)
    rec.on_gc("start", {"generation": 0})
    rec._gc_began -= flightrec.GC_STAMP_MIN_NS  # as if it began 1 ms ago
    rec.on_gc("stop", {"generation": 0, "collected": 0, "uncollectable": 0})
    (ev,) = [e for e in rec.snapshot() if e["phase"] == PH_GC]
    assert ev["end_ns"] - ev["start_ns"] >= flightrec.GC_STAMP_MIN_NS
    assert rec.gc_stats()["passes"] == [1, 0, 0]
    # a stop with no start (the hook installed mid-pass) counts nothing
    rec.on_gc("stop", {"generation": 2, "collected": 0, "uncollectable": 0})
    assert rec.gc_stats()["passes"] == [1, 0, 0]


def test_encodes_cpu_time_moves_with_and_stays_under_its_wall_time(env):
    rec = flightrec.install(FlightRecorder(capacity=1024))
    before = dict(env.host_profile)
    items = [("priv", _review(f"cpu-{i}", i % 3 == 0)) for i in range(64)]
    for _ in range(4):  # single-threaded: this thread encodes inline
        results = env.validate_batch(items)
        assert not any(isinstance(r, Exception) for r in results)
    after = dict(env.host_profile)
    rows = after["encode_rows"] - before["encode_rows"]
    wall = after["encode_ns"] - before["encode_ns"]
    cpu = after["encode_cpu_ns"] - before["encode_cpu_ns"]
    assert rows == 4 * 64
    resolution_ns = time.get_clock_info("thread_time").resolution * 1e9
    encodes = sum(1 for e in rec.snapshot() if e["phase"] == PH_ENCODE)
    assert 0 < cpu <= wall + encodes * resolution_ns


def test_snapshot_and_timeline_keep_only_what_overlaps_the_interval():
    rec = FlightRecorder(capacity=64)
    for k, (s, e) in enumerate([(100, 200), (250, 300), (300, 400),
                                (450, 500)]):
        rec.record_phase(PH_ENCODE, s, e, rows=1, batch=k)
    rec.record_batch_mix(batch=1, hit_rows=270, total_rows=300)

    def batches(**kw):
        return [e["batch"] for e in rec.snapshot(**kw)
                if e["kind"] == "batch"]

    assert batches() == [0, 1, 2, 3]
    assert batches(since_ns=200) == [0, 1, 2, 3]  # touching counts
    assert batches(since_ns=201) == [1, 2, 3]
    assert batches(until_ns=299) == [0, 1]
    assert batches(since_ns=260, until_ns=290) == [1]
    assert batches(since_ns=401, until_ns=449) == []
    doc = rec.chrome_trace(since_ns=260, until_ns=310)
    slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["args"]["batch"] for e in slices] == [1, 2]
    assert json.loads(rec.chrome_trace_json(260, 310))["traceEvents"] == \
        doc["traceEvents"]


def _program_name(jitted) -> str:
    """The name XLA knows a jitted callable's program by."""
    return "jit_" + jitted.__name__


def _matches(name: str) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in PATTERNS)


def test_jit_names_a_program_after_the_pinned_name():
    def anything_at_all(x):
        return x + 1

    pinned = environment_mod._named_program(
        anything_at_all, environment_mod.FUSED_PROGRAM_NAME
    )
    text = jax.jit(pinned).lower(1.0).as_text()
    assert "module @jit__forward " in text
    assert pinned(1) == 2


@pytest.mark.parametrize("variant", ["single", "columnar", "data:4"])
def test_the_fused_programs_names_match_the_benchmarks_patterns(variant):
    """A rename of the methods fails here and does not null
    predicate_roofline in the ledger."""
    e = EvaluationEnvironmentBuilder(backend="jax").build(_policies())
    try:
        if variant == "data:4":
            assert len(jax.devices()) >= 4
            e.attach_mesh(
                make_mesh(MeshSpec.parse("data:4"), jax.devices()[:4])
            )
            programs = [e._fused, e._fused_planes]
        else:
            programs = [e._fused if variant == "single" else e._fused_planes]
        for program in programs:
            assert _matches(_program_name(program)), _program_name(program)
        assert not _matches("jit__renamed")
    finally:
        e.close()


def test_the_launch_metrics_read_the_launch_phase_and_the_h2d_counter():
    """benchmarks/layer_metrics/{launch_ms_mean,h2d_arrays_per_launch}.json
    (data files, PR 28) against the families the server exports: the
    launch phase's histogram gives the mean and the launches, the new
    counter the arrays; a program without the counter (the parent) gives
    no number and no error."""
    from policy_server_tpu.telemetry import metrics as names

    sys.path.insert(0, str(BENCH))
    try:
        reduce = importlib.import_module("reduce")
    finally:
        sys.path.remove(str(BENCH))
    phases = "policy_server_phase_latency_seconds"
    assert flightrec.PH_LAUNCH in flightrec.PHASES

    def samples(seconds: float, launches: int, arrays: int | None):
        lines = [
            f'{phases}_sum{{phase="{flightrec.PH_LAUNCH}"}} {seconds}',
            f'{phases}_count{{phase="{flightrec.PH_LAUNCH}"}} {launches}',
            f'{phases}_sum{{phase="encode"}} 99.0',
            f'{phases}_count{{phase="encode"}} 7',
        ]
        if arrays is not None:
            lines.append(f"{names.LAUNCH_H2D_ARRAYS}_total {arrays}")
        return reduce.parse_metrics("\n".join(lines) + "\n")

    ctx = {"before": samples(1.0, 100, 90), "after": samples(1.9, 400, 390)}
    assert reduce.read_layer_metric("launch_ms_mean", ctx) == pytest.approx(3.0)
    assert reduce.read_layer_metric("h2d_arrays_per_launch", ctx) == (
        pytest.approx(1.0)
    )
    parent = {"before": samples(1.0, 100, None), "after": samples(1.9, 400, None)}
    assert reduce.read_layer_metric("launch_ms_mean", parent) == pytest.approx(3.0)
    assert reduce.read_layer_metric("h2d_arrays_per_launch", parent) is None
