"""Tests of ``benchmarks/host_spans.py``: the clock, the link and the
attribution rule on a hand-built case and on a fixture recorded from a chip
run, and that what PR 26's readers return on its recorded trace has not
moved. CPU only:

    python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import check_manifest  # noqa: E402
import host_spans  # noqa: E402
import reduce  # noqa: E402
import run  # noqa: E402

TRACE = json.loads((HERE / "recorded_trace.json").read_text())
RECORDED = json.loads((HERE / "recorded_host_spans.json").read_text())
OFFSET = 1_000_000  # the hand-built ring's clock runs this far ahead


def _timeline(events: list, since: float = 0, until: float = 20_000) -> dict:
    """A /debug/timeline document of (phase, start, end, batch) given on
    the trace's clock."""
    return {
        "since_ns": since + OFFSET, "until_ns": until + OFFSET,
        "traceEvents": [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0},
            *({"name": name, "ph": "X", "pid": 1, "tid": 1,
               "ts": (s + OFFSET) / 1e3, "dur": (e - s) / 1e3,
               "args": {"rows": 8, "batch": batch}}
              for name, s, e, batch in events),
            # a sampled row's replayed segment (pid 2) is no batch interval
            {"name": "encode", "ph": "X", "pid": 2, "tid": 1, "ts": 0.0,
             "dur": 1e6, "args": {"rows": 1, "batch": 8, "uid": "u"}},
        ]}


def _hand_built() -> tuple[dict, dict]:
    """Three executions of the program (batches 7, 8, 9), two operations
    each with 10 ns between them, and two gaps of 3,910 ns."""
    ops, modules = [], []
    for start in (1000, 5000, 9000):
        modules.append(["jit__forward_planes(123)", start, 100])
        ops += [["%fusion", start, 40], ["%concatenate.27", start + 50, 40]]
    modules.append(["jit_something_else(9)", 12_000, 10])
    trace = {
        "devices": {"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops}},
        # start, duration, batch, rows, perf_counter_ns (the readings lie a
        # few ns around the offset), enqueue: 0, 10 and 15 ns before the
        # program, so the device's clock needs no shift
        "launches": [[900, 150, 7, 8, 900 + OFFSET - 3, 1000],
                     [4800, 250, 8, 8, 4800 + OFFSET, 4990],
                     [8900, 150, 9, 8, 8900 + OFFSET + 5, 8985]],
    }
    timeline = _timeline([
        ("gc", 1200, 1500, -1),
        ("fetch", 1000, 1150, 7), ("materialize", 1150, 1200, 7),
        ("queue_wait", 2000, 3000, 8), ("form", 3000, 3100, 8),
        ("dispatch", 3100, 5200, 8), ("handoff", 3100, 3150, 8),
        ("prepare", 3150, 3300, 8), ("encode", 3300, 4400, 8),
        ("fetch", 5000, 5300, 8),
        ("queue_wait", 5000, 6000, 9), ("form", 6000, 6100, 9),
        ("native_accept", 0, 20_000, -1),  # a burst aggregate: no one's
    ])
    return trace, timeline


def test_the_rule_on_a_hand_built_case():
    trace, timeline = _hand_built()
    found = host_spans.attribute(trace, timeline)
    ns = {k: round(v * 1e9, 6) for k, v in found["idle_by_part_s"].items()}
    assert ns == {
        # first gap, 1090..5000, before batch 8's program
        "fetch": 60, "materialize": 50,  # batch 7's return leg
        "gc": 300,  # over everything else it covers
        "no_request": 500,  # 1500..2000: before batch 8's first enqueue
        "handoff": 50, "prepare": 150,
        "encode": 1100,  # nested in dispatch: the innermost has the time
        "dispatch_self": 600,  # 4400..5000: in dispatch, under no phase
        # both gaps: batch 8's 1000 + 100, batch 9's 910 + 100 (its own
        # phases go before batch 8's fetch, which covers 5090..5300 too)
        "queue_wait": 1910, "form": 200,
        "unattributed": 2900,  # 6100..9000: nothing covers it
        "in_program": 30,  # 10 ns inside each execution
    }
    assert found["idle_s"] == pytest.approx(7850e-9)
    assert found["attributed_s"] == pytest.approx((7850 - 600 - 2900 - 30) / 1e9)
    assert found["gaps"][:2] == [(pytest.approx(3910e-9), "unattributed"),
                                 (pytest.approx(3910e-9), "encode")]
    assert found["clock"]["offset_ns"] == OFFSET
    assert found["clock"]["offset_range_us"] == pytest.approx(0.008)
    assert found["link"] == {
        "executions": 3, "linked": 3, "unlinked_at_the_head": 0,
        "unlinked_at_the_tail": 0, "outside_fetch": 0, "outside_dispatch": 0,
        "device_shift_us": 0.0,
        "enqueue_to_execution_us": {"median": 0.01, "max": 0.015}}
    assert host_spans.idle_attributed({}, {"spans": found}) == \
        pytest.approx(100 * 4320 / 7850)


def test_the_gaps_keep_their_shape_and_gain_a_name():
    trace, timeline = _hand_built()
    b = host_spans.breakdown(trace, host_spans.attribute(trace, timeline))
    assert b["device_ops"] == reduce.breakdown(trace)["device_ops"]
    assert b["idle_gaps"][:2] == [["host:unattributed", pytest.approx(3910e-9)],
                                  ["host:encode", pytest.approx(3910e-9)]]
    assert b["idle_gaps"][2] == ["device:in_program", pytest.approx(10e-9)]
    assert len(b["idle_gaps"]) == 5


def test_the_link_pairs_in_order_and_finds_the_devices_clock():
    def launch(enqueue: float, batch: int) -> host_spans.Launch:
        return host_spans.Launch(enqueue - 100, 150, batch, 8, 0, enqueue)

    # the device's clock runs 2,000 ns ahead: every program seems to
    # start before it was enqueued. Gaps between launches are uneven, as
    # real ones are: only the right lay keeps enqueue-to-execution steady
    enqueues = [1000, 1800, 4000, 4300, 9000, 9700, 15_000]
    launches = [launch(t, k) for k, t in enumerate(enqueues, 1)]
    execs = [["p", t + 40 + 3 * k - 2000, 10] for k, t in enumerate(enqueues)]
    linked, shift = host_spans.link(execs, launches)
    assert [at.batch for at in linked] == [1, 2, 3, 4, 5, 6, 7]
    assert shift == 2000 - 40  # the least: the tightest pair meets
    # an execution launched before the trace began, and two launched
    # after the host's tracer stopped
    more = [["p", -5000, 10], *execs, ["p", 20_000, 10], ["p", 21_000, 10]]
    linked, shift = host_spans.link(more, launches)
    assert [at and at.batch for at in linked] == \
        [None, 1, 2, 3, 4, 5, 6, 7, None, None]
    assert shift == 2000 - 40
    # a launch whose program ran after the trace stopped
    linked, _ = host_spans.link(execs[:-1], launches)
    assert [at.batch for at in linked] == [1, 2, 3, 4, 5, 6]
    assert host_spans.link(execs, []) == ([None] * 7, 0.0)
    assert host_spans.link([], launches) == ([], 0.0)


def test_a_gap_the_hosts_tracer_did_not_see_is_counted_apart():
    trace, timeline = _hand_built()
    base = host_spans.attribute(trace, timeline)
    assert base["outside_the_hosts_trace_s"] == 0
    lines = trace["devices"]["/device:TPU:0"]
    # the device's tracer outlives the host's: one more execution, whose
    # launch is not in the trace, 3,910 ns after the last
    lines["XLA Modules"].append(["jit__forward_planes(123)", 13_000, 100])
    lines["XLA Ops"] += [["%fusion", 13_000, 40],
                         ["%concatenate.27", 13_050, 40]]
    found = host_spans.attribute(trace, timeline)
    assert found["link"]["unlinked_at_the_tail"] == 1
    assert found["outside_the_hosts_trace_s"] == pytest.approx(3920e-9)
    assert found["idle_by_part_s"] == base["idle_by_part_s"]
    assert len(found["gaps"]) == len(base["gaps"])


def test_an_execution_outside_its_batchs_fetch_or_dispatch_is_counted():
    trace, timeline = _hand_built()
    timeline["traceEvents"] += _timeline([
        ("fetch", 9000, 9050, 9),  # ends before the program's 9100
        ("dispatch", 9010, 9200, 9),  # begins after the program's start
    ])["traceEvents"]
    link = host_spans.attribute(trace, timeline)["link"]
    assert (link["outside_fetch"], link["outside_dispatch"]) == (1, 1)


def test_dispatch_unattributed_is_the_window_less_its_nested_phases():
    _trace, timeline = _hand_built()
    p = json.loads((BENCH / "layer_metrics"
                    / "dispatch_unattributed_ms_per_batch.json").read_text())
    # batch 8: 2100 ns less handoff 50, prepare 150, encode 1100 and the
    # 200 ns of its fetch that lie inside the window
    assert host_spans.dispatch_unattributed(p, {"timeline": timeline}) == \
        pytest.approx(600e-6)
    late = dict(timeline, since_ns=4000 + OFFSET)  # the window began before
    assert host_spans.dispatch_unattributed(p, {"timeline": late}) is None
    assert host_spans.dispatch_unattributed(p, {}) is None


def test_without_a_launch_or_a_ring_nothing_is_named():
    """The recorded fixture of PR 26 and an older program: ``breakdown``
    is ``reduce.breakdown``'s, and the readers find nothing."""
    trace, timeline = _hand_built()
    assert host_spans.attribute(TRACE, timeline) is None
    assert host_spans.attribute(trace, {"traceEvents": []}) is None
    assert host_spans.attribute(trace, None) is None
    assert host_spans.attribute(dict(trace, launches=[]), timeline) is None
    assert host_spans.breakdown(TRACE, None) == reduce.breakdown(TRACE)
    assert host_spans.breakdown(trace, None) == reduce.breakdown(trace)
    for ctx in ({}, {"spans": None}, {"timeline": timeline}):
        assert host_spans.idle_attributed({}, ctx) is None


GOLDEN_OPS = [["%concatenate.27", 6.651e-06], ["%fusion", 3.565e-06],
              ["%and_reduce_fusion.35", 2.147e-06]]
GOLDEN_GAPS = [["host:unattributed", 0.184048427],
               ["host:unattributed", 0.059621464],
               ["host:unattributed", 0.050618234]]


def test_pr_26s_readers_read_the_recorded_trace_as_at_the_parent():
    """Golden numbers taken from the parent's reduce.py (46b6308) on
    recorded_trace.json: this PR edits no file the benchmark had."""
    assert reduce.busy_seconds(TRACE) == {
        "/device:TPU:0": pytest.approx(3.5836e-05, rel=1e-9)}
    assert reduce.trace_idle({}, {"trace": TRACE, "traced_s": 0.4}) == \
        pytest.approx(99.991041, rel=1e-9)
    p = json.loads(
        (BENCH / "layer_metrics" / "predicate_roofline.json").read_text())
    assert reduce.module_seconds(TRACE, p["module_patterns"]) == \
        pytest.approx(3.7939e-05, rel=1e-9)
    b = reduce.breakdown(TRACE)
    assert b["device_ops"][:3] == GOLDEN_OPS
    assert b["idle_gaps"][:3] == GOLDEN_GAPS


def test_the_rule_on_a_fixture_recorded_from_a_chip_run():
    """Eight executions of a traced chip run of flagship32.unique-saturate
    (PR 27): device events, launches and ring as the run gave them."""
    trace, timeline = RECORDED["trace"], RECORDED["timeline"]
    found = host_spans.attribute(trace, timeline)
    assert found["clock"]["launches"] == len(trace["launches"]) == 7
    assert found["clock"]["offset_iqr_us"] < 100
    link = found["link"]
    # the first execution was launched before the trace began
    assert (link["executions"], link["linked"]) == (8, 7)
    assert (link["unlinked_at_the_head"], link["unlinked_at_the_tail"]) == (1, 0)
    assert link["outside_fetch"] == link["outside_dispatch"] == 0
    # on the chip the device plane's clock ran 1.8 ms ahead of the host's
    assert link["device_shift_us"] == pytest.approx(1810.736)
    assert 0 <= link["enqueue_to_execution_us"]["median"] < 100
    assert found["idle_s"] == pytest.approx(
        sum(found["idle_by_part_s"].values()))
    parts = found["idle_by_part_s"]
    assert list(parts)[:2] == ["launch", "encode"]
    assert parts["launch"] + parts["encode"] > 0.95 * found["idle_s"]
    assert found["gaps"][0] == (pytest.approx(0.224908775), "encode")
    assert host_spans.breakdown(trace, found)["idle_gaps"][:3] == [
        ["host:encode", pytest.approx(0.224908775)],
        ["host:launch", pytest.approx(0.107730949)],
        ["host:launch", pytest.approx(0.074129801)]]
    assert host_spans.idle_attributed({}, {"spans": found}) > 99.9
    # a parent's ring has no launch phase: the time falls to dispatch_self
    older = dict(timeline, traceEvents=[
        e for e in timeline["traceEvents"] if e["name"] != "launch"])
    parts = host_spans.attribute(trace, older)["idle_by_part_s"]
    assert "launch" not in parts and parts["dispatch_self"] > 0.2


def test_the_manifest_holds_the_span_metrics_and_their_readers_are_found():
    manifest = check_manifest.load(ROOT)
    cells = {m["name"]: m["workloads"] for m in manifest["per_layer"]}
    assert {"encode_cpu_us_per_row", "gc_pause_s", "idle_attributed_share",
            "dispatch_unattributed_ms_per_batch"} <= set(cells)
    assert len(cells["idle_attributed_share"]) == len(manifest["workloads"])
    assert cells["dispatch_unattributed_ms_per_batch"] == [
        "flagship32.unique-steady"]
    # a metric's data file names the module its reader kind lives in
    trace, timeline = _hand_built()
    ctx = {"timeline": timeline,
           "spans": host_spans.attribute(trace, timeline)}
    assert reduce.read_layer_metric("idle_attributed_share", ctx) == \
        pytest.approx(100 * 4320 / 7850)
    assert reduce.read_layer_metric(
        "dispatch_unattributed_ms_per_batch", ctx) == pytest.approx(600e-6)
    assert reduce.read_layer_metric("idle_attributed_share", {}) is None


def _traced(tmp_path, timeline: dict) -> dict:
    """What ``Rig.window`` keeps of a traced window, around a trace file
    that ``host_spans.load_trace`` is patched to read."""
    rows = "policy_server_dispatched_rows_total"
    return {"file": tmp_path / "recorded.xplane.pb", "timeline": timeline,
            "done": {"traced_s": 3.0},
            "before": reduce.parse_metrics(f"{rows} 1000\n"),
            "after": reduce.parse_metrics(f"{rows} 1600\n")}


def test_a_traced_run_names_its_gaps_and_reads_the_span_metrics(
        monkeypatch, tmp_path, capsys):
    """``run.read_traced`` (what ``run.py --trace 1`` does with the trace
    and the ring) on the fixture recorded from a chip run."""
    trace, timeline = RECORDED["trace"], RECORDED["timeline"]
    monkeypatch.setattr(host_spans, "load_trace", lambda path: trace)
    device, ctx = {"kind": "TPU v5 lite"}, {}
    keep = tmp_path / "kept"
    b = run.read_traced(_traced(tmp_path, timeline), device, ctx, str(keep))
    assert b["idle_gaps"][:3] == [
        ["host:encode", pytest.approx(0.224908775)],
        ["host:launch", pytest.approx(0.107730949)],
        ["host:launch", pytest.approx(0.074129801)]]
    assert b["device_ops"] == reduce.breakdown(trace)["device_ops"]
    assert device["window_s"] == 3.0 and 0 < device["busy_s"] < 0.01
    assert reduce.read_layer_metric("idle_attributed_share", ctx) > 99.9
    assert reduce.read_layer_metric("device_idle_share", ctx) > 99.0
    assert "host spans: " in capsys.readouterr().out
    kept = json.loads((keep / "spans.json").read_text())
    assert kept["link"]["linked"] == 7 and kept["gaps"]
    assert json.loads((keep / "timeline.json").read_text()) == timeline


@pytest.mark.parametrize("older", ["no /debug/timeline", "no ps:launch"])
def test_a_traced_run_of_an_older_program_falls_back_and_raises_nothing(
        older, monkeypatch, tmp_path):
    trace, timeline = RECORDED["trace"], RECORDED["timeline"]
    if older == "no ps:launch":
        trace = dict(trace, launches=[])
    else:  # what fetch_timeline gives where the route is missing
        timeline = {"since_ns": 1, "until_ns": 2, "traceEvents": []}
    monkeypatch.setattr(host_spans, "load_trace", lambda path: trace)
    device, ctx = {"kind": "TPU v5 lite"}, {}
    b = run.read_traced(_traced(tmp_path, timeline), device, ctx)
    assert b == reduce.breakdown(trace)
    assert ctx["spans"] is None and device["busy_s"] > 0
    assert reduce.read_layer_metric("idle_attributed_share", ctx) is None
    if older == "no /debug/timeline":
        assert reduce.read_layer_metric(
            "dispatch_unattributed_ms_per_batch", ctx) is None
    assert reduce.read_layer_metric("device_idle_share", ctx) > 99.0


def test_fetch_timeline_of_a_program_without_the_route_gives_no_events():
    import socket

    with socket.socket() as s:  # a port nobody listens on
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    assert host_spans.fetch_timeline(port, 5, 9) == {
        "since_ns": 5, "until_ns": 9, "traceEvents": []}


def test_the_new_counter_metrics_read_their_counters():
    def text(cpu: float, wall: float, rows: int, pauses: list) -> str:
        return "".join([
            f"policy_server_host_encode_cpu_seconds_total {cpu}\n",
            f"policy_server_host_encode_seconds_total {wall}\n",
            f"policy_server_host_encode_rows_total {rows}\n",
            *(f'policy_server_gc_pause_seconds_total{{generation="{g}"}} {s}\n'
              for g, s in enumerate(pauses))])

    ctx = {"before": reduce.parse_metrics(text(1.0, 4.0, 1000, [0.5, 0.1, 0.2])),
           "after": reduce.parse_metrics(text(1.5, 6.0, 3000, [0.75, 0.1, 0.7]))}
    assert reduce.read_layer_metric("encode_cpu_us_per_row", ctx) == \
        pytest.approx(250.0)
    assert reduce.read_layer_metric("encode_us_per_row", ctx) == \
        pytest.approx(1000.0)
    assert reduce.read_layer_metric("gc_pause_s", ctx) == pytest.approx(0.75)
    # the parent's program has neither counter: no number, no error
    older = {"before": reduce.parse_metrics(
        "policy_server_host_encode_rows_total 1\n"),
        "after": reduce.parse_metrics(
            "policy_server_host_encode_rows_total 2\n")}
    assert reduce.read_layer_metric("encode_cpu_us_per_row", older) is None
    assert reduce.read_layer_metric("gc_pause_s", older) is None
