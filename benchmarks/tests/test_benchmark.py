"""Tests of the benchmark's own arithmetic, manifest check, traffic,
reference and comparison. CPU only, seconds to run:

    python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import copy
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import check_manifest  # noqa: E402
import client  # noqa: E402
import reduce  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from traffic import Traffic  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((BENCH / "configs" / "flagship32.json").read_text())
MIX = json.loads((BENCH / "traffic" / "tiny-closed.json").read_text())


# -- client arithmetic ---------------------------------------------------------


def _sample(stall: bool) -> dict[str, np.ndarray]:
    """1000 requests due every 10 ms over a 10 s window, each answered 2 ms
    after it was due; with ``stall``, requests 500..549 all wait for
    t = 5.6 s and the last 50 are answered only after the window."""
    due = np.arange(1000) * 0.01
    done = due + 0.002
    if stall:
        done[500:550] = 5.6
        done[950:] += 1.0
    return {"due": due, "sent": due + 0.0005, "done": done,
            "good": np.ones(1000, bool)}


def test_a_stall_moves_the_tail_and_the_rate():
    calm = reduce.client_stats(_sample(False), 0.0, 10.0, 10.0)
    stalled = reduce.client_stats(_sample(True), 0.0, 10.0, 10.0)
    assert calm["latency_p50_ms"] == pytest.approx(2.0)
    assert calm["latency_p99_ms"] == pytest.approx(2.0)
    assert calm["reviews_per_s"] == pytest.approx(100.0)
    assert stalled["latency_p50_ms"] == pytest.approx(2.0)
    assert stalled["latency_p99_ms"] > 500.0  # the tail is of ALL requests
    assert stalled["reviews_per_s"] == pytest.approx(95.0)  # late ones out


def test_latency_runs_from_the_due_time_and_a_failure_is_the_limit():
    rec = {"due": np.array([1.0, 2.0]), "sent": np.array([1.5, 2.0]),
           "done": np.array([1.6, 2.1]), "good": np.array([True, False])}
    lat = reduce.latency_ms(rec["due"], rec["done"], rec["good"], 10.0)
    assert lat[0] == pytest.approx(600.0)  # not 100: it was due at 1.0
    assert lat[1] == pytest.approx(10_000.0)
    stats = reduce.client_stats(rec, 1.0, 2.0, 10.0)
    assert stats["late_p99_ms"] == pytest.approx(495.0)
    assert stats["reviews_per_s"] == pytest.approx(0.5)


def test_the_schedule_is_the_seeds_and_at_the_rate():
    a = client.poisson_schedule(2**31 + 5, 1000.0, 4.0)
    assert a == client.poisson_schedule(2**31 + 5, 1000.0, 4.0)
    assert a != client.poisson_schedule(2**31 + 6, 1000.0, 4.0)
    assert a == sorted(a) and 0.0 < a[0] and a[-1] < 4.0
    assert len(a) == 4000  # every seed offers the same number of requests
    gaps = np.diff(a)  # and the gaps are a Poisson process's
    assert gaps.mean() == pytest.approx(1e-3, rel=0.05)
    assert gaps.std() == pytest.approx(1e-3, rel=0.1)


def test_bursts_keep_the_count_and_fill_only_the_on_part_of_a_period():
    calm = client.poisson_schedule(2**31 + 5, 1000.0, 4.0)
    burst = client.poisson_schedule(2**31 + 5, 1000.0, 4.0,
                                    {"period_s": 1.0, "on_s": 0.1})
    assert len(burst) == len(calm) == 4000 and burst == sorted(burst)
    assert all(t % 1.0 < 0.1 for t in burst)  # 10 x the rate for 100 ms
    assert [int(t) for t in burst] == [int(t) for t in calm]


def test_strip_date_keeps_everything_else():
    raw = (b"HTTP/1.1 200 OK\r\nContent-Length: 2\r\nDate: Mon, 01 Jan 2024 "
           b"00:00:00 GMT\r\nServer: x\r\n\r\n{}")
    assert client.strip_date(raw) == (
        b"HTTP/1.1 200 OK\r\nContent-Length: 2\r\nServer: x\r\n\r\n{}")


# -- /metrics readers ------------------------------------------------------------

BEFORE = """\
# TYPE policy_server_host_encode_seconds_total counter
policy_server_host_encode_seconds_total 1.5
# TYPE policy_server_host_encode_rows_total counter
policy_server_host_encode_rows_total 1000
# TYPE policy_server_xla_programs_compiled_total counter
policy_server_xla_programs_compiled_total 32
# TYPE policy_server_phase_latency_seconds histogram
policy_server_phase_latency_seconds_sum{phase="materialize"} 2.0
policy_server_phase_latency_seconds_count{phase="materialize"} 100
policy_server_phase_latency_seconds_sum{phase="encode"} 9.0
policy_server_phase_latency_seconds_count{phase="encode"} 100
"""
AFTER = BEFORE.replace("total 1.5", "total 2.0").replace(
    "total 1000", "total 11000").replace(
    'sum{phase="materialize"} 2.0', 'sum{phase="materialize"} 2.6').replace(
    'count{phase="materialize"} 100', 'count{phase="materialize"} 400')


def test_counter_readers_take_deltas_over_the_window():
    ctx = {"before": reduce.parse_metrics(BEFORE),
           "after": reduce.parse_metrics(AFTER)}
    encode = json.loads(
        (BENCH / "layer_metrics" / "encode_us_per_row.json").read_text())
    assert reduce.counter_ratio(encode, ctx) == pytest.approx(50.0)
    assert reduce.read_layer_metric("materialize_ms_mean", ctx) == \
        pytest.approx(2.0)
    assert reduce.counter_delta(
        {"counters": ["policy_server_xla_programs_compiled"]}, ctx) == 0.0
    # nothing to read: no number, never a 0
    assert reduce.read_layer_metric("framing_us_per_req", ctx) is None
    same = {"before": ctx["before"], "after": ctx["before"]}
    assert reduce.counter_ratio(encode, same) is None
    # run.py --keep: every counter that moved, by name and labels
    assert run.moved_counters(ctx["before"], ctx["after"]) == {
        "policy_server_host_encode_seconds_total": 0.5,
        "policy_server_host_encode_rows_total": 10000.0,
        "policy_server_phase_latency_seconds_sum[materialize]":
            pytest.approx(0.6),
        "policy_server_phase_latency_seconds_count[materialize]": 300.0}


# -- the manifest check ------------------------------------------------------------


def test_the_committed_manifest_is_sound():
    assert check_manifest.problems(MANIFEST, ROOT) == []
    assert check_manifest.load(ROOT) == MANIFEST


def _set(path: list, value):
    def edit(m: dict, _root: Path | None = None) -> None:
        at = m
        for key in path[:-1]:
            at = at[key]
        at[path[-1]] = value
    return edit


def _in_config_file(path: list, value):
    """An edit to the first configuration's FILE, in a copy of the data
    files (what a configuration guarantees is its file's to state)."""
    def edit(m: dict, root: Path) -> None:
        file = root / m["configs"][0]["file"]
        doc = json.loads(file.read_text())
        _set(path, value)(doc)
        file.write_text(json.dumps(doc))
    return edit


def _four_chips_over_half():
    """One cell more on four chips than half of the cells, rounded down:
    the first ones, whatever the manifest's count and its own four-chip
    cells."""
    def edit(m: dict, _root: Path | None = None) -> None:
        cells = m["workloads"]
        for w in cells[:len(cells) // 2 + 1]:
            w["chips"] = 4
    return edit


DEVICE = {"device": "policy_server_dispatched_rows"}


@pytest.mark.parametrize("edit, reason", [
    (_set(["configs", 0, "source"], "x" * 201), "source must be 1 to 200"),
    (_set(["configs", 0, "source"], "café"), "source must be 1 to 200"),
    (_set(["workloads", 0, "name"], "has space"), "workload name"),
    (_set(["workloads", 0, "name"], "-leading"), "workload name"),
    (_set(["workloads", 0, "why"], "two\nlines"), "why must be"),
    (_set(["end_to_end", 0, "unit"], "reviews per s"), "unit"),
    (_set(["end_to_end", 0, "bound"], 0.3), "bound must be"),
    (_set(["per_layer", 0, "moves"], "nothing"), "is no end-to-end metric"),
    (_set(["per_layer", 0, "workloads"], ["no-such-cell"]), "no workload"),
    (_set(["workloads", 0, "config"], "no-such-config"), "no config"),
    (_four_chips_over_half(), "ask for 4 chips"),
    (lambda m, _root: m["per_layer"][0].update(
        workloads=[c["name"] for c in m["workloads"]]),
     "not all of its workloads"),  # a steady-only metric, moved by all cells
    (_set(["workloads", 0, "traffic"], "no-such-mix"), "no traffic file"),
    (_set(["run_seconds"], 52), "run_seconds"),
    (_in_config_file(["guarantees"], {"device": "prose alone"}),
     "states no guarantees.answers_from"),
    (_in_config_file(["guarantees", "answers_from"],
                     {"row_tier": "policy_server_verdict_cache_hits"}),
     "must name device"),
    (_in_config_file(["guarantees", "answers_from"],
                     {**DEVICE, "psychic": "policy_server_guesses"}),
     "is no source of"),
    (_in_config_file(["guarantees", "answers_from"],
                     {**DEVICE, "breaker_trip": "policy_server_breaker_trips"}),
     "counts events, not requests"),
    (_in_config_file(["guarantees", "answers_from"],
                     {"device": "policy_server_requests_dispatched"}),
     "is counted by policy_server_dispatched_rows"),
])
def test_the_manifest_check_refuses(edit, reason, tmp_path):
    manifest = copy.deepcopy(MANIFEST)
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns(
        "tests", "__pycache__", "*.py"))
    assert check_manifest.problems(manifest, tmp_path) == []
    edit(manifest, tmp_path)
    assert any(reason in p for p in check_manifest.problems(manifest, tmp_path)), \
        check_manifest.problems(manifest, tmp_path)


# -- traffic ---------------------------------------------------------------------------


def test_the_same_seed_gives_the_same_bytes_and_no_two_requests_share_them():
    ids = list(CONFIG["policies"])
    a, b = Traffic(MIX, 2**31 + 7, ids), Traffic(MIX, 2**31 + 7, ids)
    other = Traffic(MIX, 2**31 + 8, ids)
    sent = [a.request(n) for n in range(600)]
    assert sent == [b.request(n) for n in range(600)]
    assert sent != [other.request(n) for n in range(600)]
    assert len(set(sent)) == 600  # more requests than shapes in the pool
    head, _, body = sent[300].partition(b"\r\n\r\n")
    review = json.loads(body)
    assert f"Content-Length: {len(body)}".encode() in head
    assert review["request"]["uid"] == "synthetic-0000000300"
    assert review["request"]["name"] == "pod-0000000300" == \
        review["request"]["object"]["metadata"]["name"]
    assert head.startswith(f"POST /validate/{ids[300 % 32]} ".encode())
    # over a run a shape meets every policy id
    assert {(a.shape_of(n), a.policy_of(n)) for n in range(257 * 32)} == \
        {(s, p) for s in range(257) for p in range(32)}


def test_replicas_repeat_a_shape_for_every_policy_under_new_names():
    ids = list(CONFIG["policies"])
    mix = dict(MIX, replicas=8)
    t = Traffic(mix, 2**31 + 7, ids)
    pairs = [(t.policy_of(n), t.shape_of(n)) for n in range(8 * 32 * 20)]
    assert len(set(pairs)) == 32 * 20  # 7 of 8 requests repeat a pair
    assert len({t.shape_of(n) for n in range(8 * 32)}) == 1  # one block
    assert t.shape_of(8 * 32) != t.shape_of(0)
    assert len({t.request(n) for n in range(8 * 32)}) == 8 * 32  # new bytes
    plain = Traffic(MIX, 2**31 + 7, ids)  # no replicas: a shape a request
    assert len({plain.shape_of(n) for n in range(200)}) == 200


# -- the reference -----------------------------------------------------------------------

POD = {"uid": "u", "namespace": "kube-system", "operation": "CREATE", "object": {
    "metadata": {"labels": {"owner": "a"}, "annotations": {}},
    "spec": {"containers": [
        {"name": "c0", "image": "docker.io/library/redis:latest"},
        {"name": "c1", "image": "registry.prod.example.com/api/server:v1.4.2",
         "securityContext": {"privileged": True, "runAsNonRoot": True}},
    ]}}}


@pytest.mark.parametrize("policy, want", [
    ("pod-privileged", {"allowed": False, "status": {
        "message": "Privileged container is not allowed", "code": 400}}),
    ("pod-privileged-monitor", {"allowed": True}),
    ("ns-fence-2", {"allowed": False, "status": {
        "message": "namespace 'kube-system' is denied", "code": 400}}),
    ("labels-dev", {"allowed": False, "status": {
        "message": "mandatory label 'cost-center' is missing", "code": 400}}),
    ("verify-signatures", {"allowed": False, "status": {
        "message": "image signature verification failed for: "
                   "'docker.io/library/redis:latest'", "code": 400}}),
    ("raw-gate", {"allowed": True, "patchType": "JSONPatch",
                  "patch": "W3sib3AiOiAiYWRkIiwgInBhdGgiOiAiL3ZhbGlkYXRlZCIs"
                           "ICJ2YWx1ZSI6IHRydWV9XQ=="}),
    ("pod-security-group", {"allowed": False, "status": {
        "message": "pod security baseline not met", "code": 400,
        "details": {"causes": [{
            "field": "spec.policies.unprivileged",
            "message": "Privileged container is not allowed"}]}}}),
    ("image-provenance-group", {"allowed": False, "status": {
        "message": "image provenance cannot be established", "code": 400,
        "details": {"causes": [
            {"field": "spec.policies.signed",
             "message": "image signature verification failed: image matches "
                        "no signature entry"},
            {"field": "spec.policies.trusted",
             "message": "not coming from an allowed registry"}]}}}),
])
def test_the_reference_answers(policy, want):
    signed = set(CONFIG["signing"]["signed_images"])
    got = reference.review_response(CONFIG["policies"][policy], POD, signed)
    assert got == want


def test_the_reference_covers_every_policy_of_every_configuration():
    for entry in MANIFEST["configs"]:
        config = json.loads((ROOT / entry["file"]).read_text())
        assert len(config["policies"]) == 32
        for spec in config["policies"].values():
            members = spec.get("policies", {"": spec}).values()
            assert all(m["module"] in reference.MODULES for m in members)


# -- the comparison, and its control -----------------------------------------------------------


def _run_control(fault: str, monkeypatch, capsys) -> dict:
    """Drive run.py past its look for a chip: the reference, with ``fault``
    planted where answers are produced, in the program's place, under a
    tiny mix; everything else of a run (clients, window, comparison)."""
    manifest = copy.deepcopy(MANIFEST)
    manifest["workloads"].append({
        "name": "flagship32.tiny-closed", "config": "flagship32",
        "traffic": "tiny-closed", "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        m.get("workloads", []).append("flagship32.tiny-closed")
    monkeypatch.setattr(check_manifest, "load", lambda root=None: manifest)
    rc = run.main(["--workload", "flagship32.tiny-closed", "--seed",
                   str(2**31 + 11), "--seconds", "1.5", "--trace", "0",
                   "--platform", "cpu", "--control", fault])
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert list(result)[-1] == "compared"
    assert "compared mismatched:" in out.err.strip().splitlines()[-1]
    assert result["attempted"] > 50 and result["failed"] == 0
    return result


def test_the_reference_in_the_programs_place_is_correct(monkeypatch, capsys):
    result = _run_control("none", monkeypatch, capsys)
    assert result["correct"] is True
    assert result["compared"]["mismatched"] == [0, 0]
    assert json.loads(capsys.readouterr().out or "{}") == {}


@pytest.mark.parametrize("fault", ["first-container", "stale-uid",
                                   "alter-answer"])
def test_a_broken_guarantee_comes_out_not_correct(fault, monkeypatch, capsys):
    result = _run_control(fault, monkeypatch, capsys)
    assert result["correct"] is False
    assert result["compared"]["mismatched"][0] > 0


# -- the trace reduction, on a small recorded trace -------------------------------------------

TRACE = json.loads((HERE / "recorded_trace.json").read_text())


def test_trace_idle_is_one_minus_the_union_of_busy_intervals():
    ops = TRACE["devices"]["/device:TPU:0"]["XLA Ops"]
    covered = set()  # brute force: every nanosecond some operation covers
    for _name, start, dur in ops:
        covered.update(range(int(start), int(start + dur)))
    busy = reduce.busy_seconds(TRACE)
    assert busy == {"/device:TPU:0": pytest.approx(len(covered) / 1e9)}
    assert reduce.union_ns([["a", 0, 10], ["b", 5, 10], ["c", 30, 5],
                            ["d", 31, 2]]) == 20  # overlaps count once
    idle = reduce.trace_idle({}, {"trace": TRACE, "traced_s": 0.4})
    assert idle == pytest.approx(100 * (1 - len(covered) / 1e9 / 0.4))
    assert 99.9 < idle < 100.0
    assert reduce.trace_idle({}, {"trace": None}) is None
    assert reduce.trace_idle({}, {"trace": {"devices": {}}, "traced_s": 1}) \
        is None  # a rehearsal has no device plane: no number, not 100


def test_trace_roofline_is_least_time_over_the_programs_device_time():
    p = json.loads(
        (BENCH / "layer_metrics" / "predicate_roofline.json").read_text())
    device_s = (9963 + 9805 + 8428 + 9743) / 1e9  # the four executions
    assert reduce.module_seconds(TRACE, p["module_patterns"]) == \
        pytest.approx(device_s)
    rows = "policy_server_dispatched_rows_total"
    ctx = {
        "trace": TRACE,
        "trace_before": reduce.parse_metrics(f"{rows} 1000\n"),
        "trace_after": reduce.parse_metrics(f"{rows} 1300\n"),
        "config": {"chips": 1, "row_bytes_dense": {"value": 376},
                   "verdict_bytes_per_row": {"value": 80}},
        "peaks": reduce.peaks_of("TPU v5 lite"),
    }
    share = reduce.trace_roofline(p, ctx)
    assert share == pytest.approx(100 * (300 * 456 / 819e9) / device_s)
    assert 0 < share < 1
    ctx["trace_after"] = ctx["trace_before"]  # nothing dispatched: no number
    assert reduce.trace_roofline(p, ctx) is None
    assert reduce.module_seconds(TRACE, ["jit_something_else*"]) is None
    with pytest.raises(KeyError):
        reduce.peaks_of("TPU v9")


def test_breakdown_names_operations_and_gaps():
    b = reduce.breakdown(TRACE)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert all(" = " not in name and len(name) < 64
               for name, _s in b["device_ops"])
    seconds = [s for _n, s in b["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    assert b["idle_gaps"][0][0] == "host:unattributed"
    assert b["idle_gaps"][0][1] > 0.1  # 184 ms between two executions
