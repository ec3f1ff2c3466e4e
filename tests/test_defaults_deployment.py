"""PR 35: ``flagship32-defaults``, the flagship set with every routing flag
at its documented default, as a deployment the benchmark holds to its
guarantees (benchmarks/configs/flagship32-defaults.json).

* the environment with the cache at its default behind
  ``MicroBatcher(host_fastpath_threshold=64, latency_budget_ms=50.0)``
  answers an all-unique stream and a rollout stream (``replicas: 8``)
  byte for byte as the benchmark's plain reference does, with blocks
  submitted under and over 64 rows, one at a time and from several
  threads at once;
* one answer, one source: after each stream rows dispatched + host fast
  path + row-tier hits + blob-tier hits + in-batch duplicates = answers,
  to the unit (``reduce.held_to_its_sources`` gives 0 and 0), and both
  the device and the host oracle answered some;
* a request a dedup tier answers on the host path moves that tier's
  counter and not ``host_fastpath_requests``, with the breaker closed and
  with it open (the short circuit enters the same function);
* no answer carries another request's uid (the ``stale-uid`` fault);
* the benchmark's data files for the deployment: the manifest is sound,
  the cell is the issue's letter for letter, each of the four new layer
  metrics reads the program's own counters and reads nothing on a program
  without them; so does PR 40's ``host_declined_batch_share``.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

import pytest

from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.config.config import Config
from policy_server_tpu.evaluation.environment import (
    DEFAULT_VERDICT_CACHE_SIZE,
    EvaluationEnvironmentBuilder,
)
from policy_server_tpu.models import (
    AdmissionReviewRequest,
    AdmissionReviewResponse,
    ValidateRequest,
)
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.runtime.batcher import MicroBatcher, bucket_size
from policy_server_tpu.telemetry import metrics as metrics_mod

# the same 32 policies, built and planted as the cached deployment's tests do
from test_cached_deployment import (  # noqa: F401 (policies: the fixture)
    PHASE,
    _build,
    _planted,
    policies,
)

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmarks"
# the benchmark's own modules, imported as the benchmark imports them
sys.path.insert(0, str(BENCH))
try:
    import check_manifest
    import reduce
    import reference
    from traffic import Traffic, uid_of
finally:
    sys.path.remove(str(BENCH))

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads(
    (BENCH / "configs" / "flagship32-defaults.json").read_text())
CELL = "flagship32-defaults.unique-saturate"
NEW_METRICS = ("host_answered_share", "host_eval_ms_mean",
               "host_batch_share", "budget_routed_batch_share")
ANSWERS_FROM = {
    "device": "policy_server_dispatched_rows",
    "host_fastpath": "policy_server_host_fastpath_requests",
    "row_tier": "policy_server_verdict_cache_hits",
    "blob_tier": "policy_server_dedup_blob_hits",
    "batch_duplicate": "policy_server_batch_dedup_hits"}
SIGNED = set(CONFIG["signing"]["signed_images"])
SEED = 2**31 + 35
POOL = 61  # a prime, as the cell's 16,381 is: a shape meets every policy
MIXES = {
    "rollout": {"generator": "pod_reviews", "pool_shapes": POOL,
                "arrival": "closed", "replicas": 8},
    "unique": {"generator": "pod_reviews", "pool_shapes": POOL,
               "arrival": "closed"},
}
# a stream of 512 as blocks. The first, over 64 rows and alone, goes to
# the device (the router's estimates are pinned: _batcher); the middle
# ones are submitted together by every thread, whatever batches they
# form; the block at 256 (a rollout's next shape), under 64 rows and
# alone in an empty pipeline, goes to the host oracle; the rest as the
# middle.
BLOCKS = ((0, 100), (100, 256), (256, 296), (296, 512))
MIDDLE = (20, 100, 36, 60, 40)  # sizes the threads cut a stretch into


def _batcher(env) -> MicroBatcher:
    # the cell's flags: none about routing, so the documented defaults
    defaults = Config()
    assert (defaults.host_fastpath_threshold, defaults.latency_budget_ms,
            defaults.verdict_cache_size) == (64, 50.0, 256 * 1024 * 1024)
    batcher = MicroBatcher(
        env, max_batch_size=128, batch_timeout_ms=1.0, policy_timeout=60.0,
        host_fastpath_threshold=64, latency_budget_ms=50.0,
    )
    # the router's estimates pinned to an idle machine's and never learnt
    # again (ROADMAP D15): under a CPU hog the CPU backend's round trip
    # passed 50 ms, the budget tier kept every large batch on the host,
    # nothing refreshed the estimate and no later case saw a device
    # answer. With these a batch over the threshold always goes to the
    # device: the host's estimate for 65 rows is over what the device's
    # leaves of the budget
    batcher._dev_rtt = {bucket_size(n): 1e-3 for n in range(1, 129)}
    batcher._host_cost_per_row = 1e-4
    batcher._observe_dispatch = lambda *args, **kwargs: None
    return batcher.start()


def _samples(env, batcher) -> reduce.Samples:
    """What ``/metrics`` would show of every answer source's counter,
    from the objects ``server.py runtime_stats`` reads them from."""
    dedup = env.dedup_stats
    stats = batcher.stats_snapshot()
    have = {
        "policy_server_dispatched_rows": env.host_profile["dispatched_rows"],
        "policy_server_host_fastpath_requests": env.host_fastpath_requests,
        "policy_server_verdict_cache_hits": dedup["cache_hits"],
        "policy_server_dedup_blob_hits": dedup["blob_cache_hits"],
        "policy_server_batch_dedup_hits": dedup["batch_dup_hits"],
        "policy_server_oracle_fallbacks": env.oracle_fallbacks,
        "policy_server_breaker_short_circuited_requests":
            env.breaker_short_circuited_requests,
        "policy_server_deadline_abandoned_batches":
            stats["deadline_abandoned_batches"],
        "policy_server_dedup_blob_bypassed_rows": dedup["blob_bypassed_rows"],
        "policy_server_requests_dispatched": stats["requests_dispatched"],
    }
    return {name + "_total": [({}, float(n))] for name, n in have.items()}


def _request(traffic: Traffic, ids: list, n: int) -> tuple:
    body = traffic.request(n).partition(b"\r\n\r\n")[2]
    return ids[traffic.policy_of(n)], ValidateRequest.from_admission(
        AdmissionReviewRequest.from_dict(json.loads(body)).request)


def _reference_body(traffic: Traffic, policies: dict, n: int) -> bytes:
    ids = list(policies)
    return reference.http_response(
        ["-"], uid_of(n), reference.review_response(
            policies[ids[traffic.policy_of(n)]],
            traffic.reviews[traffic.shape_of(n)]["request"], SIGNED),
    ).partition(b"\r\n\r\n")[2]


def _wait_until(condition, timeout: float = 60.0) -> None:
    until = time.perf_counter() + timeout
    while not condition():
        assert time.perf_counter() < until, "timed out"
        time.sleep(0.002)


def _serve(env, batcher, policies: dict, stream: str, threads: int,
           base: int) -> dict:
    """Requests ``base .. base + 512`` of the stream through the batcher
    in ``BLOCKS``; the tiers start empty; every source's counter is read
    around the stream."""
    ids = list(policies)
    traffic = Traffic(MIXES[stream], SEED, ids)
    env.reset_verdict_cache()
    before = _samples(env, batcher)
    futures: list = [None] * 512

    def submit(lo: int, hi: int) -> None:
        futures[lo:hi] = batcher.submit_many(
            [_request(traffic, ids, base + i) for i in range(lo, hi)],
            RequestOrigin.VALIDATE)

    def together(lo: int, hi: int) -> None:
        cuts, at = [], lo
        while at < hi:
            size = min(MIDDLE[len(cuts) % len(MIDDLE)], hi - at)
            cuts.append((at, at + size))
            at += size
        workers = [threading.Thread(
            target=lambda k=k: [submit(*c) for c in cuts[k::threads]])
            for k in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()

    for nth, (lo, hi) in enumerate(BLOCKS):
        # the even ones alone; each answered before the next is sent, so
        # that no batch holds rows of two blocks, and sent to a pipeline
        # the batches before have left: a lone small batch has room
        _wait_until(lambda: batcher._batches_inflight == 0)
        (submit if nth % 2 == 0 else together)(lo, hi)
        for future in futures[lo:hi]:
            future.result(timeout=120)
    got = [json.dumps(AdmissionReviewResponse(
        future.result(timeout=120)).to_dict()).encode() for future in futures]
    want = [_reference_body(traffic, policies, base + i) for i in range(512)]
    after = _samples(env, batcher)
    return {"got": got, "want": want, "base": base,
            "before": before, "after": after,
            "moved": reduce.answers_by_source(before, after)}


@pytest.fixture(scope="module")
def deployment(policies):
    assert DEFAULT_VERDICT_CACHE_SIZE == 256 * 1024 * 1024
    for flag in ("--verdict-cache-size", "--host-fastpath-threshold",
                 "--latency-budget-ms"):
        assert flag not in CONFIG["server_flags"]
    env = _build(policies)  # the cache at its default
    batcher = _batcher(env)
    yield env, batcher
    batcher.shutdown()
    env.close()


STREAMS = [("unique", 1), ("unique", 4), ("rollout", 1), ("rollout", 4)]


@pytest.fixture(scope="module", params=STREAMS,
                ids=[f"{s}-{t}-submitters" for s, t in STREAMS])
def served(request, deployment, policies) -> dict:
    stream, threads = request.param
    env, batcher = deployment
    return _serve(env, batcher, policies, stream, threads,
                  base=256 * 40 * (1 + request.param_index))


def test_the_defaults_answer_as_the_reference_does(served):
    assert served["got"] == served["want"]


def test_every_answer_is_counted_by_exactly_one_source(served):
    held = reduce.held_to_its_sources(
        CONFIG, served["before"], served["after"], len(served["got"]))
    assert held == {"answered_off_device": 0, "rows_not_dispatched": 0}, (
        served["moved"])


def test_every_live_answer_skipped_the_blob_tier(served):
    """Both routes are live: every request went straight to the row tier
    (no policy of the set runs wasm), the blob tier answered none, and
    the benchmark's bypass share reads 100 over the stream."""
    ctx = {"before": served["before"], "after": served["after"]}
    assert served["moved"]["blob_tier"] == 0
    assert reduce.delta(ctx["before"], ctx["after"],
                        "policy_server_dedup_blob_bypassed_rows") == 512
    assert reduce.read_layer_metric(
        "blob_tier_bypassed_share", ctx) == pytest.approx(100.0)


def test_the_device_and_the_host_oracle_both_answered(served):
    """Whatever the machine's load (ROADMAP D15): the router's estimates
    are pinned, the block of 100 went out alone and over the threshold,
    the block of 40 alone and under it into an empty pipeline."""
    moved = served["moved"]
    assert moved["device"] > 0 and moved["host_fastpath"] > 0, moved
    # the block of 40 that went to the host alone: a new pod shape under
    # 32 policies, so the oracle answered 32 of it at the least
    assert moved["host_fastpath"] >= 32


def test_no_answer_carries_another_requests_uid(served):
    for i, body in enumerate(served["got"]):
        assert json.loads(body)["response"]["uid"] == uid_of(
            served["base"] + i)


def test_a_held_deployment_that_names_no_host_route_is_refused(served):
    """The same window under ``flagship32-cached``'s file, which names no
    ``host_fastpath``: what the oracle answered is off the device there,
    and the count does not close."""
    cached = json.loads(
        (BENCH / "configs" / "flagship32-cached.json").read_text())
    held = reduce.held_to_its_sources(
        cached, served["before"], served["after"], len(served["got"]))
    host = served["moved"]["host_fastpath"]
    assert held == {"answered_off_device": host, "rows_not_dispatched": host}


# -- who counts an answer a tier gives on the host path ----------------------------


@pytest.fixture
def small_env():
    """Two policies behind a breaker that one failure opens for good."""
    env = EvaluationEnvironmentBuilder(backend="jax", breaker_config=dict(
        failure_threshold=1, window_seconds=10.0, cooldown_seconds=3600.0,
    )).build({
        "priv": parse_policy_entry(
            "priv", {"module": "builtin://pod-privileged"}),
        "ns": parse_policy_entry("ns", {
            "module": "builtin://namespace-validate",
            "settings": {"denied_namespaces": ["blocked"]}}),
    })
    yield env
    env.close()


def _counts(env) -> dict[str, int]:
    dedup = env.dedup_stats
    return {"host_fastpath": env.host_fastpath_requests,
            "row_tier": dedup["cache_hits"],
            "blob_tier": dedup["blob_cache_hits"],
            "batch_duplicate": dedup["batch_dup_hits"],
            "device": env.host_profile["dispatched_rows"],
            "short_circuited": env.breaker_short_circuited_requests}


def _moved(env, before: dict) -> dict[str, int]:
    return {k: v - before[k] for k, v in _counts(env).items() if
            v != before[k]}


@pytest.mark.parametrize("breaker_open", [False, True],
                         ids=["breaker-closed", "breaker-open"])
@pytest.mark.parametrize("repeat, hits", [
    # a row-tier hit back-fills no blob (verdict_cache.DedupTiers), so of
    # twelve replays the blob tier knows the two the oracle answered
    ("the same bytes", {"blob_tier": 2, "row_tier": 10}),
    ("a new uid and name", {"row_tier": 12})])
def test_a_tier_hit_on_the_host_path_is_the_tiers_alone(
        small_env, breaker_open, repeat, hits):
    env = small_env
    traffic = Traffic(MIXES["rollout"], SEED, ["priv", "ns"])
    # twelve requests of one pod shape (a rollout's block is 8 x 2 = 16)
    first = [_request(traffic, ["priv", "ns"], n) for n in range(12)]
    again = first if repeat == "the same bytes" else [
        _request(traffic, ["priv", "ns"], n) for n in range(2, 14)]
    if breaker_open:
        env.breaker.record_failure()
    assert env.breaker.allow_device() is not breaker_open
    # through the door each case's requests take: the router's choice
    # (prefer_host) or the breaker's short circuit (no choice made)
    route = {} if breaker_open else {"prefer_host": True}
    before = _counts(env)
    out = env.validate_batch(first, **route)
    assert not any(isinstance(r, Exception) for r in out)
    moved = _moved(env, before)
    short = {"short_circuited": 12} if breaker_open else {}
    # one shape: the oracle answers each policy's first, the row tier the
    # other ten from what the oracle just put
    assert moved == {"host_fastpath": 2, "row_tier": 10, **short}
    before = _counts(env)
    out = env.validate_batch(again, **route)
    assert not any(isinstance(r, Exception) for r in out)
    # every one a hit: the tiers' counters move, the oracle's does not
    assert _moved(env, before) == {**hits, **short}
    uids = [r.to_dict()["uid"] for r in out]
    assert uids == [request.uid() for _pid, request in again]


def test_the_oracle_answers_what_no_tier_may_cache():
    """With the cache off the host path's every answer is the oracle's."""
    env = EvaluationEnvironmentBuilder(
        backend="jax", verdict_cache_size=0).build({"priv": parse_policy_entry(
            "priv", {"module": "builtin://pod-privileged"})})
    try:
        traffic = Traffic(MIXES["rollout"], SEED, ["priv"])
        items = [_request(traffic, ["priv"], n) for n in range(8)]
        env.validate_batch(items, prefer_host=True)
        env.validate_batch(items, prefer_host=True)
        assert env.host_fastpath_requests == 16
        assert env.dedup_stats["cache_hits"] == 0
    finally:
        env.close()


# -- the benchmark's data files for the deployment --------------------------------


def test_the_manifest_with_the_deployment_is_sound():
    assert check_manifest.problems(MANIFEST, ROOT) == []
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "flagship32-defaults")
    assert entry["name"] == CONFIG["name"]
    assert entry["file"] == "benchmarks/configs/flagship32-defaults.json"
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert entry["source"] == CONFIG["source"]
    for word in ("configs[3]", "--host-fastpath-threshold 64",
                 "--latency-budget-ms 50", "--verdict-cache-size 256Mi"):
        assert word in entry["source"]
    assert CONFIG["guarantees"]["answers_from"] == ANSWERS_FROM
    # flagship32-cached with the two routing flags left out, nothing else
    # of the deployment
    cached = json.loads(
        (BENCH / "configs" / "flagship32-cached.json").read_text())
    flags = list(cached["server_flags"])
    for flag in ("--host-fastpath-threshold", "--latency-budget-ms"):
        at = flags.index(flag)
        del flags[at:at + 2]
    assert flags == CONFIG["server_flags"] == [
        "--frontend", "native", "--policy-timeout", "10", "--mesh", "auto"]
    for key in ("policies", "signing", "response_head", "row_bytes_dense",
                "verdict_bytes_per_row", "chips", "mesh"):
        assert CONFIG[key] == cached[key]
    for key in ("exact", "answered", "no_compile"):
        assert CONFIG["guarantees"][key] == cached["guarantees"][key]
    assumed = " ".join(CONFIG["assumed"])
    for word in ("--frontend native", "--policy-timeout 10", "64", "50",
                 "256Mi"):
        assert word in assumed


def test_the_cell_is_the_issues_letter_for_letter():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert {k: entry[k] for k in ("name", "config", "traffic", "chips")} == {
        "name": CELL, "config": "flagship32-defaults",
        "traffic": "unique-saturate", "chips": 1}
    assert len(entry["why"]) <= 200
    mix = json.loads((BENCH / "traffic" / "unique-saturate.json").read_text())
    parameters = {k: v for k, v in mix.items() if not k.endswith("why")}
    assert parameters == {
        "generator": "pod_reviews", "pool_shapes": 16381, "arrival": "closed",
        "connections": 512, "client_processes": 4, "warm_requests": 8192,
        "timeout_s": 10}
    reported = {m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
                if CELL in (m.get("workloads") or [CELL])}
    assert {"reviews_per_s", "setup_s", "predicate_roofline",
            "device_idle_share", "device_answered_share",
            *NEW_METRICS} <= reported
    assert "latency_p50_ms" not in reported
    # what its sibling on the same traffic reports, this one reports too;
    # the one exception is PERF.md section 7's (host_spans.K_PHASES)
    sibling = {m["name"] for m in MANIFEST["per_layer"]
               if "flagship32-cached.unique-saturate" in m["workloads"]}
    assert sibling - reported <= {"idle_attributed_share"}


def test_the_four_new_entries_come_last_and_share_a_layer():
    """Last as PR 35 left the list: what later PRs appended (PR 37's
    native_wire_launch_share) comes after them, never between."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert at >= 29  # nothing that was there moved behind them
    last = MANIFEST["per_layer"][at : at + 4]
    assert tuple(m["name"] for m in last) == NEW_METRICS
    assert len({m["layer"] for m in last}) == 1
    for m in last:
        # the default install with the scanner on reports three of them
        assert m["moves"] == "reviews_per_s" and m["workloads"] in (
            [CELL], [CELL, "flagship32-audit-defaults.unique-trickle"])
        spec = json.loads(
            (BENCH / "layer_metrics" / f"{m['name']}.json").read_text())
        assert spec["reader"] == "counter_ratio" and "module" not in spec
    # no code came with them
    assert not [p.name for p in (BENCH / "layer_metrics").iterdir()
                if p.suffix != ".json"]


WINDOW = {  # a window of 150,000 answers in 2,400 batches, 900 on the host
    "policy_server_requests_dispatched_total": 150_000,
    "policy_server_batches_dispatched_total": 2_400,
    "policy_server_host_fastpath_requests_total": 45_000,
    "policy_server_host_fastpath_batches_total": 900,
    "policy_server_budget_routed_batches_total": 60,
    PHASE % ("count", "host_eval"): 900,
    PHASE % ("sum", "host_eval"): 10.8,
}


@pytest.mark.parametrize("name, want", zip(NEW_METRICS, (
    30.0, 12.0, 37.5, 2.5)))
def test_a_new_layer_metric_reads_the_programs_counters(name, want):
    before, after = _planted(WINDOW)
    ctx = {"before": before, "after": after}
    assert reduce.read_layer_metric(name, ctx) == pytest.approx(want)
    # a program without the phase or the counters (host_eval: the parent)
    # gives nothing to read, and nothing is raised
    assert reduce.read_layer_metric(name, {"before": {}, "after": {}}) is None


def test_the_declined_share_reads_the_programs_counter():
    """PR 40: a data file only, appended after all that was there."""
    name = "host_declined_batch_share"
    before, after = _planted({
        **WINDOW,
        "policy_server_host_fastpath_declined_batches_total": 840})
    ctx = {"before": before, "after": after}
    assert reduce.read_layer_metric(name, ctx) == pytest.approx(35.0)
    # a program without the counter (the parent) gives nothing to read
    before, after = _planted(WINDOW)
    parent = {"before": before, "after": after}
    assert reduce.read_layer_metric(name, parent) is None
    assert reduce.read_layer_metric(name, {"before": {}, "after": {}}) is None
    by_name = {m["name"]: (at, m)
               for at, m in enumerate(MANIFEST["per_layer"])}
    at, entry = by_name[name]
    assert at >= 41  # behind the 41 metrics PR 38 left
    assert entry == {**by_name["host_batch_share"][1], "name": name,
                     "better": "higher"}
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert spec["reader"] == "counter_ratio" and "module" not in spec
    assert spec["numerator"] == metrics_mod.HOST_FASTPATH_DECLINED_BATCHES
    assert spec["denominator"] == metrics_mod.BATCHES_DISPATCHED


def test_the_new_metrics_counters_are_the_programs_own():
    names = {metrics_mod.HOST_FASTPATH_REQUESTS,
             metrics_mod.HOST_FASTPATH_BATCHES,
             metrics_mod.BUDGET_ROUTED_BATCHES,
             metrics_mod.REQUESTS_DISPATCHED, metrics_mod.BATCHES_DISPATCHED}
    read = set()
    for name in NEW_METRICS:
        spec = json.loads(
            (BENCH / "layer_metrics" / f"{name}.json").read_text())
        for side in ("numerator", "denominator"):
            if isinstance(spec[side], str):
                read.add(spec[side])
            else:
                assert spec[side]["name"].startswith(
                    metrics_mod.PHASE_LATENCY_SECONDS)
                assert spec[side]["labels"] == {"phase": "host_eval"}
    assert read == names
