"""Live admission skips the blob tier (evaluation/verdict_cache.py).

The blob tier's key holds the payload, and the payload holds
``request.uid``, which the API server mints anew for every webhook call:
a live review's blob never recurs. So the micro-batcher's batches say
``live`` and go straight to the row tier, on the device route and on the
host route alike, and ``policy_server_dedup_blob_bypassed_rows`` counts
them. Replays (the audit lane, direct ``validate_batch`` callers) keep the
blob tier as it was.

* the tiers' plan of a live chunk is a replay's plan without the blob
  tier: the same answers, slots and row-tier puts, no blob lookup, put or
  back-fill, its rows counted as bypassed; likewise ``get_one`` /
  ``put_one`` on the host fast path;
* a live batch through the batcher, on either route: the blob tier's
  counters do not move, the bypass counter moves by the cacheable rows,
  the answers are byte for byte the cache-off environment's, every answer
  is counted by exactly one source, and the same requests again are
  answered by the row tier;
* the audit lane's replay and a direct ``validate_batch`` replay still
  hit the blob tier;
* the benchmark's data file for the bypass share reads the counter, and
  reads nothing on a program without it.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.evaluation.environment import EvaluationEnvironmentBuilder
from policy_server_tpu.evaluation.verdict_cache import DedupTiers, row_bytes
from policy_server_tpu.models import AdmissionReviewResponse
from policy_server_tpu.runtime.batcher import MicroBatcher
from policy_server_tpu.telemetry import metrics as metrics_mod

from test_verdict_cache import (
    PLAN_SCENARIOS,
    POLICIES,
    _plan_inputs,
    parse_all,
    pod_request,
)

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmarks"
sys.path.insert(0, str(BENCH))
try:
    import reduce
finally:
    sys.path.remove(str(BENCH))

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
METRIC = "blob_tier_bypassed_share"
TIER_CELLS = ["flagship32-cached.rollout-saturate",
              "flagship32-cached.unique-saturate",
              "flagship32-defaults.unique-saturate"]


# -- the rule in the tiers: arrays alone, no environment ----------------------


@pytest.mark.parametrize("targets", ["uniform", "mixed"])
@pytest.mark.parametrize("scenario", list(PLAN_SCENARIOS))
def test_a_live_plan_is_a_replays_plan_without_the_blob_tier(
        scenario, targets):
    replay_in = _plan_inputs(scenario, targets)
    live_in = _plan_inputs(scenario, targets)
    replay = replay_in[-1].plan(*replay_in[:-1])
    tiers = live_in[-1]
    before = tiers.stats()
    live = tiers.plan(*live_in[:-1], live=True)
    # who answers each row, which rows ship, what the row tier learns
    assert live.hits == replay.hits and live.slot_rows == replay.slot_rows
    assert live.n_rows == replay.n_rows and live.row_puts == replay.row_puts
    assert (live.ship_pos is None) == (replay.ship_pos is None)
    if live.ship_pos is not None:
        assert live.ship_pos.tolist() == replay.ship_pos.tolist()
    # and the blob tier: no lookup, no put, no back-fill
    assert live.blob_puts == []
    after = tiers.stats()
    blob = {k: after[k] - before[k] for k in after if k.startswith("blob_cache")}
    assert not any(blob.values()), blob
    _packed, ok_mask, wasm_pos, *_rest = live_in
    plain = [p for p in np.flatnonzero(ok_mask).tolist() if p not in wasm_pos]
    assert after["blob_bypassed_rows"] - before["blob_bypassed_rows"] == len(
        plain)
    assert after["cache_hits"] - before["cache_hits"] == len(live.hits)
    # learn files the row tier alone
    fetched = np.zeros((16, 5), np.uint8)
    puts = tiers.row.puts
    tiers.learn(live, row_bytes(fetched))
    assert tiers.row.puts - puts == len(live.row_puts)
    assert tiers.blob.puts == 0 and len(tiers.blob) == 0


def test_the_host_fast_path_rule_live_goes_straight_to_the_row_tier():
    tiers = DedupTiers(1 << 20)
    tkey = ("p", "x")
    encodes: list[bytes] = []

    def packed_row_of(blob: bytes) -> bytes:
        encodes.append(blob)
        return b"row-of-" + blob[:4]

    row, keys = tiers.get_one(tkey, b"podA-uid1", packed_row_of, live=True)
    assert row is None and keys == ((tkey, b"row-of-podA"), None)
    tiers.put_one(keys, {"v": 1})
    # the same bytes again: the row tier answers, the encode is paid
    assert tiers.get_one(tkey, b"podA-uid1", packed_row_of, live=True) == (
        {"v": 1}, None)
    assert encodes == [b"podA-uid1"] * 2
    # where no row key can be had nothing is learned
    row, keys = tiers.get_one(tkey, b"podB-uid2", lambda blob: None, live=True)
    assert row is None and keys == (None, None)
    tiers.put_one(keys, {"v": 2})
    tiers.count_blob_bypassed(3)
    stats = tiers.stats()
    assert len(tiers.blob) == 0 and len(tiers.row) == 1
    assert (stats["blob_cache_hits"], stats["blob_cache_misses"],
            stats["blob_cache_puts"]) == (0, 0, 0)
    assert (stats["cache_hits"], stats["blob_bypassed_rows"]) == (1, 3)
    assert DedupTiers.stats_when_off()["blob_bypassed_rows"] == 0


# -- through the batcher's live entry ----------------------------------------


@pytest.fixture(scope="module")
def envs():
    on = EvaluationEnvironmentBuilder(backend="jax").build(parse_all(POLICIES))
    off = EvaluationEnvironmentBuilder(
        backend="jax", verdict_cache_size=0).build(parse_all(POLICIES))
    yield {"on": on, "off": off}
    on.close()
    off.close()


def _items(n: int, tag: str) -> list:
    """n reviews over 6 (policy, pod) combinations, a uid each."""
    return [(["priv", "ns", "grp"][k % 3],
             pod_request("blocked" if k % 6 >= 3 else "fine", k % 2 == 0,
                         uid=f"{tag}-{k}"))
            for k in range(n)]


def _counts(env) -> dict[str, int]:
    dedup = env.dedup_stats
    return {"device": env.host_profile["dispatched_rows"],
            "host_fastpath": env.host_fastpath_requests,
            "row_tier": dedup["cache_hits"],
            "blob_tier": dedup["blob_cache_hits"],
            "batch_duplicate": dedup["batch_dup_hits"],
            "blob_misses": dedup["blob_cache_misses"],
            "blob_puts": dedup["blob_cache_puts"],
            "bypassed": dedup["blob_bypassed_rows"],
            "lane_blob": dedup["audit_tier_hits_blob"]}


def _bodies(responses) -> list[bytes]:
    return [json.dumps(AdmissionReviewResponse(r).to_dict()).encode()
            for r in responses]


ROUTES = {
    # every batch to the device
    "device": dict(host_fastpath_threshold=0, latency_budget_ms=0),
    # a block of 64 or fewer alone into an empty pipeline: the host oracle
    "host": dict(host_fastpath_threshold=64, latency_budget_ms=0),
}


def _through_batcher(batcher, items) -> list:
    deadline = time.perf_counter() + 60
    while batcher._batches_inflight:
        assert time.perf_counter() < deadline
        time.sleep(0.002)
    futures = batcher.submit_many(items, RequestOrigin.VALIDATE)
    return [f.result(timeout=120) for f in futures]


@pytest.mark.parametrize("route", list(ROUTES))
def test_a_live_batch_skips_the_blob_tier(envs, route):
    env, off = envs["on"], envs["off"]
    env.reset_verdict_cache()
    batcher = MicroBatcher(env, max_batch_size=64, batch_timeout_ms=1.0,
                           policy_timeout=60.0, **ROUTES[route]).start()
    try:
        items = _items(48, route)
        host0 = batcher.stats_snapshot()["host_fastpath_batches"]
        c0 = _counts(env)
        got = _through_batcher(batcher, items)
        c1 = _counts(env)
        again = _through_batcher(batcher, items)  # the same uids and bytes
        c2 = _counts(env)
        host_batches = batcher.stats_snapshot()["host_fastpath_batches"] - host0
    finally:
        batcher.shutdown()
    assert (host_batches > 0) == (route == "host")
    want = _bodies(off.validate_batch(items))
    assert _bodies(got) == want and _bodies(again) == want
    first = {k: c1[k] - c0[k] for k in c0}
    second = {k: c2[k] - c1[k] for k in c0}
    for moved in (first, second):
        # no blob lookup, hit, miss or put; every row counted as bypassed
        assert moved["blob_tier"] == moved["blob_misses"] == 0
        assert moved["blob_puts"] == 0
        assert moved["bypassed"] == len(items)
        # one answer, one source
        assert sum(moved[k] for k in (
            "device", "host_fastpath", "row_tier", "blob_tier",
            "batch_duplicate")) == len(items), moved
    assert first["device"] + first["host_fastpath"] > 0
    # the replay of a live batch is the row tier's alone
    assert second["row_tier"] == len(items)
    assert second["device"] == second["host_fastpath"] == 0
    assert len(env._tiers.blob) == 0


@pytest.mark.parametrize("replay", ["audit-lane", "direct"])
def test_a_replay_still_hits_the_blob_tier(envs, replay):
    env, off = envs["on"], envs["off"]
    env.reset_verdict_cache()
    batcher = MicroBatcher(env, max_batch_size=64, batch_timeout_ms=1.0,
                           policy_timeout=60.0, **ROUTES["device"]).start()
    try:
        items = _items(24, replay)
        _through_batcher(batcher, items)  # live: the row tier learns them

        def send() -> list:
            if replay == "audit-lane":
                return batcher.submit_audit(items).result(timeout=120)
            return env.validate_batch(items)

        c0 = _counts(env)
        first = send()  # row-tier hits, each back-fills one blob
        c1 = _counts(env)
        second = send()  # the same bytes: the blob tier answers some
        c2 = _counts(env)
    finally:
        batcher.shutdown()
    lane = replay == "audit-lane"
    # both ask the blob tier and fill it; a lane row answers nobody, so
    # its lookups are the lane's to count, its hits the lane's tier hits
    assert c1["blob_misses"] - c0["blob_misses"] == (0 if lane else len(items))
    assert c1["blob_puts"] > c0["blob_puts"]
    hits = "lane_blob" if lane else "blob_tier"
    assert c2[hits] - c1[hits] >= 1
    assert c2["blob_tier" if lane else "lane_blob"] == c0[
        "blob_tier" if lane else "lane_blob"]
    # a replay is no live request: the bypass counter stays
    assert c2["bypassed"] == c0["bypassed"]
    assert [r.to_dict() for r in second] == [r.to_dict() for r in first]
    if replay == "direct":
        assert _bodies(first) == _bodies(off.validate_batch(items))


# -- the benchmark's data file -----------------------------------------------


def test_the_bypass_share_reads_the_programs_counter():
    spec = json.loads(
        (BENCH / "layer_metrics" / f"{METRIC}.json").read_text())
    assert spec["reader"] == "counter_ratio" and "module" not in spec
    assert (spec["numerator"], spec["denominator"]) == (
        metrics_mod.DEDUP_BLOB_BYPASSED_ROWS, metrics_mod.REQUESTS_DISPATCHED)
    assert spec["scale"] == 100.0

    def samples(scale: float, names: dict) -> reduce.Samples:
        return reduce.parse_metrics("".join(
            f"{name}_total {100.0 + scale * n}\n" for name, n in names.items()))

    window = {metrics_mod.REQUESTS_DISPATCHED: 150_000,
              metrics_mod.DEDUP_BLOB_BYPASSED_ROWS: 148_500}
    ctx = {"before": samples(0.0, window), "after": samples(1.0, window)}
    assert reduce.read_layer_metric(METRIC, ctx) == pytest.approx(99.0)
    # a program without the counter (the parent) gives nothing to read
    parent = {metrics_mod.REQUESTS_DISPATCHED: 150_000}
    ctx = {"before": samples(0.0, parent), "after": samples(1.0, parent)}
    assert reduce.read_layer_metric(METRIC, ctx) is None
    assert reduce.read_layer_metric(METRIC, {"before": {}, "after": {}}) is None


def test_the_bypass_share_comes_last_in_the_tiers_layer():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    # last of the tiers' metrics when it was added; the default install's
    # scanner metrics, appended later, come after it
    assert names.index(METRIC) == names.index("audit_tier_answered_share") - 1
    entry = MANIFEST["per_layer"][names.index(METRIC)]
    tiers = next(m for m in MANIFEST["per_layer"]
                 if m["name"] == "row_tier_hit_share")
    # the default install with the scanner on reports both, appended
    cells = [*TIER_CELLS, "flagship32-audit-defaults.unique-trickle"]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": tiers["layer"],
        "moves": "reviews_per_s", "workloads": cells}
    assert tiers["workloads"] == cells
