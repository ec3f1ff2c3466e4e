"""PR 31: ``flagship32-cached``, the flagship set with the verdict cache at
its documented default, as a deployment the benchmark holds to its
guarantees (benchmarks/configs/flagship32-cached.json).

* the environment with the cache at its default, behind the micro-batcher
  and pinned to the device as the cells pin it, answers a rollout stream
  (``replicas: 8``) and an all-unique stream byte for byte as the
  benchmark's plain reference does, also when several threads submit a
  block at once so that repeats meet in flight;
* after each stream rows dispatched + row-tier hits + blob-tier hits +
  in-batch duplicates = answers, to the unit: the identity ``correct``
  holds the deployment to (``reduce.held_to_its_sources``);
* no answer carries another request's uid (the ``stale-uid`` fault);
* a cache of a few KB evicts, ``policy_server_verdict_cache_evictions_total``
  counts it by tier on ``/metrics``, and the answers stay exact;
* (PR 32) what the device path leaves in the tiers is the packed output
  row: every entry of both tiers is ``bytes``, a rollout's hits answer
  from them through the fragment lane and through ``_materialize``
  (eighteen of the 32 policies are fragment-eligible, fourteen are not),
  256Mi holds 90,000 entries a tier, and
  ``policy_server_verdict_cache_puts_total`` /
  ``..._put_bytes_total`` on ``/metrics`` read as ``cache_bytes_per_put``;
* the benchmark's data files for the deployment: the manifest is sound,
  the two cells are the issue's letter for letter, every new layer metric
  reads the program's own counters and reads nothing on a program without
  them, and the rollout mix gives blocks of 256.
"""

from __future__ import annotations

import json
import sys
import threading
from pathlib import Path

import pytest
import requests

from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.config.config import Config, TlsConfig
from policy_server_tpu.evaluation.environment import (
    DEFAULT_VERDICT_CACHE_SIZE,
    EvaluationEnvironmentBuilder,
)
from policy_server_tpu.evaluation.verdict_cache import VerdictCache, entry_cost
from policy_server_tpu.models import (
    AdmissionReviewRequest,
    AdmissionReviewResponse,
    ValidateRequest,
)
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.runtime.batcher import MicroBatcher
from policy_server_tpu.telemetry import metrics as metrics_mod

from test_server import ServerHandle

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmarks"
# the benchmark's own modules, imported as the benchmark imports them
sys.path.insert(0, str(BENCH))
try:
    import check_manifest
    import reduce
    import reference
    import run as bench_run
    from traffic import Traffic, uid_of
finally:
    sys.path.remove(str(BENCH))

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((BENCH / "configs" / "flagship32-cached.json").read_text())
CELLS = ("flagship32-cached.rollout-saturate",
         "flagship32-cached.unique-saturate")
# PR 35's cell runs the same tiers under the documented routing defaults
# and reports their metrics too (tests/test_defaults_deployment.py)
TIER_CELLS = [*CELLS, "flagship32-defaults.unique-saturate"]
NEW_METRICS = ("device_answered_share", "row_tier_hit_share",
               "batch_duplicate_share", "launched_batch_share",
               "bookkeeping_ms_mean", "cache_evictions_in_window",
               "cache_bytes_per_put")
SIGNED = set(CONFIG["signing"]["signed_images"])
SEED = 2**31 + 31
POOL = 61  # a prime, as the cells' 16,381 is: a shape meets every policy
MIXES = {
    "rollout": {"generator": "pod_reviews", "pool_shapes": POOL,
                "arrival": "closed", "replicas": 8},
    "unique": {"generator": "pod_reviews", "pool_shapes": POOL,
               "arrival": "closed"},
}


@pytest.fixture(scope="module")
def policies(tmp_path_factory) -> dict:
    """The configuration's 32 policies, its signature store built as a run
    builds it."""
    store = tmp_path_factory.mktemp("sigstore")
    pubkey = bench_run.build_signature_store(CONFIG["signing"], store)
    return bench_run.fill(CONFIG["policies"], {
        "@SIGSTORE@": str(store), "@PUBKEY@": pubkey})


def _build(policies: dict, **kwargs):
    return EvaluationEnvironmentBuilder(backend="jax", **kwargs).build(
        {k: parse_policy_entry(k, v) for k, v in policies.items()})


def _batcher(env) -> MicroBatcher:
    # the cells' flags: routing pinned to the device, the defaults else
    return MicroBatcher(
        env, max_batch_size=128, batch_timeout_ms=1.0, policy_timeout=60.0,
        host_fastpath_threshold=0, latency_budget_ms=0,
    ).start()


def _sources(env) -> dict[str, int]:
    """What each source of ``guarantees.answers_from`` has counted."""
    dedup = env.dedup_stats
    return {"device": env.host_profile["dispatched_rows"],
            "row_tier": dedup["cache_hits"],
            "blob_tier": dedup["blob_cache_hits"],
            "batch_duplicate": dedup["batch_dup_hits"]}


def _serve(env, batcher, policies: dict, stream: str, threads: int,
           base: int, count: int, reset: bool = True) -> dict:
    """Requests ``base .. base + count`` of the stream through the
    batcher, thread k of K submitting n = k mod K as client k of K does;
    the tiers start empty unless ``reset`` is false, their counters are
    read around the stream."""
    ids = list(policies)
    traffic = Traffic(MIXES[stream], SEED, ids)
    if reset:
        env.reset_verdict_cache()
    before = _sources(env)
    futures: list = [None] * count

    def submit(k: int) -> None:
        for i in range(k, count, threads):
            body = traffic.request(base + i).partition(b"\r\n\r\n")[2]
            futures[i] = batcher.submit(
                ids[traffic.policy_of(base + i)],
                ValidateRequest.from_admission(
                    AdmissionReviewRequest.from_dict(json.loads(body)).request),
                RequestOrigin.VALIDATE)

    workers = [threading.Thread(target=submit, args=(k,))
               for k in range(threads)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    got, want = [], []
    for i, future in enumerate(futures):
        n = base + i
        got.append(json.dumps(AdmissionReviewResponse(
            future.result(timeout=120)).to_dict()).encode())
        want.append(reference.http_response(
            ["-"], uid_of(n), reference.review_response(
                policies[ids[traffic.policy_of(n)]],
                traffic.reviews[traffic.shape_of(n)]["request"], SIGNED),
        ).partition(b"\r\n\r\n")[2])
    after = _sources(env)
    return {"got": got, "want": want, "base": base,
            "moved": {k: after[k] - before[k] for k in after}}


@pytest.fixture(scope="module")
def deployment(policies):
    assert DEFAULT_VERDICT_CACHE_SIZE == 256 * 1024 * 1024
    assert "--verdict-cache-size" not in CONFIG["server_flags"]
    env = _build(policies)  # the cache at its default
    batcher = _batcher(env)
    yield env, batcher
    batcher.shutdown()
    env.close()


# two blocks of 256 (two shapes under all 32 policies, 8 replicas each) and
# a stretch in which no (policy, shape) pair comes twice
STREAMS = [("rollout", 1), ("rollout", 4), ("unique", 1), ("unique", 4)]


@pytest.fixture(scope="module", params=STREAMS,
                ids=[f"{s}-{t}-submitters" for s, t in STREAMS])
def served(request, deployment, policies) -> dict:
    stream, threads = request.param
    env, batcher = deployment
    return _serve(env, batcher, policies, stream, threads,
                  base=10_000 * (1 + request.param_index), count=512)


def test_the_default_cache_answers_as_the_reference_does(served):
    assert served["got"] == served["want"]


def test_every_answer_is_counted_by_exactly_one_source(served):
    moved = served["moved"]
    assert sum(moved.values()) == len(served["got"]), moved
    assert moved["device"] > 0


def test_no_answer_carries_another_requests_uid(served):
    for i, body in enumerate(served["got"]):
        assert json.loads(body)["response"]["uid"] == uid_of(
            served["base"] + i)


def test_a_rollout_is_answered_by_the_tiers_and_uniques_by_the_device(
        deployment, policies):
    env, batcher = deployment
    rollout = _serve(env, batcher, policies, "rollout", 4, 60_000, 512)
    # two shapes: the device sees a handful of rows, the tiers the rest
    assert rollout["moved"]["device"] < 0.1 * 512
    assert rollout["moved"]["blob_tier"] == 0  # no request repeats a byte
    assert rollout["got"] == rollout["want"]


def test_hits_answer_from_packed_entries_through_both_lanes(
        deployment, policies):
    """PR 32: after a rollout through the batcher every entry of both
    tiers is the packed row the device returned (80 bytes here), put at
    the constant-time cost, and the tiers' hits were served from them
    through the fragment lane (eligible targets) AND through
    ``_materialize`` over the packed row (the others), byte for byte as
    the reference answers."""
    env, batcher = deployment
    eligible = [env._frag_eligible(env._fast_target(pid)) for pid in policies]
    assert 0 < sum(eligible) < len(eligible)  # both kinds among the 32
    # two whole blocks (two shapes under all 32 policies), then the same
    # requests again over the tiers they filled: nothing reaches the
    # device, and in every batch the first request of each (policy,
    # shape) pair is a tier hit of a packed entry
    first = _serve(env, batcher, policies, "rollout", 4, 256 * 500, 512)
    assert first["got"] == first["want"]
    frag0 = env.dedup_stats["fragment_hits"]
    out = _serve(env, batcher, policies, "rollout", 4, 256 * 500, 512,
                 reset=False)
    assert out["got"] == out["want"]
    assert out["moved"]["device"] == 0 and sum(out["moved"].values()) == 512
    stats = env.dedup_stats
    tier_hits = out["moved"]["row_tier"] + out["moved"]["blob_tier"]
    frag_hits = stats["fragment_hits"] - frag0
    assert tier_hits >= 64
    assert 0 < frag_hits < tier_hits  # some spliced, some materialized
    width = CONFIG["verdict_bytes_per_row"]["value"]
    assert width == 80 == len(env._out_layout.index)
    for tier in (env._tiers.row, env._tiers.blob):
        entries = list(tier._data.items())
        assert entries
        assert all(type(row) is bytes and len(row) == width
                   for _key, row in entries)
        assert tier.bytes_used == sum(
            256 + len(key[1]) + width for key, _row in entries)
    # no template hangs on a row: they are in the environment, at most one
    # per target and verdict
    lanes = [lane for lane in env._frag_lanes.values() if lane]
    assert lanes and all(len(memo) <= 4 for lane in lanes for _f, memo in lane)
    row_keys = [len(key[1]) for key in env._tiers.row._data]
    per_entry = stats["cache_bytes"] / stats["cache_entries"]
    assert per_entry == 256 + row_keys[0] + width
    assert (DEFAULT_VERDICT_CACHE_SIZE // 2) // per_entry >= 90_000


# -- a cache too small for its traffic -----------------------------------------


@pytest.mark.parametrize("stream", ["rollout", "unique"])
def test_a_cache_of_a_few_kb_evicts_by_tier_and_stays_exact(policies, stream):
    # an entry is ~1.4 KB (PR 32; ~7.7 KB before): an 8 KiB tier holds 5,
    # under the 13-17 rows even a rollout of two shapes dispatches
    env = _build(policies, verdict_cache_size=16 * 1024)
    batcher = _batcher(env)
    try:
        out = _serve(env, batcher, policies, stream, 4, 80_000, 512)
        stats = env.dedup_stats
    finally:
        batcher.shutdown()
        env.close()
    assert out["got"] == out["want"]
    assert sum(out["moved"].values()) == 512
    for tier in ("", "blob_"):
        assert stats[tier + "cache_evictions"] > 0
        assert stats[tier + "cache_bytes"] <= 8 * 1024
        assert stats[tier + "cache_entries"] < 8
        # (a key two batches in flight both missed is put twice)
        assert stats[tier + "cache_puts"] >= (
            stats[tier + "cache_evictions"] + stats[tier + "cache_entries"])


@pytest.mark.parametrize("puts, capacity_entries, reputs", [
    (10, 4, 0), (4, 4, 0), (5, 4, 3), (64, 1, 0)])
def test_the_cache_counts_what_its_byte_bound_pushes_out(
        puts, capacity_entries, reputs):
    row = {"allowed": True}
    cost = 256 + 80 + 8  # entry_cost of (("t",), 8 key bytes) over this row
    assert cost == entry_cost((("t",), b"%08d" % 0), row)
    cache = VerdictCache(capacity_entries * cost)
    cache.put_many(((("t",), b"%08d" % i), row) for i in range(puts))
    for _ in range(reputs):  # a live key put again replaces, evicts nothing
        cache.put((("t",), b"%08d" % (puts - 1)), row)
    stats = cache.stats()
    assert stats["cache_evictions"] == max(0, puts - capacity_entries)
    assert stats["cache_entries"] == min(puts, capacity_entries)
    assert cache.get((("t",), b"%08d" % (puts - 1))) is row  # newest stays


def test_the_eviction_counter_is_on_metrics_by_tier():
    """A served rollout of one pod under new uids: every request after the
    first hits the row tier and backfills its never-recurring blob, so a
    small blob tier churns; the layer metric's data file reads the move."""
    metrics_mod.reset_metrics_for_tests()
    handle = ServerHandle(Config(
        addr="127.0.0.1", port=0, readiness_probe_port=0,
        tls_config=TlsConfig(),
        policies={"priv": parse_policy_entry(
            "priv", {"module": "builtin://pod-privileged"})},
        policy_timeout_seconds=30.0, max_batch_size=8, batch_timeout_ms=1.0,
        host_fastpath_threshold=0, latency_budget_ms=0,
        verdict_cache_size=16 * 1024, warmup_at_boot=True,
    ))
    try:
        def scrape() -> reduce.Samples:
            r = requests.get(handle.readiness_url("/metrics"), timeout=10)
            return reduce.parse_metrics(r.text)

        traffic = Traffic(MIXES["rollout"], SEED, ["priv"])
        before = scrape()
        for n in range(40):
            body = traffic.request(n).partition(b"\r\n\r\n")[2]
            r = requests.post(handle.url("/validate/priv"), data=body, headers={
                "Content-Type": "application/json"}, timeout=30)
            assert r.json()["response"]["uid"] == uid_of(n)
        after = scrape()
        stats = handle.server.environment.dedup_stats
    finally:
        handle.stop()
        metrics_mod.reset_metrics_for_tests()
    by_tier = {tier: reduce.delta(before, after, {
        "name": metrics_mod.VERDICT_CACHE_EVICTIONS, "labels": {"tier": tier}})
        for tier in ("blob", "row")}
    assert by_tier == {"blob": stats["blob_cache_evictions"],
                       "row": stats["cache_evictions"]}
    assert by_tier["blob"] > 0 and by_tier["row"] == 0
    assert reduce.read_layer_metric("cache_evictions_in_window", {
        "before": before, "after": after}) == sum(by_tier.values())


def test_the_put_counters_are_on_metrics_and_read_as_bytes_per_put():
    """Forty distinct pods through a served policy; the two counters (one series each, both tiers
    together) move by the puts and their accounted bytes, and the layer
    metric's data file reads their ratio with the benchmark's reader."""
    metrics_mod.reset_metrics_for_tests()
    handle = ServerHandle(Config(
        addr="127.0.0.1", port=0, readiness_probe_port=0,
        tls_config=TlsConfig(),
        policies={"priv": parse_policy_entry(
            "priv", {"module": "builtin://pod-privileged"})},
        policy_timeout_seconds=30.0, max_batch_size=8, batch_timeout_ms=1.0,
        host_fastpath_threshold=0, latency_budget_ms=0, warmup_at_boot=True,
    ))
    try:
        def scrape() -> reduce.Samples:
            r = requests.get(handle.readiness_url("/metrics"), timeout=10)
            return reduce.parse_metrics(r.text)

        traffic = Traffic(MIXES["unique"], SEED, ["priv"])
        before = scrape()
        for n in range(40):
            body = traffic.request(n).partition(b"\r\n\r\n")[2]
            r = requests.post(handle.url("/validate/priv"), data=body, headers={
                "Content-Type": "application/json"}, timeout=30)
            assert r.json()["response"]["uid"] == uid_of(n)
        after = scrape()
        stats = handle.server.environment.dedup_stats
    finally:
        handle.stop()
        metrics_mod.reset_metrics_for_tests()
    puts = reduce.delta(before, after, metrics_mod.VERDICT_CACHE_PUTS)
    put_bytes = reduce.delta(before, after, metrics_mod.VERDICT_CACHE_PUT_BYTES)
    # a put a distinct encoded row in the row tier (a pod's name and uid
    # are no features of this policy), a put a payload in the blob tier
    assert puts == stats["cache_puts"] + stats["blob_cache_puts"]
    assert 0 < stats["cache_puts"] <= stats["blob_cache_puts"] == 40
    assert put_bytes == stats["cache_put_bytes"] + stats["blob_cache_put_bytes"]
    # nothing was evicted, so what was put is what is resident
    assert put_bytes == stats["cache_bytes"] + stats["blob_cache_bytes"]
    got = reduce.read_layer_metric("cache_bytes_per_put", {
        "before": before, "after": after})
    assert got == pytest.approx(put_bytes / puts)
    assert 256 < got < 256 + 4096  # a key and a packed row, not 80 boxed scalars


# -- the benchmark's data files for the deployment --------------------------------


def test_the_manifest_with_the_deployment_is_sound():
    assert check_manifest.problems(MANIFEST, ROOT) == []
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "flagship32-cached")
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert "configs[3]" in entry["source"]
    assert "--verdict-cache-size" in entry["source"]
    assert CONFIG["guarantees"]["answers_from"] == {
        "device": "policy_server_dispatched_rows",
        "row_tier": "policy_server_verdict_cache_hits",
        "blob_tier": "policy_server_dedup_blob_hits",
        "batch_duplicate": "policy_server_batch_dedup_hits"}
    # flagship32 with the flag left out, nothing else of the deployment
    accepted = json.loads((BENCH / "configs" / "flagship32.json").read_text())
    flags = list(accepted["server_flags"])
    at = flags.index("--verdict-cache-size")
    assert flags[:at] + flags[at + 2:] == CONFIG["server_flags"]
    for key in ("policies", "signing", "response_head", "row_bytes_dense",
                "verdict_bytes_per_row", "chips", "mesh"):
        assert CONFIG[key] == accepted[key]


def _per_layer(name: str) -> dict:
    return next(m for m in MANIFEST["per_layer"] if m["name"] == name)


def test_the_manifest_with_the_python_strings_metric_is_sound():
    """PR 34 adds one per-layer entry (last in its list until PR 35's
    four) and one data file: strings a row that the native encoder's
    mirror of the intern table had not seen, in every cell, by the
    program's own counters."""
    assert check_manifest.problems(MANIFEST, ROOT) == []
    encode = _per_layer("encode_us_per_row")
    assert _per_layer("encode_python_strings_per_row") == {
        "name": "encode_python_strings_per_row", "unit": "strings/row",
        "better": "lower", "source": "program_counter",
        "layer": encode["layer"], "moves": "reviews_per_s",
        "workloads": [w["name"] for w in MANIFEST["workloads"]]}
    spec = json.loads((BENCH / "layer_metrics"
                       / "encode_python_strings_per_row.json").read_text())
    assert spec["reader"] == "counter_ratio" and "module" not in spec
    assert (spec["numerator"], spec["denominator"]) == (
        metrics_mod.HOST_ENCODE_PYTHON_STRINGS, metrics_mod.HOST_ENCODE_ROWS)


def test_the_manifest_with_the_bytes_per_put_metric_is_sound():
    """PR 32 adds one per-layer entry and one data file; nothing else of
    the benchmark moves."""
    assert check_manifest.problems(MANIFEST, ROOT) == []
    assert _per_layer("cache_bytes_per_put") == {
        "name": "cache_bytes_per_put", "unit": "bytes", "better": "lower",
        "source": "program_counter",
        "layer": "dedup tiers in front of the device (evaluation/"
                 "verdict_cache.py, environment.py _native_schema_pass)",
        "moves": "reviews_per_s", "workloads": TIER_CELLS}
    spec = json.loads(
        (BENCH / "layer_metrics" / "cache_bytes_per_put.json").read_text())
    assert spec["reader"] == "counter_ratio" and "module" not in spec
    assert (spec["numerator"], spec["denominator"]) == (
        metrics_mod.VERDICT_CACHE_PUT_BYTES, metrics_mod.VERDICT_CACHE_PUTS)
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] in NEW_METRICS}
    assert len(layers) == 1  # the layer's name, letter for letter


@pytest.mark.parametrize("cell, traffic, extra", [
    (CELLS[0], "rollout-saturate", {"replicas": 8}),
    (CELLS[1], "unique-saturate", {}),
])
def test_the_cells_are_the_issues_letter_for_letter(cell, traffic, extra):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "flagship32-cached", traffic, 1)
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    parameters = {k: v for k, v in mix.items() if not k.endswith("why")}
    assert parameters == {
        "generator": "pod_reviews", "pool_shapes": 16381, "arrival": "closed",
        "connections": 512, "client_processes": 4, "warm_requests": 8192,
        "timeout_s": 10, **extra}
    reported = {m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
                if cell in (m.get("workloads") or [cell])}
    assert {"reviews_per_s", "setup_s", "predicate_roofline",
            "device_idle_share", *NEW_METRICS} <= reported
    assert "latency_p50_ms" not in reported
    # what the accepted saturate cell reports, this one reports too
    assert reported >= {
        m["name"] for m in MANIFEST["per_layer"]
        if "flagship32.unique-saturate" in m["workloads"]}


def test_the_rollout_mix_gives_blocks_of_256():
    mix = json.loads((BENCH / "traffic" / "rollout-saturate.json").read_text())
    ids = list(CONFIG["policies"])
    traffic = Traffic({**mix, "pool_shapes": POOL}, SEED, ids)
    assert mix["arrival"] == "closed" and traffic.block == 8 * 32 == 256
    for block in range(3):
        numbers = range(256 * block, 256 * (block + 1))
        assert len({traffic.shape_of(n) for n in numbers}) == 1
        # each policy meets the shape 8 times, under 8 uids
        assert [traffic.policy_of(n) for n in numbers] == list(range(32)) * 8
        assert len({traffic.request(n) for n in numbers}) == 256
    assert traffic.shape_of(255) != traffic.shape_of(256)


def _planted(moved: dict[str, float]) -> tuple:
    """/metrics before and after a window in which the named samples moved
    by the given amounts (a sample is ``name`` or ``name{label="v"}``)."""
    def text(scale: float) -> str:
        return "".join(f"{name} {100.0 + scale * n}\n"
                       for name, n in moved.items())
    return reduce.parse_metrics(text(0.0)), reduce.parse_metrics(text(1.0))


@pytest.mark.parametrize("moved, answers, want", [
    pytest.param({"device": 4_100, "row_tier": 60_000, "batch_duplicate": 85_000,
                  "blob_tier": 900}, 150_000, (0, 0),
                 id="the four named sources close the count"),
    pytest.param({"device": 4_100, "row_tier": 60_000, "batch_duplicate": 85_000},
                 150_000, (0, 900), id="an answer no source counted"),
    pytest.param({"device": 4_100, "row_tier": 60_900, "batch_duplicate": 85_900},
                 150_000, (0, 900), id="an answer two sources counted"),
    pytest.param({"device": 4_100, "row_tier": 145_000, "host_fastpath": 900},
                 150_000, (900, 900), id="the host fast path stays unnamed"),
])
def test_the_deployment_is_held_to_the_sources_its_file_names(
        moved, answers, want):
    sources = reduce.answer_sources()
    before, after = _planted({
        spec["counter"] + "_total": moved.get(source, 0)
        for source, spec in sources.items()})
    got = reduce.held_to_its_sources(CONFIG, before, after, answers)
    assert (got["answered_off_device"], got["rows_not_dispatched"]) == want


PHASE = 'policy_server_phase_latency_seconds_%s{phase="%s"}'
WINDOW = {  # a window of 150,000 answers in 2,000 batches, 300 launched
    "policy_server_requests_dispatched_total": 150_000,
    "policy_server_batches_dispatched_total": 2_000,
    "policy_server_dispatched_rows_total": 4_500,
    "policy_server_verdict_cache_hits_total": 60_000,
    "policy_server_batch_dedup_hits_total": 85_500,
    PHASE % ("count", "launch"): 300,
    PHASE % ("count", "bookkeeping"): 2_000,
    PHASE % ("sum", "bookkeeping"): 3.0,
    'policy_server_verdict_cache_evictions_total{tier="blob"}': 20_000,
    'policy_server_verdict_cache_evictions_total{tier="row"}': 1_500,
    "policy_server_verdict_cache_puts_total": 10_000,
    "policy_server_verdict_cache_put_bytes_total": 14_000_000,
}


@pytest.mark.parametrize("name, want", zip(NEW_METRICS, (
    3.0, 40.0, 57.0, 15.0, 1.5, 21_500.0, 1_400.0)))
def test_a_new_layer_metric_reads_the_programs_counters(name, want):
    before, after = _planted(WINDOW)
    ctx = {"before": before, "after": after}
    assert reduce.read_layer_metric(name, ctx) == pytest.approx(want)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["moves"] == "reviews_per_s"
    assert entry["workloads"] == TIER_CELLS
    # a program without the counters (the eviction counter: the parent)
    # gives nothing to read, and nothing is raised
    assert reduce.read_layer_metric(name, {"before": {}, "after": {}}) is None
