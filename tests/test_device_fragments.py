"""A dispatched row answers as a fragment (evaluation/environment.py
``_land_chunk``, ``_answer_rows``).

A chunk the device answered lands as its bytes: one copy of the fetched
batch, a slice a slot. Under the batcher's fragment scope a slot of a
fragment-eligible target (protect mode, no mutator, no wasm, static
messages) answers as uid + the target's template for that verdict, the
rule a tier's hit has followed since round 19; every other slot
materializes from its bytes. Only a chunk that carries a wasm stash is
still read through ``_unpack``'s columns.

* through the micro-batcher, a unique stream over the flagship set's 32
  policies answers byte for byte as the benchmark's plain reference does,
  with the cache off and at its default;
* inside the batcher's fused call an eligible target's dispatched row is
  a ``FragVerdict``, an ineligible one's an ``AdmissionResponse``; a
  direct ``validate_batch`` call and an audit-lane job get
  ``AdmissionResponse`` rows and move no fragment counter;
* ``device_fragment_rows`` counts, exactly, the requests of eligible
  targets that dispatched slots answered; ``fragment_hits`` moves only
  with tier hits;
* a chunk with a wasm stash still lands through ``_unpack``, a chunk
  without one never does;
* with the cache on, ``learn`` files the very row bytes the answers were
  spliced from: one ``row_bytes`` (one ``tobytes``) a chunk;
* the benchmark's data file for ``device_fragment_share`` reads the
  program's counters, reads nothing on a program without them, and its
  manifest entry is sound.
"""

from __future__ import annotations

import json

import pytest

from policy_server_tpu.evaluation import environment
from policy_server_tpu.evaluation.environment import (
    DEFAULT_VERDICT_CACHE_SIZE,
    EvaluationEnvironmentBuilder,
)
from policy_server_tpu.fetch.artifact import load_artifact
from policy_server_tpu.models import (
    AdmissionResponse,
    AdmissionReviewRequest,
    FragVerdict,
    ValidateRequest,
)
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.policies import resolve_builtin
from policy_server_tpu.policies.wasm_oracle import oracle_wasm
from policy_server_tpu.runtime.batcher import MicroBatcher
from policy_server_tpu.telemetry import metrics as metrics_mod

from test_cached_deployment import (
    BENCH,
    CONFIG,
    MANIFEST,
    MIXES,
    ROOT,
    SEED,
    Traffic,
    _batcher,
    _build,
    _serve,
    bench_run,
    check_manifest,
    reduce,
)
from test_wasm_group_members import pod_review

METRIC = "device_fragment_share"
CACHES = {"off": 0, "default": DEFAULT_VERDICT_CACHE_SIZE}


@pytest.fixture(scope="module")
def policies(tmp_path_factory) -> dict:
    store = tmp_path_factory.mktemp("sigstore")
    pubkey = bench_run.build_signature_store(CONFIG["signing"], store)
    return bench_run.fill(CONFIG["policies"], {
        "@SIGSTORE@": str(store), "@PUBKEY@": pubkey})


@pytest.fixture(scope="module")
def deployments(policies):
    """The flagship set behind the micro-batcher as the cells pin it, with
    the cache off and at its default."""
    built = {}
    for name, size in CACHES.items():
        env = _build(policies, verdict_cache_size=size)
        built[name] = (env, _batcher(env))
    yield built
    for env, batcher in built.values():
        batcher.shutdown()
        env.close()


def _pairs(policies: dict, base: int, count: int) -> list:
    """Requests ``base .. base + count`` of the unique stream."""
    ids = list(policies)
    traffic = Traffic(MIXES["unique"], SEED, ids)
    pairs = []
    for n in range(base, base + count):
        body = traffic.request(n).partition(b"\r\n\r\n")[2]
        pairs.append((ids[traffic.policy_of(n)], ValidateRequest.from_admission(
            AdmissionReviewRequest.from_dict(json.loads(body)).request)))
    return pairs


def _eligible(env, pid: str) -> bool:
    return env._frag_eligible(env._fast_target(pid))


def _counts(env) -> dict[str, int]:
    profile, dedup = env.host_profile, env.dedup_stats
    return {"fragments": profile["device_fragment_rows"],
            "dispatched": profile["dispatched_rows"],
            "chunks": profile["dispatched_chunks"],
            "fragment_hits": dedup["fragment_hits"],
            "row_tier": dedup["cache_hits"]}


def _moved(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}


# -- through the batcher ------------------------------------------------------


@pytest.mark.parametrize("cache", list(CACHES))
def test_a_unique_stream_answers_as_the_reference_does(
        deployments, policies, cache):
    env, batcher = deployments[cache]
    c0 = _counts(env)
    out = _serve(env, batcher, policies, "unique", 4, 200_000, 512)
    assert out["got"] == out["want"]
    moved = _moved(c0, _counts(env))
    assert out["moved"]["device"] > 0 and moved["fragments"] > 0


@pytest.mark.parametrize("cache", list(CACHES))
def test_an_eligible_targets_dispatched_row_is_a_fragment(
        deployments, policies, cache):
    env, batcher = deployments[cache]
    pairs = _pairs(policies, 210_000, 128)
    env.reset_verdict_cache()
    c0 = _counts(env)
    got = batcher._fused_validate(pairs)  # what a batch's worker runs
    moved = _moved(c0, _counts(env))
    assert moved["dispatched"] > 0 and moved["row_tier"] == 0
    kinds = {_eligible(env, pid) for pid, _request in pairs}
    assert kinds == {True, False}  # both kinds among the batch
    for (pid, _request), result in zip(pairs, got):
        want = FragVerdict if _eligible(env, pid) else AdmissionResponse
        assert type(result) is want, pid
    # the spliced answer is the one the direct call materializes
    env.reset_verdict_cache()
    direct = env.validate_batch(pairs)
    assert [r.to_response().to_dict() if type(r) is FragVerdict
            else r.to_dict() for r in got] == [r.to_dict() for r in direct]


@pytest.mark.parametrize("caller", ["direct", "audit-lane"])
@pytest.mark.parametrize("cache", list(CACHES))
def test_a_direct_call_and_an_audit_job_get_admission_responses(
        deployments, policies, cache, caller):
    env, batcher = deployments[cache]
    pairs = _pairs(policies, 220_000, 96)
    env.reset_verdict_cache()
    c0 = _counts(env)
    if caller == "direct":
        got = env.validate_batch(pairs)
    else:
        got = batcher.submit_audit(pairs).result(timeout=120)
    moved = _moved(c0, _counts(env))
    assert any(_eligible(env, pid) for pid, _request in pairs)
    assert all(type(r) is AdmissionResponse for r in got)
    assert moved["fragments"] == moved["fragment_hits"] == 0
    assert moved["chunks"] > 0  # the device answered them


@pytest.mark.parametrize("cache", list(CACHES))
def test_the_counter_counts_the_eligible_requests_slots_answered(
        deployments, policies, cache):
    env, batcher = deployments[cache]
    base, count = 230_000, 512
    eligible = sum(_eligible(env, pid)
                   for pid, _request in _pairs(policies, base, count))
    c0 = _counts(env)
    first = _serve(env, batcher, policies, "unique", 4, base, count)
    c1 = _counts(env)
    again = _serve(env, batcher, policies, "unique", 4, base, count,
                   reset=False)  # the same requests once more
    c2 = _counts(env)
    assert first["got"] == first["want"] and again["got"] == again["want"]
    one, two = _moved(c0, c1), _moved(c1, c2)
    # no (policy, shape) comes twice in the stream: the device answers all
    assert first["moved"]["row_tier"] == 0
    assert one["fragments"] == eligible and one["fragment_hits"] == 0
    if cache == "off":
        assert two["fragments"] == eligible and two["fragment_hits"] == 0
    else:
        # the row tier answers every one of them the second time
        assert again["moved"]["row_tier"] == count
        assert two["fragments"] == 0 and two["fragment_hits"] == eligible


def test_learn_files_the_bytes_the_answers_were_spliced_from(
        deployments, policies, monkeypatch):
    env, batcher = deployments["default"]
    made, learned, spliced = [], [], []

    def row_bytes(fetched):
        rows = real_row_bytes(fetched)
        made.append(rows)
        return rows

    def learn(plan, rows):
        learned.append(rows)
        return real_learn(plan, rows)

    def frag_of(target, row):
        spliced.append(row)
        return real_frag_of(target, row)

    real_row_bytes = environment.row_bytes
    real_learn, real_frag_of = env._tiers.learn, env._frag_of
    monkeypatch.setattr(environment, "row_bytes", row_bytes)
    monkeypatch.setattr(env._tiers, "learn", learn)
    monkeypatch.setattr(env, "_frag_of", frag_of)
    pairs = _pairs(policies, 240_000, 128)
    env.reset_verdict_cache()
    c0 = _counts(env)
    got = batcher._fused_validate(pairs)
    moved = _moved(c0, _counts(env))
    assert len(made) == moved["chunks"] >= 1  # one copy a chunk
    assert len(learned) == len(made)
    assert all(a is b for a, b in zip(learned, made))
    # every spliced answer was made from a slice of that one copy, and
    # the row tier holds the very same objects
    sliced = {id(row) for rows in made for row in rows}
    assert spliced and all(id(row) in sliced for row in spliced)
    filed = {id(row) for row in env._tiers.row._data.values()}
    assert {id(row) for row in spliced} <= filed
    assert sum(type(r) is FragVerdict for r in got) == moved["fragments"] > 0


# -- a chunk with a wasm stash ------------------------------------------------


@pytest.fixture(scope="module")
def wasm_envs(tmp_path_factory):
    """A group with a real wasm member beside an eligible IR policy, on
    the device and on the oracle backend."""
    wasm_path = tmp_path_factory.mktemp("wasm") / "priv.wasm"
    wasm_path.write_bytes(oracle_wasm("pod-privileged"))
    wasm_module = load_artifact(wasm_path)

    def resolver(url: str):
        if url.endswith(".wasm"):
            return wasm_module
        return resolve_builtin(url)

    def build(backend: str):
        return EvaluationEnvironmentBuilder(
            backend=backend, module_resolver=resolver
        ).build({
            "guard": parse_policy_entry("guard", {
                "expression": "wasmpriv() && ns()",
                "message": "pod guard rejected",
                "policies": {
                    "wasmpriv": {"module": "file:///priv.wasm"},
                    "ns": {"module": "builtin://namespace-validate",
                           "settings": {"denied_namespaces": ["blocked"]}},
                },
            }),
            "priv": parse_policy_entry(
                "priv", {"module": "builtin://pod-privileged"}),
        })

    envs = build("jax"), build("oracle")
    yield envs
    for env in envs:
        env.close()


@pytest.mark.parametrize("with_wasm_rows", [True, False])
def test_a_chunk_with_a_wasm_stash_lands_through_unpack(
        wasm_envs, monkeypatch, with_wasm_rows):
    env, oracle = wasm_envs
    unpacked = []
    real_unpack = env._unpack
    monkeypatch.setattr(
        env, "_unpack", lambda raw: unpacked.append(raw) or real_unpack(raw))
    targets = ["guard", "priv"] if with_wasm_rows else ["priv"]
    pairs = [(pid, pod_review(ns, priv))
             for pid in targets
             for ns in ("default", "blocked")
             for priv in (False, True)]
    assert _eligible(env, "priv") and not _eligible(env, "guard")
    env.reset_verdict_cache()
    c0 = _counts(env)
    got = MicroBatcher(env, max_batch_size=64)._fused_validate(pairs)
    moved = _moved(c0, _counts(env))
    assert moved["chunks"] == 1
    if with_wasm_rows:
        # the stash's bits ride beside the rows: the whole chunk is read
        # through the columns, and answered as before
        assert len(unpacked) == 1
        assert all(type(r) is AdmissionResponse for r in got)
        assert moved["fragments"] == 0
    else:
        assert unpacked == []
        assert all(type(r) is FragVerdict for r in got)
        assert moved["fragments"] == len(pairs)
    want = [r.to_dict() for r in oracle.validate_batch(pairs)]
    assert [r.to_response().to_dict() if type(r) is FragVerdict
            else r.to_dict() for r in got] == want


# -- the benchmark's data file ------------------------------------------------


def test_the_share_reads_the_programs_counters():
    spec = json.loads(
        (BENCH / "layer_metrics" / f"{METRIC}.json").read_text())
    assert spec["reader"] == "counter_ratio" and "module" not in spec
    assert (spec["numerator"], spec["denominator"]) == (
        metrics_mod.DEVICE_FRAGMENT_ROWS, metrics_mod.DISPATCHED_ROWS)
    assert spec["scale"] == 100.0

    def samples(scale: float, names: dict) -> "reduce.Samples":
        return reduce.parse_metrics("".join(
            f"{name} {100.0 + scale * n}\n" for name, n in names.items()))

    window = {metrics_mod.DISPATCHED_ROWS: 160_000,
              metrics_mod.DEVICE_FRAGMENT_ROWS: 90_000}
    ctx = {"before": samples(0.0, window), "after": samples(1.0, window)}
    assert reduce.read_layer_metric(METRIC, ctx) == pytest.approx(56.25)
    # a program without the counter (the parent) gives nothing to read
    parent = {metrics_mod.DISPATCHED_ROWS: 160_000}
    ctx = {"before": samples(0.0, parent), "after": samples(1.0, parent)}
    assert reduce.read_layer_metric(METRIC, ctx) is None
    assert reduce.read_layer_metric(METRIC, {"before": {}, "after": {}}) is None


def test_the_manifest_with_the_share_is_sound():
    assert check_manifest.problems(MANIFEST, ROOT) == []
    names = [m["name"] for m in MANIFEST["per_layer"]]
    # appended after what the benchmark had, nothing before it moved
    at = names.index(METRIC)
    assert at == names.index("audit_bookkeeping_ms_mean") + 1
    materialize = next(m for m in MANIFEST["per_layer"]
                       if m["name"] == "materialize_ms_mean")
    assert MANIFEST["per_layer"][at] == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": materialize["layer"],
        "moves": "reviews_per_s",
        "workloads": [w["name"] for w in MANIFEST["workloads"]]}
