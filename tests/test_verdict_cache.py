"""Bit-exact row dedup / verdict caching (evaluation/verdict_cache.py;
VERDICT r4 next-round #1): identical packed rows are answered without
re-dispatch — in-batch dedup, a cross-batch LRU, and the host fast-path
sharing the same key space — with verdicts REQUIRED to be bit-identical
to a dedup-disabled environment, each request keeping its own uid and
its own materialized patch."""

from __future__ import annotations

import gc
import os
import tracemalloc

import numpy as np
import pytest

from policy_server_tpu.evaluation.environment import (
    DEFAULT_VERDICT_CACHE_SIZE,
    EvaluationEnvironmentBuilder,
)
from policy_server_tpu.evaluation.verdict_cache import (
    _ENTRY_OVERHEAD,
    DedupTiers,
    OutputLayout,
    PackedRow,
    VerdictCache,
    entry_cost,
)
from policy_server_tpu.models import AdmissionReviewRequest, ValidateRequest
from policy_server_tpu.models.policy import parse_policy_entry

from conftest import build_admission_review_dict

POLICIES = {
    "priv": {"module": "builtin://pod-privileged"},
    "ns": {
        "module": "builtin://namespace-validate",
        "settings": {"denied_namespaces": ["blocked"]},
    },
    "grp": {
        "expression": "a() && b()",
        "message": "group denied",
        "policies": {
            "a": {"module": "builtin://always-happy"},
            "b": {"module": "builtin://pod-privileged"},
        },
    },
}


def parse_all(policies: dict) -> dict:
    return {k: parse_policy_entry(k, v) for k, v in policies.items()}


def pod_request(
    namespace: str, privileged: bool, uid: str = "uid-0"
) -> ValidateRequest:
    doc = build_admission_review_dict()
    doc["request"]["uid"] = uid
    doc["request"]["namespace"] = namespace
    doc["request"]["object"] = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {"name": "p", "namespace": namespace},
        "spec": {
            "containers": [
                {"name": "c", "image": "nginx",
                 "securityContext": {"privileged": privileged}}
            ]
        },
    }
    return ValidateRequest.from_admission(
        AdmissionReviewRequest.from_dict(doc).request
    )


@pytest.fixture(scope="module")
def envs():
    on = EvaluationEnvironmentBuilder(backend="jax").build(parse_all(POLICIES))
    off = EvaluationEnvironmentBuilder(
        backend="jax", verdict_cache_size=0
    ).build(parse_all(POLICIES))
    yield {"on": on, "off": off}
    on.close()
    off.close()


def dup_heavy_batch(n: int) -> list[tuple[str, ValidateRequest]]:
    """n rows over 6 distinct (policy, document) combinations, every row
    with a FRESH uid — the realistic admission stream shape (same pod
    template re-admitted; the API server mints a new uid each time)."""
    items = []
    for k in range(n):
        pid = ["priv", "ns", "grp"][k % 3]
        ns = "blocked" if k % 6 >= 3 else "fine"
        items.append((pid, pod_request(ns, k % 2 == 0, uid=f"uid-{k}")))
    return items


def test_dedup_is_bit_exact_and_keeps_uids(envs):
    items = dup_heavy_batch(96)
    a = envs["on"].validate_batch(items)
    b = envs["off"].validate_batch(items)
    assert [r.to_dict() for r in a] == [r.to_dict() for r in b]
    for (_, req), resp in zip(items, a):
        assert resp.uid == req.uid()
    # the batch REALLY deduplicated (6 unique rows in 96)
    assert envs["on"].batch_dedup_hits > 0
    assert envs["off"].dedup_stats["cache_capacity"] == 0


def test_cross_batch_cache_hits_despite_fresh_uids(envs):
    env = envs["on"]
    base = env.validate_batch(dup_heavy_batch(24))
    s0 = env.dedup_stats
    again = env.validate_batch(dup_heavy_batch(24))  # same docs + uids
    s1 = env.dedup_stats
    # identical payload replays land in the BLOB tier (pre-encode); the
    # row tier exists for uid/name-varying duplicates
    assert s1["blob_cache_hits"] > s0["blob_cache_hits"]
    assert [r.to_dict() for r in again] == [r.to_dict() for r in base]


def test_blob_tier_skips_encode_row_tier_catches_uid_variants(envs):
    """The two-tier rationale: an EXACT replay (same blob) must be
    answered pre-encode by the blob tier; a uid-varying duplicate has a
    different blob but the identical packed row, so only the row tier
    can see through it — and it must, without re-dispatching."""
    env = envs["on"]
    env.reset_verdict_cache()
    seed = pod_request("fine", True, uid="seed")
    env.validate_batch([("priv", seed)])
    p0 = env.host_profile
    s0 = env.dedup_stats

    # exact replay: identical blob → blob-tier hit, encoder untouched
    env.validate_batch([("priv", pod_request("fine", True, uid="seed"))])
    p1 = env.host_profile
    s1 = env.dedup_stats
    assert s1["blob_cache_hits"] == s0["blob_cache_hits"] + 1
    assert p1["encode_rows"] == p0["encode_rows"]

    # fresh uid: different blob (blob tier misses), identical packed row
    # (row tier hits) — encoded but not re-dispatched
    env.validate_batch([("priv", pod_request("fine", True, uid="other"))])
    p2 = env.host_profile
    s2 = env.dedup_stats
    assert s2["cache_hits"] == s1["cache_hits"] + 1
    assert p2["encode_rows"] == p1["encode_rows"] + 1
    assert p2["dispatched_rows"] == p1["dispatched_rows"]


def test_host_fastpath_shares_the_cache(envs):
    env = envs["on"]
    req = pod_request("fine", True, uid="fp-1")
    direct = env.validate_batch([("priv", req)], prefer_host=True)
    h0 = env.dedup_stats["cache_hits"]
    req2 = pod_request("fine", True, uid="fp-2")  # same doc, fresh uid
    hit = env.validate_batch([("priv", req2)], prefer_host=True)
    assert env.dedup_stats["cache_hits"] > h0
    assert hit[0].allowed == direct[0].allowed
    assert hit[0].uid == "fp-2"
    # and the device path can answer from a fast-path-inserted entry
    dev = env.validate_batch([("priv", pod_request("fine", True, uid="fp-3"))])
    assert dev[0].allowed == direct[0].allowed
    assert dev[0].uid == "fp-3"


def test_mutating_policy_duplicates_each_get_their_patch():
    env = EvaluationEnvironmentBuilder(backend="jax").build(
        parse_all({
            "mut": {"module": "builtin://raw-mutation",
                    "allowedToMutate": True},
        })
    )
    try:
        reqs = [
            ValidateRequest.from_raw({"uid": f"m-{k}", "x": 1})
            for k in range(8)
        ]
        out = env.validate_batch([("mut", r) for r in reqs])
        for k, resp in enumerate(out):
            assert resp.uid == f"m-{k}"
            assert resp.patch is not None  # every duplicate materialized
        patches = {r.patch for r in out}
        assert len(patches) == 1  # identical docs -> identical patches
    finally:
        env.close()


def test_wasm_backed_verdicts_never_cached(tmp_path):
    """Groups with wasm members are excluded: their verdict bits come
    from the host engine (deadline-dependent), not the row bytes."""
    from policy_server_tpu.fetch.artifact import load_artifact
    from policy_server_tpu.policies import resolve_builtin
    from policy_server_tpu.policies.wasm_oracle import oracle_wasm

    wasm_path = tmp_path / "priv.wasm"
    wasm_path.write_bytes(oracle_wasm("pod-privileged"))
    wasm_module = load_artifact(wasm_path)

    def resolver(url):
        if url.endswith(".wasm"):
            return wasm_module
        builtin = resolve_builtin(url)
        assert builtin is not None, url
        return builtin

    env = EvaluationEnvironmentBuilder(
        backend="jax", module_resolver=resolver
    ).build(
        parse_all({
            "wg": {
                "expression": "w() || p()",
                "message": "nope",
                "policies": {
                    "w": {"module": "file:///priv.wasm"},
                    "p": {"module": "builtin://pod-privileged"},
                },
            },
        })
    )
    try:
        items = [
            ("wg", pod_request("fine", False, uid=f"w-{k}")) for k in range(8)
        ]
        out = env.validate_batch(items)
        assert all(r.allowed for r in out), [r.to_dict() for r in out]
        # nothing was deduped or cached for the wasm-involving target
        assert env.dedup_stats["cache_entries"] == 0
        assert env.batch_dedup_hits == 0
    finally:
        env.close()


def test_lru_eviction_bounds_bytes():
    """Capacity is BYTES (round 6): inserting past the budget evicts
    oldest-first, newest entries survive, and the resident-byte gauge
    stays at or under the budget."""
    one = entry_cost(("p", bytes([0])), {"v": 0})
    c = VerdictCache(4 * one)
    for k in range(10):
        c.put(("p", bytes([k])), {"v": k})
    assert len(c) == 4
    assert c.bytes_used <= c.capacity_bytes
    assert c.get(("p", bytes([9])))["v"] == 9
    assert c.get(("p", bytes([0]))) is None


def test_get_many_put_many_batched_lock_semantics():
    c = VerdictCache(1 << 20)
    c.put_many([(("p", b"a"), {"v": 1}), (("p", b"b"), {"v": 2})])
    out = c.get_many([("p", b"a"), None, ("p", b"missing"), ("p", b"b")])
    assert out[0]["v"] == 1 and out[3]["v"] == 2
    assert out[1] is None and out[2] is None
    # None keys (uncacheable rows) are alignment placeholders, not misses
    assert c.hits == 2 and c.misses == 1


def test_default_cache_size_is_working_set_scale():
    """The round-5 default (4,096 rows) was smaller than the benchmark's
    own 12,500-template working set; the byte default must comfortably
    hold that working set in both tiers (an entry was ~6 KB then; since
    PR 32 it is ~1.4 KB, see the packed-row cases below)."""
    assert DEFAULT_VERDICT_CACHE_SIZE >= 2 * 12_500 * 6_000


def test_default_cache_size_is_on():
    assert DEFAULT_VERDICT_CACHE_SIZE > 0


# -- PR 32: a device-produced entry is the packed output row --------------------


def _wide_rules_resolver(url: str):
    """``builtin://wide-rules``: a policy of 300 rules, the last the only
    one that can fire. Its rule index (299) does not fit the uint8 wire
    form, so an environment that loads it returns int32 rows
    (``_compact_outputs`` false)."""
    from policy_server_tpu.ops.compiler import PolicyProgram, Rule
    from policy_server_tpu.ops.ir import false
    from policy_server_tpu.policies import resolve_builtin
    from policy_server_tpu.policies.base import BuiltinPolicy

    if url != "builtin://wide-rules":
        return resolve_builtin(url)
    last = resolve_builtin("builtin://pod-privileged").build({}).rules[0]

    class WideRules(BuiltinPolicy):
        name = "wide-rules"

        def build(self, settings):
            never = tuple(
                Rule(f"never-{k}", false(), "unreachable") for k in range(299)
            )
            return PolicyProgram(rules=never + (last,))

    return WideRules()


@pytest.fixture(scope="module")
def wide_env():
    env = EvaluationEnvironmentBuilder(
        backend="jax", module_resolver=_wide_rules_resolver
    ).build(parse_all({**POLICIES, "wide": {"module": "builtin://wide-rules"}}))
    yield env
    env.close()


def _fetched(env, requests: list[ValidateRequest]) -> np.ndarray:
    """The array the device returns for these requests, one row each."""
    schema = env.schemas[0]
    blobs = [r.payload_json() for r in requests]
    features, status, _ = schema.native.encode_batch(
        blobs, env.bucket_for(len(blobs)), env.table
    )
    assert not np.asarray(status)[: len(blobs)].any()
    return np.asarray(env._device_fetch(env._dispatch_features(features)))


# allowed everywhere; rejected by pod-privileged's rule (and by it the
# group, member b); rejected by namespace-validate's rule as well
ROW_KINDS = {
    "allowed": pod_request("fine", False),
    "rejected-by-a-rule-and-the-group": pod_request("fine", True),
    "rejected-by-every-policy": pod_request("blocked", True),
}


@pytest.mark.parametrize("kind", list(ROW_KINDS))
@pytest.mark.parametrize("form", ["uint8", "int32"])
def test_the_packed_rows_face_reads_what_the_batch_decoder_reads(
    envs, wide_env, form, kind
):
    """One layout, one decoder: every output key of a packed row reads,
    value and Python type, as the column ``_unpack`` gives the
    materializers of dispatched rows, in both wire forms."""
    env = envs["on"] if form == "uint8" else wide_env
    assert env._compact_outputs == (form == "uint8")
    raw = _fetched(env, list(ROW_KINDS.values()))
    assert raw.dtype == (np.uint8 if form == "uint8" else np.int32)
    slot = list(ROW_KINDS).index(kind)
    columns = env._unpack(raw)
    face = PackedRow(env._out_layout, raw[slot].tobytes())
    assert set(columns) == set(env._out_layout.index)
    assert len(columns) == raw.shape[1]  # no element unread, none twice
    for key, column in columns.items():
        want = column[slot].item()  # what extract_row used to store
        assert face[key] == want and type(face[key]) is type(want), key
        assert face.get(key, "absent") == want
        assert type(want) is (int if key.endswith(":rule") else bool)
    assert face.get("wm:grp/a:mutated", False) is False  # no such key
    # the verdicts this row kind was built for, rule sentinel included
    allowed = face["p:priv:allowed"]
    assert allowed == (kind == "allowed")
    assert face["p:priv:rule"] == (-1 if allowed else 0)
    assert face["g:grp:allowed"] == allowed
    assert face["g:grp:eval:a"] is True and face["g:grp:eval:b"] is True
    assert face["p:ns:rule"] == (0 if kind == "rejected-by-every-policy" else -1)
    if form == "int32":
        assert face["p:wide:rule"] == (-1 if allowed else 299)


def test_a_rule_index_of_254_is_not_the_sentinel():
    layout = OutputLayout({"p:x:allowed": (0, False), "p:x:rule": (1, True)}, True)
    assert PackedRow(layout, bytes([0, 254]))["p:x:rule"] == 254
    assert PackedRow(layout, bytes([1, 255]))["p:x:rule"] == -1
    wide = OutputLayout(layout.index, False)
    row = np.array([0, 255], np.int32).tobytes()
    assert PackedRow(wide, row)["p:x:rule"] == 255  # int32 rows wrap nothing
    assert PackedRow(wide, np.array([1, -1], np.int32).tobytes())["p:x:rule"] == -1


@pytest.mark.parametrize("form", ["uint8", "int32"])
def test_device_entries_are_the_fetched_bytes_and_hits_answer_from_them(
    envs, wide_env, form
):
    """What the device path puts is ``bytes``, one object under both
    tiers' keys; row-tier and blob-tier hits on it (fragment lane off:
    ``_materialize`` over the packed row) answer as the cache-off
    environment does, for a single policy and for a group."""
    env = envs["on"] if form == "uint8" else wide_env
    env.reset_verdict_cache()
    ids = ["priv", "ns", "grp"] + (["wide"] if form == "int32" else [])
    first = [(pid, pod_request("blocked", True, uid=f"a-{pid}")) for pid in ids]
    want = [r.to_dict() for r in envs["off"].validate_batch(first)] if (
        form == "uint8") else None
    got = env.validate_batch(first)
    if want is not None:
        assert [r.to_dict() for r in got] == want
    rows = {id(v) for v in env._tiers.row._data.values()}
    blob_rows = {id(v) for v in env._tiers.blob._data.values()}
    assert all(type(v) is bytes for v in env._tiers.row._data.values())
    assert len(rows) == 1 and rows == blob_rows  # one dispatched row, shared
    s0 = env.dedup_stats
    again = env.validate_batch(  # fresh uids: row tier
        [(pid, pod_request("blocked", True, uid=f"b-{pid}")) for pid in ids])
    replay = env.validate_batch(first)  # the same bytes: blob tier
    s1 = env.dedup_stats
    # (the blob tier learns ONE payload a dispatched slot, so of the
    # replay one request hits it and the others the row tier)
    moved = {k: s1[k] - s0[k] for k in ("cache_hits", "blob_cache_hits")}
    assert sum(moved.values()) == 2 * len(ids) and moved["blob_cache_hits"] >= 1
    for a, b, c in zip(got, again, replay):
        assert not a.allowed
        assert c.to_dict() == a.to_dict()
        assert {**b.to_dict(), "uid": a.uid} == a.to_dict()
    if form == "int32":
        assert again[3].status.message == "Privileged container is not allowed"


def test_host_and_device_paths_share_a_key_in_both_directions(envs):
    """A dict row the host fast path put answers the device path, and a
    packed row the device path put answers the host fast path: the same
    keys, two forms of row under one VerdictCache, both exact."""
    env, off = envs["on"], envs["off"]
    for pid in ("priv", "grp", "ns"):
        want = off.validate_batch([(pid, pod_request("blocked", True, uid="x"))])[0]
        # host put -> device-path hit
        env.reset_verdict_cache()
        env.validate_batch([(pid, pod_request("blocked", True, uid="h-1"))],
                           prefer_host=True)
        assert all(isinstance(v, dict) for v in env._tiers.row._data.values())
        p0, s0 = env.host_profile, env.dedup_stats
        dev = env.validate_batch([(pid, pod_request("blocked", True, uid="x"))])[0]
        assert env.host_profile["dispatched_rows"] == p0["dispatched_rows"]
        assert env.dedup_stats["cache_hits"] == s0["cache_hits"] + 1
        assert dev.to_dict() == want.to_dict()
        # device put -> host-path hit (row tier, then blob tier)
        env.reset_verdict_cache()
        env.validate_batch([(pid, pod_request("blocked", True, uid="d-1"))])
        assert all(type(v) is bytes for v in env._tiers.row._data.values())
        s0 = env.dedup_stats
        fast = env.validate_batch(
            [(pid, pod_request("blocked", True, uid="x"))], prefer_host=True)[0]
        exact = env.validate_batch(
            [(pid, pod_request("blocked", True, uid="d-1"))], prefer_host=True)[0]
        s1 = env.dedup_stats
        assert s1["cache_hits"] == s0["cache_hits"] + 1
        assert s1["blob_cache_hits"] == s0["blob_cache_hits"] + 1
        assert s1["cache_puts"] == s0["cache_puts"]  # nothing evaluated again
        assert fast.to_dict() == want.to_dict()
        assert {**exact.to_dict(), "uid": "x"} == want.to_dict()


def test_fragment_templates_live_in_the_environment_not_on_the_rows(envs):
    """The hit lane's templates: one per (target, the target's own slice
    of the row), whatever cached row the hit came from and in whichever
    form, and no cached row is written to."""
    env = envs["on"]
    env.reset_verdict_cache()
    env._frag_lanes.clear()
    target = env._fast_target("priv")
    env.validate_batch([("priv", pod_request("fine", True, uid="d"))])
    env.validate_batch([("ns", pod_request("blocked", True, uid="d"))])
    (row_a,) = [v for k, v in env._tiers.row._data.items() if k[0] == ("p", "priv")]
    (row_b,) = [v for k, v in env._tiers.row._data.items() if k[0] == ("p", "ns")]
    assert row_a != row_b  # two dispatched rows, the same verdict of priv
    tmpl = env._frag_of(target, row_a)
    assert tmpl is not None and tmpl.allowed is False
    assert tmpl.message == "Privileged container is not allowed"
    assert env._frag_of(target, row_b) is tmpl  # one lookup, no rebuild
    assert type(row_a) is bytes and type(row_b) is bytes
    # the host oracle's dict row of the same verdict: its own memo, an
    # equal template, and the dict is left as it was put
    host_row = env._oracle_outputs_for(target, pod_request("fine", True).payload())
    before = dict(host_row)
    host_tmpl = env._frag_of(target, host_row)
    assert host_row == before
    assert (host_tmpl.allowed, host_tmpl.code, host_tmpl.message) == (
        tmpl.allowed, tmpl.code, tmpl.message)
    assert env._frag_of(target, dict(before)) is host_tmpl
    # a target that is not eligible (dynamic message) has no lane
    assert env._frag_of(env._fast_target("ns"), row_b) is None
    assert env._frag_lanes[id(env._fast_target("ns"))] is False


KEY_BYTES, ROW_BYTES = 1064, 80  # flagship32: the packed row key, the output row


def test_a_packed_entry_costs_its_bytes_and_no_more_work():
    """The accounted cost of a packed entry is the constant plus the key's
    and the row's bytes, whatever the row holds; ``put_many`` asks the row
    for nothing but its length."""

    class Opaque(bytes):
        """A packed row that fails on any look inside it."""

        def __iter__(self):
            raise AssertionError("put_many iterated a packed row")

        def __getitem__(self, at):
            raise AssertionError("put_many indexed a packed row")

    ckey = ("p", "some-policy")
    rows = [Opaque(os.urandom(ROW_BYTES)) for _ in range(64)]
    pairs = [((ckey, os.urandom(KEY_BYTES)), row) for row in rows]
    cost = _ENTRY_OVERHEAD + KEY_BYTES + ROW_BYTES
    assert cost == 1400
    assert all(entry_cost(key, row) == cost for key, row in pairs)
    cache = VerdictCache(128 * 1024 * 1024)
    cache.put_many(pairs)
    stats = cache.stats()
    assert stats["cache_bytes"] == stats["cache_put_bytes"] == 64 * cost
    assert stats["cache_puts"] == stats["cache_entries"] == 64
    cache.put_many(pairs[:8])  # a live key put again: counted, not resident twice
    stats = cache.stats()
    assert (stats["cache_puts"], stats["cache_put_bytes"]) == (72, 72 * cost)
    assert (stats["cache_entries"], stats["cache_bytes"]) == (64, 64 * cost)
    # each tier's half of the default holds at least 90,000 such entries
    assert (DEFAULT_VERDICT_CACHE_SIZE // 2) // cost >= 90_000


def test_the_byte_bound_evicts_packed_and_dict_rows_by_their_own_cost():
    """Costs are not stored: an entry that leaves gives back what it took,
    so a tier of both forms returns to zero bytes and never exceeds its
    bound."""
    packed = entry_cost((("p", "x"), b"k" * 100), b"r" * 80)
    host = entry_cost((("p", "x"), b"k" * 100), {"a": True, "b": -1})
    assert (packed, host) == (256 + 100 + 80, 256 + 100 + 160)
    cache = VerdictCache(3 * host)
    for n in range(40):
        row = b"r" * 80 if n % 2 else {"a": True, "b": -1}
        cache.put((("p", "x"), b"%0100d" % n), row)
        assert cache.bytes_used <= cache.capacity_bytes
    stats = cache.stats()
    assert stats["cache_evictions"] == 40 - stats["cache_entries"] > 30
    live = list(cache._data.items())
    assert stats["cache_bytes"] == sum(entry_cost(k, v) for k, v in live)
    cache.clear()
    assert cache.bytes_used == 0


def test_the_accounted_bytes_are_not_under_the_resident_bytes():
    """The bound is honest: for entries of this configuration's sizes
    (one shared target tuple, a fresh key and a fresh row each, as the
    device path puts them) CPython keeps no more than the cache accounts."""
    n = 20_000
    ckey = ("p", "some-policy")
    cache = VerdictCache(1 << 30)
    gc.collect()
    # a server booted with pprof in this process keeps tracing for its
    # heap profile: leave the tracer as it was found
    was_tracing = tracemalloc.is_tracing()
    if not was_tracing:
        tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        for lo in range(0, n, 100):
            cache.put_many(
                ((ckey, os.urandom(KEY_BYTES)), os.urandom(ROW_BYTES))
                for _ in range(100)
            )
        resident = tracemalloc.get_traced_memory()[0] - base
    finally:
        if not was_tracing:
            tracemalloc.stop()
    accounted = cache.bytes_used
    assert accounted == n * (_ENTRY_OVERHEAD + KEY_BYTES + ROW_BYTES)
    assert resident <= accounted, (resident / n, accounted / n)
    assert resident > 0.9 * accounted  # and not far over either


# -- the tiers' plan of one chunk: no environment, no jax ---------------------

PLAN_WIDTH = 12
# per scenario: the row "shape" at each chunk position (equal shape, equal
# packed bytes), which positions failed to encode, which carry wasm bits,
# and which shapes the row tier already holds (for every target)
PLAN_SCENARIOS = {
    "nothing-cached": ([0, 1, 2, 3, 4, 5, 6, 7], (), (), ()),
    "all-cached": ([0, 1, 2, 3, 4, 5, 6, 7], (), (), range(8)),
    "some-cached": ([0, 1, 2, 3, 4, 5, 6, 7], (), (), (1, 2, 6)),
    "in-chunk-duplicates": ([0, 0, 1, 1, 0, 2, 2, 3, 1], (), (), ()),
    "duplicates-of-a-cached-row": ([0, 0, 0, 1, 1, 2, 0, 3, 3], (), (), (0,)),
    "wasm-beside-plain": ([0, 1, 1, 2, 0, 3, 3, 4], (), (1, 5), (2,)),
    "overflowed-in-the-ok-mask": ([0, 1, 2, 3, 4, 5, 6, 7], (2, 6), (), ()),
}


def _plan_inputs(scenario: str, targets: str):
    shapes, failed, wasm_pos, held = PLAN_SCENARIOS[scenario]
    n = len(shapes)
    packed = np.zeros((16, PLAN_WIDTH), np.uint8)
    for pos, shape in enumerate(shapes):
        packed[pos] = shape + 1
        packed[pos, 0] = 200  # rows differ in ONE byte, not in all of them
    ok_mask = np.array([pos not in failed for pos in range(n)])
    target_keys = (
        [("p", "only")] if targets == "uniform"
        else [("p", "a"), ("g", "b"), ("p", "c")]
    )
    target_ids = np.arange(n, dtype=np.intp) % len(target_keys)
    blobs = [b"blob-%d" % pos for pos in range(n)]
    tiers = DedupTiers(1 << 20)
    for tkey in target_keys:
        for shape in held:
            row = np.full(PLAN_WIDTH, shape + 1, np.uint8)
            row[0] = 200
            tiers.row.put((tkey, row.tobytes()), b"held-%s-%d" % (
                tkey[1].encode(), shape))
    return (packed, ok_mask, list(wasm_pos), target_ids, target_keys, blobs,
            tiers)


def _shipped(plan, packed):
    """What the dispatch loop ships for a plan: the encode buffer, or the
    plan's positions copied into a zeroed bucket."""
    if plan.ship_pos is None:
        return packed
    rows = np.zeros((max(4, 2 * plan.n_rows), packed.shape[1]), packed.dtype)
    rows[: plan.n_rows] = packed[plan.ship_pos]
    return rows


@pytest.mark.parametrize("targets", ["uniform", "mixed"])
@pytest.mark.parametrize("scenario", list(PLAN_SCENARIOS))
def test_the_plan_of_a_chunk_against_a_per_row_reference(scenario, targets):
    """``DedupTiers.plan`` against a plain per-row dict walk of the same
    chunk: every admitted row is answered exactly once (a hit, its own
    slot, or another row's), slots index shipped rows that carry the
    row's bytes, each tier learns each missed key once, the counters
    move by ROWS, and the encode buffer ships as it is exactly when
    nothing collapsed."""
    packed, ok_mask, wasm_pos, tids, tkeys, blobs, tiers = _plan_inputs(
        scenario, targets)
    before, blob_puts0 = tiers.stats(), tiers.blob.puts
    held = dict(tiers.row._data)

    # the reference: one row at a time, a dict and a list of shipped rows
    want_hits: dict[int, bytes] = {}
    want_miss: dict[int, tuple] = {}   # position -> its (target key, bytes)
    for pos in np.flatnonzero(ok_mask).tolist():
        if pos in wasm_pos:
            continue
        key = (tkeys[tids[pos]], packed[pos].tobytes())
        if key in held:
            want_hits[pos] = held[key]
        else:
            want_miss[pos] = key
    missed_rows = {key[1] for key in want_miss.values()}
    missed_keys = set(want_miss.values())
    collapsed = bool(
        want_hits or wasm_pos or not ok_mask.all()
        or len(missed_rows) < len(want_miss))

    plan = tiers.plan(packed, ok_mask, wasm_pos, tids, tkeys, blobs)
    shipped = _shipped(plan, packed)

    # answered exactly once, by the source the reference names
    assert dict(plan.hits) == want_hits and len(plan.hits) == len(want_hits)
    assert all(got is want_hits[pos] for pos, got in plan.hits)
    rode = [pos for _slot, pos in plan.slot_rows]
    assert sorted(rode) == sorted([*want_miss, *wasm_pos])
    assert sorted([*rode, *want_hits]) == np.flatnonzero(ok_mask).tolist()
    # a slot carries its row's bytes; distinct missed rows, a slot each,
    # a wasm row a slot of its own
    for slot, pos in plan.slot_rows:
        assert shipped[slot].tobytes() == packed[pos].tobytes()
    slots = {slot for slot, _pos in plan.slot_rows}
    assert len(slots) == plan.n_rows == len(missed_rows) + len(wasm_pos)
    wasm_slots = {slot for slot, pos in plan.slot_rows if pos in wasm_pos}
    assert len(wasm_slots) == len(wasm_pos)
    assert not wasm_slots & {
        slot for slot, pos in plan.slot_rows if pos not in wasm_pos}
    # the short cut: the encode buffer itself, slots its positions
    if plan.slot_rows:
        assert (plan.ship_pos is None) == (not collapsed)
    if plan.ship_pos is None:
        assert shipped is packed
        assert all(slot == pos for slot, pos in plan.slot_rows)
    elif plan.slot_rows:
        assert slots == set(range(plan.n_rows))  # compacted: dense slots
        assert len(plan.ship_pos) == plan.n_rows
    # what the dispatch will teach: each missed key once, on the slot
    # that carries its bytes; one representative blob a non-wasm slot
    assert sorted(key for key, _slot in plan.row_puts) == sorted(missed_keys)
    for (_tkey, row_bytes), slot in plan.row_puts:
        assert shipped[slot].tobytes() == row_bytes
    blob_slots = [slot for _key, slot in plan.blob_puts]
    assert sorted(blob_slots) == sorted(slots - wasm_slots)
    for (tkey, blob), slot in plan.blob_puts:
        pos = blobs.index(blob)
        assert (slot, pos) in plan.slot_rows and tkey == tkeys[tids[pos]]
    # the counters count rows; a hit combo back-fills ONE blob
    after = tiers.stats()
    assert after["cache_hits"] - before["cache_hits"] == len(want_hits)
    assert after["cache_misses"] - before["cache_misses"] == len(want_miss)
    assert after["batch_dup_hits"] - before["batch_dup_hits"] == (
        len(want_miss) - len(missed_rows))
    assert tiers.batch_dup_hits == after["batch_dup_hits"]
    hit_combos = {(tids[pos], packed[pos].tobytes()) for pos in want_hits}
    assert tiers.blob.puts - blob_puts0 == len(hit_combos)

    # learn, then the same chunk again: every plain row is a hit of the
    # row its slot fetched, both tiers hold the one object
    fetched = np.arange(shipped.shape[0] * 5, dtype=np.uint8).reshape(-1, 5)
    tiers.learn(plan, fetched)
    again = tiers.plan(packed, ok_mask, wasm_pos, tids, tkeys, blobs)
    assert sorted(pos for pos, _row in again.hits) == sorted(
        [*want_hits, *want_miss])
    assert [pos for _slot, pos in again.slot_rows] == wasm_pos
    assert not again.row_puts and not again.blob_puts
    slot_of = {pos: slot for slot, pos in plan.slot_rows}
    for pos, row in again.hits:
        if pos in want_miss:
            assert row == fetched[slot_of[pos]].tobytes()
    for (tkey, blob), slot in plan.blob_puts:
        pos = blobs.index(blob)
        assert tiers.blob._data[(tkey, blob)] is tiers.row._data[
            (tkey, packed[pos].tobytes())]


@pytest.mark.parametrize("scenario", list(PLAN_SCENARIOS))
def test_without_tiers_every_admitted_row_rides_its_own_position(scenario):
    """The form the dispatch loop gets with caching off: the same record,
    the encode buffer as it is, nothing to learn."""
    _packed, ok_mask, *_rest = _plan_inputs(scenario, "uniform")
    plan = DedupTiers.passthrough(ok_mask)
    admitted = np.flatnonzero(ok_mask).tolist()
    assert plan.ship_pos is None and plan.n_rows == len(admitted)
    assert plan.slot_rows == [(pos, pos) for pos in admitted]
    assert plan.hits == plan.row_puts == plan.blob_puts == []


def test_the_host_fast_path_rule_blob_first_and_no_blob_backfill():
    """``get_one`` / ``put_one``: the blob tier is asked first and the row
    key is only computed on a blob miss; a row-tier hit back-fills no
    blob; a miss files the evaluated row under both keys; where the row
    key cannot be had the blob tier alone learns."""
    tiers = DedupTiers(1 << 20)
    encodes: list[bytes] = []

    def packed_row_of(blob: bytes) -> bytes:
        encodes.append(blob)
        return b"row-of-" + blob[:4]

    tkey = ("p", "x")
    row, keys = tiers.get_one(tkey, b"podA-uid1", packed_row_of)
    assert row is None and keys == (
        (tkey, b"row-of-podA"), (tkey, b"podA-uid1"))
    tiers.put_one(keys, {"v": 1})
    assert tiers.get_one(tkey, b"podA-uid1", packed_row_of) == ({"v": 1}, None)
    assert encodes == [b"podA-uid1"]  # the replay paid no encode
    blob_entries = len(tiers.blob)
    row, keys = tiers.get_one(tkey, b"podA-uid2", packed_row_of)
    assert row == {"v": 1} and keys is None  # the uid variant: row tier
    assert len(tiers.blob) == blob_entries
    row, keys = tiers.get_one(tkey, b"podB-uid3", lambda blob: None)
    assert row is None and keys == (None, (tkey, b"podB-uid3"))
    tiers.put_one(keys, {"v": 2})
    assert len(tiers.row) == 1 and len(tiers.blob) == blob_entries + 1
    stats = tiers.stats()
    assert (stats["cache_hits"], stats["blob_cache_hits"]) == (1, 1)
    assert stats.keys() == DedupTiers.stats_when_off().keys()
    assert not any(DedupTiers.stats_when_off().values())
    tiers.clear()
    assert len(tiers.row) == len(tiers.blob) == 0
    assert tiers.stats()["cache_hits"] == 1  # counters are cumulative
