"""Native encoder differential tests: the C++ encoder (csrc/fastenc.cpp)
must be bit-exact vs the Python trie encoder on every feature array, across
the synthetic firehose, unicode/escape torture, overflow routing, and the
batch API — and whether its mirror of the intern table answered a string
or Python did (cold, warm, mixed, concurrent, at its cap). Skipped when no
C++ toolchain is available."""

from __future__ import annotations

import copy
import ctypes
import sys
import threading

import numpy as np
import pytest

# flagship_policies() builds signature-capability policies that need
# cryptography at runtime; dependency-light containers skip the module
pytest.importorskip("cryptography")

from policy_server_tpu.evaluation.environment import EvaluationEnvironmentBuilder
from policy_server_tpu.models import AdmissionReviewRequest, ValidateRequest
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.ops import fastenc
from policy_server_tpu.ops.codec import PACKED_KEY
from policy_server_tpu.policies.flagship import flagship_policies, synthetic_firehose

pytestmark = pytest.mark.skipif(
    not fastenc.native_available(), reason="native encoder unavailable"
)


@pytest.fixture(scope="module")
def env():
    return EvaluationEnvironmentBuilder(backend="jax").build(flagship_policies())


def to_request(doc: dict) -> ValidateRequest:
    return ValidateRequest.from_admission(
        AdmissionReviewRequest.from_dict(doc).request
    )


def assert_encodings_equal(schema, table, payload) -> None:
    py = schema.encode(payload, table)
    nat = schema.native.encode(payload, table)
    assert py.keys() == nat.keys()
    for k in py:
        assert np.array_equal(py[k], nat[k]), k


def test_differential_firehose(env):
    schema = env.schemas[0]
    for doc in synthetic_firehose(200, seed=9):
        assert_encodings_equal(schema, env.table, to_request(doc).payload())


def test_differential_unicode_and_escapes(env):
    doc = synthetic_firehose(1, seed=1)[0]
    doc["request"]["object"]["metadata"]["labels"] = {
        "app": "café-☃️",
        'quote"key': "line1\nline2\tend \U0001f600",
        "backslash\\key": "nul ctrl",
    }
    doc["request"]["object"]["metadata"]["annotations"] = {
        "prod.example.com/debug": "true"
    }
    for schema in env.schemas:
        assert_encodings_equal(schema, env.table, to_request(doc).payload())


def test_differential_type_mismatches(env):
    doc = synthetic_firehose(1, seed=2)[0]
    pod = doc["request"]["object"]
    # wrong-typed leaves must read as missing on both paths
    pod["spec"]["containers"][0]["image"] = 42
    pod["spec"]["hostNetwork"] = "yes"
    pod["spec"]["containers"][0]["securityContext"] = {"privileged": "true"}
    pod["metadata"]["labels"] = None
    for schema in env.schemas:
        assert_encodings_equal(schema, env.table, to_request(doc).payload())


def test_batch_api_matches_single(env):
    schema = env.schemas[0]
    docs = synthetic_firehose(17, seed=5)
    blobs = [to_request(d).payload_json() for d in docs]
    packed, status, _ = schema.native.encode_batch(blobs, 32, env.table)
    assert (status == 0).all()
    batch = schema.unpack_host(packed)
    for row, d in enumerate(docs):
        single = schema.native.encode(to_request(d).payload(), env.table)
        for k, arr in single.items():
            assert np.array_equal(batch[k][row], arr), k


def test_batch_overflow_rows_flagged_and_zeroed(env):
    schema = env.schemas[0]  # caps 8/4
    ok_doc = synthetic_firehose(1, seed=6)[0]
    big_doc = synthetic_firehose(1, seed=7)[0]
    big_doc["request"]["object"]["spec"]["containers"] = [
        {"name": f"c{i}", "image": "nginx"} for i in range(12)  # > cap 8
    ]
    blobs = [to_request(ok_doc).payload_json(), to_request(big_doc).payload_json()]
    packed, status, _ = schema.native.encode_batch(blobs, 2, env.table)
    assert status[0] == 0 and status[1] < 0
    # the failed row must read all-missing
    for k, arr in schema.unpack_host(packed).items():
        if arr.ndim >= 1 and arr.shape[0] == 2:
            assert not arr[1].any(), k


def test_native_verdicts_match_oracle(env):
    """End-to-end: native-encoded device verdicts == host oracle verdicts."""
    oracle_env = EvaluationEnvironmentBuilder(backend="oracle").build(
        flagship_policies()
    )
    docs = synthetic_firehose(64, seed=8)
    items = [("pod-security-group", to_request(d)) for d in docs]
    jax_results = env.validate_batch(items)
    oracle_results = oracle_env.validate_batch(
        [("pod-security-group", to_request(d)) for d in docs]
    )
    for a, b in zip(jax_results, oracle_results):
        assert a.to_dict() == b.to_dict()


def test_out_of_range_int_routes_to_oracle(tmp_path):
    """Regression (fail-open): an int that doesn't fit int32 must not
    truncate or read as missing — both encoders fail the encode and the
    environment answers via the oracle, matching oracle semantics."""
    import json

    from policy_server_tpu.config.config import Config
    from policy_server_tpu.fetch import dump_artifact, make_module_resolver
    from policy_server_tpu.ops import ir
    from policy_server_tpu.ops.codec import SchemaOverflow
    from policy_server_tpu.ops.compiler import Rule
    from policy_server_tpu.ops.ir import DType, Path as IRPath

    src = tmp_path / "cap.tpp.json"
    src.write_text(
        json.dumps(
            dump_artifact(
                "replica-cap",
                [
                    Rule(
                        "cap",
                        ir.gt(IRPath("object.spec.replicas", DType.I32), 3),
                        "too many replicas",
                    )
                ],
            )
        )
    )
    policies = {
        "replica-cap": parse_policy_entry(
            "replica-cap", {"module": f"file://{src}"}
        )
    }
    config = Config(policies=policies, policies_download_dir=str(tmp_path / "s"))
    jax_env = EvaluationEnvironmentBuilder(
        backend="jax", module_resolver=make_module_resolver(config)
    ).build(policies)

    doc = synthetic_firehose(1, seed=3)[0]
    doc["request"]["object"]["spec"] = {"replicas": 2**33}  # >> int32
    req = to_request(doc)

    # python encoder refuses
    with pytest.raises(SchemaOverflow):
        jax_env.schemas[-1].encode(req.payload(), jax_env.table)
    # native batch flags the row
    _, status, _ = jax_env.schemas[-1].native.encode_batch(
        [req.payload_json()], 1, jax_env.table
    )
    assert status[0] != 0
    # end to end: verdict comes from the oracle and REJECTS (2**33 > 3)
    before = jax_env.oracle_fallbacks
    resp = jax_env.validate_batch([("replica-cap", req)])[0]
    assert not resp.allowed and resp.status.message == "too many replicas"
    assert jax_env.oracle_fallbacks == before + 1


# ---------------------------------------------------------------------------
# The mirror of the intern table (PR 34): a string the encoder handle has
# seen is written as its id and predicate bits by the native call itself.
# ---------------------------------------------------------------------------


def _torture_doc(seed: int) -> dict:
    """A review whose string columns carry escapes and non-ASCII, in id
    columns (namespace, label and annotation keys and values,
    capabilities, procMount) and predicate columns (images, hostPath
    paths, the apparmor annotation prefix)."""
    doc = synthetic_firehose(1, seed=seed)[0]
    pod = doc["request"]["object"]
    doc["request"]["namespace"] = pod["metadata"]["namespace"] = "équipe-\"a\""
    pod["metadata"]["labels"] = {
        "app": "café-☃️",
        'quote"key': "line1\nline2\tend \U0001f600",
        "backslash\\key": "nul ctrl",
    }
    pod["metadata"]["annotations"] = {
        "container.apparmor.security.beta.kubernetes.io/c": "unconfined",
        "prod.example.com/dépôt": "true",
    }
    pod["spec"]["volumes"] = [
        {"name": "v0", "hostPath": {"path": "/var/log/\u00e9\t"}},
        {"name": "v1", "hostPath": {"path": "/tmp/x"}},
    ]
    pod["spec"]["containers"][0]["image"] = "docker.io/library/ngïnx:latest"
    pod["spec"]["containers"][0]["securityContext"] = {
        "procMount": "Unmasked",
        "capabilities": {"add": ["NET_ADMIN", "SYS_\u2603"]},
    }
    return doc


def _docs(n: int, seed: int) -> list[dict]:
    return synthetic_firehose(n, seed=seed) + [_torture_doc(seed)]


def _renamed(docs: list[dict], namespace: str, image: str) -> list[dict]:
    """The same reviews under a namespace and a first image the encoder
    has not met."""
    out = copy.deepcopy(docs)
    for doc in out:
        doc["request"]["namespace"] = namespace
        doc["request"]["object"]["metadata"]["namespace"] = namespace
        doc["request"]["object"]["spec"]["containers"][0]["image"] = image
    return out


def _blobs(docs: list[dict]) -> list[bytes]:
    return [to_request(d).payload_json() for d in docs]


def _trie_packed(schema, table, docs: list[dict], batch: int) -> np.ndarray:
    """The batch as the Python trie encodes it (the differential
    reference), in the packed layout."""
    encoded = [schema.encode(to_request(d).payload(), table) for d in docs]
    return schema.pack(schema.stack(encoded, batch_size=batch))[PACKED_KEY]


def _assert_exact(schema, table, enc, parent, docs: list[dict]) -> int:
    """``enc``'s packed buffer for ``docs`` is byte for byte the Python
    trie's and the parent path's (every string through _scatter_strings:
    an encoder with no table bound). → strings Python resolved."""
    batch = len(docs) + 3  # pad rows stay all-missing
    blobs = _blobs(docs)
    got, status, python_strings = enc.encode_batch(blobs, batch, table)
    assert not status.any()
    old, old_status, every_string = parent.encode_batch(blobs, batch, table)
    assert not old_status.any()
    assert python_strings <= every_string
    assert got[PACKED_KEY].tobytes() == old[PACKED_KEY].tobytes()
    assert (
        got[PACKED_KEY].tobytes()
        == _trie_packed(schema, table, docs, batch).tobytes()
    )
    return python_strings


@pytest.mark.parametrize("which", [0, -1], ids=["narrow", "wide"])
@pytest.mark.parametrize("state", ["cold", "warm", "mixed"])
def test_differential_against_trie_and_parent_path(env, which, state):
    schema = env.schemas[which]
    enc = fastenc.NativeEncoder(schema, env.table)  # an empty mirror
    parent = fastenc.NativeEncoder(schema)  # no mirror: the parent's path
    docs = _docs(12, seed=21)
    every_string = parent.encode_batch(_blobs(docs), 16, env.table)[2]
    assert every_string > 0 and enc.mirror_entries == 0
    # cold: the mirror is empty, every string leaf is a record
    assert _assert_exact(schema, env.table, enc, parent, docs) == every_string
    assert 0 < enc.mirror_entries < every_string  # strings repeat
    if state == "cold":
        return
    # warm: the same strings again come back with no record at all
    assert _assert_exact(schema, env.table, enc, parent, docs) == 0
    if state == "warm":
        return
    # mixed: a namespace and an image the mirror has not seen, mid-run
    held = enc.mirror_entries
    new = _renamed(docs, "ns-nouveau-\u00e9", "quay.io/new/image:latest")
    met = _assert_exact(schema, env.table, enc, parent, new)
    assert 0 < met < every_string
    assert enc.mirror_entries == held + 2
    assert _assert_exact(schema, env.table, enc, parent, new) == 0


def test_warm_batch_makes_no_python_pass_over_strings(env, monkeypatch):
    """Once every string is in the mirror an encode is the buffer, the
    blob arrays and one native call: neither _scatter_strings nor the
    per-array views exist on that path."""
    schema = env.schemas[0]
    enc = fastenc.NativeEncoder(schema, env.table)
    blobs = _blobs(_docs(8, seed=22))
    cold, _, met = enc.encode_batch(blobs, 16, env.table)
    assert met > 0

    def never(*args, **kwargs):
        raise AssertionError("a warm batch took the cold path")

    monkeypatch.setattr(enc, "_scatter_strings", never)
    monkeypatch.setattr(enc, "_learn", never)
    monkeypatch.setattr(schema, "packed_views", never)
    warm, status, met = enc.encode_batch(blobs, 16, env.table)
    assert met == 0 and not status.any()
    assert warm[PACKED_KEY].tobytes() == cold[PACKED_KEY].tobytes()


def test_a_table_the_mirror_is_not_bound_to_takes_the_cold_path(env):
    """An id means nothing outside the table that gave it: under another
    table every string is Python's, and nothing is published."""
    from policy_server_tpu.utils.interning import InternTable

    schema = env.schemas[0]
    enc = fastenc.NativeEncoder(schema, env.table)
    docs = _docs(4, seed=23)
    blobs = _blobs(docs)
    met = enc.encode_batch(blobs, 8, env.table)[2]
    held = enc.mirror_entries
    other = InternTable()
    other.intern("shifts every id by one")
    schema.register_preds(other)
    got, status, met_other = enc.encode_batch(blobs, 8, other)
    assert met_other == met and enc.mirror_entries == held
    assert not status.any()
    assert (
        got[PACKED_KEY].tobytes()
        == _trie_packed(schema, other, docs, 8).tobytes()
    )


def test_eight_threads_encode_while_strings_are_published(env):
    """Readers of the mirror take no lock and publishers take their own:
    eight threads on one handle, every batch bringing strings nobody has
    seen, every buffer exact."""
    schema = env.schemas[0]
    enc = fastenc.NativeEncoder(schema, env.table)
    parent = fastenc.NativeEncoder(schema)
    base = _docs(6, seed=24)
    rounds = 12
    work = [
        [
            _blobs(_renamed(base, f"ns-{t % 4}-{r}", f"r.example/{t}/{r}:v1"))
            for r in range(rounds)
        ]
        for t in range(8)
    ]
    failures: list[str] = []
    start = threading.Barrier(8)

    def run(t: int) -> None:
        try:
            start.wait(timeout=60)
            for r, blobs in enumerate(work[t]):
                # twice: the second pass reads what was just published
                for _ in range(2):
                    got, status, _ = enc.encode_batch(blobs, 8, env.table)
                    want, _, _ = parent.encode_batch(blobs, 8, env.table)
                    if status.any() or (
                        got[PACKED_KEY].tobytes() != want[PACKED_KEY].tobytes()
                    ):
                        failures.append(f"thread {t} round {r}")
        except Exception as e:  # a thread's failure must reach the test
            failures.append(f"thread {t}: {e!r}")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(t,)) for t in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    assert failures == []
    # threads t and t + 4 shared a namespace each round: published once
    assert enc.mirror_entries >= 8 * rounds + 4 * rounds
    for blobs in (work[0][0], work[7][rounds - 1]):
        assert enc.encode_batch(blobs, 8, env.table)[2] == 0


def test_mirror_at_its_cap_publishes_nothing_more_and_stays_exact():
    """The Python table is unbounded; the mirror is not. Past its cap a
    string it has not seen is Python's every time, exactly."""
    small = EvaluationEnvironmentBuilder(backend="jax").build(
        {
            "priv": parse_policy_entry(
                "priv", {"module": "builtin://pod-privileged"}
            ),
            "latest": parse_policy_entry(
                "latest", {"module": "builtin://disallow-latest-tag"}
            ),
        }
    )
    try:
        schema, table = small.schemas[0], small.table
        enc = schema.native
        parent = fastenc.NativeEncoder(schema)
        offered = 40_000
        names = [b"filler-%07d" % i for i in range(offered)]
        arena = ctypes.create_string_buffer(b"".join(names))
        learned = {
            14 * i: (14, table.intern(name.decode()))
            for i, name in enumerate(names)
        }
        enc._learn(arena, learned, table)
        cap = enc.mirror_entries
        assert 0 < cap < offered and not enc._mirror_open
        docs = _renamed(_docs(4, seed=25), "ns-past-the-cap", "r.io/x:latest")
        first = _assert_exact(schema, table, enc, parent, docs)
        assert first > 0 and enc.mirror_entries == cap
        # not published: Python's again, and as exact
        assert _assert_exact(schema, table, enc, parent, docs) == first
        # what went in before the cap still answers from the mirror
        warm = _renamed(docs, "filler-0000007", "filler-0000008")
        assert _assert_exact(schema, table, enc, parent, warm) < first
    finally:
        small.close()


def test_string_met_under_one_predicate_then_another(env):
    """A string is published with its bit under every predicate of the
    encoder, so one first met as a hostPath path (the prefix predicates)
    is right when it later arrives as an image (suffix, regex, globs) and
    as an annotation key (an id column and a prefix predicate)."""
    schema = env.schemas[-1]
    enc = fastenc.NativeEncoder(schema, env.table)
    parent = fastenc.NativeEncoder(schema)
    word = "/tmp/registry.prod.example.com/x:latest"
    as_path = _torture_doc(26)
    as_path["request"]["object"]["spec"]["volumes"] = [
        {"name": "v", "hostPath": {"path": word}}
    ]
    assert _assert_exact(schema, env.table, enc, parent, [as_path]) > 0
    elsewhere = copy.deepcopy(as_path)
    pod = elsewhere["request"]["object"]
    pod["spec"]["containers"][0]["image"] = word
    pod["spec"]["initContainers"] = [{"name": "i", "image": word}]
    pod["metadata"]["annotations"] = {word: word}
    assert _assert_exact(schema, env.table, enc, parent, [elsewhere]) == 0


# -- the launch's host half, written by the encode call (PR 37) -----------


def _wire_forms(env, which: int, narrow: bool, packed: np.ndarray) -> dict:
    """The three kinds of wire form a launch can ship in, for one schema
    and id width: the all-elided one, the one a column set settles on
    after seeing ``packed``, and the dense one."""
    from policy_server_tpu.evaluation.environment import (
        _live_words,
        _PlaneColumns,
    )

    schema_idx = range(len(env.schemas))[which]
    layout = env._wire_layout(schema_idx, narrow)
    columns = _PlaneColumns(layout)
    columns.admit(_live_words(packed), env._select_delta_cols)
    assert columns.version and columns.form.width
    return {
        "elided": layout.elided, "settled": columns.form,
        "dense": layout.dense,
    }


@pytest.mark.parametrize("rows", [1, 4, 67, 128])
@pytest.mark.parametrize("kind", ["elided", "settled", "dense"])
@pytest.mark.parametrize("narrow", [True, False], ids=["u16", "i32"])
def test_the_native_wire_is_the_launchs_own_byte_for_byte(
    env, narrow, kind, rows
):
    """The wire buffer and the liveness words the encode call writes are
    what _WireForm.wire and _live_words make of the rows it wrote:
    narrow and full-width ids, every kind of form, a bucket with no
    padding (1, 4, 128 rows) and with it (67 of 128)."""
    from policy_server_tpu.evaluation.environment import (
        _live_words,
        bucket_size,
    )

    schema = env.schemas[0]
    enc = fastenc.NativeEncoder(schema, env.table)
    blobs = _blobs(synthetic_firehose(rows, seed=37))
    bucket = bucket_size(rows)
    cold, status, met = enc.encode_batch(blobs, bucket, env.table)
    assert met and not status.any()
    form = _wire_forms(env, 0, narrow, cold[PACKED_KEY])[kind]
    got, status, met = enc.encode_batch(
        blobs, bucket, env.table, form.gather
    )
    assert met == 0 and not status.any()
    packed = got[PACKED_KEY]
    assert packed.tobytes() == cold[PACKED_KEY].tobytes()
    wire, live = got[fastenc.WIRE_KEY], got[fastenc.LIVE_KEY]
    want = form.wire(packed)
    assert wire.flags.c_contiguous and wire.dtype == np.uint8
    assert wire.shape == want.shape == (bucket, form.width)
    assert wire.tobytes() == want.tobytes()
    assert live.dtype == np.uint32
    assert live.tobytes() == _live_words(packed).tobytes()
    assert not wire[rows:].any()


def test_a_batch_with_a_record_leaves_the_wire_to_the_launch(env):
    """A string the mirror has not seen comes back as a record, and
    Python rewrites id columns after the native call: such a batch
    carries neither words nor wire, whatever form it was told."""
    schema = env.schemas[0]
    enc = fastenc.NativeEncoder(schema, env.table)
    docs = synthetic_firehose(8, seed=38)
    warm = enc.encode_batch(_blobs(docs), 8, env.table)[0]
    form = _wire_forms(env, 0, True, warm[PACKED_KEY])["settled"]
    cold_docs = _renamed(docs, "ns-wire-37", "registry.example/wire:37")
    cold, _, met = enc.encode_batch(
        _blobs(cold_docs), 8, env.table, form.gather
    )
    assert met and fastenc.WIRE_KEY not in cold
    assert fastenc.LIVE_KEY not in cold
    # met once: the same batch again is the native call's, wire and all
    again, _, met = enc.encode_batch(
        _blobs(cold_docs), 8, env.table, form.gather
    )
    assert met == 0
    assert again[PACKED_KEY].tobytes() == cold[PACKED_KEY].tobytes()
    assert (
        again[fastenc.WIRE_KEY].tobytes()
        == form.wire(again[PACKED_KEY]).tobytes()
    )
    # and no form asked for, none written
    assert set(enc.encode_batch(_blobs(docs), 8, env.table)[0]) == {
        PACKED_KEY
    }


def test_the_native_wire_of_a_batch_with_a_failed_row(env):
    """A row that fails to parse is wiped to all-missing in the wide
    buffer before the wire is written: its wire row is zero and its bytes
    are in no liveness word."""
    from policy_server_tpu.evaluation.environment import _live_words

    schema = env.schemas[0]
    enc = fastenc.NativeEncoder(schema, env.table)
    blobs = _blobs(synthetic_firehose(5, seed=39))
    enc.encode_batch(blobs, 8, env.table)
    good = enc.encode_batch(blobs, 8, env.table)[0][PACKED_KEY]
    form = _wire_forms(env, 0, True, good)["settled"]
    blobs[2] = blobs[2][: len(blobs[2]) // 2]  # truncated JSON
    got, status, met = enc.encode_batch(blobs, 8, env.table, form.gather)
    assert met == 0 and status[2] < 0 and not status[[0, 1, 3, 4]].any()
    packed = got[PACKED_KEY]
    assert not packed[2].any() and not got[fastenc.WIRE_KEY][2].any()
    assert got[fastenc.WIRE_KEY].tobytes() == form.wire(packed).tobytes()
    assert got[fastenc.LIVE_KEY].tobytes() == _live_words(packed).tobytes()


@pytest.mark.parametrize("picked, rows", [(0, 1), (1, 1), (3, 4), (5, 8)])
def test_take_rows_is_the_launch_half_of_a_wide_copy(picked, rows):
    """Compacting what the encode call wrote: the picked rows of the wire
    at the head of a zeroed bucket, and the liveness words of the picked
    wide rows alone."""
    from policy_server_tpu.evaluation.environment import _live_words

    rng = np.random.default_rng(40)
    wire = rng.integers(0, 256, (16, 52), dtype=np.uint8)
    wide = rng.integers(0, 2, (16, 1064), dtype=np.uint8)
    pos = rng.permutation(16)[:picked].astype(np.intp)
    want = np.zeros((rows, 52), np.uint8)
    want[:picked] = wire[pos]
    got, live = fastenc.take_rows(wire, wide, pos, rows)
    assert got.flags.c_contiguous and got.dtype == np.uint8
    assert got.tobytes() == want.tobytes()
    assert live.dtype == np.uint32
    assert live.tobytes() == _live_words(
        np.ascontiguousarray(wide[pos])
    ).tobytes()


def test_take_rows_refuses_what_it_cannot_copy():
    wire, wide = np.zeros((4, 8), np.uint8), np.zeros((4, 16), np.uint8)
    with pytest.raises(IndexError):
        fastenc.take_rows(wire, wide, np.array([4]), 2)
    with pytest.raises(IndexError):
        fastenc.take_rows(wire, wide, np.array([-1]), 2)
    with pytest.raises(IndexError):
        fastenc.take_rows(wire, wide, np.array([0, 1, 2]), 2)
    with pytest.raises(ValueError):
        fastenc.take_rows(wire[:, ::2], wide, np.array([0]), 2)
    with pytest.raises(ValueError):
        fastenc.take_rows(wire, wide[:, ::2], np.array([0]), 2)
