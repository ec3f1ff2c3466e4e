"""Round-12 differential + bulk-submission suite.

Columnar transport (evaluation/environment.py planes): the columnar
delta-plane dispatch must be bit-exact against BOTH the row-packed
transport it replaces and the host oracle — including mutation patches
and group causes — and its wire accounting must reconcile.

Bulk submission (runtime/batcher.py submit_many): a burst of N rows must
produce exactly the results of N sequential submit_nowait calls, with
deadline/shed semantics preserved, in both completion modes (futures and
the batch-granular sink)."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from policy_server_tpu.api.service import RequestOrigin
from policy_server_tpu.evaluation.environment import (
    WIRE_KEY,
    EvaluationEnvironmentBuilder,
    _live_words,
)
from policy_server_tpu.models import AdmissionReviewRequest, ValidateRequest
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.policies.flagship import synthetic_firehose
from policy_server_tpu.runtime.batcher import MicroBatcher, ShedError

POLICIES = {
    "pod-privileged": {"module": "builtin://pod-privileged"},
    # mutating policy: parity must cover patch bytes, not just verdicts
    "psp-capabilities": {
        "module": "builtin://psp-capabilities",
        "allowedToMutate": True,
        "settings": {
            "allowed_capabilities": ["NET_BIND_SERVICE", "CHOWN"],
            "required_drop_capabilities": ["NET_ADMIN"],
            "default_add_capabilities": ["CHOWN"],
        },
    },
    # group: parity must cover causes + member-evaluated masks
    "pod-security-group": {
        "expression": "unprivileged() && (nonroot() || readonly())",
        "message": "pod security baseline not met",
        "policies": {
            "unprivileged": {"module": "builtin://pod-privileged"},
            "nonroot": {"module": "builtin://run-as-non-root"},
            "readonly": {"module": "builtin://readonly-root-fs"},
        },
    },
}


def _parsed():
    return {k: parse_policy_entry(k, v) for k, v in POLICIES.items()}


def _requests(n: int, seed: int = 11):
    return [
        ValidateRequest.from_admission(
            AdmissionReviewRequest.from_dict(d).request
        )
        for d in synthetic_firehose(n, seed=seed)
    ]


def _items(reqs):
    pids = list(POLICIES)
    return [(pids[i % len(pids)], r) for i, r in enumerate(reqs)]


@pytest.fixture(scope="module")
def corpus():
    return _items(_requests(150))


@pytest.fixture(scope="module")
def col_env():
    env = EvaluationEnvironmentBuilder(backend="jax").build(_parsed())
    yield env
    env.close()


def _dicts(results):
    assert not any(isinstance(r, Exception) for r in results), results
    return [r.to_dict() for r in results]


def _wait_compiled(env, seconds: float = 180.0):
    """Until the off-path compiler has nothing queued or running."""
    deadline = time.monotonic() + seconds
    while env.plane_programs_pending:
        assert time.monotonic() < deadline
        time.sleep(0.05)


def _settle(env, corpus):
    """Serve the corpus until its column sets are settled AND compiled:
    from then on a pass over it ships the settled forms."""
    for _ in range(2):
        env.reset_verdict_cache()
        env.validate_batch(corpus)
        _wait_compiled(env)


def _record_launches(env, monkeypatch) -> list:
    """Every later launch of the columnar program, in order, as (spec,
    form, what was shipped: name → host array, whether the serving path
    made it — not warm-up or the off-path compiler)."""
    launched: list = []
    launch, dispatch = env._launch_planes, env._plane_dispatch
    on = threading.local()

    def recording(spec, form, shipped):
        launched.append(
            (spec, form, dict(shipped), getattr(on, "serving", False))
        )
        return launch(spec, form, shipped)

    def serving(*args, **kwargs):
        on.serving = True
        try:
            return dispatch(*args, **kwargs)
        finally:
            on.serving = False

    monkeypatch.setattr(env, "_launch_planes", recording)
    monkeypatch.setattr(env, "_plane_dispatch", serving)
    return launched


class TestColumnarParity:
    def test_columnar_enabled_by_default(self, col_env):
        assert col_env.columnar

    def test_columnar_matches_row_packed_and_oracle(self, col_env, corpus):
        """The tri-way differential: columnar vs packed transport vs the
        host oracle, bit-exact AdmissionResponse dicts (uids, messages,
        causes, and base64 mutation patches included)."""
        row_env = EvaluationEnvironmentBuilder(
            backend="jax", columnar=False
        ).build(_parsed())
        oracle_env = EvaluationEnvironmentBuilder(backend="oracle").build(
            _parsed()
        )
        try:
            col = _dicts(col_env.validate_batch(corpus))
            row = _dicts(row_env.validate_batch(corpus))
            ora = _dicts(oracle_env.validate_batch(corpus))
            assert col == row
            assert col == ora
        finally:
            row_env.close()
            oracle_env.close()

    def test_mutation_patches_survive_columnar(self, col_env, corpus):
        """At least one psp-capabilities row must actually carry a patch
        — otherwise the mutation leg of the differential is vacuous."""
        results = col_env.validate_batch(corpus)
        patches = [
            r.patch
            for (pid, _), r in zip(corpus, results)
            if pid == "psp-capabilities" and not isinstance(r, Exception)
            and r.patch is not None
        ]
        assert patches, "corpus produced no mutation patches"

    def test_wire_accounting_reconciles(self, col_env, corpus, monkeypatch):
        """Shipped bytes are exactly the wire buffers' ``nbytes`` plus,
        once per column set, its index vectors' (they go to the device
        when the set first launches and stay there, so a pass over a
        settled corpus adds none); they are strictly below the
        packed-form equivalent, and every dispatched chunk was one
        columnar launch."""
        _settle(col_env, corpus)
        launched = _record_launches(col_env, monkeypatch)
        before = col_env.host_profile
        col_env.reset_verdict_cache()
        col_env.validate_batch(corpus)
        after = col_env.host_profile
        shipped = after["wire_bytes_shipped"] - before["wire_bytes_shipped"]
        packed = (
            after["wire_bytes_packed_equiv"]
            - before["wire_bytes_packed_equiv"]
        )
        rows = after["wire_rows"] - before["wire_rows"]
        chunks = after["dispatched_chunks"] - before["dispatched_chunks"]
        assert rows > 0 and shipped > 0
        assert all(served for *_rest, served in launched)
        assert shipped == sum(
            a.nbytes for _s, _f, sent, _served in launched
            for a in sent.values()
        )
        assert all(list(sent) == [WIRE_KEY] for _s, _f, sent, _ in launched)
        assert shipped < packed
        assert chunks == len(launched)
        assert (
            after["delta_cols_shipped"] - before["delta_cols_shipped"]
            <= after["delta_cols_total"] - before["delta_cols_total"]
        )

    def test_index_vectors_are_counted_once_per_column_set(
        self, corpus, monkeypatch
    ):
        """A fresh environment's whole account: every served wire buffer,
        plus the index vectors of each form that ever launched, once."""
        env = EvaluationEnvironmentBuilder(
            backend="jax", verdict_cache_size=0
        ).build(_parsed())
        try:
            launched = _record_launches(env, monkeypatch)
            env.warmup((16, 64))
            for _ in range(3):
                env.validate_batch(corpus)
                _wait_compiled(env)
            forms = {id(f): f for _spec, f, _sent, _served in launched}
            indices = sum(
                c.nbytes for f in forms.values() for c in f.cols.values()
            )
            assert indices > 0
            assert all(f.resident is not None for f in forms.values())
            # warm-up's and the off-path compiler's templates are not
            # traffic: only what the serving path launched counts
            wire = sum(
                a.nbytes for _s, _f, sent, served in launched if served
                for a in sent.values()
            )
            assert any(not served for *_rest, served in launched)
            assert env.host_profile["wire_bytes_shipped"] == wire + indices
        finally:
            env.close()

    def test_all_zero_batch_planes_elided(self, col_env):
        """The warmup shape: an all-missing batch ships ZERO delta
        bytes (every plane reconstructed from device-resident zero
        constants) and still evaluates."""
        schema = col_env.schemas[0]
        before = col_env.host_profile
        col_env.run_batch(schema.empty_batch_packed(8))
        after = col_env.host_profile
        assert after["wire_bytes_shipped"] == before["wire_bytes_shipped"]
        assert after["wire_rows"] - before["wire_rows"] == 8

    def test_delta_plane_padding_is_value_identical(self):
        """The power-of-two column padding repeats a real column, so
        duplicate scatter writes carry identical values (deterministic
        scatter)."""
        from policy_server_tpu.evaluation.environment import (
            EvaluationEnvironment,
        )

        mat = np.zeros((4, 16), np.int32)
        mat[:, 3] = 7
        mat[:, 9] = np.arange(4)
        mat[:, 12] = -1
        cols = EvaluationEnvironment._select_delta_cols(
            np.flatnonzero(mat.any(axis=0)), mat.shape[1]
        )
        vals = mat[:, cols]
        assert len(cols) == 4  # 3 live columns bucketed to 4
        assert sorted(set(cols.tolist())) == [3, 9, 12]
        # padded slot repeats the last real column with its real values
        rebuilt = np.zeros_like(mat)
        rebuilt[:, cols] = vals
        assert np.array_equal(rebuilt, mat)

    def test_column_sets_settle_and_compile_off_the_serving_path(self):
        """The shipped column set of a schema is a union that only grows:
        after the first pass over a corpus, ANY re-batching of the same
        rows ships an already-compiled structure — no program is traced
        inside a dispatch once warm-up ran (the dense form serves while
        the settled set compiles off the serving path), verdicts stay
        bit-exact throughout, and warm-up itself teaches the sets
        nothing."""
        env = EvaluationEnvironmentBuilder(
            backend="jax", verdict_cache_size=0
        ).build(_parsed())
        oracle_env = EvaluationEnvironmentBuilder(backend="oracle").build(
            _parsed()
        )
        try:
            corpus = _items(_requests(48))
            want = _dicts(oracle_env.validate_batch(corpus))
            env.warmup((1, 2, 4, 8, 16))
            assert env._plane_columns == {}
            warm = env.plane_program_compiles
            # pass 1: odd-sized batches; every dispatch must find a
            # compiled program (its own, or the dense form)
            got = []
            for lo, hi in ((0, 16), (16, 19), (19, 24), (24, 25), (25, 41),
                           (41, 48)):
                before = env.plane_program_compiles
                got += _dicts(env.validate_batch(corpus[lo:hi]))
                with env._profile_lock:
                    pending = env._plane_jobs_pending
                assert pending or env.plane_program_compiles == before
            assert got == want
            deadline = time.monotonic() + 120
            while env.plane_programs_pending:
                assert time.monotonic() < deadline
                time.sleep(0.05)
            assert env.plane_program_compiles > warm
            # pass 2: the same rows, batched differently — nothing new
            settled = env.plane_program_compiles
            shipped = env.host_profile["wire_bytes_shipped"]
            packed = env.host_profile["wire_bytes_packed_equiv"]
            got = []
            for lo, hi in ((0, 7), (7, 23), (23, 24), (24, 40), (40, 48)):
                got += _dicts(env.validate_batch(corpus[lo:hi]))
            assert got == want
            assert env.plane_programs_pending == 0
            assert env.plane_program_compiles == settled
            # ...and it ships the settled sparse columns, not the dense form
            hp = env.host_profile
            assert (hp["wire_bytes_shipped"] - shipped) < 0.5 * (
                hp["wire_bytes_packed_equiv"] - packed
            )
        finally:
            env.close()
            oracle_env.close()


@pytest.fixture(scope="module")
def references(corpus):
    """What the one-buffer form has to equal: the row-packed transport
    and the host oracle on the module's corpus, and the row-packed
    transport's raw outputs on an all-zero batch."""
    row_env = EvaluationEnvironmentBuilder(
        backend="jax", columnar=False, verdict_cache_size=0
    ).build(_parsed())
    oracle_env = EvaluationEnvironmentBuilder(backend="oracle").build(
        _parsed()
    )
    try:
        row = _dicts(row_env.validate_batch(corpus))
        assert row == _dicts(oracle_env.validate_batch(corpus))
        zero = row_env.run_batch(row_env.schemas[0].empty_batch_packed(8))
        yield row, zero
    finally:
        row_env.close()
        oracle_env.close()


class TestOneWireBuffer:
    """The columnar launch ships ONE packed wire buffer and its column
    indices stay on the device (PR 28)."""

    @pytest.mark.parametrize(
        "mode", ["settled", "dense-fallback", "all-elided", "whole-plane"]
    )
    @pytest.mark.parametrize("narrow", [True, False], ids=["u16", "i32"])
    def test_one_buffer_equals_row_packed_and_oracle(
        self, narrow, mode, corpus, references, monkeypatch
    ):
        """Every form of the wire buffer, on the narrow and the
        full-width id plane, answers what the row-packed transport and
        the oracle answer — launched TWICE through the same resident
        index vectors."""
        row, zero = references
        env = EvaluationEnvironmentBuilder(
            backend="jax", verdict_cache_size=0
        ).build(_parsed())
        try:
            if not narrow:
                monkeypatch.setattr(env, "_narrow", lambda schema_idx: False)
            if mode == "whole-plane":
                # every plane with a live column ships whole
                monkeypatch.setattr(
                    env, "_select_delta_cols", lambda live, n_cols: None
                )
            # every batch bucket the corpus is served in: a size warm-up
            # never saw would compile its set inside the dispatch
            env.warmup((8, 256))
            launched = _record_launches(env, monkeypatch)
            if mode == "dense-fallback":
                # the off-path compiler never gets the grown set: its
                # batches keep shipping the dense buffer
                monkeypatch.setattr(
                    env, "_compile_columns_async", lambda *a: None
                )
            elif mode != "all-elided":
                _settle(env, corpus)
            del launched[:]
            for _ in range(2):
                if mode == "all-elided":
                    got = env.run_batch(env.schemas[0].empty_batch_packed(8))
                    assert got.keys() == zero.keys()
                    for key, want in zero.items():
                        assert np.array_equal(got[key], want), key
                else:
                    assert _dicts(env.validate_batch(corpus)) == row
            assert len(launched) >= 2
            for spec, form, sent, served in launched:
                _idx, _batch, spec_narrow, shape = spec
                assert served and spec_narrow == narrow
                scattered = [s for k, s in shape if k]
                layout = env._wire_layout(spec[0], narrow)
                if mode == "settled":
                    assert any(scattered) and list(sent) == [WIRE_KEY]
                    assert all(
                        a.sharding.device_set for a in form.resident.values()
                    )
                elif mode == "all-elided":
                    assert not scattered and not sent
                else:
                    assert scattered and not any(scattered)
                    assert form.cols == {} and list(sent) == [WIRE_KEY]
                if mode == "dense-fallback":
                    assert form is layout.dense
                elif mode == "whole-plane":
                    assert form is not layout.dense
                if sent:
                    wire = sent[WIRE_KEY]
                    assert wire.dtype == np.uint8 and wire.flags.c_contiguous
                    assert wire.shape == (spec[1], form.width)
                    assert form.width % 4 == 0
        finally:
            env.close()

    def test_growth_across_a_bucket_compiles_off_the_serving_path(
        self, monkeypatch
    ):
        """A column set that grows across a power-of-two bucket is a new
        program: it compiles off the serving path, the batch that grew it
        ships the dense buffer meanwhile (no compile inside its
        dispatch), and once compiled the same batch ships the grown set —
        every answer equal to the row-packed transport's."""
        env = EvaluationEnvironmentBuilder(
            backend="jax", verdict_cache_size=0
        ).build(_parsed())
        row_env = EvaluationEnvironmentBuilder(
            backend="jax", columnar=False, verdict_cache_size=0
        ).build(_parsed())
        try:
            env.warmup((8,))
            narrow = env._narrow(0)
            layout = env._wire_layout(0, narrow)
            plane, at = "bits", 0
            lanes = layout.source["bits"]

            def batch_with(n_lanes: int) -> dict:
                feats = env.schemas[0].empty_batch_packed(8)
                # lane 0 is the batch mask: leave it alone
                feats[next(iter(feats))][:, lanes[1 : 1 + n_lanes]] = 1
                return feats

            def same(feats) -> None:
                got, want = env.run_batch(feats), row_env.run_batch(feats)
                assert got.keys() == want.keys()
                for key in want:
                    assert np.array_equal(got[key], want[key]), key

            launched = _record_launches(env, monkeypatch)
            small, grown = batch_with(3), batch_with(5)
            same(small)                      # teaches 3 lanes: bucket 4
            _wait_compiled(env)
            same(small)
            assert launched[-1][1].shape[2] == (4, True)
            compiles = env.plane_program_compiles
            # hold the compiler so that "meanwhile" is observable
            gate = threading.Event()
            compile_columns = env._compile_columns
            monkeypatch.setattr(
                env, "_compile_columns",
                lambda *a: (gate.wait(60), compile_columns(*a))[1],
            )
            same(grown)                      # 5 lanes: bucket 8, a new program
            assert env.plane_programs_pending == 1
            assert env.plane_program_compiles == compiles
            assert launched[-1][1] is layout.dense and launched[-1][3]
            same(grown)                      # still compiling: dense again
            assert launched[-1][1] is layout.dense
            gate.set()
            _wait_compiled(env)
            assert env.plane_program_compiles == compiles + 1  # one warm bucket
            same(grown)
            assert launched[-1][1].shape[2] == (8, True) and launched[-1][3]
            same(small)                      # a superset is exact
            assert launched[-1][1].shape[2] == (8, True)
            assert env.plane_program_compiles == compiles + 1
        finally:
            env.close()
            row_env.close()

    def test_a_launch_hands_over_exactly_one_host_array(self, corpus):
        """policy_server_launch_h2d_arrays_total (host_profile's
        ``launch_h2d_arrays``) rises by exactly 1 a launch, whatever the
        form, and by 0 for an all-elided batch."""
        env = EvaluationEnvironmentBuilder(
            backend="jax", verdict_cache_size=0
        ).build(_parsed())
        try:
            env.warmup((8, 64))
            assert env.host_profile["launch_h2d_arrays"] == 0

            def launches_and_arrays(fn) -> tuple[int, int]:
                before = env.host_profile
                fn()
                after = env.host_profile
                return (
                    after["dispatched_chunks"] - before["dispatched_chunks"],
                    after["launch_h2d_arrays"] - before["launch_h2d_arrays"],
                )

            n, arrays = launches_and_arrays(
                lambda: env.validate_batch(corpus)     # dense fallback
            )
            assert n >= 1 and arrays == n
            _wait_compiled(env)
            n, arrays = launches_and_arrays(
                lambda: env.validate_batch(corpus)     # settled
            )
            assert n >= 1 and arrays == n
            before = env.host_profile["launch_h2d_arrays"]
            env.run_batch(env.schemas[0].empty_batch_packed(8))
            assert env.host_profile["launch_h2d_arrays"] == before
        finally:
            env.close()

def _watch_dispatches(env, monkeypatch) -> list:
    """Every later columnar dispatch of the serving path, in order, as
    (the form launched, whether the encode call had written a wire for
    it, whether the chunk was compacted) — after holding the buffer it
    shipped against the launch's own ``_WireForm.wire`` of the wide rows
    it stood for, byte for byte, whoever wrote it."""
    seen: list = []
    launched = _record_launches(env, monkeypatch)
    dispatch = env._plane_dispatch

    def checking(schema_idx, features, rows=0, native=None):
        out = dispatch(schema_idx, features, rows, native)
        _spec, form, sent, _served = launched[-1]
        wide = np.ascontiguousarray(features[next(iter(features))])
        if native is not None:
            wide = native.wide(wide)
            # the words it read are the shipped rows' own, compacted or not
            assert native.live.tobytes() == _live_words(wide).tobytes()
        if form.width:
            assert sent[WIRE_KEY].tobytes() == form.wire(wide).tobytes()
            assert sent[WIRE_KEY].shape == (wide.shape[0], form.width)
        seen.append((
            form, native is not None,
            native is not None and native.ship_pos is not None,
        ))
        return out

    monkeypatch.setattr(env, "_plane_dispatch", checking)
    return seen


def _move_forms_after_encode(env, monkeypatch) -> None:
    """From now on every schema's column set moves on right after a chunk
    is encoded, as if another batch had grown it meanwhile: the same
    columns in a new form object (so the same compiled program)."""
    from policy_server_tpu.evaluation.environment import _WireForm

    encode_chunk = env._encode_chunk

    def encode_then_move(*args):
        out = encode_chunk(*args)
        with env._profile_lock:
            for columns in env._plane_columns.values():
                columns.form = _WireForm(columns.layout, columns.cols)
                columns.version += 1
        return out

    monkeypatch.setattr(env, "_encode_chunk", encode_then_move)


def _native_launches(env, fn) -> tuple[int, int]:
    """(launches, launches that shipped the encode call's wire) of fn."""
    before = env.host_profile
    fn()
    after = env.host_profile
    return (
        after["dispatched_chunks"] - before["dispatched_chunks"],
        after["launch_native_wire"] - before["launch_native_wire"],
    )


def _cold_items(capability: str, n: int = 24):
    """Requests that add a capability no encoder has met: a string the
    schema reads, which its mirror answers with a record."""
    reqs = []
    for d in synthetic_firehose(n, seed=11):
        first = d["request"]["object"]["spec"]["containers"][0]
        first.setdefault("securityContext", {})["capabilities"] = {
            "add": [capability]
        }
        reqs.append(ValidateRequest.from_admission(
            AdmissionReviewRequest.from_dict(d).request
        ))
    return _items(reqs)


class TestNativeWire:
    """The encode call writes the launch's wire buffer and liveness words
    (PR 37); the launch ships them when what it can observe allows, and
    otherwise does what it always did. Every case is held to the oracle,
    and every shipped buffer to ``_WireForm.wire`` (_watch_dispatches)."""

    @pytest.fixture()
    def env(self):
        env = EvaluationEnvironmentBuilder(
            backend="jax", verdict_cache_size=0
        ).build(_parsed())
        env.warmup((8, 32, 256))
        yield env
        env.close()

    @pytest.fixture(scope="class")
    def oracle(self):
        env = EvaluationEnvironmentBuilder(backend="oracle").build(_parsed())
        yield lambda items: _dicts(env.validate_batch(items))
        env.close()

    @pytest.mark.parametrize("narrow", [True, False], ids=["u16", "i32"])
    def test_a_settled_batch_ships_the_encode_calls_wire(
        self, env, oracle, corpus, narrow, monkeypatch
    ):
        if not narrow:
            monkeypatch.setattr(env, "_narrow", lambda schema_idx: False)
        _settle(env, corpus)
        seen = _watch_dispatches(env, monkeypatch)
        for items in (corpus, corpus[:1], corpus[:4], corpus[:67]):
            n, native = _native_launches(
                env, lambda items=items: _check(env, oracle, items)
            )
            assert n >= 1 and native == n
        assert all(wrote and not compacted for _f, wrote, compacted in seen)
        assert not any(form.resident is None for form, *_ in seen)

    def test_a_cold_string_leaves_the_wire_to_the_launch(
        self, env, oracle, corpus, monkeypatch
    ):
        _settle(env, corpus)
        seen = _watch_dispatches(env, monkeypatch)
        cold = _cold_items("NEVER_SEEN_37")
        n, native = _native_launches(env, lambda: _check(env, oracle, cold))
        assert n >= 1 and native == 0
        assert not any(wrote for _f, wrote, _c in seen)
        # the string is known now: the same requests are the native path's
        _wait_compiled(env)
        n, native = _native_launches(env, lambda: _check(env, oracle, cold))
        assert n >= 1 and native == n

    def test_a_batch_that_grows_the_column_set(
        self, env, oracle, corpus, monkeypatch
    ):
        """Settled on part of the corpus, then a batch with a column that
        part never had live: its wire was written in the old form, the
        launch decides on another (the dense one while the grown set
        compiles) and builds its own."""
        first = [it for it in corpus if it[0] == "pod-privileged"][:8]
        _settle(env, first)
        # every string of the corpus met (the mirror learns at encode):
        # what is left to fall back for is the column set alone
        env.schemas[0].native.encode_batch(
            [req.payload_json() for _pid, req in corpus], 256, env.table
        )
        version = next(iter(env._plane_columns.values())).version
        seen = _watch_dispatches(env, monkeypatch)
        n, native = _native_launches(env, lambda: _check(env, oracle, corpus))
        assert next(iter(env._plane_columns.values())).version > version
        assert n >= 1 and native == 0
        assert [wrote for _f, wrote, _c in seen] == [True] * n
        _wait_compiled(env)
        n, native = _native_launches(env, lambda: _check(env, oracle, corpus))
        assert native == n

    def test_a_form_that_moved_between_encode_and_launch(
        self, env, oracle, corpus, monkeypatch
    ):
        """The set's version moves after the chunk was encoded (another
        batch grew it meanwhile): the wire written for the old form is
        dropped, whatever the new form looks like."""
        _settle(env, corpus)
        with monkeypatch.context() as moving:
            _move_forms_after_encode(env, moving)
            seen = _watch_dispatches(env, moving)
            n, native = _native_launches(
                env, lambda: _check(env, oracle, corpus)
            )
            assert n >= 1 and native == 0
            assert all(wrote for _f, wrote, _c in seen)
        n, native = _native_launches(env, lambda: _check(env, oracle, corpus))
        assert native == n

    def test_a_program_not_compiled_yet_ships_the_dense_form(
        self, env, oracle, corpus, monkeypatch
    ):
        """The settled set's program is not in _plane_combos for this
        batch bucket: the launch ships the dense form, which is not the
        form the encode call wrote."""
        monkeypatch.setattr(env, "_compile_columns_async", lambda *a: None)
        env.validate_batch(corpus)            # teaches the set; no compile
        seen = _watch_dispatches(env, monkeypatch)
        n, native = _native_launches(env, lambda: _check(env, oracle, corpus))
        assert n >= 1 and native == 0
        layout = env._wire_layout(0, True)
        assert all(
            form is layout.dense and wrote for form, wrote, _c in seen
        )

    @pytest.mark.parametrize("moved", [False, True], ids=["wire", "fallback"])
    def test_a_compacted_chunk_takes_rows_of_the_wire(
        self, oracle, corpus, moved, monkeypatch
    ):
        """With the tiers on, a chunk with in-batch duplicates ships only
        its distinct rows: the same rows of the encode call's wire (52
        bytes each, not a wide copy) — and, when the form moved
        meanwhile, the wide copy and the launch's own wire of it."""
        env = EvaluationEnvironmentBuilder(backend="jax").build(_parsed())
        try:
            env.warmup((8, 32, 256))
            _settle(env, corpus)
            env.reset_verdict_cache()
            if moved:
                _move_forms_after_encode(env, monkeypatch)
            seen = _watch_dispatches(env, monkeypatch)
            doubled = corpus[:40] + corpus[:40]
            n, native = _native_launches(
                env, lambda: _check(env, oracle, doubled)
            )
            assert n >= 1 and native == (0 if moved else n)
            assert all(wrote and compacted for _f, wrote, compacted in seen)
        finally:
            env.close()

    def test_columnar_off_asks_for_no_wire(self, oracle, corpus):
        env = EvaluationEnvironmentBuilder(
            backend="jax", columnar=False, verdict_cache_size=0
        ).build(_parsed())
        try:
            for _ in range(2):
                n, native = _native_launches(
                    env, lambda: _check(env, oracle, corpus)
                )
                assert n >= 1 and native == 0
            assert env._wire_form_for(env.schemas[0]) is None
        finally:
            env.close()

    def test_an_all_elided_batch_ships_nothing(self, env, corpus):
        _settle(env, corpus)
        n, native = _native_launches(
            env, lambda: env.run_batch(env.schemas[0].empty_batch_packed(8))
        )
        assert native == 0


def _check(env, oracle, items) -> None:
    env.reset_verdict_cache()
    assert _dicts(env.validate_batch(items)) == oracle(items)


class TestSubmitMany:
    @pytest.fixture()
    def batcher(self, col_env):
        b = MicroBatcher(
            col_env,
            max_batch_size=64,
            batch_timeout_ms=1.0,
            policy_timeout=30.0,
            host_fastpath_threshold=0,
        ).start()
        yield b
        b.shutdown()

    def test_burst_equals_sequential(self, batcher, corpus):
        futs = batcher.submit_many(corpus, RequestOrigin.VALIDATE)
        bulk = [f.result(timeout=60).to_dict() for f in futs]
        seq = [
            batcher.submit_nowait(pid, r, RequestOrigin.VALIDATE)
            .result(timeout=60)
            .to_dict()
            for pid, r in corpus
        ]
        assert bulk == seq

    def test_sink_mode_delivers_every_token(self, batcher, corpus):
        got: list = []
        lock = threading.Lock()

        class Sink:
            def deliver_many(self, items):
                with lock:
                    got.extend(items)

        out = batcher.submit_many(
            corpus, RequestOrigin.VALIDATE, sink=Sink(),
            tokens=list(range(len(corpus))),
        )
        assert out is None  # sink mode allocates no futures
        deadline = time.time() + 60
        while time.time() < deadline:
            with lock:
                if len(got) >= len(corpus):
                    break
            time.sleep(0.01)
        with lock:
            assert sorted(t for t, _, _ in got) == list(range(len(corpus)))
            assert all(e is None for _, _, e in got)
            by_token = {t: r for t, r, _ in got}
        futs = batcher.submit_many(corpus, RequestOrigin.VALIDATE)
        for i, f in enumerate(futs):
            assert by_token[i].to_dict() == f.result(timeout=60).to_dict()

    def test_bulk_counters(self, batcher, corpus):
        before = batcher.stats_snapshot()
        batcher.submit_many(corpus, RequestOrigin.VALIDATE)
        after = batcher.stats_snapshot()
        assert after["bulk_submits"] - before["bulk_submits"] == 1
        assert (
            after["bulk_submitted_rows"] - before["bulk_submitted_rows"]
            == len(corpus)
        )

    def test_shed_semantics_preserved(self, col_env, corpus, monkeypatch):
        """When the estimated wait exceeds the deadline budget the whole
        burst sheds — futures resolve with the same ShedError
        submit_nowait raises, and sink tokens get it as exc."""
        b = MicroBatcher(
            col_env,
            max_batch_size=64,
            policy_timeout=30.0,
            request_timeout_ms=50.0,
        ).start()
        try:
            monkeypatch.setattr(b, "estimated_wait", lambda: 10.0)
            with pytest.raises(ShedError):
                b.submit_nowait(*corpus[0], RequestOrigin.VALIDATE)
            futs = b.submit_many(corpus[:5], RequestOrigin.VALIDATE)
            for f in futs:
                with pytest.raises(ShedError):
                    f.result(timeout=10)
            got: list = []

            class Sink:
                def deliver_many(self, items):
                    got.extend(items)

            b.submit_many(
                corpus[:3], RequestOrigin.VALIDATE, sink=Sink(),
                tokens=[0, 1, 2],
            )
            deadline = time.time() + 10
            while time.time() < deadline and len(got) < 3:
                time.sleep(0.01)
            assert len(got) == 3
            assert all(isinstance(e, ShedError) for _, _, e in got)
            assert b.stats_snapshot()["shed_requests"] >= 9
        finally:
            b.shutdown()

    def test_deadline_expiry_drops_pre_encode(self, col_env, corpus):
        """Rows whose propagated deadline passes while queued still drop
        before encode with the 504 expired answer on the bulk path."""
        b = MicroBatcher(
            col_env,
            max_batch_size=64,
            batch_timeout_ms=0.0,
            policy_timeout=30.0,
            request_timeout_ms=30.0,
        ).start()
        try:
            # wedge the dispatch loop briefly so queued rows age past
            # their 30 ms deadline before batch formation
            b._inflight.acquire()
            b._inflight.acquire()
            b._inflight.acquire()
            b._inflight.acquire()
            futs = b.submit_many(corpus[:8], RequestOrigin.VALIDATE)
            time.sleep(0.2)
            for s in range(4):
                b._inflight.release()
            expired = 0
            for f in futs:
                r = f.result(timeout=30)
                if r.status is not None and r.status.code == 504:
                    expired += 1
            assert expired == 8
            assert b.stats_snapshot()["expired_dropped"] >= 8
        finally:
            b.shutdown()

    def test_shutdown_rejects_burst_in_band(self, col_env, corpus):
        b = MicroBatcher(col_env, max_batch_size=64).start()
        b.shutdown()
        futs = b.submit_many(corpus[:4], RequestOrigin.VALIDATE)
        for f in futs:
            r = f.result(timeout=10)
            assert r.status is not None and r.status.code == 503
