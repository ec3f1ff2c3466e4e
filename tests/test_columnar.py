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
    EvaluationEnvironmentBuilder,
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

    def test_wire_accounting_reconciles(self, col_env, corpus):
        """Shipped bytes are positive, strictly below the packed-form
        equivalent, and every columnar dispatch was donated."""
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
        donated = after["donated_dispatches"] - before["donated_dispatches"]
        chunks = after["dispatched_chunks"] - before["dispatched_chunks"]
        assert rows > 0 and shipped > 0
        assert shipped < packed
        assert donated == chunks
        assert (
            after["delta_cols_shipped"] - before["delta_cols_shipped"]
            <= after["delta_cols_total"] - before["delta_cols_total"]
        )

    def test_donation_off_still_bit_exact(self, corpus):
        env = EvaluationEnvironmentBuilder(
            backend="jax", donate_buffers=False
        ).build(_parsed())
        oracle_env = EvaluationEnvironmentBuilder(backend="oracle").build(
            _parsed()
        )
        try:
            assert _dicts(env.validate_batch(corpus)) == _dicts(
                oracle_env.validate_batch(corpus)
            )
            assert env.host_profile["donated_dispatches"] == 0
        finally:
            env.close()
            oracle_env.close()

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
        delta: dict = {}
        EvaluationEnvironment._ship_plane(
            delta, "i32", mat,
            EvaluationEnvironment._select_delta_cols(
                np.flatnonzero(mat.any(axis=0)), mat.shape[1]
            ),
        )
        cols = delta["i32_cols"]
        vals = delta["i32"]
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
