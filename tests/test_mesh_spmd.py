"""Round-14 fused-SPMD differential suite.

One jit program over the (data × policy) mesh (evaluation/environment.py
``attach_mesh`` + parallel/mesh.py): the per-policy-shard ``lax.switch``
branches and the policy-axis ``all_gather`` must be BIT-EXACT against
both the single-device columnar path and the host oracle — including
mutation patches, group causes, the schema-overflow oracle fallback, and
the uneven-final-batch padding path — and the whole batch must execute
as ONE device program (the dispatches-per-batch collapse that replaced
the threaded MPMD dispatcher's per-shard programs + host thread joins).
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from policy_server_tpu.config.config import MeshSpec
from policy_server_tpu.evaluation.environment import (
    EvaluationEnvironmentBuilder,
)
from policy_server_tpu.models import AdmissionReviewRequest, ValidateRequest
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.parallel import (
    DATA_AXIS,
    POLICY_AXIS,
    PolicyShardedEvaluator,
    make_mesh,
    plan_policy_buckets,
)
from policy_server_tpu.parallel import mesh as mesh_mod
from policy_server_tpu.policies.flagship import synthetic_firehose

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
    "latest": {"module": "builtin://disallow-latest-tag"},
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


def _dicts(results):
    assert not any(isinstance(r, Exception) for r in results), results
    return [r.to_dict() for r in results]


@pytest.fixture(scope="module")
def mesh_env():
    """The fused-SPMD environment: ONE program over the 8-virtual-device
    (data:4, policy:2) mesh, policy axis sharded inside it."""
    env = EvaluationEnvironmentBuilder(backend="jax").build(_parsed())
    env.attach_mesh(make_mesh(MeshSpec.parse("data:4,policy:2")))
    assert env._mesh_block is not None
    yield env
    env.close()


@pytest.fixture(scope="module")
def corpus():
    return _items(_requests(48))


class TestPlanPolicyBuckets:
    def test_round_robin_partition_and_columns(self):
        buckets, width, col = plan_policy_buckets(
            ["d", "b", "a", "c", "e"], 2
        )
        # sorted round-robin, same placement rule as plan_policy_shards
        assert buckets == [("a", "c", "e"), ("b", "d")]
        assert width == 3  # every switch branch pads to the widest
        # shard-major: shard s slot k -> s * width + k
        assert col == {"a": 0, "c": 1, "e": 2, "b": 3, "d": 4}

    def test_more_shards_than_policies(self):
        buckets, width, col = plan_policy_buckets(["p"], 4)
        assert len(buckets) == 4 and width == 1
        assert buckets[0] == ("p",) and buckets[1] == ()
        assert col == {"p": 0}


class TestFusedMeshParity:
    def test_triway_differential_mesh_columnar_oracle(
        self, mesh_env, corpus
    ):
        """pjit-mesh vs single-device columnar vs host oracle: bit-exact
        AdmissionResponse dicts (uids, messages, causes, base64 mutation
        patches included)."""
        single = EvaluationEnvironmentBuilder(backend="jax").build(_parsed())
        oracle = EvaluationEnvironmentBuilder(backend="oracle").build(
            _parsed()
        )
        try:
            mesh_out = _dicts(mesh_env.validate_batch(corpus))
            single_out = _dicts(single.validate_batch(corpus))
            oracle_out = _dicts(oracle.validate_batch(corpus))
            assert mesh_out == single_out
            assert mesh_out == oracle_out
        finally:
            single.close()
            oracle.close()

    def test_mutation_patches_survive_mesh(self, mesh_env, corpus):
        """At least one psp-capabilities row must actually carry a patch
        — otherwise the mutation leg of the differential is vacuous."""
        results = mesh_env.validate_batch(corpus)
        patches = [
            r.patch
            for (pid, _), r in zip(corpus, results)
            if pid == "psp-capabilities" and not isinstance(r, Exception)
        ]
        assert any(p for p in patches), "no mutation patch exercised"

    def test_uneven_final_batch_pads_and_matches(self, mesh_env):
        """rows % data-shards != 0: the bucket pads to a multiple of the
        data axis (4) and pad rows never leak into results."""
        for n in (1, 3, 5, 10):
            items = _items(_requests(n, seed=300 + n))
            oracle = EvaluationEnvironmentBuilder(backend="oracle").build(
                _parsed()
            )
            try:
                got = _dicts(mesh_env.validate_batch(items))
                want = _dicts(oracle.validate_batch(items))
                assert got == want, f"n={n}"
                assert len(got) == n
            finally:
                oracle.close()

    def test_schema_overflow_falls_back_to_oracle(self):
        """A row no schema bucket can hold takes the per-row host-oracle
        fallback — under the mesh program too — and stays bit-exact."""
        policies = {
            "no-priv": parse_policy_entry(
                "no-priv", {"module": "builtin://pod-privileged"}
            )
        }
        env = EvaluationEnvironmentBuilder(backend="jax", axis_cap=2).build(
            dict(policies)
        )
        env.attach_mesh(make_mesh(MeshSpec.parse("data:8")))
        oracle = EvaluationEnvironmentBuilder(backend="oracle", axis_cap=2).build(
            dict(policies)
        )
        try:
            containers = [{"image": f"i{i}"} for i in range(5)]
            containers.append(
                {"image": "bad", "securityContext": {"privileged": True}}
            )
            doc = {
                "apiVersion": "admission.k8s.io/v1",
                "kind": "AdmissionReview",
                "request": {
                    "uid": "overflow-1",
                    "operation": "CREATE",
                    "kind": {"group": "", "version": "v1", "kind": "Pod"},
                    "object": {"spec": {"containers": containers}},
                },
            }
            req = ValidateRequest.from_admission(
                AdmissionReviewRequest.from_dict(doc).request
            )
            # mix the overflowing row into a normal batch: the fallback
            # must peel exactly that row while the rest ride the device
            items = [
                ("no-priv", r) for r in _requests(6, seed=77)
            ] + [("no-priv", req)]
            before = env.oracle_fallbacks
            got = _dicts(env.validate_batch(items))
            want = _dicts(oracle.validate_batch(items))
            assert got == want
            assert env.oracle_fallbacks > before
            assert got[-1]["allowed"] is False
        finally:
            env.close()
            oracle.close()


class TestOneProgramPerBatch:
    def test_fused_dispatches_once_threaded_dispatches_per_shard(
        self, mesh_env, corpus
    ):
        """The acceptance counter: a multi-policy batch over the fused
        program is ONE device dispatch; the legacy threaded MPMD
        dispatcher pays one per policy shard. Fresh (uncached) rows —
        verdict-cache hits dispatch nothing."""
        fresh = _items(_requests(16, seed=9001))
        before = mesh_env.host_profile["dispatched_chunks"]
        _ = mesh_env.validate_batch(fresh)
        fused_dispatches = (
            mesh_env.host_profile["dispatched_chunks"] - before
        )
        assert fused_dispatches == 1

        threaded = PolicyShardedEvaluator(
            _parsed(), make_mesh(MeshSpec.parse("data:4,policy:2"))
        )
        try:
            before = threaded.host_profile["dispatched_chunks"]
            _ = threaded.validate_batch(_items(_requests(16, seed=9002)))
            threaded_dispatches = (
                threaded.host_profile["dispatched_chunks"] - before
            )
            assert threaded_dispatches == len(threaded.shards) == 2
        finally:
            threaded.close()


class TestColumnarUnderMesh:
    def test_columnar_transport_active_under_mesh(self, mesh_env, corpus):
        """The STATUS 'mesh keeps row-packed' gap: the delta-plane
        transport now runs under attach_mesh (single-process), and its
        wire accounting reconciles — shipped bytes are bounded by the
        packed-equivalent and rows divide the data axis exactly, so the
        per-shard split shipped/data is exact."""
        before = dict(mesh_env.host_profile)
        _ = mesh_env.validate_batch(_items(_requests(24, seed=9100)))
        hp = mesh_env.host_profile
        rows = hp["wire_rows"] - before["wire_rows"]
        shipped = hp["wire_bytes_shipped"] - before["wire_bytes_shipped"]
        packed_equiv = (
            hp["wire_bytes_packed_equiv"] - before["wire_bytes_packed_equiv"]
        )
        assert rows > 0, "columnar path did not run under the mesh"
        assert 0 < shipped <= packed_equiv
        data_axis = mesh_env._mesh.shape[DATA_AXIS]
        assert rows % data_axis == 0  # buckets divide the data axis …
        # … so each data shard receives exactly rows/data_axis rows of
        # every batch-carrying plane
        assert rows // data_axis > 0

    def test_multi_process_mesh_keeps_packed(self, mesh_env, monkeypatch):
        """The columnar delta STRUCTURE is host-batch-content-derived, so
        a multi-process mesh must keep the packed transport (every
        process has to trace the SAME program)."""
        assert mesh_env._columnar_mesh_ok() is True
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        assert mesh_env._columnar_mesh_ok() is False

    def test_multiprocess_mesh_rejects_host_spanning_data_rows(
        self, monkeypatch
    ):
        """A data row spanning hosts breaks the host-local-rows
        contract (two processes would supply different local content
        for the same global batch region) — make_mesh must fail fast
        when the policy axis does not divide the per-host device
        count."""
        monkeypatch.setattr(jax, "process_count", lambda: 4)
        monkeypatch.setattr(jax, "local_device_count", lambda: 2)
        with pytest.raises(ValueError, match="policy axis 4 must divide"):
            make_mesh(MeshSpec.parse("data:2,policy:4"))
        # a host-local policy axis still builds, data outermost
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(jax, "local_device_count", lambda: 4)
        mesh = make_mesh(MeshSpec.parse("data:4,policy:2"))
        assert mesh.axis_names == (DATA_AXIS, POLICY_AXIS)

    def test_shard_delta_planes_placement(self):
        """What a launch ships is ONE array put once, its batch axis
        sharded over ``data``: on data:4 a copy per device, each of its
        own rows."""
        mesh = make_mesh(MeshSpec.parse("data:4"), jax.devices()[:4])
        wire = np.arange(8 * 52, dtype=np.uint8).reshape(8, 52)
        placed = mesh_mod.shard_delta_planes({"wire": wire}, mesh)
        assert list(placed) == ["wire"]
        assert placed["wire"].sharding == mesh_mod.batch_sharding(mesh)
        shards = placed["wire"].addressable_shards
        assert len(shards) == 4
        for shard in shards:
            assert shard.data.shape == (2, 52)
            assert np.array_equal(np.asarray(shard.data), wire[shard.index])
        # the 2-D mesh replicates the rows over ``policy``
        mesh2 = make_mesh(MeshSpec.parse("data:4,policy:2"))
        placed = mesh_mod.shard_delta_planes({"wire": wire}, mesh2)
        assert placed["wire"].sharding == mesh_mod.batch_sharding(mesh2)
        assert len(placed["wire"].addressable_shards) == 8

    def test_a_mesh_launch_ships_one_sharded_array(
        self, mesh_env, corpus, monkeypatch
    ):
        """Under the mesh a served batch hands the device one host array
        (the counter says so), and its column indices stay there,
        replicated, across launches."""
        forms: list = []
        launch = mesh_env._launch_planes

        def recording(spec, form, shipped):
            forms.append((form, dict(shipped)))
            return launch(spec, form, shipped)

        monkeypatch.setattr(mesh_env, "_launch_planes", recording)
        mesh_env.reset_verdict_cache()
        before = mesh_env.host_profile
        mesh_env.validate_batch(corpus)
        after = mesh_env.host_profile
        served = [(f, sent) for f, sent in forms if sent]
        assert served
        assert after["launch_h2d_arrays"] - before["launch_h2d_arrays"] == (
            len(served)
        )
        repl = mesh_mod.replicated_sharding(mesh_env.mesh)
        for form, sent in served:
            assert list(sent) == ["wire"]
            assert isinstance(sent["wire"], np.ndarray)
            assert all(a.sharding == repl for a in form.resident.values())


class TestMeshWarmup:
    def test_warmup_compiles_columnar_structures_under_mesh(self):
        """warmup under a single-process mesh primes BOTH columnar
        structures (all-elided + dense), mirroring the single-device
        contract, and warmup_dispatches reflects it for RTT seeding."""
        env = EvaluationEnvironmentBuilder(backend="jax").build(
            {
                "priv": parse_policy_entry(
                    "priv", {"module": "builtin://pod-privileged"}
                )
            }
        )
        env.attach_mesh(make_mesh(MeshSpec.parse("data:4,policy:2")))
        try:
            assert env.warmup_dispatches == 2 * len(env.schemas)
            # the plane-program counter proves both structures compiled:
            # 2 per schema; only the all-elided one is a batch through
            # the serving funnel (the dense one runs from a template, so
            # warm-up teaches the column sets nothing), and its output
            # spans every device of the mesh
            before = env.host_profile["wire_rows"]
            env.warmup((4,))
            assert env.plane_program_compiles == 2 * len(env.schemas)
            warm_rows = env.host_profile["wire_rows"] - before
            assert warm_rows == len(env.schemas) * env.bucket_for(4)
            assert env.warmup_output_devices == 8
            assert env._plane_columns == {}
        finally:
            env.close()
