"""Fused-SPMD vs threaded-MPMD mesh dispatch (round-14 acceptance).

The round-14 tentpole replaced the thread-per-shard MPMD dispatcher with
ONE jit program over the (data × policy) mesh — per-policy-shard
``lax.switch`` branches meeting in an all-gather collective instead of N
host-side thread joins. This line measures both dispatchers on the SAME
32-policy set over the same 8-virtual-device (data:4, policy:2) mesh:

* ``mesh_fused_spmd``    — rows/s through the fused program (one device
  dispatch per batch, columnar delta-plane transport, batch-sharded
  verdict fetch), with the threaded comparison and the dispatch-count
  collapse in the details.
* the round-14 decomposition: the threaded path pays
  ``dispatches_per_batch == n_policy_shards`` device programs plus the
  host-side joins that serialize them; the fused path pays 1 program in
  which XLA overlaps the cross-shard collective.

Both run in subprocesses (fresh XLA_FLAGS: the parent bench process has
a single CPU device), mirroring config 5.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from tools.bench.common import BENCH_SHIM, emit, spread

_MESH_SPEC = "data:4,policy:2"
_N_DEVICES = 8


def _mesh_policies():
    from policy_server_tpu.models.policy import parse_policy_entry

    # 8 tenants x (namespace fence, privileged, latest-tag, baseline
    # group) = 32 policies: the ISSUE's 32-policy acceptance shape, all
    # device-evaluable so the dispatch comparison measures dispatch, not
    # host fallbacks
    policies = {}
    for t in range(8):
        policies[f"tenant{t}-fence"] = parse_policy_entry(
            f"tenant{t}-fence",
            {
                "module": "builtin://namespace-validate",
                "settings": {"denied_namespaces": [f"tenant-{t}-restricted"]},
            },
        )
        policies[f"tenant{t}-priv"] = parse_policy_entry(
            f"tenant{t}-priv", {"module": "builtin://pod-privileged"}
        )
        policies[f"tenant{t}-latest"] = parse_policy_entry(
            f"tenant{t}-latest", {"module": "builtin://disallow-latest-tag"}
        )
        policies[f"tenant{t}-baseline"] = parse_policy_entry(
            f"tenant{t}-baseline",
            {
                "expression": "unpriv() && nonroot()",
                "message": f"tenant {t} baseline not met",
                "policies": {
                    "unpriv": {"module": "builtin://pod-privileged"},
                    "nonroot": {"module": "builtin://run-as-non-root"},
                },
            },
        )
    return policies


def bench_mesh_child(mode: str) -> None:
    """Runs in a subprocess with 8 virtual CPU devices. Prints one JSON
    doc: rows/s spread, dispatches per batch, and (fused) the columnar
    wire accounting under the mesh."""
    import jax

    from policy_server_tpu.config.config import MeshSpec
    from policy_server_tpu.evaluation.environment import (
        EvaluationEnvironmentBuilder,
    )
    from policy_server_tpu.parallel import PolicyShardedEvaluator, make_mesh
    from tools.bench.common import build_requests

    policies = _mesh_policies()
    mesh = make_mesh(MeshSpec.parse(_MESH_SPEC))
    if mode == "threaded":
        evaluator = PolicyShardedEvaluator(policies, mesh)
        sub_envs = list(evaluator.shards)
    else:
        evaluator = EvaluationEnvironmentBuilder(backend="jax").build(
            policies
        )
        evaluator.attach_mesh(mesh)
        assert evaluator._mesh_block is not None
        sub_envs = [evaluator]

    requests = build_requests(2048, seed=14)
    pids = sorted(policies)
    items = [(pids[i % len(pids)], r) for i, r in enumerate(requests)]

    # prime with a FULL pass so XLA compiles outside the timed region
    # (config 5 learned this in r3: priming with a slice measured
    # compile time, not serving)
    evaluator.validate_batch(items)

    chunks_before = evaluator.host_profile["dispatched_chunks"]
    for env in sub_envs:
        env.reset_verdict_cache()
    evaluator.validate_batch(items[: len(pids) * 4])
    probe_dispatches = (
        evaluator.host_profile["dispatched_chunks"] - chunks_before
    )

    rps_runs = []
    for _ in range(3):
        for env in sub_envs:
            env.reset_verdict_cache()
        t0 = time.perf_counter()
        evaluator.validate_batch(items)
        rps_runs.append(len(items) / (time.perf_counter() - t0))

    sp = spread(rps_runs)
    doc = {
        "mode": mode,
        "platform": jax.devices()[0].platform,
        "mesh": _MESH_SPEC,
        "policies": len(pids),
        "rows": len(items),
        "dispatches_per_batch": probe_dispatches,
        "rps": sp["median"],
        "rps_min": sp["min"],
        "rps_max": sp["max"],
        "rps_runs": sp["runs"],
    }
    if mode == "fused":
        hp = evaluator.host_profile
        doc["wire_rows"] = hp["wire_rows"]
        doc["wire_bytes_shipped"] = hp["wire_bytes_shipped"]
        # round 15: the predicate optimizer ran before this program
        # lowered — its per-bucket work accounting belongs next to the
        # rows/s it bought (subtrees shared / policies folded / fields
        # pruned / packed-row shrink per schema bucket)
        doc["optimizer"] = evaluator.optimizer_stats
        doc["optimizer_buckets"] = evaluator.optimizer_bucket_stats
    print(json.dumps(doc), flush=True)


def _run_child(mode: str) -> dict:
    child_env = dict(os.environ)
    child_env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(
            child_env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_N_DEVICES}"
        ).strip(),
    )
    out = subprocess.run(
        [sys.executable, BENCH_SHIM, "--mesh-child", mode],
        capture_output=True,
        text=True,
        env=child_env,
        timeout=1800,
        check=False,
    )
    line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
    try:
        doc = json.loads(line)
    except ValueError:
        raise RuntimeError(
            f"mesh bench child ({mode}) failed rc={out.returncode}:\n"
            + out.stdout[-1500:]
            + out.stderr[-3000:]
        ) from None
    return doc


def bench_mesh_dispatch() -> None:
    """One line: the fused (data × policy) SPMD program vs the legacy
    threaded MPMD dispatcher on identical work."""
    try:
        fused = _run_child("fused")
        threaded = _run_child("threaded")
    except (RuntimeError, subprocess.TimeoutExpired) as e:
        emit(
            "mesh_fused_spmd", 0, "reviews/s", 0,
            error=str(e)[:500],
        )
        return
    emit(
        "mesh_fused_spmd",
        fused["rps"],
        "reviews/s",
        fused["rps"] / 100_000.0,
        mesh=_MESH_SPEC,
        policies=fused["policies"],
        dispatches_per_batch=fused["dispatches_per_batch"],
        rps_min=fused["rps_min"],
        rps_max=fused["rps_max"],
        wire_rows=fused.get("wire_rows"),
        wire_bytes_shipped=fused.get("wire_bytes_shipped"),
        optimizer=fused.get("optimizer"),
        optimizer_buckets=fused.get("optimizer_buckets"),
        threaded_rps=threaded["rps"],
        threaded_rps_min=threaded["rps_min"],
        threaded_rps_max=threaded["rps_max"],
        threaded_dispatches_per_batch=threaded["dispatches_per_batch"],
        fused_vs_threaded=(
            round(fused["rps"] / threaded["rps"], 3)
            if threaded["rps"] else None
        ),
    )
