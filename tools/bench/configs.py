"""BASELINE.md configs 1, 2, 3, 5 and the wasm-interpreter line."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from tools.bench.common import (
    BENCH_SHIM,
    NORTH_STAR_RPS,
    build_env,
    build_requests,
    emit,
    pct,
    spread,
)


# ---------------------------------------------------------------------------
# Config 1: namespace-validate, single request (batch=1)
# ---------------------------------------------------------------------------


def bench_config1(requests) -> None:
    """The webhook-like shape: one request at a time through the SERVING
    path (micro-batcher with the host latency fast-path). vs_baseline is
    against this config's own reference point — the reference's CPU sync
    path answers a single request in ≈1 ms (≈1k reviews/s) — not the
    100k/chip pod target, which is meaningless at batch=1."""
    from policy_server_tpu.api.service import RequestOrigin
    from policy_server_tpu.runtime.batcher import MicroBatcher

    ref_single_rps = 1_000.0  # reference CPU sync path, ≈1 ms/request
    env = build_env(
        {
            "namespace-validate": {
                "module": "builtin://namespace-validate",
                "settings": {"denied_namespaces": ["kube-system"]},
            }
        }
    )
    env.warmup((1,))
    batcher = MicroBatcher(
        env,
        max_batch_size=64,
        batch_timeout_ms=0.0,
        policy_timeout=30.0,
        host_fastpath_threshold=64,
    ).start()
    reqs = requests[:2048]
    try:
        for r in reqs[:8]:
            batcher.evaluate("namespace-validate", r, RequestOrigin.VALIDATE)
        lats = []
        t0 = time.perf_counter()
        for r in reqs:
            t1 = time.perf_counter()
            batcher.evaluate("namespace-validate", r, RequestOrigin.VALIDATE)
            lats.append((time.perf_counter() - t1) * 1e3)
        wall = time.perf_counter() - t0
    finally:
        batcher.shutdown()
    lats.sort()
    rps = len(reqs) / wall
    emit(
        "config1_namespace_validate_single",
        rps,
        "reviews/s",
        rps / ref_single_rps,
        p50_ms=round(pct(lats, 0.5), 2),
        p99_ms=round(pct(lats, 0.99), 2),
        batch_size=1,
        n_requests=len(reqs),
        host_fastpath_requests=env.host_fastpath_requests,
        baseline="reference CPU sync path ≈1k reviews/s (≈1 ms/request); "
        "vs_baseline is against that, not the 100k/chip pod target",
        note="serving path: micro-batcher + host latency fast-path",
    )


# ---------------------------------------------------------------------------
# Config 2: psp-capabilities + psp-apparmor, 1k replay
# ---------------------------------------------------------------------------


def bench_config2(requests) -> None:
    env = build_env(
        {
            "psp-capabilities": {
                "module": "builtin://psp-capabilities",
                "allowedToMutate": True,
                "settings": {
                    "allowed_capabilities": ["NET_BIND_SERVICE", "CHOWN"],
                    "required_drop_capabilities": ["NET_ADMIN"],
                    "default_add_capabilities": ["CHOWN"],
                },
            },
            "psp-apparmor": {
                "module": "builtin://psp-apparmor",
                "settings": {"allowed_profiles": ["runtime/default"]},
            },
        }
    )
    corpus = requests[:1000]
    items = [
        ("psp-capabilities" if i % 2 else "psp-apparmor", r)
        for i, r in enumerate(corpus)
    ]
    env.max_dispatch_batch = 512
    env.warmup((512,))
    env.validate_batch(items)  # prime
    rps_runs = []
    for _ in range(3):
        # reset before EVERY timed call: a second pass over the identical
        # replay would otherwise be answered from the verdict cache and
        # double-count as device throughput
        t0 = time.perf_counter()
        for _rep in range(2):
            env.reset_verdict_cache()
            env.validate_batch(items)
        rps_runs.append(2 * len(items) / (time.perf_counter() - t0))
    s = spread(rps_runs)
    emit(
        "config2_psp_pair_1k_replay",
        s["median"],
        "reviews/s/chip",
        s["median"] / NORTH_STAR_RPS,
        rps_min=round(s["min"], 1),
        rps_max=round(s["max"], 1),
        rps_runs=s["runs"],
        replay_size=len(items),
        n_policies=2,
    )


# ---------------------------------------------------------------------------
# Config 3: pod-image-signatures policy group (OR/AND tree)
# ---------------------------------------------------------------------------


def bench_config3(requests) -> None:
    """Round-12 satellite fix: this line recorded 0.0 ("error") in
    BENCH_r06 because it imported the Ed25519 signature fixture
    unconditionally — in dependency-light containers (no ``cryptography``
    module) the ImportError killed the whole config. It now degrades to
    the SAME crypto-free provenance stand-in the flagship policy set uses
    (flagship.py round 11), loudly labeled, so the group-expression
    throughput is still measured; the real verification pipeline is then
    NOT exercised and the line says so."""
    try:
        from policy_server_tpu.policies.flagship import _signature_fixture

        store, pub = _signature_fixture()
        signed_member: dict = {
            "module": "builtin://verify-image-signatures",
            "settings": {
                "signatures": [
                    {
                        "image": "registry.prod.example.com/*",
                        "pubKeys": [pub],
                    }
                ],
                "signatureStore": store,
            },
        }
        stand_in_note = None
    except ImportError:
        signed_member = {
            "module": "builtin://trusted-repos",
            "settings": {
                "registries": {"allow": ["registry.prod.example.com"]}
            },
        }
        stand_in_note = (
            "cryptography module unavailable: 'signed()' member degraded "
            "to the trusted-repos stand-in (group expression and device "
            "path exercised; the signature verification pipeline is NOT)"
        )
    env = build_env(
        {
            "pod-image-signatures": {
                "expression": "signed() || (trusted() && not_latest())",
                "message": "image provenance cannot be established",
                "policies": {
                    "signed": signed_member,
                    "trusted": {
                        "module": "builtin://trusted-repos",
                        "settings": {"registries": {"allow": ["docker.io"]}},
                    },
                    "not_latest": {"module": "builtin://disallow-latest-tag"},
                },
            }
        }
    )
    corpus = requests[:4096]
    items = [("pod-image-signatures", r) for r in corpus]
    env.max_dispatch_batch = 1024
    env.warmup((1024,))
    env.validate_batch(items)  # prime with a FULL pass (same buckets)
    rps_runs = []
    for _ in range(3):
        env.reset_verdict_cache()
        t0 = time.perf_counter()
        env.validate_batch(items)
        rps_runs.append(len(items) / (time.perf_counter() - t0))
    s = spread(rps_runs)
    details = dict(
        rps_min=round(s["min"], 1),
        rps_max=round(s["max"], 1),
        rps_runs=s["runs"],
        n_requests=len(items),
        group_members=3,
        expression="signed() || (trusted() && not_latest())",
    )
    if stand_in_note is not None:
        details["note"] = stand_in_note
    emit(
        "config3_image_signatures_group",
        s["median"],
        "reviews/s/chip",
        s["median"] / NORTH_STAR_RPS,
        **details,
    )


# ---------------------------------------------------------------------------
# Config 5: 8-shard multi-tenant + preemption churn (virtual CPU mesh)
# ---------------------------------------------------------------------------


def bench_config5_child() -> None:
    """Runs in a subprocess with JAX_PLATFORMS=cpu and 8 virtual devices."""
    import jax

    from policy_server_tpu.config.config import MeshSpec
    from policy_server_tpu.parallel import PolicyShardedEvaluator, make_mesh
    from policy_server_tpu.models.policy import parse_policy_entry

    # 8 tenants × namespace fence + shared pod-security = 16 policies over
    # a policy:8 mesh (each shard data-parallel over 1 device)
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
    mesh = make_mesh(MeshSpec.parse("data:1,policy:8"))
    sharded = PolicyShardedEvaluator(policies, mesh)
    requests = build_requests(2048, seed=9)
    pids = list(policies)
    items = [(pids[i % len(pids)], r) for i, r in enumerate(requests)]
    # prime with a FULL pass: per-shard batches land in the same shape
    # bucket as the timed run, so XLA compiles OUTSIDE the timed region
    # (priming with a slice measured compile time, not serving: 2,085
    # rps reported in r3 vs ~90k steady-state on the same machine)
    sharded.validate_batch(items)
    rps_runs = []
    for _ in range(3):
        for env in sharded.shards:
            env.reset_verdict_cache()
        t0 = time.perf_counter()
        sharded.validate_batch(items)
        rps_runs.append(len(items) / (time.perf_counter() - t0))
    rps_runs.sort()

    # preemption churn: drop 2 of 8 devices, measure the rebuild, and
    # verify serving continues
    t1 = time.perf_counter()
    sharded.resize(list(jax.devices())[:6])
    churn_s = time.perf_counter() - t1
    # first post-churn batch pays the rebalanced shards' compiles —
    # report that stall separately from steady-state serving
    t2 = time.perf_counter()
    sharded.validate_batch(items[:512])
    first_post_wall = time.perf_counter() - t2
    t3 = time.perf_counter()
    sharded.validate_batch(items[:512])
    post_wall = time.perf_counter() - t3

    print(
        json.dumps(
            {
                "platform": jax.devices()[0].platform,
                "rps": rps_runs[len(rps_runs) // 2],
                "rps_min": rps_runs[0],
                "rps_max": rps_runs[-1],
                "churn_rebuild_s": churn_s,
                "post_churn_first_batch_s": first_post_wall,
                "post_churn_rps": 512 / post_wall,
                "shards_before": 8,
                "shards_after": sharded.mesh.shape["policy"],
            }
        )
    )


def bench_config5() -> None:
    child_env = dict(os.environ)
    child_env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(
            child_env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip(),
    )
    out = subprocess.run(
        [sys.executable, BENCH_SHIM, "--config5-child"],
        capture_output=True,
        text=True,
        env=child_env,
        timeout=1800,
        check=False,
    )
    line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
    try:
        doc = json.loads(line)
    except (ValueError, IndexError):
        emit(
            "config5_multitenant_8shards_virtual",
            0.0,
            "reviews/s (8 virtual cpu devices)",
            0.0,
            error=(out.stderr or "no output")[-400:],
        )
        return
    emit(
        "config5_multitenant_8shards_virtual",
        doc["rps"],
        "reviews/s (8 virtual cpu devices)",
        doc["rps"] / NORTH_STAR_RPS,
        rps_min=round(doc.get("rps_min", doc["rps"]), 1),
        rps_max=round(doc.get("rps_max", doc["rps"]), 1),
        churn_rebuild_s=round(doc["churn_rebuild_s"], 2),
        post_churn_first_batch_s=round(doc["post_churn_first_batch_s"], 2),
        post_churn_rps=round(doc["post_churn_rps"], 1),
        shards_before=doc["shards_before"],
        shards_after=doc["shards_after"],
        note="virtual CPU mesh: multi-chip hardware not present; measures "
        "MPMD routing + churn rebuild, not TPU throughput",
    )


# ---------------------------------------------------------------------------
# Wasm escape-hatch path: interpreter reviews/s
# ---------------------------------------------------------------------------


def bench_wasm(requests) -> None:
    """Cost of the host wasm engine — the generality escape hatch for
    policies outside the predicate IR. Measures reviews/s through the waPC
    WAT oracle policy and (when the upstream fixture is present) an
    upstream-compiled Gatekeeper module, on whichever engine the ABI
    hosts select (the native C++ core when it builds, else the Python
    reference interpreter). Its own baseline: the reference runs these
    under wasmtime's cranelift-JIT at ≈1 ms/request (≈1k reviews/s
    end-to-end, dominated by non-wasm overhead)."""
    import pathlib

    from policy_server_tpu.policies.wasm_oracle import oracle_policy
    from policy_server_tpu.wasm.opa import OpaPolicy, gatekeeper_validate

    ref_single_rps = 1_000.0
    docs = [r.payload() for r in requests[:200]]

    pol = oracle_policy("pod-privileged")
    pol.validate(docs[0], {})  # prime (assemble + decode)
    t0 = time.perf_counter()
    for d in docs:
        pol.validate(d, {})
    wapc_wall = time.perf_counter() - t0
    wapc_rps = len(docs) / wapc_wall

    gk_rps = None
    gk_note = None
    fixture = pathlib.Path(
        os.environ.get("REFERENCE_DIR", "/root/reference"),
        "tests/data/gatekeeper_always_happy_policy.wasm",
    )
    if fixture.exists():
        opa = OpaPolicy(fixture.read_bytes())
        gk_docs = docs[:20]  # upstream module: heavier per call
        gatekeeper_validate(opa, gk_docs[0], parameters={})
        t0 = time.perf_counter()
        for d in gk_docs:
            gatekeeper_validate(opa, d, parameters={})
        gk_rps = len(gk_docs) / (time.perf_counter() - t0)
    else:
        gk_note = f"skipped: fixture not found at {fixture} (set REFERENCE_DIR)"

    emit(
        "wasm_interpreter_reviews_per_sec",
        wapc_rps,
        "reviews/s",
        wapc_rps / ref_single_rps,
        wat_wapc_rps=round(wapc_rps, 1),
        gatekeeper_fixture_rps=round(gk_rps, 1) if gk_rps else gk_note,
        n_requests=len(docs),
        baseline="reference wasmtime-JIT sync path ≈1k reviews/s; the "
        "wasm engine is the correctness escape hatch, not the serving path",
        native_engine=__import__(
            "policy_server_tpu.wasm.native_exec", fromlist=["available"]
        ).available(),
    )
