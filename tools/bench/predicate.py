"""Round-15 headline A/B: the predicate-program optimizer on vs off on
the flagship 32-policy set (`predicate_opt_ab`).

The recorded value is the fused DEVICE PROGRAM's rows/s — pre-encoded
packed batches through ``run_batch`` (one dispatch + one verdict fetch
per call), encode outside the timed region, verdict cache off. That is
the surface the pass optimizes: CSE/folding/pruning reduce per-row
FLOPs in the lowered program, and on this dev box the end-to-end path
is host-bound (materialize + payload Python ~100 µs/row), which would
dilute a real 20% compute win into measurement noise. The end-to-end
``validate_batch`` A/B rides in the details for exactly that honesty:
both numbers are printed, the device one is the claim.

Opt-on and opt-off passes INTERLEAVE so ambient drift hits both sides
equally, and the reported value is the trimmed median (drop best + worst pass).
The optimizer's work accounting (subtrees shared / policies folded /
fields pruned / row bytes saved) rides in the details — the acceptance
gate requires a NON-vacuous pass (>0 shared subtrees AND >0 pruned
fields on this workload), not just a throughput delta.

Round 19 rebuilt the END-TO-END leg: it now drives the real serving
path (fused-pipeline MicroBatcher, verdict cache off so the device
program executes for every row) in SUBPROCESS-isolated children, one
optimizer mode per process — two live flagship environments in one
process measurably anti-bias the A/B on the dev box (allocator/LLC
interference larger than the effect under test), and the pre-round-19
host floor (~100 µs/row) drowned the device delta entirely. With the
floor erased the honest arithmetic is: device cost delta ~0.6 µs/row
against a ~40 µs/row serving wall on this 2-core box → the expected
end-to-end win is a few percent, and the leg's job is to RESOLVE it
(interleaved children, long in-child aggregates, pairwise ratios), not
to inflate it."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from tools.bench.common import (
    BENCH_SHIM,
    NORTH_STAR_RPS,
    build_requests,
    emit,
    trimmed_spread,
)

_PASSES = 9          # per side, interleaved; trimmed_spread drops best+worst
_DISPATCHES = 6      # run_batch calls per timed pass
_BATCH = 2048        # rows per dispatch: big enough that per-row compute
                     # dominates the fixed dispatch+fetch overhead
_E2E_ROWS = 16384    # rows per end-to-end wave (serving-path children)
_E2E_CHILDREN = 3    # children per side, interleaved on/off
_E2E_WAVES = 5       # timed waves per child (one untimed warm wave)


def bench_predicate_e2e_child(spec: str) -> None:
    """One end-to-end A/B child (``mode:waves``): fresh process, ONE
    optimizer mode, the batcher_serving_path drive shape with the
    verdict cache disabled — every row encodes and executes on the
    device program, so the optimizer's compute/row-size wins are in the
    measured wall. Prints one JSON line."""
    mode, _, waves_s = spec.partition(":")
    waves = int(waves_s or _E2E_WAVES)
    from policy_server_tpu.api.service import RequestOrigin
    from policy_server_tpu.evaluation.environment import (
        EvaluationEnvironmentBuilder,
    )
    from policy_server_tpu.policies.flagship import flagship_policies
    from policy_server_tpu.runtime.batcher import MicroBatcher
    from tools.bench.serving import _drive_bulk

    env = EvaluationEnvironmentBuilder(
        backend="jax", predicate_opt=(mode == "on"), verdict_cache_size=0
    ).build(flagship_policies())
    batcher = MicroBatcher(
        env,
        max_batch_size=512,
        batch_timeout_ms=8.0,
        policy_timeout=30.0,
        host_fastpath_threshold=0,
        latency_budget_ms=0.0,
        request_timeout_ms=0.0,
    ).start()
    try:
        batcher.warmup()
        corpus = build_requests(8192, seed=77)
        items = [
            ("pod-security-group", corpus[i % len(corpus)])
            for i in range(_E2E_ROWS)
        ]
        origin = RequestOrigin.VALIDATE
        _drive_bulk(batcher, items, origin, 128, 2048)  # warm wave
        runs = []
        for _ in range(waves):
            wall = _drive_bulk(batcher, items, origin, 128, 2048)
            runs.append(round(len(items) / wall, 1))
        import jax

        print(
            json.dumps({
                "mode": mode, "runs": runs,
                "platform": jax.devices()[0].platform,
            }),
            flush=True,
        )
    finally:
        batcher.shutdown()
        env.close()


def _run_e2e_child(mode: str, waves: int) -> list[float]:
    # CPU by design: the parent bench process has already built device
    # environments, and a chip belongs to one process
    child_env = dict(os.environ)
    child_env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [
            sys.executable, BENCH_SHIM,
            "--predicate-e2e-child", f"{mode}:{waves}",
        ],
        capture_output=True,
        text=True,
        env=child_env,
        timeout=1800,
        check=False,
    )
    line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else ""
    try:
        return json.loads(line)["runs"]
    except (ValueError, KeyError):
        raise RuntimeError(
            f"predicate e2e child ({mode}) failed rc={out.returncode}:\n"
            + out.stdout[-1500:]
            + out.stderr[-3000:]
        ) from None


def _device_batch(env, requests):
    """Encode the request corpus into ONE packed device batch (outside
    the timed region) and compile its shape."""
    target = env._fast_target("pod-security-group")
    encoded = []
    for r in requests:
        payload = env.payload_for(target, r)
        bucket_idx, enc = env.encode_bucketed(payload)
        if bucket_idx == 0:
            encoded.append(enc)
        if len(encoded) == _BATCH:
            break
    schema = env.schemas[0]
    batch = schema.pack(schema.stack(encoded, batch_size=_BATCH))
    env._add_wasm_bits(batch, _BATCH)
    env.run_batch(dict(batch))  # compile this shape outside timing
    return batch


def bench_predicate_opt_ab(quick: bool = False) -> None:
    from policy_server_tpu.evaluation.environment import (
        EvaluationEnvironmentBuilder,
    )
    from policy_server_tpu.policies.flagship import flagship_policies

    requests = build_requests(max(_BATCH * 2, _E2E_ROWS), seed=15)
    passes = 3 if quick else _PASSES

    envs = {}
    batches = {}
    for mode in ("on", "off"):
        env = EvaluationEnvironmentBuilder(
            backend="jax", predicate_opt=(mode == "on")
        ).build(flagship_policies())
        env.warmup((_BATCH,))
        envs[mode] = env
        batches[mode] = _device_batch(env, requests)

    # device-program A/B (the claim): one packed batch, repeated
    # dispatch+fetch; interleaved so drift is shared. One untimed warm
    # dispatch per side first — the box's first post-compile dispatch
    # runs cold (allocator + thread-pool spin-up) and would land in the
    # opt-on column only.
    for mode, env in envs.items():
        env.run_batch(dict(batches[mode]))
    dev_runs: dict[str, list[float]] = {"on": [], "off": []}
    for _ in range(passes):
        for mode, env in envs.items():
            batch = batches[mode]
            t0 = time.perf_counter()
            for _ in range(_DISPATCHES):
                env.run_batch(dict(batch))
            dev_runs[mode].append(
                _DISPATCHES * _BATCH / (time.perf_counter() - t0)
            )

    # end-to-end serving A/B (round 19): the REAL serving path (fused
    # MicroBatcher, cache off) in subprocess-isolated children —
    # interleaved on/off so slow box drift hits both sides; pairwise
    # per-round ratios cancel what interleaving cannot
    e2e_runs = {"on": [], "off": []}
    e2e_pairs: list[float] = []
    e2e_error = None
    n_children = 1 if quick else _E2E_CHILDREN
    waves = 3 if quick else _E2E_WAVES
    try:
        for _ in range(n_children):
            on_runs = _run_e2e_child("on", waves)
            off_runs = _run_e2e_child("off", waves)
            e2e_runs["on"].extend(on_runs)
            e2e_runs["off"].extend(off_runs)
            e2e_pairs.append(
                trimmed_spread(on_runs)["median"]
                / max(1.0, trimmed_spread(off_runs)["median"])
            )
    except (RuntimeError, subprocess.TimeoutExpired) as e:
        e2e_error = str(e)[:500]

    dev_on = trimmed_spread(dev_runs["on"])
    dev_off = trimmed_spread(dev_runs["off"])
    e2e_on = trimmed_spread(e2e_runs["on"] or [0.0])
    e2e_off = trimmed_spread(e2e_runs["off"] or [0.0])
    stats = envs["on"].optimizer_stats

    def _ratio(a: dict, b: dict):
        return round(a["median"] / b["median"], 3) if b["median"] else None

    emit(
        "predicate_opt_ab",
        dev_on["median"],
        "reviews/s",
        dev_on["median"] / NORTH_STAR_RPS,
        surface="device program (run_batch, encode outside timing)",
        batch=_BATCH,
        policies=len(envs["on"]._compiled),
        device_on_rps=round(dev_on["median"], 1),
        device_on_min=round(dev_on["min"], 1),
        device_on_max=round(dev_on["max"], 1),
        device_on_runs=dev_on["runs"],
        device_off_rps=round(dev_off["median"], 1),
        device_off_min=round(dev_off["min"], 1),
        device_off_max=round(dev_off["max"], 1),
        device_off_runs=dev_off["runs"],
        device_speedup=_ratio(dev_on, dev_off),
        e2e_surface=(
            "batcher serving path (fused pipeline, submit_many bursts, "
            "verdict cache off), one optimizer mode per subprocess"
        ),
        e2e_rows_per_wave=_E2E_ROWS,
        e2e_on_rps=round(e2e_on["median"], 1),
        e2e_on_runs=e2e_runs["on"],
        e2e_off_rps=round(e2e_off["median"], 1),
        e2e_off_runs=e2e_runs["off"],
        e2e_speedup=_ratio(e2e_on, e2e_off),
        e2e_pair_ratios=[round(p, 3) for p in e2e_pairs],
        e2e_error=e2e_error,
        subtrees_shared=stats["subtrees_shared"],
        policies_folded=stats["policies_folded"],
        rules_folded=stats["rules_folded"],
        fields_pruned=stats["fields_pruned"],
        row_bytes_saved=stats["row_bytes_saved"],
        bucket_rows=envs["on"].optimizer_bucket_stats,
    )
