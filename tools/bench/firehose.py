"""Config 4 (the headline): 32-policy synthetic firehose — rollout-dedup
stream + the historical all-unique trend line."""

from __future__ import annotations

import time

from tools.bench.common import (
    NORTH_STAR_RPS,
    build_rollout_stream,
    emit,
    pct,
    profile_delta,
    spread,
    trimmed_spread,
)


def bench_config4(n_requests: int, batch_size: int) -> None:
    from policy_server_tpu.policies.flagship import flagship_policies

    from policy_server_tpu.evaluation.environment import (
        EvaluationEnvironmentBuilder,
    )

    REPLICAS = 8
    stream, uniq = build_rollout_stream(n_requests, REPLICAS, seed=42)
    n_requests = len(stream)
    policy_id = "pod-security-group"  # every dispatch computes ALL verdicts
    items = [(policy_id, r) for r in stream]
    uniq_items = [(policy_id, r) for r in uniq]

    env = EvaluationEnvironmentBuilder(backend="jax").build(flagship_policies())

    # dispatch-size sweep: bigger chunks amortize the per-chunk fetch
    # round-trip — measure instead of assuming (compiles happen here,
    # outside the timed run). Probe every size in TWO interleaved rounds
    # and keep each size's best — a single ordered pass would
    # systematically favor whichever size ran last (warmest).
    candidates = [
        bs for bs in sorted({batch_size, 2048, 4096})
        if bs <= max(64, len(items))
    ]
    sweep: dict[int, float] = {}
    for bs in candidates:
        env.max_dispatch_batch = bs
        env.warmup((bs,))
        env.reset_verdict_cache()
        env.validate_batch(items[: min(2 * bs, len(items))])  # prime size
    for _round in range(2):
        for bs in candidates:
            env.max_dispatch_batch = bs
            env.reset_verdict_cache()
            probe = items[: min(2 * bs, len(items))]
            t0 = time.perf_counter()
            env.validate_batch(probe)
            rps = len(probe) / (time.perf_counter() - t0)
            sweep[bs] = max(sweep.get(bs, 0.0), rps)
    if sweep:  # tiny n_requests may skip every candidate
        batch_size = max(sweep, key=sweep.get)
    env.max_dispatch_batch = batch_size

    # prime with a FULL pass from an empty cache: the timed passes then
    # replay the exact same chunk/compaction shapes (every bucket already
    # compiled), per the r3/r4 lesson that priming at a different shape
    # puts XLA compilation inside the timed region
    env.reset_verdict_cache()
    env.validate_batch(items)
    fallbacks_before = env.oracle_fallbacks  # report the timed-pass DELTA
    dedup_before = dict(env.dedup_stats)
    profile_before = env.host_profile
    rps_runs = []
    for _ in range(3):
        env.reset_verdict_cache()  # each pass does the same work
        t_start = time.perf_counter()
        results = env.validate_batch(items)
        rps_runs.append(len(items) / (time.perf_counter() - t_start))
        errors = [r for r in results if isinstance(r, Exception)]
        if errors:
            raise RuntimeError(f"bench evaluation error: {errors[0]}")
    s_on = spread(rps_runs)
    dedup_after = env.dedup_stats
    rollout_profile = profile_delta(env.host_profile, profile_before)
    dedup_total = (
        dedup_after["cache_hits"] - dedup_before["cache_hits"]
        + dedup_after["blob_cache_hits"] - dedup_before["blob_cache_hits"]
        + dedup_after["batch_dup_hits"] - dedup_before["batch_dup_hits"]
    )
    dedup_rate = dedup_total / max(1, 3 * len(items))
    dedup_tiers = {
        "blob_tier_hits": dedup_after["blob_cache_hits"]
        - dedup_before["blob_cache_hits"],
        "row_tier_hits": dedup_after["cache_hits"]
        - dedup_before["cache_hits"],
        "in_batch_dup_hits": dedup_after["batch_dup_hits"]
        - dedup_before["batch_dup_hits"],
        "cache_bytes": dedup_after["cache_bytes"]
        + dedup_after["blob_cache_bytes"],
    }

    fallbacks_on = env.oracle_fallbacks - fallbacks_before

    # the honest no-dedup numbers on the SAME stream (cache-off build) +
    # the all-unique-rows workload (cross-round comparable with r1-r4)
    env.close()
    env_off = EvaluationEnvironmentBuilder(
        backend="jax", verdict_cache_size=0
    ).build(flagship_policies())
    env_off.max_dispatch_batch = batch_size
    env_off.warmup((batch_size,))
    env_off.validate_batch(items)  # full prime
    off_runs = []
    for _ in range(3):
        t0 = time.perf_counter()
        env_off.validate_batch(items)
        off_runs.append(len(items) / (time.perf_counter() - t0))
    s_off = spread(off_runs)
    # Round-12 variance fix for the ALL-UNIQUE trend line (rps_runs
    # spread 6.2k-41k in BENCH_r06): TWO untimed warmup waves before
    # measurement (the first primes shapes, the second drags the
    # thermal/allocator/VM state to steady), then 5 timed passes with
    # the best and worst dropped — the recorded value is the TRIMMED
    # median, with the raw runs kept for honesty.
    env_off.validate_batch(uniq_items)  # warmup wave 1: prime shapes
    env_off.validate_batch(uniq_items)  # warmup wave 2: steady-state
    uniq_profile_before = env_off.host_profile
    uniq_runs = []
    for _ in range(5):
        t0 = time.perf_counter()
        env_off.validate_batch(uniq_items)
        uniq_runs.append(len(uniq_items) / (time.perf_counter() - t0))
    s_uniq = trimmed_spread(uniq_runs)
    uniq_profile = profile_delta(env_off.host_profile, uniq_profile_before)

    # steady-state per-dispatch latency at a serving-sized batch, on the
    # CACHE-OFF environment: this metric means "one device round-trip at
    # batch N" — a cache would answer host-side and measure nothing
    lat_batch = min(256, batch_size)
    lat_items = uniq_items[:lat_batch]
    env_off.validate_batch(lat_items)
    lats = []
    for _ in range(100):
        t0 = time.perf_counter()
        env_off.validate_batch(lat_items)
        lats.append((time.perf_counter() - t0) * 1e3)
    lats.sort()
    env_off.close()

    # The dedup-on rollout number moved OFF the historical key in round 6
    # (ADVICE r5 #5): ``admission_reviews_per_sec_32policies`` measured an
    # all-unique no-dedup stream in rounds 1-4, so the historical key
    # carries that workload again (emitted last, below) and the rollout
    # stream gets its own metric here.
    emit(
        "admission_reviews_per_sec_32policies_rollout_dedup",
        s_on["median"],
        "reviews/s/chip",
        s_on["median"] / NORTH_STAR_RPS,
        n_requests=n_requests,
        batch_size=batch_size,
        workload=(
            f"rollout firehose: {len(uniq_items)} unique pod templates x "
            f"{REPLICAS} replica admissions each (bursty, fresh uid+name "
            f"per replica) — two-tier dedup: blob tier collapses exact "
            f"replays pre-encode, row tier collapses uid/name variants "
            f"post-encode"
        ),
        rps_min=round(s_on["min"], 1),
        rps_max=round(s_on["max"], 1),
        rps_runs=s_on["runs"],
        dedup_rate=round(dedup_rate, 4),
        dedup_tiers=dedup_tiers,
        host_decomposition_us_per_row=rollout_profile,
        unique_templates=len(uniq_items),
        replicas=REPLICAS,
        rps_no_dedup_same_stream=round(s_off["median"], 1),
        rps_no_dedup_min=round(s_off["min"], 1),
        rps_no_dedup_max=round(s_off["max"], 1),
        n_policies=32,
        oracle_fallbacks=fallbacks_on,
    )

    # HEADLINE (the driver records the LAST line): all-unique stream, no
    # dedup — the exact workload rounds 1-4 published under this key, so
    # cross-round trend lines stay apples-to-apples (ADVICE r5 #5).
    emit(
        "admission_reviews_per_sec_32policies",
        s_uniq["median"],
        "reviews/s/chip",
        s_uniq["median"] / NORTH_STAR_RPS,
        n_requests=len(uniq_items),
        batch_size=batch_size,
        workload=(
            "all-unique synthetic firehose, verdict cache OFF — the "
            "historical config4 workload (rounds 1-4); the rollout-dedup "
            "figure lives in admission_reviews_per_sec_32policies_rollout_dedup"
        ),
        rps_min=round(s_uniq["min"], 1),
        rps_max=round(s_uniq["max"], 1),
        rps_runs=s_uniq["runs"],
        trimmed_median_of=s_uniq["trimmed_n"],
        variance_note=(
            "value is the TRIMMED median of 5 timed passes (best+worst "
            "dropped) after 2 untimed warmup waves — round-12 fix for "
            "the 6.2k-41k rps_runs spread recorded in BENCH_r06"
        ),
        host_decomposition_us_per_row=uniq_profile,
        wire_bytes_per_row=uniq_profile.get("wire_bytes_per_row", 0),
        wire_bytes_per_row_packed_equiv=uniq_profile.get(
            "wire_bytes_per_row_packed_equiv", 0
        ),
        rps_rollout_dedup=round(s_on["median"], 1),
        rps_rollout_dedup_min=round(s_on["min"], 1),
        rps_rollout_dedup_max=round(s_on["max"], 1),
        rps_no_dedup_same_rollout_stream=round(s_off["median"], 1),
        p50_dispatch_latency_ms=round(pct(lats, 0.5), 2),
        p95_dispatch_latency_ms=round(pct(lats, 0.95), 2),
        p99_dispatch_latency_ms=round(pct(lats, 0.99), 2),
        dispatch_latency_samples=len(lats),
        latency_dispatch_size=lat_batch,
        n_policies=32,
        oracle_fallbacks=fallbacks_on,
        dispatch_size_sweep={str(k): round(v, 1) for k, v in sweep.items()},
    )
