"""Shared benchmark plumbing: the emit/spread helpers, request corpora,
and the per-stage decomposition snapshots every serving line reports."""

from __future__ import annotations

import json
import math
import statistics
import time
from pathlib import Path

NORTH_STAR_RPS = 100_000.0
NORTH_STAR_P99_MS = 10.0

# the repo-root shim — subprocess entry points (--config5-child,
# --native-client) re-invoke THIS file so the driver command stays
# `python bench.py` regardless of where a bench module lives
BENCH_SHIM = str(Path(__file__).resolve().parent.parent.parent / "bench.py")

# every emitted (metric, value, unit) — re-printed as one compact
# bench_summary line before the headline so a truncated tail window
# still records every number
_EMITTED: list[tuple[str, float, str]] = []


def pct(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = max(0, math.ceil(q * len(sorted_vals)) - 1)
    return sorted_vals[idx]


def write_json_artifact(path: str, doc: dict) -> None:
    """One writer for BENCH-style JSON artifacts (bench trend files,
    tools/soak's BENCH_soak_* output): stable formatting so round-over-
    round diffs stay readable."""
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


def emit(metric: str, value: float, unit: str, vs: float, **details) -> None:
    _EMITTED.append((metric, round(value, 2), unit))
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(value, 2),
                "unit": unit,
                "vs_baseline": round(vs, 4),
                "details": details,
            }
        ),
        flush=True,
    )


def emit_summary() -> None:
    """Compact recap of every line so far: a driver's tail window once
    truncated a run and lost config1-3 — this single line preserves
    every number even if only the last two lines survive."""
    print(
        json.dumps(
            {
                "metric": "bench_summary",
                "value": len(_EMITTED),
                "unit": "lines",
                "vs_baseline": 0,
                "details": {m: [v, u] for m, v, u in _EMITTED},
            }
        ),
        flush=True,
    )


def spread(walls_to_rps: list[float]) -> dict:
    """median + min/max over N timed passes — a point value is not
    defensible against a same-day re-run."""
    vals = sorted(walls_to_rps)
    return {
        "median": statistics.median(vals),
        "min": vals[0],
        "max": vals[-1],
        "runs": [round(v, 1) for v in walls_to_rps],
    }


def trimmed_spread(runs: list[float]) -> dict:
    """Round-12 variance taming for the all-unique trend line: drop the
    single best and single worst pass, report the median of the middle
    (the TRIMMED median) plus the full untrimmed spread — a one-off VM
    hiccup (rps_runs 6.2k-41k in BENCH_r06) can no longer become the
    recorded value, and the raw runs stay visible for honesty."""
    vals = sorted(runs)
    trimmed = vals[1:-1] if len(vals) >= 4 else vals
    return {
        "median": statistics.median(trimmed),
        "min": vals[0],
        "max": vals[-1],
        "trimmed_n": len(trimmed),
        "runs": [round(v, 1) for v in runs],
    }


def build_requests(n: int, seed: int = 42):
    from policy_server_tpu.models import AdmissionReviewRequest, ValidateRequest
    from policy_server_tpu.policies.flagship import synthetic_firehose

    return [
        ValidateRequest.from_admission(
            AdmissionReviewRequest.from_dict(doc).request
        )
        for doc in synthetic_firehose(n, seed=seed)
    ]


def build_env(policies: dict):
    from policy_server_tpu.evaluation.environment import (
        EvaluationEnvironmentBuilder,
    )
    from policy_server_tpu.models.policy import parse_policy_entry

    return EvaluationEnvironmentBuilder(backend="jax").build(
        {k: parse_policy_entry(k, v) for k, v in policies.items()}
    )


def build_rollout_stream(n_requests: int, replicas: int, seed: int):
    """The realistic admission firehose: ``n/replicas`` unique pod
    templates, each admitted ``replicas`` times in a burst — a Deployment
    rollout admits its replica pods back-to-back, identical except for
    the generated pod name and the API server's fresh uid. Returns
    (stream_requests, unique_requests)."""
    import copy

    from policy_server_tpu.models import (
        AdmissionReviewRequest,
        ValidateRequest,
    )
    from policy_server_tpu.policies.flagship import synthetic_firehose

    n_unique = max(1, n_requests // replicas)
    uniq_docs = synthetic_firehose(n_unique, seed=seed)
    stream_docs = []
    for d in uniq_docs:
        for r in range(replicas):
            dd = copy.deepcopy(d)
            dd["request"]["uid"] = f'{dd["request"]["uid"]}-r{r}'
            obj = dd["request"].get("object") or {}
            meta = obj.setdefault("metadata", {})
            meta["name"] = f'{meta.get("name", "pod")}-{r}'
            dd["request"]["name"] = meta["name"]
            stream_docs.append(dd)

    def to_req(doc):
        return ValidateRequest.from_admission(
            AdmissionReviewRequest.from_dict(doc).request
        )

    return [to_req(d) for d in stream_docs], [to_req(d) for d in uniq_docs]


def profile_delta(after: dict, before: dict) -> dict:
    """Per-row host decomposition between two host_profile snapshots:
    encode / dedup-bookkeeping / dispatch-wait in µs/row (round 6),
    plus the columnar wire accounting (round 12). Every number here is
    recoverable from the emitted BENCH JSON alone."""
    d = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    enc_rows = max(1, d.get("encode_rows", 0))
    book_rows = max(1, d.get("bookkeeping_rows", 0))
    disp_rows = max(1, d.get("dispatched_rows", 0))
    wire_rows = max(1, d.get("wire_rows", 0))
    return {
        "encode_us_per_row": round(d.get("encode_ns", 0) / 1e3 / enc_rows, 2),
        "encode_rows": d.get("encode_rows", 0),
        "bookkeeping_us_per_row": round(
            d.get("bookkeeping_ns", 0) / 1e3 / book_rows, 2
        ),
        "bookkeeping_rows": d.get("bookkeeping_rows", 0),
        "dispatch_wait_us_per_dispatched_row": round(
            d.get("dispatch_wait_ns", 0) / 1e3 / disp_rows, 2
        ),
        "dispatched_rows": d.get("dispatched_rows", 0),
        "dispatched_chunks": d.get("dispatched_chunks", 0),
        # columnar transport (round 12): bytes/row actually on the wire
        # vs what the row-packed transport form would have shipped
        "wire_bytes_per_row": round(
            d.get("wire_bytes_shipped", 0) / wire_rows, 1
        ),
        "wire_bytes_per_row_packed_equiv": round(
            d.get("wire_bytes_packed_equiv", 0) / wire_rows, 1
        ),
        "delta_col_hit_rate": round(
            1.0
            - d.get("delta_cols_shipped", 0)
            / max(1, d.get("delta_cols_total", 0)),
            4,
        ),
    }


def _decomp_snapshot(server) -> dict:
    """Cumulative per-stage counters for the framing/queue/device time
    decomposition (round-11 satellite): where a served request's wall
    time goes — native framing (C++ threads), batcher queue wait, host
    encode+bookkeeping, device wait."""
    bs = server.batcher.stats_snapshot()
    prof = dict(getattr(server.environment, "host_profile", {}) or {})
    nf = getattr(server, "_native_frontend", None)
    nstats = nf.stats() if nf is not None else {}
    return {
        "requests": bs["requests_dispatched"],
        "queue_wait_ns": bs["queue_wait_ns"],
        "encode_ns": prof.get("encode_ns", 0),
        "bookkeeping_ns": prof.get("bookkeeping_ns", 0),
        "device_wait_ns": prof.get("dispatch_wait_ns", 0),
        "framing_ns": nstats.get("framing_ns", 0),
        "parse_fallbacks": nstats.get("parse_fallbacks", 0),
        "bulk_submits": bs.get("bulk_submits", 0),
        "bulk_submitted_rows": bs.get("bulk_submitted_rows", 0),
    }


def _decompose(before: dict, after: dict) -> dict:
    """Per-request stage times between two snapshots. 'unattributed' is
    everything else — handler/runtime Python, GIL waits, and (for the
    Python frontend) the asyncio HTTP framing itself, which has no
    counter; on the native frontend framing is measured directly."""
    d = {k: after[k] - before[k] for k in before}
    n = max(1, d["requests"])
    return {
        "requests_dispatched": d["requests"],
        "framing_ms_per_req": round(d["framing_ns"] / 1e6 / n, 4),
        "queue_wait_ms_per_req": round(d["queue_wait_ns"] / 1e6 / n, 3),
        "host_encode_ms_per_req": round(d["encode_ns"] / 1e6 / n, 3),
        "host_bookkeeping_ms_per_req": round(
            d["bookkeeping_ns"] / 1e6 / n, 3
        ),
        "device_wait_ms_per_req": round(d["device_wait_ns"] / 1e6 / n, 3),
        "native_parse_fallbacks": d["parse_fallbacks"],
        # round 12: average submit_many burst size (array-at-a-time
        # admission; 0 bursts means the per-request submission path ran)
        "avg_bulk_submit_rows": round(
            d.get("bulk_submitted_rows", 0) / max(1, d.get("bulk_submits", 0)),
            1,
        ),
    }


def run_timed(fn, n_items: int, passes: int = 3, reset=None) -> list[float]:
    """N timed passes of ``fn`` → items/s per pass (``reset`` runs before
    each timed pass, outside the timed region)."""
    runs = []
    for _ in range(passes):
        if reset is not None:
            reset()
        t0 = time.perf_counter()
        fn()
        runs.append(n_items / (time.perf_counter() - t0))
    return runs
