"""On-demand profiling endpoints' engine.

Reference parity: src/profiling.rs —
* ``start_one_cpu_profile`` (profiling.rs:54-98): single-flight, default
  99 Hz / 30 s (profiling.rs:44-51), google-pprof protobuf output. Here the
  CPU profile is a host-side cProfile capture (pstats text), plus an
  optional JAX device trace: TPU "CPU time" lives in XLA, so the device
  trace (jax.profiler, viewable in TensorBoard/Perfetto) is the TPU-native
  equivalent of the sampling profiler.
  ``take_device_trace`` (GET /debug/pprof/trace) takes one.
* heap profile (profiling.rs:160-174, jemalloc_pprof): here
  ``tracemalloc`` host snapshot + per-device HBM stats from
  ``jax.Device.memory_stats()`` — the memory that actually matters on TPU.
"""

from __future__ import annotations

import collections
import json
import sys
import tempfile
import threading
import time
import tracemalloc
from dataclasses import dataclass
from pathlib import Path

import jax

DEFAULT_PROFILING_FREQUENCY = 99  # Hz (profiling.rs:44-47)
DEFAULT_PROFILING_INTERVAL = 30  # seconds (profiling.rs:48-51)

# single-flight: only one profile at a time (profiling.rs:13-21, 61-63)
_cpu_lock = threading.Lock()


class ProfileInProgress(Exception):
    pass


@dataclass
class CpuProfile:
    text: str
    interval: float


def start_one_cpu_profile(
    interval: float, frequency: int = DEFAULT_PROFILING_FREQUENCY
) -> CpuProfile:
    """Process-wide sampling profile (the pprof-crate analog): every
    1/frequency seconds, snapshot ALL thread stacks via
    ``sys._current_frames`` and aggregate collapsed stacks. Output is
    flamegraph-collapsed text (``frame;frame;frame count`` lines), sorted by
    count. Single-flight: concurrent calls fail fast like the reference's
    mutex try_lock (profiling.rs:61-63)."""
    if not _cpu_lock.acquire(blocking=False):
        raise ProfileInProgress("a CPU profile is already being generated")
    try:
        period = 1.0 / max(1, frequency)
        stacks: collections.Counter[str] = collections.Counter()
        own = threading.get_ident()
        deadline = time.perf_counter() + interval
        while time.perf_counter() < deadline:
            for tid, frame in sys._current_frames().items():
                if tid == own:
                    continue
                parts = []
                f = frame
                while f is not None and len(parts) < 64:
                    code = f.f_code
                    parts.append(f"{code.co_filename}:{code.co_name}")
                    f = f.f_back
                stacks[";".join(reversed(parts))] += 1
            time.sleep(period)
        lines = [
            f"{stack} {count}"
            for stack, count in sorted(
                stacks.items(), key=lambda kv: -kv[1]
            )
        ]
        return CpuProfile(text="\n".join(lines) + "\n", interval=interval)
    finally:
        _cpu_lock.release()


_memory_profiling_active = False


def activate_memory_profiling() -> None:
    """Lazily start host allocation tracking at boot when --enable-pprof
    (profiling.rs:160-174)."""
    global _memory_profiling_active
    if not _memory_profiling_active:
        tracemalloc.start()
        _memory_profiling_active = True


def heap_profile() -> bytes:
    """Host top allocations + per-device HBM stats as JSON."""
    doc: dict = {"devices": [], "host_top_allocations": []}
    for dev in jax.devices():
        stats = {}
        try:
            stats = dev.memory_stats() or {}
        except Exception:  # pragma: no cover - backend-dependent
            pass
        doc["devices"].append(
            {"id": dev.id, "platform": dev.platform, "memory_stats": stats}
        )
    if _memory_profiling_active:
        snapshot = tracemalloc.take_snapshot()
        for stat in snapshot.statistics("lineno")[:50]:
            doc["host_top_allocations"].append(
                {
                    "location": str(stat.traceback),
                    "size_bytes": stat.size,
                    "count": stat.count,
                }
            )
    return json.dumps(doc, indent=2).encode()


DEFAULT_TRACE_SECONDS = 3.0
MAX_TRACE_SECONDS = 60.0

# single-flight like the CPU profile: the profiler holds one session
_trace_lock = threading.Lock()


def take_device_trace(seconds: float) -> bytes:
    """A JAX/XLA trace of this process for ``seconds``, as the bytes of
    its ``.xplane.pb`` (TensorBoard's profile plugin, or
    ``jax.profiler.ProfileData.from_serialized_xspace``). Device events
    plus the host's TraceMe events and no Python tracer (tens of MB a
    second, and it slows the host being looked at): the benchmark
    launcher's settings. Each launch of the fused program is in it as a
    ``ps:launch`` host event carrying its batch id and a
    ``perf_counter_ns`` reading, which lines the trace up with
    ``/debug/timeline``. Single-flight."""
    if not _trace_lock.acquire(blocking=False):
        raise ProfileInProgress("a device trace is already being taken")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        with tempfile.TemporaryDirectory(prefix="policy-server-trace-") as d:
            jax.profiler.start_trace(d, profiler_options=options)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
            found = sorted(Path(d).glob("**/*.xplane.pb"))
            if not found:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            return found[-1].read_bytes()
    finally:
        _trace_lock.release()
