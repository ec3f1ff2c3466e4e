#!/usr/bin/env python3
"""The benchmark's launcher for the server under test.

Runs ``policy_server_tpu.config.cli.main()`` with the given arguments in
the main thread, exactly as ``python -m policy_server_tpu`` does. Traced
and untraced runs use it alike, so the process is the same. One extra
daemon thread serves the run's control directory, because only the
process that holds the chip can trace it or read its memory, and the
program offers no route for either:

* ``trace.json`` ``{"dir": ..., "seconds": ...}`` → ``jax.profiler``
  traces into ``dir`` for that long: ``trace.started`` (with the instant,
  ``time.monotonic()``) appears when the trace runs, ``trace.done`` when
  it is written;
* ``memory.req`` → ``memory.json`` with each device's peak bytes in use.

    python benchmarks/serve.py <control dir> -- <policy-server arguments>
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _write(path: Path, doc: dict) -> None:
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(doc), encoding="utf-8")
    os.replace(tmp, path)


def _control(directory: Path) -> None:
    trace_req = directory / "trace.json"
    memory_req = directory / "memory.req"
    traced = False
    while True:
        time.sleep(0.05)
        if not traced and trace_req.exists():
            traced = True
            import jax

            req = json.loads(trace_req.read_text(encoding="utf-8"))
            began = time.monotonic()
            # device events are what the reducer reads: no Python tracer
            # (tens of MB a second, and it slows the host that is measured)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(req["dir"], profiler_options=options)
            started = time.monotonic()
            _write(directory / "trace.started", {"at": started})
            time.sleep(max(0.0, started + float(req["seconds"])
                           - time.monotonic()))
            stopping = time.monotonic()
            jax.profiler.stop_trace()
            _write(directory / "trace.done", {
                "start_call_s": started - began,
                "traced_s": stopping - started,
                "stop_call_s": time.monotonic() - stopping,
            })
        if memory_req.exists():
            import jax

            memory_req.unlink()
            peaks = []
            for device in jax.devices():
                stats = device.memory_stats() or {}
                peaks.append(stats.get("peak_bytes_in_use"))
            _write(directory / "memory.json", {"peak_bytes_in_use": peaks})


def main(argv: list[str]) -> int:
    if len(argv) < 2 or argv[1] != "--":
        print(__doc__, file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    threading.Thread(
        target=_control, args=(Path(argv[0]),), name="benchmark-control",
        daemon=True,
    ).start()
    from policy_server_tpu.config.cli import main as server_main

    return server_main(argv[2:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
