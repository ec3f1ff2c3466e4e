"""Benchmark suite shim — the suite itself lives in ``tools/bench/``
(round 12: the single file outgrew its shape, ROADMAP item 5). This
entrypoint, its arguments, and every emitted BENCH json key are
unchanged:

    python bench.py [n_requests] [batch_size]

One JSON line per benchmark, the HEADLINE line LAST (config 4, the
32-policy firehose — the driver's recorded metric). Subprocess entry
points (``--config5-child``, ``--native-client``) also route through
here so child invocations stay `python bench.py ...`.

NOT a chip entry point: the parent process builds device environments and
then starts children, and a chip belongs to one process (the children that
are CPU by design assign ``JAX_PLATFORMS=cpu`` and say ``platform`` in
their JSON). Its numbers are CPU counts of work; ``chip_smoke.py`` is the
program that runs on the accelerator until ROADMAP S1 lands."""

from __future__ import annotations

import sys
from pathlib import Path

# invoked as a script: the repo root must be importable for tools.bench
_ROOT = str(Path(__file__).resolve().parent)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from tools.bench.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
