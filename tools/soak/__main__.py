"""``python -m tools.soak`` — run a seeded soak against the full stack.

    python -m tools.soak --preset smoke            # the CI mini-soak
    python -m tools.soak --preset full             # cluster-scale soak
    python -m tools.soak --duration 120 --seed 7   # custom

Exit code 1 when the SLO gate fails; the trend artifact lands at
``BENCH_soak_<tag>.json`` either way.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile


def main(argv: list[str] | None = None) -> int:
    # accelerator-less boxes (CI, dev laptops) soak on the virtual CPU
    # backend; a real TPU host can export JAX_PLATFORMS itself
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # a private, cold compile cache by design (set before jax is
    # imported): the restart storm compares this run's cold boot with its
    # own warm reboots, whatever the checkout's cache holds
    cache_dir = tempfile.mkdtemp(prefix="soak-xla-cache-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir

    ap = argparse.ArgumentParser(prog="tools.soak", description=__doc__)
    ap.add_argument("--preset", choices=["smoke", "full", "custom"],
                    default="custom")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--duration", type=float, default=None)
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--target-rps", type=float, default=None)
    ap.add_argument("--objects", type=int, default=None)
    ap.add_argument("--frontend", choices=["native", "python"],
                    default=None)
    ap.add_argument("--http-workers", type=int, default=None)
    ap.add_argument("--p99-budget-ms", type=float, default=None)
    ap.add_argument("--tenants", type=int, default=None,
                    help="tenancy mix size: ten-0 storms a tight quota, "
                         "the rest are paced victims (0/1 disables)")
    ap.add_argument("--tls", action="store_true",
                    help="terminate TLS (native when available) and run "
                         "every client + abuse surface over it, adding "
                         "the handshake-abuse waves and a windowed "
                         "tls.handshake accept outage to the storm")
    ap.add_argument("--artifact", default=None)
    ap.add_argument("--tag", default=None)
    args = ap.parse_args(argv)

    from tools.soak.engine import SoakEngine, SoakSettings

    over = {"seed": args.seed}
    for name, attr in (
        ("duration", "duration"), ("clients", "clients"),
        ("target_rps", "target_rps"), ("objects", "objects"),
        ("frontend", "frontend"), ("http_workers", "http_workers"),
        ("p99_budget_ms", "p99_budget_ms"), ("artifact", "artifact"),
        ("tag", "tag"), ("tenants", "tenants"),
    ):
        v = getattr(args, name)
        if v is not None:
            over[attr] = v
    if args.tls:
        over["tls"] = True
    if args.preset == "smoke":
        settings = SoakSettings.smoke(**over)
    elif args.preset == "full":
        settings = SoakSettings.full(**over)
    else:
        settings = SoakSettings(**over)
    try:
        return SoakEngine(settings).run()
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
