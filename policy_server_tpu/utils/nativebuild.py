"""Build driver shared by the native libraries (``csrc/*.cpp``).

Each library compiles on demand with g++ into ``build/`` under a name that
carries a hash of its source text and of the whole compiler command:

    build/<name>-<python soabi>-<hash>.so

so a file on disk can only be the library built from THIS source with THESE
flags. ``build/`` is git-ignored but travels with a copied tree (container
layers, the chip tool's copy of the disk), where file times say nothing
about which source a library was built from: a stale library is simply
never named, and the one that is named is never rebuilt.

A failed build raises :class:`NativeBuildError` with the compiler's own
words. Whether that is fatal is the caller's decision: a path that was asked
for (``--frontend native``, the jax backend's native encoder) makes it an
error; nothing falls back to Python silently.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys
import sysconfig
from pathlib import Path
from typing import Sequence

REPO_ROOT = Path(__file__).resolve().parents[2]
BUILD_DIR = REPO_ROOT / "build"

# POLICY_SERVER_NATIVE_SAN=asan (tools/sanitize_lane.py) builds an
# ASan+UBSan-instrumented variant; the flags are part of the hash, so the
# sanitize lane never touches the production libraries
_SAN_FLAGS = ("-O1", "-g", "-fsanitize=address,undefined",
              "-fno-sanitize-recover=all")


class NativeBuildError(RuntimeError):
    """A native library could not be built or loaded."""


def library_path(source: Path, flags: Sequence[str]) -> Path:
    """Where the library built from ``source`` with ``flags`` lives."""
    tag = sysconfig.get_config_var("SOABI") or (
        f"py{sys.version_info[0]}{sys.version_info[1]}"
    )
    digest = hashlib.sha256()
    digest.update(source.read_bytes())
    digest.update("\0".join(flags).encode())
    return BUILD_DIR / f"{source.stem}-{tag}-{digest.hexdigest()[:16]}.so"


def build_shared_library(
    source: Path,
    extra_flags: Sequence[str] = (),
    libs: Sequence[str] = (),
    timeout: float = 180.0,
) -> Path:
    """Return the shared library built from ``source``, compiling it first
    unless the file named by its hash already exists. ``libs`` (``-l…``)
    follow the source on the command line: link order matters to ld."""
    san = os.environ.get("POLICY_SERVER_NATIVE_SAN", "") == "asan"
    flags = [
        *(_SAN_FLAGS if san else ("-O2",)),
        "-shared", "-fPIC", "-std=c++17", *extra_flags,
    ]
    try:
        out = library_path(source, [*flags, *libs])
    except OSError as e:
        raise NativeBuildError(f"cannot read {source}: {e}") from e
    if out.exists():
        return out
    BUILD_DIR.mkdir(exist_ok=True)
    partial = out.with_name(f"{out.name}.{os.getpid()}.partial")
    try:
        subprocess.run(
            ["g++", *flags, str(source), "-o", str(partial), *libs],
            check=True, capture_output=True, timeout=timeout,
        )
        # atomic: a concurrent or killed build never leaves a truncated
        # file under the final name
        os.replace(partial, out)
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        raise NativeBuildError(
            f"g++ failed to build {source.name}: {e}\n"
            + detail.decode("utf-8", "replace")[-2000:]
        ) from e
    finally:
        partial.unlink(missing_ok=True)
    return out
