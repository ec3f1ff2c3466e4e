"""Chip bring-up contracts: where the compile cache lives, one process per
chip (imports touch no backend), native libraries named by what they were
built from, and the chip smoke's CPU rehearsal — including that it FAILS
when a phase is made to fail."""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from policy_server_tpu import failpoints
from policy_server_tpu.runtime import compile_cache
from policy_server_tpu.utils import nativebuild

REPO = Path(__file__).resolve().parent.parent


# -- compile cache placed from outside ----------------------------------------


@pytest.fixture
def jax_cache_config():
    """Snapshot/restore the three jax options configure() may touch."""
    import jax

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    before = {n: jax.config._read(n) for n in names}
    yield jax
    for n, v in before.items():
        jax.config.update(n, v)


def test_cache_dir_from_environment_sets_no_directory(
    monkeypatch, tmp_path, jax_cache_config
):
    jax = jax_cache_config
    before = jax.config._read("jax_compilation_cache_dir")
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path / "outside"))
    info = compile_cache.configure()
    assert info == {
        "dir": str(tmp_path / "outside"),
        "from_env": True,
        "populated_on_entry": False,
    }
    # the variable is JAX's own: the program set no directory in code
    assert jax.config._read("jax_compilation_cache_dir") == before
    assert jax.config._read("jax_persistent_cache_min_compile_time_secs") == 0
    assert jax.config._read("jax_persistent_cache_min_entry_size_bytes") == 0


def test_cache_dir_defaults_to_the_checkout(monkeypatch, jax_cache_config):
    jax = jax_cache_config
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    first = compile_cache.configure()
    second = compile_cache.configure()
    # a fixed path next to the package: never temporary, pid- or
    # time-derived, so a second run of the same checkout finds the first's
    assert first["dir"] == second["dir"] == str(REPO / ".jax_cache")
    assert first["from_env"] is False
    assert jax.config._read("jax_compilation_cache_dir") == first["dir"]


def test_cache_populated_on_entry(monkeypatch, tmp_path, jax_cache_config):
    (tmp_path / "entry").write_bytes(b"x")
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    assert compile_cache.configure()["populated_on_entry"] is True


def test_compile_counter_splits_hits_from_compiles():
    counter = compile_cache.CompileCounter()
    counter._on_duration("/jax/core/compile/backend_compile_duration", 0.5)
    counter._on_duration("/jax/core/compile/backend_compile_duration", 0.25)
    counter._on_duration("/jax/core/compile/jaxpr_trace_duration", 9.0)
    counter._on_event("/jax/compilation_cache/cache_hits")
    counter._on_event("/jax/compilation_cache/cache_misses")
    assert counter.snapshot() == {
        "programs": 2, "cache_hits": 1, "compiled": 1, "seconds": 0.75,
    }


# -- one process per chip ------------------------------------------------------


def test_importing_entry_modules_initialises_no_backend():
    """The server module, the CLI and the prefork worker entry can be
    imported (by a launcher, a supervisor, chip_smoke.py) without taking
    the chip: no JAX backend is initialised by import."""
    code = (
        "import policy_server_tpu.server, policy_server_tpu.config.cli, "
        "policy_server_tpu.runtime.frontend\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    subprocess.run(
        [sys.executable, "-c", code], cwd=str(REPO), check=True, timeout=120,
    )


# -- native libraries named by source + flags ----------------------------------

needs_gxx = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no g++ on this machine"
)


@needs_gxx
def test_native_library_name_follows_source_and_flags(tmp_path, monkeypatch):
    monkeypatch.setattr(nativebuild, "BUILD_DIR", tmp_path / "build")
    src = tmp_path / "demo.cpp"
    src.write_text('extern "C" int answer() { return 1; }\n')
    first = nativebuild.build_shared_library(src)
    assert ctypes.CDLL(str(first)).answer() == 1
    built_at = first.stat().st_mtime_ns
    assert nativebuild.build_shared_library(src) == first
    assert first.stat().st_mtime_ns == built_at  # named, so not rebuilt

    # the source changes while the old library stays on disk, NEWER than
    # the source (a copied tree; the mtime rule would have loaded it)
    src.write_text('extern "C" int answer() { return 2; }\n')
    os.utime(src, ns=(0, 0))
    second = nativebuild.build_shared_library(src)
    assert second != first and first.exists()
    assert ctypes.CDLL(str(second)).answer() == 2

    flagged = nativebuild.build_shared_library(src, ["-DDEMO=1"])
    assert flagged not in (first, second)


@needs_gxx
def test_failed_native_build_raises_with_the_compilers_words(
    tmp_path, monkeypatch
):
    monkeypatch.setattr(nativebuild, "BUILD_DIR", tmp_path / "build")
    src = tmp_path / "broken.cpp"
    src.write_text("this is not C++\n")
    with pytest.raises(nativebuild.NativeBuildError, match="error"):
        nativebuild.build_shared_library(src)
    assert list((tmp_path / "build").glob("*")) == []  # no partial file


def test_failed_encoder_load_fails_the_environment(monkeypatch):
    """The jax backend asks for the native encoder: no silent Python
    encode path behind a 200."""
    from policy_server_tpu.evaluation.environment import (
        EvaluationEnvironmentBuilder,
    )
    from policy_server_tpu.models.policy import parse_policy_entry
    from policy_server_tpu.ops import fastenc

    monkeypatch.setattr(fastenc, "_lib", None)
    monkeypatch.setattr(fastenc, "_lib_error", "injected load failure")
    with pytest.raises(nativebuild.NativeBuildError, match="injected"):
        EvaluationEnvironmentBuilder(backend="jax").build(
            {"priv": parse_policy_entry(
                "priv", {"module": "builtin://pod-privileged"}
            )}
        )


# -- failpoint scopes from the environment string -------------------------------


def test_failpoint_scope_in_config_string():
    failpoints.configure("device.fetch=raise:scoped-fault@default")
    try:
        failpoints.fire("device.fetch")  # unscoped thread (boot warm-up)
        with failpoints.scope("other"):
            failpoints.fire("device.fetch")
        with failpoints.scope("default"):
            with pytest.raises(failpoints.FailpointError, match="scoped"):
                failpoints.fire("device.fetch")
    finally:
        failpoints.reset()


# -- the chip smoke, rehearsed on the CPU --------------------------------------


def _smoke(*args: str, env_extra: dict | None = None):
    env = dict(os.environ)
    env.pop("FAILPOINTS", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        cwd=str(REPO), env=env, capture_output=True, text=True, timeout=1500,
    )


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_passes_and_says_so():
    import json

    proc = _smoke("--platform", "cpu", "--requests", "256")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "platform: cpu" in proc.stdout and "rehearsal: true" in proc.stdout
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= 1 and last["device"]["kind"]


@pytest.mark.slow
def test_chip_smoke_fails_when_a_phase_fails():
    """An armed device.fetch failpoint on the serving dispatches (boot
    warm-up, unscoped, goes through): batches fail, the breaker trips,
    the host oracle starts answering 200s — and the smoke exits non-zero
    without printing a result."""
    proc = _smoke(
        "--platform", "cpu", "--requests", "256",
        env_extra={"FAILPOINTS": "device.fetch=raise:smoke-fault@default"},
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.slow
def test_chip_smoke_without_a_chip_is_a_failure():
    """No --platform cpu: the server under test is told JAX_PLATFORMS=tpu
    and JAX fails; the smoke does not fall back."""
    proc = _smoke("--requests", "64", "--ready-timeout", "120")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
