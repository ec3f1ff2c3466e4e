"""Multi-host plumbing tests (SURVEY.md §7.2 step 10): the distributed
flags flow CLI → Config → bootstrap → ``jax.distributed.initialize`` —
plus the round-14 REAL bring-up smoke: 2 localhost processes form one
global mesh over ``jax.distributed`` (gloo, the CPU default) and serve
host-local rows through the fused SPMD program. Where the platform
cannot form a multi-process mesh the smoke SKIPS LOUDLY (pytest.skip
with the worker tail), never silently."""

from __future__ import annotations

import pytest

from policy_server_tpu.config.cli import build_cli
from policy_server_tpu.config.config import Config
from policy_server_tpu.parallel import mesh as mesh_mod
from policy_server_tpu.server import PolicyServer


def parse_config(tmp_path, *extra: str) -> Config:
    policies = tmp_path / "policies.yml"
    if not policies.exists():
        policies.write_text("{}")
    args = build_cli().parse_args(["--policies", str(policies), *extra])
    return Config.from_args(args)


def test_cli_distributed_flags(tmp_path):
    cfg = parse_config(
        tmp_path,
        "--distributed-coordinator", "coord:8476",
        "--distributed-num-processes", "4",
        "--distributed-process-id", "2",
    )
    assert cfg.distributed_coordinator == "coord:8476"
    assert cfg.distributed_num_processes == 4
    assert cfg.distributed_process_id == 2


def test_distributed_env_fallback(tmp_path, monkeypatch):
    policies = tmp_path / "policies.yml"
    policies.write_text("{}")
    monkeypatch.setenv("KUBEWARDEN_POLICIES", str(policies))
    monkeypatch.setenv("KUBEWARDEN_DISTRIBUTED_COORDINATOR", "c:1234")
    monkeypatch.setenv("KUBEWARDEN_DISTRIBUTED_NUM_PROCESSES", "2")
    monkeypatch.setenv("KUBEWARDEN_DISTRIBUTED_PROCESS_ID", "0")
    cfg = Config.from_args(build_cli().parse_args([]))
    assert cfg.distributed_coordinator == "c:1234"
    assert cfg.distributed_num_processes == 2
    assert cfg.distributed_process_id == 0


@pytest.mark.parametrize(
    "extra",
    [
        ["--distributed-num-processes", "2"],  # rank/size without coordinator
        ["--distributed-process-id", "0"],
        # size without rank (and vice versa) when coordinator is set
        ["--distributed-coordinator", "c:1", "--distributed-num-processes", "2"],
        ["--distributed-coordinator", "c:1", "--distributed-process-id", "0"],
        # rank out of range
        ["--distributed-coordinator", "c:1",
         "--distributed-num-processes", "2", "--distributed-process-id", "2"],
    ],
)
def test_distributed_validation_rejects(tmp_path, extra):
    with pytest.raises(ValueError):
        parse_config(tmp_path, *extra)


def test_initialize_distributed_calls_jax(monkeypatch):
    calls = {}

    def fake_initialize(coordinator_address, num_processes, process_id):
        calls.update(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )

    import jax

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    mesh_mod.initialize_distributed("coord:8476", 8, 3)
    assert calls == {
        "coordinator_address": "coord:8476",
        "num_processes": 8,
        "process_id": 3,
    }


def test_initialize_distributed_failure_leaves_config_alone(monkeypatch):
    """A failed bring-up propagates and changes no jax configuration: the
    CPU collectives implementation stays at jax's own default (a leaked
    selection with no live distributed client once broke every later CPU
    backend initialization in the process)."""
    import jax

    before = jax.config._read("jax_cpu_collectives_implementation")

    def boom(coordinator_address, num_processes, process_id):
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    with pytest.raises(RuntimeError, match="coordinator unreachable"):
        mesh_mod.initialize_distributed("coord:8476", 2, 0)
    assert jax.config._read("jax_cpu_collectives_implementation") == before


def test_initialize_distributed_noop_without_coordinator(monkeypatch):
    import jax

    def boom(*a, **k):  # pragma: no cover - must not run
        raise AssertionError("initialize called without a coordinator")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    mesh_mod.initialize_distributed(None)


def test_bootstrap_invokes_initialize_distributed(tmp_path, monkeypatch):
    """new_from_config runs the DCN bring-up BEFORE building the mesh when
    the coordinator flag is set (src/lib.rs:75-236 is the bootstrap
    analog; the reference has no multi-host counterpart)."""
    seen = {}

    def fake_init(coordinator_address=None, num_processes=None, process_id=None):
        seen.update(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )

    monkeypatch.setattr(mesh_mod, "initialize_distributed", fake_init)
    cfg = parse_config(
        tmp_path,
        "--evaluation-backend", "oracle",  # no device work in this test
        "--distributed-coordinator", "coord:8476",
        "--distributed-num-processes", "2",
        "--distributed-process-id", "1",
    )
    server = PolicyServer.new_from_config(cfg)
    try:
        assert seen == {
            "coordinator_address": "coord:8476",
            "num_processes": 2,
            "process_id": 1,
        }
    finally:
        server.batcher.shutdown()
        server.environment.close()


@pytest.mark.slow
def test_two_process_distributed_smoke():
    """The real multi-host bring-up (round 14, `make multichip`): two
    localhost processes join a gloo process group, build ONE global
    (data:4, policy:2) mesh over 2x4 virtual devices, and each serves
    host-local rows through the fused SPMD program — one device program
    per batch, verdicts bit-exact vs the host oracle on every rank. A
    platform that cannot form a multi-process mesh skips LOUDLY."""
    import __graft_entry__ as graft_entry

    stats = graft_entry.dryrun_distributed(2)
    if stats.get("distributed_smoke") == "SKIPPED":
        pytest.skip(
            "platform cannot form a multi-process jax mesh: "
            + str(stats)
        )
    assert stats["distributed_smoke"] == "PASSED"
    assert stats["processes"] == 2
    assert stats["mesh"] == {"data": 4, "policy": 2}
    assert stats["dispatches_per_batch"] == 1
    assert stats["bit_exact_vs_oracle"] is True
