"""Policy-sharded evaluation: split a large policy set across the mesh's
``policy`` axis, one fused XLA program per shard, data-parallel within.

BASELINE.md config 5 ("8 policies.yml shards pmapped across v5e-8"): very
large or multi-tenant policy sets do not fit one fused program gracefully —
compile time and program size grow with the policy count, and tenants churn
independently. Sharding the *policy* dimension keeps each fused program
small and recompilation local to the shard that changed (preemption-churn
resilience: a resize only recompiles affected shards, SURVEY.md §7.2
step 10).

Policies are heterogeneous code, so this is MPMD: each shard owns a
data-parallel submesh (one row of the global mesh) and its own jitted fused
program; shards dispatch concurrently (JAX dispatch is async — the host
enqueues all shard programs before blocking) and the host routes each
policy_id to its owning shard. This is the deterministic-placement
replacement for the reference's replicas-behind-a-Service scale-out
(SURVEY.md §2.3 last row)."""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Iterator, Mapping

from policy_server_tpu.evaluation.environment import (
    EvaluationEnvironment,
    EvaluationEnvironmentBuilder,
)
from policy_server_tpu.evaluation.errors import PolicyNotFoundError
from policy_server_tpu.models import AdmissionResponse, ValidateRequest
from policy_server_tpu.models.policy import PolicyOrPolicyGroup
from policy_server_tpu.parallel import mesh as mesh_mod


class _Routing:
    """One immutable routing snapshot (shards + policy→shard owner map)
    plus its lifecycle state: dispatches in flight against it and whether
    a resize has retired it. Retired snapshots close when the last
    in-flight dispatch drains — never on a wall-clock timer, so a
    post-churn lazy-compile stall can take arbitrarily long without its
    encode/drain pools being shut down mid-flight."""

    __slots__ = ("shards", "owner", "inflight", "retired", "closed")

    def __init__(
        self, shards: list[EvaluationEnvironment], owner: dict[str, int]
    ) -> None:
        self.shards = shards
        self.owner = owner
        self.inflight = 0  # guarded-by: PolicyShardedEvaluator._snapshot_lock
        self.retired = False  # guarded-by: PolicyShardedEvaluator._snapshot_lock
        self.closed = False  # guarded-by: PolicyShardedEvaluator._snapshot_lock


class PolicyShardedEvaluator:
    """Routes policy_ids to per-shard EvaluationEnvironments.

    Exposes the same validate/validate_batch surface as a single
    environment, so the micro-batcher and the service layer work unchanged
    on top of it."""

    def __init__(
        self,
        policies: Mapping[str, PolicyOrPolicyGroup],
        mesh: Any,
        backend: str = "jax",
        continue_on_errors: bool = False,
        builder_kwargs: dict[str, Any] | None = None,
    ) -> None:
        from concurrent.futures import ThreadPoolExecutor

        self._policies = dict(policies)
        self._backend = backend
        self._continue_on_errors = continue_on_errors
        self._builder_kwargs = dict(builder_kwargs or {})
        self._resize_lock = threading.Lock()
        # overlaps per-shard dispatches in validate_batch; sized to the
        # CONFIGURED policy axis (resize never grows past it)
        self._shard_pool = ThreadPoolExecutor(
            max_workers=max(1, mesh.shape[mesh_mod.POLICY_AXIS]),
            thread_name_prefix="policy-shard",
        )
        # guards snapshot lifecycle state (inflight/retired/closed and the
        # retired list) — resize() AND close() both take it, so retirement
        # bookkeeping is never racily mutated from two paths
        self._snapshot_lock = threading.Lock()
        # snapshots retired by resize() that still have dispatches in
        # flight; each closes when its last dispatch drains — without this
        # every churn event leaks the old shards' worker pools
        self._retired: list[_Routing] = []  # guarded-by: _snapshot_lock
        self.mesh = mesh
        # the operator-configured policy parallelism: resize() re-factors
        # toward this cap, so a transient shrink can grow back
        self._configured_policy_axis = mesh.shape[mesh_mod.POLICY_AXIS]
        self.resizes = 0  # guarded-by: _resize_lock
        # shards+owner swap as ONE _Routing object so routing always reads
        # a consistent pair across a concurrent resize
        # graftcheck: lockfree — one atomic attribute swap (resize)
        self._routing: _Routing = _Routing(*self._build_shards(mesh))

    def _build_shards(
        self, mesh: Any
    ) -> tuple[list[EvaluationEnvironment], dict[str, int]]:
        plans = mesh_mod.plan_policy_shards(list(self._policies), mesh)
        shards: list[EvaluationEnvironment] = []
        owner: dict[str, int] = {}
        # --verdict-cache-size is documented as a TOTAL byte budget:
        # split it across shard environments so an 8-shard mesh does not
        # hold 8× the operator's number resident. (During a resize the
        # retired snapshot's shards keep their caches until drained, so
        # the budget can transiently double — inherent to
        # drain-before-close.)
        shard_kwargs = dict(self._builder_kwargs)
        total_cache = shard_kwargs.get("verdict_cache_size")
        if total_cache and len(plans) > 1:
            shard_kwargs["verdict_cache_size"] = total_cache // len(plans)
        for plan in plans:
            shard_policies = {
                pid: self._policies[pid] for pid in plan.policy_ids
            }
            builder = EvaluationEnvironmentBuilder(
                backend=self._backend,
                continue_on_errors=self._continue_on_errors,
                **shard_kwargs,
            )
            env = builder.build(shard_policies)
            if self._backend == "jax" and plan.mesh.devices.size > 1:
                env.attach_mesh(plan.mesh)
            shards.append(env)
            for pid in plan.policy_ids:
                owner[pid] = plan.shard_index
        return shards, owner

    # -- preemption churn (BASELINE.md config 5) ---------------------------

    def resize(self, devices: list[Any]) -> None:
        """Rebuild/rebalance the shard set over a changed device set — the
        preemption-churn path: a preempted/lost chip shrinks the mesh, the
        policy axis re-factors over the survivors, and every shard
        recompiles (cheap when the persistent XLA compilation cache is
        configured — programs unchanged by the rebalance hit the cache).
        Serving continues on the OLD shards until the new set is fully
        built; the swap is one atomic attribute assignment."""
        if not devices:
            raise ValueError("cannot resize to an empty device set")
        with self._resize_lock:
            new_policy_axis = min(self._configured_policy_axis, len(devices))
            while len(devices) % new_policy_axis:
                new_policy_axis -= 1
            from policy_server_tpu.config.config import MeshSpec

            spec = MeshSpec.parse(
                f"data:{len(devices) // new_policy_axis},"
                f"policy:{new_policy_axis}"
            )
            new_mesh = mesh_mod.make_mesh(spec, devices)
            # atomic swap: in-flight dispatches finish on the old shard
            # environments; new calls route through the new set
            new_routing = _Routing(*self._build_shards(new_mesh))
            with self._snapshot_lock:
                old = self._routing
                self._routing = new_routing
                old.retired = True
                drained = old.inflight == 0
                if not drained:
                    self._retired.append(old)
            self.mesh = new_mesh
            self.resizes += 1
            if drained:
                self._close_snapshot(old)

    @contextlib.contextmanager
    def _pin_routing(self) -> Iterator[_Routing]:
        """Pin the current routing snapshot for one dispatch: a concurrent
        resize() cannot close its shard environments until this dispatch
        (and every other pinned one) drains."""
        with self._snapshot_lock:
            snap = self._routing
            snap.inflight += 1
        try:
            yield snap
        finally:
            with self._snapshot_lock:
                snap.inflight -= 1
                close_now = (
                    snap.retired and snap.inflight == 0 and not snap.closed
                )
                if close_now:
                    with contextlib.suppress(ValueError):
                        self._retired.remove(snap)
            if close_now:
                self._close_snapshot(snap)

    def _close_snapshot(self, snap: _Routing) -> None:
        # test-and-set UNDER _snapshot_lock (ADVICE r5 #3): close()
        # racing a draining _pin_routing could otherwise both pass the
        # unsynchronized guard and double-invoke env.close() — benign
        # only by EvaluationEnvironment.close's documented idempotence,
        # which this class must not silently depend on
        with self._snapshot_lock:
            if snap.closed:
                return
            snap.closed = True
        for env in snap.shards:
            env.close()

    # -- routing -----------------------------------------------------------

    @property
    def shards(self) -> list[EvaluationEnvironment]:
        return self._routing.shards

    @staticmethod
    def _shard_in(snap: _Routing, policy_id: str) -> EvaluationEnvironment:
        top = policy_id.split("/")[0]
        idx = snap.owner.get(top)
        if idx is None:
            raise PolicyNotFoundError(policy_id)
        return snap.shards[idx]

    def _shard_of(self, policy_id: str) -> EvaluationEnvironment:
        return self._shard_in(self._routing, policy_id)

    # -- environment surface ----------------------------------------------

    def policy_ids(self) -> list[str]:
        out: list[str] = []
        for env in self.shards:
            out.extend(env.policy_ids())
        return sorted(out)

    def get_policy_mode(self, policy_id: str):
        return self._shard_of(policy_id).get_policy_mode(policy_id)

    def get_policy_allowed_to_mutate(self, policy_id: str) -> bool:
        return self._shard_of(policy_id).get_policy_allowed_to_mutate(policy_id)

    def should_always_accept_requests_made_inside_of_namespace(
        self, namespace: str
    ) -> bool:
        return any(
            env.should_always_accept_requests_made_inside_of_namespace(namespace)
            for env in self.shards
        )

    def pre_eval_hooks_of(self, target):  # MicroBatcher compatibility
        from policy_server_tpu.evaluation.environment import pre_eval_hooks_of

        return pre_eval_hooks_of(target)

    def payload_for(self, target, request):  # MicroBatcher compatibility
        # the context service is shared across shard builders, so any shard
        # produces the same snapshot view
        return self.shards[0].payload_for(target, request)

    def _lookup_top_level(self, pid):
        return self._shard_of(str(pid))._lookup_top_level(pid)

    def validate(
        self, policy_id: str, request: ValidateRequest
    ) -> AdmissionResponse:
        with self._pin_routing() as snap:
            return self._shard_in(snap, policy_id).validate(policy_id, request)

    @property
    def host_fastpath_requests(self) -> int:
        return sum(env.host_fastpath_requests for env in self._routing.shards)

    @property
    def oracle_fallbacks(self) -> int:
        return sum(env.oracle_fallbacks for env in self._routing.shards)

    def record_dispatch_failure(self, policy_ids: Any = None) -> None:
        """Route a batcher-observed device failure (watchdog abandonment,
        device-future exception) to the breakers of the shards that owned
        the batch's policies — per-shard containment: a hung shard trips
        alone while the others keep their device path. Without
        ``policy_ids`` (no attribution), every shard takes the mark."""
        snap = self._routing
        if not policy_ids:
            for env in snap.shards:
                env.record_dispatch_failure()
            return
        hit: set[int] = set()
        for pid in policy_ids:
            idx = snap.owner.get(str(pid).split("/")[0])
            if idx is not None and idx not in hit:
                hit.add(idx)
                snap.shards[idx].record_dispatch_failure()

    @property
    def breaker_all_open(self) -> bool:
        """True only when EVERY shard's device path is tripped — the
        'tripped-everything' state the --degraded-mode policy keys on."""
        shards = self._routing.shards
        return bool(shards) and all(env.breaker_all_open for env in shards)

    @property
    def breaker_stats(self) -> dict[str, int]:
        """Breaker counters summed across shards (open_shards counts the
        currently-tripped subset; total_shards sizes it)."""
        totals: dict[str, int] = {}
        for env in self._routing.shards:
            for k, v in env.breaker_stats.items():
                totals[k] = totals.get(k, 0) + v
        return totals

    @property
    def warmup_dispatches(self) -> int:
        """Device dispatches ONE warmup((b,)) call issues: every shard
        warms sequentially, each once per shape schema — the RTT-seed
        normalizer for runtime/batcher.py (ADVICE r5 #4)."""
        return max(
            1,
            sum(env.warmup_dispatches for env in self._routing.shards),
        )

    @property
    def plane_program_compiles(self) -> int:
        """Columnar plane structures traced, summed across shards — the
        batcher's compile-window guard for its RTT estimator."""
        return sum(
            env.plane_program_compiles for env in self._routing.shards
        )

    @property
    def batch_dedup_hits(self) -> int:
        return sum(env.batch_dedup_hits for env in self._routing.shards)

    @property
    def dedup_stats(self) -> dict[str, int]:
        """Two-tier dedup counters summed across shards (capacity sums
        too: each shard owns its own byte budget)."""
        totals: dict[str, int] = {}
        for env in self._routing.shards:
            for k, v in env.dedup_stats.items():
                totals[k] = totals.get(k, 0) + v
        return totals

    @property
    def host_profile(self) -> dict[str, int]:
        """Host-pipeline decomposition counters summed across shards."""
        totals: dict[str, int] = {}
        for env in self._routing.shards:
            for k, v in env.host_profile.items():
                totals[k] = totals.get(k, 0) + v
        return totals

    @property
    def supports_host_fastpath(self) -> bool:
        """MicroBatcher latency fast-path capability (see
        EvaluationEnvironment.supports_host_fastpath)."""
        return all(
            env.supports_host_fastpath for env in self._routing.shards
        )

    def validate_batch(
        self,
        items: list[tuple[str, ValidateRequest]],
        run_hooks: bool = True,
        prefer_host: bool = False,
        audit: bool = False,
        live: bool = False,
    ) -> list[AdmissionResponse | Exception]:
        """Partition the batch by owning shard, dispatch every shard's fused
        program, merge in submission order.

        Multi-shard batches run each shard's evaluation on the shard pool:
        a shard's ``validate_batch`` blocks in ``jax.device_get`` while its
        submesh executes, so serial shard calls would serialize DEVICE time
        across shards that own disjoint devices (measured 8-shard cost:
        ~3x a single fused environment on the same batch). Threads overlap
        both the device executions (XLA runs with the GIL released) and
        each shard's host-side encode with other shards' device time.
        Each environment is only ever entered by one thread at a time —
        environments are shard-private."""
        with self._pin_routing() as snap:  # one consistent routing snapshot
            shards, owner = snap.shards, snap.owner
            per_shard: dict[int, list[int]] = {}
            results: list[AdmissionResponse | Exception | None] = (
                [None] * len(items)
            )
            for i, (pid, _) in enumerate(items):
                top = pid.split("/")[0]
                idx = owner.get(top)
                if idx is None:
                    results[i] = PolicyNotFoundError(pid)
                    continue
                per_shard.setdefault(idx, []).append(i)

            def run_shard(idx: int, indices: list[int]):
                shard_items = [items[i] for i in indices]
                return shards[idx].validate_batch(
                    shard_items, run_hooks=run_hooks,
                    prefer_host=prefer_host, audit=audit, live=live,
                )

            if len(per_shard) > 1:
                futures = {
                    idx: self._shard_pool.submit(run_shard, idx, indices)
                    for idx, indices in per_shard.items()
                }
                shard_outs = {idx: f.result() for idx, f in futures.items()}
            else:
                shard_outs = {
                    idx: run_shard(idx, indices)
                    for idx, indices in per_shard.items()
                }
            for idx, indices in per_shard.items():
                for i, r in zip(indices, shard_outs[idx]):
                    results[i] = r
            return results  # type: ignore[return-value]

    def warmup(self, batch_sizes: tuple[int, ...] = (1,)) -> None:
        for env in self.shards:
            env.warmup(batch_sizes)

    def close(self) -> None:
        """Server-shutdown surface (EvaluationEnvironment.close parity):
        close every shard environment — current AND resize-retired — and
        stop the dispatch pool. Shutdown overrides the drain-before-close
        rule: the process is going away."""
        with self._snapshot_lock:
            snaps = [self._routing] + self._retired
            self._retired = []
        for snap in snaps:
            self._close_snapshot(snap)
        self._shard_pool.shutdown(wait=False)
