"""PolicyServer — bootstrap pipeline and run loop.

Reference parity: src/lib.rs —
* ``PolicyServer::new_from_config`` (lib.rs:75-236): trust root → download →
  precompile → evaluation environment → state → TLS → routers. Here the
  pipeline is: fetch/resolve modules → build + typecheck IR programs →
  fused-program warmup (the rayon precompile analog, lib.rs:287-307) →
  micro-batcher → aiohttp routers.
* ``PolicyServer::run`` (lib.rs:238-280): API server and readiness server
  run concurrently; readiness binds only AFTER the API server is up
  (Notify handshake, lib.rs:239-268).

The wasmtime epoch ticker (lib.rs:176-190) has no analog here: the batcher
enforces the request deadline directly (runtime/batcher.py)."""

from __future__ import annotations

import asyncio
import ssl
from typing import Callable

from aiohttp import web

from policy_server_tpu.api import profiling
from policy_server_tpu.api.handlers import build_readiness_router, build_router
from policy_server_tpu.api.state import ApiServerState
from policy_server_tpu.config.config import Config
from policy_server_tpu.evaluation.environment import (
    EvaluationEnvironment,
    EvaluationEnvironmentBuilder,
)
from policy_server_tpu.evaluation.precompiled import PolicyModule
from policy_server_tpu.runtime.batcher import MicroBatcher
from policy_server_tpu.telemetry import setup_metrics
from policy_server_tpu.telemetry import metrics as metrics_names
from policy_server_tpu.telemetry.tracing import logger


class _PendingRespawn:
    """Placeholder in the worker-process table for a slot whose respawn is
    delayed by crash-loop backoff (its previous process has been reaped)."""

    def __init__(self, returncode):
        self.returncode = returncode

    def poll(self):  # duck-type subprocess.Popen for liveness checks
        return self.returncode


class PolicyServer:
    """The bootstrapped server (reference PolicyServer, lib.rs:64-72)."""

    def __init__(
        self,
        config: Config,
        state: ApiServerState,
        tls_context: ssl.SSLContext | None,
    ) -> None:
        self.config = config
        self.state = state  # carries the serving epoch's env + batcher
        self.tls_context = tls_context
        self._ready = asyncio.Event()
        self._runners: list[web.AppRunner] = []
        self.api_port: int | None = None
        self.readiness_port: int | None = None
        # prefork HTTP frontend state (runtime/frontend.py)
        self._bridge = None
        self._worker_procs: list = []
        self._bridge_socket: str | None = None
        # native HTTP frontend (runtime/native_frontend.py); None under
        # --frontend python or after a native-load fallback
        self._native_frontend = None
        # native TLS termination manager (NativeTlsManager); None under
        # plaintext, --native-tls off, or the aiohttp-TLS fallback
        self._native_tls = None
        # self-heal watchdog (supervision.py): rebuilds a wedged batcher
        # dispatch loop / frontend drainer; started with the servers
        self._selfheal = None

    # The serving environment/batcher are the CURRENT EPOCH's — a hot
    # reload (lifecycle.py) rebinds the state fields, so everything that
    # reads them through the server (tests, stop(), logging) follows the
    # promoted epoch automatically.
    @property
    def environment(self) -> EvaluationEnvironment:
        return self.state.evaluation_environment

    @property
    def batcher(self) -> MicroBatcher:
        return self.state.batcher

    @property
    def lifecycle(self):
        return self.state.lifecycle

    # -- bootstrap (lib.rs:75-236) -----------------------------------------

    @classmethod
    def new_from_config(
        cls,
        config: Config,
        module_resolver: Callable[[str], PolicyModule] | None = None,
    ) -> "PolicyServer":
        import time as _time

        boot_t0 = _time.monotonic()
        if config.enable_metrics:
            registry = setup_metrics()
            # Reference pushes metrics over OTLP gRPC (metrics.rs:14-29).
            # Here push activates when a collector endpoint is configured;
            # the Prometheus pull endpoint stays on either way (fallback
            # that also removes a collector hop from the serving path).
            import os as _os

            from policy_server_tpu.telemetry import otlp as _otlp

            if _os.environ.get(_otlp.ENDPOINT_ENV):
                _otlp.install_metrics_pusher(registry)
        # flight recorder (round 18, telemetry/flightrec.py): installed
        # BEFORE any batcher/environment is built so warmup dispatches
        # already record. Always on by default; the phase histogram
        # feeds the process-wide metrics registry (one funnel: /metrics
        # pull + OTLP push).
        from policy_server_tpu.telemetry import flightrec as _flightrec

        if config.flight_recorder:
            from policy_server_tpu.telemetry import default_registry as _dr

            _flightrec.install(
                _flightrec.FlightRecorder(
                    capacity=config.recorder_ring_events,
                    row_sample_rate=config.recorder_row_sample_rate,
                    registry=_dr(),
                )
            )
        else:
            _flightrec.install(None)
        if config.enable_pprof:
            profiling.activate_memory_profiling()
            if config.http_workers > 1:
                logger.warning(
                    "--enable-pprof with --http-workers: the pprof routes "
                    "are served by the main process only; a fraction of "
                    "connections on the shared port land on workers and "
                    "404 — hit the endpoint repeatedly or set "
                    "--http-workers 1 when profiling"
                )
        # persistent XLA compilation cache, placed BEFORE the first compile
        # (JAX_COMPILATION_CACHE_DIR wins, else <checkout>/.jax_cache), and
        # the compile counter the boot report and /metrics read
        from policy_server_tpu.runtime import compile_cache

        cache_info = compile_cache.configure()
        compiles = compile_cache.counter()
        compiles_at_entry = compiles.snapshot()
        if config.distributed_coordinator:
            # Multi-host bring-up BEFORE any device enumeration: the mesh
            # built below must span every process's devices (SURVEY.md §7.2
            # step 10; ICI within a slice, DCN across slices).
            from policy_server_tpu.parallel.mesh import initialize_distributed

            initialize_distributed(
                coordinator_address=config.distributed_coordinator,
                num_processes=config.distributed_num_processes,
                process_id=config.distributed_process_id,
            )
            logger.info(
                "jax.distributed initialized",
                extra={"span_fields": {
                    "coordinator": config.distributed_coordinator,
                    "process_id": config.distributed_process_id,
                    "num_processes": config.distributed_num_processes,
                }},
            )

        # -- durable last-good state store (round 17, statestore.py) ------
        # Opened BEFORE any fetch/compile so the whole boot can lean on
        # it: the fsck pass quarantines torn/corrupt entries (never
        # fatal), the last-good manifest pins artifact digests for the
        # zero-network warm path, and the boot report below records how
        # warm this boot actually was.
        statestore = None
        boot_report: dict = {"warm": False}
        fingerprint = None
        pinned_artifacts: dict[str, str] = {}

        def _read_text(path) -> str | None:
            if not path:
                return None
            try:
                from pathlib import Path as _Path

                return _Path(path).read_text(encoding="utf-8")
            except OSError:
                return None

        if config.state_dir:
            from policy_server_tpu.statestore import (
                StateStore,
                compute_fingerprint,
            )

            statestore = StateStore(config.state_dir)
            fingerprint = compute_fingerprint({
                "policy_ids": sorted(config.policies),
                "backend": config.evaluation_backend,
                "predicate_opt": config.predicate_opt,
                "columnar": config.columnar,
                "jax": _versions()["jax"],
            })
            manifest = statestore.last_good_manifest("default")
            boot_report.update(
                manifest_epoch=(
                    manifest.get("epoch") if manifest is not None else None
                ),
                manifest_found=manifest is not None,
                fingerprint_match=(
                    manifest is not None
                    and manifest.get("fingerprint") == fingerprint
                ),
            )
            # warm-boot artifact pins: tenants whose CURRENT policies
            # config is byte-identical to their last-good manifest load
            # those artifacts straight from the cache — zero network
            pinned_artifacts.update(
                statestore.pinned_digests(
                    "default", _read_text(config.policies_path)
                )
            )
            if config.tenants is not None:
                for t_name, t_spec in config.tenants.tenants.items():
                    pinned_artifacts.update(
                        statestore.pinned_digests(
                            t_name, _read_text(t_spec.policies_path)
                        )
                    )

        # offline sigstore trust root, loaded ONCE and shared by the
        # module resolver (artifact verification) and the evaluation
        # builder (wasm keyless v2/verify capability). The fetch/crypto
        # subsystem is optional — absent, keyless paths reject in-band
        # (lib.rs:309-336 analog; absent root = degraded like the
        # reference's failed TUF fetch, lib.rs:81-89).
        trust_root = None
        try:
            from policy_server_tpu.fetch.keyless import KeylessError, TrustRoot

            try:
                trust_root = TrustRoot.load_from_cache_dir(
                    config.sigstore_cache_dir
                )
            except KeylessError as e:
                # degrade like the reference's failed TUF fetch
                # (lib.rs:81-89): warn and continue without keyless —
                # verification configs that REQUIRE keyless will still
                # fail loudly per-requirement at policy bootstrap
                logger.warning(
                    "cannot load sigstore trust root; keyless "
                    "verification disabled: %s", e,
                )
        except ImportError:
            pass

        resolver = module_resolver
        if resolver is None and (config.sources or config.verification_config
                                 or _needs_fetch(config)):
            try:
                from policy_server_tpu.fetch import make_module_resolver
            except ImportError as e:
                raise RuntimeError(
                    "this configuration references non-builtin policy modules "
                    "or fetch settings, but the fetch subsystem is not "
                    "available"
                ) from e
            resolver = make_module_resolver(
                config,
                trust_root=trust_root,
                statestore=statestore,
                pinned_artifacts=pinned_artifacts,
            )

        context_service = _build_context_service(config)

        # registry client for the oci/v1/manifest_digest host capability:
        # the same token-auth/TLS/docker-config machinery registry://
        # pulls use (reference wires its registry sources into the
        # callback handler, src/lib.rs:91-125). Policies still opt in via
        # allowNetworkCapabilities before any egress happens.
        oci_digest_source = None
        try:
            from policy_server_tpu.fetch.downloader import Downloader

            oci_digest_source = Downloader(
                sources=config.sources,
                docker_config_json_path=config.docker_config_json_path,
            ).manifest_digest
        except ImportError:  # fetch subsystem unavailable: capability
            pass  # fails loudly in-band instead

        builder_kwargs = dict(
            module_resolver=resolver,
            always_accept_admission_reviews_on_namespace=(
                config.always_accept_admission_reviews_on_namespace
            ),
            context_service=context_service,
            # wasm guests get the configured wall-clock budget (the
            # epoch-interruption analog: fuel bounds instructions, this
            # bounds TIME, reference src/lib.rs:176-190)
            wasm_wall_clock_budget=config.policy_timeout,
            # offline sigstore trust root for the keyless v2/verify host
            # capability
            wasm_trust_root=trust_root,
            wasm_oci_digest_source=oci_digest_source,
            # bit-exact verdict cache / row dedup (0 disables)
            verdict_cache_size=config.verdict_cache_size,
            # device circuit breaker thresholds (one breaker per shard
            # environment; resilience.CircuitBreaker)
            breaker_config=dict(
                failure_threshold=config.breaker_failure_threshold,
                window_seconds=config.breaker_window_seconds,
                cooldown_seconds=config.breaker_cooldown_seconds,
            ),
            # columnar device transport (round 12)
            columnar=config.columnar,
            # predicate-program optimizer (round 15)
            predicate_opt=config.predicate_opt,
        )
        environment = _build_environment(config, builder_kwargs)

        # shadow recorder: the hot-reload canary's replay ring (every
        # epoch's batcher feeds the SAME ring, so a reload replays the
        # traffic the previous epoch actually served)
        reload_enabled = config.policy_reload_mode != "off"
        recorder = None
        if reload_enabled:
            from policy_server_tpu.lifecycle import ShadowRecorder

            recorder = ShadowRecorder(capacity=config.reload_canary_requests)

        # audit snapshot store: the background scanner's cluster view,
        # fed by every epoch's batcher (dirty-set tracking survives hot
        # reloads for the same reason the canary ring does)
        audit_enabled = config.audit_mode != "off"
        snapshot_store = None
        audit_resume: dict | None = None
        if audit_enabled:
            from policy_server_tpu.audit import SnapshotStore

            snapshot_store = SnapshotStore(
                max_bytes=config.audit_max_snapshot_bytes
            )
            if statestore is not None:
                # warm boot: rebuild the inventory from the audit spill
                # so the watch feed RESUMES from its spilled cursors
                # instead of re-LISTing the whole cluster (round 17)
                audit_resume = statestore.load_audit_spill()
                if audit_resume is not None:
                    restored = snapshot_store.restore_rows(
                        audit_resume["rows"]
                    )
                    boot_report["audit_rows_restored"] = restored
                    logger.info(
                        "audit snapshot restored from the state-store "
                        "spill", extra={"span_fields": {
                            "rows": restored,
                            "kinds_with_cursor": len(audit_resume["rvs"]),
                        }},
                    )
            if config.audit_resources_file:
                snapshot_store.seed_from_file(config.audit_resources_file)

        # persistent (object × policy) verdict matrix (round 23,
        # audit/matrix.py): built BEFORE the batchers (lookup admission
        # consults it on the submit paths) and restored AFTER the
        # snapshot (warm-boot cell validation hashes the restored rows).
        # Columns are keyed by policy-CONTENT fingerprint, so a stale
        # spilled policy set invalidates its columns by construction.
        verdict_matrix = None
        if audit_enabled and config.audit_matrix:
            from policy_server_tpu.audit import VerdictMatrix

            verdict_matrix = VerdictMatrix(
                snapshot=snapshot_store,
                statestore=statestore,
                spill_interval_seconds=config.audit_matrix_spill_seconds,
            )
            verdict_matrix.set_columns(config.policies or {}, 0)
            if statestore is not None:
                boot_report["matrix_cells_restored"] = (
                    verdict_matrix.restore()
                )

        # multi-tenant scaffolding (round 16, tenancy.py): the shared
        # weighted-fair dispatch scheduler and the default tenant's
        # admission quota exist BEFORE the default batcher is built so
        # the default tenant rides the same machinery as named tenants.
        # Without a manifest both stay None and every batcher below is
        # bit-identical to the single-tenant build.
        tenants_manifest = config.tenants
        fair_scheduler = None
        default_admission = None
        default_spec = None
        if tenants_manifest is not None:
            from policy_server_tpu.runtime.scheduler import (
                FairDispatchScheduler,
            )
            from policy_server_tpu.tenancy import (
                DEFAULT_TENANT,
                TenantAdmission,
            )

            default_spec = tenants_manifest.default
            weights = {
                name: spec.weight
                for name, spec in tenants_manifest.tenants.items()
            }
            weights[DEFAULT_TENANT] = default_spec.weight
            fair_scheduler = FairDispatchScheduler(
                max_concurrent=tenants_manifest.max_concurrent_dispatches,
                weights=weights,
            )
            if (
                default_spec.quota_rows_per_second > 0
                or default_spec.max_inflight > 0
            ):
                default_admission = TenantAdmission(
                    DEFAULT_TENANT,
                    rows_per_second=default_spec.quota_rows_per_second,
                    burst=default_spec.quota_burst,
                    max_inflight=default_spec.max_inflight,
                )

        import dataclasses

        def build_epoch_environment(policies):
            # defined BEFORE the batcher builders: the shard router
            # rebuilds sibling environments through it at boot, on every
            # reload epoch, and on rollback
            return _build_environment(
                dataclasses.replace(config, policies=dict(policies)),
                builder_kwargs,
            )

        from policy_server_tpu.supervision import SupervisorStats

        supervisor = SupervisorStats()

        from policy_server_tpu.runtime.shards import build_serving_shards

        def make_batcher(
            env, tenant_name, admission, spec, tenant_recorder, tracker
        ) -> MicroBatcher:
            """ONE batcher construction path for boot, every reload
            epoch, and every tenant — the knobs must not drift between
            generations. Per-tenant deadline class / degraded mode
            override the process defaults when the spec carries them."""
            request_timeout = config.request_timeout_ms
            degraded = config.degraded_mode
            if spec is not None:
                if spec.request_timeout_ms is not None:
                    request_timeout = spec.request_timeout_ms
                if spec.degraded_mode is not None:
                    degraded = spec.degraded_mode
            return MicroBatcher(
                env,
                max_batch_size=config.max_batch_size,
                batch_timeout_ms=config.batch_timeout_ms,
                policy_timeout=config.policy_timeout,
                queue_capacity=config.pool_size * config.max_batch_size,
                host_fastpath_threshold=config.host_fastpath_threshold,
                latency_budget_ms=config.latency_budget_ms,
                request_timeout_ms=request_timeout,
                degraded_mode=degraded,
                shadow_recorder=tenant_recorder,
                audit_tracker=(
                    tracker if config.audit_observe_admissions else None
                ),
                # lookup admission stays scoped like the audit scanner:
                # only the DEFAULT tenant (the one feeding the snapshot
                # store) consults the matrix
                verdict_matrix=(
                    verdict_matrix if tracker is not None else None
                ),
                admission=admission,
                scheduler=fair_scheduler,
                tenant=tenant_name,
            )

        def build_batcher(env):
            """The default tenant's serving plane (also every reload
            epoch's, via the lifecycle manager): the plain MicroBatcher
            when --serving-shards is 1 (router BYPASS — the path is
            byte-identical to every previous round), else a ShardRouter
            over M full stacks whose sibling environments are rebuilt
            from env.source_policies. The tenant's admission quota and
            the fair scheduler are SHARED across its shards, so quotas
            compose instead of multiplying by M."""
            return build_serving_shards(
                env,
                lambda e: make_batcher(
                    e, "default", default_admission, default_spec,
                    recorder, snapshot_store,
                ),
                build_epoch_environment,
                config.serving_shards,
                heartbeat_seconds=config.shard_heartbeat_seconds,
                supervisor=supervisor,
                statestore=statestore,
            )

        batcher = build_batcher(environment)
        if config.warmup_at_boot and config.evaluation_backend == "jax":
            batcher.warmup()
        batcher.start()

        state = ApiServerState(
            evaluation_environment=environment,
            batcher=batcher,
            hostname=config.hostname,
            enable_pprof=config.enable_pprof,
            ready=not reload_enabled,  # lifecycle flips it below
            admin_token=config.reload_admin_token,
            statestore=statestore,
            boot_report=boot_report,
            supervisor=supervisor,
            audit_matrix=verdict_matrix,
            audit_stream_max_clients=config.audit_stream_max_clients,
        )

        def build_oracle_environment(policies):
            # the canary referee: the host-oracle backend over the
            # SAME candidate set, sharing the boot module resolver
            oracle_builder = EvaluationEnvironmentBuilder(
                backend="oracle",
                continue_on_errors=config.continue_on_errors,
                **builder_kwargs,
            )
            return oracle_builder.build(dict(policies))

        if reload_enabled:
            from policy_server_tpu.lifecycle import PolicyLifecycleManager

            read_policies = None
            if config.policies_path:
                path = config.policies_path

                def read_policies():
                    # (policies, yaml_text): the manifest must persist
                    # the exact bytes this reload parsed, never a later
                    # re-read (config/config.read_policies_source)
                    from policy_server_tpu.config.config import (
                        read_policies_source,
                    )

                    return read_policies_source(path)

            state.lifecycle = PolicyLifecycleManager(
                state=state,
                build_environment=build_epoch_environment,
                build_oracle_environment=build_oracle_environment,
                build_batcher=build_batcher,
                recorder=recorder,
                read_policies=read_policies,
                policies_path=config.policies_path,
                mode=config.policy_reload_mode,
                canary_requests=config.reload_canary_requests,
                divergence_threshold=config.reload_divergence_threshold,
                warmup=(
                    config.warmup_at_boot
                    and config.evaluation_backend == "jax"
                ),
                statestore=statestore,
                fingerprint=fingerprint,
            )
            # first epoch = the boot build; flips state.ready (readiness
            # honesty: compiled + warmed before the probe says 200). The
            # yaml text is the same read the warm-boot pin decision used.
            state.lifecycle.install_first_epoch(
                environment, batcher, config.policies,
                policies_yaml=_read_text(config.policies_path),
            )
            state.lifecycle.start_watching()

        if audit_enabled:
            from policy_server_tpu.audit import (
                AuditScanner,
                PolicyReportStore,
            )

            state.audit = AuditScanner(
                state=state,
                snapshot=snapshot_store,
                reports=PolicyReportStore(),
                mode=config.audit_mode,
                interval_seconds=config.audit_interval_seconds,
                batch_size=config.audit_batch_size,
                matrix=verdict_matrix,
            )
            if boot_report.get("matrix_cells_restored", 0) > 0:
                # warm matrix resume: the restore proved the covered
                # rows current under the serving column fingerprints, so
                # the boot pass is a DIRTY sweep of the remainder — not
                # a whole-cluster re-judge
                state.audit.skip_boot_full_sweep()
            if state.lifecycle is not None:
                # epoch coherence: a promotion re-judges everything under
                # the new set; a rollback stales the revoked epoch's rows
                state.lifecycle.set_epoch_hooks(
                    on_promote=state.audit.on_promote,
                    on_rollback=state.audit.on_rollback,
                )
                if config.audit_matrix_whatif and verdict_matrix is not None:
                    # cluster what-if (round 23, stretch): during the
                    # shadow canary, evaluate the CANDIDATE's changed
                    # columns against the live snapshot and keep the
                    # verdict-flip diff for the reload-status surface
                    state.lifecycle.set_whatif_matrix(verdict_matrix)
            if config.audit_watch:
                # live-cluster feed: list+watch events populate the
                # snapshot store the scanner sweeps, so the audited
                # inventory tracks the cluster instead of only webhook
                # traffic (audit/watch_feed.py)
                state.audit_watch = _build_audit_watch_feed(
                    config, snapshot_store,
                    statestore=statestore, resume=audit_resume,
                )
                state.audit.watch_feed = state.audit_watch
            state.audit.start()

        if tenants_manifest is not None:
            # -- named tenants (round 16, tenancy.py): one full epoch
            # stack per tenant — own environment (verdict cache +
            # breaker), own batcher (admission quota, deadline class,
            # degraded mode), own lifecycle (reload/canary/rollback +
            # digest watch on ITS policies file). All tenants' policy
            # sets lower over the same device fleet/mesh; the fair
            # scheduler time-shares dispatch slots between them. The
            # audit scanner stays scoped to the DEFAULT tenant: named
            # tenants' traffic never feeds its snapshot store.
            from policy_server_tpu import failpoints
            from policy_server_tpu.lifecycle import (
                PolicyLifecycleManager,
                ShadowRecorder,
            )
            from policy_server_tpu.tenancy import (
                Tenant,
                TenantAdmission,
                TenantManager,
                TenantState,
            )

            manager = TenantManager(scheduler=fair_scheduler)
            manager.add(
                Tenant(DEFAULT_TENANT, default_spec, state,
                       default_admission)
            )

            def read_tenant_boot_policies(name: str, spec):
                """One tenant's boot-time ``(policies, yaml_text)`` read,
                carrying the crash-tolerance contract: the
                ``tenant.reload`` chaos site fires here too (an
                unreadable manifest at BOOT is the same failure as one
                at reload), and with a state store the read degrades
                LOUDLY to the tenant's last-good manifest bytes instead
                of fail-closing the whole boot."""
                import yaml as _yaml

                from policy_server_tpu.config.config import (
                    read_policies_source,
                )
                from policy_server_tpu.models.policy import parse_policies

                try:
                    with failpoints.scope(name):
                        failpoints.fire("tenant.reload")
                    return read_policies_source(spec.policies_path)
                except Exception as e:  # noqa: BLE001 — every read
                    # failure takes the same last-good path
                    if statestore is not None:
                        m = statestore.last_good_manifest(name)
                        if m is not None and m.get("policies_yaml"):
                            statestore.count_degraded_load()
                            logger.error(
                                "tenant %s policies read FAILED (%s); "
                                "booting DEGRADED on the last-good "
                                "manifest (epoch %s) — fix the manifest "
                                "and reload to clear this",
                                name, e, m.get("epoch"),
                            )
                            return (
                                parse_policies(
                                    _yaml.safe_load(m["policies_yaml"])
                                ),
                                m["policies_yaml"],
                            )
                    raise

            for tenant_name, spec in tenants_manifest.tenants.items():
                t_policies, t_policies_yaml = read_tenant_boot_policies(
                    tenant_name, spec
                )
                t_admission = None
                if spec.quota_rows_per_second > 0 or spec.max_inflight > 0:
                    t_admission = TenantAdmission(
                        tenant_name,
                        rows_per_second=spec.quota_rows_per_second,
                        burst=spec.quota_burst,
                        max_inflight=spec.max_inflight,
                    )
                t_recorder = (
                    ShadowRecorder(capacity=config.reload_canary_requests)
                    if reload_enabled else None
                )
                t_env = build_epoch_environment(t_policies)
                t_state = TenantState(name=tenant_name)

                def t_build_batcher(
                    env, _n=tenant_name, _a=t_admission, _s=spec,
                    _r=t_recorder,
                ):
                    # per-tenant shard set (round 22): the tenant's
                    # admission quota and the process-wide fair
                    # scheduler are SHARED across its shards, so tenant
                    # fairness and in-flight caps compose across the
                    # set instead of multiplying by M
                    return build_serving_shards(
                        env,
                        lambda e: make_batcher(e, _n, _a, _s, _r, None),
                        build_epoch_environment,
                        config.serving_shards,
                        heartbeat_seconds=config.shard_heartbeat_seconds,
                        supervisor=supervisor,
                        statestore=statestore,
                    )

                def t_read_policies(_spec=spec):
                    # the tenant.reload chaos site: an armed fault here
                    # rejects THIS tenant's reload at the fetch stage
                    # (last-good keeps serving); other tenants' pipelines
                    # are untouched. Returns (policies, yaml_text) so the
                    # manifest persists what this reload actually parsed.
                    from policy_server_tpu.config.config import (
                        read_policies_source,
                    )

                    failpoints.fire("tenant.reload")
                    return read_policies_source(_spec.policies_path)

                t_batcher = t_build_batcher(t_env)
                if config.warmup_at_boot and config.evaluation_backend == "jax":
                    t_batcher.warmup()
                t_batcher.start()
                if reload_enabled:
                    t_state.lifecycle = PolicyLifecycleManager(
                        state=t_state,
                        build_environment=build_epoch_environment,
                        build_oracle_environment=build_oracle_environment,
                        build_batcher=t_build_batcher,
                        recorder=t_recorder,
                        read_policies=t_read_policies,
                        policies_path=spec.policies_path,
                        mode=config.policy_reload_mode,
                        canary_requests=config.reload_canary_requests,
                        divergence_threshold=(
                            config.reload_divergence_threshold
                        ),
                        warmup=(
                            config.warmup_at_boot
                            and config.evaluation_backend == "jax"
                        ),
                        tenant=tenant_name,
                        statestore=statestore,
                        fingerprint=fingerprint,
                    )
                    t_state.lifecycle.install_first_epoch(
                        t_env, t_batcher, t_policies,
                        policies_yaml=t_policies_yaml,
                    )
                    t_state.lifecycle.start_watching()
                else:
                    t_state.evaluation_environment = t_env
                    t_state.batcher = t_batcher
                    t_state.ready = True
                manager.add(
                    Tenant(tenant_name, spec, t_state, t_admission)
                )
                logger.info(
                    "tenant serving", extra={"span_fields": {
                        "tenant": tenant_name,
                        "policies": len(t_policies),
                        "weight": spec.weight,
                        "quota_rows_per_second": spec.quota_rows_per_second,
                    }},
                )
            state.tenants = manager

        def runtime_stats():
            # one locked snapshot per scrape: bare attribute reads from
            # here would be the cross-module dirty reads the batcher's
            # guarded-by annotations forbid. Read through STATE, not the
            # bootstrap locals: a hot reload rebinds the epoch pointer,
            # and the scrape must follow the serving epoch.
            batcher = state.batcher
            environment = state.evaluation_environment
            bstats = batcher.stats_snapshot()
            yield (
                metrics_names.BATCHES_DISPATCHED, "counter",
                "Micro-batches dispatched to the device",
                bstats["batches_dispatched"],
            )
            yield (
                metrics_names.REQUESTS_DISPATCHED, "counter",
                "Requests dispatched through the micro-batcher",
                bstats["requests_dispatched"],
            )
            yield (
                metrics_names.DEADLINE_ABANDONED_BATCHES, "counter",
                "Device batches abandoned by the dispatch watchdog",
                bstats["deadline_abandoned_batches"],
            )
            yield (
                metrics_names.QUEUE_DEPTH, "gauge",
                "Requests waiting for batch formation",
                batcher.queue_depth(),
            )
            yield (
                metrics_names.ORACLE_FALLBACKS, "counter",
                "Requests routed to the host oracle (schema overflow)",
                getattr(environment, "oracle_fallbacks", 0) or 0,
            )
            yield (
                metrics_names.HOST_FASTPATH_BATCHES, "counter",
                "Micro-batches answered by the host latency fast-path",
                bstats["host_fastpath_batches"],
            )
            yield (
                metrics_names.HOST_FASTPATH_DECLINED_BATCHES, "counter",
                "Micro-batches small enough for the host fast-path that "
                "rode the device because the pipeline was full",
                bstats["host_fastpath_declined_batches"],
            )
            yield (
                metrics_names.HOST_FASTPATH_REQUESTS, "counter",
                "Requests answered by the host latency fast-path",
                getattr(environment, "host_fastpath_requests", 0) or 0,
            )
            yield (
                metrics_names.BUDGET_ROUTED_BATCHES, "counter",
                "Batches routed host-side by the latency-budget check",
                bstats["budget_routed_batches"],
            )
            # Two-tier dedup + verdict cache (round 6): hit rate is the
            # cache's whole value proposition, so it must be visible on a
            # running server
            dedup = getattr(environment, "dedup_stats", None) or {}
            yield (
                metrics_names.DEDUP_BLOB_HITS, "counter",
                "Pre-encode blob-tier dedup hits (exact payload replays "
                "that skipped encoding)",
                dedup.get("blob_cache_hits", 0),
            )
            yield (
                metrics_names.DEDUP_BLOB_MISSES, "counter",
                "Pre-encode blob-tier dedup misses",
                dedup.get("blob_cache_misses", 0),
            )
            yield (
                metrics_names.VERDICT_CACHE_HITS, "counter",
                "Row-tier verdict cache hits (post-encode, "
                "uid-insensitive)",
                dedup.get("cache_hits", 0),
            )
            yield (
                metrics_names.VERDICT_CACHE_MISSES, "counter",
                "Row-tier verdict cache misses",
                dedup.get("cache_misses", 0),
            )
            yield (
                metrics_names.VERDICT_CACHE_BYTES, "gauge",
                "Resident bytes across both verdict-cache tiers",
                dedup.get("cache_bytes", 0) + dedup.get("blob_cache_bytes", 0),
            )
            yield (
                metrics_names.VERDICT_CACHE_EVICTIONS, "counter",
                "Verdict-cache entries pushed out by the byte budget, by "
                "tier",
                [(("blob",), dedup.get("blob_cache_evictions", 0)),
                 (("row",), dedup.get("cache_evictions", 0))],
                ("tier",),
            )
            yield (
                metrics_names.VERDICT_CACHE_PUTS, "counter",
                "Entries put into the verdict cache, both tiers together",
                dedup.get("cache_puts", 0) + dedup.get("blob_cache_puts", 0),
            )
            yield (
                metrics_names.VERDICT_CACHE_PUT_BYTES, "counter",
                "Bytes the byte budget accounted for those entries (key + "
                "row + a constant each)",
                dedup.get("cache_put_bytes", 0)
                + dedup.get("blob_cache_put_bytes", 0),
            )
            yield (
                metrics_names.BATCH_DEDUP_HITS, "counter",
                "Rows answered by an identical row in the same batch",
                dedup.get("batch_dup_hits", 0),
            )
            yield (
                metrics_names.FRAGMENT_HITS, "counter",
                "Cache-hit rows answered as pre-serialized response "
                "fragments (zero per-row materialization)",
                dedup.get("fragment_hits", 0),
            )
            # Host-pipeline decomposition (round 6): where the
            # per-row host time goes on the native dispatch path
            profile = getattr(environment, "host_profile", None) or {}
            yield (
                metrics_names.HOST_ENCODE_SECONDS, "counter",
                "Host time in payload-blob build + native batch encode",
                profile.get("encode_ns", 0) / 1e9,
            )
            yield (
                metrics_names.HOST_ENCODE_CPU_SECONDS, "counter",
                "CPU time of the encoding thread over the same intervals "
                "(wall minus this: off a core, waiting for the GIL or "
                "descheduled)",
                profile.get("encode_cpu_ns", 0) / 1e9,
            )
            yield (
                metrics_names.HOST_ENCODE_ROWS, "counter",
                "Rows through the native encoder (blob-tier hits skip it)",
                profile.get("encode_rows", 0),
            )
            yield (
                metrics_names.HOST_ENCODE_PYTHON_STRINGS, "counter",
                "String leaves of encoded rows that the native encoder's "
                "mirror of the intern table had not seen, resolved in "
                "Python (per encoded row: ~0 once warm)",
                profile.get("encode_python_strings", 0),
            )
            yield (
                metrics_names.HOST_ENCODE_MIRROR_ENTRIES, "gauge",
                "Strings the native encoders' mirrors of the intern table "
                "hold (bounded; past the bound misses stay in Python)",
                profile.get("encode_mirror_entries", 0),
            )
            yield (
                metrics_names.HOST_BOOKKEEPING_SECONDS, "counter",
                "Host time in dedup tiers + slot/LRU bookkeeping",
                profile.get("bookkeeping_ns", 0) / 1e9,
            )
            yield (
                metrics_names.DISPATCH_WAIT_SECONDS, "counter",
                "Host time blocked on device results",
                profile.get("dispatch_wait_ns", 0) / 1e9,
            )
            yield (
                metrics_names.DISPATCHED_ROWS, "counter",
                "Unique rows actually shipped to the device",
                profile.get("dispatched_rows", 0),
            )
            # Resilience surface (round 7): shedding, deadline drops,
            # breaker state/transitions, degraded answers, fetch retries
            yield (
                metrics_names.SHED_REQUESTS, "counter",
                "Requests shed at admission (429 + Retry-After)",
                bstats["shed_requests"],
            )
            yield (
                metrics_names.EXPIRED_DROPPED, "counter",
                "Expired rows dropped before encode/dispatch (no dead "
                "work)",
                bstats["expired_dropped"],
            )
            yield (
                metrics_names.DEGRADED_RESPONSES, "counter",
                "Requests answered by the --degraded-mode policy while "
                "the device breaker was fully tripped",
                bstats["degraded_responses"],
            )
            breaker = getattr(environment, "breaker_stats", None) or {}
            yield (
                metrics_names.BREAKER_OPEN_SHARDS, "gauge",
                "Device shards whose circuit breaker is currently "
                "tripped (open or half-open)",
                breaker.get("open_shards", 0),
            )
            yield (
                metrics_names.BREAKER_TRIPS, "counter",
                "Circuit breaker CLOSED/HALF_OPEN -> OPEN transitions",
                breaker.get("trips", 0),
            )
            yield (
                metrics_names.BREAKER_RECOVERIES, "counter",
                "Circuit breaker HALF_OPEN -> CLOSED recoveries",
                breaker.get("recoveries", 0),
            )
            yield (
                metrics_names.BREAKER_PROBES, "counter",
                "Half-open recovery probe dispatches admitted",
                breaker.get("probes", 0),
            )
            yield (
                metrics_names.BREAKER_SHORT_CIRCUITED, "counter",
                "Requests served host-side because a breaker was open",
                breaker.get("short_circuited_requests", 0),
            )
            try:
                from policy_server_tpu.fetch.downloader import retry_stats

                fetch_retries = retry_stats()
            except ImportError:  # fetch subsystem unavailable
                fetch_retries = {}
            yield (
                metrics_names.FETCH_RETRY_ATTEMPTS, "counter",
                "Transient policy-fetch failures retried with backoff",
                fetch_retries.get("attempts", 0),
            )
            yield (
                metrics_names.FETCH_RETRY_GIVEUPS, "counter",
                "Policy-fetch operations that exhausted the retry budget",
                fetch_retries.get("giveups", 0),
            )
            # Policy-lifecycle surface (round 9): hot-reload promotions,
            # rejected candidates, rollbacks, canary volume, and the
            # serving epoch — a bad policy push must be LOUD on the
            # dashboard even though last-good kept serving
            lstats = (
                state.lifecycle.stats() if state.lifecycle is not None
                else {}
            )
            yield (
                metrics_names.POLICY_RELOADS, "counter",
                "Policy hot-reload promotions (new epoch serving)",
                lstats.get("reloads", 0),
            )
            yield (
                metrics_names.POLICY_RELOAD_FAILURES, "counter",
                "Policy reload candidates rejected (fetch/compile/canary "
                "failure) — last-good kept serving",
                lstats.get("reload_failures", 0),
            )
            yield (
                metrics_names.POLICY_RELOAD_ROLLBACKS, "counter",
                "Reverts to the last-good policy set: rejected canaries "
                "plus explicit POST /policies/rollback",
                lstats.get("rollbacks", 0),
            )
            yield (
                metrics_names.RELOAD_CANARY_REPLAYS, "counter",
                "Recorded/synthetic requests replayed through candidate "
                "epochs during shadow canary",
                lstats.get("canary_replays", 0),
            )
            yield (
                metrics_names.RELOAD_CANARY_DIVERGENCES, "counter",
                "Canary replays whose candidate verdict diverged from "
                "the host oracle",
                lstats.get("canary_divergences", 0),
            )
            yield (
                metrics_names.POLICY_EPOCH, "gauge",
                "Monotonic number of the currently serving policy epoch "
                "(0 = the boot set)",
                lstats.get("epoch", 0),
            )
            # Background audit scanner (round 10): lane throughput and
            # preemptions from the batcher, sweep cadence / report
            # freshness / snapshot footprint from the scanner. All zero
            # with --audit-mode off (the families still export so the
            # dashboard panels resolve on every deployment).
            yield (
                metrics_names.AUDIT_BATCHES_DISPATCHED, "counter",
                "Best-effort audit-lane batches dispatched on idle slots",
                bstats["audit_batches_dispatched"],
            )
            yield (
                metrics_names.AUDIT_ROWS_DISPATCHED, "counter",
                "Rows of the audit-lane batches dispatched: they answer "
                "no request and count under no answer source",
                bstats["audit_rows_dispatched"],
            )
            yield (
                metrics_names.AUDIT_PREEMPTIONS, "counter",
                "Audit batches re-queued because live work arrived first",
                bstats["audit_preemptions"],
            )
            yield (
                metrics_names.AUDIT_OBSERVE_SECONDS, "counter",
                "Wall time the dispatch path spent recording served "
                "objects into the audit snapshot store",
                bstats["audit_observe_ns"] / 1e9,
            )
            yield (
                metrics_names.AUDIT_LANE_DEPTH, "gauge",
                "Audit batches waiting for an idle dispatch slot",
                batcher.audit_lane_depth(),
            )
            astats = state.audit.stats() if state.audit is not None else {}
            yield (
                metrics_names.AUDIT_ROWS_SCANNED, "counter",
                "Resource x policy rows the audit scanner has judged",
                astats.get("rows_scanned", 0),
            )
            yield (
                metrics_names.AUDIT_FULL_SWEEPS, "counter",
                "Completed full audit sweeps (boot, epoch promotions, "
                "rollbacks)",
                astats.get("full_sweeps", 0),
            )
            yield (
                metrics_names.AUDIT_DIRTY_SWEEPS, "counter",
                "Completed dirty-set audit sweeps (interval cadence)",
                astats.get("dirty_sweeps", 0),
            )
            yield (
                metrics_names.AUDIT_SWEEP_ERRORS, "counter",
                "Audit sweeps aborted by a fault (retried on the next "
                "trigger)",
                astats.get("sweep_errors", 0),
            )
            yield (
                metrics_names.AUDIT_PAUSED_SWEEPS, "counter",
                "Audit sweeps skipped while the device breaker was open",
                astats.get("paused_sweeps", 0),
            )
            yield (
                metrics_names.AUDIT_REPORT_FRESHNESS, "gauge",
                "Seconds since the last completed full audit sweep "
                "(-1 before the first)",
                astats.get("freshness_seconds", -1.0),
            )
            yield (
                metrics_names.AUDIT_REPORTS_RESIDENT, "gauge",
                "Audit report rows currently held",
                astats.get("reports_resident", 0),
            )
            yield (
                metrics_names.AUDIT_REPORTS_STALE, "gauge",
                "Audit report rows stamped by a rolled-back policy epoch",
                astats.get("reports_stale", 0),
            )
            yield (
                metrics_names.AUDIT_SNAPSHOT_RESOURCES, "gauge",
                "Cluster resources held in the audit snapshot store",
                astats.get("snapshot_resources", 0),
            )
            yield (
                metrics_names.AUDIT_SNAPSHOT_BYTES, "gauge",
                "Resident bytes of the audit snapshot store",
                astats.get("snapshot_bytes", 0),
            )
            yield (
                metrics_names.AUDIT_SNAPSHOT_EVICTIONS, "counter",
                "Resources the byte budget pushed out of the audit "
                "snapshot store, oldest first",
                astats.get("snapshot_evictions", 0),
            )
            yield (
                metrics_names.AUDIT_OBJECTS_UNJUDGED, "counter",
                "Of those, resources pushed out before a sweep had "
                "judged them under every policy: what the byte budget "
                "costs in coverage",
                astats.get("objects_unjudged", 0),
            )
            # Native HTTP front-end (round 11): framing throughput, parse
            # fallbacks (Python stays the parse oracle), serialization
            # split, and the framing/queue legs of the per-stage time
            # decomposition. All zero with --frontend python (families
            # still export so the dashboard panels resolve everywhere).
            nstats = (
                state.native_frontend.stats()
                if state.native_frontend is not None
                else {}
            )
            yield (
                metrics_names.NATIVE_HTTP_REQUESTS, "counter",
                "HTTP requests framed by the native (GIL-free C++) "
                "front-end",
                nstats.get("http_requests", 0),
            )
            yield (
                metrics_names.NATIVE_PARSE_FALLBACKS, "counter",
                "Requests the native AdmissionReview parser declined and "
                "shipped to the Python parse oracle (floats, duplicate "
                "keys, malformed bodies)",
                nstats.get("parse_fallbacks", 0),
            )
            yield (
                metrics_names.NATIVE_RING_FULL, "counter",
                "Requests answered 503 because the native submission "
                "ring was full (drainer overrun)",
                nstats.get("ring_full_rejections", 0),
            )
            yield (
                metrics_names.NATIVE_VERDICTS_SERIALIZED, "counter",
                "Responses serialized natively (common verdict shape)",
                nstats.get("responses_native_serialized", 0),
            )
            yield (
                metrics_names.NATIVE_PYTHON_SERIALIZED, "counter",
                "Responses rendered by Python behind the native frontend "
                "(errors, mutations, exotic status fields)",
                nstats.get("responses_python_serialized", 0),
            )
            yield (
                metrics_names.NATIVE_FRAMING_SECONDS, "counter",
                "Native-thread time in HTTP framing, AdmissionReview "
                "canonicalization, and response serialization",
                nstats.get("framing_ns", 0) / 1e9,
            )
            yield (
                metrics_names.NATIVE_INFLIGHT, "gauge",
                "Requests accepted by the native frontend still awaiting "
                "their completion",
                nstats.get("inflight", 0),
            )
            yield (
                metrics_names.QUEUE_WAIT_SECONDS, "counter",
                "Cumulative time requests spent queued between batcher "
                "submission and batch formation",
                bstats["queue_wait_ns"] / 1e9,
            )
            # Array-at-a-time serving path + columnar transport (round
            # 12): bulk admission volume, wire bytes vs the row-packed
            # equivalent, delta-column hit rate, donation, and the
            # device-resident zero-constant footprint. All zero with
            # --columnar off / the python submission paths (families
            # still export so dashboard panels resolve everywhere).
            yield (
                metrics_names.BULK_SUBMITS, "counter",
                "submit_many bursts admitted (one queue-lock "
                "acquisition each)",
                bstats["bulk_submits"],
            )
            yield (
                metrics_names.BULK_SUBMITTED_ROWS, "counter",
                "Rows admitted through submit_many bursts",
                bstats["bulk_submitted_rows"],
            )
            yield (
                metrics_names.WIRE_BYTES_SHIPPED, "counter",
                "Bytes actually shipped to the device by the columnar "
                "transport (a wire buffer a launch; a column set's index "
                "vectors once, when it first launches)",
                profile.get("wire_bytes_shipped", 0),
            )
            yield (
                metrics_names.LAUNCH_H2D_ARRAYS, "counter",
                "Host arrays the columnar launches handed to the device "
                "(one wire buffer each; none for an all-zero batch)",
                profile.get("launch_h2d_arrays", 0),
            )
            yield (
                metrics_names.LAUNCH_NATIVE_WIRE, "counter",
                "Columnar launches that shipped the wire buffer their "
                "chunk's native encode call wrote (the others built it in "
                "numpy: a cold string, a column set that grew or is "
                "compiling, an all-zero batch)",
                profile.get("launch_native_wire", 0),
            )
            yield (
                metrics_names.WIRE_BYTES_PACKED_EQUIV, "counter",
                "Bytes the row-packed transport form would have shipped "
                "for the same dispatches",
                profile.get("wire_bytes_packed_equiv", 0),
            )
            yield (
                metrics_names.WIRE_ROWS, "counter",
                "Rows shipped by the columnar transport (bytes/row = "
                "wire_bytes_shipped / this)",
                profile.get("wire_rows", 0),
            )
            yield (
                metrics_names.DELTA_COLS_SHIPPED, "counter",
                "32-bit feature columns shipped (delta columns with any "
                "nonzero value, after power-of-two padding)",
                profile.get("delta_cols_shipped", 0),
            )
            yield (
                metrics_names.DELTA_COLS_TOTAL, "counter",
                "32-bit feature columns in the dispatched schemas (hit "
                "rate = 1 - shipped/total)",
                profile.get("delta_cols_total", 0),
            )
            yield (
                metrics_names.RESIDENT_CONST_BYTES, "counter",
                "Bytes of elided zero planes/columns materialized as "
                "device-resident constants of compiled columnar programs",
                profile.get("resident_const_bytes", 0),
            )
            # Live watch feed + connection-abuse hardening + soak-window
            # SLOs (round 13). All zero without --audit-watch / the
            # native frontend / a running soak (families still export so
            # dashboard panels resolve everywhere).
            yield (
                metrics_names.WATCH_EVENTS_APPLIED, "counter",
                "Kubernetes watch events applied to the audit snapshot "
                "store (ADDED/MODIFIED supersede, DELETED evicts)",
                astats.get("watch_events_applied", 0),
            )
            yield (
                metrics_names.WATCH_EVENTS_DROPPED, "counter",
                "Watch events dropped by the bounded feed queue (each "
                "forces a counted full re-LIST resync of its kind)",
                astats.get("watch_events_dropped", 0),
            )
            yield (
                metrics_names.WATCH_RESYNCS, "counter",
                "Full re-LIST resyncs of the audit watch feed (410 "
                "expiry, transport fault, queue overflow, or the "
                "staleness-bounding interval)",
                astats.get("watch_resyncs", 0),
            )
            yield (
                metrics_names.NATIVE_IDLE_CLOSES, "counter",
                "Native-frontend connections reaped by the idle or "
                "read (slowloris) timeout",
                nstats.get("idle_timeout_closes", 0),
            )
            yield (
                metrics_names.NATIVE_CONN_CAP_REJECTS, "counter",
                "Connections answered an in-band 503 because the "
                "native frontend's connection cap was reached",
                nstats.get("conn_cap_rejections", 0),
            )
            # Native TLS termination (round 20). The expiry gauge and
            # reload counters follow certs.py through the state, so
            # they export under the aiohttp TLS fallback too; the
            # handshake counters come from the native loops and are
            # zero under aiohttp termination or plaintext (families
            # still export so dashboard panels resolve everywhere).
            _reloadable = getattr(state, "tls_reloadable", None)
            _tlsmgr = getattr(state, "native_tls", None)
            _expiry = (
                _reloadable.identity_not_after()
                if _reloadable is not None
                else None
            )
            _tls_reloads, _tls_reload_failures = (
                _reloadable.counters()
                if _reloadable is not None
                else (0, 0)
            )
            yield (
                metrics_names.TLS_CERT_EXPIRY_SECONDS, "gauge",
                "Seconds until the serving TLS identity's notAfter "
                "(negative = expired; 0 when TLS is off or the leaf "
                "is undecodable)",
                (_expiry - _time.time()) if _expiry is not None else 0,
            )
            yield (
                metrics_names.TLS_HANDSHAKES_OK, "counter",
                "TLS handshakes completed by the native frontend",
                nstats.get("tls_handshakes_ok", 0),
            )
            yield (
                metrics_names.TLS_HANDSHAKES_FAILED, "counter",
                "Native TLS handshakes that failed hard (bad record, "
                "mTLS client-CA rejection, injected tls.handshake "
                "faults)",
                nstats.get("tls_handshakes_failed", 0),
            )
            yield (
                metrics_names.TLS_HANDSHAKE_TIMEOUTS, "counter",
                "Native TLS handshakes reaped by the arrival timeout "
                "(byte drips never refresh it — the TLS-layer "
                "slowloris defense)",
                nstats.get("tls_handshake_timeouts", 0),
            )
            yield (
                metrics_names.TLS_HANDSHAKE_DISCONNECTS, "counter",
                "Connections that disconnected mid-handshake before "
                "the native TLS handshake completed",
                nstats.get("tls_handshake_disconnects", 0),
            )
            yield (
                metrics_names.TLS_CLEAN_CLOSES, "counter",
                "Native TLS connections closed with a close_notify "
                "alert (in-band rejections included — no "
                "truncation-looking RSTs for well-behaved clients)",
                nstats.get("tls_clean_closes", 0),
            )
            yield (
                metrics_names.TLS_GENERATIONS, "counter",
                "SSL_CTX generations installed on the native loops "
                "(boot + each successful hot-rotation; established "
                "connections drain on the generation they pinned)",
                _tlsmgr.snapshot()["generations"] if _tlsmgr else 0,
            )
            yield (
                metrics_names.TLS_RELOADS, "counter",
                "TLS identity/client-CA hot reloads applied by "
                "certs.py (SIGHUP or digest-watch rotation)",
                _tls_reloads,
            )
            yield (
                metrics_names.TLS_RELOAD_FAILURES, "counter",
                "TLS reload attempts that failed validation; the "
                "last-good identity kept serving each time",
                _tls_reload_failures,
            )
            yield (
                metrics_names.TLS_NATIVE_TERMINATION, "gauge",
                "1 when TLS terminates on the native epoll loops, 0 "
                "under the aiohttp terminator or plaintext",
                1 if _tlsmgr is not None else 0,
            )
            # Predicate-program optimizer (round 15). Optimizer facts
            # are static per serving epoch (the pass re-runs for every
            # reload candidate); gauges follow the epoch pointer. All
            # zero with --predicate-opt off (families still export so
            # dashboard panels resolve everywhere).
            ostats = getattr(environment, "optimizer_stats", None) or {}
            yield (
                metrics_names.PREDICATE_SUBTREES_SHARED, "gauge",
                "Distinct predicate subtrees shared across policies by "
                "the optimizer's CSE table (computed once per program "
                "instead of once per policy)",
                ostats.get("subtrees_shared", 0),
            )
            yield (
                metrics_names.PREDICATE_POLICIES_FOLDED, "gauge",
                "Policies whose verdict folded to a constant and "
                "dropped out of the device program",
                ostats.get("policies_folded", 0),
            )
            yield (
                metrics_names.PREDICATE_RULES_FOLDED, "gauge",
                "Rule conditions folded to constants (unreachable or "
                "constant rules; indices preserved)",
                ostats.get("rules_folded", 0),
            )
            yield (
                metrics_names.PREDICATE_FIELDS_PRUNED, "gauge",
                "Feature-schema fields pruned by the optimizer (dead "
                "gather columns + zero-fill-redundant validity masks)",
                ostats.get("fields_pruned", 0),
            )
            yield (
                metrics_names.PREDICATE_ROW_BYTES_SAVED, "gauge",
                "Packed-row bytes saved per row, summed over schema "
                "buckets, vs the unoptimized layout",
                ostats.get("row_bytes_saved", 0),
            )
            # Multi-tenant serving (round 16): tenant-labelled
            # admission / fair-dispatch / lifecycle families. Sample
            # lists are empty without a --tenants manifest (the families
            # still export so dashboard panels resolve everywhere).
            tmgr = state.tenants
            tstats = tmgr.stats() if tmgr is not None else {}
            yield (
                metrics_names.TENANT_SHED_ROWS, "counter",
                "Rows shed by a tenant's admission quota (token bucket "
                "+ in-flight cap; 429 + Retry-After)",
                tstats.get("shed_rows", []), ("tenant",),
            )
            yield (
                metrics_names.TENANT_ADMITTED_ROWS, "counter",
                "Rows admitted through a tenant's admission quota",
                tstats.get("admitted_rows", []), ("tenant",),
            )
            yield (
                metrics_names.TENANT_INFLIGHT_ROWS, "gauge",
                "Admitted-but-unresolved rows per tenant (the "
                "max-inflight cap's numerator)",
                tstats.get("inflight_rows", []), ("tenant",),
            )
            yield (
                metrics_names.TENANT_QUEUE_DEPTH, "gauge",
                "Requests waiting in each tenant batcher's submission "
                "queue",
                tstats.get("queue_depth", []), ("tenant",),
            )
            yield (
                metrics_names.TENANT_DISPATCH_GRANTS, "counter",
                "Weighted-fair dispatch slots granted per tenant "
                "(live + audit classes)",
                tstats.get("dispatch_grants", []), ("tenant",),
            )
            yield (
                metrics_names.TENANT_DISPATCH_WAIT_SECONDS, "counter",
                "Cumulative time each tenant's batches waited for a "
                "fair-scheduler dispatch slot",
                tstats.get("dispatch_wait_seconds", []), ("tenant",),
            )
            yield (
                metrics_names.TENANT_EPOCH, "gauge",
                "Each tenant's currently serving policy epoch",
                tstats.get("epoch", []), ("tenant",),
            )
            yield (
                metrics_names.TENANT_ROLLBACKS, "counter",
                "Per-tenant reverts to last-good (rejected canaries + "
                "explicit rollbacks)",
                tstats.get("rollbacks", []), ("tenant",),
            )
            yield (
                metrics_names.TENANT_READY, "gauge",
                "Per-tenant honest readiness (1 ready, 0 degraded — "
                "the /readiness/{tenant} verdict)",
                tstats.get("ready", []), ("tenant",),
            )
            yield (
                metrics_names.TENANTS_SERVING, "gauge",
                "Tenants served by this process (0 without a tenants "
                "manifest; includes the default tenant otherwise)",
                tstats.get("serving", 0),
            )
            soak = getattr(state, "soak", None) or {}
            yield (
                metrics_names.SOAK_WINDOW_RPS, "gauge",
                "Requests/s of the current soak window (tools/soak "
                "in-process engine; 0 outside a soak)",
                soak.get("rps", 0.0),
            )
            yield (
                metrics_names.SOAK_WINDOW_P99_MS, "gauge",
                "p99 latency (ms) of the current soak window",
                soak.get("p99_ms", 0.0),
            )
            yield (
                metrics_names.SOAK_WINDOW_SHED_RATE, "gauge",
                "Shed (429) fraction of the current soak window",
                soak.get("shed_rate", 0.0),
            )
            # Crash-tolerant serving (round 17): boot shape, the durable
            # state store's cache/journal/fsck accounting, and the
            # supervision counters (worker respawn breaker + self-heal
            # watchdog). All zero without --state-dir / prefork workers
            # (families still export so dashboard panels resolve
            # everywhere).
            boot = getattr(state, "boot_report", None) or {}
            yield (
                metrics_names.BOOT_TIME_TO_READY, "gauge",
                "Seconds from process bootstrap start to the first "
                "serving epoch compiled+warmed (the MTTR numerator)",
                boot.get("time_to_ready_seconds", 0.0),
            )
            yield (
                metrics_names.BOOT_WARM, "gauge",
                "1 when this boot was WARM: a last-good manifest was "
                "found in the state store (artifact pins / audit resume "
                "applied where eligible)",
                1 if boot.get("warm") else 0,
            )
            yield (
                metrics_names.BOOT_DEGRADED_SOURCES, "gauge",
                "Policy sources this boot served from last-good state "
                "because the live read/fetch FAILED (loud degradation, "
                "not an outage)",
                boot.get("degraded_sources", 0),
            )
            sstats = (
                state.statestore.stats()
                if state.statestore is not None else {}
            )
            yield (
                metrics_names.STATESTORE_ARTIFACTS, "gauge",
                "Content-addressed policy artifacts resident in the "
                "state store's cache",
                sstats.get("artifacts_resident", 0),
            )
            yield (
                metrics_names.STATESTORE_BYTES, "gauge",
                "Bytes resident in the state store's artifact cache",
                sstats.get("bytes_resident", 0),
            )
            yield (
                metrics_names.STATESTORE_CACHE_HITS, "counter",
                "Artifact-cache hits (pinned warm-boot loads + degraded "
                "last-good fallbacks)",
                sstats.get("artifact_cache_hits", 0),
            )
            yield (
                metrics_names.STATESTORE_CACHE_MISSES, "counter",
                "Artifact-cache misses (url unknown, blob missing, or "
                "content-address verification failed)",
                sstats.get("artifact_cache_misses", 0),
            )
            yield (
                metrics_names.STATESTORE_MANIFESTS_PERSISTED, "counter",
                "Last-good epoch manifests persisted (boot, promotion, "
                "rollback — the durable rollback pin)",
                sstats.get("manifests_persisted", 0),
            )
            yield (
                metrics_names.STATESTORE_JOURNAL_RECORDS, "gauge",
                "Live records across the state store's journals "
                "(manifest history + url map)",
                sstats.get("journal_records", 0),
            )
            yield (
                metrics_names.STATESTORE_FSCK_QUARANTINED, "counter",
                "Torn/corrupt state-dir entries the fsck pass moved to "
                "quarantine (boot continued on surviving state)",
                sstats.get("fsck_quarantined", 0),
            )
            yield (
                metrics_names.STATESTORE_AUDIT_SPILLS, "counter",
                "Audit snapshot spills written (cursors + fed map + "
                "inventory, one atomic journal replace each)",
                sstats.get("audit_spills", 0),
            )
            yield (
                metrics_names.STATESTORE_AUDIT_ROWS_RESTORED, "gauge",
                "Audit inventory rows restored from the spill at this "
                "boot (the re-LIST the warm boot did NOT pay)",
                sstats.get("audit_rows_restored", 0),
            )
            sup = (
                state.supervisor.stats()
                if state.supervisor is not None else {}
            )
            yield (
                metrics_names.WORKER_RESPAWNS, "counter",
                "Prefork frontend workers respawned after dying",
                sup.get("worker_respawns", 0),
            )
            yield (
                metrics_names.WORKER_RESPAWN_BACKOFF_SECONDS, "counter",
                "Cumulative crash-loop backoff applied before worker "
                "respawns",
                sup.get("worker_backoff_seconds", 0.0),
            )
            yield (
                metrics_names.WORKER_SLOTS_GIVEN_UP, "gauge",
                "Frontend worker slots abandoned by the respawn breaker "
                "(crash-looped past the give-up cap; /readiness reports "
                "the degradation)",
                sup.get("worker_slots_given_up", 0),
            )
            yield (
                metrics_names.SELFHEAL_BATCHER_REVIVES, "counter",
                "Batcher dispatch loops the self-heal watchdog found "
                "dead and rebuilt",
                sup.get("batcher_revives", 0),
            )
            yield (
                metrics_names.SELFHEAL_FRONTEND_REVIVES, "counter",
                "Native-frontend drainer threads the self-heal watchdog "
                "found dead and rebuilt",
                sup.get("frontend_revives", 0),
            )
            # Serving shards (round 22, runtime/shards.py): the router's
            # health/fencing surface. With --serving-shards 1 the plain
            # batcher serves (no router object exists), so the gauges
            # report the one implicit shard and every fencing counter is
            # zero — the families still export so panels resolve.
            shard_rows = (
                batcher.shard_health()
                if hasattr(batcher, "shard_health") else []
            )
            yield (
                metrics_names.SHARDS_SERVING, "gauge",
                "Host-local serving shards behind the router "
                "(--serving-shards; 1 = router bypassed)",
                len(shard_rows) if shard_rows else 1,
            )
            yield (
                metrics_names.SHARD_HEALTHY, "gauge",
                "Per-shard routability (1 = routable, 0 = fenced "
                "pending warm revive)",
                [
                    ((str(r["shard"]),), 1 if r["healthy"] else 0)
                    for r in shard_rows
                ],
                ("shard",),
            )
            yield (
                metrics_names.SHARD_QUEUE_DEPTH, "gauge",
                "Per-shard submission queue depth (the router's "
                "EWMA routing signal reads this)",
                [
                    ((str(r["shard"]),), r["queue_depth"])
                    for r in shard_rows
                ],
                ("shard",),
            )
            yield (
                metrics_names.SHARD_FENCES, "counter",
                "Shards fenced by the heartbeat (wedged/dead dispatch "
                "loop or faulted probe)",
                bstats.get("shard_fences", 0),
            )
            yield (
                metrics_names.SHARD_REROUTED_ROWS, "counter",
                "Queued rows re-routed to a sibling shard at fence time "
                "(deadline, trace, and quota token preserved)",
                bstats.get("shard_reroutes", 0),
            )
            yield (
                metrics_names.SHARD_FENCED_ROWS, "counter",
                "Queued rows answered 503+Retry-After at fence time "
                "(no sibling had room)",
                bstats.get("shard_fenced_rows", 0),
            )
            yield (
                metrics_names.SHARD_RESPAWNS, "counter",
                "Fenced shards warm-revived in place (queue, pools, "
                "caches, and compiled programs survive)",
                bstats.get("shard_respawns", 0),
            )
            yield (
                metrics_names.SHARD_HEARTBEAT_FAULTS, "counter",
                "shard.heartbeat failpoint faults observed by the "
                "router's prober",
                bstats.get("shard_heartbeat_faults", 0),
            )
            # Persistent verdict matrix (round 23, audit/matrix.py):
            # residency, the row-vs-column sweep split, /audit/stream
            # fan-out accounting, the admission lookup fast path, and
            # the statestore spill/restore tie-in. All zero with
            # --audit-matrix off (families still export so dashboard
            # panels resolve everywhere).
            mstats = (
                state.audit_matrix.stats()
                if state.audit_matrix is not None
                else {}
            )
            yield (
                metrics_names.MATRIX_ROWS_RESIDENT, "gauge",
                "Distinct snapshot rows holding at least one verdict "
                "cell in the matrix",
                mstats.get("rows_resident", 0),
            )
            yield (
                metrics_names.MATRIX_CELLS_RESIDENT, "gauge",
                "Resident (object x policy) verdict cells",
                mstats.get("cells_resident", 0),
            )
            yield (
                metrics_names.MATRIX_COLUMNS, "gauge",
                "Policy columns of the serving epoch (keyed by policy "
                "content fingerprint, not epoch number)",
                mstats.get("columns", 0),
            )
            yield (
                metrics_names.MATRIX_DIRTY_COLUMNS, "gauge",
                "Columns awaiting a column-dirty sweep (epoch "
                "promotion changed their policy content)",
                mstats.get("dirty_columns", 0),
            )
            yield (
                metrics_names.MATRIX_VERSION, "gauge",
                "Monotonic matrix version — the /audit/stream resume "
                "cursor's upper bound",
                mstats.get("matrix_version", 0),
            )
            yield (
                metrics_names.MATRIX_ROW_SWEEP_ROWS, "counter",
                "Matrix rows re-judged because the watch feed dirtied "
                "the object row",
                mstats.get("row_sweep_rows", 0),
            )
            yield (
                metrics_names.MATRIX_COLUMN_SWEEP_ROWS, "counter",
                "Matrix rows re-judged because an epoch promotion "
                "dirtied the policy column",
                mstats.get("column_sweep_rows", 0),
            )
            yield (
                metrics_names.MATRIX_ROWS_EVICTED, "counter",
                "Matrix rows evicted by watch-feed DELETEs",
                mstats.get("rows_evicted", 0),
            )
            yield (
                metrics_names.MATRIX_COLUMNS_INVALIDATED, "counter",
                "Policy columns invalidated (content fingerprint "
                "changed or policy removed at promotion/rollback)",
                mstats.get("columns_invalidated", 0),
            )
            yield (
                metrics_names.MATRIX_CHANGELOG_EMITS, "counter",
                "Verdict-change entries emitted to the matrix "
                "changelog ring (re-stamps that confirm a standing "
                "verdict do not emit)",
                mstats.get("changelog_emits", 0),
            )
            yield (
                metrics_names.MATRIX_STREAM_CLIENTS, "gauge",
                "Connected GET /audit/stream subscribers",
                mstats.get("stream_clients", 0),
            )
            yield (
                metrics_names.MATRIX_STREAM_DROPPED_CLIENTS, "counter",
                "Stream subscribers dropped for slow consumption "
                "(bounded per-client queue overflowed; the applier "
                "never blocks)",
                mstats.get("changelog_dropped_clients", 0),
            )
            yield (
                metrics_names.MATRIX_LOOKUP_HITS, "counter",
                "/validate requests answered from a precomputed "
                "matrix verdict (byte-identical UPDATE payload, "
                "protect-mode hookless target)",
                bstats.get("matrix_lookup_hits", 0),
            )
            yield (
                metrics_names.MATRIX_LOOKUP_MISSES, "counter",
                "Matrix-eligible /validate requests that fell through "
                "to full evaluation (no cell, stale payload hash, or "
                "stale column fingerprint)",
                bstats.get("matrix_lookup_misses", 0),
            )
            yield (
                metrics_names.MATRIX_SPILLS, "counter",
                "Matrix spills journaled to the statestore "
                "(cadenced sweep-tail spills + the shutdown spill)",
                mstats.get("spills", 0),
            )
            yield (
                metrics_names.MATRIX_CELLS_RESTORED, "gauge",
                "Verdict cells restored from the statestore spill at "
                "warm boot (column fingerprint + payload hash matched)",
                mstats.get("cells_restored", 0),
            )
            # What the program runs on (the boot report's device facts,
            # one info-style gauge) and what the compiler did.
            _info_labels = (
                "platform", "device_kind", "device_count", "mesh",
                "output_devices", "jax", "jaxlib", "libtpu",
            )
            yield (
                metrics_names.DEVICE_INFO, "gauge",
                "The devices the serving program runs on, as JAX reports "
                "them (value 1; the facts are the labels)",
                [(tuple(str(boot.get(n, "")) for n in _info_labels), 1)],
                _info_labels,
            )
            _compiled = compiles.snapshot()
            yield (
                metrics_names.XLA_PROGRAMS_COMPILED, "counter",
                "Programs the XLA backend compiled in this process "
                "(persistent-cache misses)",
                _compiled["compiled"],
            )
            yield (
                metrics_names.XLA_COMPILE_CACHE_HITS, "counter",
                "Programs loaded from the persistent compilation cache "
                "instead of compiled",
                _compiled["cache_hits"],
            )
            yield (
                metrics_names.PLANE_PROGRAM_COMPILES, "counter",
                "Columnar plane structures traced (each one XLA program; "
                "0 per interval in steady state)",
                getattr(environment, "plane_program_compiles", 0) or 0,
            )
            yield (
                metrics_names.PLANE_PROGRAMS_PENDING, "gauge",
                "Columnar plane programs still compiling off the serving "
                "path (their batches ship the dense form meanwhile)",
                getattr(environment, "plane_programs_pending", 0) or 0,
            )
            # Flight recorder (round 18, telemetry/flightrec.py): event
            # volume, row-sampling volume, and the tail-exemplar table —
            # the slowest rows of the current window, labelled by their
            # trace id (request uid) so a dashboard p99 blip links to
            # its /debug/timeline. The sample set rebuilds per scrape,
            # so rotated-out exemplars disappear instead of lingering.
            # All zero/empty with --flight-recorder off (families still
            # export so dashboard panels resolve everywhere).
            from policy_server_tpu.telemetry import flightrec as _frec

            frec = _frec.recorder()
            yield (
                metrics_names.FLIGHT_RECORDER_EVENTS, "counter",
                "Phase events written to the flight-recorder ring",
                frec.events_recorded() if frec is not None else 0,
            )
            yield (
                metrics_names.FLIGHT_RECORDER_ROWS_SAMPLED, "counter",
                "Rows that recorded per-row timeline segments "
                "(--recorder-row-sample-rate stride)",
                frec.rows_sampled() if frec is not None else 0,
            )
            gc_stats = (
                frec.gc_stats() if frec is not None
                else {"pause_ns": [0] * _frec.GC_GENERATIONS,
                      "passes": [0] * _frec.GC_GENERATIONS}
            )
            yield (
                metrics_names.GC_PAUSE_SECONDS, "counter",
                "Time the CPython collector's passes held the "
                "interpreter, by generation (flight recorder gc hook)",
                [((str(g),), ns / 1e9)
                 for g, ns in enumerate(gc_stats["pause_ns"])],
                ("generation",),
            )
            yield (
                metrics_names.GC_PASSES, "counter",
                "Passes of the CPython collector, by generation",
                [((str(g),), n) for g, n in enumerate(gc_stats["passes"])],
                ("generation",),
            )
            yield (
                metrics_names.TAIL_EXEMPLAR_LATENCY_SECONDS, "gauge",
                "Tail exemplars: the slowest rows of the current "
                "flight-recorder window, with trace id and slowest "
                "phase (full phase breakdown on /debug/timeline)",
                [
                    (
                        (
                            ex["trace_id"], ex["policy_id"],
                            ex["slowest_phase"],
                        ),
                        ex["latency_seconds"],
                    )
                    for ex in (
                        frec.exemplars() if frec is not None else ()
                    )
                ],
                ("trace_id", "policy_id", "slowest_phase"),
            )

        from policy_server_tpu.telemetry import default_registry

        default_registry().attach_runtime_stats(runtime_stats)

        tls_context = None
        if config.tls_config.enabled:
            try:
                from policy_server_tpu.certs import (
                    create_tls_config_and_watch_certificate_changes,
                )
            except ImportError as e:
                raise RuntimeError(
                    "TLS was configured but the certs subsystem is not "
                    "available"
                ) from e
            tls_context = create_tls_config_and_watch_certificate_changes(
                config.tls_config
            )
            # cert-expiry/reload observability reads the last-good
            # identity machinery through the state, independent of
            # which frontend terminates the handshake
            state.tls_reloadable = getattr(
                tls_context, "_reloadable", None
            )

        # -- boot report: what this boot runs on and how warm it was -------
        # The device facts are read from the devices the environment
        # placed its program on; the compile counts are this boot's own
        # (in-process reboots share one counter). Logged on EVERY boot and
        # stamped on /metrics (policy_server_device_info) — the chip smoke
        # and every benchmark line read it from there.
        compiled = compiles.snapshot()
        programs, cache_hits, compile_seconds = (
            compiled[k] - compiles_at_entry[k]
            for k in ("compiled", "cache_hits", "seconds")
        )
        boot_report.update(
            _device_report(environment),
            compile_cache_dir=cache_info["dir"],
            compile_cache_populated_on_entry=cache_info[
                "populated_on_entry"
            ],
            programs_compiled=programs,
            compile_cache_hits=cache_hits,
            compile_seconds=round(compile_seconds, 3),
        )
        # "warm" = the state store carried a last-good manifest forward;
        # the drill additionally checks artifacts_from_cache/fetches to
        # prove the zero-network property.
        if statestore is not None:
            ss = statestore.stats()
            boot_report.update(
                warm=bool(boot_report.get("manifest_found")),
                time_to_ready_seconds=round(
                    _time.monotonic() - boot_t0, 3
                ),
                artifacts_from_cache=ss["artifact_cache_hits"],
                degraded_sources=boot_report.get("degraded_sources", 0)
                + ss["degraded_loads"],
                fsck_quarantined=ss["fsck_quarantined"],
            )
            try:
                from policy_server_tpu.fetch.downloader import retry_stats

                boot_report["fetch_retry_giveups"] = retry_stats()["giveups"]
            except ImportError:
                pass
            statestore.record_boot_report(boot_report)
        else:
            boot_report["time_to_ready_seconds"] = round(
                _time.monotonic() - boot_t0, 3
            )
        logger.info("boot report", extra={"span_fields": dict(boot_report)})

        return cls(config, state, tls_context)

    # -- routers (lib.rs:282 router(); used directly by in-process tests) --

    def router(self) -> web.Application:
        return build_router(self.state)

    def readiness_router(self) -> web.Application:
        return build_readiness_router(self.state)

    # -- run loop (lib.rs:238-280) -----------------------------------------

    async def start(self) -> None:
        """Bind both servers; returns once serving (used by run() and by
        socket-based tests, which read the bound ports)."""
        prefork = self.config.http_workers > 1 and self.tls_context is None
        if self.config.http_workers > 1 and self.tls_context is not None:
            logger.warning(
                "--http-workers is not supported with TLS yet (workers "
                "would each need the cert material); serving in-process"
            )
        native = False
        if self.config.frontend == "native":
            if (
                self.tls_context is not None
                and self.config.native_tls == "off"
            ):
                logger.warning(
                    "--native-tls off with --frontend native: TLS "
                    "terminates on the aiohttp frontend (the native "
                    "loops cannot share its port); serving with the "
                    "Python frontend"
                )
            else:
                native = self._start_native_frontend()
        if not native:
            api_runner = web.AppRunner(self.router())
            await api_runner.setup()
            api_site = web.TCPSite(
                api_runner, self.config.addr, self.config.port,
                ssl_context=self.tls_context,
                reuse_port=prefork or None,
            )
            await api_site.start()
            self.api_port = _bound_port(api_runner) or self.config.port
            self._runners.append(api_runner)
        if prefork:
            await self._start_frontend_workers()

        # readiness server starts only after the API server is bound
        # (Notify handshake, lib.rs:239-268)
        ready_runner = web.AppRunner(self.readiness_router())
        await ready_runner.setup()
        ready_site = web.TCPSite(
            ready_runner, self.config.addr, self.config.readiness_probe_port
        )
        await ready_site.start()
        self.readiness_port = _bound_port(ready_runner) or (
            self.config.readiness_probe_port
        )
        self._runners.append(ready_runner)

        if (
            self.config.selfheal_interval_seconds > 0
            and self.state.supervisor is not None
        ):
            from policy_server_tpu.supervision import SelfHealWatchdog

            self._selfheal = SelfHealWatchdog(
                self.state,
                self.state.supervisor,
                interval_seconds=self.config.selfheal_interval_seconds,
            ).start()

        self._ready.set()
        logger.info(
            "policy server started",
            extra={
                "span_fields": {
                    "addr": self.config.addr,
                    "port": self.api_port,
                    "readiness_probe_port": self.readiness_port,
                    "tls": self.tls_context is not None,
                    "policies": len(self.environment.policy_ids()),
                }
            },
        )

    def _start_native_frontend(self) -> bool:
        """Bind the GIL-free C++ HTTP front-end on the API port (it then
        OWNS the evaluation POST surface; pprof and /audit/reports GETs
        live on the readiness port). ``--frontend native`` asked for the
        library, so a failed build or load RAISES: a server that quietly
        frames in Python instead answers the same 200s and is not the
        server that was asked for. A bind or TLS-setup failure past that
        point returns False — with ONE loud line — and the caller serves
        through the Python frontend. With TLS configured, the
        handshake terminates ON the native epoll loops (round 20):
        certs.py's last-good identity builds the SSL_CTX, hot-rotation
        swaps it for NEW connections while established ones drain on
        the old, and a missing/unlinkable libssl falls back LOUDLY to
        the aiohttp TLS terminator — degraded in throughput, identical
        in trust surface."""
        from policy_server_tpu.api.handlers import MAX_BODY_BYTES
        from policy_server_tpu.runtime import native_frontend as nf

        if not nf.native_available():
            raise RuntimeError(
                "--frontend native: csrc/httpfront.cpp failed to build or "
                f"load: {nf.load_error()}"
            )
        sock = None
        tls_manager = None
        try:
            # one body cap across every process that can accept the API
            # socket — a drift here would make 413s nondeterministic
            # behind SO_REUSEPORT
            assert nf.MAX_BODY_BYTES == MAX_BODY_BYTES
            sock = nf.make_listen_socket(self.config.addr, self.config.port)
            front = nf.NativeFrontend(
                sock, nf.BatcherSink(self.state), max_body=MAX_BODY_BYTES,
                idle_timeout_ms=int(
                    self.config.native_idle_timeout_seconds * 1000
                ),
                read_timeout_ms=int(
                    self.config.native_read_timeout_seconds * 1000
                ),
                max_connections=self.config.native_max_connections,
            )
            if self.tls_context is not None:
                if not nf.tls_available():
                    raise RuntimeError(
                        f"native TLS unavailable ({nf.tls_error()}); "
                        "TLS will terminate on the aiohttp frontend"
                    )
                reloadable = getattr(self.tls_context, "_reloadable", None)
                if reloadable is None:
                    raise RuntimeError(
                        "TLS context carries no reloadable identity "
                        "(embedding without certs.py?)"
                    )
                tls_manager = nf.NativeTlsManager(
                    front, reloadable,
                    handshake_timeout_ms=int(
                        self.config.native_tls_handshake_timeout_seconds
                        * 1000
                    ),
                )
            front.start()
        except Exception as e:  # noqa: BLE001 — fall back, never refuse boot
            if tls_manager is not None:
                import contextlib

                with contextlib.suppress(Exception):
                    tls_manager.stop()
            if sock is not None:
                import contextlib

                with contextlib.suppress(OSError):
                    sock.close()
            logger.warning(
                "native HTTP frontend unavailable (%s); falling back to "
                "the Python frontend", e,
            )
            return False
        self._native_frontend = front
        self.state.native_frontend = front
        self._native_tls = tls_manager
        self.state.native_tls = tls_manager
        self.api_port = sock.getsockname()[1]
        if self.config.enable_pprof:
            logger.warning(
                "--enable-pprof with --frontend native: the native "
                "frontend serves only the evaluation POST surface; "
                "/debug/pprof/trace is on the readiness port, the cpu and "
                "heap endpoints need --frontend python"
            )
        logger.info(
            "native HTTP frontend started",
            extra={"span_fields": {
                "addr": self.config.addr, "port": self.api_port,
                "tls": tls_manager is not None,
                "ktls": (
                    tls_manager.snapshot()["ktls"]
                    if tls_manager is not None else False
                ),
            }},
        )
        return True

    async def _start_frontend_workers(self) -> None:
        """Spawn the prefork HTTP workers (runtime/frontend.py): the
        evaluation bridge on a unix socket, then N lightweight processes
        binding the already-bound API port with SO_REUSEPORT."""
        import os as _os
        import subprocess
        import sys
        import tempfile

        from policy_server_tpu.runtime.frontend import EvaluationBridge

        # 0700 private directory: a world-writable /tmp path would let any
        # local user squat the socket name or connect to the evaluation
        # bridge directly, bypassing the HTTP listener's TLS/auth surface
        bridge_dir = tempfile.mkdtemp(prefix="policy-server-bridge-")
        _os.chmod(bridge_dir, 0o700)
        self._bridge_dir = bridge_dir
        self._bridge_socket = _os.path.join(bridge_dir, "bridge.sock")
        self._bridge = EvaluationBridge(self.state, self._bridge_socket)
        await self._bridge.start()
        n = self.config.http_workers - 1  # this process serves too
        self._worker_cmd = [
            sys.executable,
            "-m",
            "policy_server_tpu.runtime.frontend",
            "--socket", self._bridge_socket,
            "--addr", self.config.addr,
            "--port", str(self.api_port),
            "--hostname", self.config.hostname,
            "--log-level", self.config.log_level,
            "--log-fmt",
            self.config.log_fmt
            if self.config.log_fmt != "otlp"
            else "json",  # workers log; spans stay in-process
            "--frontend", self.config.frontend,
        ]
        for i in range(n):
            self._worker_procs.append(subprocess.Popen(self._worker_cmd))
        logger.info(
            "prefork HTTP frontend started",
            extra={"span_fields": {
                "workers": n + 1, "bridge": self._bridge_socket,
            }},
        )
        self._worker_supervisor = asyncio.ensure_future(
            self._supervise_workers()
        )

    _WORKER_RESPAWN_INTERVAL_SECONDS = 2.0
    # crash-loop discipline (the reference defers to kubelet's restart
    # backoff; the in-box supervisor needs the same): a worker dying
    # within the crash window of its spawn is a crash-loop death —
    # respawn with exponential backoff, give up on the slot after the
    # --worker-respawn-giveup cap of consecutive fast deaths (a worker
    # that boots on a bad port/config would otherwise respawn forever
    # at 0.5 Hz). The give-up is the RESPAWN BREAKER: readiness then
    # reports the degraded slot honestly, and the counters export.
    _WORKER_CRASH_WINDOW_SECONDS = 5.0
    _WORKER_BACKOFF_BASE_SECONDS = 0.5
    _WORKER_BACKOFF_CAP_SECONDS = 30.0

    async def _supervise_workers(self) -> None:
        """Respawn dead frontend workers (the in-box analog of kubelet
        restarting reference replicas): a crashed worker otherwise shrinks
        the SO_REUSEPORT accept pool until restart. Fast-crashing workers
        back off exponentially and the slot is abandoned after
        ``--worker-respawn-giveup`` consecutive fast deaths."""
        import subprocess
        import time as _time

        giveup = self.config.worker_respawn_giveup
        supervisor = self.state.supervisor
        now = _time.monotonic()
        spawned_at = [now] * len(self._worker_procs)
        fast_deaths = [0] * len(self._worker_procs)
        respawn_at = [0.0] * len(self._worker_procs)

        while True:
            await asyncio.sleep(self._WORKER_RESPAWN_INTERVAL_SECONDS)
            now = _time.monotonic()
            for i, proc in enumerate(list(self._worker_procs)):
                if (
                    proc is None
                    or isinstance(proc, _PendingRespawn)
                    or proc.poll() is None
                ):
                    continue
                lifetime = now - spawned_at[i]
                if lifetime < self._WORKER_CRASH_WINDOW_SECONDS:
                    fast_deaths[i] += 1
                else:
                    fast_deaths[i] = 0
                if fast_deaths[i] >= giveup:
                    logger.error(
                        "frontend worker slot %d crash-looped %d times "
                        "within %.1fs of spawn (rc=%s); giving up on the "
                        "slot — the remaining processes keep serving",
                        i, fast_deaths[i],
                        self._WORKER_CRASH_WINDOW_SECONDS, proc.returncode,
                    )
                    self._worker_procs[i] = None
                    # SupervisorStats is the ONE authority for the
                    # give-up count (readiness + /metrics read it)
                    if supervisor is not None:
                        supervisor.count_slot_given_up()
                    continue
                backoff = 0.0
                if fast_deaths[i]:
                    backoff = min(
                        self._WORKER_BACKOFF_CAP_SECONDS,
                        self._WORKER_BACKOFF_BASE_SECONDS
                        * 2 ** (fast_deaths[i] - 1),
                    )
                respawn_at[i] = now + backoff
                logger.warning(
                    "frontend worker died (rc=%s, lived %.1fs); respawning "
                    "in %.1fs (consecutive fast deaths: %d)",
                    proc.returncode, lifetime, backoff, fast_deaths[i],
                )
                # mark the slot pending; actual spawn below when due
                self._worker_procs[i] = _PendingRespawn(proc.returncode)
                if supervisor is not None:
                    supervisor.count_respawn(backoff)
            for i, proc in enumerate(list(self._worker_procs)):
                if (
                    isinstance(proc, _PendingRespawn)
                    and now >= respawn_at[i]
                ):
                    self._worker_procs[i] = subprocess.Popen(self._worker_cmd)
                    spawned_at[i] = _time.monotonic()

    async def stop(self) -> None:
        import contextlib
        import os as _os

        if self._selfheal is not None:
            # the watchdog goes FIRST: shutting-down threads must not be
            # mistaken for wedged ones and "revived" mid-teardown
            self._selfheal.stop()
            self._selfheal = None
        if self._native_frontend is not None:
            # stop ACCEPTING first; in-flight native requests drain below
            # once the batcher shutdown resolves their futures
            self._native_frontend.stop_accepting()
        supervisor = getattr(self, "_worker_supervisor", None)
        if supervisor is not None:
            supervisor.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await supervisor
            self._worker_supervisor = None

        live_procs = [
            p for p in self._worker_procs
            if p is not None and not isinstance(p, _PendingRespawn)
        ]
        for proc in live_procs:
            with contextlib.suppress(ProcessLookupError):
                proc.terminate()
        loop = asyncio.get_running_loop()
        for proc in live_procs:
            try:
                # off-loop wait: a wedged worker must not stall shutdown's
                # event loop; escalate to SIGKILL so no orphan keeps a
                # share of the SO_REUSEPORT port serving 503s
                await loop.run_in_executor(None, proc.wait, 5)
            except Exception:  # noqa: BLE001 — TimeoutExpired and friends
                with contextlib.suppress(ProcessLookupError):
                    proc.kill()
                with contextlib.suppress(Exception):
                    await loop.run_in_executor(None, proc.wait, 5)
        self._worker_procs.clear()
        if self._bridge is not None:
            await self._bridge.stop()
            self._bridge = None
        if self._bridge_socket:
            with contextlib.suppress(OSError):
                _os.unlink(self._bridge_socket)
            self._bridge_socket = None
        if getattr(self, "_bridge_dir", None):
            with contextlib.suppress(OSError):
                _os.rmdir(self._bridge_dir)
            self._bridge_dir = None
        for runner in self._runners:
            await runner.cleanup()
        self._runners.clear()
        if self.state.audit_watch is not None:
            # stop the live feed BEFORE the scanner: a watcher applying
            # events into a store nobody will sweep again is dead work
            self.state.audit_watch.stop()
            self.state.audit_watch = None
        if self.state.audit is not None:
            # stop sweeping BEFORE epochs tear down: a sweep racing the
            # batcher shutdown would only burn its retry budget
            self.state.audit.shutdown()
        if self.state.tenants is not None:
            # named tenants tear down first (each lifecycle closes its
            # own epochs); the default tenant follows the paths below
            self.state.tenants.shutdown()
        if self.lifecycle is not None:
            # the lifecycle manager owns every epoch (current, pinned
            # previous, staged): one teardown path closes them all
            self.lifecycle.shutdown()
        else:
            self.batcher.shutdown()
            # The server built the environment, so the server closes it —
            # the batcher only borrows it (two batchers may share one env).
            self.environment.close()
        if self._native_tls is not None:
            # the TLS manager stops BEFORE the loops tear down: its
            # failpoint poll thread and reload listener must not touch
            # a frontend handle mid-destroy
            self._native_tls.stop()
            self._native_tls = None
            self.state.native_tls = None
        if self._native_frontend is not None:
            # every submitted future is resolved by now (batcher shutdown
            # drains rejecting), so this just flushes the last completions
            # out of the sockets, then stops the native loops
            await asyncio.get_running_loop().run_in_executor(
                None, self._native_frontend.shutdown
            )
            self._native_frontend = None
            self.state.native_frontend = None
        # Flush buffered spans / final metric state to the collector (the
        # reference flushes its OTEL providers on shutdown). No-op when the
        # OTLP pipeline was never installed.
        from policy_server_tpu.telemetry import otlp

        otlp.shutdown_pipeline()

    def reload_signal(self) -> None:
        """The SIGHUP contract: ONE signal drives both hot-reload paths —
        the TLS identity/client-CA reload (certs.py reload_now, forced
        regardless of the change detector) and the policy-set reload
        (lifecycle.py, background fetch+compile+canary). Both keep
        last-good state on any failure, so a SIGHUP can never make the
        server worse. Safe to invoke from a signal handler context: all
        real work happens on daemon threads."""
        reloadable = getattr(self.tls_context, "_reloadable", None)
        if reloadable is not None:
            import threading

            threading.Thread(
                target=reloadable.reload_now,
                name="sighup-cert-reload",
                daemon=True,
            ).start()
        if self.state.tenants is not None:
            # multi-tenant: one SIGHUP kicks EVERY tenant's independent
            # reload pipeline (the default included); each failure is
            # contained to its tenant
            self.state.tenants.reload_all("sighup")
        elif self.lifecycle is not None:
            self.lifecycle.request_reload("sighup")

    async def run_async(self) -> None:
        """Serve until cancelled or signalled. SIGTERM/SIGINT trigger the
        same graceful stop (drain batcher futures, close the environment,
        flush OTLP) — a pod rolling update must not drop buffered spans or
        strand in-flight webhook calls. SIGHUP triggers the combined
        cert + policy hot reload (reload_signal)."""
        import signal

        await self.start()
        stop_requested = asyncio.Event()
        loop = asyncio.get_running_loop()
        registered: list[int] = []
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop_requested.set)
                registered.append(sig)
            except (NotImplementedError, RuntimeError):
                pass  # non-main thread / platform without signal support
        # SIGHUP → hot reload, same off-main-thread guard as above (a
        # server embedded in a thread simply has no signal trigger; the
        # admin endpoint and file watcher still drive reloads)
        sighup = getattr(signal, "SIGHUP", None)
        if sighup is not None:
            try:
                loop.add_signal_handler(sighup, self.reload_signal)
                registered.append(sighup)
            except (NotImplementedError, RuntimeError):
                pass
        try:
            await stop_requested.wait()
            logger.info("shutdown signal received, stopping gracefully")
        except asyncio.CancelledError:
            pass
        finally:
            for sig in registered:
                loop.remove_signal_handler(sig)
            await self.stop()

    def run(self) -> None:
        """Blocking entry (reference PolicyServer::run, lib.rs:238)."""
        asyncio.run(self.run_async())


def run_server(args) -> int:
    """Process entry used by the CLI (reference main.rs:15-65): config →
    tracing/metrics setup → optional daemonize → bootstrap → run."""
    from policy_server_tpu.telemetry import setup_tracing

    config = Config.from_args(args)
    setup_tracing(config.log_level, config.log_fmt, config.log_no_color)
    if config.daemon:
        _daemonize(config)
    server = PolicyServer.new_from_config(config)
    try:
        server.run()
    except KeyboardInterrupt:
        pass
    return 0


def _daemonize(config: Config) -> None:
    """Double-fork daemonization (reference main.rs:35-55, daemonize crate):
    detach, write the pid file, redirect stdout/stderr."""
    import os
    import sys

    if os.fork() > 0:
        os._exit(0)
    os.setsid()
    if os.fork() > 0:
        os._exit(0)
    with open(config.daemon_pid_file, "w", encoding="utf-8") as f:
        f.write(str(os.getpid()))
    sys.stdout.flush()
    sys.stderr.flush()
    out = open(config.daemon_stdout_file or os.devnull, "ab")
    err = open(config.daemon_stderr_file or os.devnull, "ab")
    os.dup2(out.fileno(), sys.stdout.fileno())
    os.dup2(err.fileno(), sys.stderr.fileno())


def _versions() -> dict[str, str]:
    """Installed jax / jaxlib / libtpu versions ("" when absent: libtpu on
    a CPU-only installation). The jax version also keys the compile
    fingerprint — a bump invalidates the persistent XLA cache's hit
    expectations."""
    from importlib import metadata

    out = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            out[dist] = metadata.version(dist)
        except metadata.PackageNotFoundError:
            out[dist] = ""
    return out


def _device_report(environment) -> dict:
    """What the serving program runs on, as JAX reports it for the devices
    the environment placed its program on: the attached mesh's devices, or
    JAX's default device for a single-device program. A mesh covers every
    visible device (parallel/mesh.py resolve_axes), so ``device_count`` is
    also ``len(jax.devices())``; ``output_devices`` is what a warm-up
    output's ``sharding.device_set`` spans, so a program that put
    everything on one device cannot report otherwise. The oracle backend
    runs no device program and says so."""
    report = {"platform": "host-oracle", "device_kind": "", "device_count": 0,
              "mesh": "", "output_devices": 0}
    if getattr(environment, "backend", "jax") == "jax":
        import jax

        mesh = environment.mesh
        devices = (
            list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]
        )
        report = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            # "data:4,policy:1"; "" for a single-device program
            "mesh": ",".join(
                f"{axis}:{size}" for axis, size in sorted(mesh.shape.items())
            ) if mesh is not None else "",
            # devices a warm-up output's sharding spans (0: no warm-up)
            "output_devices": getattr(environment, "warmup_output_devices", 0),
        }
    report.update(_versions())
    return report


def _bound_port(runner: web.AppRunner) -> int | None:
    for site in runner.sites:
        server = getattr(site, "_server", None)
        if server and server.sockets:
            return server.sockets[0].getsockname()[1]
    return None


def _build_audit_watch_feed(
    config: Config, snapshot_store, statestore=None, resume=None
):
    """--audit-watch bring-up: the in-cluster list+watch client feeding
    the audit snapshot store (audit/watch_feed.py). Connection failure
    follows the context-service contract: fatal unless
    --ignore-kubernetes-connection-failure, which degrades to the
    dirty-tracking + seed-file feeds with a loud error."""
    from policy_server_tpu.audit import WatchFeed, parse_watch_resources
    from policy_server_tpu.context import KubeApiFetcher, KubeConnectionError

    resources = parse_watch_resources(config.audit_watch_resources)
    try:
        fetcher = KubeApiFetcher(
            insecure_skip_tls_verify=config.kube_insecure_skip_tls_verify
        )
    except KubeConnectionError as e:
        if not config.ignore_kubernetes_connection_failure:
            raise RuntimeError(
                f"--audit-watch cannot connect to the Kubernetes API: {e} "
                "(use --ignore-kubernetes-connection-failure to boot "
                "without the live feed)"
            ) from e
        logger.error(
            "Kubernetes connection failed; the audit snapshot store "
            "falls back to /validate dirty-tracking and the seed file: "
            "%s", e,
        )
        return None
    return WatchFeed(
        fetcher,
        resources,
        snapshot_store,
        refresh_seconds=config.context_refresh_seconds,
        max_queue_events=config.audit_watch_max_queue_events,
        statestore=statestore,
        spill_interval_seconds=config.state_audit_spill_seconds,
        resume_rvs=(resume or {}).get("rvs"),
        resume_fed=(resume or {}).get("fed"),
    ).start()


def _build_context_service(config: Config):
    """Context-snapshot bring-up (reference kube::Client bootstrap,
    lib.rs:91-125): only when some policy declares contextAwareResources;
    connection failure is fatal unless --ignore-kubernetes-connection-failure
    (lib.rs:106-123), in which case context-aware policies see an empty
    cluster."""
    wanted: set = set()
    for entry in config.policies.values():
        if hasattr(entry, "context_aware_resources"):
            wanted |= set(entry.context_aware_resources)
        elif hasattr(entry, "policies"):
            for member in entry.policies.values():
                wanted |= set(member.context_aware_resources)
    if not wanted:
        return None
    from policy_server_tpu.context import (
        ContextSnapshotService,
        KubeApiFetcher,
        KubeConnectionError,
        StaticContextFetcher,
    )

    try:
        fetcher = KubeApiFetcher(
            insecure_skip_tls_verify=config.kube_insecure_skip_tls_verify
        )
    except KubeConnectionError as e:
        if not config.ignore_kubernetes_connection_failure:
            raise RuntimeError(
                f"cannot connect to the Kubernetes API: {e} "
                "(use --ignore-kubernetes-connection-failure to boot anyway)"
            ) from e
        logger.error(
            "Kubernetes connection failed, context-aware policies will see "
            "an empty cluster: %s", e,
        )
        fetcher = StaticContextFetcher()
    return ContextSnapshotService(
        fetcher,
        wanted,
        refresh_seconds=config.context_refresh_seconds,
        # None = auto (watch when the fetcher supports it); False = forced
        # poll mode via --context-no-watch
        watch=None if config.context_watch else False,
    ).start()


def _build_environment(config: Config, builder_kwargs: dict):
    """Build the evaluation environment, honoring ``config.mesh`` and
    ``config.mesh_dispatch``.

    TPU-first serving topology (SURVEY.md §2.3 last row; the reference's
    scale-out is replicas behind a Service, README.md:21-26):

    * >1 device on the mesh → ONE fused SPMD program over the whole
      (data × policy) mesh via ``attach_mesh`` (round 14): batch planes
      shard on ``data``, a >1 ``policy`` axis additionally buckets the
      policy set into per-shard ``lax.switch`` branches whose verdict
      blocks meet in an all-gather collective — one device program per
      batch.
    * ``policy`` axis > 1 with ``--mesh-dispatch threaded`` →
      :class:`PolicyShardedEvaluator`, the legacy MPMD fallback: one
      fused program per policy shard on its own submesh row, dispatched
      from a host thread pool.
    * single device (the default ``auto`` spec on a 1-chip host) → plain
      single-device environment, unchanged.
    """
    mesh = None
    if config.evaluation_backend == "jax":
        from policy_server_tpu.parallel import make_mesh

        mesh = make_mesh(config.mesh)
        if (
            config.mesh.policy_size() > 1
            and config.mesh_dispatch == "threaded"
        ):
            from policy_server_tpu.parallel import PolicyShardedEvaluator

            sharded = PolicyShardedEvaluator(
                config.policies,
                mesh,
                backend=config.evaluation_backend,
                continue_on_errors=config.continue_on_errors,
                builder_kwargs=builder_kwargs,
            )
            logger.info(
                "policy-sharded mesh attached (threaded MPMD fallback)",
                extra={"span_fields": {
                    "mesh": dict(config.mesh.axes),
                    "shards": len(sharded.shards),
                }},
            )
            return sharded

    builder = EvaluationEnvironmentBuilder(
        backend=config.evaluation_backend,
        continue_on_errors=config.continue_on_errors,
        **builder_kwargs,
    )
    environment = builder.build(config.policies)
    if mesh is not None and mesh.devices.size > 1:
        environment.attach_mesh(mesh)
        logger.info(
            "fused SPMD mesh attached",
            extra={"span_fields": {"mesh": dict(config.mesh.axes),
                                   "devices": int(mesh.devices.size),
                                   "policy_sharded":
                                       environment._mesh_block is not None}},
        )
    return environment


def _needs_fetch(config: Config) -> bool:
    """True when any configured module URL is not a builtin."""
    from policy_server_tpu.policies import resolve_builtin

    urls: list[str] = []
    for entry in config.policies.values():
        if hasattr(entry, "module"):
            urls.append(entry.module)
        else:
            urls.extend(m.module for m in entry.policies.values())
    return any(resolve_builtin(u) is None for u in urls)
