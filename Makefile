# Developer entrypoints (reference Makefile parity: build/test/coverage +
# docs freshness; bench/dryrun are TPU-build additions).

IMG ?= policy-server-tpu:latest

.PHONY: all test unit-tests integration-tests bench chaos check docs \
        docs-check fastenc httpfront natives sanitize soak-smoke soak \
        image dev-stack dev-stack-down dryrun-multichip multichip \
        restart-drill phase-report shards-ab chip-smoke clean

all: natives test check sanitize soak-smoke multichip restart-drill phase-report

# full suite on the 8-virtual-device CPU backend (tests/conftest.py)
test:
	python -m pytest tests/ -q

unit-tests:
	python -m pytest tests/ -q -k "not test_server and not test_tls"

integration-tests:
	python -m pytest tests/test_server.py tests/test_server_mesh.py tests/test_tls.py -q

# the 5 BASELINE configs + HTTP-path percentiles (one JSON line each).
# NOT a chip entry point: its parent process builds device environments
# and then starts children, and a chip belongs to one process. A CPU
# count of work only (JAX_PLATFORMS=cpu); ROADMAP S1 replaces it.
bench:
	JAX_PLATFORMS=cpu python bench.py

# the quickest proof that the served path runs on the accelerator
# (chip_smoke.py: HTTPS -> native front-end -> batcher -> device, flagship
# 32-policy set, byte-compared against an oracle server; fails without a
# chip). `make chip-smoke CHIP_SMOKE_ARGS="--chips 4"` on a four-chip
# host; `CHIP_SMOKE_ARGS="--platform cpu --requests 256"` rehearses here.
chip-smoke:
	python chip_smoke.py $(CHIP_SMOKE_ARGS)

# property-based differential fuzzing (device vs IR-oracle vs wasm)
fuzz:
	python -m pytest tests/test_fuzz_differential.py tests/test_differential.py -q

# fault-injection chaos suite: shedding, deadline drops, breaker
# trip/recover, fetch retry, shutdown-under-load, plus the round-20 TLS
# storms (cert rotation under HTTPS load, corrupted-reload last-good,
# tls.handshake failpoint). Failpoints armed by the tests themselves;
# slow-marked cases included. Runs with the graftcheck lock-order
# sanitizer armed — tests/conftest.py instruments every package lock,
# records per-thread acquisition stacks, and errors the session on any
# lock-order inversion or cycle.
chaos:
	GRAFTCHECK_LOCKSAN=1 python -m pytest tests/test_resilience.py tests/test_resilience_tls.py -q

# seeded mini-soak through the FULL serving stack (tools/soak/): ~20 s
# of trace replay against the native frontend with a mid-soak fault
# storm (SIGHUP reload, breaker trip, watch/audit/frontend failpoints)
# plus slowloris/malformed/disconnect abuse waves, SLO-gated (zero
# unexplained non-2xx, p99 budget) and emitting BENCH_soak_r13_smoke.json
soak-smoke:
	JAX_PLATFORMS=cpu python -m tools.soak --preset smoke

# the cluster-scale soak: 100k+ watched objects churning into the audit
# feed, prefork workers in the kill rotation, a 5-minute storm
soak:
	JAX_PLATFORMS=cpu python -m tools.soak --preset full

# the crash-tolerance acceptance (round 17, tools/restart_drill.py):
# cold-boot a REAL server process fetching policies from a local HTTP
# registry, SIGKILL it under load, then warm-boot it with the registry
# DOWN and FAILPOINTS=fetch.http armed — the state store must supply
# every artifact (zero network), verdicts must be bit-exact across the
# restart, and warm time-to-ready must be <= 0.5x cold (persistent XLA
# cache + pinned artifact cache). Emits the restart_mttr bench line and
# BENCH_restart_mttr.json.
restart-drill:
	JAX_PLATFORMS=cpu python -m tools.restart_drill

# flight-recorder phase attribution (round 18, tools/bench/
# phasereport.py): drive a short serving burst with the recorder armed,
# reconcile summed phase time against per-batch wall time, and GATE the
# unattributed residual at <25% of wall — the host floor is measured,
# not guessed. Emits BENCH_phase_attribution.json. Round 19: every run
# also DIFFS against the committed artifact (read before the overwrite)
# so per-phase regressions/wins print as numbers, not narration.
phase-report:
	JAX_PLATFORMS=cpu python -m tools.bench.phasereport --gate \
	  --baseline BENCH_phase_attribution.json

# the 1-vs-M serving-shard A/B on an all-unique miss stream: certifies
# bit-exact verdicts, counter parity, and the M=1 router bypass, and
# records req/s + host-phase decomposition per arm (round 22)
shards-ab:
	JAX_PLATFORMS=cpu python -m tools.bench.shards_ab --gate

# the graftcheck CI gate (tools/graftcheck/): concurrency lint
# (guarded-by + lock-order cycles), trace-purity lint, observability
# counter<->OTLP<->dashboard consistency, failpoint/docs drift, the
# round-21 native checkers (NA01-NA03 ABI drift across the C++/ctypes
# boundary, NW00-NW03 wire-parser bounds analysis over csrc/), and the
# cli-docs regeneration diff. Suppressions live in
# tools/graftcheck/baseline.json (explicit + justified; stale entries
# fail).
check:
	python -m tools.graftcheck

# native host encoder (ops/fastenc.py compiles on demand into build/,
# named by a hash of source + flags; a failed build raises)
fastenc:
	python -c "from policy_server_tpu.ops import fastenc; print(fastenc._build_library())"

# native HTTP front-end (runtime/native_frontend.py compiles on demand).
# TLS termination needs no OpenSSL headers — httpfront.cpp dlopens
# libssl/libcrypto (.so.3 / .so.1.1) at runtime; when neither resolves
# the build still succeeds and the server falls back LOUDLY to aiohttp
# TLS, so this target also prints whether native TLS is live.
httpfront:
	python -c "from policy_server_tpu.runtime import native_frontend; print(native_frontend._build_library()); print('native TLS:', 'available' if native_frontend.tls_available() else 'UNAVAILABLE (libssl did not resolve; aiohttp TLS fallback)')"

# both native extensions: a failed build exits nonzero here, as it fails
# the boot of a server that asked for them
natives: fastenc httpfront

# sanitizer lane (round 21, tools/sanitize_lane.py): rebuild all three
# natives with ASan+UBSan into distinct -san.so artifacts, run the
# native differential corpora and the structure-aware fuzzer
# (tools/fuzz_native.py) under the instrumented builds, then a
# LeakSanitizer audit of the teardown paths (SSL_CTX rotation, rings
# with in-flight completions, the wedged-drainer intentional leak —
# suppressions curated in tools/lsan.supp). Skips LOUDLY
# (SANITIZE_TOOLCHAIN_SKIP) when the toolchain cannot produce sanitized
# builds — never silently.
sanitize:
	python -m tools.sanitize_lane

docs:
	python -m policy_server_tpu docs --output cli-docs.md

# CI freshness gate (reference ci.yml docs job)
docs-check: docs
	git diff --exit-code cli-docs.md

image:
	docker build -t $(IMG) .

# local observability stack: otel-collector + jaeger + prometheus + grafana
dev-stack:
	docker compose -f hack/docker-compose.yml up -d

dev-stack-down:
	docker compose -f hack/docker-compose.yml down

# the driver's multi-chip compile check on N virtual CPU devices
dryrun-multichip:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('ok')"

# the full multi-chip gate (round 14): the fused-SPMD dry-run on the
# 8-virtual-device (data:4, policy:2) mesh — ONE device program per
# batch, verdicts differentialed against the host oracle, trend-line
# stats emitted as MULTICHIP_STATS — plus the REAL multi-host smoke:
# 2 localhost processes forming one global mesh over jax.distributed
# (CPU gloo collectives), each serving host-local rows. The smoke skips
# LOUDLY (MULTICHIP_DISTRIBUTED_SKIP) where the platform cannot form a
# multi-process mesh — never silently.
multichip:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	python -c "import __graft_entry__ as g; g.dryrun_multichip(8); \
	g.dryrun_distributed(2); print('ok')"

clean:
	rm -rf .pytest_cache build/*.o __pycache__
