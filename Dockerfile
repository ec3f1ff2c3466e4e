# policy-server-tpu container image.
#
# Build args select the JAX backend wheel: the default CPU wheel serves
# the in-process test/dev loop; TPU pods install the libtpu wheel
# (requires the TPU runtime on the node, e.g. a GKE TPU nodepool).
#
# Runtime surface (reference Dockerfile parity: ports 3000/8081, non-root
# uid): API on 3000 (TLS when --cert-file/--key-file mounted), readiness +
# Prometheus /metrics on 8081.

FROM python:3.12-slim AS build

RUN apt-get update && apt-get install -y --no-install-recommends \
    g++ make && rm -rf /var/lib/apt/lists/*

ARG JAX_WHEEL="jax[cpu]"
RUN pip install --no-cache-dir \
    "${JAX_WHEEL}" aiohttp pyyaml requests cryptography prometheus_client \
    grpcio protobuf numpy

WORKDIR /src
COPY policy_server_tpu/ policy_server_tpu/
COPY csrc/ csrc/
COPY protos/ protos/
# native host encoder (ops/fastenc.py soft-fails to the Python trie if
# the extension is absent, so a failed build degrades, not breaks —
# but the failure must be VISIBLE in the build log, not swallowed)
RUN mkdir -p build && \
    { g++ -O3 -shared -fPIC -std=c++17 \
        -o build/fastenc-cpython-312-x86_64-linux-gnu.so \
        csrc/fastenc.cpp -I/usr/local/include/python3.12 \
      || echo "WARNING: fastenc build failed; Python encoder fallback"; } && \
    { g++ -O2 -shared -fPIC -std=c++17 -pthread \
        -o build/httpfront-cpython-312-x86_64-linux-gnu.so \
        csrc/httpfront.cpp \
      || echo "WARNING: httpfront build failed; --frontend native will fall back to python"; }
# native TLS termination dlopens libssl/libcrypto at RUNTIME (no
# OpenSSL -dev headers needed at build time); python:3.12-slim ships
# libssl3, so prove it resolves in the runtime base here — if this ever
# regresses (slimmer base, removed package) the build says so instead
# of every container silently serving TLS through the aiohttp fallback
RUN python -c "import ctypes; ctypes.CDLL('libssl.so.3')" \
    || echo "WARNING: libssl.so.3 missing; native TLS will fall back to aiohttp"

# test stage: the graftcheck gate (static analysis + counter/OTLP/
# dashboard consistency + failpoint and cli-docs drift) runs against the
# exact tree being shipped. CI builds this stage first
# (`docker build --target test .`); the runtime image below does not
# inherit from it, so a skipped gate never reaches production layers.
FROM build AS test
COPY tools/ tools/
COPY tests/ tests/
COPY Makefile pytest.ini cli-docs.md kubewarden-dashboard.json ./
RUN make check
# sanitizer lane: ASan+UBSan rebuilds of the natives, differential
# corpora + structure-aware fuzzer, LSan teardown audit. Skips LOUDLY
# (grep the log for SANITIZE_TOOLCHAIN_SKIP) when the stage's toolchain
# lacks the sanitizer runtimes — never silently.
RUN make sanitize

FROM python:3.12-slim

COPY --from=build /usr/local/lib/python3.12/site-packages /usr/local/lib/python3.12/site-packages
COPY --from=build /src/policy_server_tpu /app/policy_server_tpu
COPY --from=build /src/build /app/build
# csrc must ship too: each native library is named by a hash of its
# source text and compile flags (utils/nativebuild.py), so the loader needs
# the source to know which .so is the one built from it
COPY --from=build /src/csrc /app/csrc

WORKDIR /app
# non-root (reference runs uid 65533)
USER 65533:65533

EXPOSE 3000 8081

# persistent XLA compilation cache: placed from outside, by the variable
# JAX itself reads (runtime/compile_cache.py sets no directory then)
ENV JAX_COMPILATION_CACHE_DIR=/data/xla-cache

ENTRYPOINT ["python", "-m", "policy_server_tpu"]
CMD ["--policies", "/config/policies.yml", \
     "--policies-download-dir", "/data/policies"]
