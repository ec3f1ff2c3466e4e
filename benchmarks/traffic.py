"""The benchmark's traffic generator: one general generator that a traffic
mix's data file (``benchmarks/traffic/<name>.json``) parameterises.

The review generator is a COPY of ``policy_server_tpu/policies/flagship.py``
``synthetic_review`` / ``_IMAGES`` / ``_NAMESPACES`` / ``_OPERATIONS`` as of
the PR that added the benchmark: later PRs may change the program, not the
yardstick. It imports nothing of the program.

A run draws a pool of ``pool_shapes`` pod reviews from ``--seed`` once,
serialises each once, and stamps a fixed-width sequence number into the
pre-serialised bytes (``request.uid``, ``request.name`` and
``object.metadata.name``) for every request, so every request of a run has
bytes never sent before in that run and none costs a ``json.dumps``.
Request number ``n`` of a run is a pure function of (seed, n).

Sharing is a parameter of the mix: without ``replicas`` every request
carries another shape of the pool; with ``replicas: R`` a shape stays for
a block of R x (number of policies) requests, so each policy meets it R
times in a row under R different uids and names, as the pods of one
rollout do: (R - 1) / R of the requests repeat a (policy, shape) pair.
"""

from __future__ import annotations

import json
import random
from typing import Any

_IMAGES = [
    "registry.prod.example.com/api/server:v1.4.2",
    "registry.prod.example.com/web/frontend:2024.1",
    "docker.io/library/nginx:1.25",
    "docker.io/library/redis:latest",
    "ghcr.io/example/tool:dev",
    "internal.example.com/batch/worker:v9",
]

_NAMESPACES = [
    "default", "prod", "staging", "team-a", "tenant-3-restricted",
    "kube-system", "payments",
]

_OPERATIONS = ["CREATE", "UPDATE", "DELETE"]

STAMP_WIDTH = 10
_UID_MARK = "synthetic-" + "#" * STAMP_WIDTH
_NAME_MARK = "pod-" + "#" * STAMP_WIDTH


def synthetic_review(rng: random.Random, uid: int) -> dict[str, Any]:
    """One synthetic Pod AdmissionReview document (dict form); the copy's
    only change is that uid and pod name carry a fixed-width stamp mark."""
    ns = rng.choice(_NAMESPACES)
    n_containers = rng.randint(1, 4)
    containers = []
    for c in range(n_containers):
        container: dict[str, Any] = {
            "name": f"c{c}",
            "image": rng.choice(_IMAGES),
        }
        sc: dict[str, Any] = {}
        if rng.random() < 0.15:
            sc["privileged"] = True
        if rng.random() < 0.5:
            sc["runAsNonRoot"] = rng.random() < 0.8
        if rng.random() < 0.4:
            sc["readOnlyRootFilesystem"] = rng.random() < 0.7
        if rng.random() < 0.2:
            sc["capabilities"] = {
                "add": rng.sample(
                    ["NET_BIND_SERVICE", "CHOWN", "SYS_ADMIN", "NET_ADMIN"],
                    rng.randint(1, 2),
                )
            }
        if sc:
            container["securityContext"] = sc
        if rng.random() < 0.3:
            container["volumeMounts"] = [
                {"name": "v0", "mountPath": rng.choice(["/var/log", "/etc", "/tmp"])}
            ]
        containers.append(container)

    labels = {"app": f"app-{uid % 17}"}
    if rng.random() < 0.7:
        labels["owner"] = "team-core"
        labels["cost-center"] = "cc-42"
    annotations = {}
    if rng.random() < 0.25:
        annotations["container.apparmor.security.beta.kubernetes.io/c0"] = (
            rng.choice(["runtime/default", "localhost/lockdown", "unconfined"])
        )
    if rng.random() < 0.1:
        annotations["prod.example.com/debug"] = "true"

    pod = {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": _NAME_MARK,
            "namespace": ns,
            "labels": labels,
            "annotations": annotations,
        },
        "spec": {"containers": containers},
    }
    if rng.random() < 0.2:
        pod["spec"]["hostNetwork"] = rng.random() < 0.5
    if rng.random() < 0.15:
        pod["spec"]["volumes"] = [
            {"name": "v0", "hostPath": {"path": rng.choice(["/var/log", "/etc"])}}
        ]

    return {
        "apiVersion": "admission.k8s.io/v1",
        "kind": "AdmissionReview",
        "request": {
            "uid": _UID_MARK,
            "kind": {"group": "", "version": "v1", "kind": "Pod"},
            "requestKind": {"group": "", "version": "v1", "kind": "Pod"},
            "resource": {"group": "", "version": "v1", "resource": "pods"},
            "name": _NAME_MARK,
            "namespace": ns,
            "operation": rng.choice(_OPERATIONS),
            "userInfo": {"username": "system:serviceaccount:ci:deployer"},
            "object": pod,
            "dryRun": False,
        },
    }


def stamp(n: int) -> bytes:
    return b"%0*d" % (STAMP_WIDTH, n)


def uid_of(n: int) -> str:
    return "synthetic-%0*d" % (STAMP_WIDTH, n)


class Traffic:
    """The pool of a run and the map from request number to bytes."""

    def __init__(self, mix: dict[str, Any], seed: int,
                 policy_ids: list[str]) -> None:
        if mix.get("generator") != "pod_reviews":
            raise ValueError(
                f"traffic mix names generator {mix.get('generator')!r}; "
                "this harness has 'pod_reviews'"
            )
        self.policy_ids = policy_ids
        size = int(mix["pool_shapes"])
        rng = random.Random(seed)
        self.reviews = [synthetic_review(rng, i) for i in range(size)]
        # the order of a run: a full cycle over the pool, its stride and
        # offset drawn from the seed after the pool itself
        self.size = size
        self.stride = rng.randrange(1, size)
        self.offset = rng.randrange(size)
        self.block = int(mix.get("replicas", 1)) * len(policy_ids) \
            if "replicas" in mix else 1
        self._bodies: list[tuple[bytes, tuple[int, ...]]] | None = None

    def shape_of(self, n: int) -> int:
        return (n // self.block * self.stride + self.offset) % self.size

    def policy_of(self, n: int) -> int:
        return n % len(self.policy_ids)

    def _serialise(self) -> list[tuple[bytes, tuple[int, ...]]]:
        marks = (_UID_MARK.encode(), _NAME_MARK.encode())
        hashes = b"#" * STAMP_WIDTH
        out = []
        for review in self.reviews:
            body = json.dumps(review, separators=(",", ":")).encode()
            at = []
            start = 0
            while True:
                i = body.find(hashes, start)
                if i < 0:
                    break
                at.append(i)
                start = i + STAMP_WIDTH
            if len(at) != 3 or any(m not in body for m in marks):
                raise ValueError("a pooled review lost a stamp mark")
            out.append((body, tuple(at)))
        return out

    def request(self, n: int) -> bytes:
        """The whole HTTP/1.1 request number ``n`` of the run."""
        if self._bodies is None:
            self._bodies = self._serialise()
        body, at = self._bodies[self.shape_of(n)]
        digits = stamp(n)
        buf = bytearray(body)
        for i in at:
            buf[i:i + STAMP_WIDTH] = digits
        pid = self.policy_ids[n % len(self.policy_ids)]
        return (
            b"POST /validate/%b HTTP/1.1\r\nHost: benchmark\r\n"
            b"Content-Type: application/json\r\nContent-Length: %d\r\n\r\n"
            % (pid.encode(), len(buf))
        ) + bytes(buf)
