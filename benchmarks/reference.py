"""The plain reference of the benchmark's configurations: what a policy
set answers to an AdmissionReview, written straight from the policies'
documented semantics, in plain Python over the review's dict.

It imports nothing of the program and takes nothing the program made: the
policies come from the configuration's data file, the set of signed images
from the same file (the benchmark signs exactly those when it builds the
signature store). It answers whole HTTP responses (status line, headers
but ``Date``, body), because that is what a caller of the webhook reads.
"""

from __future__ import annotations

import base64
import fnmatch
import json
import re
from typing import Any, Callable

CONTAINER_LISTS = ("containers", "initContainers", "ephemeralContainers")
_TAGGED = re.compile(r"^(?:[^/]*/)*[^/]*[:@][^/]*$")
_APPARMOR = "container.apparmor.security.beta.kubernetes.io/"

Verdict = tuple[str | None, list | None]  # (rejection message, patch ops)


def _containers(request: dict) -> list[dict]:
    spec = (request.get("object") or {}).get("spec") or {}
    out = []
    for name in CONTAINER_LISTS:
        out.extend(c for c in spec.get(name) or [] if isinstance(c, dict))
    return out


def _sc(container: dict, key: str) -> Any:
    return (container.get("securityContext") or {}).get(key)


def _images(request: dict) -> list[str]:
    seen: dict[str, None] = {}
    for c in _containers(request):
        if isinstance(c.get("image"), str) and c["image"]:
            seen.setdefault(c["image"], None)
    return list(seen)


def _first(*rules: tuple[bool, str]) -> str | None:
    for fired, message in rules:
        if fired:
            return message
    return None


def always_happy(settings: dict, request: dict, signed: set) -> Verdict:
    return None, None


def always_unhappy(settings: dict, request: dict, signed: set) -> Verdict:
    return settings.get("message", "this policy always rejects"), None


def pod_privileged(settings: dict, request: dict, signed: set) -> Verdict:
    bad = any(_sc(c, "privileged") is True for c in _containers(request))
    return _first((bad, "Privileged container is not allowed")), None


def host_namespaces(settings: dict, request: dict, signed: set) -> Verdict:
    spec = (request.get("object") or {}).get("spec") or {}
    return _first(*(
        (spec.get(flag) is True and not settings.get(key, False),
         f"Pod has {flag} enabled, but this is not allowed")
        for key, flag in (("allow_host_network", "hostNetwork"),
                          ("allow_host_pid", "hostPID"),
                          ("allow_host_ipc", "hostIPC"))
    )), None


def readonly_root_fs(settings: dict, request: dict, signed: set) -> Verdict:
    bad = any(_sc(c, "readOnlyRootFilesystem") is not True
              for c in _containers(request))
    return _first((
        bad,
        "containers must set securityContext.readOnlyRootFilesystem to true",
    )), None


def run_as_non_root(settings: dict, request: dict, signed: set) -> Verdict:
    spec = (request.get("object") or {}).get("spec") or {}
    pod_ok = (spec.get("securityContext") or {}).get("runAsNonRoot") is True
    bad = not pod_ok and any(_sc(c, "runAsNonRoot") is not True
                             for c in _containers(request))
    return _first(
        (bad, "pods must set runAsNonRoot at pod or container level")
    ), None


def allowed_proc_mount_types(settings: dict, request: dict,
                             signed: set) -> Verdict:
    allowed = settings.get("allowed_types", ["Default"])
    bad = any(_sc(c, "procMount") is not None
              and _sc(c, "procMount") not in allowed
              for c in _containers(request))
    return _first((bad, f"procMount must be one of {allowed}")), None


def hostpaths(settings: dict, request: dict, signed: set) -> Verdict:
    prefixes = [e["pathPrefix"] for e in settings.get("allowed_host_paths") or []]
    spec = (request.get("object") or {}).get("spec") or {}
    bad = False
    for volume in spec.get("volumes") or []:
        path = (volume.get("hostPath") or {}).get("path")
        if path is not None and not any(path.startswith(p) for p in prefixes):
            bad = True
    return _first((bad, "hostPath volume is not allowed")), None


def disallow_latest_tag(settings: dict, request: dict, signed: set) -> Verdict:
    bad = any(
        isinstance(c.get("image"), str)
        and (not _TAGGED.match(c["image"]) or c["image"].endswith(":latest"))
        for c in _containers(request)
    )
    return _first((bad, "images must have an explicit, non-latest tag")), None


def psp_apparmor(settings: dict, request: dict, signed: set) -> Verdict:
    allowed = settings.get("allowed_profiles", ["runtime/default"])
    meta = (request.get("object") or {}).get("metadata") or {}
    bad = any(key.startswith(_APPARMOR) and value not in allowed
              for key, value in (meta.get("annotations") or {}).items())
    return _first((
        bad, "These AppArmor profiles are not allowed: not in the allowed list",
    )), None


def psp_capabilities(settings: dict, request: dict, signed: set) -> Verdict:
    allowed = settings.get("allowed_capabilities") or []
    if "*" not in allowed:
        for c in _containers(request):
            added = (_sc(c, "capabilities") or {}).get("add") or []
            if any(cap not in allowed for cap in added):
                return ("PSP capabilities policies doesn't allow these "
                        "capabilities to be added"), None
    required_drop = settings.get("required_drop_capabilities") or []
    default_add = settings.get("default_add_capabilities") or []
    ops: list[dict] = []
    spec = (request.get("object") or {}).get("spec") or {}
    for list_name in CONTAINER_LISTS:
        for i, c in enumerate(spec.get(list_name) or []):
            base = f"/spec/{list_name}/{i}/securityContext"
            sc = c.get("securityContext")
            caps = sc.get("capabilities") if isinstance(sc, dict) else None
            drop = list((caps or {}).get("drop") or [])
            add = list((caps or {}).get("add") or [])
            new_drop = drop + [x for x in required_drop if x not in drop]
            new_add = add + [x for x in default_add if x not in add]
            if new_drop == drop and new_add == add:
                continue
            if not isinstance(sc, dict):
                ops.append({"op": "add", "path": base, "value": {}})
            if not isinstance(caps, dict):
                ops.append({"op": "add", "path": f"{base}/capabilities",
                            "value": {}})
            if new_drop != drop:
                ops.append({"op": "add", "path": f"{base}/capabilities/drop",
                            "value": new_drop})
            if new_add != add:
                ops.append({"op": "add", "path": f"{base}/capabilities/add",
                            "value": new_add})
    return None, ops or None


def trusted_repos(settings: dict, request: dict, signed: set) -> Verdict:
    images = [c.get("image") if isinstance(c.get("image"), str) else ""
              for c in _containers(request)]
    registries = settings.get("registries") or {}
    rules: list[tuple[bool, str]] = []
    allow = [r.rstrip("/") + "/" for r in registries.get("allow") or []]
    if allow:
        rules.append((
            any(not any(i.startswith(r) for r in allow) for i in images),
            "not coming from an allowed registry",
        ))
    reject = [r.rstrip("/") + "/" for r in registries.get("reject") or []]
    if reject:
        rules.append((
            any(any(i.startswith(r) for r in reject) for i in images),
            "coming from a rejected registry",
        ))
    for tag in (settings.get("tags") or {}).get("reject") or []:
        rules.append((any(i.endswith(f":{tag}") for i in images),
                      f"tag '{tag}' is rejected"))
    return _first(*rules), None


def verify_image_signatures(settings: dict, request: dict,
                            signed: set) -> Verdict:
    patterns = [s["image"] for s in settings["signatures"]]

    def matched(image: str) -> bool:
        return any(fnmatch.fnmatchcase(image, p) for p in patterns)

    if any(isinstance(c.get("image"), str) and not matched(c["image"])
           for c in _containers(request)):
        return ("image signature verification failed: image matches no "
                "signature entry"), None
    bad = [i for i in _images(request) if matched(i) and i not in signed]
    if bad:
        return ("image signature verification failed for: "
                + ", ".join(f"'{i}'" for i in bad)), None
    return None, None


def raw_mutation(settings: dict, request: dict, signed: set) -> Verdict:
    if request.get("forbidden") is True:
        return "the request is forbidden", None
    if "validated" not in request:
        return None, [{"op": "add", "path": "/validated", "value": True}]
    return None, None


def replicas_max(settings: dict, request: dict, signed: set) -> Verdict:
    limit = settings["max_replicas"]
    replicas = ((request.get("object") or {}).get("spec") or {}).get("replicas")
    bad = isinstance(replicas, (int, float)) and replicas > limit
    return _first(
        (bad, f"spec.replicas must not exceed {int(limit)}")
    ), None


def namespace_validate(settings: dict, request: dict, signed: set) -> Verdict:
    ns = request.get("namespace")
    return _first((ns in settings["denied_namespaces"],
                   f"namespace '{ns}' is denied")), None


def _safe_keys(kind: str, plural: str) -> Callable[..., Verdict]:
    def policy(settings: dict, request: dict, signed: set) -> Verdict:
        meta = (request.get("object") or {}).get("metadata") or {}
        have = meta.get(plural) or {}
        rules = [(key not in have, f"mandatory {kind} {key!r} is missing")
                 for key in settings.get(f"mandatory_{plural}") or []]
        denied = settings.get(f"denied_{plural}") or []
        if denied:
            rules.append((any(k in denied for k in have),
                          f"a denied {kind} is present"))
        return _first(*rules), None
    return policy


MODULES: dict[str, Callable[..., Verdict]] = {
    "builtin://always-happy": always_happy,
    "builtin://always-unhappy": always_unhappy,
    "builtin://pod-privileged": pod_privileged,
    "builtin://host-namespaces": host_namespaces,
    "builtin://readonly-root-fs": readonly_root_fs,
    "builtin://run-as-non-root": run_as_non_root,
    "builtin://allowed-proc-mount-types": allowed_proc_mount_types,
    "builtin://hostpaths": hostpaths,
    "builtin://disallow-latest-tag": disallow_latest_tag,
    "builtin://psp-apparmor": psp_apparmor,
    "builtin://psp-capabilities": psp_capabilities,
    "builtin://trusted-repos": trusted_repos,
    "builtin://verify-image-signatures": verify_image_signatures,
    "builtin://raw-mutation": raw_mutation,
    "builtin://replicas-max": replicas_max,
    "builtin://namespace-validate": namespace_validate,
    "builtin://safe-labels": _safe_keys("label", "labels"),
    "builtin://safe-annotations": _safe_keys("annotation", "annotations"),
}

_TOKEN = re.compile(r"\s*(\|\||&&|!|\(|\)|[A-Za-z_][A-Za-z0-9_]*\(\))")


def _group(entry: dict, request: dict, signed: set) -> dict:
    """A policy group: its boolean expression over its members, evaluated
    left to right with short circuit; a rejection lists, in the order the
    policies file gives the members, every member that was evaluated and
    rejected."""
    tokens = _TOKEN.findall(entry["expression"])
    rejected: dict[str, str] = {}
    pos = 0

    def member(name: str, live: bool) -> bool:
        if not live:
            return True
        spec = entry["policies"][name]
        message, _ops = MODULES[spec["module"]](
            spec.get("settings") or {}, request, signed)
        if message is not None:
            rejected[name] = message
        return message is None

    def atom(live: bool) -> bool:
        nonlocal pos
        token = tokens[pos]
        pos += 1
        if token == "!":
            return not atom(live)
        if token == "(":
            value = either(live)
            pos += 1  # ")"
            return value
        return member(token[:-2], live)

    def both(live: bool) -> bool:
        nonlocal pos
        value = atom(live)
        while pos < len(tokens) and tokens[pos] == "&&":
            pos += 1
            right = atom(live and value)
            value = value and right
        return value

    def either(live: bool) -> bool:
        nonlocal pos
        value = both(live)
        while pos < len(tokens) and tokens[pos] == "||":
            pos += 1
            right = both(live and not value)
            value = value or right
        return value

    if either(True):
        return {"allowed": True}
    return {"allowed": False, "status": {
        "message": entry["message"], "code": 400,
        "details": {"causes": [
            {"field": f"spec.policies.{name}", "message": rejected[name]}
            for name in entry["policies"] if name in rejected
        ]},
    }}


def review_response(entry: dict, request: dict, signed: set) -> dict:
    """The ``response`` object of the AdmissionReview a policy entry
    answers, without its uid."""
    if "expression" in entry:
        return _group(entry, request, signed)
    message, ops = MODULES[entry["module"]](
        entry.get("settings") or {}, request, signed)
    if entry.get("policyMode") == "monitor":
        return {"allowed": True}
    if message is not None:
        return {"allowed": False, "status": {"message": message, "code": 400}}
    response: dict[str, Any] = {"allowed": True}
    if ops and entry.get("allowedToMutate"):
        response["patchType"] = "JSONPatch"
        response["patch"] = base64.b64encode(json.dumps(ops).encode()).decode()
    return response


def http_response(head_lines: list[str], uid: str, response: dict) -> bytes:
    """The whole HTTP answer as compared: status line and headers (the
    configuration's ``response_head``, ``Date`` left out, the body's length
    filled in), a blank line, the body."""
    body = json.dumps({
        "apiVersion": "admission.k8s.io/v1", "kind": "AdmissionReview",
        "response": {"uid": uid, **response},
    }).encode()
    head = "\r\n".join(head_lines).replace("{length}", str(len(body)))
    return head.encode() + b"\r\n\r\n" + body
