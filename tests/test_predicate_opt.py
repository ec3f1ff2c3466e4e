"""Predicate-program optimizer (round 15, ops/optimizer.py).

Three layers of proof:

1. **Golden IR fixtures per pass** — constant folding (boolean
   identities, exact Cmp/InSet evaluation, quantifier folds,
   unreachable-rule elimination), scoped-key CSE identity, and the
   zero-fill validity-mask elision analysis, each pinned on
   hand-written IR.
2. **Differential sweep over the builtin family catalog** — every
   family (mutators included, so patches are covered) judged by three
   independent executors on the same corpus: opt-on device, opt-off
   device, and the host oracle interpreting the ORIGINAL IR. Byte-
   identical AdmissionResponses required.
3. **Constant-verdict lifecycle regression** — a policy folding to a
   constant DENY drops out of the device program, but its per-policy
   audit report rows, responses, and messages must be indistinguishable
   from the unoptimized program's.
"""

from __future__ import annotations

import numpy as np
import pytest

from policy_server_tpu.evaluation.environment import (
    EvaluationEnvironmentBuilder,
)
from policy_server_tpu.models import AdmissionReviewRequest, ValidateRequest
from policy_server_tpu.models.policy import parse_policy_entry
from policy_server_tpu.ops import ir, optimizer
from policy_server_tpu.ops.codec import FeatureSchema
from policy_server_tpu.ops.ir import (
    AllOf,
    And,
    AnyOf,
    Cmp,
    CmpOp,
    Const,
    CountOf,
    DType,
    Elem,
    InSet,
    Not,
    Or,
    Path,
    eq,
    false,
    gt,
    in_set,
    true,
)
from policy_server_tpu.policies.flagship import (
    flagship_policies,
    synthetic_firehose,
)

from conftest import build_admission_review_dict


def to_request(doc: dict) -> ValidateRequest:
    return ValidateRequest.from_admission(
        AdmissionReviewRequest.from_dict(doc).request
    )


def review_of(obj: dict, namespace: str = "default") -> dict:
    """A well-formed AdmissionReview doc around ``obj``."""
    doc = build_admission_review_dict()
    name = (obj.get("metadata") or {}).get("name", "x")
    doc["request"].update(
        uid=f"predopt-{namespace}-{name}",
        name=name,
        namespace=namespace,
        operation="CREATE",
        kind={"group": "", "version": obj.get("apiVersion", "v1"),
              "kind": obj.get("kind", "Pod")},
        object=obj,
    )
    return doc


def build(policies: dict, **kw):
    return EvaluationEnvironmentBuilder(backend="jax", **kw).build(
        {k: parse_policy_entry(k, v) for k, v in policies.items()}
    )


# ---------------------------------------------------------------------------
# golden fixtures: constant folding
# ---------------------------------------------------------------------------


PRIV = eq(Elem("securityContext.privileged"), True)
NS = eq(Path("namespace", DType.ID), "kube-system")


class TestFoldExpr:
    def test_boolean_identities(self):
        # absorbing / neutral operands
        assert optimizer.fold_expr(And((PRIV, false()))) == false()
        assert optimizer.fold_expr(And((PRIV, true()))) is PRIV
        assert optimizer.fold_expr(Or((PRIV, true()))) == true()
        assert optimizer.fold_expr(Or((PRIV, false()))) is PRIV
        assert optimizer.fold_expr(Not(true())) == false()
        assert optimizer.fold_expr(Not(false())) == true()
        # a no-fold tree returns the SAME object (CSE keys stay shared)
        tree = And((PRIV, NS))
        assert optimizer.fold_expr(tree) is tree

    def test_cmp_and_inset_fold_exactly(self):
        five = Const(5, DType.I32)
        six = Const(6, DType.I32)
        assert optimizer.fold_expr(Cmp(CmpOp.LT, five, six)) == true()
        assert optimizer.fold_expr(Cmp(CmpOp.GE, five, six)) == false()
        assert optimizer.fold_expr(
            Cmp(CmpOp.EQ, Const("a", DType.ID), Const("a", DType.ID))
        ) == true()
        assert optimizer.fold_expr(
            InSet(Const("x", DType.ID), frozenset({"x", "y"}), DType.ID)
        ) == true()
        assert optimizer.fold_expr(
            InSet(Const("z", DType.ID), frozenset({"x", "y"}), DType.ID)
        ) == false()
        # empty InSet is vacuously false whatever the operand
        assert optimizer.fold_expr(
            InSet(Elem("name"), frozenset(), DType.ID)
        ) == false()
        # f32 comparison folds with numpy f32 semantics, not python float
        a = Const(0.1, DType.F32)
        b = Const(np.float32(0.1), DType.F32)
        assert optimizer.fold_expr(Cmp(CmpOp.EQ, a, b)) == true()

    def test_quantifier_folds(self):
        dom = Path("object.spec.containers")
        assert optimizer.fold_expr(AnyOf(dom, false())) == false()
        assert optimizer.fold_expr(AllOf(dom, true())) == true()
        folded = optimizer.fold_expr(CountOf(dom, false()))
        assert folded == Const(0, DType.I32)
        # domain-size-dependent shapes do NOT fold structurally
        any_true = AnyOf(dom, true())
        assert optimizer.fold_expr(any_true) is any_true
        all_false = AllOf(dom, false())
        assert optimizer.fold_expr(all_false) is all_false

    def test_fold_is_recursive(self):
        tree = Or((And((PRIV, Not(false()))), And((NS, false()))))
        assert optimizer.fold_expr(tree) is PRIV


class TestFoldPolicy:
    def test_rules_after_constant_true_fold_to_false(self):
        po = optimizer.fold_policy((PRIV, true(), NS))
        assert po.conditions[0] is PRIV
        assert po.conditions[1] == true()
        assert po.conditions[2] == false()  # unreachable, never FIRST
        assert po.constant is None  # rule 0 still needs the device

    def test_constant_deny_and_allow(self):
        deny = optimizer.fold_policy((false(), true(), PRIV))
        assert deny.constant == (False, 1)  # denied by rule index 1
        allow = optimizer.fold_policy((false(), And((PRIV, false()))))
        assert allow.constant == (True, -1)
        assert optimizer.fold_policy(()).constant == (True, -1)


# ---------------------------------------------------------------------------
# golden fixtures: scoped-key CSE identity
# ---------------------------------------------------------------------------


class TestScopedKeys:
    def test_identical_subtrees_share_keys_across_policies(self):
        dom = ir.absolute_path(Path("object.spec.containers"), ())
        a = eq(Elem("securityContext.privileged"), True)
        b = eq(Elem("securityContext.privileged"), True)
        assert a is not b
        assert optimizer.scoped_key(a, (dom,)) == optimizer.scoped_key(
            b, (dom,)
        )

    def test_same_shape_under_different_domains_differs(self):
        pods = ir.absolute_path(Path("object.spec.containers"), ())
        inits = ir.absolute_path(Path("object.spec.initContainers"), ())
        e = eq(Elem("image"), "busybox")
        assert optimizer.scoped_key(e, (pods,)) != optimizer.scoped_key(
            e, (inits,)
        )
        assert optimizer.scoped_key(e, (pods,)) == optimizer.scoped_key(
            eq(Elem("image"), "busybox"), (pods,)
        )

    def test_inset_key_is_order_insensitive(self):
        dom = (
            ir.absolute_path(Path("object.spec.containers"), ()),
        )
        k1 = optimizer.scoped_key(in_set(Elem("name"), ["b", "a"]), dom)
        k2 = optimizer.scoped_key(in_set(Elem("name"), ["a", "b"]), dom)
        assert k1 == k2

    def test_set_pass_counts_shared_subtrees(self):
        shared = AnyOf(Path("object.spec.containers"), PRIV)
        programs = {
            "p1": _program((shared,)),
            "p2": _program((AnyOf(Path("object.spec.containers"),
                                  eq(Elem("securityContext.privileged"),
                                     True)),)),
            "p3": _program((NS,)),
        }
        opt = optimizer.optimize_policy_set(programs)
        # the quantifier AND its inner Cmp are each shared once
        assert opt.subtrees_shared >= 2
        assert opt.policies_folded == 0


def _program(conditions):
    from policy_server_tpu.ops.compiler import PolicyProgram, Rule

    return PolicyProgram(
        rules=tuple(
            Rule(f"r{i}", c, f"rule {i}") for i, c in enumerate(conditions)
        )
    )


# ---------------------------------------------------------------------------
# golden fixtures: validity-mask elision + dead-field pruning
# ---------------------------------------------------------------------------


class TestMaskElision:
    def test_cmp_needs_mask_matrix(self):
        num = Path("object.spec.replicas", DType.F32)
        # x > 10 at zero-fill: 0 > 10 is False -> mask-free
        assert not optimizer._cmp_needs_mask(
            CmpOp.GT, num, Const(10.0, DType.F32)
        )
        # x < 10 at zero-fill: 0 < 10 is True -> mask required
        assert optimizer._cmp_needs_mask(
            CmpOp.LT, num, Const(10.0, DType.F32)
        )
        # id equality: MISSING id 0 never equals an interned string
        sid = Path("namespace", DType.ID)
        assert not optimizer._cmp_needs_mask(
            CmpOp.EQ, sid, Const("kube-system", DType.ID)
        )
        assert optimizer._cmp_needs_mask(
            CmpOp.NE, sid, Const("kube-system", DType.ID)
        )
        # bool == True is False at the zero-fill; == False is True
        b = Elem("securityContext.privileged", DType.BOOL)
        assert not optimizer._cmp_needs_mask(
            CmpOp.EQ, b, Const(True, DType.BOOL)
        )
        assert optimizer._cmp_needs_mask(
            CmpOp.EQ, b, Const(False, DType.BOOL)
        )
        # leaf-vs-leaf comparisons always keep the mask
        assert optimizer._cmp_needs_mask(
            CmpOp.EQ, sid, Path("object.metadata.name", DType.ID)
        )

    def test_inset_needs_mask(self):
        assert not optimizer._inset_needs_mask(
            in_set(Path("namespace", DType.ID), ["a", "b"])
        )
        i32 = Path("object.spec.replicas", DType.I32)
        assert optimizer._inset_needs_mask(
            InSet(i32, frozenset({0, 3}), DType.I32)
        )
        assert not optimizer._inset_needs_mask(
            InSet(i32, frozenset({1, 3}), DType.I32)
        )

    def test_schema_drops_elided_mask_columns(self):
        cond = gt(Path("object.spec.replicas", DType.F32), 10.0)
        opt = optimizer.optimize_policy_set({"p": _program((cond,))})
        key = "object.spec.replicas:v:f32"
        assert key in opt.unmasked_value_keys
        schema = FeatureSchema.build(
            opt.surviving_exprs, unmasked=opt.unmasked_value_keys
        )
        base = FeatureSchema.build([cond])
        assert key in schema.specs
        assert not schema.specs[key].has_mask
        assert base.specs[key].has_mask
        # the byte region is strictly smaller without the mask lane
        # (row WIDTH may hide it behind 4-byte alignment padding)
        assert schema.packed_layout().total8 < base.packed_layout().total8

    def test_constant_policy_fields_prune_from_schema(self):
        env = build({
            "priv": {"module": "builtin://pod-privileged"},
            # folds to constant-allow: its rule condition is false()
            "noop": {"module": "builtin://always-happy"},
            # folds to constant-deny: its whole feature need disappears
            "deny": {"module": "builtin://always-unhappy"},
        })
        assert env.optimization is not None
        assert env.optimization.policies["noop"].constant == (True, -1)
        assert env.optimization.policies["deny"].constant == (False, 0)
        stats = env.optimizer_stats
        assert stats["policies_folded"] == 2

    def test_unreachable_rule_fields_prune_from_schema(self):
        """A field read ONLY by a rule the fold proved unreachable loses
        its gather column; a mask-elided comparison loses its ':m:'
        lane."""
        name_read = eq(Path("object.metadata.name", DType.ID), "x")
        p_dead = _program((true(), name_read))  # rule 1 unreachable
        p_live = _program(
            (gt(Path("object.spec.replicas", DType.F32), 10.0),)
        )
        opt = optimizer.optimize_policy_set(
            {"dead": p_dead, "live": p_live}
        )
        schema = FeatureSchema.build(
            opt.surviving_exprs, unmasked=opt.unmasked_value_keys
        )
        base = FeatureSchema.build(
            [name_read, gt(Path("object.spec.replicas", DType.F32), 10.0)]
        )
        assert "object.metadata.name:v:id" in base.specs
        assert "object.metadata.name:v:id" not in schema.specs
        assert not schema.specs["object.spec.replicas:v:f32"].has_mask
        assert schema.packed_layout().width < base.packed_layout().width


# ---------------------------------------------------------------------------
# the family-catalog differential sweep (patches included)
# ---------------------------------------------------------------------------

# one representative entry per builtin family (settings chosen to
# exercise fold/CSE/mask-elision shapes, not just defaults).
# verify-image-signatures needs cryptography at build time — added in
# the fixture when importable, skipped (not errored) otherwise.
FAMILY_CATALOG: dict[str, dict] = {
    "always-happy": {"module": "builtin://always-happy"},
    "always-unhappy": {"module": "builtin://always-unhappy",
                       "settings": {"message": "nope"}},
    "sleeping": {"module": "builtin://sleeping",
                 "settings": {"sleep_ms": 0}},
    "namespace-validate": {
        "module": "builtin://namespace-validate",
        "settings": {"denied_namespaces": ["blocked", "kube-system"]},
    },
    "namespace-exists": {"module": "builtin://namespace-exists"},
    "pod-privileged": {"module": "builtin://pod-privileged"},
    "psp-capabilities": {
        "module": "builtin://psp-capabilities",
        "settings": {
            "allowed_capabilities": ["CHOWN"],
            "required_drop_capabilities": ["NET_ADMIN"],
        },
    },
    "psp-apparmor": {
        "module": "builtin://psp-apparmor",
        "settings": {"allowed_profiles": ["runtime/default"]},
    },
    "trusted-repos": {
        "module": "builtin://trusted-repos",
        "settings": {
            "registries": {"reject": ["registry.local"]},
            "tags": {"reject": ["latest"]},
        },
    },
    "disallow-latest-tag": {"module": "builtin://disallow-latest-tag"},
    "host-namespaces": {"module": "builtin://host-namespaces"},
    "readonly-root-fs": {"module": "builtin://readonly-root-fs"},
    "safe-labels": {
        "module": "builtin://safe-labels",
        "settings": {"mandatory_labels": ["app"],
                     "denied_labels": ["cost-center"]},
    },
    "safe-annotations": {
        "module": "builtin://safe-annotations",
        "settings": {"denied_annotations": ["example.com/unsafe"]},
    },
    "replicas-max": {
        "module": "builtin://replicas-max",
        "settings": {"max_replicas": 4},
    },
    "run-as-non-root": {"module": "builtin://run-as-non-root"},
    "allowed-proc-mount-types": {
        "module": "builtin://allowed-proc-mount-types",
        "settings": {"allowed_types": ["Default"]},
    },
    "hostpaths": {
        "module": "builtin://hostpaths",
        "settings": {"allowed_host_paths": [{"pathPrefix": "/data"}]},
    },
    "raw-mutation": {
        "module": "builtin://raw-mutation", "allowedToMutate": True,
    },
    "user-group-psp": {
        "module": "builtin://user-group-psp",
        "settings": {
            "run_as_user": {"rule": "MustRunAs",
                            "ranges": [{"min": 1000, "max": 2000}]},
            "run_as_group": {"rule": "MustRunAsNonRoot"},
        },
    },
    "sysctl-psp": {
        "module": "builtin://sysctl-psp",
        "settings": {"forbidden_sysctls": ["kernel.*"],
                     "allowed_unsafe_sysctls": ["kernel.shm_rmid_forced"]},
    },
    "containers-resource-limits": {
        "module": "builtin://containers-resource-limits",
        "settings": {"require_cpu": True, "require_memory": True},
    },
    "environment-variable-policy": {
        "module": "builtin://environment-variable-policy",
        "settings": {"denied_names": ["AWS_SECRET_ACCESS_KEY"]},
    },
    "selinux-psp": {
        "module": "builtin://selinux-psp",
        "settings": {"rule": "MustRunAs", "type": "container_t"},
    },
    # mutating group member + pod policies in one group expression
    "psp-group": {
        "expression": "unpriv() && nonroot()",
        "message": "baseline not met",
        "policies": {
            "unpriv": {"module": "builtin://pod-privileged"},
            "nonroot": {"module": "builtin://run-as-non-root"},
        },
    },
}


def _catalog_entries():
    # verify-image-signatures (the 25th family) is host-executed and
    # needs cryptography key material at build time; the device-path
    # passes under test never see it, and the flagship differential
    # (test_differential.py) already covers its group shape
    return {
        k: parse_policy_entry(k, v) for k, v in FAMILY_CATALOG.items()
    }


@pytest.fixture(scope="module")
def catalog_envs():
    entries = _catalog_entries()
    return {
        "opt": EvaluationEnvironmentBuilder(
            backend="jax", predicate_opt=True
        ).build(entries),
        "noopt": EvaluationEnvironmentBuilder(
            backend="jax", predicate_opt=False
        ).build(entries),
        "oracle": EvaluationEnvironmentBuilder(
            backend="oracle"
        ).build(entries),
    }


def _catalog_items(n_docs: int, seed: int):
    docs = synthetic_firehose(n_docs, seed=seed)
    pids = sorted(FAMILY_CATALOG)
    items = []
    for i, doc in enumerate(docs):
        items.append((pids[i % len(pids)], to_request(doc)))
    # targeted shapes the firehose rarely draws
    extra_objs = [
        {"kind": "Pod", "apiVersion": "v1",
         "metadata": {"name": "lab", "labels": {"cost-center": "x"}},
         "spec": {}},
        {"kind": "Deployment", "apiVersion": "apps/v1",
         "metadata": {"name": "big"}, "spec": {"replicas": 9}},
        {"kind": "Pod", "apiVersion": "v1", "metadata": {"name": "sy"},
         "spec": {"securityContext": {
             "sysctls": [{"name": "kernel.msgmax", "value": "1"}]}}},
        {"kind": "Pod", "apiVersion": "v1", "metadata": {"name": "hp"},
         "spec": {"volumes": [{"name": "v",
                               "hostPath": {"path": "/etc/shadow"}}]}},
    ]
    for obj in extra_objs:
        doc = review_of(obj)
        for pid in pids:
            items.append((pid, to_request(doc)))
    return items


@pytest.mark.parametrize("seed", [11, 22])
def test_family_catalog_triway_bit_exact(catalog_envs, seed):
    """Every builtin family (mutators included — patches ride in the
    response): opt-on, opt-off, and oracle must produce byte-identical
    AdmissionResponses."""
    items = _catalog_items(50, seed)
    results = {}
    for name, env in catalog_envs.items():
        env.reset_verdict_cache()
        results[name] = [
            r.to_dict() if not isinstance(r, Exception) else repr(r)
            for r in env.validate_batch(items)
        ]
    for i, (pid, _req) in enumerate(items):
        assert results["opt"][i] == results["noopt"][i], (
            pid, results["opt"][i], results["noopt"][i],
        )
        assert results["opt"][i] == results["oracle"][i], (
            pid, results["opt"][i], results["oracle"][i],
        )


def test_catalog_pass_is_not_vacuous(catalog_envs):
    """Acceptance guard: the optimizer must find real work on the
    catalog (shared subtrees AND pruned fields), or the differential
    above proves nothing about the passes."""
    stats = catalog_envs["opt"].optimizer_stats
    assert stats["subtrees_shared"] > 0
    assert stats["fields_pruned"] > 0
    assert stats["policies_folded"] >= 2  # always-happy/unhappy+sleeping
    assert stats["row_bytes_saved"] > 0


def test_flagship_pass_is_not_vacuous():
    env = EvaluationEnvironmentBuilder(backend="jax").build(
        flagship_policies()
    )
    stats = env.optimizer_stats
    assert stats["subtrees_shared"] > 0
    assert stats["fields_pruned"] > 0


def test_mutation_patches_identical_under_opt(catalog_envs):
    """The raw-mutation mutator's JSONPatch must be byte-identical
    opt-on vs opt-off vs oracle (patches materialize host-side from the
    device verdict — a folded policy must not disturb them)."""
    req = ValidateRequest.from_raw(
        {"uid": "raw-1", "operation": "create",
         "resource": {"replicas": 2}}
    )
    out = {}
    for name, env in catalog_envs.items():
        r = env.validate("raw-mutation", req)
        out[name] = r.to_dict()
        assert r.patch is not None, name
    assert out["opt"] == out["noopt"] == out["oracle"]


# ---------------------------------------------------------------------------
# constant-deny lifecycle regression
# ---------------------------------------------------------------------------


def test_constant_deny_policy_still_reports_everywhere():
    """always-unhappy folds to a constant DENY and leaves the device
    program — responses, messages, and per-policy audit report rows must
    be identical to the unoptimized build's."""
    from types import SimpleNamespace

    from policy_server_tpu.audit import (
        AuditScanner,
        PolicyReportStore,
        SnapshotStore,
    )
    from policy_server_tpu.runtime.batcher import MicroBatcher

    policies = {
        "deny-all": {"module": "builtin://always-unhappy",
                     "settings": {"message": "frozen out"}},
        "priv": {"module": "builtin://pod-privileged"},
    }
    rows = {}
    responses = {}
    for mode in (True, False):
        env = build(policies, predicate_opt=mode)
        if mode:
            assert env.optimization is not None
            assert env.optimization.policies["deny-all"].constant == (
                False, 0,
            )
        doc = review_of(
            {"kind": "Pod", "apiVersion": "v1",
             "metadata": {"name": "pod-a"}, "spec": {}}
        )
        r = env.validate("deny-all", to_request(doc))
        assert r.allowed is False
        assert r.status.message == "frozen out"
        responses[mode] = r.to_dict()

        batcher = MicroBatcher(
            env, max_batch_size=8, policy_timeout=10.0
        ).start()
        try:
            state = SimpleNamespace(
                evaluation_environment=env, batcher=batcher,
                lifecycle=None,
            )
            scanner = AuditScanner(
                state=state, snapshot=SnapshotStore(),
                reports=PolicyReportStore(), mode="interval",
                interval_seconds=30.0, batch_size=4,
            )
            scanner.snapshot.observe([to_request(doc)])
            assert scanner.sweep(full=True) == 2  # 1 resource × 2 policies
            body = scanner.report_payload()
            rows[mode] = {
                (row["name"], row["policy_id"]): (
                    row["allowed"], row["message"]
                )
                for row in body["reports"]
            }
        finally:
            batcher.shutdown()
    assert responses[True] == responses[False]
    assert rows[True] == rows[False]
    assert rows[True][("pod-a", "deny-all")] == (False, "frozen out")
