"""IR → JAX lowering: each policy becomes a pure function over batched
feature tensors; the full policy set fuses into ONE jit-compiled program.

This is the TPU-native replacement for the reference's per-request wasmtime
invocation (src/evaluation/evaluation_environment.rs:513-581) and its
AOT precompilation (src/evaluation/precompiled_policy.rs:46-64): "precompile"
here is jit lowering + XLA compilation, cached by (module digest, settings
digest) — see evaluation/precompiled.py.

Lowering rules (mirrored bit-exactly by evaluation/oracle.py):
* every sub-expression lowers to ``(values, n_elem_axes)`` where values has
  shape ``(B, *axis_prefix)`` — element axes are appended in quantifier
  nesting order, so any two operands align by trailing-dim broadcast;
* comparisons fold validity masks: missing operands ⇒ False;
* AnyOf = ``any(pred & domain_mask)``; AllOf = ``all(pred | ~domain_mask)``;
  CountOf = ``sum(pred & domain_mask)``;
* no data-dependent control flow — everything is masked elementwise ops the
  XLA fuser collapses into a handful of kernels (SURVEY.md §0 north star).

A policy program returns ``(allowed: bool(B,), rule_idx: int32(B,))`` where
rule_idx is the FIRST violated deny-rule (host side maps it to the message
template) or -1 when allowed.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Mapping

import jax.numpy as jnp
import numpy as np

from policy_server_tpu.ops import ir
from policy_server_tpu.ops.codec import BATCH_KEY, FeatureSchema, mask_key_for
from policy_server_tpu.ops.ir import CmpOp, DType, Expr, Path
from policy_server_tpu.utils.interning import InternTable

Features = Mapping[str, Any]


@dataclass
class Lowered:
    """A lowered sub-expression: shape (B, *axes[:naxes])."""

    values: Any
    naxes: int


def _align(a: Lowered, b: Lowered) -> tuple[Any, Any, int]:
    n = max(a.naxes, b.naxes)
    av, bv = a.values, b.values
    for _ in range(n - a.naxes):
        av = av[..., None]
    for _ in range(n - b.naxes):
        bv = bv[..., None]
    return av, bv, n


_CMP_FNS: dict[CmpOp, Callable[[Any, Any], Any]] = {
    CmpOp.EQ: lambda a, b: a == b,
    CmpOp.NE: lambda a, b: a != b,
    CmpOp.LT: lambda a, b: a < b,
    CmpOp.LE: lambda a, b: a <= b,
    CmpOp.GT: lambda a, b: a > b,
    CmpOp.GE: lambda a, b: a >= b,
}


def lower_expr(
    expr: Expr,
    features: Features,
    table: InternTable,
    cse: dict | None = None,
) -> Any:
    """Lower a typechecked boolean IR expression to a ``(B,)`` bool array.

    ``stack`` is the enclosing-quantifier domain stack (ir.DomainStack),
    threaded through the traversal — the same IR node may be reused under
    different quantifiers, so scope is contextual, never keyed on node
    identity.

    ``cse`` is the optimizer's shared let-binding table (round 15): a
    per-trace dict keyed by ``optimizer.scoped_key`` — identical scoped
    subtrees anywhere in the fused program lower to the SAME traced
    value, so a 32-policy set carrying pod-privileged three times
    computes it once. None disables sharing (``--predicate-opt off``).

    A leaf whose validity mask the schema elided (``FeatureSpec.masked``
    False — the optimizer proved every use False at the zero-fill) lowers
    mask-free: the mask key is simply absent from ``features``."""

    def value_of(e: Expr, stack: ir.DomainStack) -> tuple[Lowered, Lowered | None]:
        """→ (values, validity-mask or None-if-always-valid)."""
        if cse is not None and not isinstance(e, ir.Const):
            from policy_server_tpu.ops.optimizer import scoped_key

            memo_key = ("v", scoped_key(e, stack))
            hit = cse.get(memo_key)
            if hit is None:
                hit = cse[memo_key] = _value_of(e, stack)
            return hit
        return _value_of(e, stack)

    def _value_of(e: Expr, stack: ir.DomainStack) -> tuple[Lowered, Lowered | None]:
        if isinstance(e, ir.Const):
            if e.dtype is DType.ID:
                v = jnp.int32(table.intern(e.value))
            elif e.dtype is DType.F32:
                v = jnp.float32(e.value)
            elif e.dtype is DType.I32:
                v = jnp.int32(e.value)
            else:
                v = jnp.bool_(e.value)
            return Lowered(v, 0), None
        if isinstance(e, (Path, ir.Elem)):
            p = ir.absolute_path(e, stack)
            key = f"{p.key()}:v:{p.dtype.value}"
            vals = jnp.asarray(features[key])
            mask_arr = features.get(mask_key_for(key))
            if mask_arr is None:
                # mask elided by the optimizer: every use of this column
                # is provably False at the zero-fill (see ops/optimizer)
                return Lowered(vals, p.n_stars), None
            return (
                Lowered(vals, p.n_stars),
                Lowered(jnp.asarray(mask_arr), p.n_stars),
            )
        # boolean/integer-valued nodes used as values
        return Lowered(bool_of(e, stack), _naxes_of(e, stack)), None

    def _naxes_of(e: Expr, stack: ir.DomainStack) -> int:
        # number of element axes of a lowered node at its own scope
        if isinstance(e, (Path, ir.Elem)):
            return ir.absolute_path(e, stack).n_stars
        if isinstance(e, ir.Exists):
            return ir.absolute_path(e.target, stack).n_stars
        if isinstance(e, ir.StrPred):
            return ir.absolute_path(e.operand, stack).n_stars
        if isinstance(e, ir.Not):
            return _naxes_of(e.operand, stack)
        if isinstance(e, (ir.And, ir.Or)):
            return max(_naxes_of(op, stack) for op in e.operands)
        if isinstance(e, ir.Cmp):
            return max(_naxes_of(e.lhs, stack), _naxes_of(e.rhs, stack))
        if isinstance(e, ir.InSet):
            return _naxes_of(e.operand, stack)
        if isinstance(e, (ir.AnyOf, ir.AllOf, ir.CountOf)):
            # the domain axis is reduced away
            return ir.absolute_path(e.over, stack).n_stars - 1
        if isinstance(e, ir.Const):
            return 0
        raise ir.IRError(f"unknown IR node {type(e).__name__}")

    def _quantifier_parts(
        e: Any, stack: ir.DomainStack
    ) -> tuple[Any, Any]:
        """→ aligned (pred_values, domain_mask) for AnyOf/AllOf/CountOf."""
        dom = ir.absolute_path(e.over, stack)
        mask = jnp.asarray(features[f"{dom.key()}:p"])
        inner = stack + (dom,)
        pred = Lowered(bool_of(e.pred, inner), _naxes_of(e.pred, inner))
        m, pv, _ = _align(Lowered(mask, dom.n_stars), pred)
        return pv, m

    def bool_of(e: Expr, stack: ir.DomainStack) -> Any:
        if cse is not None and not isinstance(e, ir.Const):
            from policy_server_tpu.ops.optimizer import scoped_key

            memo_key = ("b", scoped_key(e, stack))
            hit = cse.get(memo_key)
            if hit is None:
                hit = cse[memo_key] = _bool_of(e, stack)
            return hit
        return _bool_of(e, stack)

    def _bool_of(e: Expr, stack: ir.DomainStack) -> Any:
        if isinstance(e, ir.Const):
            return jnp.bool_(e.value)
        if isinstance(e, ir.Exists):
            p = ir.absolute_path(e.target, stack)
            return jnp.asarray(features[f"{p.key()}:p"])
        if isinstance(e, ir.Not):
            return ~bool_of(e.operand, stack)
        if isinstance(e, (ir.And, ir.Or)):
            parts = [
                Lowered(bool_of(op, stack), _naxes_of(op, stack))
                for op in e.operands
            ]
            out = parts[0]
            combine = (lambda a, b: a & b) if isinstance(e, ir.And) else (lambda a, b: a | b)
            for p in parts[1:]:
                a, b, n = _align(out, p)
                out = Lowered(combine(a, b), n)
            return out.values
        if isinstance(e, ir.Cmp):
            lv, lm = value_of(e.lhs, stack)
            rv, rm = value_of(e.rhs, stack)
            a, b, n = _align(lv, rv)
            # numeric cross-dtype comparisons promote via jnp
            res = _CMP_FNS[e.op](a, b)
            out = Lowered(res, n)
            for m in (lm, rm):
                if m is not None:
                    mv, ov, n2 = _align(m, out)
                    out = Lowered(mv & ov, n2)
            return out.values
        if isinstance(e, ir.InSet):
            if not e.values:
                return jnp.bool_(False)
            ov, om = value_of(e.operand, stack)
            if e.dtype is DType.ID:
                vals = sorted(table.intern(v) for v in e.values)
                np_dtype = np.int32
            elif e.dtype is DType.F32:
                vals, np_dtype = sorted(e.values), np.float32
            elif e.dtype is DType.I32:
                vals, np_dtype = sorted(e.values), np.int32
            else:
                vals, np_dtype = sorted(e.values), np.bool_
            consts = np.asarray(vals, dtype=np_dtype)
            hits = jnp.any(
                ov.values[..., None] == jnp.asarray(consts), axis=-1
            )
            out = Lowered(hits, ov.naxes)
            if om is not None:
                mv, hv, n = _align(om, out)
                out = Lowered(mv & hv, n)
            return out.values
        if isinstance(e, ir.StrPred):
            p = ir.absolute_path(e.operand, stack)
            return jnp.asarray(features[f"{p.key()}:sp:{e.key()}"])
        if isinstance(e, ir.AnyOf):
            pv, m = _quantifier_parts(e, stack)
            return jnp.any(pv & m, axis=-1)
        if isinstance(e, ir.AllOf):
            pv, m = _quantifier_parts(e, stack)
            return jnp.all(pv | ~m, axis=-1)
        if isinstance(e, ir.CountOf):
            pv, m = _quantifier_parts(e, stack)
            return jnp.sum(pv & m, axis=-1, dtype=jnp.int32)
        raise ir.IRError(f"cannot lower {type(e).__name__} as boolean")

    return bool_of(expr, ())


# --------------------------------------------------------------------------
# Policy programs
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Rule:
    """One deny-rule of a policy: ``condition`` True ⇒ the rule is violated.
    ``message`` is a host-side template: str or fn(payload, settings) -> str
    (device selects the rule index; host materializes the text —
    SURVEY.md §7.4 hard-part #3 applied to messages)."""

    name: str
    condition: Expr
    message: str | Callable[[Any], str]


@dataclass(frozen=True)
class PolicyProgram:
    """A policy bound to its settings: ordered deny rules + optional host
    mutator. ``allowed = not any(rule violated)``; the first violated rule
    selects the rejection message (rules are priority-ordered)."""

    rules: tuple[Rule, ...]
    # host-side mutation hook: fn(payload) -> list of JSONPatch ops or None.
    # Only consulted when the verdict is "allowed" and the policy mutates
    # (mirrors reference patch flow, src/api/service.rs:160-208).
    mutator: Callable[[Any], list[dict] | None] | None = None
    # host-side pre-evaluation hook (latency-fault fixtures like the
    # 'sleeping' builtin — the reference's sleeping-policy analog,
    # tests/integration_test.rs:367-423). Runs before encoding; subject to
    # the policy-timeout deadline.
    pre_eval_hook: Callable[[Any], None] | None = None
    # host-side context provider: fn(payload) -> {context_key: [objects]}
    # merged into the payload's __context__ slice at encode time. This is
    # how host capabilities with per-request inputs (image-signature
    # verification — the reference's sigstore callback,
    # SURVEY.md §2.2 callback_handler row) feed their CACHED results to
    # the device program: the pre_eval_hook does the blocking work under
    # the request deadline, the provider is a pure cache read.
    context_provider: Callable[[Any], Mapping[str, list]] | None = None
    # host-executed policies (wasm modules, evaluation/wasm_policy.py):
    # fn(payload) -> {"accepted": bool, "message"?, "code"?,
    # "mutated_object"?}. When set, the environment routes this policy's
    # rows through host-side wasm execution; the device rules are inert.
    host_evaluator: Callable[[Any], Mapping[str, Any]] | None = None

    def typecheck(self) -> None:
        if not self.rules:
            raise ir.IRError("policy must define at least one rule")
        for r in self.rules:
            ir.typecheck(r.condition)

    def exprs(self) -> list[Expr]:
        return [r.condition for r in self.rules]


def compile_program(
    program: PolicyProgram,
    schema: FeatureSchema,
    table: InternTable,
    conditions: "tuple[Any, ...] | None" = None,
) -> Callable[..., tuple[Any, Any]]:
    """→ fn(features, cse=None) -> (allowed (B,), rule_idx (B,) int32,
    -1 if allowed).

    The returned fn is pure and trace-safe; the evaluation environment
    fuses all policies' fns into one jitted program per batch bucket,
    threading one shared ``cse`` table through every policy so identical
    scoped subtrees lower once (ops/optimizer.py).

    ``conditions``: optimizer-folded per-rule conditions aligned with
    ``program.rules`` (indices never shift — the materializer maps
    ``rule_idx`` into the ORIGINAL rule tuple). Constant-False
    conditions skip the lowered stack entirely; a constant-True
    condition lowers as a broadcast (rules after it were already folded
    to False by the optimizer)."""
    conds = (
        conditions
        if conditions is not None
        else tuple(r.condition for r in program.rules)
    )
    assert len(conds) == len(program.rules)

    def fn(
        features: Features,
        cse: dict | None = None,
    ) -> tuple[Any, Any]:
        batch = jnp.shape(jnp.asarray(features[BATCH_KEY]))
        # the stack keeps FULL rule length: folded-constant conditions
        # lower as scalar broadcasts (free after XLA constant folding),
        # so rule indices never shift and no index-map array constant is
        # needed
        violated = jnp.stack(
            [
                jnp.broadcast_to(
                    lower_expr(c, features, table, cse=cse),
                    batch,
                )
                for c in conds
            ],
            axis=-1,
        )  # (B, R)
        any_violated = jnp.any(violated, axis=-1)
        first = jnp.argmax(violated, axis=-1).astype(jnp.int32)
        rule_idx = jnp.where(any_violated, first, jnp.int32(-1))
        return ~any_violated, rule_idx

    return fn


def compile_constant(
    allowed: bool, rule_idx: int
) -> Callable[..., tuple[Any, Any]]:
    """A policy whose verdict the optimizer folded to a constant: no
    predicate work on device, just two broadcasts XLA constant-folds.
    Output columns (and therefore materialized responses, metrics, and
    audit report rows) are identical to the unoptimized program's."""

    def fn(
        features: Features,
        cse: dict | None = None,
    ) -> tuple[Any, Any]:
        batch = jnp.shape(jnp.asarray(features[BATCH_KEY]))
        return (
            jnp.broadcast_to(jnp.bool_(allowed), batch),
            jnp.broadcast_to(jnp.int32(rule_idx), batch),
        )

    return fn
