"""EvaluationEnvironment — the core registry + batched evaluator.

Reference parity: src/evaluation/evaluation_environment.rs —
* immutable registry built at boot (builder → environment, rs:130-366):
  module dedup by digest (rs:100-108), per-policy settings
  (rs:104-112), ``policy_initialization_errors`` map (rs:114-117, fed by
  --continue-on-errors semantics, lib.rs:152-158), group set (rs:120);
* settings validated at boot (rs:472-510), group expressions type-checked
  at boot (rs:1075-1112);
* ``validate(policy_id, request)`` dispatching single vs group
  (rs:546-556), PolicyNotFound / PolicyInitialization errors (rs:562-581);
* group cause aggregation + short-circuit semantics (rs:979-1042).

TPU-native execution model (replaces per-request wasm rehydration,
rs:513-543): ALL loaded policies and group expressions fuse into ONE
jit-compiled program over the batch's feature tensors; a request batch is
encoded once and every verdict falls out of a single device dispatch.
Per-request isolation is free — programs are pure functions, the fused
program is stateless, so there is nothing to rehydrate.

Backends: ``jax`` (device path) and ``oracle`` (host interpreter,
evaluation/oracle.py) — requests that overflow the feature schema
(ops/codec.py SchemaOverflow) transparently fall back to the oracle and are
counted (SURVEY.md §7.4 escape hatch).
"""

from __future__ import annotations

import base64
import functools
import json
import operator
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from policy_server_tpu import failpoints
from policy_server_tpu.resilience import CircuitBreaker
from policy_server_tpu.telemetry import flightrec
from policy_server_tpu.evaluation import groups as groups_mod
from policy_server_tpu.evaluation import oracle as oracle_mod
from policy_server_tpu.evaluation.errors import (
    BootstrapFailure,
    PolicyInitializationError,
    PolicyNotFoundError,
)
from policy_server_tpu.evaluation.policy_id import PolicyID
from policy_server_tpu.evaluation.precompiled import (
    PolicyModule,
    PrecompiledPolicy,
    ProgramCache,
)
from policy_server_tpu.evaluation.settings import PolicyEvaluationSettings
from policy_server_tpu.evaluation.verdict_cache import (
    DedupTiers,
    OutputLayout,
    PackedRow,
    row_bytes,
)
from policy_server_tpu.models import (
    AdmissionResponse,
    FragTemplate,
    FragVerdict,
    StatusCause,
    StatusDetails,
    ValidateRequest,
    ValidationStatus,
)
from policy_server_tpu.models.admission import JSON_PATCH
from policy_server_tpu.models.policy import (
    Policy,
    PolicyGroup,
    PolicyMode,
    PolicyOrPolicyGroup,
)
from policy_server_tpu.context.service import CONTEXT_KEY
from policy_server_tpu.ops.codec import (
    BATCH_KEY,
    DEFAULT_AXIS_CAP,
    DEFAULT_NESTED_AXIS_CAP,
    PACKED_KEY,
    FeatureSchema,
    SchemaOverflow,
    ensure_unique_packed_widths,
)
from policy_server_tpu.ops import fastenc
from policy_server_tpu.ops.compiler import compile_program
from policy_server_tpu.policies import resolve_builtin
from policy_server_tpu.utils.interning import InternTable

# distinct from None: None DISABLES the wasm wall-clock budget
# (--disable-timeout-protection), the sentinel leaves module defaults
_BUDGET_UNSET = object()

GROUP_MUTATION_MESSAGE = "mutation is not allowed inside of policy group"

# Device-input feature key carrying host-computed wasm group-member verdict
# bits, shape (batch, n_wasm_members) bool — how host-executed policies
# participate in the fused on-device group reduction.
WASM_BITS_KEY = "__wasm_bits__"

# Default budget of the dedup tiers in BYTES, sized to working-set scale
# (verdict_cache.py: each tier's half holds ~95,000 flagship entries).
# 0 disables caching AND in-batch row dedup.
DEFAULT_VERDICT_CACHE_SIZE = 256 * 1024 * 1024


# -- pre-serialized verdict fragments (round 19) -----------------------------
# An output row of a fragment-eligible target, a tier's hit or a slot the
# device answered, answers as uid + FragTemplate (_answer_rows): the
# batcher splices it instead of an AdmissionResponse built per row. The
# templates live in the environment (_frag_of), one per target and
# verdict, and not on the rows, which are immutable. A target's
# memo that outgrows this many verdicts starts again (a group of many
# members has many member-verdict combinations; traffic shows a handful).
_FRAG_MEMO_MAX = 4096

# Thread-local arming flag: FragVerdicts are only returned to callers
# that PROVABLY handle them (the MicroBatcher's fused pipeline, which
# runs begin+finish on one thread — batcher._fused_validate). Direct
# validate_batch callers (tests, canary replay, audit scanner, the
# single-request API) keep getting AdmissionResponse rows.
_frag_scope = threading.local()


class fragment_responses:
    """Context manager arming the fragment lane on this thread (see
    _answer_rows). Entered by the batcher around the fused
    encode→device→fetch chain."""

    __slots__ = ("_prev",)

    def __enter__(self) -> "fragment_responses":
        self._prev = getattr(_frag_scope, "on", False)
        _frag_scope.on = True
        return self

    def __exit__(self, *exc) -> None:
        _frag_scope.on = self._prev


def _fragments_enabled() -> bool:
    return getattr(_frag_scope, "on", False)


# The names XLA knows the fused programs by (``jit_<name>``, which a
# device trace shows as ``jit__forward(...)`` / ``jit__forward_planes(...)``).
# They are an interface: the benchmark finds the programs' device time by
# the patterns of benchmarks/layer_metrics/predicate_roofline.json, so they
# are fixed here, not taken from whatever the methods are called.
FUSED_PROGRAM_NAME = "_forward"
FUSED_PLANES_PROGRAM_NAME = "_forward_planes"


def _named_program(fn: Callable, name: str) -> Callable:
    """``fn`` under a fixed ``__name__``: what ``jax.jit`` names the
    program it compiles from it."""

    @functools.wraps(fn)
    def program(*args: Any) -> Any:
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    return program


class _InlineFetch:
    """Drain-future stand-in for the single-chunk serving path (round
    19): ``.result()`` runs the device fetch ON the calling thread — the
    fused pipeline worker that would otherwise park on a drain-pool
    future — instead of paying a pool crossing + future-wake per chunk.
    Multi-chunk passes keep the drain pool so fetch latency overlaps
    across chunks."""

    __slots__ = ("_fn", "_args")

    def __init__(self, fn: Callable, *args: Any) -> None:
        self._fn = fn
        self._args = args

    def result(self) -> Any:
        return self._fn(*self._args)


class _RowView:
    """Zero-copy row view over the batched output arrays — materializers
    index ``outputs[key][row]`` lazily instead of copying a per-row dict of
    every key (the per-row dict copies dominated host time at round-1
    batch sizes)."""

    __slots__ = ("_outputs", "_row")

    def __init__(self, outputs: Mapping[str, Any], row: int):
        self._outputs = outputs
        self._row = row

    def __getitem__(self, key: str) -> Any:
        return self._outputs[key][self._row]

    def get(self, key: str, default: Any = None) -> Any:
        arr = self._outputs.get(key)
        return default if arr is None else arr[self._row]


# The key of the one wire buffer in a columnar launch's shipped dict.
WIRE_KEY = "wire"
# The wire planes in the order their regions follow each other in a wire
# row, with the bytes a column takes there (the bool lanes pack 8 to a
# byte): 4-byte columns first, then 2-byte ones, so both stay aligned.
_WIRE_PLANES = (("i32", 4), ("ids", 2), ("bits", 0))
# Which uint16 of an int32 holds its low half on this host.
_LOW_HALF = 0 if sys.byteorder == "little" else 1
_NO_COLUMNS = np.zeros(0, np.intp)


def _wire_regions(shape: tuple) -> tuple:
    """The byte spans ``(lo, hi)`` of the int32, the uint16 and the bit
    region of a wire row of this shape, and the row's width (a multiple
    of 4). Host and device slice a row by these and nothing else."""
    spans, at = [], 0
    for (k, _scattered), (_name, size) in zip(shape, _WIRE_PLANES):
        hi = at + (k * size if size else (k + 7) // 8)
        spans.append((at, hi))
        at = hi
    return (*spans, (at + 3) // 4 * 4)


class _WireForm:
    """One structure of a columnar launch: which columns of each plane
    ride in the wire buffer. ``shape`` is its static identity, one
    ``(columns shipped, scattered)`` pair per plane of _WIRE_PLANES — 0
    columns for an elided plane, ``scattered`` False for a plane shipped
    whole — and fixes the wire row's width and the index vectors' shapes,
    so it keys the compiled program. ``take`` gathers every shipped
    byte straight out of the encoder's wide row (composed once, here: a
    launch pays ONE gather for all three planes, and every numpy call it
    saves is a hand-off of the GIL it saves — since PR 37 the native
    encode call makes that gather itself, told ``gather``,
    whenever this is the settled form and the batch met no cold string;
    ``wire`` below is then the reference it is tested against, and the
    path of every other launch); ``cols`` are the positions
    the columns scatter to on the device, placed there once
    (``resident``, filled at the first launch) and not shipped with
    every batch."""

    __slots__ = ("shape", "take", "cols", "regions", "resident")

    def __init__(
        self, layout: "_WireLayout", cols: Mapping[str, np.ndarray | None]
    ) -> None:
        shape = []
        first: dict[str, np.ndarray] = {}
        self.cols: dict[str, np.ndarray] = {}
        for name, _size in _WIRE_PLANES:
            source = layout.source.get(name)
            if source is None or name not in cols:
                shape.append((0, False))
                first[name] = _NO_COLUMNS
                continue
            picked = cols[name]
            if picked is None:
                first[name] = source
            else:
                first[name] = source[picked]
                self.cols[name] = picked.astype(np.int32)
            shape.append((int(first[name].size), picked is not None))
        self.shape = tuple(shape)
        self.regions = _wire_regions(self.shape)
        # the wide row's byte of every byte of the int32 region and of
        # the uint16 region (an id ships its low half; the high one is
        # zero while the vocabulary fits), then of every shipped lane
        self.take = np.concatenate([
            (first["i32"][:, None] + np.arange(4)).ravel(),
            (first["ids"][:, None] + np.arange(2) + 2 * _LOW_HALF).ravel(),
            first["bits"],
        ])
        self.resident: Any = None

    @property
    def width(self) -> int:
        return self.regions[-1]

    @property
    def gather(self) -> tuple[np.ndarray, int, int]:
        """``wire`` as plain values, for the native writer
        (ops/fastenc.py encode_batch): the gather vector, how many of its
        leading bytes are copied (the rest pack a bit each), the wire
        row's width."""
        return self.take, self.regions[2][0], self.width

    def wire(self, buf: np.ndarray) -> np.ndarray:
        """The wire buffer of one wide packed batch: one C-contiguous
        ``uint8[batch, width]`` array."""
        _r32, _r16, (lo8, hi8), width = self.regions
        picked = np.take(buf, self.take, axis=1)
        wire = np.zeros((buf.shape[0], width), np.uint8)
        wire[:, :lo8] = picked[:, :lo8]
        if hi8 > lo8:
            # packbits reads any non-zero byte as a set bit
            wire[:, lo8:hi8] = np.packbits(
                picked[:, lo8:], axis=1, bitorder="little"
            )
        return wire


class _WireLayout:
    """Where each wire plane's columns sit in one schema's wide packed
    row, for one id width (static): the int32 tail plane and the uint16
    id plane (narrow only) read the row's 32-bit region, the bool lanes
    its byte region. Holds the two forms no traffic has to teach: the
    all-elided one and the DENSE one (every plane whole)."""

    __slots__ = (
        "width", "total8", "span32", "col32", "source", "elided", "dense",
    )

    def __init__(
        self, layout: Any, split: tuple[list[int], list[int]] | None
    ) -> None:
        self.width = layout.width
        self.total8 = layout.total8
        self.span32 = slice(
            layout.off32_bytes, layout.off32_bytes + 4 * layout.total32
        )
        if split is None:  # full-width ids: every 32-bit column is int32
            col32 = {"i32": np.arange(layout.total32)}
        else:
            id_cols, other_cols = split
            col32 = {
                "i32": np.asarray(other_cols, np.intp),
                "ids": np.asarray(id_cols, np.intp),
            }
        # plane → the 32-bit region's column of each of its columns
        self.col32 = {k: v for k, v in col32.items() if v.size}
        # plane → the wide row's (first) byte of each of its columns
        self.source: dict[str, np.ndarray] = {
            name: layout.off32_bytes + 4 * cols
            for name, cols in self.col32.items()
        }
        self.source["bits"] = np.arange(layout.total8)
        self.elided = _WireForm(self, {})
        self.dense = _WireForm(self, dict.fromkeys(self.source))


class _PlaneColumns:
    """The columns one schema's columnar batches ship, per wire plane: the
    union of every column seen non-zero so far, padded by the one
    selection rule (_select_delta_cols). The union only grows, so a
    traffic mix settles on one structure after its first few batches, and
    a settled structure compiles nothing — a selection made per batch
    would trace a new XLA program for every new combination of live
    columns, inside the dispatch watchdog. Shipping a superset of a
    batch's live columns is exact: the extra columns carry zeros onto
    zeros."""

    __slots__ = (
        "layout", "seen", "unseen", "cols", "form", "version", "scheduled",
    )

    def __init__(self, layout: _WireLayout) -> None:
        self.layout = layout
        self.seen = {
            name: np.zeros(source.size, np.bool_)
            for name, source in layout.source.items()
        }
        # the bytes of the wide row no batch has had non-zero yet, 0xFF
        # each, read as uint32 like the row itself (_live_words): a
        # batch with no bit in them (the settled case) grows nothing
        self.unseen = np.full(layout.width // 4, 0xFFFFFFFF, np.uint32)
        # plane → shipped column vector, or None for the whole plane; a
        # plane absent here has shipped nothing yet and stays elided
        self.cols: dict[str, np.ndarray | None] = {}
        self.form = layout.elided
        self.version = 0
        # newest version handed to the off-path compiler
        self.scheduled = 0

    def admit(self, words: np.ndarray, select: Callable) -> None:
        """Fold one batch's live words (_live_words) into the union."""
        layout = self.layout
        live = words.view(np.uint8) != 0
        self.unseen.view(np.uint8)[live] = 0
        live32 = live[layout.span32].reshape(-1, 4).any(axis=1)
        version = self.version
        for name, seen in self.seen.items():
            mask = (
                live[: layout.total8] if name == "bits"
                else live32[layout.col32[name]]
            )
            if not (mask & ~seen).any():
                continue
            seen |= mask
            self.cols[name] = select(np.flatnonzero(seen), seen.size)
            self.version += 1
        if self.version != version:
            self.form = _WireForm(layout, self.cols)


def _live_words(buf: np.ndarray) -> np.ndarray:
    """The OR of a wide packed batch's rows, four bytes to a word (a wide
    row is a multiple of 4 wide): one pass over the batch, and what is
    left to look at afterwards is small enough that numpy keeps the
    GIL."""
    return np.bitwise_or.reduce(buf.view(np.uint32), axis=0)


class _NativeWire:
    """What a chunk's encode call wrote of its launch's host half
    (ops/fastenc.py encode_batch, csrc/fastenc.cpp write_wire): the
    liveness words and the wire buffer, in ``form``, the form its schema
    had settled on when the encode began. The launch ships the buffer
    when that is still the form it decides on (_plane_dispatch) and
    builds its own otherwise. A compacted chunk (_launch_chunk) takes its
    shipped rows out of both (``compact``: 52 bytes a row where a wide
    copy is a thousand) and keeps their positions for a launch that has
    to fall back (``wide``)."""

    __slots__ = ("form", "live", "wire", "ship_pos")

    def __init__(
        self, form: _WireForm, live: np.ndarray, wire: np.ndarray
    ) -> None:
        self.form = form
        self.live = live
        self.wire = wire
        self.ship_pos: np.ndarray | None = None

    def compact(self, wide: np.ndarray, pos: np.ndarray, batch: int) -> None:
        self.wire, self.live = fastenc.take_rows(self.wire, wide, pos, batch)
        self.ship_pos = pos

    def wide(self, buf: np.ndarray) -> np.ndarray:
        """The wide rows to ship, for a launch that builds its own wire."""
        if self.ship_pos is None:
            return buf
        return _compacted(buf, self.ship_pos, self.wire.shape[0])


def _compacted(rows: np.ndarray, pos: np.ndarray, bucket: int) -> np.ndarray:
    """``rows[pos]`` at the head of a zeroed bucket."""
    out = np.zeros((bucket, rows.shape[1]), rows.dtype)
    out[: pos.size] = rows[pos]
    return out


def pre_eval_hooks_of(target: "BoundPolicy | BoundGroup") -> list:
    """Hooks of a bound policy/group (shared by EvaluationEnvironment and
    PolicyShardedEvaluator — depends only on the target)."""
    targets = (
        list(target.members.values())
        if isinstance(target, BoundGroup)
        else [target]
    )
    return [
        bp.precompiled.program.pre_eval_hook
        for bp in targets
        if bp.precompiled.program.pre_eval_hook is not None
    ]


def bucket_size(n: int) -> int:
    """Round a batch length up to the next power of two — bounds the set of
    shapes the fused program compiles for (SURVEY.md §7.4 hard-part #1:
    bucketed shapes bound recompilation)."""
    b = 1
    while b < n:
        b <<= 1
    return b


@dataclass
class BoundPolicy:
    """A module bound to settings under a policy id ('name' or
    'group/member')."""

    policy_id: str
    module_url: str
    precompiled: PrecompiledPolicy
    eval_settings: PolicyEvaluationSettings
    # per-policy cluster-state capability allowlist (reference
    # EvaluationContext.ctx_aware_resources_allow_list,
    # evaluation_environment.rs:243-247)
    ctx_allowlist: frozenset = frozenset()


@dataclass
class BoundGroup:
    name: str
    expression: str
    ast: Any
    message: str
    policy_mode: PolicyMode
    members: dict[str, BoundPolicy] = field(default_factory=dict)


def default_module_resolver(url: str) -> PolicyModule:
    builtin = resolve_builtin(url)
    if builtin is None:
        raise BootstrapFailure(
            f"module {url!r} is not a builtin and no fetcher was configured "
            "(use PolicyServer bootstrap, or builtin:// modules)"
        )
    return builtin


class EvaluationEnvironmentBuilder:
    """Boot-time assembly (reference EvaluationEnvironmentBuilder,
    evaluation_environment.rs:139-194 + build at 198-332)."""

    def __init__(
        self,
        backend: str = "jax",
        continue_on_errors: bool = False,
        module_resolver: Callable[[str], PolicyModule] | None = None,
        axis_cap: int = DEFAULT_AXIS_CAP,
        nested_axis_cap: int = DEFAULT_NESTED_AXIS_CAP,
        small_axis_cap: int = 8,
        small_nested_axis_cap: int = 4,
        always_accept_admission_reviews_on_namespace: str | None = None,
        context_service: Any = None,
        wasm_wall_clock_budget: float | None | object = _BUDGET_UNSET,
        wasm_trust_root: Any = None,
        wasm_oci_digest_source: Callable[[str], str] | None = None,
        verdict_cache_size: int = DEFAULT_VERDICT_CACHE_SIZE,
        breaker_config: Mapping[str, Any] | None = None,
        columnar: bool = True,
        predicate_opt: bool = True,
    ) -> None:
        self.backend = backend
        self.continue_on_errors = continue_on_errors
        self.module_resolver = module_resolver or default_module_resolver
        self.axis_cap = axis_cap
        self.nested_axis_cap = nested_axis_cap
        self.small_axis_cap = small_axis_cap
        self.small_nested_axis_cap = small_nested_axis_cap
        self.always_accept_namespace = always_accept_admission_reviews_on_namespace
        self.context_service = context_service
        # unset = leave each module's own default; a float syncs wasm
        # modules to the server's --policy-timeout (wall-clock epoch
        # analog); None disables (--disable-timeout-protection)
        self.wasm_wall_clock_budget = wasm_wall_clock_budget
        # offline sigstore trust root handed to wasm modules for the
        # keyless v2/verify host capability
        self.wasm_trust_root = wasm_trust_root
        # registry client (image ref → manifest digest) handed to wasm
        # modules for the oci/v1/manifest_digest host capability
        self.wasm_oci_digest_source = wasm_oci_digest_source
        # bit-exact row dedup / verdict caching (verdict_cache.py); 0 = off
        self.verdict_cache_size = verdict_cache_size
        # per-environment device circuit breaker thresholds
        # (resilience.CircuitBreaker kwargs); None = defaults
        self.breaker_config = breaker_config
        # columnar device transport (round 12): ship bit-packed /
        # narrowed PLANES with all-zero columns elided instead of one
        # row-packed buffer; False restores the packed transport
        self.columnar = columnar
        # predicate-program optimizer (round 15, ops/optimizer.py):
        # cross-policy CSE + constant folding + dead-field/mask pruning
        # before lowering; False restores the naive per-policy lowering
        self.predicate_opt = predicate_opt

    def build(self, policies: Mapping[str, PolicyOrPolicyGroup]) -> "EvaluationEnvironment":
        cache = ProgramCache()
        bound: dict[str, BoundPolicy] = {}
        groups: dict[str, BoundGroup] = {}
        init_errors: dict[str, str] = {}

        def bootstrap_policy(
            pid: str,
            module_url: str,
            settings: Mapping[str, Any] | None,
            policy_mode: PolicyMode,
            allowed_to_mutate: bool,
            ctx_allowlist: frozenset = frozenset(),
        ) -> BoundPolicy:
            module = self.module_resolver(module_url)
            if self.wasm_wall_clock_budget is not _BUDGET_UNSET and hasattr(
                module, "wall_clock_budget"
            ):
                module.wall_clock_budget = self.wasm_wall_clock_budget
            if self.wasm_trust_root is not None and hasattr(
                module, "trust_root"
            ):
                module.trust_root = self.wasm_trust_root
            if self.wasm_oci_digest_source is not None and hasattr(
                module, "oci_digest_source"
            ):
                module.oci_digest_source = self.wasm_oci_digest_source
            validation = module.validate_settings(dict(settings or {}))
            if not validation.valid:
                # reference: "Policy settings are invalid" (rs:472-510)
                raise PolicyInitializationError(
                    pid, f"Policy settings are invalid: {validation.message or ''}"
                )
            pre = cache.get_or_build(module, settings or {})
            return BoundPolicy(
                policy_id=pid,
                module_url=module_url,
                precompiled=pre,
                eval_settings=PolicyEvaluationSettings(
                    policy_mode=policy_mode,
                    allowed_to_mutate=allowed_to_mutate,
                    settings=dict(settings or {}),
                ),
                ctx_allowlist=ctx_allowlist,
            )

        for name, entry in policies.items():
            try:
                if isinstance(entry, Policy):
                    bound[name] = bootstrap_policy(
                        name,
                        entry.module,
                        entry.settings,
                        entry.policy_mode,
                        bool(entry.allowed_to_mutate),
                        entry.context_aware_resources,
                    )
                elif isinstance(entry, PolicyGroup):
                    ast = groups_mod.validate_expression(
                        entry.expression, set(entry.policies)
                    )
                    group = BoundGroup(
                        name=name,
                        expression=entry.expression,
                        ast=ast,
                        message=entry.message,
                        policy_mode=entry.policy_mode,
                    )
                    for member_name, member in entry.policies.items():
                        member_pid = f"{name}/{member_name}"
                        member_bp = bootstrap_policy(
                            member_pid,
                            member.module,
                            member.settings,
                            entry.policy_mode,
                            False,  # group members never mutate (rs group ban)
                            member.context_aware_resources,
                        )
                        # wasm-executed members are supported: their
                        # verdicts are computed host-side at encode time
                        # and fed into the fused group reduction as device
                        # input bits (WASM_BITS_KEY), matching the
                        # reference's free composition of any loaded
                        # policy into groups
                        # (evaluation_environment.rs:596-651)
                        group.members[member_name] = member_bp
                    groups[name] = group
                    for member_name, bp in group.members.items():
                        bound[bp.policy_id] = bp
                else:  # pragma: no cover
                    raise BootstrapFailure(f"unknown policy entry type for {name!r}")
            except (
                PolicyInitializationError,
                groups_mod.ExpressionError,
                BootstrapFailure,
                KeyError,
                ValueError,
            ) as e:
                if not self.continue_on_errors:
                    raise BootstrapFailure(
                        f"failed to bootstrap policy {name!r}: {e}"
                    ) from e
                init_errors[name] = str(e)

        env = EvaluationEnvironment(
            backend=self.backend,
            bound=bound,
            groups=groups,
            init_errors=init_errors,
            axis_cap=self.axis_cap,
            nested_axis_cap=self.nested_axis_cap,
            small_axis_cap=self.small_axis_cap,
            small_nested_axis_cap=self.small_nested_axis_cap,
            always_accept_namespace=self.always_accept_namespace,
            context_service=self.context_service,
            verdict_cache_size=self.verdict_cache_size,
            breaker_config=self.breaker_config,
            columnar=self.columnar,
            predicate_opt=self.predicate_opt,
        )
        # the source policy mapping the environment was built from: the
        # shard router (runtime/shards.py) rebuilds sibling environments
        # from it, so every build path (boot, reload, rollback) carries
        # it uniformly. Not read by the serving path.
        env.source_policies = dict(policies)
        return env


# Stats-dict key schema of the round-15 optimizer surface. graftcheck's
# OB07 cross-checks each key against a metrics.py constant
# (policy_server_predicate_<key>) exported through runtime_stats with a
# dashboard panel — the stats dict cannot grow a key the observability
# funnel does not carry.
OPTIMIZER_STAT_KEYS = (
    "subtrees_shared",
    "policies_folded",
    "rules_folded",
    "fields_pruned",
    "row_bytes_saved",
)


class EvaluationEnvironment:
    """Immutable post-boot registry + the fused batched evaluator.

    Thread-safe by construction: all state is read-only after __init__
    (reference relies on Arc for the same guarantee, lib.rs:194-197).
    """

    def __init__(
        self,
        backend: str,
        bound: dict[str, BoundPolicy],
        groups: dict[str, BoundGroup],
        init_errors: dict[str, str],
        axis_cap: int = DEFAULT_AXIS_CAP,
        nested_axis_cap: int = DEFAULT_NESTED_AXIS_CAP,
        small_axis_cap: int = 8,
        small_nested_axis_cap: int = 4,
        always_accept_namespace: str | None = None,
        context_service: Any = None,
        verdict_cache_size: int = DEFAULT_VERDICT_CACHE_SIZE,
        breaker_config: Mapping[str, Any] | None = None,
        columnar: bool = True,
        predicate_opt: bool = True,
    ) -> None:
        self.backend = backend
        self.always_accept_namespace = always_accept_namespace
        self.context_service = context_service
        self._bound = bound
        self._groups = groups
        self._init_errors = init_errors
        self.table = InternTable()
        exprs = [
            rule.condition
            for bp in bound.values()
            for rule in bp.precompiled.program.rules
        ]
        # Element-axis shape buckets (SURVEY.md §7.4 hard-part #1: bucketed
        # shapes bound recompilation AND host→device bytes — the serving
        # bottleneck is transfer, not FLOPs). Requests encode into the
        # smallest schema whose caps fit; the final schema's caps are the
        # oracle-fallback boundary.
        cap_buckets: list[tuple[int, int]] = []
        if small_axis_cap and small_axis_cap < axis_cap:
            cap_buckets.append((small_axis_cap, small_nested_axis_cap))
        cap_buckets.append((axis_cap, nested_axis_cap))
        # Predicate-program optimizer (round 15, ops/optimizer.py):
        # cross-policy CSE + constant folding + dead-field pruning run
        # BEFORE schema build and lowering, so pruned fields never get
        # feature columns and elided validity masks never get ':m:'
        # lanes. jax backend only — the oracle backend interprets the
        # ORIGINAL IR over raw JSON and stays the independent
        # differential reference.
        self.predicate_opt = bool(predicate_opt) and backend == "jax"
        self.optimization = None
        schema_exprs = exprs
        unmasked: frozenset = frozenset()
        if self.predicate_opt:
            from policy_server_tpu.ops.optimizer import optimize_policy_set

            self.optimization = optimize_policy_set(
                {
                    pid: bp.precompiled.program
                    for pid, bp in bound.items()
                }
            )
            schema_exprs = self.optimization.surviving_exprs
            unmasked = self.optimization.unmasked_value_keys
        self.schemas = [
            FeatureSchema.build(
                schema_exprs, axis_cap=a, nested_axis_cap=n,
                unmasked=unmasked,
            )
            for a, n in cap_buckets
        ]
        self.schema = self.schemas[-1]  # the widest (legacy name)
        # pruning accounting vs the unoptimized schema (optimizer_stats):
        # LAZY — rebuilding the naive schema per cap bucket is pure
        # gauge math, and the reload path (one candidate build per
        # policy-churn rewrite, plus the canary) must not pay it; the
        # first stats read (metrics scrape, bench line) computes once
        self._opt_accounting: "tuple[int, int, list[dict]] | None" = None
        self._opt_base_exprs = exprs if self.optimization is not None else None
        self._opt_cap_buckets = list(cap_buckets)
        for schema in self.schemas:
            schema.register_preds(self.table)
        # The packed device unpack selects its layout by row width
        # (_unpack_features); widths must be unique so the selection is
        # total — must happen BEFORE attach_native captures row_stride.
        ensure_unique_packed_widths(self.schemas)
        # Native (C++) encoder: JSON bytes → batch arrays in one call per
        # dispatch (csrc/fastenc.cpp). The jax backend asks for it: a
        # failed build or load raises here, it does not degrade to a
        # slower server that still answers 200.
        self.native_encoding = backend == "jax"
        if self.native_encoding:
            for schema in self.schemas:
                fastenc.attach_native(schema, self.table)
        if self.optimization is not None:
            from policy_server_tpu.ops.compiler import compile_constant

            self._compiled = {}
            for pid, bp in bound.items():
                po = self.optimization.policies[pid]
                if po.constant is not None:
                    # whole-policy constant verdict: drops out of the
                    # device program (two broadcasts XLA const-folds);
                    # output columns — and therefore responses, metrics,
                    # and audit report rows — are unchanged
                    self._compiled[pid] = compile_constant(*po.constant)
                else:
                    self._compiled[pid] = compile_program(
                        bp.precompiled.program, self.schema, self.table,
                        conditions=po.conditions,
                    )
        else:
            self._compiled = {
                pid: compile_program(
                    bp.precompiled.program, self.schema, self.table
                )
                for pid, bp in bound.items()
            }
        # Stable orders for the packed device outputs (host↔device traffic
        # must be O(1) transfers per batch, not O(#policies): each
        # transfer is a full roundtrip).
        self._policy_order = list(bound)
        # compact (uint8) device outputs when every rule index fits a
        # byte — 4x less fetch traffic;
        # a >255-rule policy (none in practice) falls back to int32
        self._compact_outputs = all(
            len(bp.precompiled.program.rules) < 255 for bp in bound.values()
        )
        self._group_order = list(groups)
        self._max_group_members = max(
            (len(g.members) for g in groups.values()), default=0
        )
        # where each output key sits in a row of the one output array the
        # program returns (_combine_outputs concatenates in this order):
        # policies' allowed, policies' rule, groups' allowed, then each
        # group's members' evaluated flags, padded to the widest group
        n_p, n_g = len(self._policy_order), len(self._group_order)
        out_index: dict[str, tuple[int, bool]] = {}
        for j, pid in enumerate(self._policy_order):
            out_index[f"p:{pid}:allowed"] = (j, False)
            out_index[f"p:{pid}:rule"] = (n_p + j, True)
        for gi, name in enumerate(self._group_order):
            out_index[f"g:{name}:allowed"] = (2 * n_p + gi, False)
            at = 2 * n_p + n_g + gi * self._max_group_members
            for mi, mname in enumerate(groups[name].members):
                out_index[f"g:{name}:eval:{mname}"] = (at + mi, False)
        self._out_layout = OutputLayout(out_index, self._compact_outputs)
        # Host-executed (wasm) group members: their verdict bits enter the
        # fused program as the WASM_BITS_KEY input, one column per member
        # in this order. Standalone wasm policies are not listed — they
        # bypass the device entirely (_host_executed).
        self._wasm_member_order = [
            bp.policy_id
            for g in groups.values()
            for bp in g.members.values()
            if bp.precompiled.program.host_evaluator is not None
        ]
        self._wasm_member_col = {
            pid: j for j, pid in enumerate(self._wasm_member_order)
        }
        self._groups_with_wasm = {
            g.name
            for g in groups.values()
            if any(
                bp.precompiled.program.host_evaluator is not None
                for bp in g.members.values()
            )
        }
        self._fused = jax.jit(
            _named_program(self._forward, FUSED_PROGRAM_NAME)
        )
        # Columnar serving transport (round 12, ROADMAP item 3): the wide
        # packed batch splits into bit-packed / uint16 / int32 PLANES and
        # only all-nonzero ("delta") columns ship, in ONE wire buffer a
        # launch (_WireForm) — all-zero planes and columns are
        # reconstructed on device from resident zero constants, the
        # column indices stay on the device.
        # ``spec`` (static arg 0) carries (schema index, batch, narrow,
        # the form's shape) and keys the jit cache per plane subset. The
        # root itself is branch-free (TP02); structure branching lives in
        # the _features_from_planes helper.
        self.columnar = bool(columnar) and backend == "jax"
        self._fused_planes = self._jit_planes()
        # specs whose program is compiled — the serving path dispatches
        # only these (see _plane_dispatch); also sizes the resident
        # zero-constant accounting (the first run of a spec materializes
        # its skipped planes as device constants)
        self._plane_combos: set = set()  # guarded-by: _profile_lock
        # monotonic count of plane-structure combos traced so far, each
        # one XLA compile: at warm-up, off the serving path
        # (_compile_columns), or — for a batch size warm-up never saw —
        # inside a dispatch, whose RTT sample the batcher then discards
        # (a compile-inclusive reading would misroute traffic host-side)
        self._plane_compiles = 0  # guarded-by: _profile_lock
        # Per (schema, narrow): the columns its batches ship, a union
        # that only grows, so a traffic mix settles on ONE structure per
        # batch bucket instead of one per batch content
        self._plane_columns: dict[tuple, _PlaneColumns] = {}  # guarded-by: _profile_lock
        # per (schema, narrow): where the wire planes sit in a wide row
        self._wire_layouts: dict[tuple, _WireLayout] = {}
        # batch buckets warm-up compiled: the sizes a settled structure
        # is compiled for, off the serving path
        self._warm_batches: set[int] = set()  # guarded-by: _profile_lock
        # column-structure compile jobs queued or running
        self._plane_jobs_pending = 0  # guarded-by: _profile_lock
        self._plane_compiler = None  # DaemonExecutor, built on first use
        # devices a warm-up output's sharding spans (0 before warm-up):
        # the boot report's proof that the program is on every chip
        self.warmup_output_devices = 0
        self._oracle_fallbacks = 0  # guarded-by: _fallback_lock
        # Device circuit breaker (resilience.py): repeated dispatch faults
        # or watchdog trips (reported by the batcher via
        # record_dispatch_failure) trip THIS environment — one breaker per
        # shard on a policy-sharded mesh, so a hung shard degrades alone —
        # and tripped batches short-circuit to the bit-exact host oracle
        # until a half-open probe succeeds. Oracle backend: no device, no
        # breaker.
        self.breaker = (
            CircuitBreaker(**dict(breaker_config or {}))
            if backend == "jax"
            else None
        )
        # requests answered host-side because the breaker was open
        self._breaker_short_circuited = 0  # guarded-by: _fallback_lock
        # Serving-layer host fast-path counter (validate_batch(prefer_host=
        # True) rows answered by the targeted host oracle; metrics surface)
        self._host_fastpath_requests = 0  # guarded-by: _fallback_lock
        # The dedup tiers in front of the device (verdict_cache.py owns
        # them and every rule about them); ``verdict_cache_size`` is their
        # BYTE budget. jax-backend only: the oracle backend exists to be
        # the independent differential reference, so it always recomputes.
        self._tiers = (
            DedupTiers(verdict_cache_size)
            if verdict_cache_size > 0 and backend == "jax"
            else None
        )
        # Host-pipeline decomposition counters (round 6): where
        # the per-row host time goes on the native dispatch path. All
        # nanosecond totals + row counts; bench/metrics divide.
        self._profile_lock = threading.Lock()
        self._host_profile: dict[str, int] = {  # guarded-by: _profile_lock
            "encode_ns": 0,          # native encode_batch (_encode_chunk)
            "encode_cpu_ns": 0,      # the encoding thread's CPU time of it
            "encode_rows": 0,        # rows that went through the encoder
            # string leaves of those rows that the encoder's mirror of the
            # intern table had not seen, resolved in Python (fastenc.py)
            "encode_python_strings": 0,
            "bookkeeping_ns": 0,     # dedup tiers + slot/LRU bookkeeping
            "bookkeeping_rows": 0,
            "dispatch_wait_ns": 0,   # blocked in device_get at materialize
            "dispatched_rows": 0,    # unique rows actually shipped
            # requests a dispatched slot answered as a fragment
            "device_fragment_rows": 0,
            "audit_rows": 0,         # ... by the audit lane, for nobody
            "dispatched_chunks": 0,
            # -- columnar transport (round 12) ----------------------------
            "wire_bytes_shipped": 0,     # bytes actually transferred
            "launch_h2d_arrays": 0,      # host arrays launches handed over
            # launches whose wire buffer the encode call had written
            "launch_native_wire": 0,
            "wire_bytes_packed_equiv": 0,  # what the packed transport
            "wire_rows": 0,                # form would have shipped
            "delta_cols_shipped": 0,   # 32-bit columns shipped (delta)
            "delta_cols_total": 0,     # 32-bit columns in the schema
            "resident_const_bytes": 0,  # device-resident zero-plane bytes
        }
        # memoized service-layer lookups (immutable registry; unknown ids
        # still raise through the uncached path)
        self._mode_cache: dict[str, PolicyMode] = {}
        self._mutate_cache: dict[str, bool] = {}
        # Hot-loop memos (round 6, reference hot-path discipline of
        # src/api/handlers.rs:256-286): the registry is immutable after
        # boot, so per-request target resolution, hook lists, and
        # blob-plainness are all cacheable. Dict get/set is atomic under
        # the GIL; racing builders produce identical values.
        self._target_memo: dict[str, Any] = {}
        self._hooks_memo: dict[int, list] = {}
        self._blob_plain_memo: dict[int, bool] = {}
        self._ckey_memo: dict[int, tuple[str, str]] = {}
        # fragment eligibility per target (round 19): whether a cached
        # row's response is a pure function of (target, output row) + uid
        # with identity constraints — see _frag_eligible
        self._frag_eligible_memo: dict[int, bool] = {}  # graftcheck: lockfree — GIL-atomic dict ops; racing builders store identical values
        # per eligible target, the templates of the verdicts it has
        # answered hits with (_frag_of); False for an ineligible target
        self._frag_lanes: dict[int, Any] = {}  # graftcheck: lockfree — GIL-atomic dict ops; racing builders store identical values
        # Pre-built output-key strings per policy/group: the per-row
        # f-string construction in the materializers showed up in the
        # round-6 profile at ~7 µs/row on group targets.
        self._single_mat: dict[str, tuple[str, str]] = {
            pid: (f"p:{pid}:allowed", f"p:{pid}:rule") for pid in bound
        }
        self._group_mat: dict[str, tuple] = {}
        for name, group in groups.items():
            members = []
            for m, bp in group.members.items():
                members.append(
                    (
                        m,
                        bp,
                        f"g:{name}:eval:{m}",
                        f"p:{bp.policy_id}:allowed",
                        f"p:{bp.policy_id}:rule",
                        f"wm:{bp.policy_id}:mutated",
                        f"wm:{bp.policy_id}:msg",
                        bp.precompiled.program.host_evaluator is not None,
                        bp.precompiled.program.mutator,
                    )
                )
            # members that could possibly trip the group-mutation ban —
            # for the (typical) all-static group the allowed fast path
            # skips the member scan entirely
            risky = [e for e in members if e[7] or e[8] is not None]
            self._group_mat[name] = (f"g:{name}:allowed", members, risky)
        self._fallback_lock = threading.Lock()
        self._mesh = None  # set by attach_mesh
        # fused-SPMD policy sharding (round 14, attach_mesh with a >1
        # policy axis): the shard_map'd per-policy block, its lax.switch
        # branch closures, and the policy → gathered-column map. None on
        # single-device / pure data-parallel programs.
        self._mesh_block = None
        self._mesh_branches: list = []
        self._mesh_buckets: list = []
        self._mesh_block_width = 0
        self._mesh_policy_col: dict[str, int] = {}
        self._min_bucket = 1
        self._closed = False
        # Drain pool: fetching results pays the device's full sync
        # latency; overlapping many in-flight device_gets on threads
        # hides it — the dispatch thread never blocks on a fetch.
        self._drain_pool = (
            ThreadPoolExecutor(max_workers=16, thread_name_prefix="drain")
            if backend == "jax"
            else None
        )
        # Encode pool: the native encode is a GIL-free C call, so chunks
        # encode in true parallel and overlap device transfers/compute.
        self._encode_pool = (
            ThreadPoolExecutor(max_workers=4, thread_name_prefix="encode")
            if backend == "jax"
            else None
        )

    def close(self) -> None:
        """Release the drain/encode thread pools (idempotent). Called by
        whoever BUILT the environment (the server at teardown, a test
        fixture at scope exit) — never by a MicroBatcher, which borrows the
        environment it dispatches into. After close() every dispatch raises
        RuntimeError("environment closed") rather than failing deep inside
        the batch path."""
        self._closed = True
        for pool in (
            self._drain_pool, self._encode_pool, self._plane_compiler
        ):
            if pool is not None:
                pool.shutdown(wait=False)
        self._drain_pool = self._encode_pool = self._plane_compiler = None

    # -- mesh attachment (parallel/mesh.py) --------------------------------

    def attach_mesh(self, mesh: Any) -> None:
        """Switch the fused program to SPMD dispatch over a device mesh:
        batch-sharded inputs/outputs, XLA-partitioned predicate program
        (SURVEY.md §2.3 last row). Batch buckets are forced to multiples
        of the data-axis size.

        A mesh with a ``policy`` axis > 1 additionally shards the POLICY
        dimension inside the same single program (round 14): policies
        bucket round-robin into per-shard ``lax.switch`` branches selected
        by ``lax.axis_index("policy")`` under a ``shard_map``, and the
        per-shard verdict blocks meet in an ``all_gather`` collective
        before the group/expression combine — one device program per
        batch where the threaded MPMD dispatcher paid one per policy
        shard plus N host-side thread joins."""
        import functools

        from policy_server_tpu.parallel import mesh as mesh_mod
        from jax.sharding import PartitionSpec

        self._mesh = mesh
        self._min_bucket = mesh.shape[mesh_mod.DATA_AXIS]
        n_policy = mesh.shape.get(mesh_mod.POLICY_AXIS, 1)
        self._mesh_block = None
        if n_policy > 1 and self._compiled:
            buckets, width, column_of = mesh_mod.plan_policy_buckets(
                list(self._compiled), n_policy
            )
            self._mesh_buckets = buckets
            self._mesh_block_width = width
            self._mesh_policy_col = column_of
            self._mesh_branches = [
                functools.partial(self._mesh_bucket_block, bucket=b)
                for b in buckets
            ]
            data_spec = PartitionSpec(mesh_mod.DATA_AXIS)
            # check_vma off: the all-gather makes the outputs replicated
            # over the policy axis, but shard_map cannot infer that
            # through lax.switch
            self._mesh_block = mesh_mod.shard_map(
                self._mesh_block_local,
                mesh=mesh,
                in_specs=data_spec,
                out_specs=(data_spec, data_spec),
                check_vma=False,
            )
        self._fused = mesh_mod.jit_data_parallel(
            _named_program(self._forward, FUSED_PROGRAM_NAME), mesh
        )
        # rebuild the columnar root: its traces must capture the mesh
        # (plane reconstruction places resident zero constants with the
        # mesh's NamedSharding). Nothing of the old root is compiled in
        # the new one, and the forms' resident index vectors were placed
        # for the old topology: start the column sets over.
        self._fused_planes = self._jit_planes()
        with self._profile_lock:
            self._plane_combos.clear()
            self._plane_columns.clear()
            self._wire_layouts.clear()

    def _jit_planes(self) -> Callable:
        """The columnar jit root (rebuilt when a mesh attaches: its traces
        must capture the mesh)."""
        return jax.jit(
            _named_program(
                self._forward_planes, FUSED_PLANES_PROGRAM_NAME
            ),
            static_argnums=(0,),
        )

    def _columnar_mesh_ok(self) -> bool:
        """Columnar dispatch is safe on this topology: the delta-plane
        STRUCTURE is derived from host-local batch content, so every
        process of a multi-host mesh could trace a different program and
        deadlock the SPMD step — multi-process meshes keep the packed
        transport (structure depends only on schema width there)."""
        if self._mesh is None:
            return True
        return jax.process_count() == 1

    def bucket_for(self, n: int) -> int:
        """Power-of-two bucket, rounded up to a multiple of the mesh data
        axis (batches must divide the axis for P('data') sharding)."""
        b = max(bucket_size(n), self._min_bucket)
        if self._min_bucket > 1 and b % self._min_bucket:
            b = ((b + self._min_bucket - 1) // self._min_bucket) * self._min_bucket
        return b

    # -- registry accessors (reference rs:434-470) ------------------------

    def policy_ids(self) -> list[str]:
        """Top-level addressable ids (singles + groups), like the reference's
        policies.yml keys."""
        singles = [pid for pid in self._bound if "/" not in pid]
        return sorted(singles + list(self._groups))

    def _lookup_top_level(self, pid: PolicyID) -> BoundPolicy | BoundGroup:
        raw = str(pid)
        if raw in self._init_errors:
            raise PolicyInitializationError(raw, self._init_errors[raw])
        if pid.is_group_member:
            bp = self._bound.get(raw)
            if bp is None:
                raise PolicyNotFoundError(raw)
            return bp
        if pid.name in self._groups:
            return self._groups[pid.name]
        bp = self._bound.get(pid.name)
        if bp is None:
            raise PolicyNotFoundError(raw)
        return bp

    def get_policy_mode(self, policy_id: str) -> PolicyMode:
        # memoized: the registry is immutable after boot and the service
        # layer asks per REQUEST (the lookup+parse showed up in the
        # serving profile at batch sizes)
        hit = self._mode_cache.get(policy_id)
        if hit is not None:
            return hit
        target = self._lookup_top_level(PolicyID.parse(policy_id))
        mode = (
            target.policy_mode
            if isinstance(target, BoundGroup)
            else target.eval_settings.policy_mode
        )
        self._mode_cache[policy_id] = mode
        return mode

    def get_policy_allowed_to_mutate(self, policy_id: str) -> bool:
        hit = self._mutate_cache.get(policy_id)
        if hit is not None:
            return hit
        target = self._lookup_top_level(PolicyID.parse(policy_id))
        allowed = (
            False
            if isinstance(target, BoundGroup)
            else target.eval_settings.allowed_to_mutate
        )
        self._mutate_cache[policy_id] = allowed
        return allowed

    def get_policy_settings(self, policy_id: str) -> PolicyEvaluationSettings:
        target = self._lookup_top_level(PolicyID.parse(policy_id))
        if isinstance(target, BoundGroup):
            return PolicyEvaluationSettings(policy_mode=target.policy_mode)
        return target.eval_settings

    def should_always_accept_requests_made_inside_of_namespace(
        self, namespace: str
    ) -> bool:
        """Reference evaluation_environment.rs namespace shortcut predicate
        (used by src/api/service.rs:40-71)."""
        return (
            self.always_accept_namespace is not None
            and namespace == self.always_accept_namespace
        )

    def _allowlist_of(self, target: "BoundPolicy | BoundGroup") -> frozenset:
        if isinstance(target, BoundGroup):
            out: set = set()
            for bp in target.members.values():
                out |= bp.ctx_allowlist
            return frozenset(out)
        return target.ctx_allowlist

    @staticmethod
    def _host_executed(target: "BoundPolicy | BoundGroup") -> bool:
        """True when the target's verdict comes from host-side wasm
        execution (evaluation/wasm_policy.py), bypassing the device."""
        return (
            not isinstance(target, BoundGroup)
            and target.precompiled.program.host_evaluator is not None
        )

    def _providers_of(self, target: "BoundPolicy | BoundGroup") -> list:
        """Host-side context providers of a target's program(s)
        (PolicyProgram.context_provider — cached host-capability results
        fed to the device at encode time)."""
        bps = (
            list(target.members.values())
            if isinstance(target, BoundGroup)
            else [target]
        )
        return [
            bp.precompiled.program.context_provider
            for bp in bps
            if bp.precompiled.program.context_provider is not None
        ]

    def payload_for(self, target: "BoundPolicy | BoundGroup", request: ValidateRequest) -> Any:
        """The evaluation payload: the request document, plus — under
        ``__context__`` — the capability-filtered cluster snapshot for
        context-aware policies (context/service.py; the reference's
        EvaluationContext allowlist, evaluation_environment.rs:243-247)
        and any program context-provider output (cached host capabilities
        such as image-signature verification)."""
        payload = request.payload()
        if self._target_plain(target):
            return payload
        allowlist = self._allowlist_of(target)
        providers = self._providers_of(target)
        has_snapshot = bool(allowlist) and self.context_service is not None
        if not has_snapshot and not providers:  # pragma: no cover — memo
            return payload
        payload = dict(payload)
        ctx: dict = {}
        if has_snapshot:
            ctx.update(self.context_service.snapshot().view(allowlist))
        for provider in providers:
            ctx.update(provider(payload))
        payload[CONTEXT_KEY] = ctx
        return payload

    def _fast_target(self, policy_id: str) -> "BoundPolicy | BoundGroup":
        """Memoized top-level lookup for the batch hot loops (the parse +
        dict walk showed in the round-6 profile). Failing ids (unknown,
        init-error) raise through the uncached path every time."""
        target = self._target_memo.get(policy_id)
        if target is None:
            target = self._lookup_top_level(PolicyID.parse(policy_id))
            self._target_memo[policy_id] = target
        return target

    def _hooks_of(self, target: "BoundPolicy | BoundGroup") -> list:
        hooks = self._hooks_memo.get(id(target))
        if hooks is None:
            hooks = pre_eval_hooks_of(target)
            self._hooks_memo[id(target)] = hooks
        return hooks

    def _payload_blob(self, target: "BoundPolicy | BoundGroup", request: ValidateRequest) -> bytes:
        if self._target_plain(target):
            return request.payload_json()
        return json.dumps(
            self.payload_for(target, request), separators=(",", ":")
        ).encode()

    def _cache_key_of(self, target: "BoundPolicy | BoundGroup") -> tuple[str, str]:
        """Stable per-environment identity of an evaluation target for the
        verdict cache. Top-level names are unique across policies and
        groups (policies.yml), the prefix keeps the spaces disjoint
        regardless. ONE tuple a target, shared by every cache key that
        names it (a tuple a key would be 56 bytes an entry the byte bound
        does not count)."""
        ckey = self._ckey_memo.get(id(target))
        if ckey is None:
            ckey = self._ckey_memo[id(target)] = (
                ("g", target.name)
                if isinstance(target, BoundGroup)
                else ("p", target.policy_id)
            )
        return ckey

    def _cacheable(self, target: "BoundPolicy | BoundGroup") -> bool:
        """Whether a target's verdict is a pure function of its payload
        blob. Wasm-involving targets are not: a wasm wall-clock deadline
        makes their verdict time-dependent (verdict_cache.py docstring)."""
        if isinstance(target, BoundGroup):
            return target.name not in self._groups_with_wasm
        return target.precompiled.program.host_evaluator is None

    def _blob_of(
        self, target, request: ValidateRequest, payload: Any
    ) -> bytes:
        """Canonical payload blob for ONE request given its already-built
        ``payload``. ``payload`` MUST be the same object the verdict is
        computed from: re-running payload_for here would take a SECOND
        context snapshot, and a context update between the two would
        cache the old verdict under the new-context key (stale-serving
        race)."""
        if self._target_plain(target):
            return request.payload_json()
        return json.dumps(payload, separators=(",", ":")).encode()

    def _target_plain(self, target: "BoundPolicy | BoundGroup") -> bool:
        """Memoized: True when the target's evaluation payload is the raw
        request document — no context snapshot, no providers — so the
        canonical blob is just ``request.payload_json()``. The single
        source of truth for payload_for / _payload_blob / _blob_of
        (desynchronizing them would key the blob cache on different
        bytes than the payload actually evaluated)."""
        plain = self._blob_plain_memo.get(id(target))
        if plain is None:
            plain = not (
                (
                    self._allowlist_of(target)
                    and self.context_service is not None
                )
                or self._providers_of(target)
            )
            self._blob_plain_memo[id(target)] = plain
        return plain

    def _frag_eligible(self, target: "BoundPolicy | BoundGroup") -> bool:
        """True when a cached output row's RESPONSE (not just its verdict
        bits) is a pure function of (target, row) plus the request uid,
        AND the service layer's post_evaluate constraints are provably
        the identity on it — the conditions under which a pre-built
        FragTemplate may answer its rows (tier hits and dispatched
        slots alike) with zero per-row materialization:

        * protect mode (monitor mode logs + rewrites every response);
        * no mutators and no wasm anywhere in the target (patches and
          host verdicts depend on the per-request payload / wall clock);
        * every reachable rule message is a static string (dynamic
          messages are payload functions).

        Memoized per target — the registry is immutable post-boot."""
        hit = self._frag_eligible_memo.get(id(target))
        if hit is not None:
            return hit
        ok = self._cacheable(target)
        if ok:
            if isinstance(target, BoundGroup):
                _ak, members, risky = self._group_mat[target.name]
                ok = (
                    target.policy_mode is PolicyMode.PROTECT
                    and not risky
                    and isinstance(target.message, str)
                    and all(
                        isinstance(r.message, str)
                        for e in members
                        for r in e[1].precompiled.program.rules
                    )
                )
            else:
                prog = target.precompiled.program
                ok = (
                    target.eval_settings.policy_mode is PolicyMode.PROTECT
                    and prog.mutator is None
                    and prog.host_evaluator is None
                    and all(isinstance(r.message, str) for r in prog.rules)
                )
        self._frag_eligible_memo[id(target)] = ok
        return ok

    def _frag_of(
        self, target: "BoundPolicy | BoundGroup", row: "bytes | Mapping[str, Any]"
    ) -> "FragTemplate | None":
        """The FragTemplate of an output row for ``target`` — built on the
        FIRST row of that target with that verdict (one
        materialize-equivalent pass) and kept in the target's memo, keyed
        by the target's OWN slice of the row: for an eligible target the
        response is a pure function of the outputs its materializer reads
        (_frag_eligible), so every later row with the same slice costs
        one lookup, and no row is written to.
        Packed rows (the device's) and dict rows (the host fast path's)
        are keyed apart — their slices are raw bytes and decoded scalars.
        Dict stores are GIL-atomic and racing builders produce identical
        templates. Returns None for ineligible targets (the caller
        materializes normally)."""
        lane = self._frag_lanes.get(id(target))
        if lane is None:
            lane = self._frag_lane_of(target)
        if lane is False:
            return None
        slice_of, memo = lane[type(row) is bytes]
        own = slice_of(row)
        tmpl = memo.get(own)
        if tmpl is None:
            if len(memo) >= _FRAG_MEMO_MAX:
                memo.clear()
            tmpl = memo[own] = self._frag_template(target, row)
        return tmpl or None  # False sentinel → None

    def _frag_lane_of(self, target: "BoundPolicy | BoundGroup"):
        """``(slice of a dict row, memo), (slice of a packed row, memo)``
        over the keys ``target``'s materializer reads, or False for a
        target that is not fragment-eligible. Memoized per target — the
        registry is immutable post-boot."""
        lane: Any = False
        if self._frag_eligible(target):
            if isinstance(target, BoundGroup):
                allowed_key, members, _risky = self._group_mat[target.name]
                keys = [allowed_key]
                for e in members:
                    keys.extend(e[2:5])  # eval, allowed, rule
            else:
                keys = list(self._single_mat[target.policy_id])
            lane = (
                (operator.itemgetter(*keys), {}),
                (self._out_layout.slice_of(keys), {}),
            )
        self._frag_lanes[id(target)] = lane
        return lane

    def _frag_template(
        self, target: "BoundPolicy | BoundGroup", row: "bytes | Mapping[str, Any]"
    ) -> "FragTemplate | bool":
        """The uid-independent response of an eligible ``target`` to
        ``row``, or False where it cannot be pre-serialized."""
        # eligibility guarantees the payload is never touched and the uid
        # is spliced per row, so materialize once with inert stand-ins
        # and capture the template
        resp = self._materialize_from_row(target, "", self._row_face(row))
        st = resp.status
        try:
            return FragTemplate(
                allowed=resp.allowed,
                code=None if st is None else st.code,
                message=None if st is None else st.message,
                causes=(
                    tuple((c.field, c.message) for c in st.details.causes)
                    if st is not None and st.details is not None
                    else None
                ),
            )
        except UnicodeEncodeError:
            # a static message json can represent but utf-8 cannot encode
            # (lone surrogates survive json.loads): this verdict of this
            # target is permanently Python-rendered — the per-row path
            # serializes it fine, a raised batch would not
            return False

    def _answer_rows(
        self,
        items: list[tuple[str, ValidateRequest]],
        targets: list[Any],
        results: list,
        rows: "Iterable[tuple[int, bytes | Mapping[str, Any]]]",
    ) -> int:
        """Answer each ``(item index, output row)``: a tier's hit or a slot
        the device answered, one rule for both. Under the batcher's
        fragment scope (round 19) an eligible target's row answers as uid
        + pre-built template: its responses differ ONLY in uid, so the
        AdmissionResponse/ValidationStatus construction happens once per
        target and verdict, not once per row. Every other row
        materializes. Returns how many answered as fragments."""
        frag_on = _fragments_enabled()
        n_frag = 0
        for i, row in rows:
            tmpl = self._frag_of(targets[i], row) if frag_on else None
            if tmpl is not None:
                results[i] = FragVerdict(items[i][1].uid(), tmpl)
                n_frag += 1
            else:
                results[i] = self._materialize(targets[i], items[i][1], row)
        return n_frag

    def _answer_hits(
        self,
        items: list[tuple[str, ValidateRequest]],
        targets: list[Any],
        results: list,
        hits: "Iterable[tuple[int, bytes | Mapping[str, Any]]]",
    ) -> None:
        """Answer each ``(item index, cached row)`` a tier served
        (_answer_rows); ``fragment_hits`` counts those spliced."""
        n_frag = self._answer_rows(items, targets, results, hits)
        if n_frag:
            self._tiers.count_fragment_hits(n_frag)

    def _row_face(self, row: "bytes | Mapping[str, Any]") -> Mapping[str, Any]:
        """What the materializers read of a cached row: a dict row as it
        is, a packed row through its layout."""
        if type(row) is bytes:
            return PackedRow(self._out_layout, row)  # type: ignore[return-value]
        return row

    def _materialize_from_row(
        self, target: "BoundPolicy | BoundGroup", uid: str, row: Mapping[str, Any]
    ) -> AdmissionResponse:
        """_materialize for a bare output row with no request in hand
        (fragment-template construction): eligible targets never touch
        the payload, so a raising stand-in keeps that claim checked."""

        def _no_payload() -> Any:
            raise RuntimeError(
                "fragment-eligible target touched the request payload"
            )

        if isinstance(target, BoundGroup):
            return self._materialize_group(target, uid, _no_payload, row)
        return self._materialize_single(target, uid, _no_payload, row)

    def _packed_row_of(self, blob: bytes) -> bytes | None:
        """The packed row bytes of ONE request's blob — the host
        fast-path's entry into the key space the device path dedups on —
        at the cost of a single-row encode. None when they cannot be had
        (no native encoder, schema overflow)."""
        if not self.native_encoding:
            return None
        try:
            for schema in self.schemas:
                features, status, _ = schema.native.encode_batch(
                    [blob], 1, self.table
                )
                if status[0] == 0:
                    return features[PACKED_KEY][0].tobytes()
        except ValueError:
            return None
        return None

    def reset_verdict_cache(self) -> None:
        """Drop every cached verdict row in both tiers (benchmark pass
        isolation; a no-op when caching is disabled). Counters are kept —
        they are cumulative serving metrics."""
        if self._tiers is not None:
            self._tiers.clear()

    def _profile_add(self, **deltas: int) -> None:
        with self._profile_lock:
            hp = self._host_profile
            for k, v in deltas.items():
                hp[k] += v

    @property
    def oracle_fallbacks(self) -> int:
        """SchemaOverflow host-oracle fallbacks (locked read: the
        /metrics scrape and the sharded evaluator's sums see a value no
        increment is mid-flight on)."""
        with self._fallback_lock:
            return self._oracle_fallbacks

    @property
    def host_fastpath_requests(self) -> int:
        with self._fallback_lock:
            return self._host_fastpath_requests

    @property
    def batch_dedup_hits(self) -> int:
        return 0 if self._tiers is None else self._tiers.batch_dup_hits

    @property
    def breaker_short_circuited_requests(self) -> int:
        with self._fallback_lock:
            return self._breaker_short_circuited

    @property
    def host_profile(self) -> dict[str, int]:
        """Host-pipeline decomposition counters (ns totals + row counts)
        for the native dispatch path: encode / dedup-bookkeeping /
        dispatch-wait, and the strings the native encoders' mirrors hold
        (a gauge, read here). Bench and /metrics read this."""
        with self._profile_lock:
            profile = dict(self._host_profile)
        if self.native_encoding:
            profile["encode_mirror_entries"] = sum(
                schema.native.mirror_entries for schema in self.schemas
            )
        return profile

    @property
    def plane_program_compiles(self) -> int:
        """Monotonic count of columnar plane structures traced (each is
        one serve- or warmup-time XLA compile). The batcher snapshots it
        around a dispatch and discards RTT samples whose window saw a
        compile — the warmup rule ("the second, compile-free run is the
        routing baseline") applied to serve time."""
        with self._profile_lock:
            return self._plane_compiles

    @property
    def plane_programs_pending(self) -> int:
        """Column-structure compile jobs queued or running off the
        serving path (their batches ship the dense form meanwhile)."""
        with self._profile_lock:
            return self._plane_jobs_pending

    @property
    def mesh(self) -> Any:
        """The attached device mesh (None: single-device program)."""
        return self._mesh

    @property
    def warmup_dispatches(self) -> int:
        """Device dispatches ONE ``warmup((b,))`` call issues — warmup
        runs every shape schema (twice per schema on the columnar path:
        the all-elided and the dense structures), a serving batch
        dispatches exactly one, so RTT seeds divide by this
        (runtime/batcher.py; ADVICE r5 #4)."""
        per_schema = 2 if (self.columnar and self._columnar_mesh_ok()) else 1
        return max(1, len(self.schemas) * per_schema)

    @property
    def optimizer_stats(self) -> dict[str, int]:
        """Predicate-optimizer work accounting (ops/optimizer.py):
        static per-environment facts, re-derived for every reload
        candidate epoch. Keys are OPTIMIZER_STAT_KEYS (graftcheck OB07
        ties each to an exported metrics family). All zeros with
        --predicate-opt off."""
        if self.optimization is None:
            return {k: 0 for k in OPTIMIZER_STAT_KEYS}
        fields_pruned, row_bytes_saved, _rows = self._opt_accounting_get()
        return {
            "subtrees_shared": self.optimization.subtrees_shared,
            "policies_folded": self.optimization.policies_folded,
            "rules_folded": self.optimization.rules_folded,
            "fields_pruned": fields_pruned,
            "row_bytes_saved": row_bytes_saved,
        }

    def _opt_accounting_get(self) -> "tuple[int, int, list[dict]]":
        """Lazy pruning accounting vs the unoptimized schema: rebuilds
        the naive FeatureSchema per cap bucket ONCE on first read (a
        benign race — the computation is pure and idempotent)."""
        if self._opt_accounting is not None:
            return self._opt_accounting
        if self._opt_base_exprs is None:
            self._opt_accounting = (0, 0, [])
            return self._opt_accounting

        from policy_server_tpu.ops.codec import mask_key_for

        def keyset(schema: FeatureSchema) -> set:
            keys = set(schema.specs)
            keys.update(
                mask_key_for(s.key)
                for s in schema.specs.values()
                if s.has_mask
            )
            return keys

        fields_pruned = 0
        row_bytes_saved = 0
        bucket_rows: list[dict] = []
        for i, (a, n) in enumerate(self._opt_cap_buckets):
            base = FeatureSchema.build(
                self._opt_base_exprs, axis_cap=a, nested_axis_cap=n
            )
            bw = base.packed_layout().width
            ow = self.schemas[i].packed_layout().width
            row_bytes_saved += max(0, bw - ow)
            bucket_rows.append(
                {"bucket": i, "row_bytes": ow, "row_bytes_unopt": bw}
            )
            if i == len(self._opt_cap_buckets) - 1:
                fields_pruned = len(keyset(base) - keyset(self.schemas[i]))
        self._opt_accounting = (fields_pruned, row_bytes_saved, bucket_rows)
        return self._opt_accounting

    @property
    def optimizer_bucket_stats(self) -> list[dict]:
        """Per-schema-bucket packed-row widths, optimized vs naive
        (bench detail lines)."""
        return [dict(d) for d in self._opt_accounting_get()[2]]

    @property
    def dedup_stats(self) -> dict[str, int]:
        """The dedup tiers' counters (bench/metrics): DedupTiers.stats."""
        if self._tiers is None:
            return DedupTiers.stats_when_off()
        return self._tiers.stats()

    def has_policy(self, policy_id: str) -> bool:
        try:
            self._lookup_top_level(PolicyID.parse(policy_id))
            return True
        except PolicyInitializationError:
            return True
        except Exception:
            return False

    # -- the fused device program -----------------------------------------

    def _layout_for_buffer(
        self, width: int
    ) -> tuple[int, Any, bool, bool]:
        """→ (schema index, layout, is_transport, is_narrow) for a packed
        buffer width. Total by construction: ensure_unique_packed_widths
        keeps every wide/transport/narrow width distinct across schemas."""
        for i, s in enumerate(self.schemas):
            lo = s.packed_layout()
            if lo.transport16_width == width:
                return i, lo, True, True
            if lo.transport_width == width:
                return i, lo, True, False
            if lo.width == width:
                return i, lo, False, False
        raise AssertionError("no schema matches packed buffer width")

    def _unpack_features(
        self, features: Mapping[str, Any]
    ) -> Mapping[str, Any]:
        """Packed buffer input → the per-key feature dict the compiled
        predicates consume. Slices/offsets are static per batch bucket, so
        XLA fuses the unpack into the predicate program — the packing
        exists purely to make host→device traffic O(1) transfers. The
        slice math itself lives in ``ops.codec.unpack_rows``."""
        if PACKED_KEY not in features:
            return features  # already per-key (tests, entry())
        buf = jnp.asarray(features[PACKED_KEY])
        _idx, layout, transport, narrow = self._layout_for_buffer(
            buf.shape[1]
        )
        # side-channel inputs riding alongside the packed buffer (wasm
        # member verdict bits) pass through untouched
        out: dict[str, Any] = {
            k: v for k, v in features.items() if k != PACKED_KEY
        }
        from policy_server_tpu.ops.codec import unpack_rows

        out.update(unpack_rows(buf, layout, transport, narrow))
        return out

    def _forward(self, features: Mapping[str, Any]) -> tuple[Any, ...]:
        """All policies + group expressions over one feature batch. Pure —
        jit-compiled once per batch bucket shape.

        Outputs are PACKED into four stacked arrays (policy verdicts (B,P),
        rule indices (B,P), group verdicts (B,G), group member-evaluated
        masks (B,G,Mmax)) so the host fetches the whole result in a single
        device_get — per-key fetches pay one transport roundtrip each."""
        features = self._unpack_features(features)
        return self._eval_features(features)

    def _forward_planes(
        self,
        spec: tuple,
        shipped: Mapping[str, Any],
        resident: Mapping[str, Any],
    ):
        """Columnar jit root: ``spec`` is static (schema index, batch,
        narrow, the wire form's shape); ``shipped`` is what this launch
        copied to the device (the wire buffer); ``resident`` the
        column-index vectors that live there across launches. The body
        is deliberately branch-free — plane reconstruction (which
        branches on the form's STRUCTURE at trace time) lives in the
        helper."""
        features = self._features_from_planes(spec, shipped, resident)
        return self._eval_features(features)

    def _resident_zeros(self, shape: tuple, dtype: Any) -> Any:
        """A zero constant reconstructed ON DEVICE for an elided plane —
        resident across dispatches (XLA materializes it once per
        compiled program). Mesh programs place it with the mesh's
        NamedSharding (leading batch dim split on ``data``, replicated
        on ``policy``) so the reconstruction never gathers: each shard
        materializes only its local zero rows."""
        z = jnp.zeros(shape, dtype)
        if self._mesh is not None:
            from policy_server_tpu.parallel import mesh as mesh_mod

            z = jax.lax.with_sharding_constraint(
                z, mesh_mod.batch_sharding(self._mesh)
            )
        return z

    def _features_from_planes(
        self,
        spec: tuple,
        shipped: Mapping[str, Any],
        resident: Mapping[str, Any],
    ) -> dict[str, Any]:
        """Reconstruct the per-key feature dict from the wire buffer: per
        row the shipped int32 columns, the shipped uint16 id columns and
        the shipped bool lanes bit-packed 8:1 (_wire_regions), sliced and
        bitcast here as ``ops.codec.unpack_rows`` does for the row-packed
        transport. Planes/columns the form elides were all-zero on the
        host: they come back as device-generated zero constants (resident
        across dispatches — XLA materializes them once per compiled
        program), so steady-state traffic ships only the columns that
        actually carry data. Shipped columns scatter into the zero base
        by their resident column-index vector; padded index slots repeat
        a real column with identical values, so duplicate scatter writes
        are value-identical (deterministic). A plane shipped whole needs
        no scatter."""
        schema_idx, batch, narrow, shape = spec
        layout = self.schemas[schema_idx].packed_layout()
        zeros = self._resident_zeros
        out: dict[str, Any] = {BATCH_KEY: zeros((batch,), jnp.bool_)}
        wire = shipped.get(WIRE_KEY)
        (k32, _), (k16, _), (k8, _) = shape
        r32, r16, r8, _width = _wire_regions(shape)

        def region(span: tuple, size: int, dtype: Any) -> Any:
            raw = jax.lax.slice_in_dim(wire, *span, axis=1)
            return jax.lax.bitcast_convert_type(
                raw.reshape(batch, -1, size), dtype
            )

        def plane(name: str, vals: Any, n_cols: int) -> Any:
            cols = resident.get(name)
            if cols is None:
                return vals
            return zeros((batch, n_cols), vals.dtype).at[:, cols].set(vals)

        # -- byte region: delta'd at LANE (bool column) granularity —
        #    only lanes with any nonzero value ship, bit-packed, and
        #    scatter into a resident zero lane matrix on device ----------
        lanes = None
        if k8:
            bits = jax.lax.slice_in_dim(wire, *r8, axis=1)
            shifts = jnp.arange(8, dtype=jnp.uint8)
            expanded = (bits[:, :, None] >> shifts) & jnp.uint8(1)
            lanes = plane(
                "bits", expanded.reshape(batch, -1)[:, :k8], layout.total8
            )
        for e in layout.entries8:
            if e.key == BATCH_KEY:
                continue
            if lanes is None:
                out[e.key] = zeros((batch, *e.caps), jnp.bool_)
            else:
                block = jax.lax.slice_in_dim(
                    lanes, e.offset, e.offset + e.elems, axis=1
                )
                out[e.key] = block.reshape((batch, *e.caps)) != 0
        # -- 32-bit region: uint16 id plane + int32 tail plane ------------
        n_id = layout.u16_count if narrow else 0
        n_other = layout.total32 - n_id
        if n_id:
            ids = (
                plane("ids", region(r16, 2, jnp.uint16), n_id)
                if k16
                else zeros((batch, n_id), jnp.uint16)
            ).astype(jnp.int32)
        if n_other:
            other = (
                plane("i32", region(r32, 4, jnp.int32), n_other)
                if k32
                else zeros((batch, n_other), jnp.int32)
            )
        id_off = other_off = 0
        for e in layout.entries32:
            if narrow and e.is_id:
                block = jax.lax.slice_in_dim(
                    ids, id_off, id_off + e.elems, axis=1
                )
                id_off += e.elems
            else:
                block = jax.lax.slice_in_dim(
                    other, other_off, other_off + e.elems, axis=1
                )
                other_off += e.elems
            block = block.reshape((batch, *e.caps))
            if e.is_f32:
                block = jax.lax.bitcast_convert_type(block, jnp.float32)
            out[e.key] = block
        # -- side channel: host-computed wasm member verdict bits ---------
        if self._wasm_member_order:
            wb = shipped.get(WASM_BITS_KEY)
            out[WASM_BITS_KEY] = (
                zeros((batch, len(self._wasm_member_order)), jnp.bool_)
                if wb is None
                else jnp.asarray(wb)
            )
        return out

    def _mesh_bucket_block(self, features: Mapping[str, Any], bucket: tuple):
        """One ``lax.switch`` branch of the fused SPMD program: this
        policy shard's compiled predicates over the LOCAL batch rows,
        stacked and zero-padded to the common block width so every
        branch agrees on shape."""
        batch = jnp.shape(jnp.asarray(features[BATCH_KEY]))[0]
        # one shared CSE table per switch branch: identical scoped
        # subtrees within this policy shard lower once (ops/optimizer)
        cse: dict | None = {} if self.optimization is not None else None
        outs = [self._compiled[pid](features, cse) for pid in bucket]
        allowed_cols = [jnp.asarray(a, jnp.bool_) for a, _r in outs]
        rule_cols = [jnp.asarray(r, jnp.int32) for _a, r in outs]
        pad = self._mesh_block_width - len(allowed_cols)
        allowed_cols.extend([jnp.zeros((batch,), jnp.bool_)] * pad)
        rule_cols.extend([jnp.zeros((batch,), jnp.int32)] * pad)
        return (
            jnp.stack(allowed_cols, axis=-1),
            jnp.stack(rule_cols, axis=-1),
        )

    def _mesh_block_local(self, features: Mapping[str, Any]):
        """The fused-SPMD per-policy body (shard_map root; runs once per
        device on its local batch rows): select this device's
        policy-shard branch by its position on the policy axis, compute
        that shard's verdict block, and all-gather the blocks over the
        policy axis — the XLA collective that replaces the threaded
        dispatcher's N host-side thread joins. Returns shard-major
        ``(batch_local, n_shards * width)`` allowed/rule matrices."""
        from policy_server_tpu.parallel import mesh as mesh_mod

        idx = jax.lax.axis_index(mesh_mod.POLICY_AXIS)
        allowed_blk, rule_blk = jax.lax.switch(
            idx, self._mesh_branches, features
        )
        a_all = jax.lax.all_gather(allowed_blk, mesh_mod.POLICY_AXIS)
        r_all = jax.lax.all_gather(rule_blk, mesh_mod.POLICY_AXIS)
        batch = allowed_blk.shape[0]
        a_mat = jnp.transpose(a_all, (1, 0, 2)).reshape(batch, -1)
        r_mat = jnp.transpose(r_all, (1, 0, 2)).reshape(batch, -1)
        return a_mat, r_mat

    def _per_policy_verdicts(
        self, features: Mapping[str, Any]
    ) -> dict[str, tuple[Any, Any]]:
        """pid → (allowed, rule) columns for every compiled policy — the
        per-policy half of the fused body. Policy-sharded meshes compute
        them through the shard_map collective block (each device runs
        only its own shard's predicates); everything else inlines each
        compiled program directly."""
        per_policy: dict[str, tuple[Any, Any]] = {}
        if self._mesh_block is not None:
            a_mat, r_mat = self._mesh_block(features)
            col = self._mesh_policy_col
            for pid in self._compiled:
                c = col[pid]
                per_policy[pid] = (a_mat[:, c], r_mat[:, c])
        else:
            # the optimizer's shared let-binding table: ONE dict per
            # trace — identical scoped subtrees across the whole policy
            # set lower to the same traced value (ops/optimizer.py CSE)
            cse: dict | None = (
                {} if self.optimization is not None else None
            )
            for pid, fn in self._compiled.items():
                per_policy[pid] = fn(features, cse)
        return per_policy

    def _eval_features(self, features: Mapping[str, Any]):
        """The fused predicate + group-reduction body shared by the packed
        (_forward) and columnar (_forward_planes) roots — and, through
        _per_policy_verdicts, by the single-device and mesh-SPMD forms."""
        per_policy = self._per_policy_verdicts(features)
        batch = jnp.shape(jnp.asarray(features[BATCH_KEY]))[0]
        return self._combine_outputs(per_policy, features, batch)

    def _combine_outputs(
        self,
        per_policy: dict[str, tuple[Any, Any]],
        features: Mapping[str, Any],
        batch: Any,
    ):
        """The group-reduction + output-packing epilogue of the fused
        body. ``features`` supplies only the side channels here (wasm
        member bits)."""
        # Host-executed group members: their compiled programs are inert
        # placeholders — the real verdicts arrive as input bits, computed
        # by the host wasm engine at encode time, and join the fused group
        # reduction here like any other member column.
        if self._wasm_member_order:
            bits = jnp.asarray(features[WASM_BITS_KEY])
            zero_rule = jnp.zeros(bits.shape[0], jnp.int32)
            for j, pid in enumerate(self._wasm_member_order):
                per_policy[pid] = (bits[:, j] != 0, zero_rule)
        p_allowed = jnp.stack(
            [per_policy[pid][0] for pid in self._policy_order], axis=-1
        ) if self._policy_order else jnp.zeros((0, 0), jnp.bool_)
        p_rule = jnp.stack(
            [per_policy[pid][1] for pid in self._policy_order], axis=-1
        ) if self._policy_order else jnp.zeros((0, 0), jnp.int32)

        g_allowed_cols = []
        g_eval_cols = []
        for name in self._group_order:
            group = self._groups[name]
            member_allowed = {
                m: per_policy[f"{name}/{m}"][0] for m in group.members
            }
            verdict, evaluated = groups_mod.lower_group(group.ast, member_allowed)
            g_allowed_cols.append(verdict)
            # a member defined but unreferenced by the expression is never
            # evaluated → all-False mask
            masks = [
                evaluated.get(m, jnp.zeros_like(verdict)) for m in group.members
            ]
            pad = self._max_group_members - len(masks)
            masks.extend([jnp.zeros_like(verdict)] * pad)
            g_eval_cols.append(jnp.stack(masks, axis=-1))  # (B, Mmax)
        g_allowed = (
            jnp.stack(g_allowed_cols, axis=-1)
            if g_allowed_cols
            else jnp.zeros((batch, 0), jnp.bool_)
        )
        g_eval = (
            jnp.stack(g_eval_cols, axis=1)  # (B, G, Mmax)
            if g_eval_cols
            else jnp.zeros((batch, 0, 0), jnp.bool_)
        )
        # ONE output array: every result fetch pays a full per-array
        # sync, so the four logical outputs ride a single tensor
        # (B, P + P + G + G*Mmax) — uint8 when every rule index fits a
        # byte (compact outputs: 4x fewer bytes fetched)
        out_dtype = jnp.uint8 if self._compact_outputs else jnp.int32
        out = jnp.concatenate(
            [
                p_allowed.astype(out_dtype),
                p_rule.astype(out_dtype),
                g_allowed.astype(out_dtype),
                g_eval.reshape(batch, -1).astype(out_dtype),
            ],
            axis=1,
        )
        if self._mesh is not None:
            # the verdict reduction stays batch-sharded: per-host
            # frontends fetch only their local rows, and XLA keeps the
            # group combine partitioned on data instead of gathering
            from policy_server_tpu.parallel import mesh as mesh_mod

            out = jax.lax.with_sharding_constraint(
                out, mesh_mod.batch_sharding(self._mesh)
            )
        return out

    def _unpack(self, packed: np.ndarray) -> dict[str, np.ndarray]:
        """Packed device output → the per-key dict the materializers use."""
        return self._out_layout.columns(np.asarray(packed))

    def _transport(self, features: Mapping[str, Any]) -> Mapping[str, Any]:
        """Wide packed batch → bit-packed transport form (roughly a
        quarter of the bytes over the host→device link while the intern
        vocabulary fits uint16); per-key dicts pass through."""
        buf = features.get(PACKED_KEY)
        if buf is None:
            return features
        width = np.asarray(buf).shape[1]
        for s in self.schemas:
            if s.packed_layout().width == width:
                return s.to_transport(features, vocab_size=len(self.table))
        return features  # already transport width (or side-channel only)

    # Ship a delta plane as full when the shipped-column bucket would be
    # at least this fraction of the plane — the scatter then buys nothing.
    _DELTA_FULL_FRACTION = 0.75

    def _schema_index_for(self, features: Mapping[str, Any]) -> int | None:
        """Schema index for a WIDE packed buffer (None for per-key dicts
        or buffers already in a transport width — those keep the packed
        path)."""
        buf = features.get(PACKED_KEY)
        if buf is None:
            return None
        width = np.asarray(buf).shape[1]
        for i, s in enumerate(self.schemas):
            if s.packed_layout().width == width:
                return i
        return None

    @classmethod
    def _select_delta_cols(
        cls, live: np.ndarray, n_cols: int
    ) -> np.ndarray | None:
        """The ONE column-selection rule every plane uses: given the
        indices of the columns to ship, return the shipped column vector
        — padded to a power-of-two count by repeating the last real
        column (value-identical duplicate scatter writes are
        deterministic) — or None when the padded count is dense enough
        that shipping the whole plane beats the scatter."""
        k = int(live.size)
        kb = bucket_size(k)
        if kb >= cls._DELTA_FULL_FRACTION * n_cols:
            return None
        if kb == k:
            return live
        return np.concatenate(
            [live, np.full(kb - k, live[-1], dtype=live.dtype)]
        )

    def _narrow(self, schema_idx: int) -> bool:
        """Intern-id lanes ship as uint16 while the vocabulary fits."""
        layout = self.schemas[schema_idx].packed_layout()
        return layout.u16_count > 0 and len(self.table) <= 65536

    def _wire_layout(self, schema_idx: int, narrow: bool) -> _WireLayout:
        """The (cached) map from one schema's wide rows to wire planes."""
        key = (schema_idx, narrow)
        layout = self._wire_layouts.get(key)
        if layout is None:
            schema = self.schemas[schema_idx]
            # a race builds it twice, equal: setdefault keeps one
            layout = self._wire_layouts.setdefault(key, _WireLayout(
                schema.packed_layout(),
                schema._transport_col_split() if narrow else None,
            ))
        return layout

    def _plane_template(self, batch: int, form: _WireForm) -> dict[str, Any]:
        """An all-zero shipped dict with the exact structure, shapes and
        dtypes a real batch of this form has — what warm-up and the
        off-path compiler run a program with."""
        shipped: dict = {}
        self._add_wasm_bits(shipped, batch)
        if form.width:
            shipped[WIRE_KEY] = np.zeros((batch, form.width), np.uint8)
        return shipped

    def _note_plane_program(self, spec: tuple) -> None:  # holds: _profile_lock
        """Record (under _profile_lock) that the program of this spec is
        being compiled: it is dispatchable from now on, counts one
        compile, and its resident zero constants are accounted. The spec
        is the program's whole jit-cache identity: the form's shape in it
        fixes the wire row's width and the index vectors' shapes (a new
        power-of-two column bucket is a NEW compiled program), and an
        environment's wasm side channel never changes."""
        if spec in self._plane_combos:
            return
        self._plane_combos.add(spec)
        self._plane_compiles += 1
        # planes reconstructed on device are resident zero constants of
        # this compiled program: the elided byte-columns plus every
        # unshipped 32-bit column. The byte region counts in DEVICE lane
        # units (one uint8 lane per bool column), not packed wire bytes:
        # the device materializes (batch, total8) lanes and everything
        # not scattered from the shipped subset is constant zero
        schema_idx, batch, _narrow, ((k32, _), (k16, _), (k8, _)) = spec
        layout = self.schemas[schema_idx].packed_layout()
        self._host_profile["resident_const_bytes"] += batch * (
            layout.total8 - k8 + 4 * (layout.total32 - k32 - k16)
        )

    def _launch_planes(
        self, spec: tuple, form: _WireForm, shipped: Mapping[str, Any]
    ) -> Any:
        """Launch the columnar program (async) on what a batch ships: ONE
        host array, the wire buffer (plus the wasm side channel where an
        environment has one), so one host-to-device copy — on a mesh one
        put sharded over the data axis, a copy per device (batches are
        bucketed to divide the axis, so the split is exact). The form's
        column-index vectors go to the device the first time it launches
        (replicated over a mesh) and stay: warm-up, the off-path compiler
        and the serving path all come through here, so all hand the
        program arguments of one kind."""
        mesh = self._mesh
        resident = form.resident
        if mesh is not None:
            from policy_server_tpu.parallel import mesh as mesh_mod
        if resident is None:  # a race places them twice, equal
            resident = form.resident = jax.device_put(
                form.cols,
                None if mesh is None else mesh_mod.replicated_sharding(mesh),
            )
            self._profile_add(
                wire_bytes_shipped=sum(c.nbytes for c in form.cols.values())
            )
        if mesh is not None:
            shipped = mesh_mod.shard_delta_planes(shipped, mesh)
        return self._fused_planes(spec, shipped, resident)

    def _plane_dispatch(
        self,
        schema_idx: int,
        features: Mapping[str, Any],
        rows: int = 0,
        native: _NativeWire | None = None,
    ) -> Any:
        """Columnar device dispatch: settle the form, account wire bytes /
        delta columns, and launch the columnar program on the one wire
        buffer (async — caller fetches through _device_fetch).

        A batch with no live column ships nothing (the all-elided
        program). Every other batch ships its schema's settled column set
        (_PlaneColumns). The serving path never waits on a compiler where
        it can help it: while the program for that set is not compiled
        yet, the batch ships the DENSE form — every plane whole, warm for
        every batch bucket since boot, bit-exact by construction — and
        the set compiles off the serving path for every warm batch bucket
        (_compile_columns). Only a batch size warm-up never saw compiles
        inside the dispatch, watchdog-bounded like any cold bucket.

        Who writes the wire: the chunk's encode call did (``native``,
        _NativeWire) whenever it left no string record — then the launch
        reads its liveness words, and ships its buffer if the form decided
        here, under the lock, is still the one it was written in. Every
        other launch (a cold string, a set that grew or is compiling, the
        all-elided batch, any caller with no encode call behind it)
        computes _live_words and _WireForm.wire here, in numpy: the same
        bytes, the reference the native writer is tested against, at a
        hand-off of the GIL a call (PERF.md section 6, PRs 28 and 37)."""
        buf = np.ascontiguousarray(features[PACKED_KEY])
        playout = self.schemas[schema_idx].packed_layout()
        narrow = self._narrow(schema_idx)
        layout = self._wire_layout(schema_idx, narrow)
        shipped: dict[str, Any] = {}
        wasm_bits = features.get(WASM_BITS_KEY)
        if wasm_bits is not None:
            # ALWAYS ships when present (tiny: batch × the member count):
            # eliding the all-zero case would flap the jit structure
            # between wasm-present and wasm-absent programs per batch
            shipped[WASM_BITS_KEY] = np.asarray(wasm_bits)
        side_bytes = sum(a.nbytes for a in shipped.values())
        # the liveness check is what keeps a superset exact: a byte no
        # batch had non-zero before grows the set before this one ships
        if native is None:
            batch, live = buf.shape[0], _live_words(buf)
        else:
            batch, live = native.wire.shape[0], native.live
        any_live = live.any()
        form, version, schedule = layout.elided, 0, False
        with self._profile_lock:
            if any_live:
                settled = self._plane_columns.get((schema_idx, narrow))
                if settled is None:
                    settled = self._plane_columns[(schema_idx, narrow)] = (
                        _PlaneColumns(layout)
                    )
                if (live & settled.unseen).any():
                    settled.admit(live, self._select_delta_cols)
                form, version = settled.form, settled.version
            spec = (schema_idx, batch, narrow, form.shape)
            if spec not in self._plane_combos:
                dense = (schema_idx, batch, narrow, layout.dense.shape)
                if version and dense in self._plane_combos:
                    schedule = True
                    form, spec = layout.dense, dense
                else:
                    self._note_plane_program(spec)
            written = native is not None and native.form is form
            hp = self._host_profile
            hp["wire_bytes_shipped"] += batch * form.width + side_bytes
            hp["wire_bytes_packed_equiv"] += batch * (
                playout.transport16_width if narrow
                else playout.transport_width
            )
            hp["wire_rows"] += batch
            hp["delta_cols_shipped"] += form.shape[0][0] + form.shape[1][0]
            hp["delta_cols_total"] += playout.total32
            hp["launch_h2d_arrays"] += len(shipped) + bool(form.width)
            hp["launch_native_wire"] += written
        if schedule:
            self._compile_columns_async(schema_idx, narrow, version)
        if written:
            shipped[WIRE_KEY] = native.wire
        elif form.width:
            shipped[WIRE_KEY] = form.wire(
                buf if native is None else native.wide(buf)
            )
        return self._device_call(
            self._launch_planes, spec, form, shipped, rows=rows
        )

    def _compile_columns_async(
        self, schema_idx: int, narrow: bool, version: int
    ) -> None:
        """Hand a schema's newly grown column set to the off-path
        compiler, once per version."""
        with self._profile_lock:
            settled = self._plane_columns[(schema_idx, narrow)]
            if settled.scheduled >= version or self._closed:
                return
            settled.scheduled = version
            self._plane_jobs_pending += 1
            if self._plane_compiler is None:
                from policy_server_tpu.runtime.workers import DaemonExecutor

                self._plane_compiler = DaemonExecutor(
                    max_workers=1, thread_name_prefix="plane-compile"
                )
            pool = self._plane_compiler
        try:
            pool.submit(self._compile_columns, schema_idx, narrow, version)
        except RuntimeError:  # close() shut the pool down meanwhile
            with self._profile_lock:
                self._plane_jobs_pending -= 1

    def _compile_columns(
        self, schema_idx: int, narrow: bool, version: int
    ) -> None:
        """Off the serving path: compile the program of one schema's
        column set for EVERY batch bucket warm-up visited (largest first —
        full batches carry the traffic), so that a settled traffic mix
        finds every program it can ask for. A set that grew meanwhile is
        abandoned: the dispatch that grew it queued the newer one."""
        from policy_server_tpu.telemetry.tracing import logger

        try:
            with self._profile_lock:
                batches = sorted(self._warm_batches, reverse=True)
            for batch in batches:
                with self._profile_lock:
                    settled = self._plane_columns[(schema_idx, narrow)]
                    if settled.version != version or self._closed:
                        return
                    form = settled.form
                    spec = (schema_idx, batch, narrow, form.shape)
                    if spec in self._plane_combos:
                        continue
                t0 = time.perf_counter()
                jax.block_until_ready(self._launch_planes(
                    spec, form, self._plane_template(batch, form)
                ))
                # dispatchable only now: until the program exists the
                # serving path keeps shipping the dense form
                with self._profile_lock:
                    self._note_plane_program(spec)
                logger.info(
                    "columnar plane program compiled off the serving path",
                    extra={"span_fields": {
                        "schema": schema_idx, "batch": batch,
                        "column_set_version": version,
                        "seconds": round(time.perf_counter() - t0, 3),
                    }},
                )
        except Exception:  # noqa: BLE001 — the compiler thread must outlive a failed compile; the dense form keeps serving
            logger.exception(
                "columnar plane program failed to compile off the serving "
                "path (schema %d, column-set version %d); its batches keep "
                "shipping the dense form", schema_idx, version,
            )
        finally:
            with self._profile_lock:
                self._plane_jobs_pending -= 1

    def _dispatch_features(
        self,
        features: Mapping[str, Any],
        rows: int = 0,
        native: _NativeWire | None = None,
    ) -> Any:
        """The one device-dispatch funnel for full batches: columnar when
        enabled and the features are a wide packed buffer — including
        mesh-sharded programs (round 14: delta planes ship batch-sharded,
        elided planes come back as NamedSharding-placed resident zero
        constants); otherwise the packed (row-major, bit-packed
        transport) path. Multi-process meshes keep the packed path (see
        _columnar_mesh_ok). ``native`` is what the chunk's encode call
        wrote for a columnar launch."""
        schema_idx = self._schema_index_for(features)
        if self.columnar and self._columnar_mesh_ok():
            if schema_idx is not None:
                return self._plane_dispatch(
                    schema_idx, features, rows, native
                )
        # _wire_form_for asks the encode call for a wire only where the
        # test above holds, and its wide rows are a schema's own
        assert native is None
        features = self._transport(features)
        if self._mesh is not None:
            from policy_server_tpu.parallel import mesh as mesh_mod

            features = mesh_mod.shard_features(features, self._mesh)
        return self._device_call(self._fused, features, rows=rows)

    def _device_call(self, fn: Callable, *args: Any, rows: int = 0) -> Any:
        """Run a synchronous device-path call (the jit dispatch itself),
        feeding dispatch-time raises — driver errors, RESOURCE_EXHAUSTED
        thrown at the call rather than at fetch — to the breaker before
        re-raising. Fetch-time raises feed it in _device_fetch.

        The call is held under the package's one profiler annotation,
        ``ps:launch`` (flightrec.LAUNCH_ANNOTATION): with a jax.profiler
        trace running it puts this thread's ambient batch id, the rows
        shipped and a ``perf_counter_ns`` reading into the trace, which
        is all a reader needs to put the flight recorder's ring on the
        device trace's clock and to give each execution of the fused
        program its batch. Without a trace it is one inactive TraceMe."""
        try:
            with jax.profiler.TraceAnnotation(
                flightrec.LAUNCH_ANNOTATION,
                batch=flightrec.current_batch(), rows=rows,
                perf_counter_ns=time.perf_counter_ns(),
            ):
                return fn(*args)
        except Exception:
            if self.breaker is not None:
                self.breaker.record_failure()
            raise

    def _scoped_device_fetch(
        self,
        scope_name: str | None,
        dev_out: Any,
        rec_batch: int = -1,
        rec_rows: int = 0,
    ):
        """_device_fetch on a drain-pool thread, re-applying the
        submitter's ambient failpoint scope — tenant-scoped chaos
        (failpoints.scope) must cross the pool boundary with the work.
        ``rec_batch``/``rec_rows`` carry the submitter's flight-recorder
        attribution the same way: the device_get window recorded here is
        the host-observed device-execute segment of that batch's
        timeline (it runs UNDER the materialize fetch wait, so the
        attribution report treats it as informational, never additive)."""
        with failpoints.scope(scope_name):
            rec = flightrec.recorder()
            if rec is None:
                return self._device_fetch(dev_out)
            t0 = time.perf_counter_ns()
            out = self._device_fetch(dev_out)
            rec.record_phase(
                flightrec.PH_DEVICE_EXECUTE, t0, time.perf_counter_ns(),
                rows=rec_rows, batch=rec_batch,
            )
            return out

    def _device_fetch(self, dev_out: Any) -> Any:
        """The choke point every device RESULT FETCH goes through (plain
        run_batch and the native pipeline's drain futures): fires the
        ``device.fetch`` failpoint and feeds the circuit breaker — a
        fetch that raises is a dispatch fault, a fetch that returns is
        the success that closes a half-open breaker. Dispatch-time raises
        feed the breaker in _device_call; a fetch that HANGS is invisible
        to both, and the batcher's watchdog reports those through
        record_dispatch_failure."""
        breaker = self.breaker
        try:
            failpoints.fire("device.fetch")
            if (
                getattr(dev_out, "is_fully_addressable", True)
                or not isinstance(dev_out, jax.Array)
            ):
                out = jax.device_get(dev_out)
            else:
                # multi-host mesh: the verdict tensor is batch-sharded
                # across processes — this host fetches ONLY its local
                # rows (its own frontend's requests; the make_mesh
                # data-outermost layout makes them contiguous), never a
                # cross-DCN gather of rows another host will answer
                out = self._local_rows(dev_out)
        except Exception:
            if breaker is not None:
                breaker.record_failure()
            raise
        if breaker is not None:
            breaker.record_success()
        return out

    @staticmethod
    def _local_rows(dev_out: Any) -> np.ndarray:
        # holds: nothing — pure shard assembly for _device_fetch (the
        # TP03 choke point); policy-axis replicas dedup by global row
        # range, rows concatenate in global order == this host's
        # submission order
        by_start: dict[int, Any] = {}
        for shard in dev_out.addressable_shards:
            row_slice = shard.index[0] if shard.index else slice(None)
            start = row_slice.start or 0
            if start not in by_start:
                by_start[start] = np.asarray(shard.data)
        return np.concatenate(
            [by_start[s] for s in sorted(by_start)], axis=0
        )

    def record_dispatch_failure(self, policy_ids: Any = None) -> None:
        """Report a device-path failure the environment cannot observe
        itself — the dispatch watchdog abandoning a hung batch
        (runtime/batcher.py). ``policy_ids`` exists for the sharded
        evaluator's override, which routes the report to the owning
        shards; a single environment has exactly one breaker."""
        if self.breaker is not None:
            self.breaker.record_failure()

    @property
    def breaker_all_open(self) -> bool:
        """True while the device path is fully tripped AND still blocking
        (the --degraded-mode gate consults this; on a sharded mesh it
        means EVERY shard). Deliberately ``blocking_device``, not
        ``is_open``: when the cooldown makes a probe due this flips False
        so the batch proceeds to the dispatch path, whose allow_device()
        runs the half-open probe — otherwise monitor/reject modes would
        bypass the only recovery mechanism and stay degraded forever."""
        return self.breaker is not None and self.breaker.blocking_device

    @property
    def breaker_stats(self) -> dict[str, int]:
        """Breaker counters for /metrics (+ open-shard aggregation keys so
        the single-env and sharded surfaces expose the same schema)."""
        if self.breaker is None:
            return {}
        stats = self.breaker.stats()
        stats.pop("state_code", None)  # per-shard; not summable
        stats["open_shards"] = stats.pop("open")
        stats["total_shards"] = 1
        with self._fallback_lock:
            stats["short_circuited_requests"] = (
                self._breaker_short_circuited
            )
        return stats

    def run_batch(self, features: Mapping[str, Any]) -> dict[str, np.ndarray]:
        """Dispatch one encoded feature batch to the device; ONE device_get
        fetches every verdict."""
        packed = self._device_fetch(self._dispatch_features(features))
        return self._unpack(packed)

    def warmup(self, batch_sizes: tuple[int, ...] = (1,)) -> None:
        """AOT-compile the fused program for every (shape bucket × batch
        bucket) so the first request isn't a compile stall (reference
        precompiles at boot via rayon, lib.rs:287-307; SURVEY.md §7.2
        step 6)."""
        buckets = sorted({self.bucket_for(b) for b in batch_sizes})
        columnar = self.columnar and self._columnar_mesh_ok()
        if columnar:
            with self._profile_lock:
                self._warm_batches.update(buckets)
        for idx, schema in enumerate(self.schemas):
            for b in buckets:
                batch = schema.empty_batch_packed(b)
                self._add_wasm_bits(batch, b)
                dev_out = self._dispatch_features(batch)
                if not self.warmup_output_devices:
                    self.warmup_output_devices = len(
                        dev_out.sharding.device_set
                    )
                self._device_fetch(dev_out)
                if columnar:
                    # also compile the DENSE columnar structure (every
                    # plane shipped whole): the all-zero batch above only
                    # compiles the all-elided program, and the dense form
                    # is what a real batch ships until the program for
                    # its schema's settled column set is compiled
                    # (_plane_dispatch). Built from a template, not from
                    # a batch of ones: warm-up must teach the column
                    # sets nothing.
                    narrow = self._narrow(idx)
                    dense = self._wire_layout(idx, narrow).dense
                    spec = (idx, b, narrow, dense.shape)
                    with self._profile_lock:
                        self._note_plane_program(spec)
                    self._device_fetch(self._device_call(
                        self._launch_planes, spec, dense,
                        self._plane_template(b, dense),
                    ))

    def encode_bucketed(
        self, payload: Any
    ) -> tuple[int, dict[str, np.ndarray]]:
        """Encode into the smallest shape bucket that fits; raises
        SchemaOverflow when even the widest schema cannot hold the
        request (→ oracle fallback)."""
        failpoints.fire("encode.batch")
        last_error: SchemaOverflow | None = None
        for i, schema in enumerate(self.schemas):
            try:
                return i, schema.encode(payload, self.table)
            except SchemaOverflow as e:
                last_error = e
        assert last_error is not None
        raise last_error

    # -- single-request evaluation (batch of 1; the batcher uses the
    #    *_from_outputs materializers below for real micro-batches) --------

    def validate(self, policy_id: str, request: ValidateRequest) -> AdmissionResponse:
        """Reference EvaluationEnvironment::validate (rs:546-556)."""
        pid = PolicyID.parse(policy_id)
        target = self._lookup_top_level(pid)
        payload = self.payload_for(target, request)
        if pre_eval_hooks_of(target):
            self._run_pre_eval_hooks(target, payload)
            # rebuild: context providers must observe hook results (e.g.
            # image verification caching happens in the hook)
            payload = self.payload_for(target, request)

        if self._host_executed(target):
            # pass the context-bearing payload (payload_for output), not
            # the raw request: wasm policies get __context__ too
            return self._materialize_single(target, request.uid(), payload, {})
        if self.backend == "oracle":
            return self._materialize(target, request, self._oracle_outputs(payload, target))
        if self.breaker is not None and not self.breaker.allow_device():
            # tripped: the targeted host oracle serves (bit-exact by the
            # differential guarantee) until a half-open probe closes it
            with self._fallback_lock:
                self._breaker_short_circuited += 1
            return self._materialize(
                target, request, self._oracle_outputs_for(target, payload)
            )
        try:
            bucket_idx, encoded = self.encode_bucketed(payload)
        except SchemaOverflow:
            with self._fallback_lock:
                self._oracle_fallbacks += 1
            return self._materialize(target, request, self._oracle_outputs(payload, target))
        schema = self.schemas[bucket_idx]
        bucket = self.bucket_for(1)
        batch = schema.pack(schema.stack([encoded], batch_size=bucket))
        winfo = self._eval_wasm_members(target, payload)
        stash = self._add_wasm_bits(
            batch, bucket, [(0, winfo)] if winfo else None
        )
        outputs = {k: v[0] for k, v in self.run_batch(batch).items()}
        for k, v in stash.items():
            outputs[k] = v[0]
        return self._materialize(target, request, outputs)

    def pre_eval_hooks_of(
        self, target: BoundPolicy | BoundGroup
    ) -> list[Callable[[Any], None]]:
        """Host-side pre-eval hooks of a policy/group (latency-fault
        fixtures); the batcher runs them off-thread under the request
        deadline (runtime/batcher.py)."""
        return pre_eval_hooks_of(target)

    def _run_pre_eval_hooks(
        self, target: BoundPolicy | BoundGroup, payload: Any
    ) -> None:
        for hook in pre_eval_hooks_of(target):
            hook(payload)

    # -- wasm group members (host verdicts as device inputs) ---------------

    @staticmethod
    def _wasm_verdict_triple(verdict: Mapping[str, Any]) -> tuple[bool, Any, bool]:
        """Host-evaluator verdict dict → (allowed, message, would_mutate);
        the single decode point for every path that consumes wasm member
        verdicts."""
        return (
            bool(verdict.get("accepted")),
            verdict.get("message"),
            verdict.get("mutated_object") is not None,
        )

    def _wasm_member_outputs(
        self, bp: BoundPolicy, payload: Any, out: dict[str, Any]
    ) -> bool:
        """Evaluate one wasm member host-side and write its output keys
        (used by both oracle paths); returns the allowed bit."""
        verdict = bp.precompiled.program.host_evaluator(payload)
        allowed, msg, mutated = self._wasm_verdict_triple(verdict)
        out[f"p:{bp.policy_id}:allowed"] = allowed
        out[f"p:{bp.policy_id}:rule"] = -1
        out[f"wm:{bp.policy_id}:msg"] = msg
        out[f"wm:{bp.policy_id}:mutated"] = mutated
        return allowed

    def _eval_wasm_members(
        self, target: "BoundPolicy | BoundGroup", payload: Any
    ) -> dict[str, tuple[bool, Any, bool]]:
        """Host-evaluate a group target's wasm members on one payload →
        {member pid: (allowed, message, would_mutate)}. Members the group
        expression never references are skipped — their verdicts are
        masked out anyway (evaluated-semantics), so running the engine
        for them would be pure waste. Host evaluators never raise (wasm
        errors map to in-band rejections, evaluation/wasm_policy.py)."""
        if not isinstance(target, BoundGroup) or (
            target.name not in self._groups_with_wasm
        ):
            return {}
        referenced = groups_mod.referenced_members(target.ast)
        out: dict[str, tuple[bool, Any, bool]] = {}
        for member_name, bp in target.members.items():
            he = bp.precompiled.program.host_evaluator
            if he is None or member_name not in referenced:
                continue
            out[bp.policy_id] = self._wasm_verdict_triple(he(payload))
        return out

    def _add_wasm_bits(
        self,
        batch_features: dict,
        bucket: int,
        row_infos: "list[tuple[int, dict]] | None" = None,
    ) -> dict[str, list]:
        """Attach the WASM_BITS_KEY device input for a batch and return
        the host-side stash (per-row member messages / mutation flags) to
        merge into the outputs dict. ``row_infos``: (row, info) pairs from
        _eval_wasm_members. No-op (returns {}) when no wasm members are
        loaded — the jit signature then stays bit-for-bit identical to a
        wasm-free environment."""
        if not self._wasm_member_order:
            return {}
        bits = np.zeros((bucket, len(self._wasm_member_order)), np.bool_)
        stash: dict[str, list] = {}
        for row, info in row_infos or []:
            for pid, (allowed, msg, mutated) in info.items():
                bits[row, self._wasm_member_col[pid]] = allowed
                stash.setdefault(f"wm:{pid}:msg", [None] * bucket)[row] = msg
                stash.setdefault(f"wm:{pid}:mutated", [False] * bucket)[
                    row
                ] = mutated
        batch_features[WASM_BITS_KEY] = bits
        return stash

    def _oracle_outputs_for(
        self, target: BoundPolicy | BoundGroup, payload: Any
    ) -> dict[str, Any]:
        """Targeted host-oracle evaluation: only the programs the target's
        materializer reads (one policy, or a group's members + expression).
        This is the latency fast-path kernel — cost is proportional to the
        addressed policy, not the whole loaded set (contrast
        _oracle_outputs, the full-registry fallback)."""
        out: dict[str, Any] = {}
        if isinstance(target, BoundGroup):
            member_allowed: dict[str, bool] = {}
            referenced = groups_mod.referenced_members(target.ast)
            for m, bp in target.members.items():
                if bp.precompiled.program.host_evaluator is not None:
                    if m in referenced:
                        member_allowed[m] = self._wasm_member_outputs(
                            bp, payload, out
                        )
                    else:
                        # unreferenced wasm member: masked out — skip the
                        # engine, write an inert verdict (the materializer
                        # indexes every member's keys)
                        out[f"p:{bp.policy_id}:allowed"] = False
                        out[f"p:{bp.policy_id}:rule"] = -1
                        member_allowed[m] = False
                    continue
                allowed, rule_idx = oracle_mod.evaluate_program(
                    bp.precompiled.program, payload
                )
                out[f"p:{bp.policy_id}:allowed"] = allowed
                out[f"p:{bp.policy_id}:rule"] = rule_idx
                member_allowed[m] = bool(allowed)
            verdict, evaluated = groups_mod.evaluate_group_host(
                target.ast, member_allowed
            )
            out[f"g:{target.name}:allowed"] = verdict
            for m in target.members:
                out[f"g:{target.name}:eval:{m}"] = evaluated.get(m, False)
            return out
        allowed, rule_idx = oracle_mod.evaluate_program(
            target.precompiled.program, payload
        )
        out[f"p:{target.policy_id}:allowed"] = allowed
        out[f"p:{target.policy_id}:rule"] = rule_idx
        return out

    def _oracle_outputs(
        self, payload: Any, target: "BoundPolicy | BoundGroup | None" = None
    ) -> dict[str, Any]:
        """Host-interpreter evaluation of every policy + group (scalar
        outputs, same keys as the device path). The wasm engine runs ONLY
        for members the target's materializer will read (referenced
        members of the target group) — every other wasm entry is inert;
        running a 50M-fuel interpretation for a verdict nobody reads
        would dominate this fallback's cost."""
        needed: set[str] = set()
        if (
            isinstance(target, BoundGroup)
            and target.name in self._groups_with_wasm
        ):
            referenced = groups_mod.referenced_members(target.ast)
            needed = {
                bp.policy_id
                for m, bp in target.members.items()
                if m in referenced
                and bp.precompiled.program.host_evaluator is not None
            }
        out: dict[str, Any] = {}
        for pid, bp in self._bound.items():
            if bp.precompiled.program.host_evaluator is not None:
                if pid in needed:
                    self._wasm_member_outputs(bp, payload, out)
                else:
                    # unread (standalone wasm routes via _host_executed;
                    # other groups' members are not this target's)
                    out[f"p:{pid}:allowed"] = False
                    out[f"p:{pid}:rule"] = -1
                continue
            allowed, rule_idx = oracle_mod.evaluate_program(
                bp.precompiled.program, payload
            )
            out[f"p:{pid}:allowed"] = allowed
            out[f"p:{pid}:rule"] = rule_idx
        for name, group in self._groups.items():
            member_allowed = {
                m: bool(out[f"p:{name}/{m}:allowed"]) for m in group.members
            }
            verdict, evaluated = groups_mod.evaluate_group_host(
                group.ast, member_allowed
            )
            out[f"g:{name}:allowed"] = verdict
            for m in group.members:
                out[f"g:{name}:eval:{m}"] = evaluated.get(m, False)
        return out

    # -- batched evaluation (the micro-batcher's device path) --------------

    @property
    def supports_host_fastpath(self) -> bool:
        """True when validate_batch(prefer_host=True) short-circuits the
        device: the scheduler (runtime/batcher.py) may answer small or
        latency-critical batches on the host. Only meaningful on the jax
        backend — the oracle backend is already host-side."""
        return self.backend == "jax"

    def validate_batch(
        self,
        items: list[tuple[str, ValidateRequest]],
        run_hooks: bool = True,
        prefer_host: bool = False,
        audit: bool = False,
        live: bool = False,
    ) -> list[AdmissionResponse | Exception]:
        """Evaluate many (policy_id, request) pairs in ONE device dispatch.

        This is the TPU-native replacement for the reference's
        one-wasm-instance-per-request loop (evaluation_environment.rs:513-581):
        the fused program computes every policy's verdict for every row, so
        requests targeting *different* policies batch together freely — the
        batcher never needs to partition by policy.

        Per-item failures (unknown id, initialization error) come back as
        Exception entries rather than failing the batch; SchemaOverflow rows
        fall back to the host oracle (SURVEY.md §7.4 escape hatch).

        ``prefer_host=True`` (the scheduler's latency fast-path) answers
        every IR row with the TARGETED host oracle instead of a device
        dispatch — bit-exact by the differential suite's guarantee, and
        microseconds instead of a device round-trip. The direct API
        (prefer_host=False, the default) always exercises the device, so
        differential tests comparing this environment against the oracle
        backend stay non-circular.

        ``audit=True`` (the batcher's best-effort lane) marks rows that
        answer nobody: what they ship counts as ``audit_rows``, not as
        ``dispatched_rows``, which is how many requests the device
        answered, and what a dedup tier answers of them counts as the
        lane's tier hits, not as the tier's (verdict_cache.py).

        ``live=True`` (the micro-batcher's batches of the front end's
        requests) keeps the batch off the blob tier, whose key holds a
        uid the API server mints anew for every webhook call: a live
        review goes straight to the row tier (verdict_cache.py). The
        default keeps the blob tier for the replays that can hit it.
        """
        if self._closed:
            raise RuntimeError("environment closed")
        if prefer_host and self.backend == "jax":
            return self._validate_batch_hostpath(items, run_hooks, live)
        if (
            self.backend == "jax"
            and self.breaker is not None
            and not self.breaker.allow_device()
        ):
            # breaker tripped: graceful degradation to the bit-exact host
            # oracle — correct verdicts, zero device exposure; half-open
            # probes re-enter through allow_device after the cooldown
            with self._fallback_lock:
                self._breaker_short_circuited += len(items)
            return self._validate_batch_hostpath(items, run_hooks, live)
        if self.backend == "jax":
            # chunks to max_dispatch_batch internally, with pipelining
            return self._validate_batch_native(
                items, run_hooks, audit=audit, live=live
            )
        # the oracle backend: every row by the host interpreter
        results: list[AdmissionResponse | Exception] = []
        for policy_id, request in items:
            try:
                target = self._lookup_top_level(PolicyID.parse(policy_id))
                payload = self.payload_for(target, request)
                if run_hooks and pre_eval_hooks_of(target):
                    self._run_pre_eval_hooks(target, payload)
                    # rebuild: providers must observe hook results
                    payload = self.payload_for(target, request)
                if self._host_executed(target):
                    results.append(
                        self._materialize_single(
                            target, request.uid(), payload, {}
                        )
                    )
                    continue
                results.append(
                    self._materialize(
                        target, request, self._oracle_outputs(payload, target)
                    )
                )
            except Exception as e:  # noqa: BLE001 — per-item error channel
                results.append(e)
        return results

    def _validate_batch_hostpath(
        self,
        items: list[tuple[str, ValidateRequest]],
        run_hooks: bool,
        live: bool = False,
    ) -> list[AdmissionResponse | Exception]:
        """The latency fast-path: per-item semantics identical to the device
        path (lookup, hooks, wasm routing, context snapshot), but IR
        verdicts come from the targeted host oracle — no encode, no
        transfer, no device round-trip. The reference's per-request sync
        path (src/api/handlers.rs:256-286) answers one request in ~1 ms on
        CPU; this is the build's equivalent for batches too small to
        amortize the device dispatch.

        One answer, one source: a request a dedup tier answers here is
        counted by that tier's own hit counter (``VerdictCache.get``) and
        by nothing else; ``host_fastpath_requests`` counts the requests
        whose row the targeted oracle produced. So rows dispatched + host
        fast path + row tier + blob tier + in-batch duplicates = answers
        with every routing flag at its default, as with the fast path
        off. The breaker's short circuit enters here too and counts the
        same way. The whole item loop is the ``host_eval`` ring phase:
        one clock pair a batch. A ``live`` batch's requests go straight
        to the row tier (validate_batch)."""
        results: list[AdmissionResponse | Exception | None] = [None] * len(items)
        n_host = n_bypassed = 0
        rec = flightrec.recorder()
        t0 = time.perf_counter_ns() if rec is not None else 0
        for i, (policy_id, request) in enumerate(items):
            try:
                target = self._fast_target(policy_id)
                payload = self.payload_for(target, request)
                if run_hooks and self._hooks_of(target):
                    self._run_pre_eval_hooks(target, payload)
                    payload = self.payload_for(target, request)
                if self._host_executed(target):
                    results[i] = self._materialize_single(
                        target, request.uid(), payload, {}
                    )
                    continue
                # the dedup tiers serve the fast-path too: executors are
                # bit-exact by the differential guarantee, and the serving
                # layer already mixes host/device answers per batch size
                row = learn = None
                if self._tiers is not None and self._cacheable(target):
                    row, learn = self._tiers.get_one(
                        self._cache_key_of(target),
                        self._blob_of(target, request, payload),
                        self._packed_row_of, live,
                    )
                    n_bypassed += live
                from_oracle = row is None
                if from_oracle:
                    row = self._oracle_outputs_for(target, payload)
                    if learn is not None:
                        self._tiers.put_one(learn, row)
                results[i] = self._materialize(target, request, row)
                n_host += from_oracle
            except Exception as e:  # noqa: BLE001 — per-item error channel
                results[i] = e
        if n_host:
            with self._fallback_lock:
                self._host_fastpath_requests += n_host
        if n_bypassed:
            self._tiers.count_blob_bypassed(n_bypassed)
        if rec is not None:
            rec.record_phase(
                flightrec.PH_HOST_EVAL, t0, time.perf_counter_ns(),
                rows=len(items), batch=flightrec.current_batch(),
            )
        return results  # type: ignore[return-value]

    def _validate_batch_native(
        self,
        items: list[tuple[str, ValidateRequest]],
        run_hooks: bool,
        defer_sink: list | None = None,
        audit: bool = False,
        live: bool = False,
    ) -> list[AdmissionResponse | Exception]:
        """The native fast path: JSON bytes → batch arrays in one C++ call
        per shape bucket, rows written in place (no per-request arrays, no
        re-stack). Rows that overflow a bucket cascade to the next; rows
        failing the widest bucket fall back to the host oracle.

        The payload blob is built once per item up front (the encoder's
        input), and, unless the batch is ``live``, the blob tier
        (verdict_cache.py) answers exact payload replays before any
        encoding happens. ``defer_sink``: see validate_batch_begin."""
        results: list[AdmissionResponse | Exception | None] = [None] * len(items)
        targets: list[Any] = [None] * len(items)
        blobs: list[bytes | None] = [None] * len(items)
        pending: list[int] = []
        wasm_infos: dict[int, dict] = {}
        # flight recorder: the target-resolution + payload-blob loop is
        # its own phase — round 18's first phase-report run measured it
        # as ~90 µs/row of UNATTRIBUTED dispatch time on the all-cache-
        # hit serving shape (exactly the guesswork the recorder exists
        # to retire)
        _rec = flightrec.recorder()
        _t_prep = time.perf_counter_ns() if _rec is not None else 0
        for i, (policy_id, request) in enumerate(items):
            try:
                target = self._fast_target(policy_id)
                targets[i] = target
                if run_hooks and self._hooks_of(target):
                    # payload_for, not payload(): hooks must observe the
                    # same (context-snapshotted) input on every path.
                    # Payload building is skipped entirely when the
                    # target has no hooks (the common case — it showed
                    # in the round-6 per-row profile).
                    self._run_pre_eval_hooks(
                        target, self.payload_for(target, request)
                    )
                if self._host_executed(target):
                    # wasm-backed rows never enter the device batch; the
                    # payload carries the __context__ snapshot like every
                    # other path
                    results[i] = self._materialize_single(
                        target,
                        request.uid(),
                        self.payload_for(target, request),
                        {},
                    )
                    continue
                if (
                    isinstance(target, BoundGroup)
                    and target.name in self._groups_with_wasm
                ):
                    # groups with wasm members: run the wasm engine NOW
                    # (host side), bits join the device batch below; the
                    # payload parse is paid only for these rows
                    wasm_infos[i] = self._eval_wasm_members(
                        target, self.payload_for(target, request)
                    )
                blobs[i] = self._payload_blob(target, request)
                pending.append(i)
            except Exception as e:  # noqa: BLE001 — per-item error channel
                results[i] = e
        if _rec is not None:
            _rec.record_phase(
                flightrec.PH_PREPARE, _t_prep, time.perf_counter_ns(),
                rows=len(items), batch=flightrec.current_batch(),
            )

        # the blob tier: exact payload replays are answered here and never
        # reach the encoder (wasm-involving targets are uncacheable). A
        # live batch skips it: a live review's uid, so its blob, is new
        # on every webhook call (verdict_cache.py module docstring)
        if self._tiers is not None and pending and not live:
            t0 = time.perf_counter_ns()
            rows = self._tiers.get_blobs(
                (
                    self._cache_key_of(targets[i])
                    if self._cacheable(targets[i])
                    else None
                    for i in pending
                ),
                (blobs[i] for i in pending),
                audit,
            )
            self._answer_hits(
                items, targets, results,
                ((i, row) for i, row in zip(pending, rows) if row is not None),
            )
            t1 = time.perf_counter_ns()
            self._profile_add(
                bookkeeping_ns=t1 - t0,
                bookkeeping_rows=len(pending),
            )
            if _rec is not None:
                _rec.record_phase(
                    flightrec.PH_BLOB_DEDUP, t0, t1,
                    rows=len(pending), batch=flightrec.current_batch(),
                )
            pending = [i for i, row in zip(pending, rows) if row is None]

        for schema in self.schemas:
            if not pending:
                break
            pending = self._native_schema_pass(
                schema, items, targets, results, pending, wasm_infos,
                blobs, defer_sink, audit, live,
            )

        for i in pending:  # beyond the widest schema → oracle
            with self._fallback_lock:
                self._oracle_fallbacks += 1
            policy_id, request = items[i]
            results[i] = self._materialize(
                targets[i], request,
                self._oracle_outputs(
                    self.payload_for(targets[i], request), targets[i]
                ),
            )
        return results  # type: ignore[return-value]

    # -- split host/device halves (runtime/batcher.py double-buffering) ----

    def validate_batch_begin(
        self,
        items: list[tuple[str, ValidateRequest]],
        run_hooks: bool = True,
        live: bool = False,
    ) -> tuple | None:
        """Host half of the native batch pipeline: lookup, hooks, blob
        dedup (not for a ``live`` batch: validate_batch), native encode,
        row dedup, and the ASYNC device dispatch — everything except
        blocking on device results. Returns an opaque handle for
        validate_batch_finish, or None when the native pipeline is
        unavailable (caller falls back to validate_batch).

        The split exists so the micro-batcher can double-buffer: batch
        N+1's host encode (this call, on an encode worker) overlaps batch
        N's device execution (whose finish blocks in device_get on a
        device worker). Device fetches are already in flight when this
        returns — the drain futures were submitted here."""
        if self._closed:
            raise RuntimeError("environment closed")
        if self.backend != "jax":
            return None
        if self.breaker is not None and not self.breaker.allow_device():
            # tripped: decline the split pipeline — the caller falls back
            # to validate_batch, which routes host-side
            return None
        deferred: list = []
        results = self._validate_batch_native(
            items, run_hooks, defer_sink=deferred, live=live
        )
        return (results, deferred)

    def validate_batch_finish(
        self, handle: tuple
    ) -> list[AdmissionResponse | Exception]:
        """Device half: block on each chunk's device fetch and materialize
        responses. Watchdog-safe — all blocking happens here."""
        results, deferred = handle
        for land in deferred:
            land()
        return results  # type: ignore[return-value]

    # Largest single device dispatch; bigger lists pipeline in chunks so
    # host encode of chunk N+1 overlaps device transfer+compute of chunk N.
    max_dispatch_batch = 1024
    # In-flight dispatch window: bounds device/host memory for huge lists
    # while keeping enough dispatches outstanding to hide the transport's
    # per-fetch sync latency.
    max_inflight_dispatches = 32

    def _native_schema_pass(
        self,
        schema: FeatureSchema,
        items: list[tuple[str, ValidateRequest]],
        targets: list[Any],
        results: list[AdmissionResponse | Exception | None],
        pending: list[int],
        wasm_infos: dict[int, dict],
        blobs: list[bytes | None],
        defer_sink: list | None,
        audit: bool = False,
        live: bool = False,
    ) -> list[int]:
        """Encode+dispatch all ``pending`` rows against one schema, a
        chunk at a time in four steps: encode (_encode_chunk) → plan, who
        answers each row (_plan_chunk; the dedup tiers of verdict_cache.py
        sit here, between encode and dispatch) → launch (_launch_chunk) →
        fetch, learn, materialize (_land_chunk). Returns the rows that
        overflowed this schema.

        Pipeline shape: the dispatch thread only encodes (one C call
        with the GIL released; Python resolves only strings the encoder's
        mirror of the intern table has not seen, fastenc.py) and enqueues
        device executions; every result fetch runs on the drain pool, so
        its sync latency overlaps other fetches and device work. With
        ``defer_sink`` set, the last step is appended instead of run, so
        validate_batch_finish can block on device results on a different
        thread than the one encoding the next batch (double-buffering)."""
        chunk_size = min(self.bucket_for(len(pending)), self.max_dispatch_batch)
        chunks = [
            pending[c : c + chunk_size]
            for c in range(0, len(pending), chunk_size)
        ]
        # flight recorder (round 18): the ambient batch id rides the
        # encode-thread's scope (batcher._scoped_rec); the steps are handed
        # it so drain/device-pool events attribute to the submitting batch
        _rec = flightrec.recorder()
        _bid = flightrec.current_batch() if _rec is not None else -1
        overflowed: list[int] = []
        drains: list[Callable] = []  # launched chunks' _land_chunk
        # encode ahead on the pool (bounded window), dispatch in order.
        # A SINGLE chunk — every serving batch up to max_dispatch_batch —
        # encodes inline instead (round 19): with nothing to overlap, the
        # pool submit + future-wake per chunk was pure handoff cost.
        single = len(chunks) == 1
        encode_futs: dict[int, Any] = {}
        drained = 0
        for ci, chunk in enumerate(chunks):
            if not single:
                for cj in range(ci, min(ci + 4, len(chunks))):
                    if cj not in encode_futs:
                        encode_futs[cj] = self._encode_pool.submit(
                            self._encode_chunk, schema, chunks[cj], blobs,
                            _rec, _bid,
                        )
            try:
                chunk_blobs, (features, status), native = (
                    self._encode_chunk(schema, chunk, blobs, _rec, _bid)
                    if single
                    else encode_futs.pop(ci).result()
                )
            except ValueError:
                # arena/records overflow on a pathological chunk: keep
                # per-item isolation — route the whole chunk to the next
                # schema / the oracle instead of failing the batch
                overflowed.extend(chunk)
                continue
            ok_mask = np.asarray(status)[: len(chunk)] == 0
            if not ok_mask.all():
                overflowed.extend(
                    chunk[p] for p in np.flatnonzero(~ok_mask).tolist()
                )
            plan = self._plan_chunk(
                items, targets, results, chunk, features[PACKED_KEY],
                ok_mask, wasm_infos, chunk_blobs, _rec, _bid, live, audit,
            )
            if not plan.slot_rows:
                continue  # all overflowed, or answered by the tiers
            fetch, stash = self._launch_chunk(
                features, native, plan, chunk, wasm_infos, single, _rec,
                _bid, audit,
            )
            land = functools.partial(
                self._land_chunk, fetch, plan, stash, chunk, items,
                targets, results, _rec, _bid,
            )
            if defer_sink is not None:
                defer_sink.append(land)
                continue
            drains.append(land)
            if len(drains) - drained >= self.max_inflight_dispatches:
                drains[drained]()
                drained += 1
        for land in drains[drained:]:
            land()
        return overflowed

    def _wire_form_for(self, schema: FeatureSchema) -> _WireForm | None:
        """The wire form a chunk of this schema should expect to launch
        in — the one its column set has settled on — or None where the
        launch builds its own wire whatever the encode call does: before
        any batch taught the set a column, and off the columnar path."""
        if not (self.columnar and self._columnar_mesh_ok()):
            return None
        schema_idx = self.schemas.index(schema)
        key = (schema_idx, self._narrow(schema_idx))
        with self._profile_lock:
            settled = self._plane_columns.get(key)
            return settled.form if settled and settled.version else None

    def _encode_chunk(
        self,
        schema: FeatureSchema,
        chunk: list[int],
        blobs: list[bytes | None],
        rec: Any,
        bid: int,
    ) -> tuple[
        list, tuple[dict[str, np.ndarray], np.ndarray], _NativeWire | None
    ]:
        """Step 1: the chunk's blobs, and their rows and per-row status
        out of ONE native call — which, told the schema's settled wire
        form, also writes the launch's host half (the liveness words and
        the wire buffer, _NativeWire) while it has the rows and not the
        GIL; None when it was not asked or met a string its mirror had
        not seen."""
        failpoints.fire("encode.batch")
        form = self._wire_form_for(schema)
        # the CPU clock is read inside the wall clock's interval, so
        # encode_cpu_ns never exceeds encode_ns; the difference is
        # time this thread was off a core (GIL wait, descheduled)
        t0 = time.perf_counter_ns()
        c0 = time.thread_time_ns()
        bl = [blobs[i] for i in chunk]
        features, status, python_strings = schema.native.encode_batch(
            bl, self.bucket_for(len(bl)), self.table,
            None if form is None else form.gather,
        )
        c1 = time.thread_time_ns()
        t1 = time.perf_counter_ns()
        self._profile_add(
            encode_ns=t1 - t0, encode_cpu_ns=c1 - c0,
            encode_rows=len(chunk), encode_python_strings=python_strings,
        )
        if rec is not None:
            rec.record_phase(
                flightrec.PH_ENCODE, t0, t1, rows=len(chunk), batch=bid,
            )
        live = features.pop(fastenc.LIVE_KEY, None)
        native = None if live is None else _NativeWire(
            form, live, features.pop(fastenc.WIRE_KEY)
        )
        return bl, (features, status), native

    def _plan_chunk(
        self,
        items: list[tuple[str, ValidateRequest]],
        targets: list[Any],
        results: list[AdmissionResponse | Exception | None],
        chunk: list[int],
        packed: np.ndarray,
        ok_mask: np.ndarray,
        wasm_infos: dict[int, dict],
        chunk_blobs: list,
        rec: Any,
        bid: int,
        live: bool = False,
        audit: bool = False,
    ) -> DedupTiers.Plan:
        """Step 2: who answers each row of an encoded chunk — with the
        tiers on, their plan (row identity, the locked lookup, slots and
        keys laid out; a ``live`` chunk's off the blob tier), its hits
        answered here; else every row its own slot. An ``audit`` chunk's
        plan is the ring phase ``audit_bookkeeping``, so ``bookkeeping``
        stays the live chunks' alone."""
        tiers = self._tiers
        if tiers is None:
            return DedupTiers.passthrough(ok_mask)
        t0 = time.perf_counter_ns()
        # wasm verdict bits ride beside the row — not a pure function of
        # the row bytes, never deduped or cached
        wasm_pos = [
            pos
            for pos, i in enumerate(chunk)
            if i in wasm_infos and ok_mask[pos]
        ] if wasm_infos else []
        # a small-int id per distinct target, by first sight
        distinct = {id(targets[i]): targets[i] for i in chunk}
        tid_of = {key: tid for tid, key in enumerate(distinct)}
        tids = np.fromiter(
            (tid_of[id(targets[i])] for i in chunk),
            dtype=np.intp, count=len(chunk),
        )
        tkeys = [self._cache_key_of(t) for t in distinct.values()]
        plan = tiers.plan(
            packed, ok_mask, wasm_pos, tids, tkeys, chunk_blobs, live, audit
        )
        self._answer_hits(
            items, targets, results,
            ((chunk[pos], row) for pos, row in plan.hits),
        )
        # bookkeeping_rows must mean ROWS, not stage-passes, or the
        # µs/row denominator doubles: the blob-tier pre-pass counted a
        # replay's rows already; a live batch has no pre-pass, so its
        # rows are counted here, where this schema holds them (a row the
        # ok mask refuses is counted by the schema that takes it)
        t1 = time.perf_counter_ns()
        self._profile_add(
            bookkeeping_ns=t1 - t0,
            bookkeeping_rows=int(ok_mask.sum()) if live else 0,
        )
        if rec is not None and audit:
            rec.record_phase(
                flightrec.PH_AUDIT_BOOKKEEPING, t0, t1, rows=len(chunk),
                batch=bid,
            )
        elif rec is not None:
            rec.record_phase(
                flightrec.PH_BOOKKEEPING, t0, t1, rows=len(chunk), batch=bid,
            )
        return plan

    def _launch_chunk(
        self,
        features: dict[str, np.ndarray],
        native: _NativeWire | None,
        plan: DedupTiers.Plan,
        chunk: list[int],
        wasm_infos: dict[int, dict],
        single: bool,
        rec: Any,
        bid: int,
        audit: bool = False,
    ) -> tuple[Any, dict[str, list]]:
        """Step 3: ship the plan's rows (async). Returns the fetch of the
        result — run inline by whoever asks for a single chunk's, on the
        drain pool otherwise — and the wasm stash."""
        batch = features[PACKED_KEY].shape[0]
        if plan.ship_pos is not None:
            # compact: only the rows a request waits for cross the wire.
            # Where the encode call wrote the wire, the launch takes them
            # out of that; else a copy of the wide batch here (kept out
            # of the bookkeeping span)
            batch = self.bucket_for(plan.n_rows)
            if native is None:
                features = {PACKED_KEY: _compacted(
                    features[PACKED_KEY], plan.ship_pos, batch
                )}
            else:
                native.compact(features[PACKED_KEY], plan.ship_pos, batch)
        stash = self._add_wasm_bits(
            features, batch,
            [
                (slot, wasm_infos[chunk[pos]])
                for slot, pos in plan.slot_rows
                if chunk[pos] in wasm_infos
            ] if wasm_infos else None,
        )
        t_launch = time.perf_counter_ns() if rec is not None else 0
        dev_out = self._dispatch_features(
            features, rows=plan.n_rows, native=native
        )
        if rec is not None:
            # plane selection, the jit call's host-to-device copies
            # and the enqueue: on the chip, milliseconds a batch
            # between encode's end and the program's start, and with
            # encode most of the device's idle time (PERF.md, PR 27)
            rec.record_phase(
                flightrec.PH_LAUNCH, t_launch, time.perf_counter_ns(),
                rows=plan.n_rows, batch=bid,
            )
        # one answer, one source: dispatched_rows counts the requests the
        # device answered, and an audit row answers nobody
        self._profile_add(
            **{"audit_rows" if audit else "dispatched_rows": plan.n_rows},
            dispatched_chunks=1,
        )
        fetch = (_InlineFetch if single else self._drain_pool.submit)(
            self._scoped_device_fetch, failpoints.current_scope(), dev_out,
            bid, plan.n_rows,
        )
        return fetch, stash

    def _land_chunk(
        self,
        fetch: Any,
        plan: DedupTiers.Plan,
        stash: dict[str, list],
        chunk: list[int],
        items: list[tuple[str, ValidateRequest]],
        targets: list[Any],
        results: list[AdmissionResponse | Exception | None],
        rec: Any,
        bid: int,
    ) -> None:
        """Step 4: block on a launched chunk's fetch, teach the tiers its
        rows, answer the requests that rode it. A slot lands as its bytes
        (one copy of the batch, a slice a slot) and is answered as a
        tier's hit is (_answer_rows); only a chunk with a wasm stash,
        whose bits ride beside the rows and are no function of their
        bytes, is read through the columns of the whole batch."""
        t0 = time.perf_counter_ns()
        raw = np.asarray(fetch.result())
        t1 = time.perf_counter_ns()
        rows = row_bytes(raw)
        if self._tiers is not None:
            self._tiers.learn(plan, rows)
        n_frag = 0
        if stash:
            outputs = self._unpack(raw)
            outputs.update(stash)
            for slot, pos in plan.slot_rows:
                i = chunk[pos]
                results[i] = self._materialize(
                    targets[i], items[i][1], _RowView(outputs, slot)
                )
        else:
            n_frag = self._answer_rows(
                items, targets, results,
                ((chunk[pos], rows[slot]) for slot, pos in plan.slot_rows),
            )
        self._profile_add(
            dispatch_wait_ns=t1 - t0, device_fragment_rows=n_frag
        )
        if rec is not None:
            t2 = time.perf_counter_ns()
            rec.record_phase(
                flightrec.PH_FETCH, t0, t1, rows=len(plan.slot_rows),
                batch=bid,
            )
            rec.record_phase(
                flightrec.PH_MATERIALIZE, t1, t2,
                rows=len(plan.slot_rows), batch=bid,
            )

    # -- response materialization (host side) ------------------------------

    def _materialize(
        self,
        target: BoundPolicy | BoundGroup,
        request: ValidateRequest,
        outputs: "bytes | Mapping[str, Any]",
    ) -> AdmissionResponse:
        """``outputs``: an output row in either of its forms
        (verdict_cache.py), the device's bytes or the host oracle's dict,
        or the view of a dispatched row beside a wasm stash."""
        uid = request.uid()
        outputs = self._row_face(outputs)
        # payload materializes LAZILY: most verdicts (allowed, or rejected
        # with a static message) never need the parsed document, and for
        # wire requests from the prefork frontend payload() costs a JSON
        # parse the hot path should skip
        if isinstance(target, BoundGroup):
            return self._materialize_group(target, uid, request.payload, outputs)
        return self._materialize_single(target, uid, request.payload, outputs)

    def _materialize_single(
        self,
        bp: BoundPolicy,
        uid: str,
        payload_fn: Any,  # zero-arg callable OR a pre-built payload value
        outputs: Mapping[str, Any],
    ) -> AdmissionResponse:
        payload_of = payload_fn if callable(payload_fn) else (lambda: payload_fn)
        host_eval = bp.precompiled.program.host_evaluator
        if host_eval is not None:
            # wasm-backed policy: the verdict comes from host-side wasm
            # execution (evaluation/wasm_policy.py); device outputs are
            # inert for these rows
            verdict = host_eval(payload_of())
            if bool(verdict.get("accepted")):
                response = AdmissionResponse(uid=uid, allowed=True)
                mutated = verdict.get("mutated_object")
                if mutated is not None:
                    # whole-object replacement patch (waPC mutation shape)
                    response.patch = base64.b64encode(
                        json.dumps(
                            [{"op": "replace", "path": "", "value": mutated}]
                        ).encode()
                    ).decode()
                    response.patch_type = JSON_PATCH
                return response
            return AdmissionResponse(
                uid=uid,
                allowed=False,
                status=ValidationStatus(
                    message=str(
                        verdict.get("message") or "rejected by policy"
                    ),
                    code=int(verdict.get("code") or 400),
                ),
            )
        mat = self._single_mat.get(bp.policy_id)
        allowed_key, rule_key = mat if mat is not None else (
            f"p:{bp.policy_id}:allowed", f"p:{bp.policy_id}:rule"
        )
        allowed = bool(outputs[allowed_key])
        if not allowed:
            rule_idx = int(outputs[rule_key])
            rule = bp.precompiled.program.rules[rule_idx]
            message = (
                rule.message
                if isinstance(rule.message, str)
                else rule.message(payload_of())
            )
            return AdmissionResponse(
                uid=uid,
                allowed=False,
                status=ValidationStatus(message=message, code=400),
            )
        response = AdmissionResponse(uid=uid, allowed=True)
        mutator = bp.precompiled.program.mutator
        if mutator is not None:
            ops = mutator(payload_of())
            if ops:
                response.patch = base64.b64encode(
                    json.dumps(ops).encode()
                ).decode()
                response.patch_type = JSON_PATCH
        return response

    def _materialize_group(
        self,
        group: BoundGroup,
        uid: str,
        payload_fn: Any,  # zero-arg callable OR a pre-built payload value
        outputs: Mapping[str, Any],
    ) -> AdmissionResponse:
        payload_of = payload_fn if callable(payload_fn) else (lambda: payload_fn)
        # pre-built key strings + the risky-member subset (_group_mat):
        # per-row f-string construction and the full member scan showed
        # at ~7 µs/row in the round-6 profile
        allowed_key, members, risky = self._group_mat[group.name]
        allowed = bool(outputs[allowed_key])
        # group-member mutation ban (reference integration_test.rs:239-251):
        # an evaluated member that *would* mutate rejects the whole group.
        # Wasm members report would-mutate from their host verdict
        # (wm:<pid>:mutated, stashed at encode time). Only members that
        # CAN mutate (a mutator or a wasm evaluator) are scanned.
        for (
            _m, _bp, eval_key, allowed_key_m, _rule_key,
            wm_mut_key, _wm_msg_key, is_wasm, mutator,
        ) in risky:
            evaluated = bool(outputs.get(eval_key, False))
            member_allowed = bool(outputs[allowed_key_m])
            if not (evaluated and member_allowed):
                continue
            if is_wasm:
                would_mutate = bool(outputs.get(wm_mut_key, False))
            else:
                would_mutate = mutator is not None and bool(
                    mutator(payload_of())
                )
            if would_mutate:
                return AdmissionResponse(
                    uid=uid,
                    allowed=False,
                    status=ValidationStatus(
                        message=GROUP_MUTATION_MESSAGE, code=500
                    ),
                )
        if allowed:
            return AdmissionResponse(uid=uid, allowed=True)
        causes: list[StatusCause] = []
        for (
            member_name, bp, eval_key, allowed_key_m, rule_key,
            _wm_mut_key, wm_msg_key, is_wasm, _mutator,
        ) in members:
            evaluated = bool(outputs.get(eval_key, False))
            member_allowed = bool(outputs[allowed_key_m])
            if evaluated and not member_allowed:
                if is_wasm:
                    message = (
                        outputs.get(wm_msg_key) or "rejected by policy"
                    )
                else:
                    rule_idx = int(outputs[rule_key])
                    rule = bp.precompiled.program.rules[rule_idx]
                    message = (
                        rule.message
                        if isinstance(rule.message, str)
                        else rule.message(payload_of())
                    )
                causes.append(
                    StatusCause(
                        field=f"spec.policies.{member_name}", message=message
                    )
                )
        return AdmissionResponse(
            uid=uid,
            allowed=False,
            status=ValidationStatus(
                message=group.message,
                code=400,
                details=StatusDetails(causes=tuple(causes)),
            ),
        )
