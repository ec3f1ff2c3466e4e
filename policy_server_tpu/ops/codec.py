"""Feature-tensor codec: AdmissionReview JSON → fixed-shape feature arrays.

TPU-first design (SURVEY.md §7.4 hard-part #1): instead of flattening
*arbitrary* JSON, the schema is **policy-derived** — the union of JSON paths
referenced by the loaded policies' IR defines exactly which feature columns
exist. Shapes are static for a given policy set:

* scalar path            → value ``(B,)``   + validity mask ``(B,)``
* path with one ``*``    → value ``(B, N)`` + mask ``(B, N)``
* path with two ``*``    → value ``(B, N1, N2)`` + mask

Array axes are padded/capped at schema-build time (power-of-two caps).
A request whose arrays exceed a cap **overflows**: it is routed to the host
oracle backend and counted, never silently truncated (SURVEY.md §7.4 escape
hatch). Strings are interned host-side; string predicates are precomputed
bits (see utils/interning.py). Missing/null/type-mismatched leaves are
encoded as mask=0.

Feature keys:
* ``{path}:v:{dtype}`` / ``{path}:m:{dtype}`` — value + dtype-valid mask
* ``{path}:p``                               — JSON presence (Exists,
  quantifier domain masks)
* ``{path}:sp:{predkey}``                    — precomputed string-pred bit

There is no reference counterpart — the reference hands raw JSON to WASM.
This codec is what turns the admission stream into MXU/VPU-friendly batches.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Iterable, Mapping

import numpy as np

from policy_server_tpu.ops import ir
from policy_server_tpu.ops.ir import (
    DType,
    Expr,
    Path,
    STAR,
    StrPred,
)
from policy_server_tpu.utils.interning import MISSING_ID, InternTable

DEFAULT_AXIS_CAP = 64
DEFAULT_NESTED_AXIS_CAP = 32

# Cluster-state snapshot paths (__context__.<apiVersion/Kind>[*]...) carry
# whole resource collections, not per-request arrays — they get their own,
# larger element-axis caps in every shape bucket.
CONTEXT_PREFIX = "__context__"
CONTEXT_AXIS_CAP = 256
CONTEXT_NESTED_AXIS_CAP = 32

# Reserved feature carrying only the batch dimension — lets constant-only
# programs (e.g. the always-happy fixture) produce (B,)-shaped outputs.
BATCH_KEY = "__batch__"

# Packed-batch feature key: the WHOLE feature set rides in ONE contiguous
# (B, width) uint8 buffer — 1-byte columns first (bools, presence, preds,
# masks, BATCH_KEY at column 0), then a 4-byte-aligned region of int32
# columns (id/i32; f32 bit-stored). Host→device traffic is then ONE
# transfer per dispatch regardless of schema width (round 1 shipped ~93
# per-key arrays, one transfer each); outputs are packed into one array
# for the same reason.
PACKED_KEY = "__packed__"

_NP_DTYPES = {
    DType.ID: np.int32,
    DType.F32: np.float32,
    DType.BOOL: np.bool_,
    DType.I32: np.int32,
}


@dataclass(frozen=True)
class FeatureSpec:
    key: str
    segments: tuple[str, ...]
    kind: str  # "value" | "present" | "pred"
    dtype: DType | None
    pred_kind: str | None
    pred_pattern: str | None
    caps: tuple[int, ...]
    # validity-mask elision (ops/optimizer.py round 15): False when every
    # use of this value column is provably False at the zero-fill, so the
    # ':m:' mask column is redundant and never materializes — not in the
    # encoder output, not in the packed layout, not on the wire
    masked: bool = True

    @property
    def has_mask(self) -> bool:
        return self.kind == "value" and self.masked

    @property
    def n_axes(self) -> int:
        return len(self.caps)

    def shape(self, batch: int) -> tuple[int, ...]:
        return (batch, *self.caps)

    def np_dtype(self) -> Any:
        if self.kind == "value":
            assert self.dtype is not None
            return _NP_DTYPES[self.dtype]
        return np.bool_

    def pred_key(self) -> str:
        return f"{self.pred_kind}:{self.pred_pattern}"


def _pow2_cap(n: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(1, n))))


class SchemaOverflow(Exception):
    """A request exceeded a schema axis cap — route to the oracle backend."""

    def __init__(self, key: str, axis: int, length: int, cap: int):
        super().__init__(
            f"feature {key!r} axis {axis} length {length} exceeds cap {cap}"
        )
        self.key = key


class FeatureSchema:
    """The static feature layout for a fixed policy set."""

    def __init__(self, specs: dict[str, FeatureSpec]):
        self.specs = specs

    @classmethod
    def build(
        cls,
        exprs: Iterable[Expr],
        axis_cap: int = DEFAULT_AXIS_CAP,
        nested_axis_cap: int = DEFAULT_NESTED_AXIS_CAP,
        unmasked: "frozenset[str] | set[str] | None" = None,
    ) -> "FeatureSchema":
        """``unmasked``: value-spec keys whose validity mask is provably
        redundant (ops/optimizer.py zero-fill analysis) — their ':m:'
        columns are never created."""
        specs: dict[str, FeatureSpec] = {}
        unmasked = unmasked or frozenset()

        def caps_for(segs: tuple[str, ...]) -> tuple[int, ...]:
            n = sum(1 for s in segs if s == STAR)
            if n == 0:
                return ()
            a, na = axis_cap, nested_axis_cap
            if segs and segs[0] == CONTEXT_PREFIX:
                a, na = CONTEXT_AXIS_CAP, CONTEXT_NESTED_AXIS_CAP
            if n == 1:
                return (_pow2_cap(a),)
            return (_pow2_cap(a), _pow2_cap(na))

        def add(spec: FeatureSpec) -> None:
            specs.setdefault(spec.key, spec)

        def add_value(p: Path) -> None:
            base = p.key()
            caps = caps_for(p.segments)
            key = f"{base}:v:{p.dtype.value}"
            add(FeatureSpec(key, p.segments, "value", p.dtype, None, None,
                            caps, masked=key not in unmasked))

        def add_present(segments: tuple[str, ...]) -> None:
            key = ir.render_key(segments) + ":p"
            add(FeatureSpec(key, segments, "present", None, None, None,
                            caps_for(segments)))

        def add_pred(p: Path, sp: StrPred) -> None:
            base = p.key()
            add(FeatureSpec(f"{base}:sp:{sp.key()}", p.segments, "pred", None,
                            sp.kind, sp.pattern, caps_for(p.segments)))

        def visit(e: Expr, stack: ir.DomainStack) -> None:
            if isinstance(e, (Path, ir.Elem)):
                # bare leaf used as a value
                add_value(ir.absolute_path(e, stack))
            elif isinstance(e, ir.Exists):
                add_present(ir.absolute_path(e.target, stack).segments)
            elif isinstance(e, ir.Not):
                visit(e.operand, stack)
            elif isinstance(e, (ir.And, ir.Or)):
                for op in e.operands:
                    visit(op, stack)
            elif isinstance(e, ir.Cmp):
                visit(e.lhs, stack)
                visit(e.rhs, stack)
            elif isinstance(e, ir.InSet):
                visit(e.operand, stack)
            elif isinstance(e, StrPred):
                add_pred(ir.absolute_path(e.operand, stack), e)
            elif isinstance(e, (ir.AnyOf, ir.AllOf, ir.CountOf)):
                domain = ir.absolute_path(e.over, stack)
                add_present(domain.segments)  # domain mask
                visit(e.pred, stack + (domain,))
            elif isinstance(e, ir.Const):
                pass
            else:
                raise ir.IRError(f"unknown IR node {type(e).__name__}")

        for expr in exprs:
            visit(expr, ())
        return cls(specs)

    # -- encoding ----------------------------------------------------------

    def register_preds(self, table: InternTable) -> None:
        for spec in self.specs.values():
            if spec.kind == "pred":
                table.register_pred(
                    spec.pred_key(), ir.build_str_pred(spec.pred_kind, spec.pred_pattern)
                )

    def _trie(self) -> "_TrieNode":
        """Lazily-built single-pass extraction trie over all specs: the
        payload tree is walked ONCE per request instead of once per spec
        (the host encode path is serving-throughput critical)."""
        trie = getattr(self, "_trie_cache", None)
        if trie is None:
            trie = _build_trie(self.specs.values())
            self._trie_cache = trie
        return trie

    def encode(
        self, payload: Any, table: InternTable
    ) -> dict[str, np.ndarray]:
        """Encode one request payload → unbatched feature arrays (no leading
        batch dim). Raises SchemaOverflow when an array exceeds its cap."""
        out: dict[str, np.ndarray] = {BATCH_KEY: np.zeros((), dtype=np.bool_)}
        for spec in self.specs.values():
            out[spec.key] = np.zeros(spec.caps, dtype=spec.np_dtype())
            if spec.has_mask:
                out[_mask_key(spec.key)] = np.zeros(spec.caps, dtype=np.bool_)
        _walk_trie(self._trie(), payload, (), out, table)
        return out

    def stack(self, encoded: list[dict[str, np.ndarray]], batch_size: int) -> dict[str, np.ndarray]:
        """Stack per-request encodings into batch arrays padded to
        ``batch_size`` (pad rows are all-missing; batch bucketing bounds XLA
        recompilation, SURVEY.md §7.4)."""
        assert encoded and len(encoded) <= batch_size
        out: dict[str, np.ndarray] = {BATCH_KEY: np.zeros(batch_size, dtype=np.bool_)}
        for spec in self.specs.values():
            keys = (
                [spec.key, _mask_key(spec.key)]
                if spec.has_mask
                else [spec.key]
            )
            for key in keys:
                first = encoded[0][key]
                arr = np.zeros((batch_size, *first.shape), dtype=first.dtype)
                for i, enc in enumerate(encoded):
                    arr[i] = enc[key]
                out[key] = arr
        return out

    def empty_batch(self, batch_size: int) -> dict[str, np.ndarray]:
        """An all-missing batch (for warmup/AOT compilation at boot,
        SURVEY.md §7.2 step 6)."""
        out: dict[str, np.ndarray] = {BATCH_KEY: np.zeros(batch_size, dtype=np.bool_)}
        for spec in self.specs.values():
            out[spec.key] = np.zeros(spec.shape(batch_size), dtype=spec.np_dtype())
            if spec.has_mask:
                out[_mask_key(spec.key)] = np.zeros(
                    spec.shape(batch_size), dtype=np.bool_
                )
        return out

    # -- packed batch layout ----------------------------------------------

    def packed_layout(self) -> "PackedLayout":
        layout = getattr(self, "_packed_layout_cache", None)
        if layout is None:
            layout = self._packed_layout_cache = PackedLayout.build(self)
        return layout

    def install_packed_layout(self, layout: "PackedLayout") -> None:
        """Pin a (widened) layout for this schema — see
        :func:`ensure_unique_packed_widths`. Must run before any encode or
        native attach captures the row stride."""
        self._packed_layout_cache = layout

    def empty_batch_packed(self, batch_size: int) -> dict[str, np.ndarray]:
        layout = self.packed_layout()
        return {PACKED_KEY: np.zeros((batch_size, layout.width), np.uint8)}

    def packed_views(
        self, packed: Mapping[str, np.ndarray]
    ) -> dict[str, np.ndarray]:
        """Per-key views INTO the packed buffer (zero-copy; the native
        encoder writes through these). 1-byte entries are uint8 column
        blocks; 4-byte entries are int32/float32 views of the aligned
        tail region. Views are 2-D (batch, elems) — reshaping to caps
        would copy (non-contiguous); flat indexing matches caps order."""
        layout = self.packed_layout()
        buf = packed[PACKED_KEY]
        batch = buf.shape[0]
        region32 = buf[:, layout.off32_bytes :].view(np.int32)
        out: dict[str, np.ndarray] = {}
        for e in layout.entries8:
            out[e.key] = buf[:, e.offset : e.offset + e.elems]
        for e in layout.entries32:
            block = region32[:, e.offset : e.offset + e.elems]
            out[e.key] = block.view(np.float32) if e.is_f32 else block
        return out

    def unpack_host(
        self, packed: Mapping[str, np.ndarray]
    ) -> dict[str, np.ndarray]:
        """Packed buffer → per-key batch arrays shaped (batch, *caps)
        (host-side mirror of the device unpack; tests/debugging)."""
        layout = self.packed_layout()
        batch = packed[PACKED_KEY].shape[0]
        views = self.packed_views(packed)
        out: dict[str, np.ndarray] = {}
        for e in layout.entries8:
            arr = views[e.key].reshape(batch, *e.caps)
            out[e.key] = arr.astype(np.bool_)
        for e in layout.entries32:
            out[e.key] = views[e.key].reshape(batch, *e.caps)
        return out

    def to_transport(
        self,
        packed: Mapping[str, np.ndarray],
        vocab_size: int | None = None,
    ) -> dict[str, np.ndarray]:
        """Wide packed batch → the bit-packed TRANSPORT buffer shipped to
        the device: the byte region (all 0/1-valued) packs 8:1 via one
        vectorized packbits; intern-id lanes narrow to uint16 while the
        vocabulary fits (``vocab_size``, the NARROW form — ids are dense
        and non-negative); remaining int32/f32 lanes copy verbatim.
        Non-packed side-channel keys (wasm member bits) pass through.
        Idempotent — a buffer already at a transport width is returned
        unchanged."""
        layout = self.packed_layout()
        buf = np.asarray(packed[PACKED_KEY])
        if buf.shape[1] in (layout.transport_width, layout.transport16_width):
            return dict(packed)
        batch = buf.shape[0]
        narrow = (
            vocab_size is not None
            and vocab_size <= 65536
            and layout.u16_count > 0
        )
        bits = np.packbits(
            buf[:, : layout.total8] != 0, axis=1, bitorder="little"
        )
        if narrow:
            region32 = np.ascontiguousarray(
                buf[
                    :,
                    layout.off32_bytes : layout.off32_bytes
                    + layout.total32 * 4,
                ]
            ).view(np.int32)
            out = np.zeros((batch, layout.transport16_width), np.uint8)
            out[:, : bits.shape[1]] = bits
            id_cols, other_cols = self._transport_col_split()
            u16 = np.ascontiguousarray(
                region32[:, id_cols].astype(np.uint16)
            )
            o = layout.t16_off_u16_bytes
            out[:, o : o + u16.shape[1] * 2] = u16.view(np.uint8).reshape(
                batch, -1
            )
            if other_cols:
                rest = np.ascontiguousarray(region32[:, other_cols])
                o = layout.t16_off32_bytes
                out[:, o : o + rest.shape[1] * 4] = rest.view(
                    np.uint8
                ).reshape(batch, -1)
        else:
            out = np.zeros((batch, layout.transport_width), np.uint8)
            out[:, : bits.shape[1]] = bits
            n32 = layout.total32 * 4
            if n32:
                out[:, layout.t_off32_bytes : layout.t_off32_bytes + n32] = (
                    buf[:, layout.off32_bytes : layout.off32_bytes + n32]
                )
        converted = dict(packed)
        converted[PACKED_KEY] = out
        return converted

    def _transport_col_split(self) -> tuple[list[int], list[int]]:
        """(id int32-columns, non-id int32-columns) of the 32-bit region,
        in entry order — cached; used by the narrow transport gather."""
        cached = getattr(self, "_col_split_cache", None)
        if cached is None:
            layout = self.packed_layout()
            id_cols: list[int] = []
            other_cols: list[int] = []
            for e in layout.entries32:
                cols = range(e.offset, e.offset + e.elems)
                (id_cols if e.is_id else other_cols).extend(cols)
            cached = self._col_split_cache = (id_cols, other_cols)
        return cached

    def pack(self, features: Mapping[str, np.ndarray]) -> dict[str, np.ndarray]:
        """Per-key batch arrays → the packed buffer (slow-path/test helper;
        the native encoder writes the packed buffer directly)."""
        batch = len(np.asarray(features[BATCH_KEY]))
        out = self.empty_batch_packed(batch)
        views = self.packed_views(out)
        layout = self.packed_layout()
        for e in layout.entries8:
            if e.key == BATCH_KEY:
                continue
            views[e.key][:] = np.asarray(features[e.key]).reshape(
                batch, e.elems
            )
        for e in layout.entries32:
            views[e.key][:] = np.asarray(features[e.key]).reshape(
                batch, e.elems
            )
        return out


@dataclass(frozen=True)
class PackedEntry:
    key: str
    offset: int  # element (column) offset within the packed buffer
    elems: int  # elements per row
    caps: tuple[int, ...]
    is_f32: bool = False
    is_id: bool = False  # intern-table id lane (non-negative, dense)


@dataclass(frozen=True)
class PackedLayout:
    """Column layout of the single packed batch buffer.

    Byte columns: [0, total8) 1-byte entries (BATCH_KEY at column 0), then
    padding to 4-byte alignment at ``off32_bytes``, then ``total32`` int32
    columns; total row width ``width`` bytes. Entry order is the spec-dict
    iteration order (the same order ops/fastenc._describe_schema assigns
    array ids), masks appended after all primaries — deterministic for a
    given schema, so the device-side unpack slices are static under jit.
    32-bit entry offsets are in INT32 ELEMENTS within the aligned tail
    region."""

    entries32: tuple[PackedEntry, ...]
    entries8: tuple[PackedEntry, ...]
    total32: int
    total8: int
    off32_bytes: int
    width: int
    # transport forms: every 1-byte entry is 0/1-valued (the device unpack
    # reads them all as ``!= 0``), so the wire row bit-packs the byte
    # region 8:1, which roughly halves bytes/row. The wide
    # (byte-per-entry) form remains the HOST working layout (fastenc
    # writes it; views stay zero-copy); ``FeatureSchema.to_transport``
    # converts one whole batch with a single vectorized packbits.
    #
    # The NARROW form additionally ships intern-id lanes as uint16 while
    # the intern table fits (ids are dense and non-negative; admission
    # vocabularies are small) — ids dominate the 32-bit region, so this
    # nearly halves the wire row AGAIN. A table past 65,536 strings falls
    # back to the int32 transport (lazily compiled, watchdog-bounded like
    # any cold bucket).
    transport_width: int = 0
    transport16_width: int = 0

    @property
    def bits_bytes(self) -> int:
        return (self.total8 + 7) // 8

    @property
    def t_off32_bytes(self) -> int:
        return (self.bits_bytes + 3) // 4 * 4

    @property
    def u16_count(self) -> int:
        return sum(e.elems for e in self.entries32 if e.is_id)

    @property
    def t16_off_u16_bytes(self) -> int:
        return (self.bits_bytes + 1) // 2 * 2

    @property
    def t16_off32_bytes(self) -> int:
        return (self.t16_off_u16_bytes + self.u16_count * 2 + 3) // 4 * 4

    @classmethod
    def build(cls, schema: "FeatureSchema") -> "PackedLayout":
        e32: list[PackedEntry] = []
        e8: list[PackedEntry] = [PackedEntry(BATCH_KEY, 0, 1, ())]
        off32, off8 = 0, 1
        specs = list(schema.specs.values())
        for spec in specs:
            elems = int(np.prod(spec.caps, dtype=np.int64)) if spec.caps else 1
            if spec.kind == "value" and spec.dtype in (
                DType.ID, DType.I32, DType.F32,
            ):
                e32.append(PackedEntry(
                    spec.key, off32, elems, spec.caps,
                    is_f32=spec.dtype is DType.F32,
                    is_id=spec.dtype is DType.ID,
                ))
                off32 += elems
            else:
                e8.append(PackedEntry(spec.key, off8, elems, spec.caps))
                off8 += elems
        for spec in specs:  # masks after all primaries (fastenc order)
            if not spec.has_mask:
                continue
            elems = int(np.prod(spec.caps, dtype=np.int64)) if spec.caps else 1
            e8.append(PackedEntry(_mask_key(spec.key), off8, elems, spec.caps))
            off8 += elems
        off32_bytes = (off8 + 3) // 4 * 4
        width = off32_bytes + off32 * 4
        base = cls(tuple(e32), tuple(e8), off32, off8, off32_bytes, width)
        # transport widths derive from the instance's OWN offset
        # properties — one copy of the alignment math
        import dataclasses

        return dataclasses.replace(
            base,
            transport_width=base.t_off32_bytes + off32 * 4,
            transport16_width=(
                base.t16_off32_bytes + (off32 - base.u16_count) * 4
            ),
        )

    def widened(self, width: int) -> "PackedLayout":
        """A copy with trailing pad bytes up to ``width`` (multiple of 4).

        The environment widens colliding layouts so every schema bucket has
        a UNIQUE row width — the device unpack selects its layout by packed
        buffer width, and two buckets with coincidentally equal widths but
        different entry maps would otherwise silently mis-slice features.
        Pad bytes live after the int32 region and are never read.
        """
        assert width >= self.width and width % 4 == 0
        import dataclasses

        return dataclasses.replace(self, width=width)

    def transport_widened(self, width: int) -> "PackedLayout":
        """Like ``widened`` but pads the TRANSPORT row width — transport
        widths must be unique across schemas AND disjoint from every wide
        width, since the device unpack keys on buffer width alone."""
        assert width >= self.transport_width and width % 4 == 0
        import dataclasses

        return dataclasses.replace(self, transport_width=width)

    def transport16_widened(self, width: int) -> "PackedLayout":
        assert width >= self.transport16_width and width % 4 == 0
        import dataclasses

        return dataclasses.replace(self, transport16_width=width)


def unpack_rows(
    buf: Any,
    layout: "PackedLayout",
    transport: bool,
    narrow: bool,
) -> dict[str, Any]:
    """Packed (row-major) buffer → the per-key feature dict the compiled
    predicates consume, as traced jnp ops. Slices/offsets are static for
    a given layout, so XLA fuses the unpack into the predicate program.

    The environment's packed jit root (``_forward``) runs it.

    ``transport``: the buffer is in a wire form (bit-packed byte region);
    ``narrow``: the uint16-narrowed id variant of the wire form.
    """
    import jax
    import jax.numpy as jnp

    buf = jnp.asarray(buf)
    batch = buf.shape[0]
    out: dict[str, Any] = {}
    if narrow:
        # NARROW form: id lanes ride as uint16, the rest as int32 —
        # two regions with their own sequential offsets (entry order)
        n_id = layout.u16_count
        if n_id:
            u16_bytes = jax.lax.slice_in_dim(
                buf,
                layout.t16_off_u16_bytes,
                layout.t16_off_u16_bytes + n_id * 2,
                axis=1,
            )
            ids32 = jax.lax.bitcast_convert_type(
                u16_bytes.reshape(batch, n_id, 2), jnp.uint16
            ).astype(jnp.int32)
        n_other = layout.total32 - n_id
        if n_other:
            tail = jax.lax.slice_in_dim(
                buf,
                layout.t16_off32_bytes,
                layout.t16_off32_bytes + n_other * 4,
                axis=1,
            )
            o32 = jax.lax.bitcast_convert_type(
                tail.reshape(batch, n_other, 4), jnp.int32
            )
        id_off = other_off = 0
        for e in layout.entries32:
            if e.is_id:
                block = jax.lax.slice_in_dim(
                    ids32, id_off, id_off + e.elems, axis=1
                )
                id_off += e.elems
            else:
                block = jax.lax.slice_in_dim(
                    o32, other_off, other_off + e.elems, axis=1
                )
                other_off += e.elems
            block = block.reshape((batch, *e.caps))
            if e.is_f32:
                block = jax.lax.bitcast_convert_type(block, jnp.float32)
            out[e.key] = block
    else:
        off32_bytes = (
            layout.t_off32_bytes if transport else layout.off32_bytes
        )
        if layout.total32:
            # int32 tail region: groups of 4 bytes bitcast to int32
            # (slice the exact region — widened layouts carry trailing
            # pad bytes)
            tail = jax.lax.slice_in_dim(
                buf,
                off32_bytes,
                off32_bytes + layout.total32 * 4,
                axis=1,
            )
            p32 = jax.lax.bitcast_convert_type(
                tail.reshape(batch, layout.total32, 4), jnp.int32
            )
        for e in layout.entries32:
            block = jax.lax.slice_in_dim(
                p32, e.offset, e.offset + e.elems, axis=1
            )
            block = block.reshape((batch, *e.caps))
            if e.is_f32:
                block = jax.lax.bitcast_convert_type(block, jnp.float32)
            out[e.key] = block
    if transport:
        # bit-packed byte region (to_transport, little bit order):
        # expand once to a (batch, bits_bytes*8) 0/1 matrix — static
        # shapes, pure elementwise; XLA fuses it into the predicates
        bits = jax.lax.slice_in_dim(buf, 0, layout.bits_bytes, axis=1)
        shifts = jnp.arange(8, dtype=jnp.uint8)
        expanded = (bits[:, :, None] >> shifts) & jnp.uint8(1)
        lanes = expanded.reshape(batch, layout.bits_bytes * 8)
        for e in layout.entries8:
            block = jax.lax.slice_in_dim(
                lanes, e.offset, e.offset + e.elems, axis=1
            )
            out[e.key] = block.reshape((batch, *e.caps)) != 0
    else:
        for e in layout.entries8:
            block = jax.lax.slice_in_dim(
                buf, e.offset, e.offset + e.elems, axis=1
            )
            out[e.key] = block.reshape((batch, *e.caps)) != 0
    return out


class _TrieNode:
    """One node of the single-pass extraction trie."""

    __slots__ = ("children", "star", "terminals", "axis_cap", "repr_key")

    def __init__(self) -> None:
        self.children: dict[str, _TrieNode] = {}
        self.star: _TrieNode | None = None
        self.terminals: list[FeatureSpec] = []
        self.axis_cap: int = 0  # cap of the star axis rooted here
        self.repr_key: str = ""  # a spec key for SchemaOverflow reporting


def ensure_unique_packed_widths(schemas) -> None:
    """Widen colliding packed layouts so every schema bucket has a UNIQUE
    row width (the device unpack selects its layout by packed buffer width;
    equal widths with different entry maps would silently mis-slice
    features). Must run BEFORE any encode or native attach captures the
    row stride."""
    used_widths: set[int] = set()
    for schema in schemas:
        layout = schema.packed_layout()
        while layout.width in used_widths:
            layout = layout.widened(layout.width + 4)
            schema.install_packed_layout(layout)
        used_widths.add(layout.width)
    # transport widths share the same width-keyed dispatch, so they must
    # be unique among themselves AND never collide with a wide width
    for schema in schemas:
        layout = schema.packed_layout()
        while layout.transport_width in used_widths:
            layout = layout.transport_widened(layout.transport_width + 4)
            schema.install_packed_layout(layout)
        used_widths.add(layout.transport_width)
    for schema in schemas:
        layout = schema.packed_layout()
        while layout.transport16_width in used_widths:
            layout = layout.transport16_widened(layout.transport16_width + 4)
            schema.install_packed_layout(layout)
        used_widths.add(layout.transport16_width)


def _build_trie(specs) -> _TrieNode:
    root = _TrieNode()
    for spec in specs:
        node = root
        axis = 0
        for seg in spec.segments:
            if seg == STAR:
                if node.star is None:
                    node.star = _TrieNode()
                node.axis_cap = spec.caps[axis] if axis < len(spec.caps) else 0
                node.repr_key = spec.key
                node = node.star
                axis += 1
            else:
                node = node.children.setdefault(seg, _TrieNode())
        node.terminals.append(spec)
    return root


def _walk_trie(
    node: _TrieNode,
    value: Any,
    coords: tuple[int, ...],
    out: dict[str, np.ndarray],
    table: InternTable,
) -> None:
    for spec in node.terminals:
        if spec.kind == "value":
            try:
                ok, converted = _convert(value, spec.dtype, table)
            except UnencodableValue:
                # fail the whole encode → wider bucket won't help, the
                # environment routes the request to the oracle
                raise SchemaOverflow(spec.key, -1, 0, 0) from None
            if ok:
                out[spec.key][coords] = converted
                if spec.masked:
                    out[_mask_key(spec.key)][coords] = True
        elif spec.kind == "present":
            if value is not None:
                out[spec.key][coords] = True
        else:  # pred
            if isinstance(value, str):
                out[spec.key][coords] = table.pred_value(spec.pred_key(), value)
    if node.children and isinstance(value, Mapping):
        for key, child in node.children.items():
            if key in value:
                _walk_trie(child, value[key], coords, out, table)
    if node.star is not None:
        elems = star_elements(value)
        if elems is None:
            return
        if node.axis_cap and len(elems) > node.axis_cap:
            raise SchemaOverflow(
                node.repr_key, len(coords), len(elems), node.axis_cap
            )
        star = node.star
        for i, elem in enumerate(elems):
            _walk_trie(star, elem, coords + (i,), out, table)


def _mask_key(value_key: str) -> str:
    # "...:v:id" -> "...:m:id"
    head, _, dtype = value_key.rpartition(":v:")
    return f"{head}:m:{dtype}"


def mask_key_for(value_key: str) -> str:
    return _mask_key(value_key)


class UnencodableValue(Exception):
    """A well-typed value that does not FIT the tensor dtype (out-of-range
    int32/float32). Treating it as missing would fail OPEN (the oracle sees
    the real value and may reject); the encoder instead fails the request's
    encoding so it routes to the host oracle."""


_I32_MIN, _I32_MAX = -(2**31), 2**31 - 1
_F32_MAX = 3.4028235677973366e38


def _convert(v: Any, dtype: DType, table: InternTable) -> tuple[bool, Any]:
    """JSON leaf → typed scalar; type mismatch means missing (mask=0);
    out-of-range numerics raise UnencodableValue (oracle fallback).
    Mirrored exactly by the oracle interpreter (evaluation/oracle.py) and
    the native encoder (csrc/fastenc.cpp)."""
    if dtype is DType.ID:
        if isinstance(v, str):
            return True, table.intern(v)
        return False, MISSING_ID
    if dtype is DType.F32:
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            return False, 0.0
        f = float(v)
        if f != f or abs(f) > _F32_MAX:
            raise UnencodableValue(f"value {v!r} does not fit float32")
        return True, f
    if dtype is DType.BOOL:
        if isinstance(v, bool):
            return True, v
        return False, False
    if dtype is DType.I32:
        if isinstance(v, bool) or not isinstance(v, int):
            return False, 0
        if not (_I32_MIN <= v <= _I32_MAX):
            raise UnencodableValue(f"value {v!r} does not fit int32")
        return True, int(v)
    raise AssertionError(dtype)


def _extract(
    payload: Any,
    segments: tuple[str, ...],
    caps: tuple[int, ...],
    key: str,
):
    """Yield ``(coords, json_value)`` for every leaf the path reaches.
    ``coords`` indexes the star axes. Raises SchemaOverflow if an array is
    longer than its axis cap."""

    def rec(value: Any, segs: tuple[str, ...], coords: tuple[int, ...], axis: int):
        if not segs:
            yield coords, value
            return
        head, rest = segs[0], segs[1:]
        if head == STAR:
            elems = star_elements(value)
            if elems is None:
                return
            if caps and len(elems) > caps[axis]:
                raise SchemaOverflow(key, axis, len(elems), caps[axis])
            for i, elem in enumerate(elems):
                yield from rec(elem, rest, coords + (i,), axis + 1)
        else:
            if not isinstance(value, Mapping) or head not in value:
                return
            yield from rec(value[head], rest, coords, axis)

    yield from rec(payload, segments, (), 0)


def star_elements(value: Any) -> list[Any] | None:
    """Elements a ``*`` axis iterates. Lists iterate their items; mappings
    iterate ``{"__key__": k, "__value__": v}`` entry wrappers in sorted key
    order (deterministic — lets policies quantify over dynamic-key maps like
    metadata.annotations). Shared with the oracle (evaluation/oracle.py) so
    both backends see identical element streams."""
    if isinstance(value, list):
        return value
    if isinstance(value, Mapping):
        return [
            {"__key__": str(k), "__value__": value[k]}
            for k in sorted(value, key=str)
        ]
    return None
