"""ctypes bindings + build driver for the native encoder (csrc/fastenc.cpp).

The native encoder is the C++ twin of the codec's extraction trie
(ops/codec.py): it parses the request's JSON bytes directly (no Python dict
on the hot path) and writes the features straight into the packed batch
buffer, with the GIL released, so the batcher can encode on parallel threads.

Strings (ID and string-predicate columns) resolve in the same native call
against a MIRROR of the intern table that the encoder handle keeps: string
bytes -> id and the bit of each of this encoder's predicates. A string the
mirror has not seen comes back as a record; ``_scatter_strings`` interns
those in Python (the table stays the only source of ids and bits) and
``_learn`` publishes what it resolved, so the next batch finds them. A warm
batch is therefore the output buffer, the blob arrays and one native call:
no records, no numpy call over them, no Python loop over strings — numpy
hands the GIL away in any call over a few hundred elements, and in a
serving process another thread always takes it (PERF.md section 6, PR 34).
The mirror is bounded (constants in fastenc.cpp); past its cap nothing more
is published and the record path answers, exactly, as before.

Who writes the wire: told the wire form its schema's launches have settled
on as plain values (``encode_batch(..., wire=(take, n_plain, width))``;
evaluation/environment.py _WireForm.gather),
the same native call also writes the launch's host half — the batch's
liveness words and its one wire buffer, LIVE_KEY and WIRE_KEY beside
PACKED_KEY — whenever the batch left no record. The launch ships them if
that is still its form and otherwise builds its own with ``_live_words`` and
``_WireForm.wire``, the numpy reference the native writer is held to byte
for byte (tests/test_fastenc.py). ``take_rows`` compacts such a buffer
with the GIL kept (PERF.md section 6, PR 37).

Build model: compiled on demand with g++ into ``build/`` under a name that
hashes its source and flags (utils/nativebuild.py). A failed build or load
raises — the pure-Python trie (ops/codec.py) stays as the differential
reference the tests hold this encoder to (tests/test_fastenc.py), not as a
silent serving fallback."""

from __future__ import annotations

import ctypes
import json
import threading
from pathlib import Path
from typing import Any

import numpy as np

from policy_server_tpu.ops.codec import (
    BATCH_KEY,
    PACKED_KEY,
    FeatureSchema,
    FeatureSpec,
    SchemaOverflow,
    mask_key_for,
)
from policy_server_tpu.utils.interning import InternTable
from policy_server_tpu.utils.nativebuild import (
    REPO_ROOT,
    NativeBuildError,
    build_shared_library,
)

_SRC = REPO_ROOT / "csrc" / "fastenc.cpp"

_KIND = {"value": 0, "present": 1, "pred": 2}
_DTYPE = {"id": 0, "f32": 1, "bool": 2, "i32": 3}

_I32P = ctypes.POINTER(ctypes.c_int32)

# Where encode_batch leaves what it wrote for the launch, beside PACKED_KEY
# in the feature dict it returns (only when asked, and only for a batch
# that left no record): the batch's liveness words and its wire buffer.
LIVE_KEY = "__live_words__"
WIRE_KEY = "__wire__"


class _WireRequest(ctypes.Structure):
    """csrc/fastenc.cpp WireRequest, field for field."""

    _fields_ = [
        ("row_width", ctypes.c_int64),
        ("take", ctypes.c_void_p),
        ("n_take", ctypes.c_int64),
        ("n_plain", ctypes.c_int64),
        ("wire_width", ctypes.c_int64),
        ("wire_rows", ctypes.c_int64),
        ("wire", ctypes.c_void_p),
        ("live", ctypes.c_void_p),
    ]


_lib_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
# the same library bound again with the GIL KEPT across a call: for the
# calls too small to be worth handing the interpreter away (take_rows)
_pylib: ctypes.PyDLL | None = None
_lib_error: str | None = None  # guarded-by: _lib_lock


def _build_library() -> Path:
    return build_shared_library(_SRC, timeout=120)


def _load() -> ctypes.CDLL:
    """The loaded library; raises NativeBuildError when it cannot be built
    or loaded (the failure is remembered: one compile attempt per
    process)."""
    global _lib, _pylib, _lib_error
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _lib_error is not None:
            raise NativeBuildError(_lib_error)
        try:
            path = str(_build_library())
            lib = ctypes.CDLL(path)
            pylib = ctypes.PyDLL(path)
        except (NativeBuildError, OSError) as e:
            _lib_error = f"native encoder unavailable: {e}"
            raise NativeBuildError(_lib_error) from e
        lib.fastenc_create.restype = ctypes.c_void_p
        lib.fastenc_create.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.fastenc_destroy.argtypes = [ctypes.c_void_p]
        lib.fastenc_encode.restype = ctypes.c_int64
        lib.fastenc_encode.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ]
        lib.fastenc_encode_batch.restype = ctypes.c_int64
        lib.fastenc_encode_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(_WireRequest),
        ]
        pylib.fastenc_take_rows.restype = None
        pylib.fastenc_take_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.fastenc_learn.restype = ctypes.c_int32
        lib.fastenc_learn.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_char_p, ctypes.c_int64,
        ]
        lib.fastenc_mirror_entries.restype = ctypes.c_int64
        lib.fastenc_mirror_entries.argtypes = [ctypes.c_void_p]
        _lib, _pylib = lib, pylib
        return _lib


def native_available() -> bool:
    try:
        _load()
    except NativeBuildError:
        return False
    return True


def take_rows(
    wire: np.ndarray, wide: np.ndarray, pos: np.ndarray, rows: int
) -> tuple[np.ndarray, np.ndarray]:
    """A compacted chunk's launch half out of the one its encode call
    wrote: rows ``pos`` of ``wire`` at the head of a new buffer of
    ``rows`` rows, the others zero, and the liveness words of the same
    rows of ``wide`` (the chunk's wide packed buffer) — what
    ``_WireForm.wire`` and ``_live_words`` make of a wide copy of those
    rows. One native call that KEEPS the GIL (a few KB read; numpy would
    hand the interpreter away for each of its calls)."""
    _load()
    pos = np.ascontiguousarray(pos, np.int64)
    for matrix in (wire, wide):
        if not (matrix.flags.c_contiguous and matrix.dtype == np.uint8):
            raise ValueError("take_rows: C-contiguous uint8 matrices")
    if pos.size > rows or (
        pos.size
        and not 0 <= pos.min() <= pos.max() < min(len(wire), len(wide))
    ):
        raise IndexError("take_rows: positions outside the chunk or bucket")
    out = np.empty((rows, wire.shape[1]), np.uint8)
    live = np.empty(wide.shape[1] // 4, np.uint32)
    _pylib.fastenc_take_rows(
        wire.ctypes.data, wire.shape[1], wide.ctypes.data, wide.shape[1],
        pos.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), pos.size,
        out.ctypes.data, rows,
        live.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
    )
    return out, live


# ---------------------------------------------------------------------------
# Schema description (mirrors the Python trie, _build_trie in codec.py)
# ---------------------------------------------------------------------------


def _describe_schema(schema: FeatureSchema) -> tuple[str, list[FeatureSpec], list[str]]:
    """→ (schema JSON for fastenc_create, array-id → spec order, pred keys).

    Array ids: each spec gets one buffer; value specs get a second (mask)
    buffer appended after all primary buffers."""
    specs = list(schema.specs.values())
    array_id = {spec.key: i for i, spec in enumerate(specs)}
    mask_id: dict[str, int] = {}
    next_id = len(specs)
    for spec in specs:
        if spec.has_mask:
            mask_id[spec.key] = next_id
            next_id += 1
    pred_keys: list[str] = []
    pred_id: dict[str, int] = {}
    for spec in specs:
        if spec.kind == "pred":
            pk = spec.pred_key()
            if pk not in pred_id:
                pred_id[pk] = len(pred_keys)
                pred_keys.append(pk)

    def elsize(spec: FeatureSpec) -> int:
        return 4 if spec.kind == "value" and spec.dtype is not None and spec.dtype.value in ("id", "f32", "i32") else 1

    # Batch mode writes into column blocks of the single packed buffer
    # (codec.PackedLayout): every array's row stride is the full packed
    # row width, and its block starts ``offset`` bytes into the row.
    layout = schema.packed_layout()
    offset = {e.key: e.offset for e in layout.entries8}
    offset.update(
        (e.key, layout.off32_bytes + 4 * e.offset) for e in layout.entries32
    )
    arrays = [
        {"caps": list(s.caps), "elsize": elsize(s),
         "row_stride": layout.width, "offset": offset[s.key]}
        for s in specs
    ]
    arrays += [
        {"caps": list(s.caps), "elsize": 1, "row_stride": layout.width,
         "offset": offset[mask_key_for(s.key)]}
        for s in specs if s.has_mask
    ]

    # Serialize the SAME trie the Python encoder walks (codec._build_trie):
    # one source of truth for traversal order, caps, and overflow reporting.
    def node_desc(node: Any) -> dict[str, Any]:
        return {
            "terminals": [
                {
                    "array": array_id[spec.key],
                    "kind": _KIND[spec.kind],
                    "dtype": _DTYPE[spec.dtype.value] if spec.dtype else 0,
                    "mask": mask_id.get(spec.key, -1),
                    "pred": (
                        pred_id[spec.pred_key()] if spec.kind == "pred" else -1
                    ),
                }
                for spec in node.terminals
            ],
            "children": {
                seg: node_desc(child) for seg, child in node.children.items()
            },
            "star": node_desc(node.star) if node.star is not None else None,
            "axis_cap": node.axis_cap,
            "overflow_id": array_id.get(node.repr_key, -1),
        }

    doc = {
        "arrays": arrays,
        "n_preds": len(pred_keys),
        "trie": node_desc(schema._trie()),
    }
    return json.dumps(doc), specs, pred_keys


class NativeEncoder:
    """Per-schema native encoder instance. Thread-safe for concurrent
    encodes: per-call state is the call's own, and the mirror inside the
    handle is read without a lock and published to under one only
    publishers take (csrc/fastenc.cpp).

    ``table`` is the intern table the mirror is published from; an encode
    against any other table (or with none bound) resolves every string in
    Python, since an id means nothing outside the table that gave it."""

    ARENA_CAP = 1 << 20
    RECORDS_CAP = 1 << 16

    def __init__(self, schema: FeatureSchema, table: InternTable | None = None):
        self._lib = lib = _load()
        desc, self._specs, self._pred_keys = _describe_schema(schema)
        raw = desc.encode()
        self._handle = lib.fastenc_create(raw, len(raw))
        if not self._handle:
            raise RuntimeError("fastenc_create failed (bad schema description)")
        # specs carrying a validity-mask buffer (value specs minus the
        # optimizer's mask-elided columns)
        self._value_specs = [s for s in self._specs if s.has_mask]
        self._schema = schema
        self._table = table
        # cleared once a cap of the mirror refused an entry: what is left
        # of _learn then is not worth its Python (racing writers agree)
        self._mirror_open = table is not None
        self._scratch = threading.local()

    def __del__(self) -> None:  # pragma: no cover
        lib, handle = getattr(self, "_lib", None), getattr(self, "_handle", None)
        if lib is not None and handle:
            lib.fastenc_destroy(handle)

    @property
    def mirror_entries(self) -> int:
        """Strings the mirror holds (bounded: fastenc.cpp Mirror)."""
        return int(self._lib.fastenc_mirror_entries(self._handle))

    def encode_json(
        self, payload_json: bytes, table: InternTable
    ) -> dict[str, np.ndarray]:
        """Encode raw JSON bytes → feature dict (same layout as
        FeatureSchema.encode). Raises SchemaOverflow on axis overflow and
        ValueError on malformed JSON."""
        out: dict[str, np.ndarray] = {BATCH_KEY: np.zeros((), dtype=np.bool_)}
        buffers = (ctypes.c_void_p * (len(self._specs) + len(self._value_specs)))()
        for i, spec in enumerate(self._specs):
            arr = np.zeros(spec.caps, dtype=spec.np_dtype())
            out[spec.key] = arr
            buffers[i] = arr.ctypes.data_as(ctypes.c_void_p)
        mi = len(self._specs)
        for spec in self._value_specs:
            arr = np.zeros(spec.caps, dtype=np.bool_)
            out[mask_key_for(spec.key)] = arr
            buffers[mi] = arr.ctypes.data_as(ctypes.c_void_p)
            mi += 1
        arena = ctypes.create_string_buffer(self.ARENA_CAP)
        records = (ctypes.c_int32 * (self.RECORDS_CAP * 6))()
        n = self._lib.fastenc_encode(
            self._handle, payload_json, len(payload_json),
            buffers, arena, self.ARENA_CAP,
            ctypes.cast(records, ctypes.POINTER(ctypes.c_int32)),
            self.RECORDS_CAP,
        )
        if n == -1:
            raise ValueError("fastenc: malformed JSON payload")
        if n == -2:
            raise ValueError("fastenc: arena overflow")
        if n < 0:
            spec = self._specs[-(n + 1000)]
            raise SchemaOverflow(spec.key, 0, -1, spec.caps[0] if spec.caps else 0)
        # Python-side interning pass over the collected strings.
        raw_arena = arena.raw
        rec = np.frombuffer(records, dtype=np.int32, count=int(n) * 6).reshape(-1, 6)
        for array_id, flat_off, is_pred, pred_idx, soff, slen in rec:
            s = raw_arena[soff : soff + slen].decode("utf-8", "surrogatepass")
            spec = self._specs[array_id]
            arr = out[spec.key]
            if is_pred:
                arr.flat[flat_off] = table.pred_value(
                    self._pred_keys[pred_idx], s
                )
            else:
                arr.flat[flat_off] = table.intern(s)
        return out

    def encode(self, payload: Any, table: InternTable) -> dict[str, np.ndarray]:
        return self.encode_json(
            json.dumps(payload, separators=(",", ":")).encode(), table
        )

    def encode_batch(
        self,
        payload_jsons: list[bytes],
        batch_size: int,
        table: InternTable,
        wire: tuple[np.ndarray, int, int] | None = None,
    ) -> tuple[dict[str, np.ndarray], np.ndarray, int]:
        """Encode a whole batch in ONE native call, rows written directly
        into the packed batch buffer (codec.PackedLayout) — a dispatch is
        O(1) host→device transfers regardless of schema width.

        ``wire`` is the wire form the launch expects to ship this batch
        in, as plain values ``(take, n_plain, width)``: a wire row is
        ``width`` bytes, the wide row's bytes ``take[:n_plain]`` copied,
        then the bytes ``take[n_plain:]`` packed a bit each, little-endian
        (evaluation/environment.py _WireForm.gather). The same call then
        also writes the launch's host half — the batch's liveness words
        (LIVE_KEY, what environment._live_words computes) and its wire
        buffer in that form (WIRE_KEY, what _WireForm.wire returns), byte
        for byte — unless the batch left a record: Python then rewrites id
        columns of the wide rows after the call, and the launch computes
        both itself.

        → ({PACKED_KEY: buffer} feature dict,
           per-row status: 0 ok, <0 failed — failed rows are all-missing
           in the buffer and must be re-routed by the caller,
           strings the mirror had not seen, resolved in Python: 0 for a
           warm batch, which then made no numpy call over its strings)."""
        n = len(payload_jsons)
        assert n <= batch_size
        out = self._schema.empty_batch_packed(batch_size)
        buf = out[PACKED_KEY]
        request = None
        if wire is not None:
            take, n_plain, width = wire
            take = np.ascontiguousarray(take, np.int64)
            # np.empty: the native call writes every byte of both
            wire_buf = np.empty((batch_size, width), np.uint8)
            live = np.empty(buf.shape[1] // 4, np.uint32)
            request = _WireRequest(
                buf.shape[1], take.ctypes.data, take.size, n_plain,
                width, batch_size, wire_buf.ctypes.data, live.ctypes.data,
            )
        blob_lens = [len(b) for b in payload_jsons]
        jsons = (ctypes.c_char_p * n)(*payload_jsons)
        lens = (ctypes.c_int64 * n)(*blob_lens)
        arena_cap = max(self.ARENA_CAP, sum(blob_lens))
        records_cap = self.RECORDS_CAP * max(1, (n + 63) // 64)
        # Reusable per-thread scratch: allocating+zeroing tens of MB per
        # dispatch would dominate the very path this encoder accelerates.
        scratch = self._scratch
        arena = getattr(scratch, "arena", None)
        if arena is None or len(arena) < arena_cap:
            arena = scratch.arena = ctypes.create_string_buffer(arena_cap)
        records = getattr(scratch, "records", None)
        if records is None or len(records) < records_cap * 6:
            records = scratch.records = (ctypes.c_int32 * (records_cap * 6))()
            scratch.records_ptr = ctypes.cast(records, _I32P)
        status = np.empty(n, np.int32)
        mirrored = table is self._table
        n_rec = self._lib.fastenc_encode_batch(
            self._handle, jsons, lens, n,
            buf.ctypes.data, mirrored,
            arena, len(arena),
            scratch.records_ptr, len(records) // 6,
            status.ctypes.data_as(_I32P), request,
        )
        if n_rec == -2:
            raise ValueError("fastenc: arena/records overflow")
        if n_rec:
            # the cold path: strings the mirror has not seen (all of them
            # under a table it is not bound to)
            learned = self._scatter_strings(
                np.frombuffer(
                    records, dtype=np.int32, count=int(n_rec) * 6
                ).reshape(-1, 6),
                arena, self._schema.packed_views(out), table,
            )
            if mirrored and self._mirror_open:
                self._learn(arena, learned, table)
        elif request is not None:
            out[LIVE_KEY], out[WIRE_KEY] = live, wire_buf
        return out, status, int(n_rec)

    def _scatter_strings(
        self,
        rec: np.ndarray,
        arena,
        views: dict[str, np.ndarray],
        table: InternTable,
    ) -> dict[int, tuple[int, int]]:
        """Intern the strings of ``rec`` and scatter their ids and
        predicate bits into ``views``. Vectorized: the native encoder
        dedups strings at the batch level, so Python work is O(#unique
        strings) + a handful of numpy scatters — not a Python loop over
        every record. → arena offset of each string it resolved →
        (its length, its id), what ``_learn`` publishes."""
        specs = self._specs
        pred_keys = self._pred_keys
        used = int((rec[:, 4] + rec[:, 5]).max())
        raw_arena = ctypes.string_at(arena, used)
        # The native encoder dedups strings, so the arena offset uniquely
        # identifies a string; a (pred-tag, offset) composite int64 key
        # makes the unique pass a plain integer sort (np.unique(axis=0)
        # argsort over rows dominated this function before).
        tag = np.where(
            rec[:, 2] == 1, rec[:, 3].astype(np.int64) + 1, 0
        )
        keys = (tag << 40) | rec[:, 4].astype(np.int64)
        uniq, first, inverse = np.unique(
            keys, return_index=True, return_inverse=True
        )
        vals = np.empty(len(uniq), np.int32)
        learned: dict[int, tuple[int, int]] = {}
        for u, ri in enumerate(first):
            is_pred, pred_idx, soff, slen = rec[ri, 2:6].tolist()
            known = learned.get(soff)
            if known is None:
                s = raw_arena[soff : soff + slen].decode(
                    "utf-8", "surrogatepass"
                )
                known = learned[soff] = (slen, table.intern(s))
            vals[u] = (
                table.pred_bit(pred_keys[pred_idx], known[1])
                if is_pred
                else known[1]
            )
        rvals = vals[inverse]
        aids = rec[:, 0]
        for aid in np.unique(aids):
            m = aids == aid
            arr = views[specs[aid].key]
            arr.flat[rec[m, 1]] = rvals[m].astype(arr.dtype, copy=False)
        return learned

    def _learn(
        self, arena, learned: dict[int, tuple[int, int]], table: InternTable
    ) -> None:
        """Publish what ``_scatter_strings`` resolved to the mirror: each
        string's id, and its bit under EVERY predicate of this encoder
        (so a string first met under one predicate is right under the
        next). Runs after ``table.intern`` returned for each of them, so
        the table's publish-last rule holds for the mirror too."""
        n = len(learned)
        offs = np.fromiter(learned, np.int32, n)
        lens = np.fromiter((v[0] for v in learned.values()), np.int32, n)
        ids = np.fromiter((v[1] for v in learned.values()), np.int32, n)
        bits = bytes(
            table.pred_bit(pk, i) for i in ids.tolist()
            for pk in self._pred_keys
        )
        if self._lib.fastenc_learn(
            self._handle, arena, offs.ctypes.data_as(_I32P),
            lens.ctypes.data_as(_I32P), ids.ctypes.data_as(_I32P), bits, n,
        ):
            self._mirror_open = False


def attach_native(schema: FeatureSchema, table: InternTable) -> None:
    """Give a FeatureSchema a native encoder whose mirror is published from
    ``table`` (used by the evaluation environment at boot, after
    ``schema.register_preds(table)``). Raises NativeBuildError when the
    library cannot be built or loaded: the jax backend asks for the native
    encoder, and a server that silently encodes in Python instead is a
    different, slower server."""
    schema.native = NativeEncoder(schema, table)
