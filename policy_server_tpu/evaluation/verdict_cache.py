"""Bit-exact verdict caching / dedup (VERDICT r4 #1, r5 "top_next").

Soundness: the fused device program is a stateless pure function of the
encoded row (environment.py module docstring; the reference's
fresh-instance-per-eval isolation, evaluation_environment.rs:76-84,
exists precisely because evaluation is context+request -> verdict). What
is cached is the OUTPUT ROW (verdict bits / rule indices), never the
AdmissionResponse: materialization re-runs per request, so uids, patches,
and dynamic messages are computed from each request's own payload
(bit-identical by key equality, but carrying the right uid).

The row has one of two forms, by who produced it (PR 32):

* the device's: the row's ``bytes`` out of the fused program's one output
  array, as fetched (``OutputLayout`` says which byte is which key; the
  flagship set's row is 80 bytes: 32 ``allowed``, 32 ``rule``, 2 group
  ``allowed``, 2 x 7 member ``eval``). It holds every policy's outputs
  and is put with no per-key work at all; a hit reads the two or so keys
  its target needs through ``PackedRow``;
* the host fast path's: the small dict ``_oracle_outputs_for`` built, the
  target's own keys only.

Either way a row is IMMUTABLE once it is put (the fragment templates of
the hit lane live in the environment, keyed by the target and its own
outputs, not on the row), so an entry's cost is a function of (key, row):
it is not stored, and is computed again when the entry leaves.

Two dedup tiers, and why BOTH exist (round-6 tentpole):

* **Blob tier** — key: (target, canonical payload blob) — the exact JSON
  bytes the encoder consumes (environment._payload_blob, which already
  embeds the context snapshot and provider outputs). Equal blobs mean
  equal encoded rows mean equal device outputs. Because the key exists
  BEFORE encoding, an exact replay skips the encoder entirely — this is
  the tier that attacks the round-5 host floor, where every duplicate
  still paid a full C++ encode just to discover its post-encode row key.
  It cannot, however, see through uid/name variation: a Deployment
  rollout admits replica pods whose payloads differ in uid and generated
  name, so their blobs differ even though no policy reads those fields.

* **Row tier** — key: (target, packed row bytes) — the encoded feature
  row. The request uid is not a policy feature, so uid/name-varying
  duplicates collapse to one row AFTER encoding; this tier catches what
  the blob tier structurally cannot, at the price of paying the encode.
  Schema packed widths are unique (ensure_unique_packed_widths), so the
  bytes alone identify (schema, encoded request).

A hit in either tier returns the identical output row, so the tiers are
interchangeable for correctness; they differ only in what they can prove
equal and how early. Lookups go blob tier first (cheaper, earlier),
then row tier; misses populate both.

Capacity is BYTES, not rows (round-6: the old 4,096-row default was
smaller than the benchmark's own 12,500-template working set, so the
cross-batch cache thrashed and the measured dedup was pure in-chunk
replica collapse). An entry's cost is ``_ENTRY_OVERHEAD`` + its key's
bytes + its row: ``len`` of a packed row, constant-time; a per-item
estimate of a dict row. With the flagship set's 1,064-byte row key that
is 1,400 bytes an entry, so each tier's half of the default 256Mi holds
~95,000 entries; the estimate is not under what CPython keeps for them
(tests/test_verdict_cache.py measures it).

Exclusions (enforced by the caller): rows whose verdict involves the
host wasm engine (standalone wasm policies, groups with wasm members)
are never cached — a wasm deadline timeout is wall-clock-dependent, so
those verdicts are not pure functions of the payload bytes.
"""

from __future__ import annotations

import struct
import threading
from collections import OrderedDict
from operator import itemgetter
from typing import (
    Any,
    Callable,
    Hashable,
    Iterable,
    Mapping,
    NamedTuple,
    Sequence,
)

import numpy as np

# Fixed per-entry overhead: the OrderedDict's slot and link, the key tuple
# and the headers of the key's and the row's bytes objects (~200 bytes in
# CPython 3.12, measured by tests/test_verdict_cache.py).
_ENTRY_OVERHEAD = 256
# Per row-dict item: dict slot + boxed Python scalar (keys are interned
# strings shared across every row of an environment, so not counted).
_ROW_ITEM_COST = 80

# The uint8 wire form of the rule index -1 ("allowed"): the fused program
# casts int32 to uint8, which wraps it (rule indices are bounded < 255
# where the form is used, so 255 is unambiguous).
_RULE_ALLOWED_U8 = 255


class OutputLayout:
    """Which element of a row of the fused program's ONE output array
    holds which output key, and how it decodes: ``index[key]`` is
    ``(offset, is_rule)``, an ``allowed`` / ``eval`` flag or a rule index.
    ``compact`` rows are uint8 with the rule sentinel wrapped to 255, the
    others int32. The one decoder of that array: ``columns`` for a fetched
    batch (what the materializers of dispatched rows read) and ``value``
    for one key of one cached row."""

    __slots__ = ("index", "compact", "_item")

    def __init__(
        self, index: Mapping[str, tuple[int, bool]], compact: bool
    ) -> None:
        self.index = dict(index)
        self.compact = compact
        self._item = struct.Struct("B" if compact else "i")

    def columns(self, packed: np.ndarray) -> dict[str, np.ndarray]:
        """A fetched ``[batch, width]`` array as one column per key."""
        flags = packed != 0
        rules = packed.astype(np.int32)
        if self.compact:
            rules = np.where(rules == _RULE_ALLOWED_U8, -1, rules)
        return {
            key: (rules if is_rule else flags)[:, offset]
            for key, (offset, is_rule) in self.index.items()
        }

    def value(self, row: bytes, key: str) -> "bool | int":
        """One key of one packed row, as a Python scalar."""
        offset, is_rule = self.index[key]
        (v,) = self._item.unpack_from(row, offset * self._item.size)
        if not is_rule:
            return v != 0
        return -1 if self.compact and v == _RULE_ALLOWED_U8 else v

    def slice_of(self, keys: Sequence[str]) -> Callable[[bytes], Hashable]:
        """A function of a packed row: the raw elements of ``keys``, in
        one C-level pass (what those keys decode to is a pure function of
        it, so it can key a memo of anything computed from them)."""
        size = self._item.size
        spans = [self.index[key][0] * size for key in keys]
        if size == 1:
            return itemgetter(*spans)
        return itemgetter(*(slice(at, at + size) for at in spans))


class PackedRow:
    """The mapping face of one packed row: what the materializers read
    (``row[key]`` / ``row.get(key, default)``), decoded on demand, so a
    hit pays for the keys of its own target and no others."""

    __slots__ = ("_layout", "_row")

    def __init__(self, layout: OutputLayout, row: bytes) -> None:
        self._layout = layout
        self._row = row

    def __getitem__(self, key: str) -> "bool | int":
        return self._layout.value(self._row, key)

    def get(self, key: str, default: Any = None) -> Any:
        return self[key] if key in self._layout.index else default


def entry_cost(key: Hashable, row: "bytes | Mapping[str, Any]") -> int:
    """Accounted resident bytes of one cache entry (key + row). Constant
    time for a packed row; one pass over a dict row's items."""
    cost = _ENTRY_OVERHEAD
    if isinstance(key, tuple):
        for part in key:
            if isinstance(part, (bytes, bytearray, str)):
                cost += len(part)
    if isinstance(row, bytes):
        return cost + len(row)
    cost += _ROW_ITEM_COST * len(row)
    for v in row.values():
        nbytes = getattr(v, "nbytes", None)
        if nbytes is not None:
            cost += int(nbytes)
        elif isinstance(v, (bytes, str)):
            cost += len(v)
    return cost


class VerdictCache:
    """Thread-safe, byte-bounded LRU of cache key -> output row.

    One instance per tier (blob / row); the batched ``get_many`` /
    ``put_many`` entry points exist so a dispatch chunk pays ONE lock
    acquisition per tier per chunk instead of one per row (the per-row
    lock+move_to_end was part of the round-5 host bookkeeping floor).
    """

    def __init__(self, capacity_bytes: int) -> None:
        if capacity_bytes <= 0:
            raise ValueError("capacity_bytes must be positive")
        self.capacity_bytes = capacity_bytes
        # key -> row; rows are immutable once put, so entry_cost(key, row)
        # at eviction is what it was at the put
        self._data: OrderedDict[Hashable, Any] = OrderedDict()  # guarded-by: _lock
        self._bytes = 0  # guarded-by: _lock
        self._lock = threading.Lock()
        self.hits = 0  # guarded-by: _lock
        self.misses = 0  # guarded-by: _lock
        # entries pushed out by the byte bound (a re-put of a live key
        # replaces, it does not evict)
        self.evictions = 0  # guarded-by: _lock
        # entries put and their accounted bytes (a re-put counts again:
        # it is the work of a put that these measure)
        self.puts = 0  # guarded-by: _lock
        self.put_bytes = 0  # guarded-by: _lock

    def get(self, key: Hashable) -> "bytes | Mapping[str, Any] | None":
        with self._lock:
            row = self._data.get(key)
            if row is None:
                self.misses += 1
                return None
            self._data.move_to_end(key)
            self.hits += 1
            return row

    def get_many(
        self, keys: Iterable[Hashable | None]
    ) -> "list[bytes | Mapping[str, Any] | None]":
        """Batched get under ONE lock; ``None`` keys pass through as
        ``None`` without counting as misses (callers use them for
        uncacheable rows to keep index alignment)."""
        out: list = []
        with self._lock:
            data = self._data
            hits = misses = 0
            for key in keys:
                if key is None:
                    out.append(None)
                    continue
                row = data.get(key)
                if row is None:
                    misses += 1
                else:
                    data.move_to_end(key)
                    hits += 1
                out.append(row)
            self.hits += hits
            self.misses += misses
        return out

    def adjust_counts(self, hits: int = 0, misses: int = 0) -> None:
        """Re-scale hit/miss accounting to ROW granularity: a batched
        ``get_many`` over deduplicated combo keys counts one hit per KEY,
        but one key may answer many rows of the chunk — the caller adds
        the per-row remainder so the counters keep round-5's meaning
        (rows served from / missed by this tier)."""
        with self._lock:
            self.hits += hits
            self.misses += misses

    def _put_locked(self, key: Hashable, row: Any, cost: int) -> None:
        data = self._data
        old = data.pop(key, None)  # pop+reinsert lands at the MRU end
        if old is not None:
            self._bytes -= entry_cost(key, old)
        data[key] = row
        self._bytes += cost
        self.puts += 1
        self.put_bytes += cost
        while self._bytes > self.capacity_bytes and data:
            self._bytes -= entry_cost(*data.popitem(last=False))
            self.evictions += 1

    def put(self, key: Hashable, row: "bytes | Mapping[str, Any]") -> None:
        cost = entry_cost(key, row)
        with self._lock:
            self._put_locked(key, row, cost)

    def put_many(
        self,
        pairs: "Iterable[tuple[Hashable, bytes | Mapping[str, Any]]]",
    ) -> None:
        """Batched put under ONE lock, the costs taken before it."""
        costed = [(key, row, entry_cost(key, row)) for key, row in pairs]
        with self._lock:
            for key, row, cost in costed:
                self._put_locked(key, row, cost)

    def __len__(self) -> int:
        # locked: len(OrderedDict) races a concurrent _put_locked's
        # pop/reinsert (graftcheck GB01 finding, round 8)
        with self._lock:
            return len(self._data)

    @property
    def bytes_used(self) -> int:
        with self._lock:
            return self._bytes

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self._bytes = 0

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "cache_hits": self.hits,
                "cache_misses": self.misses,
                "cache_evictions": self.evictions,
                "cache_puts": self.puts,
                "cache_put_bytes": self.put_bytes,
                "cache_entries": len(self._data),
                "cache_bytes": self._bytes,
                "cache_capacity": self.capacity_bytes,
            }


class ChunkPlan(NamedTuple):
    """Who answers each row of one encoded chunk. Every row the ok mask
    admits is in exactly one of ``hits`` and ``slot_rows``; positions are
    the chunk's own (the caller knows which request sits at each)."""

    # (position, cached row): answered by the row tier
    hits: "list[tuple[int, bytes | Mapping[str, Any]]]"
    # (slot, position): answered by row ``slot`` of the dispatch, its own
    # or, for an in-chunk duplicate, another row's
    slot_rows: list[tuple[int, int]]
    # the encode positions to ship, in slot order; None when nothing
    # collapsed: the encode buffer ships as it is, a slot is a position
    ship_pos: "np.ndarray | None"
    # rows of the dispatch that some request waits for
    n_rows: int
    # (key, slot) of what the dispatch teaches each tier
    row_puts: list[tuple[Hashable, int]]
    blob_puts: list[tuple[Hashable, int]]


def _row_identity(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(first, inverse)`` of the distinct rows of a contiguous 2-D
    array in ONE vectorized pass: a void view makes each row one
    comparable scalar, so ``np.unique`` replaces a per-row
    tobytes-and-dict loop."""
    void = rows.view(
        np.dtype((np.void, rows.shape[1] * rows.itemsize))
    ).ravel()
    _uniq, first, inverse = np.unique(
        void, return_index=True, return_inverse=True
    )
    return first, np.asarray(inverse).ravel()


def _combos(
    first: np.ndarray, inverse: np.ndarray, tids: np.ndarray, one_target: bool
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The distinct (target, row) combos of a chunk's rows: for each
    combo its target id, the first row that shows it and which distinct
    row it is; for each row its combo. Same row bytes under different
    targets share a dispatch slot but carry separate cache keys. With
    one target the combo space IS the row space."""
    m = first.size
    if one_target:
        return np.zeros(m, dtype=np.intp), first, np.arange(m), inverse
    combos, combo_first, combo_inverse = np.unique(
        tids * m + inverse, return_index=True, return_inverse=True
    )
    return (
        combos // m, combo_first, combos % m,
        np.asarray(combo_inverse).ravel(),
    )


class DedupTiers:
    """The two tiers in front of the device (module docstring) and every
    rule about them: lookup order, key shapes, back-fill, in-chunk
    duplicate collapse, how hits are counted. The environment holds one
    (None: caching off, or the oracle backend) and brings target keys
    (one shared tuple a target), blobs and encoded rows, no more.

    Keys: blob tier ``(target key, payload blob)``, row tier ``(target
    key, packed row bytes)``.

    Back-fill of the blob tier is BOUNDED: on uid-varying rollout
    traffic nearly every blob never recurs, and a put a row (~4 us) would
    churn the byte-bounded tier out of its genuine exact-replay entries.
    A dispatch teaches it one representative blob a dispatched slot, a
    row-tier hit one a hit combo a chunk, the host fast path's row-tier
    hit none (its blob went in when the row first missed). A replayed
    stream still converges, one representative a cycle."""

    Plan = ChunkPlan  # what plan() and passthrough() return

    def __init__(self, capacity_bytes: int) -> None:
        # the byte budget is split between the tiers
        self.row = VerdictCache(max(1, capacity_bytes // 2))
        self.blob = VerdictCache(max(1, capacity_bytes - capacity_bytes // 2))
        self._lock = threading.Lock()
        # rows answered by another identical row of the SAME chunk
        self._batch_dup_hits = 0  # guarded-by: _lock
        # tier hits the environment answered as pre-built fragments
        self._fragment_hits = 0  # guarded-by: _lock

    # -- before encode: exact payload replays ----------------------------

    def get_blobs(
        self,
        target_keys: Iterable[Hashable | None],
        blobs: Iterable[bytes],
    ) -> "list[bytes | Mapping[str, Any] | None]":
        """The blob tier's answer to each (target, blob) of a batch, ONE
        locked lookup; a None target key is an uncacheable row and comes
        back None, uncounted."""
        return self.blob.get_many(
            None if tkey is None else (tkey, blob)
            for tkey, blob in zip(target_keys, blobs)
        )

    # -- the host fast path: one request ---------------------------------

    def get_one(
        self,
        target_key: Hashable,
        blob: bytes,
        packed_row_of: Callable[[bytes], "bytes | None"],
    ) -> "tuple[bytes | Mapping[str, Any] | None, tuple | None]":
        """``(row, None)`` on a hit, else ``(None, keys)`` with what
        ``put_one`` files the evaluated row under. Blob tier first: its
        key is in hand, so an exact replay costs no encode; the row key
        (``packed_row_of``: a single-row encode, None where it cannot be
        had) is only paid for on a blob miss."""
        blob_key = (target_key, blob)
        row = self.blob.get(blob_key)
        if row is not None:
            return row, None
        row_key = None
        packed = packed_row_of(blob)
        if packed is not None:
            row_key = (target_key, packed)
            row = self.row.get(row_key)
            if row is not None:
                return row, None  # and no blob back-fill (class docstring)
        return None, (row_key, blob_key)

    def put_one(self, keys: tuple, row: "bytes | Mapping[str, Any]") -> None:
        row_key, blob_key = keys
        if row_key is not None:
            self.row.put(row_key, row)
        self.blob.put(blob_key, row)

    # -- after encode: one chunk -----------------------------------------

    def plan(
        self,
        packed: np.ndarray,
        ok_mask: np.ndarray,
        wasm_pos: Sequence[int],
        target_ids: np.ndarray,
        target_keys: Sequence[Hashable],
        blobs: Sequence[bytes],
    ) -> ChunkPlan:
        """Lay out one encoded chunk: a (target, row) combo the row tier
        holds is a hit for every row that shows it, in-chunk duplicates
        collapse onto one dispatched row, and only distinct missed rows
        ship (equal packed bytes, equal outputs: module docstring).

        ``target_ids[pos]`` indexes ``target_keys``. ``wasm_pos`` (ok
        positions, ascending) carry verdict bits beside the row that are
        no function of its bytes: never deduped or cached, they take the
        first slots of a compacted dispatch. ONE locked lookup and at
        most one locked back-fill; reads and writes the tiers and
        nothing else."""
        n_wasm = len(wasm_pos)
        dedup_pos = np.flatnonzero(ok_mask)
        if n_wasm:
            dedup_pos = dedup_pos[~np.isin(dedup_pos, wasm_pos)]
        hits: list = []
        slot_rows: list[tuple[int, int]] = []
        row_puts: list = []
        blob_puts: list = []
        uncompacted = False
        keep_pos = miss_rows = dedup_pos[:0]
        if dedup_pos.size:
            rows_arr = np.ascontiguousarray(packed[dedup_pos])
            first, inverse = _row_identity(rows_arr)
            combo_tid, combo_first, combo_row, combo_inverse = _combos(
                first, inverse, target_ids[dedup_pos], len(target_keys) == 1
            )
            keys = [
                (target_keys[tid], rows_arr[at].tobytes())
                for tid, at in zip(combo_tid.tolist(), combo_first.tolist())
            ]
            hits, hit_flags = self._row_tier_hits(
                keys, combo_inverse, dedup_pos, blobs
            )
            miss_rows = np.flatnonzero(~hit_flags[combo_inverse])
        if miss_rows.size:
            uniq_miss, miss_first = np.unique(
                inverse[miss_rows], return_index=True
            )
            dup_hits = int(miss_rows.size - uniq_miss.size)
            if dup_hits:
                with self._lock:
                    self._batch_dup_hits += dup_hits
            keep_pos = dedup_pos[miss_rows[miss_first]]
            # nothing collapsed: ship the encode buffer as it is
            uncompacted = (
                not n_wasm and not hits and not dup_hits
                and bool(ok_mask.all())
            )
            # the slot of each distinct missed row: its encode position
            # in the buffer as it is, else its place among the kept rows
            slot_of = np.empty(first.size, dtype=np.intp)
            slot_of[uniq_miss] = (
                keep_pos if uncompacted
                else np.arange(n_wasm, n_wasm + uniq_miss.size)
            )
            miss_pos = dedup_pos[miss_rows].tolist()
            slot_rows = list(
                zip(slot_of[inverse[miss_rows]].tolist(), miss_pos)
            )
            miss_combos = np.flatnonzero(~hit_flags)
            combo_slots = slot_of[combo_row[miss_combos]].tolist()
            row_puts = [
                (keys[k], slot)
                for k, slot in zip(miss_combos.tolist(), combo_slots)
            ]
            blob_puts = [
                ((target_keys[tid], blobs[pos]), slot)
                for tid, pos, slot in zip(
                    target_ids[keep_pos].tolist(), keep_pos.tolist(),
                    slot_of[uniq_miss].tolist(),
                )
            ]
        slot_rows += enumerate(wasm_pos)
        ship_pos = None
        if not uncompacted:
            ship_pos = np.concatenate(
                (np.asarray(wasm_pos, dtype=np.intp), keep_pos)
            )
        return ChunkPlan(
            hits, slot_rows, ship_pos, n_wasm + keep_pos.size,
            row_puts, blob_puts,
        )

    @staticmethod
    def passthrough(ok_mask: np.ndarray) -> ChunkPlan:
        """The plan of a chunk no tier stands in front of: every row
        rides its own encode position and nothing is learned."""
        slot_rows = [(pos, pos) for pos in np.flatnonzero(ok_mask).tolist()]
        return ChunkPlan([], slot_rows, None, len(slot_rows), [], [])

    def _row_tier_hits(
        self,
        keys: list[tuple[Hashable, bytes]],
        combo_inverse: np.ndarray,
        dedup_pos: np.ndarray,
        blobs: Sequence[bytes],
    ) -> tuple[list, np.ndarray]:
        """ONE locked lookup of a chunk's combo keys: ``(position, cached
        row)`` of every row a held combo answers, and which combos were
        held. Counts rows, and back-fills the blob tier with one
        representative blob a hit combo (class docstring)."""
        cached = self.row.get_many(keys)
        hit_flags = np.fromiter(
            (c is not None for c in cached), dtype=bool, count=len(cached)
        )
        hit_rows = np.flatnonzero(hit_flags[combo_inverse])
        # get_many counted one hit/miss per combo KEY; the counters mean
        # ROWS served from / missed by the row tier
        n_hit_keys = int(hit_flags.sum())
        self.row.adjust_counts(
            hits=int(hit_rows.size) - n_hit_keys,
            misses=(int(dedup_pos.size) - int(hit_rows.size))
            - (len(cached) - n_hit_keys),
        )
        hits = []
        backfill: dict[int, tuple] = {}  # the first row of each hit combo
        for pos, k in zip(
            dedup_pos[hit_rows].tolist(), combo_inverse[hit_rows].tolist()
        ):
            hits.append((pos, cached[k]))
            if k not in backfill:
                backfill[k] = ((keys[k][0], blobs[pos]), cached[k])
        if backfill:
            self.blob.put_many(backfill.values())
        return hits, hit_flags

    def learn(self, plan: ChunkPlan, fetched: np.ndarray) -> None:
        """File the fetched output rows of a planned dispatch under both
        tiers' keys. A dispatched row's entry IS its bytes of the fetched
        array: one copy of the batch, a slice a slot, the same object
        under every key of both tiers."""
        if not (plan.row_puts or plan.blob_puts):
            return
        width = fetched.shape[1] * fetched.itemsize
        whole = fetched.tobytes()
        rows = [whole[at : at + width] for at in range(0, len(whole), width)]
        if plan.row_puts:
            self.row.put_many(
                [(key, rows[slot]) for key, slot in plan.row_puts]
            )
        if plan.blob_puts:
            self.blob.put_many(
                [(key, rows[slot]) for key, slot in plan.blob_puts]
            )

    # -- counters ---------------------------------------------------------

    def count_fragment_hits(self, n: int) -> None:
        with self._lock:
            self._fragment_hits += n

    @property
    def batch_dup_hits(self) -> int:
        with self._lock:
            return self._batch_dup_hits

    def clear(self) -> None:
        """Drop every cached row of both tiers; the counters are
        cumulative serving metrics and stay."""
        self.row.clear()
        self.blob.clear()

    def stats(self) -> dict[str, int]:
        """``cache_*`` keys are the row tier (legacy names), ``blob_*``
        the blob tier."""
        stats = self.row.stats()
        for k, v in self.blob.stats().items():
            stats["blob_" + k] = v
        with self._lock:
            stats["batch_dup_hits"] = self._batch_dup_hits
            stats["fragment_hits"] = self._fragment_hits
        return stats

    @classmethod
    def stats_when_off(cls) -> dict[str, int]:
        """``stats()`` of an environment that holds no tiers: the same
        keys, all zero."""
        return dict.fromkeys(cls(2).stats(), 0)
