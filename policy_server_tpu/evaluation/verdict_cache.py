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
from typing import Any, Callable, Hashable, Iterable, Mapping, Sequence

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
