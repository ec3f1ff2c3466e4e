"""Snapshot store — the audit scanner's view of the cluster.

The reference delegates continuous re-scanning to an external companion
(Kubewarden's audit-scanner) that LISTs cluster resources and replays
them through ``POST /audit/{policy_id}``. This build keeps the scan
in-process, so it needs its own resource inventory. Two feeds populate
it:

* **Dirty-set tracking** — every object served through ``/validate`` is
  recorded per formed batch by :class:`~policy_server_tpu.runtime.
  batcher.MicroBatcher` (the same one-call-per-batch discipline as the
  round-9 shadow-canary ring), keyed by GVK + namespace + name so a
  later admission of the same object SUPERSEDES the earlier snapshot —
  the store always holds the newest served generation. A ``DELETE``
  admission evicts the key (the object is gone; re-auditing it would
  report on a resource the cluster no longer has).
* **File seeding** (``--audit-resources-file``) — a YAML/JSON list of
  Kubernetes objects (or a ``List``-style ``{items: [...]}`` document)
  synthesized into CREATE admission reviews, the stand-in for the
  companion scanner's initial cluster LIST when no traffic has been
  served yet.

Rows are kept payload-encoded (``ValidateRequest.payload_json`` is
memoized, and the live path computed it already), so a sweep re-submits
pre-encoded rows and the verdict-cache/dedup tiers make re-scans of
unchanged objects nearly free. Memory is bounded by
``--audit-max-snapshot-bytes`` with LRU eviction on the recording order.

A request that can ``freeze()`` itself (the native front-end's zero-parse
request: payload bytes and four header strings) is kept as that tuple of
atoms and made a request again by the class that froze it when a sweep
reaches it (:meth:`SnapshotStore.request_of`). The collector stops
tracking such a tuple at its first pass, where the request and its
header are five objects every full pass walks, GIL held, for as long as
the store keeps them: with ~76,000 of them and a sweep's rows as a list
beside them the passes took 4.5 s of a 20 s saturated window on the chip
and the cell's runs spread 7-10%; this way 1.2-1.3 s and 2-3% (PR 38).

The budget also bounds what the scanner can cover. An object it pushes
out is gone for the scanner as a deleted one is: the scanner drops its
report rows (:meth:`SnapshotStore.take_evictions`) and a sweep that had
collected it skips it (:meth:`SnapshotStore.holds`), so the reports speak
of resident objects only. One pushed out while still dirty was never
re-judged at all, and is counted (``evicted_dirty``): when admissions
outrun the lane for longer than the budget lasts, that is most of them
(PR 38, on the chip: ~76,600 pods fit in 64Mi, a saturated server
records ~5,500 a second and re-judges ~25).
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Iterable

from policy_server_tpu.models import (
    AdmissionRequest,
    GroupVersionKind,
    ValidateRequest,
)
from policy_server_tpu.telemetry.tracing import logger


def _payload_json(stored: Any) -> bytes:
    """The payload bytes of a stored entry: a frozen request's first
    item, else the request's own (memoized)."""
    return stored[0] if type(stored) is tuple else stored.payload_json()


def _nbytes(stored: Any) -> int:
    """What a stored entry counts against the byte budget."""
    return len(_payload_json(stored))


def synthesize_review(
    obj: Any, operation: str = "CREATE", uid: str | None = None
) -> ValidateRequest | None:
    """One Kubernetes object → a synthetic admission review the snapshot
    store can record: the stand-in for the review the API server would
    have sent had this object been admitted through the webhook. Used by
    file seeding (CREATE rows) and by the live watch feed (ADDED →
    CREATE, MODIFIED → UPDATE, DELETED → DELETE — the DELETE shape only
    needs the identity fields; :meth:`SnapshotStore.observe` evicts on
    it without storing the payload). Returns ``None`` for objects with
    no usable kind."""
    if not isinstance(obj, dict) or "kind" not in obj:
        return None
    api_version = obj.get("apiVersion", "v1") or "v1"
    group, _, version = api_version.rpartition("/")
    meta = obj.get("metadata") or {}
    gvk = GroupVersionKind(
        group=group, version=version, kind=obj.get("kind", "")
    )
    uid = uid or meta.get("uid") or f"audit-synth-{id(obj):x}"
    name = meta.get("name") or uid
    req = AdmissionRequest(
        uid=uid,
        kind=gvk,
        name=name,
        namespace=meta.get("namespace"),
        operation=operation,
        user_info={"username": "system:policy-server-audit"},
        object=None if operation == "DELETE" else obj,
        dry_run=True,
    )
    return ValidateRequest.from_admission(req)


def resource_key(request: ValidateRequest) -> str | None:
    """GVK + namespace + name identity of the object an admission review
    targets; ``None`` for rows the store cannot track (raw requests,
    nameless reviews with no uid to fall back on)."""
    adm = request.admission_request
    if adm is None:
        return None
    kind = adm.kind or GroupVersionKind()
    name = adm.name or adm.uid
    if not name:
        return None
    return "/".join(
        (kind.group, kind.version, kind.kind, adm.namespace or "", name)
    )


class SnapshotStore:
    """Bounded, dirty-tracking inventory of cluster resources as
    admission requests (see module docstring). Thread-safe: the
    micro-batcher records from its dispatch workers while the scanner
    collects from its sweep thread."""

    def __init__(self, max_bytes: int = 64 * 1024 * 1024):
        self.max_bytes = max(0, int(max_bytes))
        self._lock = threading.Lock()
        # key -> the request as stored (itself, or what it froze to);
        # insertion order is the LRU axis
        self._rows: collections.OrderedDict[
            str, Any
        ] = collections.OrderedDict()  # guarded-by: _lock
        # the class whose requests froze themselves into the store: it
        # thaws them (one front-end a process, so one class)
        self._thaw: Any = None
        self._dirty: set[str] = set()  # guarded-by: _lock
        # keys evicted by an observed DELETE since the last sweep — the
        # scanner drains these to prune the objects' report rows
        self._pending_deletions: set[str] = set()  # guarded-by: _lock
        # keys the byte budget pushed out since the last sweep: their
        # report rows go too, but nothing was deleted from the cluster
        self._pending_evictions: set[str] = set()  # guarded-by: _lock
        self._bytes = 0  # guarded-by: _lock
        self._recorded = 0  # guarded-by: _lock
        self._superseded = 0  # guarded-by: _lock
        self._evicted = 0  # guarded-by: _lock
        self._evicted_dirty = 0  # guarded-by: _lock
        self._deleted = 0  # guarded-by: _lock
        # bumps on every mutating observe — the /audit/reports ETag axis
        self._generation = 0  # guarded-by: _lock

    # -- recording (the batcher's dirty-set tracker) -----------------------

    def observe(self, requests: Iterable[ValidateRequest]) -> None:
        """Record a batch of served ``/validate`` requests. Called once
        per formed batch from the dispatch worker — sizes are computed
        OUTSIDE the lock (payload_json is memoized; the encoder reuses
        it, so this is not wasted work)."""
        prepared: list[tuple[str, Any, int]] = []
        for request in requests:
            key = resource_key(request)
            if key is None:
                continue
            adm = request.admission_request
            if adm is not None and (adm.operation or "").upper() == "DELETE":
                prepared.append((key, None, 0))
                continue
            freeze = getattr(request, "freeze", None)
            if freeze is None:
                stored = request
            else:
                stored, self._thaw = freeze(), type(request).thaw
            prepared.append((key, stored, len(request.payload_json())))
        if not prepared:
            return
        with self._lock:
            self._generation += 1
            for key, stored, nbytes in prepared:
                old = self._rows.pop(key, None)
                if old is not None:
                    self._bytes -= _nbytes(old)
                if stored is None:
                    if old is not None:
                        self._deleted += 1
                    self._dirty.discard(key)
                    self._pending_deletions.add(key)
                    continue
                self._pending_deletions.discard(key)  # re-created object
                self._pending_evictions.discard(key)
                if old is not None:
                    self._superseded += 1
                self._rows[key] = stored
                self._bytes += nbytes
                self._recorded += 1
                self._dirty.add(key)
            self._evict_over_budget_locked()

    def _evict_over_budget_locked(self) -> None:
        # holds: _lock
        if self.max_bytes <= 0:
            return
        while self._bytes > self.max_bytes and self._rows:
            key, stored = self._rows.popitem(last=False)
            self._bytes -= _nbytes(stored)
            self._evicted += 1
            if key in self._dirty:  # no sweep ever collected it
                self._dirty.discard(key)
                self._evicted_dirty += 1
            self._pending_evictions.add(key)

    def request_of(self, stored: Any) -> ValidateRequest:
        """The request a stored entry stands for: itself, or, for one
        that froze, a new request each time, so keep it no longer than
        the work that needs it."""
        return self._thaw(stored) if type(stored) is tuple else stored

    # -- durable spill (round 17, statestore.py) ---------------------------

    def export_rows(self) -> list[tuple[str, bytes]]:
        """One locked snapshot of the inventory as ``(key, payload_json)``
        pairs — the audit-spill corpus (payload_json is memoized, so this
        is serialization-free for rows the live path already encoded)."""
        with self._lock:
            items = list(self._rows.items())
        return [(key, _payload_json(stored)) for key, stored in items]

    def restore_rows(self, pairs: Iterable[tuple[str, bytes]]) -> int:
        """Rebuild inventory rows from a spill's pre-encoded payloads (a
        warm boot's snapshot seed — the watch feed then RESUMES from its
        spilled resourceVersion instead of re-LISTing the cluster).
        Undecodable rows are skipped loudly; the next full re-LIST
        repairs whatever a damaged spill lost."""
        import json as _json

        restored: list[ValidateRequest] = []
        skipped = 0
        for _key, payload in pairs:
            try:
                req = AdmissionRequest.from_dict(_json.loads(payload))
                restored.append(ValidateRequest.from_admission(req))
            except Exception:  # noqa: BLE001 — a damaged row must not
                skipped += 1  # fail the boot; the resync repairs it
        self.observe(restored)
        if skipped:
            logger.warning(
                "audit spill restore skipped %d undecodable row(s); the "
                "next full re-LIST resync repairs the inventory", skipped,
            )
        return len(restored)

    # -- seeding -----------------------------------------------------------

    def seed_from_file(self, path: str) -> int:
        """Load a YAML/JSON resources file (a list of objects or a
        ``{items: [...]}`` List document) and record one synthetic
        CREATE review per object. Returns the number of rows seeded."""
        import yaml

        with open(path, "r", encoding="utf-8") as f:
            doc = yaml.safe_load(f)
        if isinstance(doc, dict) and "items" in doc:
            objects = doc["items"]
        elif isinstance(doc, list):
            objects = doc
        else:
            raise ValueError(
                f"audit resources file {path!r} must hold a list of "
                "objects or a List document with an 'items' field"
            )
        seeded = 0
        batch: list[ValidateRequest] = []
        for i, obj in enumerate(objects):
            req = synthesize_review(obj, "CREATE", uid=f"audit-seed-{i}")
            if req is not None:
                batch.append(req)
                seeded += 1
        self.observe(batch)
        logger.info(
            "audit snapshot seeded from resources file",
            extra={"span_fields": {"path": path, "resources": seeded}},
        )
        return seeded

    # -- collection (the scanner's sweep feed) -----------------------------

    def collect(
        self, dirty_only: bool = False
    ) -> list[tuple[str, Any]]:
        """Snapshot the sweep corpus and clear the dirty set: the FULL
        inventory, or only the keys touched since the last collect, each
        with its entry as stored (:meth:`request_of` gives the request).
        A failed sweep re-marks its unscanned keys via
        :meth:`remark_dirty` so the next sweep picks them back up."""
        with self._lock:
            if dirty_only:
                keys = [k for k in self._dirty if k in self._rows]
            else:
                keys = list(self._rows)
            self._dirty.clear()
            return [(k, self._rows[k]) for k in keys]

    def remark_dirty(self, keys: Iterable[str]) -> None:
        with self._lock:
            self._dirty.update(k for k in keys if k in self._rows)

    def clear_dirty(self, keys: Iterable[str]) -> int:
        """Drop dirty marks for rows proven current by other means — the
        verdict matrix's warm-boot restore clears the marks its restored
        columns fully cover, so the boot sweep re-judges nothing that is
        provably up to date."""
        with self._lock:
            before = len(self._dirty)
            self._dirty.difference_update(keys)
            return before - len(self._dirty)

    def rows_snapshot(self) -> list[tuple[str, ValidateRequest]]:
        """The full inventory WITHOUT clearing dirty marks — the verdict
        matrix's row axis (clean-rows × dirty-columns sweeps and warm-
        boot payload-hash validation read this; :meth:`collect` remains
        the only consumer that claims the dirty set)."""
        with self._lock:
            items = list(self._rows.items())
        return [(k, self.request_of(stored)) for k, stored in items]

    def dirty_keys(self) -> set[str]:
        with self._lock:
            return set(self._dirty)

    def take_deletions(self) -> set[str]:
        """Drain the keys evicted by observed DELETEs since the last
        call — the scanner prunes their report rows."""
        with self._lock:
            out = self._pending_deletions
            self._pending_deletions = set()
            return out

    def take_evictions(self) -> set[str]:
        """Drain the keys the byte budget pushed out since the last call:
        the scanner drops their report rows, as it does a deleted
        object's (the matrix keeps its own bound: a full sweep's
        ``retain``)."""
        with self._lock:
            out = self._pending_evictions
            self._pending_evictions = set()
            return out

    def holds(self, keys: Iterable[str]) -> set[str]:
        """Of ``keys``, those still in the store: a sweep asks before it
        spends a lane job on objects collected a while ago."""
        with self._lock:
            return {k for k in keys if k in self._rows}

    # -- introspection -----------------------------------------------------

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {
                "resources": len(self._rows),
                "bytes": self._bytes,
                "dirty": len(self._dirty),
                "generation": self._generation,
                "recorded": self._recorded,
                "superseded": self._superseded,
                "evicted": self._evicted,
                "evicted_dirty": self._evicted_dirty,
                "deleted": self._deleted,
            }

    def __len__(self) -> int:
        with self._lock:
            return len(self._rows)
