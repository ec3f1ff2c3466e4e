"""Fault-injection failpoints — the chaos harness behind `make chaos`.

The serving stack calls ``fire("site")`` at a handful of named failure
sites (device fetch, batch encode, registry HTTP, cert reload). With no
failpoints configured — the production state — ``fire`` is a single
attribute test on a module global and returns immediately: zero
allocations, no dict lookups, no locks on the hot path.

Activation, either:

* environment/config string (``FAILPOINTS`` env var, read at import and
  re-readable via :func:`configure_from_env`)::

      FAILPOINTS="device.fetch=sleep:5;fetch.http=raise:boom*3"

  grammar per entry: ``site=action[:param][*count][@scope]`` —
  ``raise[:message]`` raises :class:`FailpointError`, ``sleep:seconds``
  blocks, ``off`` clears the site. ``*count`` disarms the action after
  it fired ``count`` times (the retry-then-succeed shape chaos tests
  need). ``@scope`` arms it only for threads carrying that ambient
  scope (see below): ``device.fetch=raise@default`` faults the default
  tenant's SERVING dispatches of a server process while its boot
  warm-up, which runs unscoped, goes through.

* programmatic (tests): ``set_failpoint("site", fn, count=None)``
  installs any callable — an Event-gated hang, a custom exception —
  or use the :func:`active` context manager for scoped injection.

**Tenant scoping** (round 16): multi-tenant chaos needs a fault that
hits ONE tenant's serving path while the others share the same process
and the same ``fire`` sites. ``set_failpoint(..., scope="tenant-a")``
arms the action only for threads whose ambient failpoint scope (a
thread-local the batcher/lifecycle set around tenant-owned work via
:func:`scope`) matches; unscoped failpoints fire everywhere, preserving
every existing arming. Scope propagation is explicit — the code that
hands tenant work to another thread wraps it in ``with scope(name):``.

Sites instrumented (grep for ``failpoints.fire``):

==================  =====================================================
``device.fetch``    device result fetch (environment._device_fetch) —
                    ``sleep`` = hung transport, ``raise`` = dispatch fault
``encode.batch``    host batch encode (native pipeline + bucketed encode)
``fetch.http``      registry/HTTPS GET (fetch/downloader) — injected
                    failures are retryable, like a real 5xx/timeout
``certs.reload``    TLS identity reload (certs.py) — simulates corrupted
                    on-disk cert material mid-rotation
``reload.fetch``    policy hot-reload fetch stage (lifecycle.py) —
                    ``raise`` = unreadable/unfetchable policies config;
                    the reload rejects and last-good keeps serving
``reload.compile``  policy hot-reload compile+warm stage (lifecycle.py)
                    — ``raise`` = a candidate set that fails to build;
                    ``sleep`` = a compile stall (reload stays
                    background; serving is untouched)
``reload.canary``   policy hot-reload shadow canary (lifecycle.py) —
                    ``raise`` = canary infrastructure fault; the
                    candidate is rejected, never promoted
``audit.sweep``     background audit sweep head (audit/scanner.py) —
                    ``raise`` = sweep infrastructure fault; the sweep
                    aborts (un-judged keys re-marked dirty), the error
                    is counted, and the scanner retries on the next
                    trigger; live serving is untouched
``watch.stream``    audit watch-feed stream connect (audit/
                    watch_feed.py) — ``raise`` = watch transport fault;
                    the kind's loop backs off and recovers through a
                    counted full re-LIST resync, the snapshot keeps
                    serving its last good inventory
``frontend.accept`` native frontend burst intake (runtime/
                    native_frontend.py drain loop) — ``raise`` = a
                    fault between framing and admission; every request
                    of the poll burst answers an in-band 500 instead of
                    stranding, and the drainer keeps running
``tenant.reload``   per-tenant policies.yml re-read at the head of a
                    tenant's reload pipeline (tenancy.py read_policies
                    closure) — ``raise`` = one tenant's manifest became
                    unreadable; THAT tenant rejects at the fetch stage
                    and keeps serving last-good, every other tenant's
                    reload (e.g. the same SIGHUP) proceeds untouched
``tenant.admission`` per-tenant admission quota check (tenancy.py
                    TenantAdmission.admit) — ``raise`` = an admission-
                    layer fault for one tenant; its requests answer
                    in-band errors while other tenants admit normally
``tls.handshake``   native TLS accept path (runtime/native_frontend.py
                    NativeTlsManager failpoint poll) — an armed
                    ``raise`` makes the native loops refuse EVERY new
                    handshake (counted, alert sent, connection closed)
                    until the site disarms; established connections
                    keep serving, so the blast radius is accept-only
``shard.dispatch``  top of each MicroBatcher dispatch-loop iteration
                    (runtime/batcher.py _loop), BEFORE any queue pop —
                    an armed ``raise`` kills that shard's dispatch
                    thread holding zero rows, the shard-death drill:
                    the router's heartbeat fences the shard (queued
                    rows re-route to a sibling or answer 503) and
                    warm-revives it. Scope with the shard's failpoint
                    scope (``shard-<i>``) to kill one specific shard
``shard.heartbeat`` head of each per-shard heartbeat probe
                    (runtime/shards.py ShardRouter), under that
                    shard's ``shard-<i>`` scope — ``raise`` = the
                    probe itself faults for one shard; the router
                    counts it and treats the shard as unprobeable
                    (fenced) until the site disarms
==================  =====================================================

Every fire is counted (``fired_count(site)``) so chaos tests can assert
an injection actually intercepted the path it claims to cover.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable

ENV_VAR = "FAILPOINTS"


class FailpointError(Exception):
    """The injected fault for ``raise`` actions."""


class _Point:
    __slots__ = ("fn", "remaining", "scope")

    def __init__(
        self,
        fn: Callable[[], None],
        remaining: int | None,
        scope: str | None = None,
    ):
        self.fn = fn
        self.remaining = remaining  # None = unlimited
        self.scope = scope  # None = fire for every thread


_lock = threading.Lock()
_points: dict[str, _Point] = {}  # guarded-by: _lock
_fired: dict[str, int] = {}  # guarded-by: _lock
# the ONE hot-path gate: False ⇒ fire() returns before touching any dict
# graftcheck: lockfree — single bool, stale reads only delay (dis)arming
_armed = False


def fire(site: str) -> None:
    """Trigger the failpoint for ``site`` if one is armed; no-op (one
    global check) otherwise. Called from serving hot paths — per batch,
    never per row."""
    if not _armed:
        return
    _fire_slow(site)


def _fire_slow(site: str) -> None:
    with _lock:
        point = _points.get(site)
        if point is None:
            return
        if point.scope is not None and point.scope != current_scope():
            return  # scoped to another tenant's threads: no-op
        if point.remaining is not None:
            if point.remaining <= 0:
                return
            point.remaining -= 1
            if point.remaining == 0:
                # leave the exhausted point in place (fired counts keep
                # accumulating semantics simple); it no longer fires
                pass
        _fired[site] = _fired.get(site, 0) + 1
        fn = point.fn
    fn()  # OUTSIDE the lock: a sleeping/hanging action must not block
    # concurrent fire() calls on other sites


def set_failpoint(
    site: str,
    fn: Callable[[], None],
    count: int | None = None,
    scope: str | None = None,
) -> None:
    """Install a callable to run on every ``fire(site)`` (at most
    ``count`` times when given; only for threads whose ambient
    failpoint scope matches when ``scope`` is given — the multi-tenant
    chaos knob)."""
    global _armed
    with _lock:
        _points[site] = _Point(fn, count, scope)
        _armed = True


# -- tenant scoping (thread-local ambient scope) ----------------------------

_tls = threading.local()


def current_scope() -> str | None:
    """The calling thread's ambient failpoint scope (None outside any
    ``with scope(...)`` block)."""
    return getattr(_tls, "scope", None)


class scope:
    """Set the ambient failpoint scope for the calling thread::

        with failpoints.scope("tenant-a"):
            ...  # scoped failpoints for tenant-a fire here

    Nests (the previous scope is restored on exit); a ``None`` name is a
    no-op passthrough so call sites need no conditional."""

    __slots__ = ("name", "_prev")

    def __init__(self, name: str | None):
        self.name = name
        self._prev: str | None = None

    def __enter__(self) -> "scope":
        self._prev = getattr(_tls, "scope", None)
        if self.name is not None:
            _tls.scope = self.name
        return self

    def __exit__(self, *exc) -> None:
        if self.name is not None:
            _tls.scope = self._prev


def clear(site: str | None = None) -> None:
    """Remove one site's failpoint, or all of them (``site=None``)."""
    global _armed
    with _lock:
        if site is None:
            _points.clear()
        else:
            _points.pop(site, None)
        _armed = bool(_points)


def reset() -> None:
    """Full reset: clear every failpoint AND the fired counters."""
    clear()
    with _lock:
        _fired.clear()


def fired_count(site: str) -> int:
    with _lock:
        return _fired.get(site, 0)


class active:
    """Scoped injection for tests::

        with failpoints.active("device.fetch", lambda: time.sleep(2)):
            ...
    """

    def __init__(
        self,
        site: str,
        fn: Callable[[], None],
        count: int | None = None,
        scope: str | None = None,
    ):
        self.site = site
        self.fn = fn
        self.count = count
        self.scope = scope

    def __enter__(self) -> "active":
        set_failpoint(self.site, self.fn, self.count, scope=self.scope)
        return self

    def __exit__(self, *exc) -> None:
        clear(self.site)


# ---------------------------------------------------------------------------
# String/env configuration
# ---------------------------------------------------------------------------


def _parse_action(spec: str) -> tuple[Callable[[], None], int | None]:
    """``action[:param][*count]`` → (callable, count)."""
    count: int | None = None
    if "*" in spec:
        spec, _, c = spec.rpartition("*")
        count = int(c)
    action, _, param = spec.partition(":")
    action = action.strip().lower()
    if action == "raise":
        message = param or "injected fault"

        def fn() -> None:
            raise FailpointError(message)

        return fn, count
    if action == "sleep":
        seconds = float(param or "1")

        def fn() -> None:
            time.sleep(seconds)

        return fn, count
    raise ValueError(f"unknown failpoint action {action!r}")


def configure(spec: str) -> None:
    """Install failpoints from a ``site=action;site=action`` string.
    ``site=off`` clears that site; an empty string clears everything."""
    spec = (spec or "").strip()
    if not spec:
        reset()
        return
    for entry in spec.split(";"):
        entry = entry.strip()
        if not entry:
            continue
        site, sep, action = entry.partition("=")
        if not sep:
            raise ValueError(f"malformed failpoint entry {entry!r}")
        site = site.strip()
        if action.strip().lower() == "off":
            clear(site)
            continue
        action, _, scope_name = action.partition("@")
        fn, count = _parse_action(action)
        set_failpoint(site, fn, count, scope=scope_name.strip() or None)


def configure_from_env() -> None:
    spec = os.environ.get(ENV_VAR, "")
    if spec:
        configure(spec)


configure_from_env()
