#!/usr/bin/env python3
"""One load-generating client process of the benchmark.

Modelled on kube-apiserver's webhook caller: HTTPS, keep-alive, ONE request
in flight per connection, no pipelining. The harness starts K of these
(none touches JAX); each draws the run's pool from the seed itself and
sends its slice: request numbers ``base + k, base + k + K, ...``.

The parent speaks lines on stdin; each gets one answer on stdout:

* first line: the JSON spec (port, cafile, seed, mix, policy ids, k, K);
  answered ``pooled`` once the pool is drawn and serialised;
* ``connect`` (once the server listens): ``ready`` once the first
  connections are open;
* ``warm <base> <count>``: a pass of this client's share of ``count``
  requests in the window's own shape (closed loop, or the mix's Poisson
  rate) → ``warm <sent> <failed>``;
* ``window <base> <t0> <seconds> [rate]``: the measured window (``rate``
  in place of the mix's, for the sweep that finds it). Closed loop: every
  connection sends its next request when the last is answered, until
  ``t0 + seconds``. Open loop: a Poisson schedule drawn from the seed (in
  bursts, if the mix has ``burst``); each
  request goes out at its due time on an idle connection, or a new one up
  to the cap, else waits for one — latency runs from the due time.
  Requests in flight at the close are waited for up to ``timeout_s``.
  → ``result <bytes>`` and that many bytes of pickle;
* ``quit``.

Times are ``time.monotonic()``, one clock for every process of a host.
"""

from __future__ import annotations

import asyncio
import collections
import gc
import json
import pickle
import random
import re
import ssl
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from traffic import Traffic  # noqa: E402

_LENGTH = re.compile(rb"\r\ncontent-length:\s*(\d+)", re.IGNORECASE)
now = time.monotonic
# an open loop polls for at most this long before a request is due; kept at
# the selector's one millisecond, because K processes polling all window long
# take K cores from the server they share the machine with
SPIN_S = 0.001


def poisson_schedule(seed: int, rate: float, seconds: float,
                     burst: dict | None = None) -> list[float]:
    """Due times, in seconds from the window's start, of the whole cell's
    arrivals: a Poisson process at ``rate`` conditioned on its count
    (``rate x seconds`` arrivals at independent uniform instants), so every
    seed offers the same number of requests in another order. With the
    mix's ``burst`` (``{"period_s": 1.0, "on_s": 0.1}``) each period's
    arrivals come in its first ``on_s`` seconds and none in the rest: the
    same count, at ``period_s / on_s`` times the rate while it is on."""
    rng = random.Random(seed ^ 0x5EEDA221)
    due = sorted(rng.uniform(0.0, seconds)
                 for _ in range(round(rate * seconds)))
    if burst:
        period, on = float(burst["period_s"]), float(burst["on_s"])
        due = [t // period * period + t % period * on / period for t in due]
    return due


def strip_date(raw: bytes) -> bytes:
    """A response without its Date header line: what is compared."""
    head, sep, body = raw.partition(b"\r\n\r\n")
    kept = [line for line in head.split(b"\r\n")
            if not line[:5].lower() == b"date:"]
    return b"\r\n".join(kept) + sep + body


class Conn(asyncio.Protocol):
    """One keep-alive connection with at most one request in flight."""

    def __init__(self, runner: "Runner") -> None:
        self.runner = runner
        self.transport: asyncio.Transport | None = None
        self.buf = b""
        self.inflight: tuple[int, float, float] | None = None  # n, due, sent

    def connection_made(self, transport) -> None:
        self.transport = transport

    def send(self, n: int, due: float) -> None:
        self.transport.write(self.runner.traffic.request(n))
        self.inflight = (n, due, now())

    def data_received(self, data: bytes) -> None:
        buf = self.buf + data if self.buf else data
        end = buf.find(b"\r\n\r\n")
        if end >= 0:
            m = _LENGTH.search(buf, 0, end + 2)
            need = end + 4 + (int(m.group(1)) if m else 0)
            if len(buf) >= need:
                self.buf = buf[need:]
                inflight, self.inflight = self.inflight, None
                if inflight is not None:
                    self.runner.answered(self, inflight, buf[:need])
                return
        self.buf = buf

    def connection_lost(self, exc) -> None:
        self.runner.lost(self)


class Runner:
    def __init__(self, spec: dict) -> None:
        self.spec = spec
        self.mix = spec["mix"]
        self.k, self.K = spec["k"], spec["K"]
        self.traffic = Traffic(self.mix, spec["seed"], spec["policy_ids"])
        self.traffic.request(0)  # serialise the pool now, not in a window
        self.tls = None
        if spec.get("cafile"):
            self.tls = ssl.create_default_context(cafile=spec["cafile"])
        self.conns: set[Conn] = set()
        # idle connections are taken oldest first, so each is used every
        # moment and none reaches the server's keep-alive limit (75 s)
        self.idle: collections.deque[Conn] = collections.deque()
        self.opening: set[asyncio.Task] = set()
        self.records: list[tuple] = []  # n, due, sent, done, raw | None
        self.on_answer = None
        self.timeout_s = float(self.mix.get("timeout_s", 10))
        closed = self.mix["arrival"] == "closed"
        total = int(self.mix["connections" if closed else "max_connections"])
        first = total if closed else int(self.mix["initial_connections"])
        self.cap = self._share(total)
        self.first = min(self.cap, self._share(first))

    def _share(self, total: int) -> int:
        return total // self.K + (1 if self.k < total % self.K else 0)

    async def open(self, count: int) -> None:
        """Open ``count`` connections, 32 handshakes at a time."""
        loop = asyncio.get_running_loop()
        for start in range(0, count, 32):
            made = await asyncio.gather(*(
                loop.create_connection(
                    lambda: Conn(self), "127.0.0.1", self.spec["port"],
                    ssl=self.tls,
                    server_hostname="localhost" if self.tls else None,
                )
                for _ in range(min(32, count - start))
            ))
            for _transport, conn in made:
                self.conns.add(conn)
                self.released(conn)

    def open_one_more(self) -> None:
        """Start opening a connection, if the cap allows one more."""
        if len(self.conns) + len(self.opening) >= self.cap:
            return
        task = asyncio.ensure_future(self.open(1))
        self.opening.add(task)
        task.add_done_callback(self._opened)

    def _opened(self, task: asyncio.Task) -> None:
        self.opening.discard(task)
        if not task.cancelled() and task.exception() is not None:
            print(f"client {self.k}: a connection failed to open: "
                  f"{task.exception()!r}", file=sys.stderr, flush=True)

    # -- events ------------------------------------------------------------

    def answered(self, conn: Conn, inflight: tuple, raw: bytes) -> None:
        n, due, sent = inflight
        self.records.append((n, due, sent, now(), raw))
        self.released(conn)

    def lost(self, conn: Conn) -> None:
        self.conns.discard(conn)
        if conn in self.idle:
            self.idle.remove(conn)
        if conn.inflight is not None:
            n, due, sent = conn.inflight
            conn.inflight = None
            self.records.append((n, due, sent, now(), None))
            if self.on_answer is not None:
                self.on_answer()

    def released(self, conn: Conn) -> None:
        if self.on_answer is None or not self.on_answer(conn):
            self.idle.append(conn)

    # -- passes ------------------------------------------------------------

    async def closed_loop(self, numbers, until: float | None) -> None:
        """Every open connection sends the next number of ``numbers`` when
        its last is answered, until they run out or ``until`` passes; then
        waits for what is in flight."""
        done = asyncio.get_running_loop().create_future()
        it = iter(numbers)
        over = False

        def next_on(conn: Conn | None = None) -> bool:
            nonlocal over
            if conn is not None and not over:
                if until is None or now() < until:
                    n = next(it, None)
                    if n is not None:
                        conn.send(n, now())
                        return True
                over = True
            if over and not done.done() and not any(
                    c.inflight for c in self.conns):
                done.set_result(None)
            return False

        self.on_answer = next_on
        try:
            conns, self.idle = self.idle, collections.deque()
            for conn in conns:
                if not next_on(conn):
                    self.idle.append(conn)
            wait = self.timeout_s + ((until - now()) if until else 600.0)
            await asyncio.wait_for(done, max(wait, 1.0))
        except asyncio.TimeoutError:
            pass
        finally:
            self.on_answer = None
            self._abandon()

    async def open_loop(self, numbers, due_at: list[float]) -> None:
        """Send ``numbers[i]`` at ``due_at[i]`` (monotonic seconds)."""
        pending: collections.deque = collections.deque()

        def take(conn: Conn | None = None) -> bool:
            if conn is not None and pending:
                conn.send(*pending.popleft())
                return True
            return False

        self.on_answer = take
        try:
            for n, due in zip(numbers, due_at):
                while True:
                    ahead = due - now()
                    if ahead <= 0:
                        break
                    # the selector's timeout is whole milliseconds, rounded
                    # up: sleep to within one of the due time, then yield
                    # to I/O and look again
                    await asyncio.sleep(
                        ahead - SPIN_S if ahead > SPIN_S else 0)
                if self.idle:
                    self.idle.popleft().send(n, due)
                    continue
                pending.append((n, due))
                self.open_one_more()
            limit = (due_at[-1] if due_at else now()) + self.timeout_s
            while (pending or any(c.inflight for c in self.conns)) \
                    and now() < limit:
                await asyncio.sleep(0.005)
        finally:
            self.on_answer = None
            for n, due in pending:  # never written: no answer
                self.records.append((n, due, float("nan"), now(), None))
            self._abandon()

    def _abandon(self) -> None:
        """What is still in flight got no answer in time: a failure, and
        its connection cannot be used again."""
        for conn in list(self.conns):
            if conn.inflight is not None:
                n, due, sent = conn.inflight
                conn.inflight = None
                self.records.append((n, due, sent, now(), None))
                conn.transport.abort()

    def share(self, base: int, count: int | None):
        """This client's request numbers from ``base`` on."""
        if count is None:
            return iter(range(base + self.k, 1 << 62, self.K))
        return range(base + self.k, base + count, self.K)

    async def warm(self, base: int, count: int) -> None:
        """A pass of ``count`` requests in the window's own shape, so that
        it forms the batches the window will."""
        self.records = []
        if self.mix["arrival"] == "closed":
            await self.closed_loop(self.share(base, count), None)
            return
        rate = float(self.mix["rate"])
        due = poisson_schedule(self.spec["seed"] + base, rate,
                               count / rate, self.mix.get("burst"))[:count]
        mine = range(self.k, len(due), self.K)
        t0 = now() + 0.05
        await self.open_loop([base + i for i in mine],
                             [t0 + due[i] for i in mine])

    async def window(self, base: int, t0: float, seconds: float,
                     rate: float | None = None) -> bytes:
        self.records = []
        if self.mix["arrival"] == "closed":
            await asyncio.sleep(max(0.0, t0 - now()))
            await self.closed_loop(self.share(base, None), t0 + seconds)
        else:
            due = poisson_schedule(
                self.spec["seed"], rate or float(self.mix["rate"]), seconds,
                self.mix.get("burst"))
            mine = range(self.k, len(due), self.K)
            await self.open_loop([base + i for i in mine],
                                 [t0 + due[i] for i in mine])
        records = [
            (n, due, sent, done, None if raw is None else strip_date(raw))
            for n, due, sent, done, raw in self.records
        ]
        return pickle.dumps({
            "records": records, "connections": len(self.conns),
        }, protocol=pickle.HIGHEST_PROTOCOL)


async def serve(spec: dict) -> None:
    loop = asyncio.get_running_loop()
    reader = asyncio.StreamReader()
    await loop.connect_read_pipe(
        lambda: asyncio.StreamReaderProtocol(reader), sys.stdin)
    out = sys.stdout.buffer
    runner = Runner(spec)
    out.write(b"pooled\n")
    out.flush()
    while True:
        line = (await reader.readline()).decode().split()
        if not line or line[0] == "quit":
            break
        if line[0] == "connect":
            await runner.open(runner.first)
            out.write(b"ready\n")
        elif line[0] == "warm":
            base, count = int(line[1]), int(line[2])
            await runner.warm(base, count)
            failed = sum(1 for r in runner.records if r[4] is None)
            out.write(b"warm %d %d\n" % (len(runner.records), failed))
        elif line[0] == "window":
            blob = await runner.window(
                int(line[1]), float(line[2]), float(line[3]),
                float(line[4]) if len(line) > 4 else None)
            out.write(b"result %d\n" % len(blob))
            out.write(blob)
        out.flush()
    for conn in list(runner.conns):
        conn.transport.abort()


def main() -> int:
    # no collector pause inside a window: this process lives a minute and
    # makes no cycles worth collecting
    gc.disable()
    spec = json.loads(sys.stdin.buffer.readline())
    asyncio.run(serve(spec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
