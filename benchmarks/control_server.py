#!/usr/bin/env python3
"""The control of the benchmark's comparison: the plain reference put in
the program's place behind the same HTTPS entry, with ONE guarantee of the
configuration broken. ``run.py --control <fault>`` drives it through the
same clients, window and comparison as the program, and ``correct`` has to
come out false. It runs no device code and claims no device.

Faults (``--break``):

* ``first-container``: the verdict looks at each pod's first container
  only — an approximate answer where the configuration states an exact
  one, the step that would tempt a later PR (less per-row work);
* ``stale-uid``: answers come from a cache keyed on the pod's shape, uid
  and all — a stale answer where it was exact;
* ``alter-answer``: one answer in 64 has its ``allowed`` flipped where it
  is produced;
* ``none``: the reference unbroken (the control's own control: it must
  come out correct).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import re
import ssl
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import check_manifest  # noqa: E402

FAULTS = ("first-container", "stale-uid", "alter-answer", "none")
_LENGTH = re.compile(rb"\r\ncontent-length:\s*(\d+)", re.IGNORECASE)


class Control:
    def __init__(self, config: dict, fault: str, reference) -> None:
        self.reference = reference
        self.policies = config["policies"]
        self.signed = set(config["signing"]["signed_images"])
        self.head = config["response_head"]
        self.fault = fault
        self.cache: dict[tuple, bytes] = {}
        self.count = 0

    def answer(self, policy_id: str, body: bytes) -> bytes:
        request = json.loads(body)["request"]
        uid = request["uid"]
        if self.fault == "first-container":
            spec = request["object"]["spec"]
            spec["containers"] = spec["containers"][:1]
        if self.fault == "stale-uid":
            key = (policy_id, json.dumps(request["object"]["spec"]),
                   request["namespace"])
            if key in self.cache:
                return self.cache[key]
        response = self.reference.review_response(
            self.policies[policy_id], request, self.signed)
        self.count += 1
        if self.fault == "alter-answer" and self.count % 64 == 0:
            response = dict(response, allowed=not response["allowed"])
        raw = self.reference.http_response(self.head, uid, response)
        if self.fault == "stale-uid":
            self.cache[key] = raw
        return raw

    async def serve_connection(self, reader: asyncio.StreamReader,
                               writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                head = await reader.readuntil(b"\r\n\r\n")
                m = _LENGTH.search(head)
                body = await reader.readexactly(int(m.group(1)) if m else 0)
                policy_id = head.split(b" ", 2)[1].rsplit(b"/", 1)[1].decode()
                writer.write(self.answer(policy_id, body))
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()


async def _ready(reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter) -> None:
    await reader.readuntil(b"\r\n\r\n")
    writer.write(b"HTTP/1.1 200 OK\r\nContent-Length: 0\r\n"
                 b"Connection: close\r\n\r\n")
    await writer.drain()
    writer.close()


async def serve(args: argparse.Namespace) -> None:
    config = json.loads(Path(args.config).read_text(encoding="utf-8"))
    control = Control(config, args.fault,
                      check_manifest.module_at(args.reference))
    tls = ssl.create_default_context(ssl.Purpose.CLIENT_AUTH)
    tls.load_cert_chain(args.cert_file, args.key_file)
    api = await asyncio.start_server(
        control.serve_connection, args.addr, args.port, ssl=tls, backlog=2048)
    ready = await asyncio.start_server(
        _ready, args.addr, args.readiness_probe_port)
    async with api, ready:
        await asyncio.Event().wait()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--reference", required=True,
                    help="the configuration's plain reference module")
    ap.add_argument("--break", dest="fault", choices=FAULTS, required=True)
    ap.add_argument("--policies")  # the run's filled-in file; not needed
    ap.add_argument("--cert-file", required=True)
    ap.add_argument("--key-file", required=True)
    ap.add_argument("--addr", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--readiness-probe-port", type=int, required=True)
    asyncio.run(serve(ap.parse_args()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
