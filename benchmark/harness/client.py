"""The wire, from the client's side: newline-delimited JSON over TCP.

Copied in spirit from ``rbg_tpu/engine/protocol.py`` and
``bench_serving.py`` (the frame format is the program's; the code is the
benchmark's, so that no later PR can change how the yardstick talks).
Imports no JAX.
"""

from __future__ import annotations

import json
import socket
import time


def _split(addr: str):
    host, port = addr.rsplit(":", 1)
    return host, int(port)


def request(addr: str, obj: dict, timeout: float = 120.0) -> dict:
    """One request, one reply line."""
    with socket.create_connection(_split(addr), timeout=timeout) as s:
        s.sendall(json.dumps(obj).encode() + b"\n")
        buf = bytearray()
        while not buf.endswith(b"\n"):
            chunk = s.recv(1 << 16)
            if not chunk:
                raise ConnectionError(f"{addr} closed before replying to "
                                      f"{obj.get('op')!r}")
            buf.extend(chunk)
    return json.loads(buf)


def checked(addr: str, obj: dict, timeout: float = 120.0) -> dict:
    reply = request(addr, obj, timeout)
    if reply.get("error"):
        raise RuntimeError(f"{obj.get('op')} to {addr} failed: {reply}")
    return reply


def wait_healthy(addr: str, alive, timeout: float) -> dict:
    """Poll ``health`` until the server says ok. ``alive()`` raises when
    the process behind ``addr`` has died."""
    deadline = time.monotonic() + timeout
    while True:
        alive()
        try:
            h = request(addr, {"op": "health"}, timeout=5)
            if h.get("ok"):
                return h
        except (OSError, ValueError):
            pass
        if time.monotonic() > deadline:
            raise RuntimeError(f"{addr} not ready in {timeout:.0f}s")
        time.sleep(0.5)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
