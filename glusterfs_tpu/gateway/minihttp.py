"""Minimal HTTP/1.1 client for driving the gateway.

One copy shared by the tests (tests/test_gateway.py and others),
tools/chaos.py and the ci.sh smoke stages — a dialect change (headers,
chunked bodies, HEAD semantics) lands everywhere at once instead of
drifting across hand-rolled parsers.  Deliberately tiny: no redirects, no
TLS, no response streaming — exactly what driving the gateway needs.
"""

from __future__ import annotations

import asyncio


async def request(reader: asyncio.StreamReader,
                  writer: asyncio.StreamWriter, method: str,
                  target: str, headers: dict | None = None,
                  body: bytes = b"", chunks=None):
    """One request/response on an open connection (keep-alive safe).
    ``chunks`` sends the body chunked (the multipart-style streaming
    shape).  Returns ``(status, headers, body)``."""
    h = dict(headers or {})
    h.setdefault("host", "gw")
    if chunks is not None:
        h["transfer-encoding"] = "chunked"
    elif body or method in ("PUT", "POST"):
        h.setdefault("content-length", str(len(body)))
    writer.write((f"{method} {target} HTTP/1.1\r\n" + "".join(
        f"{k}: {v}\r\n" for k, v in h.items())
        + "\r\n").encode("latin-1"))
    if chunks is not None:
        for chunk in chunks:
            writer.write(f"{len(chunk):x}\r\n".encode()
                         + bytes(chunk) + b"\r\n")
        writer.write(b"0\r\n\r\n")
    else:
        writer.write(body)
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    resp_headers: dict[str, str] = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, v = line.decode("latin-1").partition(":")
        resp_headers[k.strip().lower()] = v.strip()
    n = int(resp_headers.get("content-length", 0))
    data = await reader.readexactly(n) if n and method != "HEAD" \
        else b""
    return status, resp_headers, data


async def fetch(host: str, port: int, method: str, target: str,
                headers: dict | None = None, body: bytes = b"",
                chunks=None):
    """One-shot request on its own connection (Connection: close)."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        h = {"connection": "close", **(headers or {})}
        return await request(reader, writer, method, target, h,
                             body, chunks)
    finally:
        writer.close()
