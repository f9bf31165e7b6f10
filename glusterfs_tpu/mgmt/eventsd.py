"""Events daemon — the glustereventsd analog.

Reference: events/src/glustereventsd.py + eventsapiconf: a per-node UDP
listener collects gf_event datagrams and POSTs them as JSON to every
registered webhook; webhooks are managed via gluster-eventsapi.

TPU-build shape: an asyncio UDP endpoint plus a wire-framed TCP control
port (webhook-add / webhook-del / status / recent).  Webhook delivery is
a minimal HTTP/1.1 POST over asyncio streams — no external HTTP client,
zero-egress friendly.  Undeliverable webhooks are counted, never
retried into a queue explosion.
"""

from __future__ import annotations

import argparse
import asyncio
import errno
import json
import os
import signal
import sys
from collections import deque
from urllib.parse import urlparse

from .. import pin_cpu
from ..core.fops import FopError
from ..core import gflog
from ..core import metrics as _metrics
from ..rpc import wire

log = gflog.get_logger("eventsd")

# event-plane health in the unified registry (weakref: a stopped
# daemon's families age out) — `eventsapi status` answers humans, these
# answer the scraper
_LIVE_EVENTSD = _metrics.REGISTRY.register_objects(
    "gftpu_events_received_total", "counter",
    "gf_event datagrams ingested by this eventsd",
    lambda d: [({}, d.received)])
_metrics.REGISTRY.register_objects(
    "gftpu_events_webhook_total", "counter",
    "webhook delivery outcomes per registered url",
    lambda d: [({"url": url, "result": k}, v)
               for url, st in d.webhooks.items()
               for k, v in st.items()],
    live=_LIVE_EVENTSD)
_metrics.REGISTRY.register_objects(
    "gftpu_events_webhook_retries_total", "counter",
    "webhook delivery attempts retried after a connect failure or 5xx",
    lambda d: [({"url": url}, n)
               for url, n in sorted(d.webhook_retries.items())],
    live=_LIVE_EVENTSD)

#: bounded retry: one retry (2 attempts total) with a short backoff —
#: enough to ride out a webhook restart, bounded enough that a dead
#: webhook can never queue-explode the delivery tasks (the reference's
#: glustereventsd never retries at all; one bounded retry keeps the
#: no-explosion property while surviving the common blip)
_WEBHOOK_ATTEMPTS = 2
_WEBHOOK_BACKOFF_CAP_S = 1.0


class _UdpSink(asyncio.DatagramProtocol):
    def __init__(self, daemon: "EventsDaemon"):
        self.daemon = daemon

    def datagram_received(self, data: bytes, addr) -> None:
        try:
            event = json.loads(data.decode())
        except ValueError:
            return
        self.daemon._ingest(event)


class EventsDaemon:
    def __init__(self, host: str = "127.0.0.1", udp_port: int = 0,
                 ctl_port: int = 0, history: int = 256):
        self.host = host
        self.udp_port = udp_port
        self.ctl_port = ctl_port
        self.webhooks: dict[str, dict] = {}  # url -> delivery stats
        self.webhook_retries: dict[str, int] = {}  # url -> retry count
        self.recent: deque = deque(maxlen=history)
        self.received = 0
        self._transport = None
        self._ctl: asyncio.AbstractServer | None = None
        self._bg: set[asyncio.Task] = set()
        _LIVE_EVENTSD.add(self)

    async def start(self) -> tuple[int, int]:
        loop = asyncio.get_running_loop()
        self._transport, _ = await loop.create_datagram_endpoint(
            lambda: _UdpSink(self), local_addr=(self.host, self.udp_port))
        self.udp_port = self._transport.get_extra_info("sockname")[1]
        self._ctl = await asyncio.start_server(self._serve_ctl, self.host,
                                               self.ctl_port)
        self.ctl_port = self._ctl.sockets[0].getsockname()[1]
        log.info(1, "eventsd udp=%d ctl=%d", self.udp_port, self.ctl_port)
        return self.udp_port, self.ctl_port

    async def stop(self) -> None:
        if self._transport is not None:
            self._transport.close()
            self._transport = None
        if self._ctl is not None:
            self._ctl.close()
            await self._ctl.wait_closed()
            self._ctl = None
        for t in list(self._bg):
            t.cancel()

    # -- ingestion + fan-out ----------------------------------------------

    def _ingest(self, event: dict) -> None:
        self.received += 1
        self.recent.append(event)
        for url in list(self.webhooks):
            t = asyncio.get_event_loop().create_task(
                self._deliver(url, event))
            self._bg.add(t)
            t.add_done_callback(self._bg.discard)

    async def _deliver(self, url: str, event: dict) -> None:
        stats = self.webhooks.get(url)
        if stats is None:
            return
        for attempt in range(_WEBHOOK_ATTEMPTS):
            outcome = await self._post(url, event)
            if outcome == "ok":
                stats["delivered"] += 1
                return
            # a 4xx is the webhook REJECTING the event — retrying it
            # re-sends the same rejected payload; only transport blips
            # (connect failure / timeout) and 5xx earn the retry
            if outcome == "fatal" or attempt == _WEBHOOK_ATTEMPTS - 1:
                break
            self.webhook_retries[url] = \
                self.webhook_retries.get(url, 0) + 1
            await asyncio.sleep(min(_WEBHOOK_BACKOFF_CAP_S,
                                    0.25 * (2 ** attempt)))
        stats["failed"] += 1

    async def _post(self, url: str, event: dict) -> str:
        """One delivery attempt: ``ok`` (2xx), ``retryable`` (connect
        failure / timeout / 5xx) or ``fatal`` (any other status)."""
        u = urlparse(url)
        body = json.dumps(event).encode()
        try:
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(u.hostname, u.port or 80), 5)
        except Exception:
            return "retryable"
        try:
            req = (f"POST {u.path or '/'} HTTP/1.1\r\n"
                   f"Host: {u.hostname}\r\n"
                   f"Content-Type: application/json\r\n"
                   f"Content-Length: {len(body)}\r\n"
                   f"Connection: close\r\n\r\n").encode() + body
            writer.write(req)
            await writer.drain()
            status = await asyncio.wait_for(reader.readline(), 5)
            if b" 2" in status:
                return "ok"
            return "retryable" if b" 5" in status else "fatal"
        except Exception:
            return "retryable"
        finally:
            try:
                writer.close()
            except Exception:
                pass

    # -- control port ------------------------------------------------------

    async def _serve_ctl(self, reader, writer) -> None:
        try:
            while True:
                try:
                    rec = await wire.read_frame(reader)
                except (asyncio.IncompleteReadError, ConnectionError):
                    break
                xid, _, payload = wire.unpack(rec)
                try:
                    method, kwargs = payload
                    ret = self._ctl_op(method, kwargs or {})
                    resp = (wire.MT_REPLY, ret)
                except Exception as e:
                    resp = (wire.MT_ERROR, FopError(errno.EINVAL,
                                                    repr(e)))
                writer.write(wire.pack(xid, *resp))
                await writer.drain()
        finally:
            try:
                writer.close()
            except Exception:
                pass

    def _ctl_op(self, method: str, kwargs: dict):
        if method == "webhook-add":
            u = urlparse(kwargs["url"])
            if u.scheme != "http" or not u.hostname:
                # delivery is plaintext HTTP/1.1; silently degrading an
                # https:// registration to port-80 plaintext would leak
                # event payloads
                raise ValueError("only http:// webhook URLs are supported")
            self.webhooks.setdefault(kwargs["url"],
                                     {"delivered": 0, "failed": 0})
            return {"ok": True, "webhooks": sorted(self.webhooks)}
        if method == "webhook-del":
            self.webhooks.pop(kwargs["url"], None)
            self.webhook_retries.pop(kwargs["url"], None)
            return {"ok": True, "webhooks": sorted(self.webhooks)}
        if method == "status":
            return {"received": self.received,
                    "webhooks": dict(self.webhooks),
                    "udp_port": self.udp_port}
        if method == "recent":
            n = int(kwargs.get("count", 50))
            return {"events": list(self.recent)[-n:]}
        raise ValueError(f"unknown op {method!r}")


async def _amain(args) -> None:
    from ..core import flight, history
    from ..core.metrics import register_build_info

    flight.set_role("eventsd")
    register_build_info("eventsd")
    history.arm()
    d = EventsDaemon(args.host, args.udp_port, args.ctl_port)
    await d.start()
    metrics_srv = None
    if args.metrics_port:
        # the received/webhook counter families above, in Prometheus
        # text form (shares daemon.serve_metrics with brick processes)
        from ..daemon import serve_metrics

        metrics_srv = await serve_metrics(args.host, args.metrics_port)
    if args.portfile:
        with open(args.portfile + ".tmp", "w") as f:
            json.dump({"udp": d.udp_port, "ctl": d.ctl_port}, f)
        os.replace(args.portfile + ".tmp", args.portfile)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    if metrics_srv is not None:
        metrics_srv.close()
    await d.stop()


def main(argv=None) -> int:
    pin_cpu()
    p = argparse.ArgumentParser(prog="gftpu-eventsd")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--udp-port", type=int, default=24009)
    p.add_argument("--ctl-port", type=int, default=24010)
    p.add_argument("--portfile", default="")
    p.add_argument("--metrics-port", type=int, default=0,
                   help="serve the unified metrics registry (event "
                        "received/delivered/failed counters) as a "
                        "Prometheus endpoint (0 = off)")
    args = p.parse_args(argv)
    asyncio.run(_amain(args))
    return 0


if __name__ == "__main__":
    sys.exit(main())
