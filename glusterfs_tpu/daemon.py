"""Process entry points — the glusterfsd analog.

Reference: glusterfsd/src/glusterfsd.c:2650 — one binary runs every
data-plane role, selected by the volfile it loads.  Same here: this
module turns a volfile into a served graph (brick server) or a mounted
client, from the command line or programmatically.

Usage:
    python -m glusterfs_tpu.daemon --volfile brick.vol --listen 24010
    python -m glusterfs_tpu.daemon --volfile brick.vol --listen 0 \
        --portfile /tmp/port   # writes the chosen port (tests use this)
"""

from __future__ import annotations

import argparse
import asyncio
import os
import signal
import sys

from . import pin_cpu
from .core.graph import Graph
from .protocol.server import BrickServer
from .core import gflog

log = gflog.get_logger("core.daemon")


async def serve_brick(volfile_text: str, host: str = "127.0.0.1",
                      port: int = 0, top_name: str | None = None,
                      portfile: str | None = None) -> BrickServer:
    """Activate a brick graph and serve it (returns the running server)."""
    graph = Graph.construct(volfile_text, top_name=top_name)
    await graph.activate()
    server = BrickServer(graph.top, host, port, graph=graph)
    await server.start()
    if portfile:
        tmp = portfile + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(server.port))
        os.replace(tmp, portfile)
    return server


def http_route_handler(routes):
    """A one-shot HTTP/1.0 responder over ``routes``: path ->
    ``async () -> (body_bytes, content_type_bytes)``.  ONE copy of the
    head parse / 404 / Content-Length plumbing, shared by the daemon
    metrics endpoint and the gateway worker-pool supervisor's
    aggregated endpoint — an endpoint or header fix lands everywhere."""
    async def handle(reader: asyncio.StreamReader,
                     writer: asyncio.StreamWriter) -> None:
        try:
            try:
                head = await asyncio.wait_for(
                    reader.readuntil(b"\r\n\r\n"), 5)
            except (asyncio.IncompleteReadError, asyncio.LimitOverrunError,
                    asyncio.TimeoutError, ConnectionError):
                return
            line = head.split(b"\r\n", 1)[0].split()
            path = line[1].decode("latin-1") if len(line) > 1 else "/"
            path = path.split("?", 1)[0]
            route = routes.get(path)
            if route is None:
                writer.write(b"HTTP/1.0 404 Not Found\r\n"
                             b"Content-Length: 0\r\n\r\n")
                return
            body, ctype = await route()
            writer.write(b"HTTP/1.0 200 OK\r\n"
                         b"Content-Type: " + ctype + b"\r\n"
                         + f"Content-Length: {len(body)}\r\n\r\n".encode()
                         + body)
            await writer.drain()
        except ConnectionError:
            pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    return handle


async def serve_metrics(host: str = "127.0.0.1",
                        port: int = 0) -> asyncio.AbstractServer:
    """Prometheus-style scrape endpoint (OFF by default — armed by
    ``--metrics-port``): a minimal HTTP/1.0 responder serving the
    unified registry's text dump at ``/metrics`` and the structured
    snapshot at ``/metrics.json`` (what ``gftpu volume metrics`` and
    the worker-pool supervisor ingest).  Read-only and
    allocation-light; scraping is a cold path by design."""
    import json

    from .core import flight, history, slo
    from .core.metrics import REGISTRY

    async def text():
        return REGISTRY.render().encode(), b"text/plain; version=0.0.4"

    async def structured():
        return (json.dumps(REGISTRY.snapshot()).encode(),
                b"application/json")

    async def incident_json():
        # the per-process incident door (single-process gateway / any
        # daemon with a metrics port): glusterd's incident fan-out
        # GETs this when no worker-pool supervisor is in front
        return (json.dumps(flight.snapshot(), default=repr).encode(),
                b"application/json")

    async def history_json():
        # the time dimension (ISSUE 20): windowed series reconstructed
        # from the delta-compressed sampler ring, with derived
        # per-counter rates (core/history.py)
        return (json.dumps(history.HISTORY.dump(), default=repr).encode(),
                b"application/json")

    async def alerts_json():
        return (json.dumps(slo.ENGINE.status(), default=repr).encode(),
                b"application/json")

    srv = await asyncio.start_server(
        http_route_handler({"/metrics": text, "/": text,
                            "/metrics.json": structured,
                            "/incident.json": incident_json,
                            "/metrics/history.json": history_json,
                            "/alerts.json": alerts_json}),
        host, port)
    log.info(6, "metrics endpoint on %s:%d", host,
             srv.sockets[0].getsockname()[1])
    return srv


def _dump_state(server: BrickServer, volfile: str) -> None:
    """SIGUSR1 statedump (reference glusterfsd.c:2230 wiring +
    statedump.c:831): full graph dump to a timestamped file next to
    the volfile — the de-facto live-debugging interface."""
    import json
    import time

    src = server.graph if server.graph is not None else server.top
    path = (os.path.splitext(volfile)[0]
            + f".dump.{int(time.time())}.{os.getpid()}")
    try:
        with open(path + ".tmp", "w") as f:
            json.dump(src.statedump(), f, indent=1, default=repr)
        os.replace(path + ".tmp", path)
        log.info(2, "statedump written to %s", path)
    except Exception as e:
        log.error(3, "statedump failed: %r", e)


async def _amain(args) -> None:
    if getattr(args, "eventsd", ""):
        # arm gf_event emission for this process (CLIENT_CONNECT /
        # POSIX_HEALTH_CHECK_FAILED ...); same effect as GFTPU_EVENTSD
        # in the environment, but explicit per-daemon
        from .core import events

        events.configure(args.eventsd)
    # cluster.mesh-distributed (ISSUE 12): a brick spawned into a
    # jax.distributed job (glusterd exports GFTPU_MESH_*) joins the
    # coordinator in the BACKGROUND — glusterd spawns bricks one at a
    # time awaiting each port, so a rank that blocked startup waiting
    # for siblings would deadlock the volume start.  Failure degrades
    # to the single-runtime plane, never wedges serving.
    from .parallel import meshd

    meshd.maybe_initialize()
    from .core import flight, history
    from .core.metrics import register_build_info

    flight.set_role("brick")
    register_build_info("brick")
    history.arm()
    with open(args.volfile) as f:
        text = f.read()
    server = await serve_brick(text, args.host, args.listen,
                               args.top or None, args.portfile or None)
    metrics_srv = None
    if getattr(args, "metrics_port", 0):
        metrics_srv = await serve_metrics(args.host, args.metrics_port)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    loop.add_signal_handler(signal.SIGUSR1, _dump_state, server,
                            args.volfile)
    await stop.wait()
    if metrics_srv is not None:
        metrics_srv.close()
    await server.stop()


def main(argv=None) -> int:
    pin_cpu()
    p = argparse.ArgumentParser(prog="gftpu-daemon")
    p.add_argument("--volfile", required=True)
    p.add_argument("--top", default="",
                   help="top layer name (default: unreferenced layer)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--listen", type=int, default=0,
                   help="TCP port (0 = ephemeral)")
    p.add_argument("--portfile", default="",
                   help="write the bound port here (for ephemeral ports)")
    p.add_argument("--metrics-port", type=int, default=0,
                   help="serve the unified metrics registry as a "
                        "Prometheus text endpoint on this port "
                        "(0 = off, the default)")
    p.add_argument("--eventsd", default="",
                   help="host:port of the local gftpu-eventsd: arms "
                        "gf_event lifecycle emission in this process "
                        "(same as the GFTPU_EVENTSD env var)")
    args = p.parse_args(argv)
    asyncio.run(_amain(args))
    return 0


if __name__ == "__main__":
    sys.exit(main())
