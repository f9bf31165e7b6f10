"""Shared plumbing for per-volume service daemons (bitd, quotad, …):
credential/TLS wiring between glusterd's spawner and the daemon's
brick ClientLayers, the migration-wave throttle both rebalance walks
share, and the token-bucket rate limiter the scrubber and the QoS
plane share.  One copy, so an auth change lands everywhere
(glusterd-svc-mgmt.c is the reference's shared service layer)."""

from __future__ import annotations

import asyncio
import os
import time
from typing import Any

from . import volgen


class TokenBucket:
    """The libglusterfs throttle-tbf.c analog, generalized from the
    bitrot scrubber's bandwidth cap (mgmt/bitd.py) for the QoS plane
    (features/qos.py): ``rate`` tokens refill per second up to a
    ``burst`` ceiling.  ``take`` sleeps until the debit fits (shaping —
    the scrubber / rebalance-lane semantic); ``try_take`` never sleeps
    and instead reports how long the caller would have to wait (the
    admission-shed semantic: the brick answers a retryable errno
    carrying that wait instead of parking the connection).

    rate <= 0 disables — every take is free, every try_take admits.
    ``set_rate`` retunes a LIVE bucket (volume set): accumulated
    tokens are clamped to the new burst so a rate cut takes effect
    within one refill window instead of after the old burst drains."""

    def __init__(self, rate: float, burst: float | None = None):
        self.rate = float(rate)
        self.burst = float(burst) if burst is not None else self.rate
        self.tokens = self.burst
        self._t = time.monotonic()

    def set_rate(self, rate: float, burst: float | None = None) -> None:
        rate = float(rate)
        if rate != self.rate or (burst is not None
                                 and float(burst) != self.burst):
            was_off = self.rate <= 0
            self._refill()
            self.rate = rate
            self.burst = float(burst) if burst is not None else rate
            # a bucket switching on starts FULL (a disabled bucket
            # accrued nothing — without this a client's first frame
            # after enable would shed); a live retune keeps the
            # accrued balance, clamped to the new burst
            self.tokens = self.burst if was_off \
                else min(self.tokens, self.burst)

    def _refill(self) -> None:
        now = time.monotonic()
        self.tokens = min(self.burst,
                          self.tokens + (now - self._t) * self.rate)
        self._t = now

    def level(self) -> float:
        """Current token balance (refilled to now) — the gauge probe."""
        if self.rate <= 0:
            return 0.0
        self._refill()
        return self.tokens

    def debit(self, n: float) -> None:
        """Unconditional debit — the balance may go NEGATIVE
        (borrowing): reply bytes are charged after the send, and the
        debt delays the next admission instead of blocking this one."""
        if self.rate <= 0:
            return
        self._refill()
        self.tokens -= n

    def try_take(self, n: float) -> float:
        """Debit ``n`` tokens without ever sleeping.  Returns 0.0 on
        success; otherwise the seconds until ``n`` (clamped to one
        burst — a debit bigger than the bucket proceeds when it is
        full, the tbf never-starve rule) would be available."""
        if self.rate <= 0:
            return 0.0
        self._refill()
        need = min(n, self.burst)
        if self.tokens >= need:
            self.tokens -= n  # may go negative: oversized debits owe
            return 0.0
        return (need - self.tokens) / self.rate

    async def take(self, n: float) -> None:
        if self.rate <= 0:
            return
        while True:
            self._refill()
            # an object bigger than one burst's budget proceeds when
            # the bucket is full (tbf_mod semantics: never starve)
            if self.tokens >= n or self.tokens >= self.burst:
                self.tokens -= n
                return
            await asyncio.sleep(
                min(1.0, (min(n, self.burst) - self.tokens) / self.rate))


class ThrottleWave:
    """The ``cluster.rebal-throttle`` wave loop (dht-rebalance.c:3269
    migrator thread scaling) — ONE copy shared by the rebalance
    daemon's ``_migrate_dir`` and the legacy in-process
    ``DistributeLayer.rebalance`` walk: admit a migration task when the
    in-flight set drops below ``width``, track the peak, and (lazy
    mode) hand the loop back so serving fops interleave with the
    crawl.  Width/pause are passed PER ADMIT because both callers
    re-read the throttle option every wave — a live ``volume set``
    retunes a running migration."""

    def __init__(self) -> None:
        self.pending: list[asyncio.Task] = []
        self.max_inflight = 0

    async def admit(self, coro, width: int, pause: float = 0.0) -> None:
        """Wait for a slot under ``width``, launch ``coro``, then
        optionally yield (``pause`` — the lazy throttle's cooperative
        beat)."""
        while len(self.pending) >= max(1, int(width)):
            _done, rest = await asyncio.wait(
                self.pending, return_when=asyncio.FIRST_COMPLETED)
            self.pending = list(rest)
        self.pending.append(asyncio.ensure_future(coro))
        self.max_inflight = max(self.max_inflight, len(self.pending))
        if pause:
            await asyncio.sleep(pause)

    async def drain(self) -> None:
        """Await every in-flight migration (end of a directory wave).
        Tasks never re-raise here — both callers count failures inside
        the task body (an uncounted escape would report a clean run
        with files still misplaced)."""
        if self.pending:
            await asyncio.wait(self.pending)
        self.pending = []


def add_ssl_args(parser) -> None:
    parser.add_argument("--ssl", action="store_true")
    parser.add_argument("--ssl-ca", default="")
    parser.add_argument("--ssl-cert", default="")
    parser.add_argument("--ssl-key", default="")


def client_opts(args, env_prefix: str, host: str, port: int,
                subvol: str) -> dict[str, Any]:
    """ClientLayer options for a service daemon's brick connection:
    credentials from the environment (argv is world-readable), TLS from
    the spawner's flags."""
    copts: dict[str, Any] = {"remote-host": host, "remote-port": port,
                             "remote-subvolume": subvol}
    user = os.environ.get(f"{env_prefix}_USERNAME", "")
    if user:
        copts["username"] = user
        copts["password"] = os.environ.get(f"{env_prefix}_PASSWORD", "")
    if args.ssl:
        for k, v in (("ssl-ca", args.ssl_ca), ("ssl-cert", args.ssl_cert),
                     ("ssl-key", args.ssl_key)):
            if v:
                copts[k] = v
        copts["ssl"] = "on"
    return copts


def spawn_ssl_argv(opts: dict) -> list[str]:
    """argv TLS flags matching add_ssl_args, from volume options."""
    out: list[str] = []
    if volgen._bool(opts.get("server.ssl", "off")):
        out.append("--ssl")
    for volkey, flag in (("ssl.ca", "--ssl-ca"),
                         ("ssl.cert", "--ssl-cert"),
                         ("ssl.key", "--ssl-key")):
        if opts.get(volkey):
            out += [flag, opts[volkey]]
    return out


def spawn_env(vol: dict, env_prefix: str) -> dict[str, str]:
    """Subprocess environment for a service daemon: jax pinned to CPU
    plus the volume's mgmt credential pair under the given prefix."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    auth = vol.get("auth") or {}
    if auth:
        env[f"{env_prefix}_USERNAME"] = auth.get(
            "mgmt-username", auth.get("username", ""))
        env[f"{env_prefix}_PASSWORD"] = auth.get(
            "mgmt-password", auth.get("password", ""))
    return env


def brick_group(vol: dict, index: int) -> int:
    """Aggregation group of a brick: bricks in one replica/disperse
    group hold the same logical files (aggregate = max within group);
    distinct groups hold disjoint DHT subtrees (aggregate = sum across
    groups)."""
    n = len(vol["bricks"])
    if vol["type"] in ("disperse", "replicate"):
        g = vol.get("group-size") or n
        return index // g
    return index  # pure distribute: every brick its own group
