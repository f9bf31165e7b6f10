"""Per-process incident flight recorder — the always-on black box.

The reference keeps a per-xlator ``circ-buff.c`` event history that
only a manual statedump can read; by the time an operator asks, the
interesting window has usually scrolled away.  This module is the
process's flight recorder: a bounded ring of NOTABLE records (error
fops with their span trees, slow fops, lifecycle events, circuit/QoS/
shm transitions, worker respawns) that costs nothing while healthy,
plus :func:`snapshot` which packs the record ring, the span ring
(:mod:`core.tracing`), the full metrics registry and any registered
per-process sections (brick client accounting, gateway dump) into one
JSON-able bundle.

Capture is the other half: :func:`maybe_capture` writes that bundle
into ``diagnostics.incident-dir`` when a failure-class event fires
(:data:`FAILURE_EVENTS`, tapped from :func:`core.events.gf_event`),
rate-limited to one bundle per ``diagnostics.incident-min-interval``
seconds and pruned oldest-first so the directory never exceeds
``diagnostics.incident-max-bytes`` — a crash loop fills a quota, not a
disk.  Service daemons with no inbound RPC surface (shd, rebalanced)
arm :func:`arm_signal_capture` instead: SIGUSR2 writes a snapshot to a
well-known path, which glusterd's ``volume incident capture`` fan-out
collects (the statedump-SIGUSR1 precedent, daemon._dump_state).

Everything here honours the :mod:`core.tracing` DARK gate: a process
darkened by ``GFTPU_NO_OBSERVABILITY`` records nothing and captures
nothing.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Callable

from . import gflog, tracing
from .metrics import REGISTRY

log = gflog.get_logger("core.flight")

#: rides the same master gate as the span ring: a darkened process
#: (bench metrics-off) must not pay for — or leak state through — the
#: flight ring either
ENABLED = tracing.ENABLED

_RING_DEFAULT = 512

#: the bounded record ring; record = {"ts", "kind", ...fields}
RING: collections.deque = collections.deque(maxlen=_RING_DEFAULT)

#: gf_event names that auto-capture a local incident bundle (the
#: failure CLASS: connectivity loss, quorum loss, containment firing,
#: storage health, pool self-healing — not routine lifecycle)
FAILURE_EVENTS = frozenset((
    "BRICK_DISCONNECTED", "CLIENT_CIRCUIT_OPEN", "EC_MIN_BRICKS_NOT_UP",
    "AFR_QUORUM_FAIL", "POSIX_HEALTH_CHECK_FAILED", "SERVER_QUORUM_LOST",
    "GATEWAY_WORKER_RESPAWN", "ALERT_RAISED",
))

# -- capture configuration (diagnostics.* v18 keys / --incident-dir) ------
#: directory for auto-captured bundles; "" disables capture (recording
#: into the ring is always on — capture is the part that touches disk)
INCIDENT_DIR = ""
#: total bytes the incident dir may hold; oldest bundles pruned first
INCIDENT_MAX_BYTES = 64 * 1024 * 1024
#: min seconds between auto-captures (one incident, one bundle — not
#: one bundle per breaker flap during the same outage)
INCIDENT_MIN_INTERVAL = 60.0

#: what this process calls itself in bundles ("brick", "gateway-worker",
#: "shd", ...) — set once at daemon startup, purely descriptive
ROLE = ""

#: diagnostics.access-log: the gateway's structured per-request access
#: line (method, path, status, bytes, ms, trace).  Owned here because
#: io-stats pushes the diagnostics.* keys process-wide and the gateway
#: only reads the resulting flag — same shape as tracing.ENABLED
ACCESS_LOG = False

_lock = threading.Lock()
_record_counts: dict[str, int] = {}
_capture_counts = {"written": 0, "rate_limited": 0, "error": 0}
_pruned = 0
_last_capture = 0.0
_capturing = False  # reentrancy guard: a capture must not capture
_sections: dict[str, Callable[[], Any]] = {}

REGISTRY.register(
    "gftpu_flight_records_total", "counter",
    "flight-recorder ring appends by record kind",
    lambda: [({"kind": k}, v) for k, v in sorted(_record_counts.items())])
REGISTRY.register(
    "gftpu_incident_captures_total", "counter",
    "incident bundle auto-capture attempts by outcome",
    lambda: [({"outcome": k}, v)
             for k, v in sorted(_capture_counts.items())])
REGISTRY.register(
    "gftpu_incident_pruned_total", "counter",
    "incident bundles deleted by the size-bound pruner",
    lambda: [({}, _pruned)])


def set_ring_size(n: int) -> None:
    """Rebound the record ring, keeping the newest entries."""
    global RING
    n = max(16, int(n))
    if RING.maxlen != n:
        RING = collections.deque(list(RING)[-n:], maxlen=n)


def set_role(role: str) -> None:
    global ROLE
    ROLE = str(role)


def set_access_log(on: bool) -> None:
    global ACCESS_LOG
    ACCESS_LOG = bool(on) and ENABLED


def configure_capture(incident_dir: str | None = None,
                      max_bytes: int | None = None,
                      min_interval: float | None = None) -> None:
    """Arm/tune auto-capture (io-stats option push or daemon argv)."""
    global INCIDENT_DIR, INCIDENT_MAX_BYTES, INCIDENT_MIN_INTERVAL
    if incident_dir is not None:
        INCIDENT_DIR = str(incident_dir)
    if max_bytes is not None:
        INCIDENT_MAX_BYTES = max(0, int(max_bytes))
    if min_interval is not None:
        INCIDENT_MIN_INTERVAL = max(0.0, float(min_interval))


def record(kind: str, /, **fields) -> None:
    """Append one notable record to the ring (cheap, never raises).
    ``kind`` is positional-only so a field literally named "kind"
    (e.g. an alert's rule kind) cannot raise a TypeError; the ring's
    classification key always wins the collision."""
    if not ENABLED:
        return
    try:
        rec = {"ts": round(time.time(), 6)}
        rec.update(fields)
        rec["kind"] = str(kind)
        RING.append(rec)
        _record_counts[kind] = _record_counts.get(kind, 0) + 1
    except Exception:  # noqa: BLE001 - the recorder must never hurt a fop
        pass


def note_event(event: str, payload: dict) -> None:
    """The gf_event tap: every emission lands in the ring; a
    failure-class event additionally triggers a local auto-capture."""
    if not ENABLED:
        return
    record("event", event=event,
           **{("event_kind" if k == "kind" else k): v
              for k, v in payload.items()
              if k not in ("event", "ts", "pid")})
    if event in FAILURE_EVENTS:
        maybe_capture(event)


def add_section(name: str, fn: Callable[[], Any]) -> None:
    """Register a per-process extra for :func:`snapshot` (the brick
    registers its per-client accounting, the gateway its dump)."""
    _sections[str(name)] = fn


def snapshot(spans: int = 500, records: int = 0,
             metrics: bool = True) -> dict:
    """The bundle: record ring + span ring + metrics registry + every
    registered section, one JSON-able dict.  ``metrics=False`` skips
    the registry scrape for carriers that already ship it beside the
    bundle (the gateway worker control channel)."""
    out: dict[str, Any] = {
        "ts": round(time.time(), 6),
        "pid": os.getpid(),
        "role": ROLE,
        "enabled": ENABLED,
        "records": list(RING)[-records:] if records else list(RING),
        "spans": tracing.recent_spans(spans),
    }
    if metrics:
        out["metrics"] = REGISTRY.snapshot()
    for name, fn in list(_sections.items()):
        try:
            out[name] = fn()
        except Exception as e:  # noqa: BLE001 - scrape isolation
            out[name] = {"error": repr(e)[:200]}
    return out


def _jsonable_dumps(bundle: dict) -> str:
    return json.dumps(bundle, default=repr, separators=(",", ":"),
                      sort_keys=True)


def write_snapshot(path: str, reason: str = "") -> None:
    """Atomically write one bundle to ``path`` (tmp + rename)."""
    bundle = snapshot()
    if reason:
        bundle["reason"] = reason
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(_jsonable_dumps(bundle))
    os.replace(tmp, path)


def prune_dir(incident_dir: str, max_bytes: int) -> int:
    """Delete oldest bundles until the dir fits ``max_bytes``; returns
    how many were pruned (shared by capture and the chaos leak audit)."""
    global _pruned
    try:
        entries = []
        for name in os.listdir(incident_dir):
            if not name.startswith("incident-") \
                    or not name.endswith(".json"):
                continue
            p = os.path.join(incident_dir, name)
            try:
                st = os.stat(p)
            except OSError:
                continue
            entries.append((st.st_mtime, st.st_size, p))
    except OSError:
        return 0
    entries.sort()  # oldest first
    total = sum(e[1] for e in entries)
    n = 0
    while entries and total > max_bytes:
        mtime, size, p = entries.pop(0)
        try:
            os.unlink(p)
        except OSError:
            continue
        total -= size
        n += 1
    if n:
        _pruned += n
        log.info(1, "pruned %d incident bundle(s) from %s "
                 "(size bound %d bytes)", n, incident_dir, max_bytes)
    return n


def maybe_capture(reason: str, force: bool = False) -> str | None:
    """Write an incident bundle if capture is armed and the rate limit
    allows; returns the bundle path or None.  ``force`` (the operator's
    explicit ``incident capture``) skips the rate limit, never the
    size bound."""
    global _last_capture, _capturing
    if not ENABLED or not INCIDENT_DIR:
        return None
    with _lock:
        if _capturing:
            return None
        now = time.monotonic()
        if not force and _last_capture \
                and now - _last_capture < INCIDENT_MIN_INTERVAL:
            _capture_counts["rate_limited"] += 1
            return None
        _last_capture = now
        _capturing = True
    try:
        os.makedirs(INCIDENT_DIR, exist_ok=True)
        safe = "".join(c if c.isalnum() or c in "-_" else "_"
                       for c in str(reason))[:48] or "manual"
        path = os.path.join(
            INCIDENT_DIR,
            f"incident-{time.time_ns()}-{os.getpid()}-{safe}.json")
        write_snapshot(path, reason=str(reason))
        _capture_counts["written"] += 1
        record("incident_captured", reason=str(reason), path=path)
        log.warning(2, "incident bundle captured: %s (%s)", path, reason)
        prune_dir(INCIDENT_DIR, INCIDENT_MAX_BYTES)
        return path
    except Exception as e:  # noqa: BLE001 - capture must never cascade
        _capture_counts["error"] += 1
        log.warning(3, "incident capture failed: %r", e)
        return None
    finally:
        _capturing = False


def arm_signal_capture(path: str, signum: int | None = None) -> None:
    """SIGUSR2 (default) writes a snapshot bundle to ``path`` — the
    capture door for daemons with no inbound RPC surface (shd,
    rebalanced); glusterd signals, polls for the file, and merges it."""
    import signal

    sig = signal.SIGUSR2 if signum is None else signum

    def _cap():
        try:
            write_snapshot(path, reason="signal")
        except Exception as e:  # noqa: BLE001 - a capture door only
            log.warning(4, "signal capture to %s failed: %r", path, e)

    try:
        import asyncio

        asyncio.get_running_loop().add_signal_handler(sig, _cap)
    except (RuntimeError, NotImplementedError):
        signal.signal(sig, lambda *_: _cap())


__all__ = ["ENABLED", "ACCESS_LOG", "RING", "FAILURE_EVENTS",
           "record", "note_event", "set_access_log",
           "add_section", "snapshot", "write_snapshot", "maybe_capture",
           "prune_dir", "configure_capture", "arm_signal_capture",
           "set_ring_size", "set_role"]
