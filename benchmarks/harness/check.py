"""The comparison that decides ``correct``: what the window left on the
volume, and what it answered, against the plain reference.

Every number compared is exact, so every limit is 0:

``failed_ops``            operations of the window that raised or came
                          back short;
``door_bad_bytes``        bytes that differ between the model of the
                          files (the acknowledged writes, replayed) and
                          what the door returns: the answers the window
                          itself got, a sample drawn from the seed, and,
                          where the window wrote or healed, a read-back
                          of the files after it closed, through the same
                          mount (after a heal, of every file whole);
``fragment_bad_bytes``    bytes on the brick directories that differ
                          from the reference encoding of the model, over
                          extents drawn from the seed plus each file's
                          last written one (after a heal, its first one
                          too): data fragments and parity, on every
                          brick of the file's group, the returning ones
                          included;
``bricks_wrongly_down``   bricks not online that the mix did not stop;
``heal_pending``          entries pending heal, where no brick is down
                          (after a heal, none may be left);
``flushes_off_device``    codec flushes of the window that did not
                          launch on the device, or went to the CPU
                          ladder: the configuration pins the device;
``error_logs``            ERROR lines this process logged in the run.

It runs after the window has closed and after ``memory_peak_bytes`` was
read, file by file, so the host holds one file's model at a time.
"""

from __future__ import annotations

import asyncio
import os

import numpy as np

from . import reference
from .traffic import MIB, WRITE, Traffic

LIMITS = ("failed_ops", "door_bad_bytes", "fragment_bad_bytes",
          "bricks_wrongly_down", "heal_pending", "flushes_off_device",
          "error_logs")


def _diff(a: np.ndarray, b: bytes | np.ndarray) -> int:
    b = np.frombuffer(b, dtype=np.uint8) if isinstance(b, bytes) else b
    if a.size != b.size:
        return max(a.size, b.size)
    return int(np.count_nonzero(a != b))


def sampled_answers(traffic: Traffic, j: int, buf: np.ndarray,
                    layout_ops: int) -> tuple[int, int]:
    """(bytes compared, bytes that differ) over the answers of file j
    kept in the window; ``buf`` holds the file as laid out and leaves
    as the whole record makes it."""
    at, compared, bad = layout_ops, 0, 0
    for index, off, data in traffic.samples[j]:
        traffic.contents(j, upto=index, into=buf, since=at)
        at = index
        bad += _diff(buf[off:off + len(data)], data)
        compared += len(data)
    traffic.contents(j, into=buf, since=at)
    return compared, bad


async def door_readback(traffic: Traffic, j: int, buf: np.ndarray,
                        block: int = MIB, depth: int = 4) -> int:
    """Read file j back through the mount the window used, ``depth``
    reads in flight."""
    f = traffic.files[j]
    offsets = list(range(0, traffic.file_bytes, block))

    async def lane(mine: list[int]) -> int:
        bad = 0
        for off in mine:
            bad += _diff(buf[off:off + block], await f.read(block, off))
        return bad

    return sum(await asyncio.gather(
        *(lane(offsets[i::depth]) for i in range(depth))))


def fragment_extents(traffic: Traffic, j: int, count: int, stripe: int,
                     first: bool = False) -> list[tuple[int, int]]:
    """Extents of file j to hold against the bricks: ``count`` drawn
    from the seed, the one its last write touched and, with ``first``,
    the file's first."""
    rng = np.random.default_rng([traffic.seed, 2, j])
    span = min(MIB, traffic.file_bytes)
    starts = set(int(x) * span for x in rng.integers(
        0, traffic.file_bytes // span, count))
    last = next((op for op in reversed(traffic.log[j])
                 if op[2] == WRITE), None)
    if last is not None:
        starts.add(last[3] // span * span)
    if first:
        starts.add(0)
    return [(s // stripe * stripe, span) for s in sorted(starts)]


def fragments_on_bricks(brick_dirs: list[str], name: str, k: int, n: int,
                        buf: np.ndarray, extents) -> tuple[int, int]:
    """(bytes compared, bytes that differ) between brick files and the
    reference encoding, over ``extents`` of user offsets."""
    compared = bad = 0
    paths = [os.path.join(d, name.lstrip("/")) for d in brick_dirs]
    for off, size in extents:
        want = reference.encode(buf[off:off + size], k, n)
        for i, path in enumerate(paths):
            with open(path, "rb") as f:
                f.seek(off // k)
                got = f.read(size // k)
            bad += _diff(want[i], got)
            compared += want[i].size
    return compared, bad


def group_of(name: str, brick_dirs: list[str], n: int) -> list[str]:
    """The disperse group's bricks that hold ``name`` (under
    ``cluster/dht`` a file lives in one group; another may hold an empty
    link file)."""
    groups = [brick_dirs[g:g + n] for g in range(0, len(brick_dirs), n)]
    sized = [g for g in groups if all(
        os.path.exists(os.path.join(d, name.lstrip("/"))) and
        os.path.getsize(os.path.join(d, name.lstrip("/"))) for d in g)]
    return sized[0] if len(sized) == 1 else []


async def compare(run) -> dict[str, list]:
    """``{name: [value, limit]}`` for every number in :data:`LIMITS`."""
    t: Traffic = run.traffic
    g = run.config["geometry"]
    k, n = g["data"], g["data"] + g["redundancy"]
    mix = t.mix
    wrote = t.read_share < 1.0
    door_bad = frag_bad = 0
    seen = {"answers": 0, "readback": 0, "fragments": 0}
    rng = np.random.default_rng([t.seed, 3])
    readback = set(rng.permutation(t.jobs)[:int(mix.get(
        "verify_files", t.jobs))].tolist()) if wrote else set()
    if t.heal:  # the returning bricks are read through the door too
        readback = set(range(t.jobs))
    for j in range(t.jobs):
        layout_ops = run.layout_ops[j]
        buf = t.contents(j, upto=layout_ops)
        compared, bad = sampled_answers(t, j, buf, layout_ops)
        seen["answers"] += compared
        door_bad += bad
        if j in readback:
            door_bad += await door_readback(t, j, buf)
            seen["readback"] += t.file_bytes
        if j in readback or not wrote:
            bricks = group_of(t.names[j], run.volume.bricks, n)
            if not bricks:
                frag_bad += t.file_bytes  # the file is not where it must be
                continue
            compared, bad = await asyncio.to_thread(
                fragments_on_bricks, bricks, t.names[j], k, n, buf,
                fragment_extents(t, j, int(mix.get("verify_extents", 2)),
                                 k * reference.CHUNK, first=t.heal))
            seen["fragments"] += compared
            frag_bad += bad
    if not (seen["answers"] or seen["readback"]) or not seen["fragments"]:
        door_bad += 1  # nothing was compared: that is not a pass
    run.note("compared", **seen)
    down = set(mix.get("bricks_down", []))
    status = await run.volume.rpc("volume-status")
    wrongly_down = [b["name"] for i, b in enumerate(status["bricks"])
                    if not b["online"] and i not in down]
    heal = 0
    if not down:
        hc = await run.volume.rpc("volume-heal-count")
        heal = hc["total"] + (1 if "partial" in hc else 0)
    before, after = run.counters[0]["codec"], run.counters[1]["codec"]
    flushes = after["flushes"] - before["flushes"]
    off_device = max(0, flushes - (after["launches"] - before["launches"])) \
        + after["cpu_launches"] - before["cpu_launches"]
    values = {
        "failed_ops": run.failed,
        "door_bad_bytes": door_bad,
        "fragment_bad_bytes": frag_bad,
        "bricks_wrongly_down": len(wrongly_down),
        "heal_pending": heal,
        "flushes_off_device": off_device,
        "error_logs": len(run.error_logs()),
    }
    return {name: [values[name], 0] for name in LIMITS}
