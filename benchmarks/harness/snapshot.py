"""Counters of the program and the state of the host, read at the
edges of the window and never inside it."""

from __future__ import annotations

import gc
import glob
import os


def counters(volume) -> dict:
    """What the mounted graph counts, summed over its disperse groups:
    the codec's ``dump_stats()`` integers, ``cluster/ec``'s write paths
    and read fan-outs, and per layer type the per-fop count and
    seconds (``latency_sum``) of ``core/layer._FopStats``."""
    codec = {"flushes": 0, "launches": 0, "cpu_launches": 0,
             "batched_fops": 0}
    ec = {"rmw": 0, "delta": 0, "fast": 0, "staged": 0}
    calibration = []
    for layer in volume.ecs:
        stats = layer.codec.dump_stats()
        for key in codec:
            codec[key] += stats[key]
        calibration.append([stats["calibration"], stats["min_batch_bytes"],
                            stats["backend"], stats["calibration_error"]])
        private = layer.dump_private()
        for key in ("rmw", "delta"):
            ec[key] += private["write_path"][key]
        for key in ("fast", "staged"):
            ec[key] += private["read_fanout"][key]
    fops: dict[str, dict[str, dict]] = {}
    for type_name in ("cluster/disperse", "protocol/client"):
        agg: dict[str, dict] = {}
        for layer in volume.layers(type_name):
            for op, st in layer.stats.items():
                cur = agg.setdefault(op, {"count": 0, "seconds": 0.0})
                cur["count"] += st.count
                cur["seconds"] += st.latency_sum
        fops[type_name] = agg
    return {"codec": codec, "ec": ec, "calibration": calibration,
            "fops": fops}


async def brick_profile(volume) -> dict[str, dict]:
    """``volume profile`` over the management RPC: per fop (count,
    latency seconds) summed over the bricks that answer."""
    prof = await volume.rpc("volume-profile")
    agg: dict[str, dict] = {}
    for brick in prof["bricks"].values():
        for op, st in brick["fops"].items():
            cur = agg.setdefault(op, {"count": 0, "seconds": 0.0})
            cur["count"] += st["count"]
            cur["seconds"] += st["latency_avg"] * st["count"]
    return agg


def _first_line(path: str) -> str | None:
    try:
        with open(path) as f:
            return f.readline().strip()
    except OSError:
        return None


def children_cpu_s(session: int) -> float:
    """CPU seconds so far of every live process of one session (glusterd
    starts its own, and the bricks stay in it): what the volume's
    processes cost beside this one."""
    ticks = 0
    for stat in glob.glob("/proc/[0-9]*/stat"):
        try:
            with open(stat) as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except (OSError, IndexError):
            continue  # it ended meanwhile
        # after "pid (comm)": state ppid pgrp session ... utime stime
        if int(fields[3]) == session:
            ticks += int(fields[11]) + int(fields[12])
    return ticks / os.sysconf("SC_CLK_TCK")


def host_state(session: int | None = None) -> dict:
    """What could explain a stall: dirty and writeback pages, IO and
    CPU pressure where the kernel reports them, load, collections, and
    the CPU seconds of the volume's processes."""
    out: dict = {"loadavg": _first_line("/proc/loadavg"),
                 "gc": [s["collections"] for s in gc.get_stats()]}
    if session is not None:
        out["children_cpu_s"] = round(children_cpu_s(session), 2)
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith(("Dirty:", "Writeback:", "MemAvailable:",
                                    "Shmem:")):
                    key, val = line.split(":")
                    out[key] = val.strip()
    except OSError:
        pass
    for res in ("io", "cpu"):
        line = _first_line(f"/proc/pressure/{res}")
        if line is not None:
            out[f"pressure_{res}"] = line
    out["cpus"] = len(os.sched_getaffinity(0))
    return out
