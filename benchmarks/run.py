#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the chip this machine holds.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the one chip owner and the gfapi client; glusterd and
the bricks it spawns are CPU-pinned children.  Set-up (native build,
backend start-up beside volume create/start, mount, payload pool, file
layout, bricks stopped, every coding shape warmed, profiler start) is
timed as ``setup_s``; then the window holds the steady loop and nothing
else; then the counters are read, the trace is reduced, and what the
window left behind is compared with the plain reference.  A heal mix
(``harness/traffic.py``) creates its files, stops its returning bricks,
lays the files out, starts the bricks again in set-up, and holds one
heal sweep in the window in place of the loop.  The last line
of stdout is the result; earlier lines are for the reader.  Without an
accelerator, or beside nothing else of the repo, it exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, as near as Python can say

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import check, devtrace, snapshot  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402
from benchmarks.harness.traffic import (FSYNC, READ, WRITE, InFlight,  # noqa: E402
                                        Traffic)
from benchmarks.harness.volume import Volume, make_dirs  # noqa: E402


def client_usage(since) -> dict:
    """This process over the window: CPU seconds of all its threads,
    and how often it was switched out, by its own waiting and by the
    scheduler (a busy neighbour shows in the second)."""
    now = resource.getrusage(resource.RUSAGE_SELF)
    return {"cpu_s": round(now.ru_utime + now.ru_stime
                           - since.ru_utime - since.ru_stime, 3),
            "switches_own": now.ru_nvcsw - since.ru_nvcsw,
            "switches_forced": now.ru_nivcsw - since.ru_nivcsw}


class NoAccelerator(Exception):
    """No result may be printed: this is not the machine of the cell."""


class Run:
    """What one run knows; the readers and the comparison read it."""

    def __init__(self, args, manifest: Manifest, rehearsal: dict | None):
        self.args = args
        self.manifest = manifest
        self.rehearsal = rehearsal or {}
        self.cell = manifest.cell(args.workload)
        self.config = manifest.config(self.cell)
        self.mix = manifest.traffic(self.cell)
        self.traffic = Traffic(self.mix, args.seed)
        self.volume: Volume | None = None
        self.device: dict = {}
        self.counters: list[dict] = []
        self.profile: list[dict] = []
        self.window = (0.0, 0.0)
        self.setup_s = 0.0
        self.layout_ops: list[int] = []
        self.trace: dict | None = None
        self.compiles = {"requests": 0, "hits": 0, "secs": 0.0}
        #: a heal window's: user bytes brought back to full redundancy
        #: inside it, and the sweep's report once it has ended
        self.rebuilt_bytes: int | None = None
        self.heal_report: dict | None = None
        self.fault = None
        self._log_mark = f"benchmark run {time.monotonic_ns()} begins"

    def note(self, what: str, **kv) -> None:
        print(f"{what} {json.dumps(kv, default=str)}", flush=True)

    # -- what the readers read ---------------------------------------------

    @property
    def elapsed(self) -> float:
        return self.window[1] - self.window[0]

    @functools.cached_property
    def ops(self) -> list:
        """Every read and write of the window (read once it has closed)."""
        return self.traffic.ops(*self.window)

    @property
    def attempted(self) -> int:
        """Operations of the window; in a heal window, files swept."""
        if self.heal_report is not None:
            return sum(len(self.heal_report[key])
                       for key in ("healed", "skipped", "failed"))
        return len(self.ops)

    @property
    def failed(self) -> int:
        heals = len(self.heal_report["failed"]) if self.heal_report else 0
        return heals + sum(1 for log in self.traffic.log for op in log
                           if op[0] >= self.window[0] and not op[6])

    def user_bytes(self, kind: int) -> int:
        return sum(op[4] for op in self.ops if op[2] == kind and op[6])

    def delta(self, *path, of: str = "counters") -> float:
        """After minus before of one number of :func:`snapshot.counters`
        (or, ``of="profile"``, of the bricks' ``volume profile``)."""
        before, after = getattr(self, of)
        for key in path:
            before, after = before.get(key, {}), after.get(key, {})
        return (after or 0) - (before or 0)

    def error_logs(self) -> list[str]:
        from glusterfs_tpu.core import gflog

        msgs = gflog.recent_messages(4096)
        mark = max((i for i, m in enumerate(msgs) if self._log_mark in m),
                   default=-1)
        return [m for m in msgs[mark + 1:]
                if m.startswith(("ERROR", "CRITICAL"))]

    # -- set-up --------------------------------------------------------------

    def open_device(self) -> None:
        """Backend start-up (9-12 s on the v5e), in a thread beside the
        volume's create and start.  ``codec.tpu_devices`` is the
        program's one place that asks, and it places the persistent
        compile cache inside the checkout before anything is jitted."""
        import jax

        from glusterfs_tpu.ops import codec

        try:
            tpus = codec.tpu_devices()
        except RuntimeError as e:
            raise NoAccelerator(str(e)) from e
        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        if self.rehearsal:
            return
        if devs[0].platform != "tpu" or len(tpus) < self.cell["chips"]:
            raise NoAccelerator(
                f"cell {self.cell['name']} needs {self.cell['chips']} TPU "
                f"chip(s); jax found {devs}")
        self.note("compile_cache", dir=codec.compile_cache_dir())

    def watch_compiles(self) -> None:
        import jax.monitoring

        def event(name, **_kw):
            if name == "/jax/compilation_cache/compile_requests_use_cache":
                self.compiles["requests"] += 1
            elif name == "/jax/compilation_cache/cache_hits":
                self.compiles["hits"] += 1

        def duration(name, secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles["secs"] += secs

        jax.monitoring.register_event_listener(event)
        jax.monitoring.register_event_duration_secs_listener(duration)

    def warm_codec(self) -> None:
        """Every coding shape the mix can reach, through the codec's own
        synchronous entry: each power-of-two stripe bucket from the
        mix's ``warm_stripes_min`` to its ``warm_stripes_max``, for the encode where the mix writes
        and for every k-subset of the surviving fragments where bricks
        are down.  A heal re-encodes what it decodes from the first k
        bricks that kept the file (``cluster/ec heal_file``), so where
        bricks return the encode and that one mask are warmed.  The warm
        loop through the door follows; this makes sure that a bucket it
        happened not to hit is compiled, or loaded from the cache,
        before the window."""
        import numpy as np

        g = self.config["geometry"]
        k, n = g["data"], g["data"] + g["redundancy"]
        lo, top = (int(self.mix.get(key, 0)) for key in (
            "warm_stripes_min", "warm_stripes_max"))
        buckets = [b for b in (16 << i for i in range(16)) if lo <= b <= top]
        down = {d % n for d in self.mix.get("bricks_down", [])}
        up = [i for i in range(n) if i not in down]
        masks = list(itertools.combinations(up, k)) if down else []
        back = {d % n for d in self.traffic.returning}
        if back:
            masks.append(tuple(i for i in range(n) if i not in back)[:k])
        for ec in self.volume.ecs:
            for b in buckets:
                if self.traffic.read_share < 1.0 or back:
                    ec.codec.encode(np.zeros(b * k * 512, dtype=np.uint8))
                for rows in masks:
                    ec.codec.decode(np.zeros((k, b * 512), dtype=np.uint8),
                                    rows)

    def check_pinned(self) -> None:
        from glusterfs_tpu.ops import batch

        for ec in self.volume.ecs:
            st = ec.codec.dump_stats()
            if st["backend"] not in batch._DEVICE_BACKENDS or \
                    st["min_batch_bytes"] != 0:
                raise RuntimeError(
                    f"{ec.name}: the device path is not pinned: {st}")

    async def set_up(self) -> None:
        from glusterfs_tpu import native
        from glusterfs_tpu.core import gflog

        gflog.get_logger("core").info(0, self._log_mark)
        # built once per checkout; the children load what is built here
        if not native.available():
            raise RuntimeError(f"native build: {native._BUILD_ERROR}")
        native.wirec_module()
        workdir, brick_root = make_dirs(self.rehearsal.get("tmp"))
        self.volume = Volume(self.config, workdir, brick_root,
                             self.rehearsal.get("backend"))
        self.volume.spawn_glusterd()
        t = time.monotonic()
        await asyncio.gather(asyncio.to_thread(self.open_device),
                             self.volume.create_and_start())
        self.watch_compiles()
        t_started = time.monotonic()
        await self.volume.mount()
        self.check_pinned()
        if self.traffic.returning:
            await self.traffic.create(self.volume.client)
            for index in self.traffic.returning:
                await self.volume.stop_brick(index)
        await self.traffic.layout(self.volume.client)
        self.layout_ops = [len(log) for log in self.traffic.log]
        t_laid = time.monotonic()
        for index in self.traffic.returning:
            await self.volume.start_brick(index)
        if self.fault is not None:
            self.fault(self)
        for index in self.mix.get("bricks_down", []):
            await self.volume.stop_brick(index)
        await asyncio.to_thread(self.warm_codec)
        await self.traffic.run(float(self.mix["warm_seconds"]), sample=False)
        self.note("set_up", device_and_volume_s=round(t_started - t, 2),
                  mount_and_layout_s=round(t_laid - t_started, 2),
                  warm_s=round(time.monotonic() - t_laid, 2),
                  brick_root=self.volume.brick_root,
                  compiles=dict(self.compiles))

    # -- the window --------------------------------------------------------------

    async def measure(self) -> None:
        trace_dir = os.path.join(self.volume.workdir, "trace")
        tracing = bool(self.args.trace)
        gc.collect()
        gc.freeze()  # what set-up built is not the collector's to scan
        self.counters.append(snapshot.counters(self.volume))
        self.profile.append(await snapshot.brick_profile(self.volume))
        usage = resource.getrusage(resource.RUSAGE_SELF)
        self.note("window_start", host=snapshot.host_state(self.volume.gd.pid),
                  calibration=self.counters[0]["calibration"])
        if tracing:
            devtrace.start(trace_dir)
        flight = InFlight(devtrace.annotate() if tracing else None)
        compiles = dict(self.compiles)
        self.setup_s = time.monotonic() - T0
        with devtrace.annotate()(devtrace.WINDOW) if tracing \
                else contextlib.nullcontext():
            if self.traffic.heal:
                sweep = await self.heal(flight)
            else:
                self.window = await self.traffic.run(self.args.seconds,
                                                     flight)
        requests = self.compiles["requests"] - compiles["requests"]
        path = devtrace.stop(trace_dir) if tracing else None
        self.counters.append(snapshot.counters(self.volume))
        self.profile.append(await snapshot.brick_profile(self.volume))
        if self.traffic.heal:  # a sweep the cap cut ends here, untimed
            self.heal_report = await sweep
            self.note("heal", rebuilt_bytes=self.rebuilt_bytes,
                      ended_s=time.perf_counter() - self.window[0],
                      **{key: len(self.heal_report[key])
                         for key in ("healed", "skipped", "failed")},
                      first_failure=self.heal_report["failed"][:1])
        self.note("window_end", host=snapshot.host_state(self.volume.gd.pid),
                  calibration=self.counters[1]["calibration"],
                  compiles_in_window=requests,
                  cold_compiles_in_window=requests - (
                      self.compiles["hits"] - compiles["hits"]),
                  elapsed_s=self.elapsed, last_error=self.traffic.last_error,
                  client=client_usage(usage))
        self.series()
        import jax

        stats = [d.memory_stats() or {} for d in jax.devices()]
        self.device["memory_peak_bytes"] = max(
            s.get("peak_bytes_in_use", 0) for s in stats)
        if path:
            events = devtrace.events_of(path)
            self.trace = devtrace.reduce(events)
            if self.trace:
                self.note("trace", window_s=self.trace["window_s"],
                          busy_s=self.trace["busy_s"],
                          op_s=self.trace["op_s"],
                          custom_call_s=self.trace["custom_call_s"])
                self.device["busy_s"] = self.trace["busy_s"]
                self.device["window_s"] = self.trace["window_s"]

    async def heal(self, flight: InFlight) -> asyncio.Future:
        """The heal window: one full sweep (``mgmt/shd.full_crawl``,
        what ``volume heal VOL full`` and glustershd run) on this
        process's mount, ``disperse.shd-max-threads`` files at a time
        (upstream's default, 1, where the configuration sets none), from
        its call to its return or to ``--seconds``, whichever comes
        first.  What it rebuilt is read off the returning bricks at both
        edges.  Returns the sweep, which runs on where the cap came
        first."""
        from glusterfs_tpu.mgmt import shd

        max_heals = int(self.config["options"].get(
            "disperse.shd-max-threads", 1))
        before = self.returned_bytes()
        flight.enter("heal")
        start = time.perf_counter()
        sweep = asyncio.ensure_future(
            shd.full_crawl(self.volume.client, max_heals))
        await asyncio.wait([sweep], timeout=self.args.seconds)
        end = time.perf_counter()
        self.rebuilt_bytes = self.returned_bytes() - before
        flight.exit("heal")
        self.window = (start, end)
        return sweep

    def returned_bytes(self) -> int:
        """User bytes whose stripes the returning bricks hold again: the
        sizes of the mix's files on those bricks, times k, over the
        number of bricks returning.  A heal writes a file's fragments
        from offset 0 up, one window at a time, to every returning brick
        at once, so this is exact to a window with no counter of the
        program's."""
        k = self.config["geometry"]["data"]
        total = 0
        for index in self.traffic.returning:
            for name in self.traffic.names:
                with contextlib.suppress(FileNotFoundError):
                    total += os.path.getsize(os.path.join(
                        self.volume.bricks[index], name.lstrip("/")))
        return total * k // len(self.traffic.returning)

    def kernel_alone(self, metrics: dict) -> None:
        """For the reader, never a metric: the share the hand-written
        kernels (custom calls, found by name) would show on their own
        time, beside the roofline that reads the whole launch."""
        own = self.trace["custom_call_s"]
        for name, m in metrics.items():
            if name.endswith("_roofline") and own > 0:
                self.note("custom_calls_alone", beside=name,
                          share=m["value"] * self.trace["op_s"] / own)

    def series(self) -> None:
        """Per second of the window the MiB acknowledged in it, and the
        slowest operation with its second: where a stall would show."""
        start = self.window[0]
        per = [0.0] * (int(self.elapsed) + 1)
        worst = (0.0, 0.0, "")
        for log in self.traffic.log:
            for t0, t1, kind, _off, size, _po, ok in log:
                if t0 < start:
                    continue
                if ok and kind != FSYNC:
                    per[min(int(t1 - start), len(per) - 1)] += size / 2**20
                if t1 - t0 > worst[0]:
                    worst = (t1 - t0, t0 - start,
                             {READ: "read", WRITE: "write",
                              FSYNC: "fsync"}[kind])
        self.note("series_MiB_per_s", values=[round(x, 1) for x in per])
        self.note("slowest_op", ms=round(worst[0] * 1e3, 2),
                  at_s=round(worst[1], 2), kind=worst[2])

    # -- the result ----------------------------------------------------------------

    def metrics(self) -> dict:
        kind = "per_layer" if self.args.trace else "end_to_end"
        out = {}
        for m in self.manifest.cell_metrics(self.cell["name"], kind):
            value = self.manifest.reader(m["reader"])(self, **m["params"])
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out


async def run_cell(args, manifest: Manifest | None = None,
                   rehearsal: dict | None = None, fault=None) -> dict:
    """Drive one run to its result (the dict of the last line).
    ``rehearsal`` (tests: ``backend``, ``tmp``) lets the run
    stand on the CPU; ``fault`` (``benchmarks/control.py``) is planted
    under the timed path once the files are laid out."""
    run = Run(args, manifest or Manifest(), rehearsal)
    run.fault = fault
    try:
        await run.set_up()
        await run.measure()
        result = {"correct": False, "attempted": run.attempted,
                  "failed": run.failed, "metrics": run.metrics(),
                  "device": run.device}
        if run.trace:
            run.kernel_alone(result["metrics"])
            result["breakdown"] = {"device_ops": run.trace["device_ops"],
                                   "idle_gaps": run.trace["idle_gaps"]}
        checks = await check.compare(run)
        await run.traffic.close()
        result["correct"] = all(v <= limit for v, limit in checks.values())
        result["checks"] = checks  # last: each number beside its limit
        return result
    except BaseException:
        if run.volume is not None:
            print(run.volume.log_tail(), file=sys.stderr)
        raise
    finally:
        if run.volume is not None:
            await run.volume.close()


def drive(coro):
    """Run ``coro`` to its result.  A SIGTERM (a time limit) cancels it
    instead of ending the process on the spot, so that ``run_cell``
    still closes the volume: its processes stopped and waited for, its
    directories removed."""

    async def guarded():
        asyncio.get_running_loop().add_signal_handler(
            signal.SIGTERM, asyncio.current_task().cancel)
        return await coro

    return asyncio.run(guarded())


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "glusterfs_tpu")):
        print("benchmarks/run.py: no glusterfs_tpu/ beside benchmarks/: "
              "nothing to measure", file=sys.stderr)
        return 2
    try:
        result = drive(run_cell(args))
    except NoAccelerator as e:
        print(f"benchmarks/run.py: no accelerator: {e}", file=sys.stderr)
        return 1
    except asyncio.CancelledError:
        print("benchmarks/run.py: terminated", file=sys.stderr)
        return 143
    for name, (value, limit) in result["checks"].items():
        print(f"check {name} value={value} limit={limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
