#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served disperse path still
starts on the chip and that the chip does the coding.

    python3 chip_smoke.py          (no arguments, from the checkout root)

This process is the ONE chip owner and the gfapi client; everything it
spawns (glusterd, six bricks, the CLI) runs on the CPU.  It exits
non-zero on the first failed stage and prints one line per stage, then
``summary {...}`` (versions, compile-cache directory, ``reduced``, per
stage ok / wall / compile seconds and the codec's ``dump_stats()``), and
as the last line of stdout exactly this JSON object, the device as jax
reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without an accelerator (or beside nothing else of the repo) it exits
non-zero before any daemon starts and prints no result.  Wall and compile
seconds in the summary are set-up information, NOT metrics: nothing here
is a throughput.

Stages: 0 device + native build; 1 every kernel form compiles
(``interpret=False``) and is exact; 2 a managed 4+2 volume as a user
creates it, default routing; 3 the documented device pin
(``disperse.stripe-cache-min-batch 0``), fragments on disk checked
against ``gf256.ref_encode``; 4 brick loss, degraded read, heal from this
process, two other bricks lost, exact read-back; 5 (more than one chip)
the mesh's arrays really are spread over the devices.
"""

from __future__ import annotations

import asyncio
import glob
import hashlib
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
MIB = 1 << 20

#: upstream's geometry (BASELINE.json config 1 -> tests/basic/ec/ec.t):
#: 4+2, 512-byte chunks, 2048-byte stripes, six real brick processes.
K, R = 4, 2
VOLUME = "smoke"

#: the size a run is held to.  256 MiB of traffic per stage is eight
#: times the 32 MB io-cache default.  A run that must be smaller passes
#: other values and every difference lands in the summary's ``reduced``.
FULL = {
    "files": 8,            # per traffic stage
    "file_mib": 32.0,
    "io_kib": 1024,        # one writev / readv
    "inflight": 4,         # files in flight
    "kernel_stripes": 300,  # ragged: exercises every pad path
    "kernel_big_mib": 64,
    "geometries": ((4, 2), (8, 3), (8, 4), (16, 4)),  # BASELINE sweep
    # what else cpu-extensions can select on a chip host, once at 4+2:
    # xla, xla-xor
    "xla_forms": ("matmul", "xor"),
}

#: stop before the driver's 1200 s limit would, with time to clean up
DEADLINE_S = 1100


class SmokeError(Exception):
    """A stage's requirement did not hold."""


def _need(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


class Smoke:
    """One run: the state the stages share and the summary they fill."""

    def __init__(self, workdir: str, sizes: dict | None = None, *,
                 interpret: bool = False, backend: str | None = None,
                 require_tpu: bool = True):
        self.workdir = workdir
        self.sizes = dict(FULL, **(sizes or {}))
        # tier-1 (CPU) drives the same stages with interpret-mode
        # kernels and an explicit jax backend for the volume
        self.interpret = interpret
        self.backend = backend
        self.require_tpu = require_tpu
        self.gd: subprocess.Popen | None = None
        self.port = 0
        self.bricks: list[str] = []
        self.client = None
        self.ec = None  # the mounted graph's cluster/disperse layer
        self.payloads: dict[str, bytes] = {}
        self.stages: list[dict] = []
        self.summary: dict = {
            "ok": False, "device": None,
            "reduced": [f"{k}: {FULL[k]} -> {v}"
                        for k, v in self.sizes.items() if v != FULL[k]],
            "stages": self.stages}
        self._compile = {"requests": 0, "hits": 0, "secs": 0.0}
        # what this process logged before this run is not this run's
        from glusterfs_tpu.core import gflog

        self._log_mark = f"chip_smoke run {time.monotonic_ns()} begins"
        gflog.get_logger("core").info(0, self._log_mark)
        # children: CPU only, and this checkout's package
        self.child_env = dict(os.environ, JAX_PLATFORMS="cpu",
                              PYTHONPATH=ROOT)

    @property
    def multi_chip(self) -> bool:
        return bool(self.summary["device"]) and \
            self.summary["device"]["count"] > 1

    # -- bookkeeping -------------------------------------------------------

    def watch_compiles(self) -> None:
        """Count jax's compile requests, persistent-cache hits and
        backend-compile seconds from here on."""
        import jax.monitoring

        def event(name, **_kw):
            if name == "/jax/compilation_cache/compile_requests_use_cache":
                self._compile["requests"] += 1
            elif name == "/jax/compilation_cache/cache_hits":
                self._compile["hits"] += 1

        def duration(name, secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self._compile["secs"] += secs

        jax.monitoring.register_event_listener(event)
        jax.monitoring.register_event_duration_secs_listener(duration)

    def _compile_delta(self, before: dict) -> dict:
        req = self._compile["requests"] - before["requests"]
        hit = self._compile["hits"] - before["hits"]
        return {"compile_s": round(self._compile["secs"] - before["secs"],
                                   3),
                "compiles_cold": req - hit, "compiles_cached": hit}

    async def stage(self, number: int, name: str, fn) -> None:
        """Run one stage; record ok / wall / compile; re-raise."""
        before, t0 = dict(self._compile), time.monotonic()
        rec = {"stage": number, "name": name, "ok": False}
        self.stages.append(rec)
        try:
            out = fn()
            if asyncio.iscoroutine(out):
                out = await out
            rec.update(out or {})
            rec["ok"] = True
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {e}"
            raise
        finally:
            rec["wall_s_setup_info"] = round(time.monotonic() - t0, 2)
            rec.update(self._compile_delta(before))
            print(f"stage {number} {name}: "
                  f"{'ok' if rec['ok'] else 'FAILED'} "
                  f"wall_s={rec['wall_s_setup_info']} "
                  f"compile_s={rec['compile_s']} "
                  f"cold={rec['compiles_cold']} "
                  f"cached={rec['compiles_cached']}"
                  + (f" error={rec['error']}" if "error" in rec else ""),
                  flush=True)

    # -- stage 0: the device, the native library ---------------------------

    def stage0_device(self) -> dict:
        import jax

        from glusterfs_tpu.ops import codec

        tpus = codec.tpu_devices()  # places the compile cache first
        devs = jax.devices()
        d0 = devs[0]
        self.summary["device"] = {"platform": d0.platform,
                                  "kind": d0.device_kind,
                                  "count": len(devs)}
        _need(d0.platform == "tpu", f"jax found no TPU: {devs}")
        _need(len(tpus) == len(devs),
              f"codec.tpu_devices() disagrees with jax: {codec.probe_state()}")
        self.summary["compile_cache_dir"] = codec.compile_cache_dir()
        print(f"  compile cache: {self.summary['compile_cache_dir']}",
              flush=True)
        import jaxlib

        try:
            import libtpu

            libtpu_version = getattr(libtpu, "__version__", "unknown")
        except ImportError:
            libtpu_version = "not installed"
        self.summary["versions"] = {"jax": jax.__version__,
                                    "jaxlib": jaxlib.__version__,
                                    "libtpu": libtpu_version}
        free = shutil.disk_usage(self.workdir).free
        need = 4 * int(self.sizes["files"] * self.sizes["file_mib"] * MIB)
        _need(free > need, f"{self.workdir}: {free >> 20} MiB free, "
              f"need {need >> 20}")
        return self.build_native()

    def build_native(self) -> dict:
        """Build the codec library and the wire codec from source (a
        prebuilt one may have come with the copy; git would not carry
        it).  ``native`` is the route every sub-256 KiB flush takes."""
        from glusterfs_tpu import native

        for so in glob.glob(os.path.join(ROOT, "glusterfs_tpu", "native",
                                         "*.so")):
            os.unlink(so)
        _need(native.available(), f"native build: {native._BUILD_ERROR}")
        native.wirec_module()  # raises with the compiler's message
        from glusterfs_tpu.rpc import wire

        _need(wire._wirec is not None,
              "rpc/wire fell back to the pure-Python codec")
        return {"native": "built"}

    # -- stage 1: every kernel form, interpret=False ------------------------

    def stage1_kernels(self) -> dict:
        import numpy as np

        from glusterfs_tpu import native
        from glusterfs_tpu.ops import gf256, gf256_pallas, gf256_xla

        interp = self.interpret
        tally = {"kernels": 0, "compiled_cold": 0, "from_cache": 0}

        def check(name: str, fn, expect) -> None:
            before = dict(self._compile)
            got = np.asarray(fn())
            _need(got.shape == expect.shape and np.array_equal(got, expect),
                  f"{name}: result differs from the reference")
            d = self._compile_delta(before)
            tally["kernels"] += 1
            tally["compiled_cold"] += bool(d["compiles_cold"])
            tally["from_cache"] += bool(d["compiles_cached"]
                                        and not d["compiles_cold"])
            print(f"  kernel {name}: exact cold={d['compiles_cold']} "
                  f"cached={d['compiles_cached']} "
                  f"compile_s={d['compile_s']}", flush=True)

        def sys_bits(k, n):
            return gf256.expand_bitmatrix(gf256.systematic_matrix(k, n))

        def enc_bits(k, n):
            return gf256.expand_bitmatrix(gf256.encode_matrix(k, n))

        def shapes(k):
            """(label, stripes, too big for the NumPy oracle) per size"""
            small, big = self.sizes["kernel_stripes"], \
                self.sizes["kernel_big_mib"]
            yield f"s{small}", small, False
            if big:
                yield f"{big}MiB", big * MIB // (k * 512), True

        for k, r in self.sizes["geometries"]:
            n = k + r
            for label, stripes, big in shapes(k):
                data = np.random.default_rng(k * 100 + r).integers(
                    0, 256, stripes * k * 512, dtype=np.uint8)
                if big:
                    fr = native.encode(data, k, n, enc_bits(k, n))
                    frs = native.encode(data, k, n, sys_bits(k, n))
                else:
                    fr = gf256.ref_encode(data, k, n)
                    frs = gf256.ref_encode(data, k, n, systematic=True)
                tag = f"{k}+{r}/{label}"
                check(f"{tag}/fused-encode",
                      lambda: gf256_pallas.encode(data, k, n, interp),
                      fr)
                for rows in (tuple(range(r, n)),
                             (0,) + tuple(range(2, k + 1))):
                    check(f"{tag}/fused-decode{list(rows)}",
                          lambda: gf256_pallas.decode(
                              fr[list(rows)], rows, k, interp),
                          data)
                check(f"{tag}/parity",
                      lambda: gf256_pallas.parity(data, k, n, interp),
                      frs[k:])
                for miss in ((1,), tuple(range(r))):
                    rows = tuple(j for j in range(n) if j not in miss)[:k]
                    check(f"{tag}/reconstruct-{len(miss)}-missing",
                          lambda: gf256_pallas.reconstruct(
                              frs[list(rows)], rows, miss, k, interp),
                          frs[list(miss)])
        # the reference C kernel's golden vectors
        g = np.load(os.path.join(ROOT, "tests", "golden", "ec_golden.npz"))
        for k, r in self.sizes["geometries"]:
            n = k + r
            data = g[f"in_{k}_{r}"]
            frags = np.stack([g[f"frag_{k}_{r}_{i}"] for i in range(n)])
            check(f"golden/{k}+{r}/encode",
                  lambda: gf256_pallas.encode(data, k, n, interp),
                  frags)
            for which in (0, 1):
                rows = tuple(int(x) for x in g[f"decmask_{k}_{r}_{which}"])
                check(f"golden/{k}+{r}/decode{which}",
                      lambda: gf256_pallas.decode(
                          frags[list(rows)], rows, k, interp),
                      data)
        # everything else cpu-extensions can select on a chip host, 4+2
        k, r, n = K, R, K + R
        rows = (1, 3, 4, 5)
        for label, stripes, _big in shapes(k):
            data = np.random.default_rng(7).integers(
                0, 256, stripes * k * 512, dtype=np.uint8)
            fr = native.encode(data, k, n, enc_bits(k, n))
            par = native.encode(data, k, n, sys_bits(k, n))[k:]
            for form in self.sizes["xla_forms"]:
                check(f"4+2/{label}/xla-{form}-encode",
                      lambda: gf256_xla.encode(data, k, n, form), fr)
                check(f"4+2/{label}/xla-{form}-decode",
                      lambda: gf256_xla.decode(fr[list(rows)], rows, k, form),
                      data)
                check(f"4+2/{label}/xla-{form}-parity",
                      lambda: gf256_xla.parity(data, k, n, form), par)
        return tally

    # -- the managed volume -------------------------------------------------

    async def cli(self, *args: str):
        """One real CLI invocation (its own CPU-pinned process)."""
        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "glusterfs_tpu.mgmt.cli",
            "--server", f"127.0.0.1:{self.port}", "--json", *args,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            env=self.child_env, cwd=ROOT)
        try:
            out, err = await asyncio.wait_for(proc.communicate(), 120)
        except asyncio.TimeoutError:
            proc.kill()
            await proc.wait()
            raise SmokeError(f"gftpu {' '.join(args)}: no answer in 120 s")
        err = err.decode(errors="replace")
        _need(proc.returncode == 0,
              f"gftpu {' '.join(args)}: rc={proc.returncode} {err[-400:]}")
        _need("MSGID: 110040" not in err,
              f"gftpu {' '.join(args)} tried to open the accelerator: {err}")
        return json.loads(out)

    async def start_volume(self) -> None:
        """glusterd in its own process, then create + start through the
        real CLI, then mount here."""
        from glusterfs_tpu.core.layer import walk
        from glusterfs_tpu.mgmt.glusterd import mount_volume

        portfile = os.path.join(self.workdir, "glusterd.port")
        with open(os.path.join(self.workdir, "glusterd.log"), "ab") as logf:
            self.gd = subprocess.Popen(
                [sys.executable, "-m", "glusterfs_tpu.mgmt.glusterd",
                 "--workdir", os.path.join(self.workdir, "gd"),
                 "--listen", "0", "--portfile", portfile],
                env=self.child_env, cwd=ROOT, stdout=logf, stderr=logf,
                start_new_session=True)
        deadline = time.monotonic() + 60
        while not os.path.exists(portfile):
            _need(self.gd.poll() is None and time.monotonic() < deadline,
                  "glusterd did not come up (see glusterd.log)")
            await asyncio.sleep(0.1)
        with open(portfile) as f:
            self.port = int(f.read())
        self.bricks = [os.path.join(self.workdir, f"brick{i}")
                       for i in range(K + R)]
        await self.cli("volume", "create", VOLUME, "disperse", str(R),
                       *self.bricks)
        # the managed self-heal daemon is CPU-pinned like every managed
        # daemon (ROADMAP S3); stage 4 heals from THIS process so that
        # heal's decode and re-encode run on the chip, and with the
        # daemon off nothing can mend a degraded write before the
        # heal-count check sees it
        await self.cli("volume", "set", VOLUME,
                       "cluster.disperse-self-heal-daemon", "off")
        if self.backend:
            await self.cli("volume", "set", VOLUME,
                           "disperse.cpu-extensions", self.backend)
        await self.cli("volume", "start", VOLUME)
        self.client = await mount_volume("127.0.0.1", self.port, VOLUME)
        self.ec = next(l for l in walk(self.client.graph.top)
                       if hasattr(l, "codec"))

    async def set_option(self, key: str, value: str, applied) -> None:
        """``volume set`` through the CLI, then wait until this mount's
        live reconfigure shows it (``applied(codec)``) and every brick
        is connected again."""
        await self.cli("volume", "set", VOLUME, key, value)
        await self._until(lambda: applied(self.ec.codec) and all(self.ec.up),
                          f"{key}={value} to reach the mounted client")

    async def _until(self, cond, what: str, timeout: float = 60.0) -> None:
        deadline = time.monotonic() + timeout
        while not cond():
            _need(time.monotonic() < deadline, f"timed out waiting for {what}")
            await asyncio.sleep(0.1)

    async def rpc(self, method: str, **kwargs):
        """One mgmt RPC to glusterd (what the CLI sends, without the
        second or so a fresh interpreter costs)."""
        from glusterfs_tpu.mgmt.glusterd import MgmtClient

        async with MgmtClient("127.0.0.1", self.port) as c:
            return await c.call(method, name=VOLUME, **kwargs)

    async def brick(self, index: int, action: str) -> None:
        await self.rpc("volume-brick", brick=f"{VOLUME}-brick-{index}",
                       action=action)
        await self._until(lambda: self.ec.up[index] == (action == "start"),
                          f"brick {index} to {action}")

    # -- traffic -------------------------------------------------------------

    def _payload(self, seed: int) -> bytes:
        import numpy as np

        return np.random.default_rng(seed).bytes(
            int(self.sizes["file_mib"] * MIB))

    async def write_files(self, names: list[str], seed: int) -> None:
        """Each file in ``io_kib`` writes, ``inflight`` files at once
        (created, or overwritten in place when it exists); fsync before
        close, so every byte counted is acknowledged."""
        io = self.sizes["io_kib"] * 1024
        sem = asyncio.Semaphore(self.sizes["inflight"])

        async def one(i: int, name: str) -> None:
            data = self._payload(seed + i)
            async with sem:
                if name in self.payloads:
                    f = await self.client.open(name)
                else:
                    f = await self.client.create(
                        name, os.O_RDWR | os.O_EXCL)
                try:
                    for off in range(0, len(data), io):
                        await f.write(data[off:off + io], off)
                    await f.fsync()
                finally:
                    await f.close()
            self.payloads[name] = data

        await asyncio.gather(*(one(i, n) for i, n in enumerate(names)))

    async def read_back(self, names: list[str] | None = None) -> int:
        """Read files in ``io_kib`` reads and compare digests with what
        was acknowledged; returns the bytes verified."""
        io = self.sizes["io_kib"] * 1024
        sem = asyncio.Semaphore(self.sizes["inflight"])
        names = list(self.payloads) if names is None else names

        async def one(name: str) -> int:
            want = self.payloads[name]
            h = hashlib.sha256()
            async with sem:
                f = await self.client.open(name, os.O_RDONLY)
                try:
                    for off in range(0, len(want), io):
                        h.update(await f.read(io, off))
                finally:
                    await f.close()
            _need(h.digest() == hashlib.sha256(want).digest(),
                  f"{name}: read-back differs from the acknowledged write")
            return len(want)

        return sum(await asyncio.gather(*(one(n) for n in names)))

    async def healthy(self) -> dict:
        """Six bricks up (glusterd's view and this client's) and
        nothing pending heal."""
        st = await self.rpc("volume-status")
        down = [b["name"] for b in st["bricks"] if not b["online"]]
        _need(not down, f"bricks down: {down}")
        _need(all(self.ec.up), f"client sees bricks down: {self.ec.up}")
        hc = await self.rpc("volume-heal-count")
        _need(hc["total"] == 0 and "partial" not in hc,
              f"heal-count not 0 (a write went out degraded?): {hc}")
        return {"bricks_online": len(st["bricks"]), "heal_count": 0}

    def no_quiet_fallback(self) -> dict:
        """Fail on every path that could have served from the CPU
        without saying so; returns the codec's stats."""
        from glusterfs_tpu.core import gflog, metrics
        from glusterfs_tpu.ops import batch, codec

        stats = self.ec.codec.dump_stats()
        _need(stats["backend"] in batch._DEVICE_BACKENDS,
              f"backend resolved to {stats['backend']!r}, not a device")
        _need(not stats["calibration_error"] and not stats["mesh"]["error"],
              f"codec recorded a failure: {stats}")
        probe = codec.probe_state()
        if self.require_tpu:
            _need(probe["state"] == "present", f"device probe: {probe}")
        snap = metrics.REGISTRY.snapshot()
        live = {l["backend"]: v for l, v in
                snap["gftpu_codec_instances"]["samples"]}
        _need(not live.get("ref"), f"a codec fell to the NumPy oracle: {live}")
        msgs = gflog.recent_messages(1024)
        mark = max((i for i, m in enumerate(msgs) if self._log_mark in m),
                   default=-1)
        loud = [m for m in msgs[mark + 1:]
                if m.startswith(("ERROR", "CRITICAL"))
                or "MSGID: 110043" in m or "MSGID: 150040" in m]
        _need(not loud, f"logged by this process: {loud[:5]}")
        return stats

    # -- stage 2: as a user creates it ---------------------------------------

    async def stage2_served(self) -> dict:
        await self.start_volume()
        codec = self.ec.codec
        want = self.backend or ("mesh" if self.multi_chip else "pallas-xor")
        _need(codec.backend == want,
              f"cpu-extensions resolved to {codec.backend!r}, want {want!r}")
        _need(await codec.ensure_calibrated(),
              f"calibration did not finish: {codec.dump_stats()}")
        names = [f"/auto-{i}" for i in range(self.sizes["files"])]
        await self.write_files(names, seed=1000)
        verified = await self.read_back(names)
        stats = self.no_quiet_fallback()
        # which route auto chose per flush is REPORTED, not asserted
        return {"bytes_verified": verified, "codec": stats,
                **await self.healthy()}

    # -- stage 3: the chip does the coding ------------------------------------

    async def stage3_device_coding(self) -> dict:
        import numpy as np

        from glusterfs_tpu.ops import gf256

        if self.multi_chip and not self.backend:
            # on a multi-chip host auto means mesh; the Pallas path is
            # still what this stage proves
            await self.set_option("disperse.cpu-extensions", "pallas-xor",
                                  lambda c: c.backend == "pallas-xor")
        await self.set_option("disperse.stripe-cache-min-batch", "0",
                              lambda c: c.min_batch == 0)
        codec = self.ec.codec  # the reconfigure built a new one
        before = codec.dump_stats()
        names = [f"/pinned-{i}" for i in range(self.sizes["files"])]
        await self.write_files(names, seed=2000)
        verified = await self.read_back(names)
        stats = self.no_quiet_fallback()
        flushes = stats["flushes"] - before["flushes"]
        launches = stats["launches"] - before["launches"]
        _need(flushes > 0 and launches >= flushes,
              f"{launches} device launches for {flushes} flushes")
        _need(stats["cpu_launches"] == before["cpu_launches"],
              f"flushes went to the CPU ladder: {stats}")
        # what the bricks hold is what the reference says they should
        name = names[0]
        data = np.frombuffer(self.payloads[name], dtype=np.uint8)
        pad = (-data.size) % (K * 512)
        expect = await asyncio.to_thread(
            gf256.ref_encode, np.concatenate(
                [data, np.zeros(pad, dtype=np.uint8)]), K, K + R,
            systematic=True)
        for i, bdir in enumerate(self.bricks):
            with open(os.path.join(bdir, name.lstrip("/")), "rb") as f:
                frag = np.frombuffer(f.read(), dtype=np.uint8)
            _need(np.array_equal(frag, expect[i][:frag.size])
                  and frag.size >= -(-data.size // K),
                  f"fragment {i} of {name} on disk differs from ref_encode")
        return {"bytes_verified": verified, "flushes": flushes,
                "device_launches": launches, "cpu_launches_delta": 0,
                "fragments_on_disk": "equal to gf256.ref_encode",
                "codec": stats, **await self.healthy()}

    # -- stage 4: the guarantees ----------------------------------------------

    async def stage4_guarantees(self) -> dict:
        from glusterfs_tpu.mgmt import shd

        codec = self.ec.codec
        out: dict = {}
        lost = 1  # a data brick: every read now needs reconstruction
        await self.brick(lost, "stop")
        # an acknowledged write while degraded, then everything read
        # back: the overwritten file cannot come from a cache in front
        # of the codec, so even a run small enough to fit in io-cache
        # reconstructs
        victim = next(iter(self.payloads))
        await self.write_files([victim], seed=3000)
        l0 = codec.launches
        out["degraded_bytes_verified"] = await self.read_back()
        out["reconstruct_launches"] = codec.launches - l0
        _need(out["reconstruct_launches"] > 0,
              "degraded reads never launched the reconstruct kernel")
        await self.brick(lost, "start")
        l0 = codec.launches
        report = await shd.full_crawl(self.client)
        _need(not report["failed"], f"heal failed: {report['failed']}")
        healed = {h["path"]: h["bricks"] for h in report["healed"]}
        _need(lost in healed.get(victim, []),
              f"heal did not rebuild brick {lost} of {victim}: {report}")
        out["heal_launches"] = codec.launches - l0
        _need(out["heal_launches"] > 0, "heal never launched on the device")
        out.update(await self.healthy())
        out["heal_info_doors"] = await self.heal_info_off_chip()
        # two OTHER bricks: the k survivors now include the healed one
        for i in (0, 2):
            await self.brick(i, "stop")
        out["after_heal_bytes_verified"] = await self.read_back()
        out["survivors"] = [i for i, up in enumerate(self.ec.up) if up]
        _need(lost in out["survivors"] and len(out["survivors"]) == K,
              f"survivors {out['survivors']}")
        out["codec"] = self.no_quiet_fallback()
        return out

    async def heal_info_off_chip(self) -> list[str]:
        """``volume heal info`` mounts a client graph inside the CLI's
        process, and inside glusterd's when asked over RPC.  Both are
        CPU-pinned: with this process holding the chip they must answer,
        and neither may have tried to open it."""
        await asyncio.gather(self.cli("volume", "heal", VOLUME, "info"),
                             self.rpc("volume-heal", action="info"))
        with open(os.path.join(self.workdir, "glusterd.log"),
                  errors="replace") as f:
            _need("MSGID: 110040" not in f.read(),
                  "glusterd tried to open the accelerator (glusterd.log)")
        return ["gftpu volume heal info", "glusterd volume-heal info"]

    # -- stage 5: more than one chip -------------------------------------------

    def stage5_mesh_spread(self) -> dict:
        """The mesh's arrays are spread over the devices, not all on
        device 0, and what comes back is exact."""
        import jax.numpy as jnp
        import numpy as np

        from glusterfs_tpu.ops import gf256
        from glusterfs_tpu.parallel import mesh_codec

        mesh = mesh_codec.default_mesh()
        dp = mesh.devices.shape[0]
        data = np.random.default_rng(5).integers(
            0, 256, 64 * dp * K * 512, dtype=np.uint8)
        x = jnp.asarray(data.reshape(-1, K * 8, gf256.WORD_SIZE))
        y = mesh_codec._parity_fn(K, K + R, mesh)(x)
        devices = {s.device for s in y.addressable_shards}
        _need(len(devices) > 1, f"mesh output lives on {devices} only")
        got = mesh_codec.sharded_encode(K, R, data, mesh, systematic=True)
        _need(np.array_equal(got, gf256.ref_encode(data, K, K + R,
                                                   systematic=True)),
              "mesh systematic encode differs from ref_encode")
        return {"mesh_shape": list(mesh.devices.shape),
                "devices_holding_shards": len(devices)}

    # -- teardown ------------------------------------------------------------------

    async def close(self) -> None:
        """Unmount and stop every process this run started."""
        if self.client is not None:
            try:
                await asyncio.wait_for(self.client.unmount(), 30)
            except Exception as e:  # teardown must reach the kill below
                print(f"unmount: {type(e).__name__}: {e}", file=sys.stderr)
            self.client = None
        if self.gd is not None:
            self.gd.terminate()  # glusterd stops its bricks on SIGTERM
            try:
                await asyncio.to_thread(self.gd.wait, 20)
            except subprocess.TimeoutExpired:
                pass
            try:  # whatever is left of its session
                os.killpg(self.gd.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.gd.wait()
            self.gd = None


async def run(smoke: Smoke) -> None:
    """Every stage in order; the first failure ends the run."""
    try:
        await smoke.stage(0, "device", smoke.stage0_device)
        smoke.watch_compiles()
        await smoke.stage(1, "kernels", smoke.stage1_kernels)
        await smoke.stage(2, "served-auto", smoke.stage2_served)
        await smoke.stage(3, "device-coding", smoke.stage3_device_coding)
        await smoke.stage(4, "guarantees", smoke.stage4_guarantees)
        if smoke.multi_chip:
            await smoke.stage(5, "mesh-spread", smoke.stage5_mesh_spread)
        smoke.summary["ok"] = True
    finally:
        await smoke.close()


def result_line(summary: dict) -> str:
    """The last line of stdout: ``ok`` and the device, nothing else (the
    driver refuses any other key; the detail is the ``summary`` line)."""
    return json.dumps({"ok": summary["ok"], "device": summary["device"]})


def main() -> int:
    if not os.path.isdir(os.path.join(ROOT, "glusterfs_tpu")):
        print("chip_smoke: run from a checkout (no glusterfs_tpu/ beside "
              "this script)", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    import glusterfs_tpu

    if not os.path.abspath(glusterfs_tpu.__file__).startswith(ROOT + os.sep):
        print(f"chip_smoke: glusterfs_tpu imported from "
              f"{glusterfs_tpu.__file__}, not this checkout", file=sys.stderr)
        return 2
    def out_of_time(*_):
        raise TimeoutError(f"chip_smoke exceeded {DEADLINE_S} s")

    signal.signal(signal.SIGALRM, out_of_time)
    signal.alarm(DEADLINE_S)
    workdir = tempfile.mkdtemp(prefix="gftpu-smoke-")
    smoke = Smoke(workdir)
    try:
        asyncio.run(run(smoke))
    except Exception as e:
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        if smoke.summary["device"] is None or \
                smoke.summary["device"]["platform"] != "tpu":
            return 1  # no accelerator: no result line
        try:
            with open(os.path.join(workdir, "glusterd.log"),
                      errors="replace") as f:
                print(f"--- glusterd.log ---\n{f.read()[-3000:]}",
                      file=sys.stderr)
        except OSError:
            pass
    finally:
        signal.alarm(0)
        shutil.rmtree(workdir, ignore_errors=True)
    print("summary " + json.dumps(smoke.summary, default=repr))
    print(result_line(smoke.summary), flush=True)
    return 0 if smoke.summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
