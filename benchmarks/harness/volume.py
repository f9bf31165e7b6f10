"""One managed volume for one run: glusterd in its own CPU-pinned
process, the volume created, pinned and started over the management RPC
(what the CLI sends, without a second of interpreter per call), and
mounted here through gfapi.  Copied from ``chip_smoke.py``'s lifecycle,
which proved it on the chip; nothing is imported from it."""

from __future__ import annotations

import asyncio
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

from .manifest import ROOT

VOLUME = "bench"

class VolumeError(Exception):
    pass


class Volume:
    def __init__(self, config: dict, workdir: str, brick_root: str,
                 backend: str | None = None):
        self.config = config
        self.workdir = workdir
        self.brick_root = brick_root
        self.backend = backend  # tests: a jax backend in place of the chip
        self.gd: subprocess.Popen | None = None
        self.port = 0
        self.client = None
        self.ecs: list = []      # cluster/disperse layers of the mount
        self.bricks = [os.path.join(brick_root, f"brick{i}")
                       for i in range(config["bricks"])]
        # children: CPU only, and this checkout's package
        self.child_env = dict(os.environ, JAX_PLATFORMS="cpu",
                              PYTHONPATH=ROOT)

    # -- lifecycle ---------------------------------------------------------

    def spawn_glusterd(self) -> None:
        self._portfile = os.path.join(self.workdir, "glusterd.port")
        with open(os.path.join(self.workdir, "glusterd.log"), "ab") as logf:
            self.gd = subprocess.Popen(
                [sys.executable, "-c", _DIE_WITH_PARENT,
                 "-m", "glusterfs_tpu.mgmt.glusterd",
                 "--workdir", os.path.join(self.workdir, "gd"),
                 "--listen", "0", "--portfile", self._portfile],
                env=self.child_env, cwd=ROOT, stdout=logf, stderr=logf,
                start_new_session=True)

    async def create_and_start(self) -> None:
        """Create, set every option, then start: the mounted codec is
        born with the pin, so no live reconfigure rebuilds it (and
        reconnects every brick) next to the window."""
        deadline = time.monotonic() + 60
        while not os.path.exists(self._portfile):
            if self.gd.poll() is not None or time.monotonic() > deadline:
                raise VolumeError("glusterd did not come up: "
                                  + self.log_tail())
            await asyncio.sleep(0.05)
        with open(self._portfile) as f:
            self.port = int(f.read())
        g = self.config["geometry"]
        await self.rpc(
            "volume-create", vtype="disperse", redundancy=g["redundancy"],
            bricks=[{"path": b, "host": "127.0.0.1"} for b in self.bricks],
            group_size=g["data"] + g["redundancy"]
            if g["groups"] > 1 else 0, systematic=-1)
        options = dict(self.config["options"])
        if self.backend:
            options["disperse.cpu-extensions"] = self.backend
        for key, value in options.items():
            await self.rpc("volume-set", key=key, value=str(value))
        await self.rpc("volume-start")

    async def mount(self) -> None:
        from glusterfs_tpu.core.layer import walk
        from glusterfs_tpu.mgmt.glusterd import mount_volume

        self.client = await mount_volume("127.0.0.1", self.port, VOLUME)
        self.ecs = sorted((l for l in walk(self.client.graph.top)
                           if hasattr(l, "codec")), key=lambda l: l.name)
        if not self.ecs or not all(all(e.up) for e in self.ecs):
            raise VolumeError("mounted without every brick connected")

    def layers(self, type_name: str) -> list:
        from glusterfs_tpu.core.layer import walk

        return [l for l in walk(self.client.graph.top)
                if l.type_name == type_name]

    async def rpc(self, method: str, **kwargs):
        from glusterfs_tpu.mgmt.glusterd import MgmtClient

        async with MgmtClient("127.0.0.1", self.port) as c:
            return await c.call(method, name=VOLUME, **kwargs)

    async def stop_brick(self, index: int) -> None:
        await self.rpc("volume-brick", brick=f"{VOLUME}-brick-{index}",
                       action="stop")
        n = self.config["geometry"]["data"] + \
            self.config["geometry"]["redundancy"]
        ec = self.ecs[index // n]
        deadline = time.monotonic() + 60
        while ec.up[index % n]:
            if time.monotonic() > deadline:
                raise VolumeError(f"brick {index} did not go down")
            await asyncio.sleep(0.05)

    def log_tail(self, n: int = 3000) -> str:
        try:
            with open(os.path.join(self.workdir, "glusterd.log"),
                      errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""

    async def close(self) -> None:
        """Unmount, stop every process this run started, wait for each,
        and remove what they wrote."""
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
        shutil.rmtree(self.workdir, ignore_errors=True)


#: the child's first act: ask for SIGTERM when this process dies, however
#: it dies (PR_SET_PDEATHSIG outlives the exec).  glusterd stops its
#: bricks on SIGTERM, so a run that is killed outright leaves no process.
_DIE_WITH_PARENT = (
    "import ctypes, os, signal, sys; "
    "ctypes.CDLL(None).prctl(1, signal.SIGTERM); "
    "os.execv(sys.executable, [sys.executable] + sys.argv[1:])")


def make_dirs(parent: str | None = None) -> tuple[str, str]:
    """(work directory, brick root inside it): one new directory under
    the run's own ``TMPDIR`` (``tempfile``'s default), which
    :meth:`Volume.close` removes.  Nothing is written anywhere else,
    and no run looks at another's."""
    workdir = tempfile.mkdtemp(prefix="gftpu-bench-", dir=parent)
    brick_root = os.path.join(workdir, "bricks")
    os.mkdir(brick_root)
    return workdir, brick_root
