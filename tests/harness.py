"""Multi-process cluster-on-one-host harness — the tests/cluster.rc analog
(reference tests/cluster.rc:6-61 launch_cluster): brick daemons as real
subprocesses with ephemeral ports, clients connecting over TCP."""

from __future__ import annotations

import os
import subprocess
import sys
import time


BRICK_VOLFILE = """
volume posix
    type storage/posix
    option directory {dir}
end-volume

volume locks
    type features/locks
    subvolumes posix
end-volume
"""


def have_tpu() -> bool:
    """True when this process owns a TPU (``ops/codec.tpu_devices``).
    Under GFTPU_TEST_TPU=1 a backend that cannot initialize raises here
    and fails collection: the silicon tests never skip quietly."""
    from glusterfs_tpu.ops.codec import tpu_devices

    return bool(tpu_devices())


class BrickProc:
    """One brick daemon subprocess."""

    def __init__(self, base: str, name: str,
                 volfile_tmpl: str | None = None):
        self.name = name
        self.dir = os.path.join(base, name)
        self.volfile = os.path.join(base, f"{name}.vol")
        self.portfile = os.path.join(base, f"{name}.port")
        with open(self.volfile, "w") as f:
            f.write((volfile_tmpl or BRICK_VOLFILE).format(dir=self.dir))
        self.proc: subprocess.Popen | None = None
        self.port: int | None = None

    def start(self, timeout: float = 15.0, port: int = 0) -> int:
        """port=0 picks an ephemeral port; a fixed port lets bounce
        tests restart the brick where clients expect it."""
        if os.path.exists(self.portfile):
            os.unlink(self.portfile)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # bricks never need a TPU
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "glusterfs_tpu.daemon",
             "--volfile", self.volfile, "--listen", str(port),
             "--portfile", self.portfile],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
        deadline = time.time() + timeout
        while time.time() < deadline:
            if os.path.exists(self.portfile):
                with open(self.portfile) as f:
                    self.port = int(f.read())
                return self.port
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"brick {self.name} died: "
                    f"{self.proc.stderr.read().decode()[-2000:]}")
            time.sleep(0.05)
        raise TimeoutError(f"brick {self.name} did not report a port")

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def terminate(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            self.proc.wait()


class Cluster:
    """N brick daemons (launch_cluster analog)."""

    def __init__(self, base: str, n: int):
        self.base = str(base)
        self.bricks = [BrickProc(self.base, f"brick{i}") for i in range(n)]

    def start(self) -> list[int]:
        return [b.start() for b in self.bricks]

    def stop(self) -> None:
        for b in self.bricks:
            b.terminate()

    def client_volfile(self, cluster_type: str | None = None,
                       options: dict | None = None) -> str:
        """Client graph: protocol/client per brick + optional cluster top."""
        out = []
        for i, b in enumerate(self.bricks):
            out.append(f"""
volume client{i}
    type protocol/client
    option remote-host 127.0.0.1
    option remote-port {b.port}
    option remote-subvolume locks
end-volume
""")
        if cluster_type:
            subs = " ".join(f"client{i}" for i in range(len(self.bricks)))
            opts = "".join(f"    option {k} {v}\n"
                           for k, v in (options or {}).items())
            out.append(f"volume top\n    type {cluster_type}\n{opts}"
                       f"    subvolumes {subs}\nend-volume\n")
        return "\n".join(out)


async def wait_async(pred, timeout: float = 60.0,
                     interval: float = 0.3) -> bool:
    """Poll an async predicate until true or timeout (EXPECT_WITHIN)."""
    import asyncio

    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while True:
        if await pred():
            return True
        if loop.time() > deadline:
            return False
        await asyncio.sleep(interval)


def spawn_fuse(server: str, volume: str, ready: str, mnt: str,
               timeout: float = 60.0):
    """Spawn the FUSE bridge for a managed volume and block until the
    mount is ready.  Returns the Popen; callers stop it with
    stop_fuse().  One home for the hardened recipe (module spawn, env
    scrub, readyfile poll with death detection)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "glusterfs_tpu.mount.fuse_bridge",
         "--server", server, "--volume", volume,
         "--readyfile", ready, str(mnt)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
    deadline = time.time() + timeout
    while not os.path.exists(ready):
        if proc.poll() is not None:
            raise RuntimeError("fuse daemon died: "
                               + proc.stderr.read().decode()[-2000:])
        if time.time() > deadline:
            proc.terminate()
            raise TimeoutError("mount never became ready")
        time.sleep(0.1)
    return proc


def stop_fuse(proc, mnt: str) -> None:
    """Terminate the bridge, wait it out, and lazily unmount."""
    proc.terminate()
    try:
        proc.wait(timeout=15)
    except subprocess.TimeoutExpired:
        proc.kill()
    subprocess.run(["umount", "-l", str(mnt)],
                   stderr=subprocess.DEVNULL)
