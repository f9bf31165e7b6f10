"""gftpu-meshd: multi-process ``jax.distributed`` coordinator glue.

The PR-8 ``Mesh(dp, frag)`` codec plane ran ONE jax runtime over every
visible device — which on a multi-host (or multi-brick-process) layout
means one interpreter owns all of them.  ``cluster.mesh-distributed``
(op-version 14) flips that: each brick daemon is one **process** of a
``jax.distributed`` job, binding its own local device(s), with the
coordinator riding brick 0's node.  The mesh then spans interpreters —
``jax.devices()`` is the GLOBAL device list, collectives cross process
boundaries over the distributed runtime, and the same
``parallel/mesh_codec`` programs shard over all of it (SNIPPETS.md
[1]/[3]: partition-rule maps + SPMD partitioner wrappers are exactly
this shape).

Wiring (mgmt/glusterd.py ``_mesh_env``): the brick spawner exports

    GFTPU_MESH_COORDINATOR = host:port      (brick 0's node)
    GFTPU_MESH_PROCESSES   = <brick count>
    GFTPU_MESH_RANK        = <brick index>

and the brick daemon calls :func:`maybe_initialize` at startup.  The
init runs on a BACKGROUND daemon thread with a hard deadline:
glusterd spawns bricks one at a time awaiting each port, so a rank that blocked startup waiting for its
siblings would deadlock the whole volume start.  A rank that cannot
join within the deadline logs, stays single-process, and serves —
degraded to the PR-8 one-runtime plane, never wedged.

On CPU hosts the distributed backend needs a collectives
implementation; :func:`initialize` arms gloo (the only one this jaxlib
ships for CPU) before backend init — without it a multi-process CPU
mesh fails at dispatch with "Multiprocess computations aren't
implemented on the CPU backend".
"""

from __future__ import annotations

import os
import threading
import time

from ..core import gflog
from ..core import metrics as _metrics

log = gflog.get_logger("meshd")

ENV_COORDINATOR = "GFTPU_MESH_COORDINATOR"
ENV_PROCESSES = "GFTPU_MESH_PROCESSES"
ENV_RANK = "GFTPU_MESH_RANK"

#: distributed-init lifecycle: off (no env / never asked) -> joining ->
#: ready / failed
_state = {"status": "off", "coordinator": "", "processes": 0,
          "rank": -1, "error": ""}
_lock = threading.Lock()

_STATUS_GAUGE = {"off": 0, "joining": 1, "ready": 2, "failed": 3}

_metrics.REGISTRY.register(
    "gftpu_mesh_distributed", "gauge",
    "jax.distributed join state of this process "
    "(0 off, 1 joining, 2 ready, 3 failed; labels carry the job "
    "shape)",
    lambda: [({"coordinator": _state["coordinator"],
               "rank": str(_state["rank"]),
               "processes": str(_state["processes"])},
              _STATUS_GAUGE.get(_state["status"], 0))])


def state() -> dict:
    """A copy of the join state (statedumps / tests)."""
    with _lock:
        return dict(_state)


def configured(env=None) -> dict | None:
    """The job shape from the environment, or None when the brick was
    not spawned into a distributed mesh."""
    env = os.environ if env is None else env
    coord = env.get(ENV_COORDINATOR, "")
    if not coord:
        return None
    try:
        return {"coordinator": coord,
                "processes": int(env.get(ENV_PROCESSES, "1")),
                "rank": int(env.get(ENV_RANK, "0"))}
    except ValueError:
        log.warning(2, "malformed mesh env (%s=%r %s=%r); ignoring",
                    ENV_PROCESSES, env.get(ENV_PROCESSES),
                    ENV_RANK, env.get(ENV_RANK))
        return None


def arm_cpu_collectives() -> None:
    """Select gloo CPU collectives BEFORE the backend initializes (a
    no-op when jax already picked a platform with its own collectives,
    or on jax builds without the flag)."""
    try:
        import jax

        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:  # noqa: BLE001 - older jax: flag absent
        pass


def initialize(coordinator: str, num_processes: int, rank: int,
               timeout_s: float = 60.0) -> bool:
    """Join the distributed job; True on success.  BLOCKS up to
    ``timeout_s`` (jax's own initialization_timeout) — daemons must
    call :func:`maybe_initialize` instead, which runs this on a
    background thread."""
    with _lock:
        _state.update({"status": "joining", "coordinator": coordinator,
                       "processes": int(num_processes),
                       "rank": int(rank), "error": ""})
    try:
        arm_cpu_collectives()
        import jax

        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=int(num_processes), process_id=int(rank),
            initialization_timeout=max(1, int(timeout_s)))
        with _lock:
            _state["status"] = "ready"
        log.info(2, "joined distributed mesh %s as rank %d/%d",
                 coordinator, rank, num_processes)
        return True
    except Exception as e:  # noqa: BLE001 - stay single-process
        with _lock:
            _state.update({"status": "failed",
                           "error": repr(e)[:300]})
        log.warning(1, "distributed mesh join failed (%s rank %d): "
                    "%r — serving single-process", coordinator, rank, e)
        return False


def maybe_initialize(coordinator: str = "", num_processes: int = 0,
                     rank: int = -1,
                     timeout_s: float = 60.0) -> bool:
    """Non-blocking join: explicit args, or the spawner's environment.
    Returns True when a background join was STARTED (not when it
    succeeded — poll :func:`state`/``await``-loop for that).  Idempotent:
    a second call while joining/ready is a no-op."""
    if not coordinator:
        cfg = configured()
        if cfg is None:
            return False
        coordinator = cfg["coordinator"]
        num_processes = cfg["processes"]
        rank = cfg["rank"]
    with _lock:
        if _state["status"] in ("joining", "ready"):
            return False
        # mark joining BEFORE the thread starts, under the lock: a
        # probe thread observing 'off' in the spawn window would treat
        # the join as absent (settle_before_backend_init returns, the
        # probe initializes a single-process backend, the join fails
        # forever) — and a concurrent second maybe_initialize would
        # start a duplicate join whose loser overwrites the winner
        _state.update({"status": "joining", "coordinator": coordinator,
                       "processes": int(num_processes),
                       "rank": int(rank), "error": ""})
    threading.Thread(
        target=initialize,
        args=(coordinator, num_processes, rank, timeout_s),
        daemon=True, name=f"gftpu-meshd-join-{rank}").start()
    return True


def settle_before_backend_init(max_wait_s: float = 75.0) -> None:
    """Block THIS thread until a configured background join reaches a
    terminal state.  ``jax.distributed.initialize`` must run before
    the process's FIRST jax backend init — but the device probes
    (mesh_codec.device_count, codec.tpu_devices) may win that race,
    initializing a single-process backend and making the join fail
    forever.  Both call this first: a no-op outside a distributed job
    (and after the join settles), a bounded wait on the caller's own
    thread otherwise.  If the join was configured but not yet started
    (import-order corner), it is started here (idempotent)."""
    if configured() is None:
        return
    if state()["status"] == "off":
        maybe_initialize()
    deadline = time.monotonic() + max_wait_s
    while time.monotonic() < deadline:
        if state()["status"] in ("ready", "failed", "off"):
            return
        time.sleep(0.1)


def wait_ready(timeout_s: float = 60.0) -> bool:
    """Poll the background join to a terminal state (tests/dryrun)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = state()["status"]
        if st == "ready":
            return True
        if st in ("failed", "off"):
            return False
        time.sleep(0.05)
    return False


# ---------------------------------------------------------------------------
# one-rank proof step (the dryrun's 2-process virtual-mesh attempt and
# tests/test_process_plane.py's handshake unit both exec this)
# ---------------------------------------------------------------------------


def rank_step(coordinator: str, num_processes: int, rank: int,
              k: int = 4, r: int = 2, stripes: int = 8) -> None:
    """Join a (virtual, CPU) distributed job and push ONE sharded
    encode through the global mesh — the cross-interpreter analog of
    ``__graft_entry__._dryrun_inline``'s raw-array step.

    Every rank builds the same deterministic stripe batch, contributes
    its dp-slice as its local shard, jits the shared
    ``mesh_codec._encode_fn`` over the GLOBAL mesh (dp = process
    count), and verifies its addressable output shards byte-for-byte
    against the single-process reference encode — proving the
    coordinator handshake AND that one sharded encode landed across
    interpreters.  Raises on any mismatch; the caller owns deadlines
    (it runs in a kill-able subprocess)."""
    import numpy as np

    if not initialize(coordinator, num_processes, rank,
                      timeout_s=45.0):
        raise RuntimeError(f"rank {rank}: distributed init failed: "
                           f"{state()['error']}")
    import jax

    assert jax.process_count() == num_processes, (
        jax.process_count(), num_processes)
    devs = jax.devices()  # GLOBAL: one cpu device per process
    assert len(devs) >= num_processes, len(devs)

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..ops import gf256
    from . import mesh_codec

    n = k + r
    mesh = Mesh(np.asarray(devs[:num_processes]).reshape(
        num_processes, 1), ("dp", "frag"))
    rng = np.random.default_rng(7)  # same bytes on every rank
    data = rng.integers(0, 256,
                        stripes * k * gf256.CHUNK_SIZE, dtype=np.uint8)
    x = data.reshape(stripes, k * 8, gf256.WORD_SIZE)
    per = stripes // num_processes
    local = x[rank * per:(rank + 1) * per]
    sharding = NamedSharding(mesh, P("dp", None, None))
    arr = jax.make_array_from_single_device_arrays(
        x.shape, sharding,
        [jax.device_put(local, jax.local_devices()[0])])
    fn = mesh_codec._encode_fn(k, n, mesh)
    y = fn(arr)  # (n*8, stripes, 64) sharded P("frag", "dp", None)
    # reference: the single-process systematic-free encode, re-laid
    # out plane-major (the inverse of sharded_encode's wire transform)
    frags = gf256.ref_encode(data, k, n)
    expect = frags.reshape(n, stripes, 8, gf256.WORD_SIZE) \
        .transpose(0, 2, 1, 3).reshape(n * 8, stripes,
                                       gf256.WORD_SIZE)
    checked = 0
    for shard in y.addressable_shards:
        got = np.asarray(shard.data)
        if not np.array_equal(got, expect[shard.index]):
            raise AssertionError(
                f"rank {rank}: sharded encode mismatch at "
                f"{shard.index}")
        checked += 1
    if checked == 0:
        raise AssertionError(f"rank {rank}: no addressable shards")
    print(f"meshd rank {rank}/{num_processes}: ok "
          f"({checked} shards verified)", flush=True)
