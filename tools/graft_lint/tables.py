"""graft-lint declarative tables — the editing surface for op-version 16.

Adding a fop, option key, or capability should mean editing DATA here
(plus the real code site), never checker logic.  Every exemption is a
``fop-or-key -> reason`` pair; the reason is rendered into findings
when a table drifts, so a stale entry explains itself.

Checker-facing contracts:

* GL01 reads ``READ_CLASS`` (the explicit non-mutating half of the fop
  vocabulary), ``CHANGELOG_EXEMPT``, ``IOT_SLOW_EXEMPT`` and
  ``FENCES`` (per brick-side gate layer: how its gate set is declared
  and which write fops it deliberately does not gate).
* GL02 reads ``OPTION_READ_EXEMPT`` (dotted ``.get()`` keys that look
  like volume options but are not), ``OPTION_KEY_PREFIXES`` (what
  counts as option-shaped) and ``CAPABILITIES`` (SETVOLUME reply key
  -> where the client must check it, or an exemption reason).
* GL05 reads ``NON_FAMILY_LITERALS`` (``gftpu_``-prefixed strings that
  are not metrics families).
"""

from __future__ import annotations

# --------------------------------------------------------------------------
# GL01 — fop vocabulary
# --------------------------------------------------------------------------

#: The non-mutating half of the vocabulary.  GL01 requires
#: READ_CLASS ∪ WRITE_FOPS == every Fop member, disjoint — a new fop
#: lands here or in core/fops.WRITE_FOPS, explicitly, or lint fails.
#: (flush/fsync/fsyncdir are durability ops over already-journaled
#: mutations; lock/lease fops are coordination; compound is a carrier
#: whose links classify individually.)
READ_CLASS = frozenset({
    "stat", "readlink", "open", "readv", "statfs", "flush", "fsync",
    "getxattr", "opendir", "fsyncdir", "access", "fstat", "lk",
    "lookup", "readdir", "inodelk", "finodelk", "entrylk", "fentrylk",
    "fgetxattr", "rchecksum", "readdirp", "ipc", "seek", "lease",
    "getactivelk", "setactivelk", "compound",
})

#: Write-class fops deliberately absent from changelog's E/D/M record
#: classes (features/changelog.py).
CHANGELOG_EXEMPT = {
    "xattrop": "internal version/dirty settle accounting — the EC/AFR "
               "transaction engines' bookkeeping, not a user mutation "
               "(the reference changelog excludes it too; user-visible "
               "xattr changes journal via setxattr/M)",
    "fxattrop": "fd twin of xattrop — same internal-settle exemption",
}

#: Write-class fops allowed to fall into io-threads' implicit slow
#: queue instead of an explicit FAST/NORMAL/LEAST/UNGATED class.
#: Empty on purpose: PR 13 classified the whole write vocabulary after
#: GL01 caught nine write fops (fallocate/discard/zerofill/put/
#: copy_file_range/removexattr/fremovexattr/icreate/namelink) silently
#: riding the slow queue, inverting them vs sibling writevs of the
#: same workload — the exact inversion the XORV comment warns about.
IOT_SLOW_EXEMPT: dict[str, str] = {}

#: Brick-side fence layers and their deliberate non-gates.
#: ``kind``: how GL01 discovers the gate set —
#:   "loop"    : a module-level ``for _f in <set-expr>: setattr(...)``
#:               (read-only, barrier);
#:   "methods" : explicitly defined write-fop methods whose body calls
#:               one of ``markers`` (or raises FopError) before
#:               winding (worm, locks, bit-rot-stub).
#: ``exempt`` : write-class fop -> reason it is NOT gated here.
_ENTRY_OPS_LOCKS = "namespace ops are serialized by entrylk/inodelk " \
    "domains (features/locks' other half), not posix byte-range locks"
_XATTR_OPS_LOCKS = "xattr mutations are not byte-range file content; " \
    "mandatory lock semantics cover data ranges only"
_ENTRY_OPS_BITROT = "quarantine fences object CONTENT; removing or " \
    "re-homing the object whole (unlink/rename/entry ops) is the " \
    "operator remedy and leaves nothing corrupt to serve"
_XATTR_OPS_BITROT = "scrub/heal bookkeeping (signatures, quarantine " \
    "marks, EC versions) rides xattrs and must flow through the stub"
_CREATE_OPS_WORM = "creating NEW entries is the WORM-allowed half of " \
    "write-once-read-many; only mutation of existing state is fenced"

FENCES = {
    "glusterfs_tpu/features/read_only.py": {
        "layer": "ReadOnlyLayer",
        "kind": "loop",
        "exempt": {},
    },
    "glusterfs_tpu/features/barrier.py": {
        "layer": "BarrierLayer",
        "kind": "loop",
        "exempt": {
            "xattrop": "the eager-window settle wave (xattrop post-op "
                       "+ compound unlock) must flow THROUGH an armed "
                       "barrier or the snapshot quiesce deadlocks on "
                       "its own contention upcalls (barrier.py module "
                       "comment; absent from the reference barrier "
                       "fop table too)",
            "fxattrop": "fd twin of xattrop — same settle-wave "
                        "exemption",
        },
    },
    "glusterfs_tpu/features/worm.py": {
        "layer": "WormLayer",
        "kind": "methods",
        "markers": ("_deny_file_level", "_on", "_file_level"),
        "exempt": {
            "mknod": _CREATE_OPS_WORM, "mkdir": _CREATE_OPS_WORM,
            "symlink": _CREATE_OPS_WORM, "create": _CREATE_OPS_WORM,
            "icreate": _CREATE_OPS_WORM,
            "namelink": "no storage/posix implementation yet "
                        "(EOPNOTSUPP at the leaf) — fence it like "
                        "link the day it lands",
            "rmdir": "directories carry no WORM state (worm.c fences "
                     "file bodies; an empty dir has no retained data)",
            "xattrop": "internal EC/AFR accounting must flow (same "
                       "settle-wave argument as the barrier exemption)",
            "fxattrop": "fd twin of xattrop",
        },
    },
    "glusterfs_tpu/features/locks.py": {
        "layer": "LocksLayer",
        "kind": "methods",
        "markers": ("_mandatory_check",),
        "exempt": {
            "mknod": _ENTRY_OPS_LOCKS, "mkdir": _ENTRY_OPS_LOCKS,
            "unlink": _ENTRY_OPS_LOCKS, "rmdir": _ENTRY_OPS_LOCKS,
            "symlink": _ENTRY_OPS_LOCKS, "rename": _ENTRY_OPS_LOCKS,
            "link": _ENTRY_OPS_LOCKS, "create": _ENTRY_OPS_LOCKS,
            "icreate": _ENTRY_OPS_LOCKS,
            "namelink": "no storage/posix implementation yet "
                        "(EOPNOTSUPP at the leaf); an entry op anyway "
                        "— the entrylk domain is its fence",
            "setxattr": _XATTR_OPS_LOCKS,
            "removexattr": _XATTR_OPS_LOCKS,
            "fsetxattr": _XATTR_OPS_LOCKS,
            "fremovexattr": _XATTR_OPS_LOCKS,
            "xattrop": _XATTR_OPS_LOCKS, "fxattrop": _XATTR_OPS_LOCKS,
            "setattr": "inode metadata (mode/times/owner) is not "
                       "byte-range content; reference posix-locks has "
                       "no pl_setattr mandatory hook",
            "fsetattr": "fd twin of setattr",
        },
    },
    "glusterfs_tpu/features/bit_rot_stub.py": {
        "layer": "BitRotStubLayer",
        "kind": "methods",
        "markers": ("_deny",),
        "exempt": {
            "mknod": _ENTRY_OPS_BITROT, "mkdir": _ENTRY_OPS_BITROT,
            "unlink": _ENTRY_OPS_BITROT, "rmdir": _ENTRY_OPS_BITROT,
            "symlink": _ENTRY_OPS_BITROT, "rename": _ENTRY_OPS_BITROT,
            "link": _ENTRY_OPS_BITROT, "create": _ENTRY_OPS_BITROT,
            "icreate": _ENTRY_OPS_BITROT,
            "namelink": "no storage/posix implementation yet "
                        "(EOPNOTSUPP at the leaf); an entry op anyway",
            "setxattr": _XATTR_OPS_BITROT,
            "removexattr": _XATTR_OPS_BITROT,
            "fsetxattr": _XATTR_OPS_BITROT,
            "fremovexattr": _XATTR_OPS_BITROT,
            "setattr": "metadata does not touch the corrupt content "
                       "the quarantine preserves for the scrubber",
            "fsetattr": "fd twin of setattr",
        },
    },
}

# --------------------------------------------------------------------------
# GL02 — option plane
# --------------------------------------------------------------------------

#: What an option-shaped dotted key looks like (left of the first dot).
#: Dotted ``.get()`` reads under these prefixes must resolve to
#: volgen's OPTION_MAP.
OPTION_KEY_PREFIXES = (
    "auth", "bitrot", "changelog", "client", "cluster", "config",
    "ctime", "debug", "diagnostics", "disperse", "features", "gateway",
    "locks", "network", "performance", "rebalance", "server", "ssl",
    "storage", "transport",
)

#: Dotted keys that match the prefixes but are NOT volume-set options.
OPTION_READ_EXEMPT: dict[str, str] = {}

#: SETVOLUME reply capabilities (protocol/server handshake reply keys
#: beyond volume/ok/error).  Value: the ``res.get("<cap>")`` check the
#: client must have, or ("exempt", reason).
CAPABILITIES = {
    "compound": "checked",
    "trace": "checked",
    "deadline": "checked",
    "xorv": "checked",
    "leases": "checked",
    "sg": ("exempt",
           "requester-driven: the client ASKS via the sg-replies cred "
           "and must decode sg frames iff it asked; the reply key is "
           "the server's per-connection grant, consumed by the "
           "server's own encoder (conn.sg) — there is no client-side "
           "branch to take on it"),
    "shm": ("exempt",
            "advert, not a flag: the reply value is a dict (boot-id + "
            "side-channel addr + one-shot token) consumed by "
            "client._shm_arm via res.get('shm'); the armed state "
            "lives in _peer_shm after the fd exchange + __shm_ok__ "
            "confirm, not in a res.get branch"),
}

# --------------------------------------------------------------------------
# GL06-GL09 — graft-race concurrency plane (ctxgraph)
# --------------------------------------------------------------------------

#: Extra thread-context entry points the syntax cannot see (dynamic
#: dispatch, callables stored then spawned elsewhere).  Key:
#: ``path::Scope.func``; value: why this runs on a thread.
_LANE_ENTRY = "called by attribute from the lane's pool run " \
    "(ops/batch ``_run``: ``getattr(codec, lane.entry)``), so that a " \
    "replacement on the instance is what a flush calls"
CTX_THREAD_ENTRY: dict[str, str] = {
    "glusterfs_tpu/ops/batch.py::BatchingCodec.encode": _LANE_ENTRY,
    "glusterfs_tpu/ops/batch.py::BatchingCodec.encode_delta": _LANE_ENTRY,
    "glusterfs_tpu/ops/batch.py::BatchingCodec.decode": _LANE_ENTRY,
}

#: Extra loop-context entry points (callables registered with a loop
#: through an indirection ctxgraph cannot follow).
CTX_LOOP_ENTRY: dict[str, str] = {}

#: Functions whose ``set_result``/``set_exception`` from thread
#: context resolve a **concurrent.futures.Future** (thread-safe by
#: contract) rather than an asyncio future.  Key: ``path::Scope.func``.
THREADSAFE_FUTURE_RESOLVE: dict[str, str] = {}

#: Callables that trace/compile on FIRST call (jax.jit laziness):
#: calling one inside a ``with <threading.Lock>`` body turns the lock
#: into a seconds-long process-wide stall (GL07).  Key: dotted-name
#: suffix as written at call sites; value: what makes it lazy.
_MESH_JIT = "lru-cached jax.jit factory — the returned callable " \
    "traces + compiles the whole mesh program at first call per shape"
KNOWN_LAZY: dict[str, str] = {
    "sharded_step_fn": _MESH_JIT + " (parallel/mesh_codec.py)",
    "_encode_fn": _MESH_JIT,
    "_parity_fn": _MESH_JIT,
    "_decode_fn": _MESH_JIT + " (one program per surviving mask)",
    "_ring_decode_fn": _MESH_JIT + " (parallel/ring_codec.py)",
    "jax.jit":
        "jit construction is cheap but the returned callable compiles "
        "at first call; building it under a lock invites calling it "
        "there too",
}

#: Sites that hold a lock across a known-lazy call ON PURPOSE
#: (serializing the first compile IS the design, the PR-8 second-pass
#: fix).  Key: ``path::Scope.func::lazy-name``; value: reason.
_BUILD_LOCK_WHY = "deliberate (PR 8, second review pass): jax.jit is " \
    "LAZY, so the serialization _BUILD_LOCK exists for — two flush " \
    "workers racing an encode/decode first trace+compile (observed " \
    "once as a pybind11 instance-allocation failure under e2e load) " \
    "— only happens when the lock SPANS the jitted call; holding it " \
    "costs little because the backend serializes on-device execution " \
    "anyway and shape bucketing bounds how often a call compiles"
LAZY_UNDER_LOCK_OK: dict[str, str] = {
    "glusterfs_tpu/parallel/mesh_codec.py::run_step::sharded_step_fn":
        _BUILD_LOCK_WHY,
    "glusterfs_tpu/parallel/mesh_codec.py::sharded_encode::_encode_fn":
        _BUILD_LOCK_WHY,
    "glusterfs_tpu/parallel/mesh_codec.py::sharded_encode::_parity_fn":
        _BUILD_LOCK_WHY + " (systematic branch)",
    "glusterfs_tpu/parallel/mesh_codec.py::sharded_parity::_parity_fn":
        _BUILD_LOCK_WHY,
    "glusterfs_tpu/parallel/mesh_codec.py::sharded_decode::_decode_fn":
        _BUILD_LOCK_WHY,
    "glusterfs_tpu/parallel/ring_codec.py::ring_decode::_ring_decode_fn":
        _BUILD_LOCK_WHY,
}

#: Cross-context instance attributes (written in one of loop/thread
#: context, touched in the other) that are neither machine-verifiably
#: lock-protected nor immutable-after-start.  Key:
#: ``path::Class.attr``; value: (classification, reason) with
#: classification one of "lock-protected" (a design the lexical check
#: cannot see), "immutable-after-start", "threadsafe-handoff"
#: (queue/event/GIL-atomic flag).  New cross-context state is a
#: reviewed DATA edit here — the graft-lint precedent (GL09).
OWNERSHIP: dict[str, tuple[str, str]] = {
    "glusterfs_tpu/features/changelog.py::ChangelogLayer._dir": (
        "immutable-after-start",
        "set once in async init() before the brick serves a single "
        "fop; the history-scan closure (asyncio.to_thread) and the "
        "journal writers only ever read it"),
    "glusterfs_tpu/mount/fuse_bridge.py::FuseBridge.dev_fd": (
        "threadsafe-handoff",
        "GIL-atomic int sentinel: mount() publishes the fd BEFORE "
        "spawning the reader/writer split threads, and the only "
        "cross-context write afterwards is _teardown's -1, which the "
        "threads poll to stand down (each thread OWNS its actual fd: "
        "_rfd/_wfd, closed by the owner) — the documented split-plane "
        "teardown contract (docs/event_threads.md)"),
    "glusterfs_tpu/ops/batch.py::BatchingCodec._cpu": (
        "lock-protected",
        "double-checked lazy build under self._lock (the graft-race "
        "fix): the unlocked fast-path read can see a stale None and "
        "then serializes on the lock; it can never see a partially "
        "built codec because the GIL publishes the assignment whole"),
    "glusterfs_tpu/ops/batch.py::BatchingCodec._mesh": (
        "threadsafe-handoff",
        "written exactly once by the warm thread BEFORE _mesh_state "
        "flips to 'ready' (program-order publication the GIL makes "
        "visible); loop readers gate every access on _mesh_state"),
    "glusterfs_tpu/ops/batch.py::BatchingCodec._mesh_state": (
        "threadsafe-handoff",
        "single-writer state machine (off -> warming -> ready/"
        "unavailable) advanced only by the warm thread via GIL-atomic "
        "str assignment; loop reads tolerate staleness BY DESIGN — "
        "'warming' routes flushes to the measured ladder, so a "
        "seconds-long backend init never stalls a fop"),
}

# --------------------------------------------------------------------------
# GL05 — metrics plane
# --------------------------------------------------------------------------

#: ``gftpu_``-prefixed string literals that are not metrics families
#: and that the checker cannot recognize structurally
#: (``ContextVar("gftpu_...")`` names are already auto-exempt).
NON_FAMILY_LITERALS: dict[str, str] = {}
