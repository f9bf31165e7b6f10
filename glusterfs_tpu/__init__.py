"""glusterfs_tpu: a TPU-native scale-out storage framework.

Brand-new implementation of the capabilities of the reference distributed
storage system (GlusterFS, mounted read-only at /root/reference): translator
graphs over bricks, hash distribution, replication, Reed-Solomon erasure
coding, self-heal, management plane and client APIs — with all GF(256)
erasure-coding compute batched onto TPU via JAX/XLA/Pallas.
"""

import os as _os

__version__ = "0.1.0"


def pin_cpu() -> None:
    """Keep this process off the accelerator.  A TPU belongs to one
    process at a time, and that process is a data door (a gfapi
    application, ``gftpu-fuse``, ``gftpu-gateway``): every management
    and service entry point calls this first in ``main()``, before
    anything can import jax, so a client graph it mounts (``volume
    heal``, replace-brick, the CLI) codes on the CPU ladder instead of
    taking the chip from the door that serves with it."""
    _os.environ["JAX_PLATFORMS"] = "cpu"

# This build's management op-version (xlator.h:758 / GD_OP_VERSION):
# peers advertise theirs at probe time and the cluster operates at the
# minimum, gating newer volume-set keys until every member upgrades.
# Lives here (not in mgmt/glusterd) so protocol/client can advertise it
# at SETVOLUME without dragging the whole management plane into every
# client process.  Version history: 19 history + SLO alerting plane
# (per-process metrics history ring core/history.py + the declarative
# SLO engine core/slo.py, diagnostics.history-* / diagnostics.slo-rules
# keys, the __history__/__alerts__ brick doors and glusterd's
# volume-alerts fan-out, volgen._V19_KEYS);
# 18 incident plane (per-process
# flight recorder core/flight.py + auto-capture diagnostics.incident-*
# keys, the __incident__ brick RPC and glusterd's cluster capture
# fan-out, the gateway's --incident-dir spawner arm, volgen._V18_KEYS);
# 17 same-host shared-memory bulk
# lane (memfd arena transport rpc/shm, the "shm" SETVOLUME capability,
# network.shm-transport + network.shm-arena-size, volgen._V17_KEYS);
# 16 multi-tenant QoS plane
# (per-client token buckets + priority lanes at the brick's frame
# admission, server.qos-* + client.qos-backoff, the gateway's --qos-*
# spawner arm, volgen._V16_KEYS); 15 lease plane (brick-side lease
# grants/recalls advertised as the "leases" SETVOLUME capability,
# features.lease-timeout idle expiry + the gateway's lease-held object
# cache gateway.object-cache-size, volgen._V15_KEYS); 14 multi-process
# data plane
# (gateway.workers shared-nothing worker pool + cluster.mesh-distributed
# jax.distributed brick mesh, volgen._V14_KEYS; also lifts the
# mesh-codec-vs-systematic mutual exclusion — the mesh tier gained a
# parity-rows-only systematic encode); 13 managed rebalance daemon
# (volume rebalance start/status/stop ops + rebalance-update RPC +
# rebalance.checkpoint-interval / cluster.rebal-migrate-window,
# volgen._V13_KEYS); 12 parity-delta write plane (the
# brick-side xorv fop + cluster.delta-writes, volgen._V12_KEYS; also
# the cluster floor for volgen's systematic-by-default disperse
# layout); 11 failure-containment plane (lock
# revocation features.locks-revocation-*, client circuit breaking +
# idempotent retries + deadline propagation, debug.error-failure-count,
# volgen._V11_KEYS); 10 mesh-sharded codec data plane
# (cluster.mesh-codec, volgen._V10_KEYS); 9 concurrent event plane
# (server/client.event-threads frame-turning pools + the reader/
# writer-split fuse bridge, _V9_KEYS); 8 HTTP object gateway
# keys (_V8_KEYS); 7 observability (trace propagation + slow-fop
# diagnostics, _V7_KEYS); 6 zero-copy reads + strict-locks (_V6_KEYS);
# 5 compound fops + auth.ssl-allow (_V5_KEYS); 4 round-5 keys
# (_V4_KEYS); 3 the round-4 option long tail (_V3_KEYS).
OP_VERSION = 19
