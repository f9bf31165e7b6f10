"""Multi-device (mesh-sharded) codec data plane.

Public surface of the ICI scale-out story (ROADMAP item 2 — the mesh
analog of the reference's socket fan-out, ec-common.c:816-900):

* :func:`make_mesh` / :func:`default_mesh` — factor the visible devices
  into the ``(dp, frag)`` mesh (stripe batches shard over ``dp``, the
  fragment dimension over ``frag``; the encode IS the scatter-to-bricks
  step).
* :func:`device_count` / :func:`device_count_cached` /
  :func:`local_device_count` — device discovery (asked once; the
  cached form never touches jax and is what serving-path routing
  reads).  Under a ``cluster.mesh-distributed`` job (``meshd``) the
  global count spans every member process; ``local_device_count`` is
  this process's share.
* :mod:`glusterfs_tpu.parallel.meshd` — the multi-process
  ``jax.distributed`` coordinator glue (ISSUE 12): brick daemons join
  a per-volume distributed job in the background, so the mesh plane
  binds one PROCESS per device instead of one runtime over all of
  them.
* :func:`sharded_encode` / :func:`sharded_decode` — the pjit'd
  NamedSharding entry points the BatchingCodec's mesh backend and the
  ``cpu-extensions=mesh`` Codec backend launch.
* :func:`ring_decode` — the all-to-all ALTERNATIVE to
  ``sharded_decode``: same answer, but fragments stay sharded over the
  ring (``frag``) axis and an XOR accumulator travels it via
  ``ppermute``, so per-device memory holds one stripe block instead of
  the whole gathered operand.  ``ops/codec.Codec`` routes mesh decodes
  past ``MESH_RING_DECODE_BYTES`` through it; below the threshold the
  plain all-gather plane wins (one collective, no p-step pipeline).
  tests/test_mesh_plane.py::test_ring_codec_is_the_large_decode_alternative
  pins the routing.
"""

from .mesh_codec import (  # noqa: F401
    default_mesh,
    device_count,
    device_count_cached,
    local_device_count,
    make_mesh,
    sharded_decode,
    sharded_encode,
)
from .ring_codec import ring_decode  # noqa: F401

__all__ = [
    "make_mesh", "default_mesh", "device_count", "device_count_cached",
    "local_device_count", "sharded_encode", "sharded_decode",
    "ring_decode",
]
