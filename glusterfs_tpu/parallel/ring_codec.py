"""Ring-pipelined decode over the ``frag`` mesh axis.

The plain sharded decode (parallel/mesh_codec.py) lets XLA insert an
all-gather of fragment shards before reconstructing — simple, but every
device materializes ALL surviving fragments, so device memory bounds
the batch.  This module is the ring formulation — the same
communication pattern ring attention uses for long sequences, applied
to reconstruction:

* fragments stay sharded over the ring axis (each device holds its
  fragment group's bit-planes for the whole batch);
* the OUTPUT is stripe-sharded: device j owns stripe block j;
* an accumulator per stripe block travels the ring via ``ppermute``:
  at every step each device XORs in its fragments' contribution to the
  block currently visiting it, then forwards the block.  After p steps
  block j has collected every fragment group's contribution and sits
  on device j — a ring reduce-scatter with XOR as the reduction.

Per-step working set is one stripe BLOCK (1/p of the batch), so the
batch can exceed any single device's memory by the ring length — the
long-sequence scaling story.  Comm volume is (p-1)/p of the output,
pipelined with compute over ICI (reference analog: the fan-in of
``ec_dispatch_min`` network reads, ec-common.c:816-900, but streamed).

Role in the data plane: this is the memory-bounded ALTERNATIVE to
``mesh_codec.sharded_decode`` — ``ops/codec`` and the BatchingCodec's
mesh tier route decodes past ``MESH_RING_DECODE_BYTES`` through
:func:`ring_decode`; below the threshold the plain all-gather plane
wins (one collective, no p-step pipeline).  Exported via
``glusterfs_tpu.parallel``; the routing is pinned by
tests/test_mesh_plane.py::test_ring_codec_is_the_large_decode_alternative.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import gf256


@functools.lru_cache(maxsize=64)
def _ring_decode_fn(k: int, rows: tuple[int, ...], mesh: Mesh):
    """Build the jitted ring decode for one surviving mask.

    Input: fragment bit-planes (k*8, S, 64) sharded over ``frag`` on
    the plane axis (each ring member holds k*8/p planes).
    Output: reconstructed planes (S, k*8, 64) sharded over ``frag`` on
    the STRIPE axis (stripe block j on device j).
    """
    p = mesh.devices.shape[mesh.axis_names.index("frag")]
    if (k * 8) % p:
        raise ValueError(f"k*8={k * 8} planes must divide over {p} "
                         "ring members")
    bbits = gf256.decode_bits_cached(k, rows)  # (k*8, k*8)

    def shard_body(planes, bb):
        # planes: (k*8/p, S, 64) — THIS member's fragment planes
        # bb:     (k*8, k*8/p)  — decode columns for these planes
        idx = jax.lax.axis_index("frag")
        s = planes.shape[1]
        blk = s // p

        def get_block(j):
            return jax.lax.dynamic_slice_in_dim(planes, j * blk, blk, 1)

        def contrib(j):
            """This member's XOR contribution to stripe block j:
            (blk, k*8, 64) = bb (k8, local) applied to local planes."""
            x = get_block(j)  # (local, blk, 64)
            # bitwise XOR-accumulate: out[r] = XOR over local planes c
            # with bb[r, c] == 1.  uint8 XOR has no matmul form; use
            # masked XOR-reduce over the (small) local plane dim.
            mask = bb.astype(jnp.uint8)  # (k8, local)
            # (k8, local, 1, 1) * (local, blk, 64) -> reduce local
            terms = mask[:, :, None, None] * x[None, :, :, :]
            out = terms[:, 0]
            for c in range(1, x.shape[0]):
                out = out ^ terms[:, c]
            return jnp.transpose(out, (1, 0, 2))  # (blk, k8, 64)

        # the accumulator starts as my contribution to the block that
        # will, after p-1 forwards, land on its owner
        acc = contrib((idx + (p - 1)) % p)

        def step(t, acc):
            # forward to the next ring member, then add my contribution
            # to the block that just arrived
            acc = jax.lax.ppermute(
                acc, "frag", [(d, (d + 1) % p) for d in range(p)])
            j = (idx + (p - 1) - (t + 1)) % p
            return acc ^ contrib(j)

        acc = jax.lax.fori_loop(0, p - 1, step, acc)
        return acc  # (blk, k8, 64): stripe block `idx`, fully reduced

    # split decode columns per member along the input-plane dim
    bb_full = jnp.asarray(bbits)

    # stripes shard over dp as well: each dp row runs its own
    # independent ring over its stripe slice (specs naming only frag
    # would replicate the whole problem dp times)
    fn = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P("frag", "dp", None), P(None, "frag")),
        out_specs=P(("dp", "frag"), None, None), check_vma=False)

    @jax.jit
    def run(planes):
        return fn(planes, bb_full)

    return run


def ring_decode(k: int, rows, frags: np.ndarray,
                mesh: Mesh | None = None) -> np.ndarray:
    """Decode k surviving fragments (fragment-major (k, S*512)) into
    the original bytes via the ring pipeline.  Stripe counts that do
    not divide the ring length are zero-padded internally and trimmed
    from the result — callers need not align anything."""
    from . import mesh_codec

    if mesh is None:
        mesh = mesh_codec.make_mesh()
    rows = tuple(int(x) for x in rows)
    x = gf256.frags_to_planes(frags, k)    # (S, k*8, 64)

    s = x.shape[0]
    p = mesh.devices.shape[mesh.axis_names.index("frag")]
    dp = mesh.devices.shape[mesh.axis_names.index("dp")]
    pad = (-s) % (p * dp)  # dp slices, each ring-split into p blocks
    if pad:
        x = np.concatenate(
            [x, np.zeros((pad, *x.shape[1:]), dtype=np.uint8)], axis=0)
    planes = np.ascontiguousarray(np.transpose(x, (1, 0, 2)))
    # jit is lazy: the lock SPANS the call (a declared graft-race
    # tables.LAZY_UNDER_LOCK_OK site — GL07 verifies the extent)
    with mesh_codec._BUILD_LOCK:
        out = _ring_decode_fn(k, rows, mesh)(jnp.asarray(planes))
    out = np.asarray(out)[:s]              # (S, k*8, 64)
    return out.reshape(s * k * gf256.CHUNK_SIZE)
