"""Disperse 16+4, the widest layout upstream supports (ISSUE 30): twenty
bricks, 8 KiB stripes, behind the batcher on a jax backend as the served
volume has it.  An acknowledged write lies on all twenty bricks as the
benchmark's plain reference encodes it (``benchmarks/harness/
reference.py``, which imports nothing of the program), and is read back
byte-exact from a seeded choice of sixteen of them with four stopped."""

import asyncio
import os

import numpy as np
import pytest

from benchmarks.harness import reference
from glusterfs_tpu.api.glfs import Client
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.utils.volspec import ec_volfile

K, R, N = 16, 4, 20
STRIPE = K * reference.CHUNK
SIZE = 1 << 20  # the cell's write: 128 stripes, one bucket


@pytest.mark.parametrize("seed", [30, 3000000019, 4100009406])
def test_an_acked_write_is_read_from_any_16_of_the_20_bricks(tmp_path, seed):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, 2 * SIZE, dtype=np.uint8)
    stopped = sorted(rng.choice(N, R, replace=False).tolist())
    assert any(i < K for i in stopped)  # else the read rebuilds nothing
    g = Graph.construct(ec_volfile(tmp_path, N, R, options={
        "cpu-extensions": "xla", "stripe-cache": "on",
        "stripe-cache-min-batch": 0, "systematic": "on"}))
    c, ec = Client(g), g.top
    assert (ec.k, ec.n, STRIPE) == (K, N, 8192)

    async def run():
        await c.mount()
        f = await c.create("/f", os.O_RDWR)
        try:
            for off in range(0, data.size, SIZE):
                assert await f.write(data[off:off + SIZE].tobytes(),
                                     off) == SIZE
            await f.fsync()
            # a full-stripe write behind the batcher goes out in two
            # parts, sixteen data calls and four parity calls
            assert ec.dump_private()["write_path"]["split"] == 2
            want = reference.encode(data, K, N)
            for i in range(N):
                with open(tmp_path / f"brick{i}" / "f", "rb") as b:
                    assert b.read() == want[i].tobytes(), f"brick {i}"
            for i in stopped:
                ec.set_child_up(i, False)
            assert bytes(await f.read(data.size, 0)) == data.tobytes()
            # and unaligned, across a stripe's edge
            assert bytes(await f.read(12345, STRIPE - 77)) == \
                data[STRIPE - 77:STRIPE - 77 + 12345].tobytes()
        finally:
            await f.close()
            await c.unmount()

    asyncio.run(run())
