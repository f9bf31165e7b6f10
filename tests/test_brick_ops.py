"""Elasticity brick ops: add-brick growth, remove-brick drain + commit
(decommission rebalance), replace-brick rebuild
(glusterd-brick-ops.c / glusterd-replace-brick.c analogs)."""

import asyncio
import os

import pytest

from glusterfs_tpu.core.fops import FopError
from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                         mount_volume)


from tests.harness import wait_async as _wait


@pytest.mark.slow
def test_add_and_remove_brick_distribute(tmp_path):
    async def run():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-create", name="ev",
                             vtype="distribute",
                             bricks=[{"path": str(tmp_path / f"b{i}")}
                                     for i in range(2)])
                await c.call("volume-start", name="ev")
                m = await mount_volume(d.host, d.port, "ev")
                try:
                    names = [f"f{i:02d}" for i in range(16)]
                    for n in names:
                        await m.write_file(f"/{n}", n.encode())

                    # grow: third brick joins the layout after the
                    # pushed graph swap
                    out = await c.call("volume-add-brick", name="ev",
                                       bricks=[{"path":
                                                str(tmp_path / "b2")}])
                    assert out["added"] == ["ev-brick-2"]

                    async def swapped():
                        return any(
                            l.type_name == "protocol/client" and
                            "ev-client-2" == l.name
                            for l in m.graph.by_name.values())

                    assert await _wait(swapped), "client graph not swapped"
                    # everything still readable (lookup-everywhere)
                    for n in names:
                        assert await m.read_file(f"/{n}") == n.encode()
                    # rebalance settles files onto the 3-way layout
                    from glusterfs_tpu.cluster.dht import DistributeLayer

                    dht = next(l for l in m.graph.by_name.values()
                               if isinstance(l, DistributeLayer))
                    await dht.rebalance("/")
                    assert any((tmp_path / "b2" / n).exists()
                               for n in names), "no data moved to b2"

                    # shrink: drain b2 again
                    await c.call("volume-remove-brick", name="ev",
                                 bricks=["ev-brick-2"], action="start")

                    async def drained():
                        st = await c.call("volume-remove-brick",
                                          name="ev", bricks=[],
                                          action="status")
                        return st.get("status") == "completed"

                    assert await _wait(drained), "drain did not finish"
                    # all data back off the leaving brick
                    left = [n for n in names
                            if (tmp_path / "b2" / n).exists()
                            and (tmp_path / "b2" / n).stat().st_size]
                    assert not left, left
                    await c.call("volume-remove-brick", name="ev",
                                 bricks=[], action="commit")
                    info = await c.call("volume-info", name="ev")
                    assert len(info["ev"]["bricks"]) == 2

                    # commit pushes a 2-brick volfile; like the
                    # add-brick half above, wait for the swapped
                    # graph's clients to CONNECT before reading (the
                    # swap window is sub-second but real)
                    async def settled():
                        cls = [l for l in m.graph.by_name.values()
                               if l.type_name == "protocol/client"]
                        return len(cls) == 2 and \
                            all(l.connected for l in cls)

                    assert await _wait(settled), "post-commit swap"
                    for n in names:
                        assert await m.read_file(f"/{n}") == n.encode()
                finally:
                    await m.unmount()
                await c.call("volume-stop", name="ev")
        finally:
            await d.stop()

    asyncio.run(run())


@pytest.mark.slow
def test_replace_brick_heals_replica(tmp_path):
    async def run():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-create", name="rv",
                             vtype="replicate",
                             bricks=[{"path": str(tmp_path / f"r{i}")}
                                     for i in range(2)])
                await c.call("volume-start", name="rv")
                m = await mount_volume(d.host, d.port, "rv")
                try:
                    await m.write_file("/keep", b"precious" * 64)
                finally:
                    await m.unmount()
                # swap replica 1 for an empty directory
                await c.call("volume-replace-brick", name="rv",
                             brick="rv-brick-1",
                             new_path=str(tmp_path / "r1new"))
                info = await c.call("volume-info", name="rv")
                assert info["rv"]["bricks"][1]["path"] == \
                    str(tmp_path / "r1new")

                async def healed():
                    p = tmp_path / "r1new" / "keep"
                    return p.exists() and \
                        p.read_bytes() == b"precious" * 64

                assert await _wait(lambda: healed()), \
                    "replaced brick not rebuilt"
                # distribute volumes must refuse (data loss)
                await c.call("volume-create", name="dv",
                             vtype="distribute",
                             bricks=[{"path": str(tmp_path / "dx")}])
                with pytest.raises(FopError):
                    await c.call("volume-replace-brick", name="dv",
                                 brick="dv-brick-0",
                                 new_path=str(tmp_path / "dy"))
                await c.call("volume-stop", name="rv")
        finally:
            await d.stop()

    asyncio.run(run())


def test_managed_4p2_volume_started_over_the_rpc_serves_bytes(tmp_path):
    """The program's own spawner with real brick processes (ISSUE 35):
    ``volume-start`` brings the six up side by side, each on a port of
    its own and recorded in brick order, and the mounted volume gives
    back what was written, also with two of the six stopped."""
    data = os.urandom((1 << 20) + 12345)

    async def run():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call(
                    "volume-create", name="mv", vtype="disperse",
                    bricks=[{"path": str(tmp_path / f"b{i}")}
                            for i in range(6)], redundancy=2)
                await c.call("volume-start", name="mv")
                status = await c.call("volume-status", name="mv")
                assert all(b["online"] for b in status["bricks"])
                names = [f"mv-brick-{i}" for i in range(6)]
                bricks = d.state["volumes"]["mv"]["bricks"]
                assert list(d.bricks) == names
                assert [b["port"] for b in bricks] == \
                    [d.ports[n] for n in names]
                assert len({b["port"] for b in bricks}) == 6
                client = await mount_volume(d.host, d.port, "mv")
                try:
                    await client.write_file("/f", data)
                    assert await client.read_file("/f") == data
                    for brick in names[1::3]:
                        await c.call("volume-brick", name="mv",
                                     brick=brick, action="stop")
                    assert await client.read_file("/f") == data
                finally:
                    await client.unmount()
        finally:
            await d.stop()

    asyncio.run(run())
