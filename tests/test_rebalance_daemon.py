"""Elastic scale-out: the glusterd-managed rebalance daemon (ISSUE 11).

Covers the checkpoint math (canonical walk order, resume skipping),
the torn-read-safe migration fop sequence (temp + rename commit,
internal-op cleanup unlinks, gfid stability), live throttle retune,
the rebalance task row in plain ``volume status``, the EC
traffic-origin plumb, and the acceptance satellite: SIGKILL the
daemon mid-migration, respawn, and prove it CONTINUES from the
checkpoint and converges byte-identical.
"""

import asyncio
import os
import signal
import time

import pytest

from glusterfs_tpu.cluster.dht import XA_LINKTO, DistributeLayer
from glusterfs_tpu.core.fops import FopError
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.layer import Loc
from glusterfs_tpu.features.trash import INTERNAL_OP
from glusterfs_tpu.mgmt.rebalanced import Rebalancer, tag_rebalance_origin


def _volfile(base, n=3) -> str:
    out = []
    for i in range(n):
        out.append(f"volume b{i}\n    type storage/posix\n"
                   f"    option directory {base}/brick{i}\nend-volume\n")
    subs = " ".join(f"b{i}" for i in range(n))
    out.append(f"volume dist\n    type cluster/distribute\n"
               f"    subvolumes {subs}\nend-volume\n")
    return "\n".join(out)


# -- walk-order / checkpoint math (pure) ------------------------------------


def test_dir_key_is_preorder_position():
    """Preorder DFS with sorted children emits directory paths exactly
    in dir_key order — the property the checkpoint skip relies on."""
    key = Rebalancer.dir_key
    preorder = ["/", "/a", "/a/b", "/a/c", "/a/c/x", "/b", "/b/a"]
    keys = [key(p) for p in preorder]
    assert keys == sorted(keys)
    assert key("/") < key("/a") < key("/a/b") < key("/b")
    # a child always sorts after its parent
    assert key("/a/c") < key("/a/c/x")


def test_resume_skip_math():
    r = Rebalancer(None, "v", checkpoint={
        "phase": "migrate", "last_dir": "/a/c",
        "counters": {"moved": 7, "scanned": 9}})
    # counters carried over, resume marker recorded
    assert r.counters["moved"] == 7 and r.counters["scanned"] == 9
    assert r.resumed_from == {"phase": "migrate", "last_dir": "/a/c"}
    # a migrate-phase checkpoint means fix-layout finished earlier
    assert r._done_before_resume("fix-layout", "/zzz")
    # migrate dirs at/before the checkpoint are done, later ones not
    assert r._done_before_resume("migrate", "/")
    assert r._done_before_resume("migrate", "/a/b")
    assert r._done_before_resume("migrate", "/a/c")
    assert not r._done_before_resume("migrate", "/a/c/x")
    assert not r._done_before_resume("migrate", "/b")
    # no checkpoint -> nothing is skipped
    r2 = Rebalancer(None, "v")
    assert not r2._done_before_resume("migrate", "/")


def test_throttle_table_shape():
    """lazy/normal/aggressive map onto (width, pause) with lazy the
    only cooperative-yield mode (dht-rebalance.c:3269 scaling)."""
    t = DistributeLayer._THROTTLE
    assert set(t) == {"lazy", "normal", "aggressive"}
    assert t["lazy"][0] < t["normal"][0] < t["aggressive"][0]
    assert t["lazy"][1] > 0 and t["normal"][1] == 0


# -- migration fop sequence (in-process graph) ------------------------------


def _misplace(c, dht):
    """Create a file whose cached subvol differs from its hashed one
    (the rename-linkto shape rebalance exists to fix)."""
    src, dst = "alpha", "beta"
    if dht.hashed_idx(src) == dht.hashed_idx(dst):
        dst = "gamma2"
        assert dht.hashed_idx(src) != dht.hashed_idx(dst)
    return src, dst


def test_migrate_temp_rename_commit_and_internal_unlinks(tmp_path):
    """The safe sequence: data lands in a hidden reserved-suffix temp,
    commits via same-child rename, cleanup unlinks carry the
    internal-op flag (features/trash must not capture them), the gfid
    survives the move, and no temp or stale linkto is left behind."""
    from glusterfs_tpu.api.glfs import Client

    async def run():
        c = Client(Graph.construct(_volfile(tmp_path)))
        await c.mount()
        try:
            dht = c.graph.top
            src, dst = _misplace(c, dht)
            body = b"move me" * 500
            await c.write_file(f"/{src}", body)
            await c.rename(f"/{src}", f"/{dst}")
            g0 = (await c.stat(f"/{dst}")).gfid
            unlink_xdata = []
            for child in dht.children:
                orig = child.unlink

                async def spy(loc, xdata=None, _orig=orig):
                    unlink_xdata.append((loc.path, dict(xdata or {})))
                    return await _orig(loc, xdata)

                child.unlink = spy
            res = await dht.rebalance("/")
            assert len(res["moved"]) == 1, res
            assert res["status"]["failed"] == 0
            # every migration cleanup unlink is an internal-engine op
            assert unlink_xdata, "migration made no cleanup unlinks"
            assert all(x.get(INTERNAL_OP) for _p, x in unlink_xdata), \
                unlink_xdata
            # byte-identical at the new home, gfid stable
            assert bytes(await c.read_file(f"/{dst}")) == body
            ia = await c.stat(f"/{dst}")
            assert ia.gfid == g0, "migration re-minted the gfid"
            di = dht.hashed_idx(dst)
            assert (tmp_path / f"brick{di}" / dst).read_bytes() == body
            # exactly one copy, no temp, no linkto marker left
            for i in range(3):
                names = os.listdir(tmp_path / f"brick{i}")
                assert not any(n.endswith(dht.MIGRATE_SUFFIX)
                               for n in names), names
            count = sum((tmp_path / f"brick{i}" / dst).exists()
                        for i in range(3))
            assert count == 1
            with pytest.raises(FopError):
                await dht.children[di].getxattr(Loc(f"/{dst}"),
                                                XA_LINKTO)
        finally:
            await c.unmount()

    asyncio.run(run())


def test_failed_linkto_removal_aborts_before_source_unlink(tmp_path):
    """A failed linkto-marker removal after the rename commit must
    abort the migration BEFORE the source unlink: the surviving marker
    routes readers at the source, so deleting it would strand the file
    unreadable forever.  Failing instead keeps the file served from
    the source, and a later pass retries the whole migration."""
    import errno

    from glusterfs_tpu.api.glfs import Client

    async def run():
        c = Client(Graph.construct(_volfile(tmp_path)))
        await c.mount()
        try:
            dht = c.graph.top
            src, dst = _misplace(c, dht)
            body = b"keep me readable" * 400
            await c.write_file(f"/{src}", body)
            await c.rename(f"/{src}", f"/{dst}")
            target = dht.children[dht.hashed_idx(dst)]
            orig = target.removexattr
            fails = {"n": 0}

            async def flaky(loc, name, xdata=None):
                if name == XA_LINKTO:
                    fails["n"] += 1
                    raise FopError(errno.EIO, "brick hiccup")
                return await orig(loc, name, xdata)

            target.removexattr = flaky
            res = await dht.rebalance("/")
            assert res["status"]["failed"] == 1, res["status"]
            assert fails["n"] == 1
            # source copy survived: still readable, byte-identical
            assert bytes(await c.read_file(f"/{dst}")) == body
            # brick heals: the next pass redoes the migration whole
            target.removexattr = orig
            res = await dht.rebalance("/")
            assert res["status"]["failed"] == 0, res["status"]
            assert len(res["moved"]) == 1
            assert bytes(await c.read_file(f"/{dst}")) == body
            with pytest.raises(FopError):
                await target.getxattr(Loc(f"/{dst}"), XA_LINKTO)
        finally:
            await c.unmount()

    asyncio.run(run())


def test_committed_but_unswept_destination_not_clobbered(tmp_path):
    """A migrator that died between its rename commit and the source
    unlink left TWO real copies — and clients have been writing to the
    committed (hashed) one since.  The next walk must finish the dead
    migrator's teardown (unlink the stale source), never re-copy the
    stale source over the committed copy."""
    from glusterfs_tpu.api.glfs import Client

    async def run():
        c = Client(Graph.construct(_volfile(tmp_path)))
        await c.mount()
        try:
            dht = c.graph.top
            # names arranged so the stale source sits at a LOWER child
            # index than the hashed destination — the order in which a
            # _locate_real scan would find the stale copy first
            src = dst = None
            for s in ("alpha", "beta", "gamma2", "delta", "omega"):
                for d in ("alpha", "beta", "gamma2", "delta", "omega"):
                    if dht.hashed_idx(s) < dht.hashed_idx(d):
                        src, dst = s, d
                        break
                if src:
                    break
            assert src, "no name pair with si < hi on this layout"
            stale = b"pre-migration bytes" * 300
            await c.write_file(f"/{src}", stale)
            await c.rename(f"/{src}", f"/{dst}")
            g0 = (await c.stat(f"/{dst}")).gfid
            si, hi = dht.hashed_idx(src), dht.hashed_idx(dst)
            # forge the post-commit crash state at the hashed child:
            # linkto replaced by a committed real copy (same gfid, the
            # rename preserves it) that a client has since rewritten
            committed = b"client wrote AFTER the commit" * 200
            hc, loc = dht.children[hi], Loc(f"/{dst}")
            await hc.unlink(loc, {INTERNAL_OP: True})
            fd, _ = await hc.create(loc, os.O_RDWR | os.O_EXCL, 0o644,
                                    {"gfid-req": g0})
            await hc.writev(fd, committed, 0)
            await hc.release(fd)
            try:
                await hc.removexattr(loc, XA_LINKTO)
            except FopError:
                pass  # marker already absent
            unlink_xdata = []
            orig_unlink = dht.children[si].unlink

            async def spy(l, xdata=None):
                unlink_xdata.append((l.path, dict(xdata or {})))
                return await orig_unlink(l, xdata)

            dht.children[si].unlink = spy
            idx, fia = await dht._locate_real(loc)
            assert idx == si, "stale source must be the scan's find"
            nbytes = await dht._migrate_file(loc, fia, si, hi)
            assert nbytes == 0, "teardown must not re-copy bytes"
            # the committed copy survived, the stale source is gone,
            # and the teardown unlink was an internal-engine op
            assert bytes(await c.read_file(f"/{dst}")) == committed
            assert not (tmp_path / f"brick{si}" / dst).exists()
            assert unlink_xdata and \
                all(x.get(INTERNAL_OP) for _p, x in unlink_xdata)
        finally:
            await c.unmount()

    asyncio.run(run())


def test_committed_check_transport_error_never_guesses(tmp_path):
    """A transport error while probing the destination for the
    committed-copy state proves nothing — the check must propagate
    (counted failed, retried later), never unlink the source on a
    guess (the only real copy) or fall through to the copy path
    (clobbering a committed one)."""
    import errno

    from glusterfs_tpu.api.glfs import Client

    async def run():
        c = Client(Graph.construct(_volfile(tmp_path)))
        await c.mount()
        try:
            dht = c.graph.top
            src, dst = _misplace(c, dht)
            body = b"the only real copy" * 300
            await c.write_file(f"/{src}", body)
            await c.rename(f"/{src}", f"/{dst}")
            si, hi = dht.hashed_idx(src), dht.hashed_idx(dst)
            hc, loc = dht.children[hi], Loc(f"/{dst}")
            orig = hc.getxattr

            async def flaky(l, name=None, xdata=None):
                if name == XA_LINKTO:
                    raise FopError(errno.ENOTCONN, "brick dropped")
                return await orig(l, name, xdata)

            hc.getxattr = flaky
            ia, _ = await dht.children[si].lookup(loc)
            with pytest.raises(FopError):
                await dht._migrate_file(loc, ia, si, hi)
            # the source copy survived the failed probe
            hc.getxattr = orig
            assert bytes(await c.read_file(f"/{dst}")) == body
            assert (tmp_path / f"brick{si}" / dst).exists()
        finally:
            await c.unmount()

    asyncio.run(run())


def test_reserved_suffix_names_refused(tmp_path):
    """User names carrying the reserved migration-temp suffix are
    refused at every namespace entry point — accepted, they would be
    hidden from every listing and later DELETED by the orphan
    sweep."""
    import errno

    from glusterfs_tpu.api.glfs import Client

    async def run():
        c = Client(Graph.construct(_volfile(tmp_path)))
        await c.mount()
        try:
            dht = c.graph.top
            sfx = dht.MIGRATE_SUFFIX
            for attempt in (
                    c.write_file(f"/user{sfx}", b"x"),
                    c.mkdir(f"/dir{sfx}"),
                    dht.symlink("t", Loc(f"/sym{sfx}")),
                    dht.mknod(Loc(f"/dev{sfx}"))):
                with pytest.raises(FopError) as ei:
                    await attempt
                assert ei.value.err == errno.EPERM
            await c.write_file("/ok", b"fine")
            with pytest.raises(FopError) as ei:
                await c.rename("/ok", f"/ok{sfx}")
            assert ei.value.err == errno.EPERM
            with pytest.raises(FopError) as ei:
                await dht.link(Loc("/ok"), Loc(f"/lnk{sfx}"))
            assert ei.value.err == errno.EPERM
            assert bytes(await c.read_file("/ok")) == b"fine"
        finally:
            await c.unmount()

    asyncio.run(run())


def test_fresh_run_sweeps_orphan_temps(tmp_path):
    """A FRESH (checkpoint-free) daemon run still reclaims
    crash-orphaned migration temps — a crashed predecessor's
    checkpoint may have been abandoned (topology change, stop before
    restart), and the hidden temps are invisible to every other
    path."""
    from glusterfs_tpu.api.glfs import Client

    async def run():
        c = Client(Graph.construct(_volfile(tmp_path)))
        await c.mount()
        try:
            from glusterfs_tpu.core.iatt import gfid_new

            dht = c.graph.top
            await c.write_file("/keep", b"serving data" * 100)
            # forge the crash leftover the way the migrator makes it:
            # a hidden reserved-suffix temp on one child
            b1 = dht.children[1]
            tloc = Loc(f"/.dead{dht.MIGRATE_SUFFIX}")
            fd, _ = await b1.create(tloc, os.O_RDWR | os.O_EXCL, 0o600,
                                    {"gfid-req": gfid_new()})
            await b1.writev(fd, b"x" * 4096, 0)
            await b1.release(fd)
            orphan = tmp_path / "brick1" / f".dead{dht.MIGRATE_SUFFIX}"
            assert orphan.exists()
            reb = Rebalancer(c, "tv", mode="full",
                             checkpoint_interval=0.01)
            assert reb.resumed_from is None  # genuinely fresh
            await reb.run()
            assert reb.phase == "done"
            assert reb.counters["temps_swept"] >= 1, reb.counters
            assert not orphan.exists()
        finally:
            await c.unmount()

    asyncio.run(run())


def test_concurrent_readers_never_torn(tmp_path):
    """Readers racing the migration see the old full bytes or the new
    full bytes — never a partial copy and never a transient ENOENT
    (the temp+rename commit plus the re-resolution retry)."""
    from glusterfs_tpu.api.glfs import Client

    async def run():
        c = Client(Graph.construct(_volfile(tmp_path)))
        await c.mount()
        try:
            dht = c.graph.top
            src, dst = _misplace(c, dht)
            body = os.urandom(256 * 1024)
            await c.write_file(f"/{src}", body)
            await c.rename(f"/{src}", f"/{dst}")
            stop = asyncio.Event()
            reads = {"n": 0}

            async def reader():
                while not stop.is_set():
                    got = await c.read_file(f"/{dst}")
                    assert bytes(got) == body, "reader saw a torn file"
                    reads["n"] += 1
                    await asyncio.sleep(0)

            tasks = [asyncio.ensure_future(reader()) for _ in range(3)]
            try:
                res = await dht.rebalance("/")
                assert len(res["moved"]) == 1
                # keep reading a beat after the commit
                await asyncio.sleep(0.05)
            finally:
                stop.set()
                await asyncio.gather(*tasks)
            assert reads["n"] > 0
            assert bytes(await c.read_file(f"/{dst}")) == body
        finally:
            await c.unmount()

    asyncio.run(run())


def test_rebalancer_throttle_retunes_live(tmp_path):
    """volume-set of cluster.rebal-throttle mid-run retunes the NEXT
    wave (the daemon reads the option per wave; here the reconfigure
    lands through the same opts object a live volfile push updates)."""
    from glusterfs_tpu.api.glfs import Client

    async def run():
        c = Client(Graph.construct(_volfile(tmp_path)))
        await c.mount()
        try:
            dht = c.graph.top
            dht.reconfigure({"rebal-throttle": "lazy"})
            # misplace many files: rename leaves the data at the old
            # hashed child behind a linkto, so most need migration
            for i in range(18):
                await c.write_file(f"/n{i:02d}", f"n{i:02d}".encode() * 50)
                await c.rename(f"/n{i:02d}", f"/m{i:02d}")
            flips = {"n": 0}
            real_migrate = dht._migrate_file

            async def spy(cloc, ia, idx, hi):
                out = await real_migrate(cloc, ia, idx, hi)
                if flips["n"] == 0:
                    # live volume-set mid-run: the NEXT wave widens
                    dht.reconfigure({"rebal-throttle": "aggressive"})
                flips["n"] += 1
                return out

            dht._migrate_file = spy
            reb = Rebalancer(c, "tv", mode="full",
                             checkpoint_interval=0.01)
            await reb.run()
            assert reb.phase == "done"
            assert reb.counters["failed"] == 0
            assert reb.counters["moved"] >= 2, reb.counters
            # the wave after the flip read the retuned mode and widened
            assert reb.throttle == "aggressive", reb.throttle
            assert reb.max_inflight > 1, reb.max_inflight
            assert reb.counters["scanned"] >= 18
        finally:
            await c.unmount()

    asyncio.run(run())


# -- EC traffic-origin plumb -------------------------------------------------


def test_ec_traffic_origin_default_and_rebalance_tag(tmp_path):
    """The daemon tags its private graph's EC layers
    traffic_origin="rebalance"; codec batches then carry that origin
    (mesh/batch family attribution), while explicit heal call sites
    keep origin="heal"."""
    from glusterfs_tpu.api.glfs import Client

    vf = []
    for i in range(3):
        vf.append(f"volume e{i}\n    type storage/posix\n"
                  f"    option directory {tmp_path}/eb{i}\nend-volume\n")
    vf.append("volume ec\n    type cluster/disperse\n"
              "    option redundancy 1\n"
              "    subvolumes e0 e1 e2\nend-volume\n")

    async def run():
        c = Client(Graph.construct("\n".join(vf)))
        await c.mount()
        try:
            ec = c.graph.top
            assert ec.traffic_origin == "serve"
            tagged = tag_rebalance_origin(c.graph)
            assert tagged >= 1
            assert ec.traffic_origin == "rebalance"

            seen = []

            class StubCodec:
                async def encode_async(self, buf, origin="serve",
                                       launched=None):
                    seen.append(origin)
                    return buf  # the plumb is under test, not the math

            real_codec, real_batching = ec.codec, ec._batching
            ec.codec, ec._batching = StubCodec(), True
            try:
                await ec._codec_encode(b"")
                await ec._codec_encode(b"", origin="heal")
            finally:
                ec.codec, ec._batching = real_codec, real_batching
            assert seen == ["rebalance", "heal"], seen
        finally:
            await c.unmount()

    asyncio.run(run())


def test_migrate_streaming_rides_delta_path_with_rebalance_origin(
        tmp_path):
    """A streamed migration copy onto a healthy systematic disperse
    destination pre-sizes the temp and stripe-aligns its windows, so
    the unaligned tail rides the PR-10 parity-delta path (no full
    RMW), and the gftpu_ec_delta_writes_total family attributes it to
    origin="rebalance" (ROADMAP item 3, narrow form)."""
    from glusterfs_tpu.api.glfs import Client
    from glusterfs_tpu.core.metrics import REGISTRY

    vf = []
    for g in range(2):
        for i in range(3):
            vf.append(f"volume e{g}{i}\n    type storage/posix\n"
                      f"    option directory {tmp_path}/b{g}{i}\n"
                      "end-volume\n")
        subs = " ".join(f"e{g}{i}" for i in range(3))
        vf.append(f"volume ec{g}\n    type cluster/disperse\n"
                  "    option redundancy 1\n"
                  "    option systematic on\n"
                  f"    subvolumes {subs}\nend-volume\n")
    vf.append("volume dist\n    type cluster/distribute\n"
              "    option rebal-migrate-window 64KB\n"
              "    subvolumes ec0 ec1\nend-volume\n")

    async def run():
        c = Client(Graph.construct("\n".join(vf)))
        await c.mount()
        try:
            dht = c.graph.top
            src, dst = _misplace(c, dht)
            stripe = dht.children[0].stripe
            # two full 64 KiB windows + an unaligned 700-byte tail:
            # the streaming path (size > window), tail not a stripe
            # multiple
            size = 2 * 64 * 1024 + 700
            assert size % stripe, "tail must be unaligned"
            body = bytes(range(256)) * (size // 256) + b"T" * (size % 256)
            await c.write_file(f"/{src}", body)
            await c.rename(f"/{src}", f"/{dst}")
            tag_rebalance_origin(c.graph)
            dec = dht.children[dht.hashed_idx(dst)]
            assert dht._delta_stripe(dec) == stripe
            rmw0 = dec.write_path["rmw"]
            delta0 = dec.delta_origin.get("rebalance", 0)
            res = await dht.rebalance("/")
            assert len(res["moved"]) == 1, res
            assert res["status"]["failed"] == 0
            # the tail took the delta plane, attributed to rebalance
            assert dec.delta_origin.get("rebalance", 0) == delta0 + 1, \
                dec.delta_origin
            # ...and NOTHING on the destination paid a full RMW: the
            # aligned windows are pure encodes over the pre-sized temp
            assert dec.write_path["rmw"] == rmw0, dec.write_path
            snap = REGISTRY.snapshot()
            by_origin = {
                s[0].get("origin"): s[1]
                for s in snap["gftpu_ec_delta_writes_total"]["samples"]
                if s[0]["layer"] == dec.name}
            assert by_origin.get("rebalance", 0) >= 1, by_origin
            assert bytes(await c.read_file(f"/{dst}")) == body
        finally:
            await c.unmount()

    asyncio.run(run())


# -- glusterd surfaces -------------------------------------------------------


def test_volume_status_tasks_rebalance_row(tmp_path):
    """An active rebalance shows in plain ``volume status`` as a task
    row beside the remove-brick one (_add_task_to_dict analog); a
    drain-mode walk reports through the remove-brick row only."""
    from glusterfs_tpu.mgmt.glusterd import Glusterd

    d = Glusterd(str(tmp_path / "gd"))
    d.state["volumes"]["tv"] = {
        "name": "tv", "type": "distribute", "status": "started",
        "bricks": [], "options": {}}
    st = d.op_volume_status("tv")
    assert "tasks" not in st
    d.state["volumes"]["tv"]["rebalance"] = {
        "status": "started", "mode": "full", "phase": "migrate",
        "node": d.uuid, "counters": {"moved": 3},
        "throttle": "normal"}
    st = d.op_volume_status("tv")
    rows = [t for t in st["tasks"] if t["type"] == "rebalance"]
    assert rows and rows[0]["status"] == "started"
    assert rows[0]["phase"] == "migrate"
    assert rows[0]["counters"] == {"moved": 3}
    # drain mode: the remove-brick row IS the task row
    d.state["volumes"]["tv"]["rebalance"]["mode"] = "drain"
    d.state["volumes"]["tv"]["remove-brick"] = {
        "status": "started", "bricks": ["tv-brick-2"]}
    st = d.op_volume_status("tv")
    types = [t["type"] for t in st["tasks"]]
    assert types == ["remove-brick"], types


def test_registry_families_present(tmp_path):
    """The gftpu_rebalance_* families exist and label by volume."""
    from glusterfs_tpu.core.metrics import REGISTRY

    r = Rebalancer(None, "famvol")
    r.counters["moved"] = 4
    r.counters["bytes_moved"] = 4096
    r.counters["failed"] = 1
    r.phase = "migrate"
    snap = REGISTRY.snapshot()
    for fam in ("gftpu_rebalance_files_total",
                "gftpu_rebalance_bytes_total",
                "gftpu_rebalance_failures_total",
                "gftpu_rebalance_phase"):
        assert fam in snap, fam
    rows = {tuple(sorted(s[0].items())): s[1]
            for s in snap["gftpu_rebalance_files_total"]["samples"]}
    assert rows[(("result", "moved"), ("volume", "famvol"))] == 4
    phase = [s for s in snap["gftpu_rebalance_phase"]["samples"]
             if s[0].get("volume") == "famvol"]
    assert phase and phase[0][1] == 2  # migrate


# -- the acceptance satellite: SIGKILL + respawn resumes ---------------------


def test_checkpoint_resume_after_sigkill(tmp_path):
    """SIGKILL the managed daemon mid-migration, respawn through
    ``volume rebalance start``, and prove it CONTINUES from the
    checkpoint (counters carry, fix-layout is not redone, resumed_from
    recorded) and converges byte-identical."""
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    async def run():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-create", name="rv",
                             vtype="distribute", redundancy=0,
                             bricks=[{"path": str(tmp_path / f"b{i}")}
                                     for i in range(2)])
                await c.call("volume-start", name="rv")
                await c.call("volume-set", name="rv",
                             key="rebalance.checkpoint-interval",
                             value="0.05")
                await c.call("volume-set", name="rv",
                             key="cluster.rebal-throttle", value="lazy")
            cl = await mount_volume(d.host, d.port, "rv")
            data = {}
            try:
                for dd in range(6):
                    await cl.mkdir(f"/d{dd}")
                    for i in range(8):
                        p = f"/d{dd}/f{i}"
                        data[p] = f"{p}-x".encode() * 300
                        await cl.write_file(p, data[p])
                async with MgmtClient(d.host, d.port) as c:
                    await c.call("volume-add-brick", name="rv",
                                 bricks=[{"path": str(tmp_path / "b2")}])
                    out = await c.call("volume-rebalance", name="rv",
                                       action="start")
                    assert out["status"] == "started", out

                    def rb():
                        return d._vol("rv").get("rebalance") or {}

                    deadline = time.monotonic() + 120
                    while True:
                        r = rb()
                        ck = r.get("checkpoint") or {}
                        if r.get("phase") == "migrate" and \
                                ck.get("last_dir") and \
                                (r.get("counters") or {}).get(
                                    "moved", 0) >= 1:
                            break
                        assert r.get("status") == "started", r
                        assert time.monotonic() < deadline, r
                        await asyncio.sleep(0.02)
                    pre = dict(rb()["counters"])
                    proc = d.rebalanced["rv"]
                    os.kill(proc.pid, signal.SIGKILL)
                    proc.wait()
                    # respawn through the SAME op: a dead daemon with
                    # status=started resumes, never errors
                    out = await c.call("volume-rebalance", name="rv",
                                       action="start")
                    assert out["status"] == "resumed", out
                    deadline = time.monotonic() + 240
                    while rb().get("status") not in ("completed",
                                                     "failed"):
                        assert time.monotonic() < deadline, rb()
                        await asyncio.sleep(0.2)
                    r = rb()
                    assert r["status"] == "completed", r
                    # CONTINUED, not restarted: resume marker present,
                    # counters monotonic over the checkpoint, and the
                    # fix-layout phase was NOT rerun
                    assert r.get("resumed_from", {}).get("last_dir"), r
                    fin = r["counters"]
                    assert fin["scanned"] > pre["scanned"], (pre, fin)
                    assert fin["moved"] >= pre["moved"]
                    assert fin["dirs_fixed"] == pre["dirs_fixed"], \
                        "respawn redid fix-layout (restart, not resume)"
                for p, body in data.items():
                    assert bytes(await cl.read_file(p)) == body, p
            finally:
                await cl.unmount()
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-stop", name="rv")
        finally:
            await d.stop()

    asyncio.run(run())


@pytest.mark.slow
def test_remove_brick_drain_rides_daemon_with_stop(tmp_path):
    """remove-brick start spawns the drain-mode daemon (status /
    checkpoints for free); stop aborts it and restores the layout."""
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    async def run():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-create", name="sv",
                             vtype="distribute", redundancy=0,
                             bricks=[{"path": str(tmp_path / f"b{i}")}
                                     for i in range(3)])
                await c.call("volume-start", name="sv")
            cl = await mount_volume(d.host, d.port, "sv")
            data = {}
            try:
                for i in range(16):
                    p = f"/f{i:02d}"
                    data[p] = f"{p}-body".encode() * 80
                    await cl.write_file(p, data[p])
                async with MgmtClient(d.host, d.port) as c:
                    await c.call("volume-remove-brick", name="sv",
                                 bricks=["sv-brick-2"], action="start")
                    assert (d._vol("sv").get("rebalance")
                            or {}).get("mode") == "drain"
                    deadline = time.monotonic() + 180
                    while True:
                        st = await c.call("volume-remove-brick",
                                          name="sv", bricks=[],
                                          action="status")
                        if st.get("status") in ("completed", "failed"):
                            break
                        assert time.monotonic() < deadline, st
                        await asyncio.sleep(0.3)
                    assert st["status"] == "completed", st
                    assert st.get("moved", 0) >= 1
                    leftover = [
                        x for x in os.listdir(tmp_path / "b2")
                        if not x.startswith(".glusterfs")]
                    assert not leftover, leftover
                    await c.call("volume-remove-brick", name="sv",
                                 bricks=[], action="commit")
                    # a fresh shrink can be aborted with stop
                    await c.call("volume-remove-brick", name="sv",
                                 bricks=["sv-brick-1"], action="start")
                    out = await c.call("volume-remove-brick", name="sv",
                                       bricks=[], action="stop")
                    assert out["status"] == "stopped"
                    assert "remove-brick" not in d._vol("sv")
                for p, body in data.items():
                    for _ in range(40):  # graph swap settling
                        try:
                            got = await cl.read_file(p)
                            break
                        except FopError:
                            await asyncio.sleep(0.25)
                    assert bytes(got) == body, p
            finally:
                await cl.unmount()
        finally:
            await d.stop()

    asyncio.run(run())


# -- growth placement safety (the chaos-caught pair) ------------------------


def test_no_layout_dir_places_on_holders(tmp_path):
    """A directory with NO layout xattr that exists on only a subset
    of children (the pre-add-brick namespace of a grown single-leg
    volume) derives its split over the HOLDERS — hashing over all
    children would route creates at a child with no parent directory
    to create under (the rebalance_grow chaos scenario's ENOENT)."""
    from glusterfs_tpu.api.glfs import Client

    async def run():
        c = Client(Graph.construct(_volfile(tmp_path)))
        await c.mount()
        try:
            dht = c.graph.top
            # the dir exists ONLY on child 0, stamped by nobody — as
            # if created before the other legs were added
            await dht.children[0].mkdir(Loc("/old"), 0o755)
            for i in range(40):
                assert await dht._placed(Loc(f"/old/f{i}")) == 0
            layout, authoritative = await dht._dir_meta("/old")
            assert layout and {r[2] for r in layout} == {0}
            assert not authoritative  # a miss here proves NOTHING
            # a serving create lands (on the holder), bytes exact
            await c.write_file("/old/newfile", b"grown" * 100)
            assert bytes(await c.read_file("/old/newfile")) \
                == b"grown" * 100
            assert (tmp_path / "brick0" / "old" / "newfile").exists()
            # once fix-layout stamps ranges the holders rule retires
            dht._layouts.clear()
            await dht.fix_layout("/old")
            dht._layouts.clear()
            layout2, auth2 = await dht._dir_meta("/old")
            assert layout2 and auth2
        finally:
            await c.unmount()

    asyncio.run(run())


def test_locate_real_sees_optimize_pruned_file_and_walk_fixes_it(tmp_path):
    """A file created through a stale parent layout sits misplaced
    with no linkto; cluster.lookup-optimize (default on) prunes it
    into ENOENT for serving lookups — _locate_real still finds it and
    the migrate walk moves it home, restoring visibility."""
    from glusterfs_tpu.api.glfs import Client

    async def run():
        c = Client(Graph.construct(_volfile(tmp_path)))
        await c.mount()
        try:
            dht = c.graph.top
            assert dht.opts["lookup-optimize"]
            await c.mkdir("/d")  # stamps an authoritative layout
            name = None
            for i in range(64):
                if await dht._placed(Loc(f"/d/x{i}")) != 1:
                    name = f"x{i}"
                    break
            assert name is not None
            # plant the file on a NON-owner child, no linkto anywhere
            fd, _ = await dht.children[1].create(Loc(f"/d/{name}"), 0,
                                                 0o644, {})
            await dht.children[1].writev(fd, b"stale-routed" * 64, 0)
            owner = await dht._placed(Loc(f"/d/{name}"))
            assert owner != 1
            # serving resolution prunes it invisible...
            with pytest.raises(FopError):
                await dht._cached_idx(Loc(f"/d/{name}"))
            # ...the migrator's resolution does not
            idx, ia = await dht._locate_real(Loc(f"/d/{name}"))
            assert idx == 1 and ia.size == len(b"stale-routed") * 64
            # and one migrate pass restores serving visibility
            reb = Rebalancer(c, "v", mode="drain")  # migrate-only walk
            out = await reb.run()
            assert out["counters"]["moved"] >= 1, out
            assert out["counters"]["failed"] == 0, out
            assert bytes(await c.read_file(f"/d/{name}")) \
                == b"stale-routed" * 64
        finally:
            await c.unmount()

    asyncio.run(run())


# -- checkpoint safety against glusterd (unit) ------------------------------


def test_checkpoint_reuse_guarded_by_topology(tmp_path):
    """stop -> start continues from the stop's checkpoint ONLY under
    the same topology fingerprint: a checkpoint taken before add-brick
    would skip fix-layout for the new leg, so a grown volume restarts
    the walk instead of resuming."""
    from glusterfs_tpu.mgmt.glusterd import Glusterd

    d = Glusterd(str(tmp_path / "gd"))
    vol = {"name": "v", "status": "created", "version": 1,
           "bricks": [{"name": "n:/b0"}, {"name": "n:/b1"}]}
    d.state["volumes"] = {"v": vol}
    ck = {"phase": "migrate", "dir": ["0", "3"],
          "counters": {"moved": 3, "scanned": 9}}
    vol["rebalance"] = {"status": "stopped", "mode": "full",
                        "checkpoint": ck,
                        "topology": d._rebal_topology(vol)}
    # same topology: the stop's checkpoint rides into the new run
    d.commit_rebalance_start("v", "full", "peer-uuid", 1.0)
    assert vol["rebalance"]["checkpoint"] == ck
    # grow the volume between stop and start ...
    vol["rebalance"]["status"] = "stopped"
    vol["bricks"].append({"name": "n:/b2"})
    # ... and the stale checkpoint must NOT steer the restarted run
    d.commit_rebalance_start("v", "full", "peer-uuid", 2.0)
    assert "checkpoint" not in vol["rebalance"]
    # drain fingerprints too: same bricks, different leaver set
    vol["rebalance"] = {"status": "stopped", "mode": "drain",
                        "checkpoint": ck,
                        "topology": d._rebal_topology(vol)}
    vol["remove-brick"] = {"bricks": ["n:/b2"]}
    d.commit_rebalance_start("v", "drain", "peer-uuid", 3.0)
    assert "checkpoint" not in vol["rebalance"]


def test_kill_rebalanced_harvests_statusfile_checkpoint(tmp_path):
    """SIGTERM stop: the daemon's final rebalance-update cannot land
    while glusterd blocks in wait(), so _kill_rebalanced harvests the
    daemon's statusfile snapshot into the volinfo (this is what keeps
    the stop-continues-from-the-stop's-checkpoint contract)."""
    import json
    import subprocess
    import sys

    from glusterfs_tpu.mgmt.glusterd import Glusterd

    d = Glusterd(str(tmp_path / "gd"))
    vol = {"name": "v", "status": "started", "version": 1,
           "bricks": [{"name": "n:/b0"}],
           "rebalance": {"status": "started", "mode": "full",
                         "node": d.uuid}}
    d.state["volumes"] = {"v": vol}
    snap = {"phase": "migrate",
            "checkpoint": {"phase": "migrate", "dir": ["0", "3"]},
            "counters": {"moved": 7, "scanned": 21}}
    with open(os.path.join(d.workdir, "rebalanced-v.json"), "w") as f:
        json.dump(snap, f)
    # stand-in daemon: alive until terminate() reaps it
    d.rebalanced["v"] = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(60)"])
    d._kill_rebalanced("v")
    rb = vol["rebalance"]
    assert rb["checkpoint"] == snap["checkpoint"]
    assert rb["counters"]["moved"] == 7
    assert rb["phase"] == "migrate"
    # a COMPLETED record is never clobbered by a stale statusfile
    rb["status"] = "completed"
    rb["counters"] = {"moved": 8}
    d._harvest_rebal_statusfile("v")
    assert rb["counters"] == {"moved": 8}


def test_opversion_13_gates_rebalance_and_drain(tmp_path):
    """Both daemon-riding ops refuse below cluster op-version 13: a
    v12 peer has neither the rebalance-start commit nor the
    rebalance-update RPC, and remove-brick start failing mid-txn-pair
    would strand the decommission 'started' with no daemon draining
    it."""
    from glusterfs_tpu.mgmt.glusterd import Glusterd, MgmtError

    d = Glusterd(str(tmp_path / "gd"))
    d.state["volumes"] = {"v": {
        "name": "v", "status": "started", "version": 1,
        "type": "distribute",
        "bricks": [{"name": "n:/b0"}, {"name": "n:/b1"}]}}
    d.cluster_op_version = lambda: 12

    async def run():
        # the gate re-handshakes before refusing; stub the poll
        async def noop():
            return None
        d._refresh_peers = noop
        with pytest.raises(MgmtError, match="op-version >= 13"):
            await d.op_volume_rebalance("v", action="start")
        # refused BEFORE brick validation or any txn: the record
        # stays untouched
        with pytest.raises(MgmtError, match="op-version >= 13"):
            await d.op_volume_remove_brick("v", ["n:/b0"],
                                           action="start")
        assert "remove-brick" not in d.state["volumes"]["v"]
        assert "rebalance" not in d.state["volumes"]["v"]

    asyncio.run(run())


def test_fresh_spawn_drops_stale_statusfile(tmp_path):
    """A FRESH rebalance run must not inherit the previous run's
    statusfile: the daemon writes it only at its first push (after
    the mount settles), so a stop before that would harvest the OLD
    run's checkpoint into the new record — under the new record's
    own topology stamp, where the fingerprint guard cannot catch
    it."""
    from glusterfs_tpu.mgmt.glusterd import Glusterd

    d = Glusterd(str(tmp_path / "gd"))
    vol = {"name": "v", "status": "started", "version": 1,
           "bricks": [{"name": "n:/b0"}],
           "rebalance": {"status": "started", "mode": "full",
                         "node": d.uuid}}
    d.state["volumes"] = {"v": vol}
    sf = os.path.join(d.workdir, "rebalanced-v.json")
    with open(sf, "w") as f:
        f.write('{"checkpoint": {"phase": "migrate", "dir": ["9"]}}')
    try:
        d._spawn_rebalanced(vol)  # fresh: no checkpoint in the record
        assert not os.path.exists(sf), "stale statusfile survived"
        d.rebalanced.pop("v").kill()
        # a RESUME keeps the file: the volinfo checkpoint is
        # authoritative and the snapshot belongs to this same run
        vol["rebalance"]["checkpoint"] = {"phase": "migrate",
                                          "dir": ["0"]}
        with open(sf, "w") as f:
            f.write("{}")
        d._spawn_rebalanced(vol)
        assert os.path.exists(sf)
    finally:
        p = d.rebalanced.pop("v", None)
        if p is not None:
            p.kill()
