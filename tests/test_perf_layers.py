"""Performance layers: write-behind aggregation, io-cache hits,
read-ahead, md-cache invalidation, quick-read, open-behind, nl-cache,
readdir-ahead, io-threads gating (reference tests/performance/ +
write-behind.md semantics)."""

import asyncio

import pytest

from glusterfs_tpu.api.glfs import SyncClient
from glusterfs_tpu.core.fops import FopError
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.layer import Loc


def _vol(tmp_path, *layers) -> str:
    out = [f"volume posix\n    type storage/posix\n"
           f"    option directory {tmp_path}/b\nend-volume\n"]
    prev = "posix"
    for i, (ltype, opts) in enumerate(layers):
        name = f"l{i}"
        body = "".join(f"    option {k} {v}\n" for k, v in opts.items())
        out.append(f"volume {name}\n    type {ltype}\n{body}"
                   f"    subvolumes {prev}\nend-volume\n")
        prev = name
    return "\n".join(out)


def _client(tmp_path, *layers) -> SyncClient:
    c = SyncClient(Graph.construct(_vol(tmp_path, *layers)))
    c.mount()
    return c


def test_write_behind(tmp_path):
    c = _client(tmp_path, ("performance/write-behind",
                           {"window-size": "64KB"}))
    wb = c.graph.top
    posix = c.graph.by_name["posix"]
    f = c.create("/f")
    for i in range(8):
        f.write(b"A" * 1000, i * 1000)  # adjacent: coalesce, below window
    # nothing flushed yet (below window): posix saw create only
    assert posix.stats.get("writev") is None
    assert f.read(4, 0) == b"AAAA"  # read forces flush
    assert posix.stats["writev"].count == 1  # coalesced to ONE write
    f.close()
    assert c.read_file("/f") == b"A" * 8000
    c.close()


def test_write_behind_deferred_error(tmp_path):
    vf = _vol(tmp_path) + """
volume errg
    type debug/error-gen
    option failure 100
    option enable writev
    subvolumes posix
end-volume
volume wb
    type performance/write-behind
    subvolumes errg
end-volume
"""
    c = SyncClient(Graph.construct(vf))
    c.mount()
    f = c.create("/f")
    f.write(b"x", 0)  # buffered: acked
    with pytest.raises(FopError):
        f.fsync()  # flush surfaces the injected error
    c.close()


def test_io_cache(tmp_path):
    c = _client(tmp_path, ("performance/io-cache", {"page-size": "4KB"}))
    ioc = c.graph.top
    posix = c.graph.by_name["posix"]
    c.write_file("/f", b"z" * 10000)
    assert c.read_file("/f") == b"z" * 10000
    n1 = posix.stats["readv"].count
    assert c.read_file("/f") == b"z" * 10000  # cached
    assert posix.stats["readv"].count == n1
    assert ioc.hits > 0
    # write invalidates
    f = c.open("/f")
    f.write(b"y", 0)
    f.close()
    assert c.read_file("/f")[:1] == b"y"
    c.close()


def test_io_cache_cross_client_revalidation(tmp_path):
    """Cached pages older than cache-timeout are revalidated against
    the file's mtime (ioc_cache_validate): a change made BEHIND the
    cache (another client / direct brick write) becomes visible after
    the timeout instead of never."""
    import time

    c = _client(tmp_path, ("performance/io-cache",
                           {"page-size": "4KB",
                            "cache-timeout": "0.2"}))
    ioc = c.graph.top
    posix = c.graph.by_name["posix"]
    c.write_file("/f", b"old" * 2000)
    assert c.read_file("/f") == b"old" * 2000
    time.sleep(0.25)
    c.read_file("/f")  # establishes the (mtime, pages) baseline
    # mutate BEHIND the cache: straight through posix, invisible to
    # the io-cache layer's own invalidation
    from glusterfs_tpu.core.layer import FdObj
    ia = c.stat("/f")
    anon = FdObj(ia.gfid, path="/f", anonymous=True)
    time.sleep(0.05)
    c._run(posix.writev(anon, b"new" * 2000, 0))
    # within the timeout the stale page may still be served; after it,
    # revalidation sees the mtime change and refetches
    time.sleep(0.25)
    assert c.read_file("/f")[:6] == b"newnew"
    assert ioc.validations > 0
    c.close()


def test_read_ahead(tmp_path):
    c = _client(tmp_path, ("performance/read-ahead",
                           {"page-size": "4KB", "page-count": 2}))
    c.write_file("/f", bytes(range(256)) * 100)
    f = c.open("/f")
    out = b""
    for i in range(6):  # sequential reads trigger prefetch
        out += f.read(4096, i * 4096)
    f.close()
    assert out == (bytes(range(256)) * 100)[:6 * 4096]
    c.close()


def test_md_cache(tmp_path):
    c = _client(tmp_path, ("performance/md-cache", {"timeout": "60"}))
    mdc = c.graph.top
    posix = c.graph.by_name["posix"]
    c.write_file("/f", b"12345")
    # the writev postbuf was absorbed (mdc_writev_cbk analog): stats
    # after a write are served from cache without reaching the brick
    c.stat("/f")
    c.stat("/f")
    assert posix.stats.get("stat") is None  # never reached posix
    assert c.stat("/f").size == 5
    assert mdc.hits >= 2
    # write invalidates: size change visible
    f = c.open("/f")
    f.write(b"6789ab", 5)
    f.close()
    assert c.stat("/f").size == 11
    c.close()


def test_quick_read(tmp_path):
    c = _client(tmp_path, ("performance/quick-read",
                           {"max-file-size": "1KB"}))
    qr = c.graph.top
    posix = c.graph.by_name["posix"]
    c.write_file("/small", b"tiny")
    assert c.read_file("/small") == b"tiny"
    n = posix.stats["readv"].count
    assert c.read_file("/small") == b"tiny"
    assert posix.stats["readv"].count == n
    assert qr.hits >= 1
    big = b"B" * 5000
    c.write_file("/big", big)
    assert c.read_file("/big") == big  # above limit: passthrough
    c.close()


def _fop_count(layer, fop: str) -> int:
    st = layer.stats.get(fop)
    return st.count if st is not None else 0


@pytest.mark.parametrize("case", [
    "survives_writev", "survives_cache_timeout", "ftruncate_drops",
    "truncate_drops", "upcall_drops", "short_read_inside_limit_drops",
    "short_read_past_limit_keeps", "capped", "grown_by_writes_is_probed",
    "small_file_write_read_back"])
def test_quick_read_too_big_hint(tmp_path, monkeypatch, case):
    """What quick-read remembers about a file over max-file-size, and
    what makes it forget: not a write and not the clock, but a
    truncate, an upcall, or EOF seen inside the limit."""
    import time

    from glusterfs_tpu.core.layer import Event, FdObj
    from glusterfs_tpu.performance import quick_read

    c = _client(tmp_path, ("performance/quick-read",
                           {"max-file-size": "1KB",
                            "cache-timeout": "0.01"
                            if case == "survives_cache_timeout" else "60"}))
    qr = c.graph.top
    posix = c.graph.by_name["posix"]
    big = bytes(range(256)) * 20  # 5120 bytes, five times the limit

    def probes() -> int:
        # the layer's own count and the child's fstat calls agree
        assert qr.dump_private()["size_probes"] == _fop_count(posix, "fstat")
        return qr.dump_private()["size_probes"]

    def small_again(f, content: bytes) -> None:
        """The next small read probes and caches, the one after it is
        served with no readv below."""
        n = probes()
        assert f.read(100, 0) == content[:100]
        assert probes() == n + 1
        assert qr.dump_private()["too_big_entries"] == 0
        reads, hits = _fop_count(posix, "readv"), qr.hits
        assert f.read(100, 10) == content[10:110]
        assert _fop_count(posix, "readv") == reads
        assert qr.hits == hits + 1 and probes() == n + 1

    if case == "small_file_write_read_back":
        c.write_file("/s", b"old bytes")
        f = c.open("/s")
        assert f.read(100, 0) == b"old bytes"
        assert f.read(100, 0) == b"old bytes" and qr.hits == 1
        f.write(b"NEW", 0)  # content invalidation on writev still there
        assert f.read(100, 0) == b"NEW bytes"
        assert qr.dump_private()["too_big_entries"] == 0
        f.close()
        c.close()
        return
    if case == "grown_by_writes_is_probed":
        c.write_file("/g", b"tiny")
        f = c.open("/g")
        assert f.read(100, 0) == b"tiny" and probes() == 1
        f.write(big, 0)
        assert f.read(100, 0) == big[:100]  # probed anew, found too big
        assert probes() == 2
        assert qr.dump_private()["too_big_entries"] == 1
        f.close()
        c.close()
        return
    if case == "capped":
        monkeypatch.setattr(quick_read, "TOO_BIG_MAX", 3)
        files = []
        for i in range(5):
            c.write_file(f"/big{i}", big)
            files.append(c.open(f"/big{i}"))
            assert files[i].read(100, 0) == big[:100]
        assert probes() == 5
        assert qr.dump_private()["too_big_entries"] == 3
        assert files[4].read(100, 0) == big[:100]  # a young one: kept
        assert probes() == 5
        assert files[0].read(100, 0) == big[:100]  # the oldest went
        assert probes() == 6
        assert qr.dump_private()["too_big_entries"] == 3
        for f in files:
            f.close()
        c.close()
        return

    c.write_file("/big", big)
    f = c.open("/big")
    assert f.read(100, 0) == big[:100]
    assert probes() == 1
    assert f.read(100, 200) == big[200:300]
    d = qr.dump_private()
    assert (probes(), d["forwarded_too_big"], d["too_big_entries"]) == \
        (1, 1, 1)

    if case == "survives_writev":
        f.write(b"x" * 10, 0)
        assert f.read(100, 0) == b"x" * 10 + big[10:100]
        assert probes() == 1
        assert qr.dump_private()["forwarded_too_big"] == 2
    elif case == "survives_cache_timeout":
        time.sleep(0.05)
        assert f.read(100, 0) == big[:100]
        assert probes() == 1
        assert qr.dump_private()["forwarded_too_big"] == 2
    elif case == "ftruncate_drops":
        f.ftruncate(300)
        assert qr.dump_private()["too_big_entries"] == 0
        small_again(f, big[:300])
    elif case == "truncate_drops":
        c.truncate("/big", 300)
        assert qr.dump_private()["too_big_entries"] == 0
        small_again(f, big[:300])
    elif case == "upcall_drops":
        qr.notify(Event.UPCALL, None, {"gfid": f.fd.gfid})
        assert qr.dump_private()["too_big_entries"] == 0
        assert f.read(100, 0) == big[:100]  # still big: probed, kept out
        assert probes() == 2
        assert qr.dump_private()["too_big_entries"] == 1
    elif case == "short_read_inside_limit_drops":
        # shrunk BEHIND the mount (another client, no upcall): straight
        # through posix, where this layer sees nothing
        anon = FdObj(f.fd.gfid, path="/big", anonymous=True)
        c._run(posix.ftruncate(anon, 300))
        assert f.read(100, 0) == big[:100]  # a full answer says nothing
        assert qr.dump_private()["too_big_entries"] == 1
        assert f.read(400, 0) == big[:300]  # EOF at 300, under the limit
        assert probes() == 1
        assert qr.dump_private()["too_big_entries"] == 0
        small_again(f, big[:300])
    elif case == "short_read_past_limit_keeps":
        assert f.read(400, 5000) == big[5000:]  # EOF at 5120: still big
        assert f.read(100, 6000) == b""
        assert probes() == 1
        assert qr.dump_private()["too_big_entries"] == 1
    f.close()
    c.close()


def test_open_behind(tmp_path):
    c = _client(tmp_path, ("performance/open-behind", {}))
    posix = c.graph.by_name["posix"]

    def opens():
        st = posix.stats.get("open")
        return st.count if st else 0

    c.write_file("/f", b"lazily")
    n_opens = opens()
    f = c.open("/f")  # deferred: no child open yet
    assert opens() == n_opens
    assert f.read(6, 0) == b"lazily"  # first use opens
    assert opens() == n_opens + 1
    f.close()
    c.close()


def test_nl_cache(tmp_path):
    c = _client(tmp_path, ("performance/nl-cache", {}))
    nlc = c.graph.top
    posix = c.graph.by_name["posix"]
    for _ in range(3):
        assert not c.exists("/missing")
    assert nlc.hits >= 2  # negative entries served from cache
    # creating the file invalidates the negative entry
    c.write_file("/missing", b"now here")
    assert c.exists("/missing")
    c.close()


def test_readdir_ahead(tmp_path):
    c = _client(tmp_path, ("performance/readdir-ahead", {}))
    for i in range(5):
        c.write_file(f"/f{i}", b".")
    assert c.listdir("/") == [f"f{i}" for i in range(5)]
    c.close()


def test_io_threads_gating(tmp_path):
    c = _client(tmp_path, ("performance/io-threads", {"thread-count": 2}))
    iot = c.graph.top
    c.write_file("/f", b"x" * 100)
    assert c.read_file("/f") == b"x" * 100
    assert iot.executed[1] > 0  # normal-prio fops went through the gate
    assert iot.executed[0] > 0  # lookups on the fast path
    c.close()


def test_full_perf_stack(tmp_path):
    """All perf layers stacked (volgen order) still give correct I/O."""
    c = _client(
        tmp_path,
        ("performance/write-behind", {}),
        ("performance/read-ahead", {}),
        ("performance/readdir-ahead", {}),
        ("performance/io-cache", {}),
        ("performance/quick-read", {}),
        ("performance/open-behind", {}),
        ("performance/md-cache", {}),
        ("performance/nl-cache", {}),
    )
    data = bytes(range(256)) * 300
    c.write_file("/f", data)
    assert c.read_file("/f") == data
    f = c.open("/f")
    f.write(b"PATCH", 1000)
    f.close()
    expect = data[:1000] + b"PATCH" + data[1005:]
    assert c.read_file("/f") == expect
    assert c.stat("/f").size == len(data)
    c.mkdir("/d")
    assert sorted(c.listdir("/")) == ["d", "f"]
    c.close()


def test_write_behind_bridging_write_order(tmp_path):
    """A bridging write that overlaps TWO buffered chunks must win over
    both: stale higher-offset chunk bytes must not clobber newer data on
    drain (advisor round-1 finding)."""
    c = _client(tmp_path, ("performance/write-behind",
                           {"window-size": "1MB"}))
    f = c.create("/f")
    f.write(b"A" * 10, 0)      # chunk [0,10)
    f.write(b"B" * 10, 20)     # chunk [20,30) — disjoint, older
    f.write(b"C" * 20, 5)      # bridges both: [5,25), newest
    f.close()                  # drain
    want = b"A" * 5 + b"C" * 20 + b"B" * 5
    assert c.read_file("/f") == want
    c.close()


def test_write_behind_many_overlaps_disjoint_invariant(tmp_path):
    """Random overlapping writes replayed through write-behind must equal
    a plain sequential replay (newest-wins everywhere)."""
    import random

    rnd = random.Random(3)
    shadow = bytearray(4096)
    c = _client(tmp_path, ("performance/write-behind",
                           {"window-size": "1MB"}))
    f = c.create("/f")
    for step in range(60):
        off = rnd.randrange(0, 3500)
        ln = rnd.randrange(1, 500)
        pat = bytes([step % 256]) * ln
        f.write(pat, off)
        shadow[off:off + ln] = pat
    f.close()
    got = c.read_file("/f")
    assert got == bytes(shadow[:len(got)])
    assert bytes(shadow[len(got):]).count(0) == len(shadow) - len(got)
    c.close()
