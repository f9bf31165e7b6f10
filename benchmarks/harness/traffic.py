"""One general closed-loop generator.  A traffic mix is a file of
parameters; everything a mix can ask for is here, so a new mix is data.

``jobs`` jobs each own one file of ``file_MiB`` and keep one operation
in flight (fio's ``iodepth=1``).  Files are laid out in set-up by the
same write path; the window only loops over them.  ``pattern`` is
``sequential`` (offset advances by the block and wraps to 0; ``fsync``
at every wrap where the mix says so) or ``random`` (offset uniform,
aligned to ``align_KiB``).  Every job draws its operations from a deck:
a fixed multiset of (read | write) x block size in the mix's shares,
shuffled by the seed, so that every seed does the same work in another
order.  Payloads are slices of one pool made from the seed in set-up.

Each operation is timed at the door from call to return and recorded
as one tuple; that record is the model of the files' contents, the
source of every rate and percentile, and nothing heavier happens per
operation inside the window.
"""

from __future__ import annotations

import asyncio
import os
import time

import numpy as np

KIB, MIB = 1 << 10, 1 << 20
READ, WRITE, FSYNC = 0, 1, 2
KINDS = {READ: "read", WRITE: "write", FSYNC: "fsync"}

#: one record per operation:
#: (start, end, kind, offset, size, pool offset or -1, ok)
Op = tuple


class InFlight:
    """Names what the host is doing, for the profiler's trace: one
    ``TraceAnnotation`` at a time on the loop's thread, named by the
    kinds of operation in flight (``fsync_write_in_flight``), renamed
    whenever that set changes.  Off (``None`` annotate) it is two
    dictionary updates per operation."""

    def __init__(self, annotate=None):
        self.annotate = annotate
        self.counts: dict[str, int] = {}
        self.current = None

    def _rename(self) -> None:
        if self.current is not None:
            self.current.__exit__(None, None, None)
            self.current = None
        live = sorted(k for k, v in self.counts.items() if v)
        if live and self.annotate is not None:
            self.current = self.annotate("_".join(live) + "_in_flight")
            self.current.__enter__()

    def enter(self, kind: str) -> None:
        n = self.counts.get(kind, 0)
        self.counts[kind] = n + 1
        if n == 0 and self.annotate is not None:
            self._rename()

    def exit(self, kind: str) -> None:
        self.counts[kind] -= 1
        if self.counts[kind] == 0 and self.annotate is not None:
            self._rename()


class Traffic:
    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.seed = seed
        self.jobs = int(mix["jobs"])
        self.file_bytes = int(mix["file_MiB"] * MIB)
        self.blocks = [int(b * KIB) for b in mix["block_KiB"]]
        self.read_share = float(mix["read_share"])
        self.random = mix["pattern"] == "random"
        self.align = int(mix.get("align_KiB", mix["block_KiB"][0]) * KIB)
        self.fsync_on_wrap = mix.get("fsync", "close") == "wrap_and_close"
        self.layout_block = int(mix.get("layout_block_KiB", 1024) * KIB)
        self.layout_jobs = int(mix.get("layout_jobs", 4))
        self.names = [f"/job{j}" for j in range(self.jobs)]
        rng = np.random.default_rng([seed, 0])
        pool_bytes = int(mix.get("pool_MiB", 64) * MIB)
        big = max(self.blocks + [self.layout_block])
        # the pool is bytes (what gfapi's write takes without a copy of
        # its own); a write's payload is one slice of it
        self.pool = rng.bytes(pool_bytes + big)
        self.pool_span = pool_bytes
        self.files: list = [None] * self.jobs
        #: per file, every operation in the order it was acknowledged
        self.log: list[list[Op]] = [[] for _ in range(self.jobs)]
        self.samples: list[list[tuple]] = [[] for _ in range(self.jobs)]
        self.sample_every = int(mix.get("sample_reads_every", 64))
        self._writes = [0] * self.jobs  # per job, for the payload walk
        self.deck = [self._deck(j) for j in range(self.jobs)]

    # -- what a job does ---------------------------------------------------

    def _deck(self, j: int) -> np.ndarray | None:
        """(D, 3) int64 rows of (kind, size, offset) for a random job;
        a sequential job needs none."""
        if not self.random:
            return None
        rng = np.random.default_rng([self.seed, 1, j])
        per = int(self.mix.get("deck_per_combo", 4))
        reads = int(round(per * 2 * self.read_share))
        base = np.array([(kind, size) for size in self.blocks
                         for kind in [READ] * reads
                         + [WRITE] * (2 * per - reads)], dtype=np.int64)
        # round after round of the same multiset, each shuffled afresh:
        # any stretch of a job's work holds the mix's shares
        deck = np.concatenate([rng.permutation(base) for _ in range(
            int(self.mix.get("deck_rounds", 50)))])
        slots = self.file_bytes // self.align
        offs = rng.integers(0, slots, len(deck)) * self.align
        offs = np.minimum(offs, self.file_bytes - deck[:, 1])
        return np.column_stack([deck, offs // self.align * self.align])

    def _payload_offset(self, j: int, size: int) -> int:
        """Where in the pool job j's next write takes its bytes: a walk
        that differs from pass to pass, so contents change each pass."""
        c = self._writes[j]
        self._writes[j] = c + 1
        return ((j * 7919 + c) * 4096 * 257) % self.pool_span

    def _next(self, j: int, i: int) -> tuple[int, int, int]:
        if self.random:
            kind, size, off = self.deck[j][i % len(self.deck[j])]
            return int(kind), int(size), int(off)
        size = self.blocks[0]
        per_pass = self.file_bytes // size
        kind = READ if self.read_share >= 1.0 else WRITE
        return kind, size, (i % per_pass) * size

    async def _op(self, j: int, kind: int, size: int, off: int,
                  flight: InFlight, seq: int = -1) -> bool:
        f, name = self.files[j], KINDS[kind]
        po, ok = -1, False
        if kind == WRITE:
            po = self._payload_offset(j, size)
            data = self.pool[po:po + size]
        flight.enter(name)
        t0 = time.perf_counter()
        try:
            if kind == WRITE:
                ok = await f.write(data, off) == size
            elif kind == READ:
                data = await f.read(size, off)
                ok = len(data) == size
                if ok and seq >= 0 and \
                        (seq + j + self.seed) % self.sample_every == 0:
                    self.samples[j].append((len(self.log[j]), off, data))
            else:
                await f.fsync()
                ok = True
        except Exception as e:  # a failed operation is counted, not fatal
            self.last_error = f"{name}@{off}: {type(e).__name__}: {e}"
        t1 = time.perf_counter()
        flight.exit(name)
        self.log[j].append((t0, t1, kind, off, size, po, ok))
        return ok

    # -- set-up ------------------------------------------------------------

    async def layout(self, client) -> None:
        """Create every file, write it once end to end and fsync it."""
        flight = InFlight()
        sem = asyncio.Semaphore(self.layout_jobs)

        async def one(j: int) -> None:
            async with sem:
                self.files[j] = await client.create(
                    self.names[j], os.O_RDWR | os.O_EXCL)
                for off in range(0, self.file_bytes, self.layout_block):
                    size = min(self.layout_block, self.file_bytes - off)
                    if not await self._op(j, WRITE, size, off, flight):
                        raise RuntimeError(f"layout: {self.last_error}")
                if not await self._op(j, FSYNC, 0, 0, flight):
                    raise RuntimeError(f"layout: {self.last_error}")

        await asyncio.gather(*(one(j) for j in range(self.jobs)))

    # -- the loop ------------------------------------------------------------

    async def run(self, seconds: float, flight: InFlight | None = None,
                  sample: bool = True) -> tuple[float, float]:
        """Every job loops until ``seconds`` have passed, then closes
        with an ``fsync`` if it wrote; returns (start, end) of the
        window, the end being the return of the last job's last call."""
        flight = flight or InFlight()
        start = time.perf_counter()
        stop = start + seconds
        wrote = self.read_share < 1.0

        async def job(j: int) -> None:
            i = 0
            while time.perf_counter() < stop:
                kind, size, off = self._next(j, i)
                await self._op(j, kind, size, off, flight,
                               seq=i if sample else -1)
                i += 1
                if self.fsync_on_wrap and kind == WRITE and \
                        off + size == self.file_bytes:
                    await self._op(j, FSYNC, 0, 0, flight)
            if wrote:
                await self._op(j, FSYNC, 0, 0, flight)

        await asyncio.gather(*(job(j) for j in range(self.jobs)))
        return start, time.perf_counter()

    async def close(self) -> None:
        for f in self.files:
            if f is not None:
                await f.close()
        self.files = [None] * self.jobs

    last_error = ""

    # -- reading the record ----------------------------------------------------

    def ops(self, start: float, end: float) -> list[Op]:
        """Every read and write that began in [start, end)."""
        return [op for log in self.log for op in log
                if start <= op[0] < end and op[2] != FSYNC]

    def contents(self, j: int, upto: int | None = None,
                 into: np.ndarray | None = None, since: int = 0):
        """File j as the acknowledged writes leave it, replayed in order
        (one job writes a file, one operation at a time, so the order of
        the record is the order of the writes)."""
        buf = np.zeros(self.file_bytes, dtype=np.uint8) \
            if into is None else into
        pool = np.frombuffer(self.pool, dtype=np.uint8)
        for _t0, _t1, kind, off, size, po, ok in self.log[j][since:upto]:
            if kind == WRITE and ok:
                buf[off:off + size] = pool[po:po + size]
        return buf
