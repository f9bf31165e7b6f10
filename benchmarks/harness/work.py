"""What a coding operation has to move, from its shapes alone, and what
the chip can move at best.  No kernel's name appears here: a PR that
fuses, renames or replaces a kernel is still held to these bytes."""

from __future__ import annotations

CHUNK = 512

#: peaks of one chip, by ``device_kind`` as jax reports it.  A device
#: that is not listed is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e: 16 GB HBM2e at "
                  "819 GB/s per chip",
    },
}


def peak_bytes_per_s(device_kind: str) -> float:
    try:
        return PEAKS[device_kind]["hbm_bytes_per_s"]
    except KeyError:
        raise KeyError(f"no peak recorded for device kind {device_kind!r}: "
                       f"add it to benchmarks/harness/work.py with its "
                       f"source") from None


def stripes(user_bytes: int, k: int) -> int:
    """Whole stripes that ``user_bytes`` fill; the benchmark's traffic
    is stripe-aligned, so nothing is rounded away.  Bucket padding the
    program adds is not work: it counts as waste."""
    return user_bytes // (k * CHUNK)


def parity_bytes(user_bytes: int, k: int, r: int) -> int:
    """Systematic encode of S stripes: the k data chunks of each go in,
    the r parity chunks come out.  The data fragments are the stripe's
    own chunks and need no device."""
    s = stripes(user_bytes, k)
    return s * k * CHUNK + s * r * CHUNK


def reconstruct_bytes(user_bytes: int, k: int, missing: int) -> int:
    """Degraded systematic read of S stripes with ``missing`` data
    fragments down: k surviving fragments go in, the missing data
    chunks come out.  A read policy that picks survivors which leave
    more rows to rebuild does more than this, and that is waste."""
    s = stripes(user_bytes, k)
    return s * k * CHUNK + s * missing * CHUNK


def roofline_share(bytes_moved: int, device_kind: str,
                   device_seconds: float) -> float | None:
    """Per cent of the memory roofline: least time over measured time.
    GF(2^8) coding on bit planes is XORs on the vector unit, a few per
    byte, so the memory system bounds it.  Nothing measured is nothing
    returned, never 0."""
    if bytes_moved <= 0 or device_seconds <= 0:
        return None
    return 100.0 * (bytes_moved / peak_bytes_per_s(device_kind)) \
        / device_seconds
