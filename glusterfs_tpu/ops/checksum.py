"""Block checksums — the libglusterfs checksum.c (gf_rchecksum)
analog, TPU-batchable.

The reference computes a weak rolling checksum + a strong digest per
block so AFR data heal can skip byte-identical regions
(afr-self-heal-data.c).  The weak sum here is Adler-32 (zlib.adler32
byte-compatible) — sequential by definition, but algebraically just
two weighted sums:

    A = 1 + sum(d_i)                 (mod 65521)
    B = n + sum((n - i) * d_i)       (mod 65521)

which makes a [batch, block] uint8 array one reduction pair on the
MXU-adjacent vector units — thousands of blocks checksummed per
launch, the coalesced-batch regime everything else in ops/ uses.
Strong digests stay sha256 on the host (cryptographic, not worth
emulating on-device).
"""

from __future__ import annotations

import zlib

import numpy as np

from ..core import gflog

_MOD = 65521


def adler32_ref(block: bytes) -> int:
    """zlib oracle."""
    return zlib.adler32(block) & 0xFFFFFFFF


def adler32_batch_np(blocks: np.ndarray) -> np.ndarray:
    """NumPy fallback: [n, b] uint8 -> [n] uint32 adler32."""
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
    n, b = blocks.shape
    d = blocks.astype(np.uint64)
    a = (1 + d.sum(axis=1)) % _MOD
    w = np.arange(b, 0, -1, dtype=np.uint64)
    bsum = (b + (d * w).sum(axis=1)) % _MOD
    return (bsum.astype(np.uint32) << 16) | a.astype(np.uint32)


_JIT_CACHE: dict = {}


def adler32_batch_jax(blocks):
    """jit-compiled batched adler32: [n, b] uint8 on device -> [n]
    uint32.  Weighted sums are taken in int32 segments small enough
    not to overflow, then folded mod 65521."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        n, b = x.shape
        d = x.astype(jnp.uint32)
        # segment the weighted sum so partials stay under 2^31:
        # max term = 255 * seg_len * seg_count-scaled weights; use
        # float-free exact arithmetic by reducing in uint32 with
        # interleaved mods every segment
        seg = 4096
        pad = (-b) % seg
        dp = jnp.pad(d, ((0, 0), (0, pad)))
        w = jnp.pad(jnp.arange(b, 0, -1, dtype=jnp.uint32),
                    (0, pad))
        ds = dp.reshape(n, -1, seg)
        ws = w.reshape(-1, seg)
        a = (1 + jnp.sum(ds, axis=(1, 2))) % _MOD
        partial = jnp.sum(ds * ws[None, :, :] % _MOD,
                          axis=2) % _MOD  # [n, segs]
        bsum = (b + jnp.sum(partial, axis=1)) % _MOD
        return (bsum << 16) | a

    jitted = _JIT_CACHE.get("fn")
    if jitted is None:
        jitted = _JIT_CACHE["fn"] = jax.jit(fn)
    return jitted(blocks)


_fell_through: list = []  # the auto ladder's one log line was written


def adler32_batch(blocks: np.ndarray, backend: str = "auto"):
    """Backend ladder for the batched weak checksum — the
    disperse.cpu-extensions dispatch pattern applied to the rchecksum
    workload: TPU (jax) when this process owns one, native C++ (AVX2
    auto-vectorized) when the toolchain built, NumPy always.  An
    explicit backend raises when it cannot run; ``auto`` logs once
    which rung it fell to and why.  Returns [n] uint32."""
    if backend in ("auto", "jax", "tpu"):
        try:
            from .codec import tpu_devices

            if backend != "auto" or tpu_devices():
                import jax.numpy as jnp

                return np.asarray(adler32_batch_jax(jnp.asarray(blocks)))
        except Exception as e:
            if backend != "auto":
                raise
            if not _fell_through:
                _fell_through.append(True)
                gflog.get_logger("ec").error(
                    44, "adler32_batch: device rung failed, checksums "
                    "take the CPU ladder: %s: %s", type(e).__name__, e)
    if backend in ("auto", "native"):
        from .. import native

        if native.available():
            return native.adler32_batch(blocks)
        if backend == "native":
            raise RuntimeError("native checksum backend unavailable")
    return adler32_batch_np(blocks)


def rchecksum(data: bytes, fips: bool = True) -> dict:
    """One block's weak+strong checksum (the posix rchecksum fop
    payload).  fips (storage.fips-mode-rchecksum): sha256; off = the
    reference's legacy md5 strong sum."""
    import hashlib

    strong = hashlib.sha256(data) if fips else hashlib.md5(data)
    return {"weak": adler32_ref(data), "strong": strong.hexdigest()}
