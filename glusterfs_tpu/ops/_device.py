"""The device entry of every jax backend (ops/gf256_xla, ops/gf256_pallas):
host arrays in, one jitted call, a host array out."""

from __future__ import annotations

import threading

import jax.numpy as jnp
import numpy as np

from ..core import tracing

#: per thread: who wants to hear of this thread's next launch
_THREAD = threading.local()


def after_launch(cb) -> None:
    """``cb()`` is called once, on this thread, when its next launch
    has been dispatched (:func:`launched`).  Up to there the entry
    works in the interpreter (``jnp.asarray`` and the jitted call hold
    it almost throughout on the v5e's host: PERF.md section 6, PR 25);
    what is left is the wait for the answer, which releases it.  The
    batcher tells the fops of a flush (ops/batch ``encode_async``
    ``launched``)."""
    _THREAD.cb = cb


def launched() -> None:
    """The launch is dispatched: tell whoever asked, once."""
    cb = getattr(_THREAD, "cb", None)
    if cb is not None:
        _THREAD.cb = None
        cb()


def device_call(fn, *host) -> np.ndarray:
    """``np.asarray(fn(*[jnp.asarray(a) for a in host]))`` as three
    phases of the enclosing flush's span (core/tracing.py), each a
    wait of the HOST: ``codec.h2d`` is ``jnp.asarray`` returning,
    ``codec.launch`` the jitted call returning (the dispatch: nothing
    waits for the device that did not wait before), ``codec.d2h`` is
    ``np.asarray``, which waits for the kernel and then for the copy
    back.  None of them is the transfer alone, and a profiler trace
    does not make them so: its device planes stood up to a millisecond
    off its host planes (PERF.md section 6, PR 24), so a host span
    cannot be laid against a device op."""
    with tracing.phase(None, "codec.h2d", cpu=True):
        dev = [jnp.asarray(a) for a in host]
    with tracing.phase(None, "codec.launch", cpu=True):
        out = fn(*dev)
    launched()
    with tracing.phase(None, "codec.d2h", cpu=True):
        return np.asarray(out)
