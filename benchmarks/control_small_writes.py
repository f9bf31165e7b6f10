#!/usr/bin/env python3
"""Controls for a cell whose every write is smaller than a stripe and
takes the parity-delta wave, where two of ``control.py``'s faults cannot
bite: ``codec_answer_altered`` wraps ``encode`` and ``decode`` and the
wave asks ``encode_delta``; ``acked_write_half_stored`` keeps whole
stripes and at least one, which is all of a write of half a stripe.

    python3 benchmarks/control_small_writes.py --workload <cell> --fault <name> --seed <n> --seconds <s>

The same command line and exit code as ``control.py`` (0 where the run
came out ``correct: false``), and its three faults are offered here
too, so one command serves a cell's every control.

``codec_delta_answer_altered``   every parity row ``encode_delta``
    answers (the parity of old XOR new, which the bricks' ``xorv`` folds
    into their fragments) has one bit flipped per 512-byte chunk.  The
    batcher's delta lane looks its entry up on the instance when a
    flush runs (``ops/batch.py`` ``_Lane``), so the one replacement
    serves ``encode_delta_async`` too; replacing both would flip twice.
    Breaks "fragments on the bricks are the reference encoding": the
    data fragments stay right, so the door reads back clean and only
    the bricks' parity files tell.
``acked_small_write_half_stored`` of every ``writev`` that reaches
    ``cluster/ec`` only the first half of its bytes goes further, and
    all of it is acknowledged.  Breaks "an acknowledged, fsynced write
    is readable byte-exact through the door" for a write of any size.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import control  # noqa: E402


def codec_delta_answer_altered(run) -> None:
    for ec in run.volume.ecs:
        codec = ec.codec

        def bad_delta(delta, encode_delta=codec.encode_delta):
            rows = encode_delta(delta).copy()
            rows[:, ::512] ^= 1
            return rows

        codec.encode_delta = bad_delta


def acked_small_write_half_stored(run) -> None:
    for ec in run.volume.ecs:

        async def writev(fd, data, offset, xdata=None, real=ec.writev):
            return await real(fd, bytes(data[:len(data) // 2]), offset,
                              xdata)

        ec.writev = writev


FAULTS = dict(control.FAULTS,
              codec_delta_answer_altered=codec_delta_answer_altered,
              acked_small_write_half_stored=acked_small_write_half_stored)


def main(argv=None) -> int:
    control.FAULTS.update(FAULTS)  # its main looks a fault up there
    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
