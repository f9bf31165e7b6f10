#!/usr/bin/env python3
"""The controls: a run of a cell with one guarantee of its configuration
broken underneath the timed path.  Each has to come out ``correct:
false``; a comparison that a control passes compares nothing.

    python3 benchmarks/control.py --workload <cell> --fault <name> --seed <n> --seconds <s>

The benchmark's own runs never plant a fault.  On the chip a control
runs at the cell's own size with a short window (long enough to wrap or
to finish the mix's longest operation many times over); the tests under
``tests/benchmarks`` run the same faults on the CPU at tiny size.

``codec_answer_altered``   every answer of the codec has one bit
    flipped where it is produced: parity rows on encode, the rebuilt
    bytes on decode.  Breaks "fragments on the bricks are the reference
    encoding" (write cells) and "readable byte-exact from any k of the n
    bricks" (degraded reads).
``acked_write_half_stored`` of every ``writev`` that reaches
    ``cluster/ec`` only the first half (in whole stripes) goes further,
    and all of it is acknowledged: the rest of the range keeps what it
    held.  Breaks "an acknowledged, fsynced write is readable
    byte-exact through the door" where the window writes.
``brick_fragment_altered`` after the layout, one byte in every 4 KiB of
    a data brick's fragment files is flipped on the brick directory.
    Breaks "an acknowledged, fsynced write is readable byte-exact
    through the door" for a healthy read, which never touches the codec.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def codec_answer_altered(run) -> None:
    k = run.config["geometry"]["data"]
    for ec in run.volume.ecs:
        codec = ec.codec
        encode, decode = codec.encode, codec.decode

        def bad_encode(data, encode=encode):
            frags = encode(data).copy()
            frags[k:, ::512] ^= 1  # parity only: the data rows are copies
            return frags

        def bad_decode(frags, rows, decode=decode):
            out = decode(frags, rows).copy()
            out[::512] ^= 1
            return out

        codec.encode, codec.decode = bad_encode, bad_decode


def acked_write_half_stored(run) -> None:
    stripe = run.config["geometry"]["stripe_bytes"]
    for ec in run.volume.ecs:

        async def writev(fd, data, offset, xdata=None, real=ec.writev):
            half = max(len(data) // 2 // stripe, 1) * stripe
            return await real(fd, bytes(data[:half]), offset, xdata)

        ec.writev = writev


def brick_fragment_altered(run) -> None:
    for name in run.traffic.names:
        for group in range(0, len(run.volume.bricks),
                           run.config["bricks"]
                           // run.config["geometry"]["groups"]):
            path = os.path.join(run.volume.bricks[group], name.lstrip("/"))
            if os.path.exists(path) and os.path.getsize(path):
                with open(path, "r+b") as f:
                    data = bytearray(f.read())
                    data[::4096] = bytes(b ^ 1 for b in data[::4096])
                    f.seek(0)
                    f.write(data)


FAULTS = {f.__name__: f for f in (codec_answer_altered,
                                  acked_write_half_stored,
                                  brick_fragment_altered)}


def main(argv=None) -> int:
    from benchmarks import run as bench

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--fault", required=True, choices=sorted(FAULTS))
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    args.trace = 0
    result = bench.drive(bench.run_cell(args, fault=FAULTS[args.fault]))
    print(json.dumps({"fault": args.fault, "workload": args.workload,
                      "seed": args.seed, "correct": result["correct"],
                      "checks": result["checks"]}), flush=True)
    return 0 if result["correct"] is False else 1  # a control has to fail


if __name__ == "__main__":
    sys.exit(main())
