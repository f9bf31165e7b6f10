"""chip_smoke.py's control flow, on the CPU at tiny size: the stage
functions the chip run uses, with interpret-mode kernels and the ``xla``
backend standing in for the device, so the script cannot rot between
chip runs.  What only a chip can show stays in the script's own run."""

import asyncio
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = {"files": 2, "file_mib": 0.25, "io_kib": 64, "inflight": 2,
        "kernel_stripes": 20, "kernel_big_mib": 0,
        "geometries": ((4, 2),), "xla_forms": ()}  # the volume below runs on xla anyway


def test_stages_at_tiny_size(tmp_path):
    smoke = chip_smoke.Smoke(str(tmp_path), TINY, interpret=True,
                             backend="xla", require_tpu=False)

    async def go():
        try:
            smoke.watch_compiles()
            await smoke.stage(1, "kernels", smoke.stage1_kernels)
            await smoke.stage(2, "served", smoke.stage2_served)
            await smoke.stage(3, "device-coding",
                              smoke.stage3_device_coding)
            await smoke.stage(4, "guarantees", smoke.stage4_guarantees)
        finally:
            await smoke.close()

    asyncio.run(go())
    assert [s["ok"] for s in smoke.stages] == [True] * 4
    assert smoke.summary["reduced"], "a tiny run must list its cuts"
    s3, s4 = smoke.stages[2], smoke.stages[3]
    assert s3["device_launches"] >= s3["flushes"] > 0
    assert s4["reconstruct_launches"] > 0 and s4["heal_launches"] > 0
    assert s4["survivors"] == [1, 3, 4, 5]
    assert smoke.gd is None, "glusterd left running"


def test_last_line_is_ok_and_device_only():
    """The driver refuses a last line with any other key."""
    import json

    summary = {"ok": True, "reduced": [], "stages": [{"stage": 0}],
               "device": {"platform": "tpu", "kind": "TPU v5 lite",
                          "count": 1}}
    assert json.loads(chip_smoke.result_line(summary)) == {
        "ok": True, "device": summary["device"]}


def test_refuses_to_run_without_a_tpu(tmp_path):
    """No accelerator: non-zero in seconds, no result line, and no
    daemon was ever started."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    t0 = time.monotonic()
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode != 0
    assert time.monotonic() - t0 < 30
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr
    # its work directory never grew a glusterd
    assert not any("glusterd" in f for _d, _s, fs in os.walk(tmp_path)
                   for f in fs)
