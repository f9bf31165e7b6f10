"""Where a run keeps its files: one new directory under its own
``TMPDIR``, bricks inside it, nothing anywhere else."""

import asyncio
import os
import tempfile

from benchmarks.harness import volume


def test_make_dirs_is_one_new_directory_under_tmpdir(tmp_path, monkeypatch):
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr(tempfile, "tempdir", None)
    first = volume.make_dirs()
    second = volume.make_dirs()
    for workdir, bricks in (first, second):
        assert os.path.dirname(workdir) == str(tmp_path)
        assert os.path.dirname(bricks) == workdir and os.path.isdir(bricks)
    assert first[0] != second[0]
    assert sorted(os.listdir(tmp_path)) == sorted(
        os.path.basename(w) for w, _b in (first, second))


def test_close_removes_only_its_own(tmp_path):
    mine = volume.make_dirs(str(tmp_path))
    other = volume.make_dirs(str(tmp_path))
    config = {"bricks": 2}
    v = volume.Volume(config, *mine)
    assert v.bricks == [os.path.join(mine[1], f"brick{i}") for i in (0, 1)]
    asyncio.run(v.close())
    assert not os.path.exists(mine[0])
    assert os.path.isdir(other[1])


def test_no_path_outside_the_run_is_named():
    with open(volume.__file__) as f:
        text = f.read()
    assert "/dev/shm" not in text and '"/tmp' not in text
