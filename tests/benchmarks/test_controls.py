"""The controls at a size a test run can hold: a whole run with one
guarantee of the configuration broken underneath the timed path
(``benchmarks/control.py``) has to come out ``correct: false``, by the
number that is there to catch it."""

import pytest

from benchmarks import control
from tests.benchmarks.test_rehearsal import CELLS, rehearse


@pytest.mark.parametrize("cell, fault, number", [
    (CELLS["sequential-write"], "codec_answer_altered",
     "fragment_bad_bytes"),
    (CELLS["random-readwrite-extra"], "codec_answer_altered",
     "fragment_bad_bytes"),
    (CELLS["sequential-write"], "acked_write_half_stored",
     "door_bad_bytes"),
    (CELLS["random-readwrite-extra"], "acked_write_half_stored",
     "door_bad_bytes"),
    (CELLS["sequential-read-degraded"], "codec_answer_altered",
     "door_bad_bytes"),
    (CELLS["sequential-read-degraded"], "brick_fragment_altered",
     "door_bad_bytes"),
    (CELLS["sequential-write-four"], "codec_answer_altered",
     "fragment_bad_bytes"),
    (CELLS["sequential-write-four"], "acked_write_half_stored",
     "door_bad_bytes"),
    (CELLS["heal-full"], "codec_answer_altered", "fragment_bad_bytes"),
    (CELLS["heal-full"], "brick_fragment_altered", "door_bad_bytes"),
    (CELLS["heal-full"], "heal_leaves_one_file", "fragment_bad_bytes"),
])
def test_a_broken_guarantee_is_not_correct(tmp_path, cell, fault, number):
    _m, result = rehearse(tmp_path, cell, fault=control.FAULTS[fault])
    assert result["correct"] is False
    assert result["checks"][number][0] > result["checks"][number][1]
