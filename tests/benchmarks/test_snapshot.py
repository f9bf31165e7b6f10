"""What a run prints at the window's edges about the host."""

import os
import subprocess
import sys
import time

from benchmarks.harness import snapshot


def test_host_state_names_what_could_explain_a_stall():
    state = snapshot.host_state()
    assert {"loadavg", "gc", "cpus"} <= set(state)
    assert len(state["gc"]) == 3 and state["cpus"] >= 1
    assert "children_cpu_s" not in state


def test_children_cpu_is_the_sessions_own():
    child = subprocess.Popen(
        [sys.executable, "-c",
         "import time\nt = time.process_time()\n"
         "while time.process_time() - t < 0.3: pass\ntime.sleep(30)"],
        start_new_session=True)
    try:
        deadline = time.monotonic() + 20
        while snapshot.children_cpu_s(child.pid) < 0.25:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert snapshot.children_cpu_s(os.getsid(0)) >= 0.0
        assert snapshot.host_state(child.pid)["children_cpu_s"] >= 0.25
    finally:
        child.kill()
        child.wait()
