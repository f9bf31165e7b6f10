"""graft-lint runner.

Usage::

    python tools/graft_lint/run.py [--json] [--changed] [paths...]
    python -m tools.graft_lint    [--json] [--changed] [paths...]

Exit codes: 0 clean, 1 findings, 2 internal error.  ``paths`` narrows
the scan to the given repo-relative files/directories (cross-file
checks that need files outside the narrowed set skip themselves);
default is the whole tree.  ``--changed`` narrows to the files git
reports as modified/staged/untracked plus their cross-file table
anchors — the fast pre-commit path (a change to the lint suite or a
table anchor falls back to the full tree, because those files feed
every checker).  ``--json`` prints a machine-readable finding list
with per-checker timings (the ci.sh stage-0 archive format).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

#: where the graft_lint PACKAGE lives — always importable from here,
#: independent of what tree is being scanned
_PKG_ROOT = Path(__file__).resolve().parents[2]
#: GRAFT_LINT_ROOT points the SCAN at another tree (the --changed
#: test fixtures build throwaway git repos); default is this repo
REPO_ROOT = Path(os.environ.get("GRAFT_LINT_ROOT")
                 or _PKG_ROOT).resolve()

#: files every checker (or its table evaluation) reads — a change here
#: can produce findings anywhere, so --changed escalates to full tree
FULL_TREE_ANCHORS = ("tools/graft_lint/", "glusterfs_tpu/core/fops.py",
                     "glusterfs_tpu/mgmt/volgen.py",
                     "glusterfs_tpu/core/metrics.py")

#: cross-file anchors added to every non-empty --changed scan so GL01/
#: GL02/GL05 have their vocabulary/option/registry ground truth
CHANGED_DEPS = ("glusterfs_tpu/core/fops.py",
                "glusterfs_tpu/mgmt/volgen.py",
                "glusterfs_tpu/core/metrics.py")


def _git_changed() -> list[str] | None:
    """Changed scan files (unstaged + staged + untracked), or None for
    'escalate to the full tree'."""
    def lines(*args: str) -> list[str]:
        res = subprocess.run(["git", *args], cwd=REPO_ROOT,
                             capture_output=True, text=True, timeout=30)
        if res.returncode != 0:
            raise RuntimeError(res.stderr.strip() or "git failed")
        return [ln.strip() for ln in res.stdout.splitlines()
                if ln.strip()]

    changed = set(lines("diff", "--name-only"))
    changed |= set(lines("diff", "--name-only", "--cached"))
    changed |= set(lines("ls-files", "--others", "--exclude-standard"))
    for c in changed:
        if any(c == a or c.startswith(a) for a in FULL_TREE_ANCHORS):
            return None  # suite/anchor change: findings can be anywhere
    scannable = [c for c in changed
                 if c.endswith(".py") and
                 (c.startswith(("glusterfs_tpu/", "tools/", "tests/"))
                  or c == "__graft_entry__.py")]
    return sorted(set(scannable) | set(CHANGED_DEPS)) if scannable \
        else []


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="graft-lint")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings on stdout")
    ap.add_argument("--changed", action="store_true",
                    help="lint only git-changed files plus their "
                         "table anchors (fast pre-commit path)")
    ap.add_argument("paths", nargs="*",
                    help="repo-relative files/dirs to narrow the scan")
    args = ap.parse_args(argv)

    if str(_PKG_ROOT) not in sys.path:
        sys.path.insert(0, str(_PKG_ROOT))
    from tools.graft_lint import engine

    only = args.paths or None
    if args.changed:
        if only is not None:
            print("graft-lint: --changed and explicit paths are "
                  "mutually exclusive", file=sys.stderr)
            return 2
        try:
            only = _git_changed()
        except Exception as e:  # noqa: BLE001 - degrade to full tree
            print(f"graft-lint: --changed: git unavailable ({e}); "
                  "scanning the full tree", file=sys.stderr)
            only = None
        if only == []:
            if args.json:
                print(json.dumps({"findings": [], "count": 0,
                                  "seconds": 0.0, "changed": [],
                                  "checker_seconds": {}}, indent=2))
            else:
                print("graft-lint: no changed files — clean")
            return 0

    t0 = time.monotonic()
    timings: dict = {}
    try:
        findings = engine.run(REPO_ROOT, only, timings=timings)
    except engine.NoFilesMatched as e:
        print(f"graft-lint: {e}", file=sys.stderr)
        return 2
    except Exception as e:  # noqa: BLE001 - runner must not masquerade
        print(f"graft-lint: internal error: {e!r}", file=sys.stderr)
        return 2
    dt = time.monotonic() - t0
    if args.json:
        payload = {
            "findings": [vars(f) for f in findings],
            "count": len(findings),
            "seconds": round(dt, 2),
            "checker_seconds": timings,
        }
        if args.changed:
            payload["changed"] = only if only is not None else \
                "full tree (lint-suite or table-anchor change)"
        print(json.dumps(payload, indent=2))
    else:
        for f in findings:
            print(f.render())
        slowest = max(timings.items(), key=lambda kv: kv[1],
                      default=None)
        slow = f", slowest {slowest[0]} {slowest[1]:.1f}s" \
            if slowest else ""
        print(f"graft-lint: {len(findings)} finding(s) in "
              f"{dt:.1f}s{slow}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
