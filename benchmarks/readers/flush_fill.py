"""How full the window's launches of one coding operation were: over
the ``codec.flush`` spans of that ``op`` that began inside the window,
the stripes coded for fops (``stripes`` of the span's metadata) over
the stripes launched (``bucket_stripes``: the power-of-two bucket the
batch was zero-padded to), every codec of the mount together.  A
program whose flush spans carry no ``stripes`` (an older commit), an
untraced run and a window without such a flush leave nothing to read,
and nothing is returned."""

import glob
import os

from benchmarks.harness import spans

FLUSH = spans.PREFIX + "codec.flush"


def fill(events: list, w0: float, w1: float, op: str):
    """``events`` as ``spans.events_of`` gives them; ``None`` where no
    flush of ``op`` that began in [w0, w1) says how full it was."""
    coded = launched = 0
    for name, start, _dur, _trace, _span, _parent, meta in events:
        if name != FLUSH or meta.get("op") != op or \
                "stripes" not in meta or "bucket_stripes" not in meta \
                or not w0 <= start < w1:
            continue
        coded += int(meta["stripes"])
        launched += int(meta["bucket_stripes"])
    return coded / launched if launched > 0 else None


def read(run, op: str):
    sp = spans.of_run(run)
    if sp is None:
        return None
    found = glob.glob(os.path.join(
        run.volume.workdir, "trace", "plugins", "profile", "*",
        "*.xplane.pb"))
    if len(found) != 1:
        return None
    return fill(spans.events_of(found[0]), sp.w0, sp.w1, op)
