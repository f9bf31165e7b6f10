"""Share of the device's idle time in the traced window during which
the program had a span of its own open (``harness/spans.py``), each
idle piece going to the innermost span open over it.  What is left is
time in which the program says nothing about itself: the door above the
first layer, the turn-around between two operations.

For the reader, on earlier lines: ``idle_by_span`` (seconds per span
name), ``span_means`` (count and mean of every span name, beside the
counters that time the same calls from outside), ``span_parts`` (the
cell's ``span_ms`` metrics and the flushes' device time against the
door's mean operation) and the slowest operation's tree."""

from benchmarks.harness import spans
from benchmarks.harness.traffic import READ, WRITE

KIND = {"read": READ, "write": WRITE}


def read(run):
    sp = spans.of_run(run)
    if sp is None:
        return None
    run.note("span_means", spans=sp.means())
    for kind, code in KIND.items():
        times = [op[1] - op[0] for op in run.ops if op[2] == code]
        if not times:
            continue
        parts = {m["name"]: run.manifest.reader(m["reader"])(
            run, **m["params"]) for m in run.manifest.cell_metrics(
                run.cell["name"], "per_layer")
            if m["reader"] == "span_ms" and m["params"]["kind"] == kind}
        busy = sp.device_busy()
        parts["device_ms"] = busy and busy * 1e-6 / len(times)
        run.note("span_parts", kind=kind, parts=parts,
                 sum_ms=sum(v for v in parts.values() if v is not None),
                 door_mean_ms=sum(times) / len(times) * 1e3)
    run.note("slowest_span_tree", lines=sp.slowest_tree())
    idle = sp.idle_by_span()
    if not idle or idle[0] <= 0:
        return None
    total, by = idle
    run.note("idle_by_span", idle_s=total * 1e-9, spans=[
        [name, ns * 1e-9] for name, ns
        in sorted(by.items(), key=lambda kv: -kv[1])])
    return sum(by.values()) / total
