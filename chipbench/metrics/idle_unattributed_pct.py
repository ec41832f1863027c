"""Share of the device's idle time in the window under no span of the
program. Each idle gap is split by its overlap with the union of the
program's spans, not given whole to one span."""
from harness import program


def _merged(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def read(ctx):
    v = ctx.view
    found = program.spans(ctx)
    if not v.ops or not found:
        return None
    w0, w1 = v.window_ns
    edges = [w0]
    for a, b in v.busy_intervals():
        a, b = max(a, w0), min(b, w1)
        if b > a:
            edges += [a, b]
    edges.append(w1)
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    idle = sum(b - a for a, b in gaps)
    covered = _merged((r.start_ns, r.end_ns) for r in found)
    under, j = 0.0, 0
    for a, b in gaps:          # both sorted; covered does not overlap
        while j < len(covered) and covered[j][1] <= a:
            j += 1
        k = j
        while k < len(covered) and covered[k][0] < b:
            under += min(b, covered[k][1]) - max(a, covered[k][0])
            k += 1
    return 100.0 * (idle - under) / idle if idle > 0 else None
