"""Share of the traced window in which the first chip was idle while the
engine admitted requests, in %: 100 x (time inside the host's ``engine.admit``
spans in which none of the chip's operations ran) / window. Idle and busy are
as ``device_idle.decode`` counts them. None where the trace holds no
``engine.step`` span: the program then has no spans to read."""

STEP, ADMIT = "engine.step", "engine.admit"


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap_ns(a, b):
    """Length of the intersection of two merged, sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run):
    tr = run.trace
    if tr is None or not tr.chips or not tr.window_s:
        return None
    host = [e for evs in tr.host.values() for e in evs]
    if not any(e.name == STEP for e in host):
        return None
    admit = _merged((e.start_ns, e.end_ns) for e in host if e.name == ADMIT)
    chip = tr.chips[0]
    busy = _merged((e.start_ns, e.end_ns) for e in (tr.leaf_ops(chip) or chip.modules))
    idle_ns = sum(e - s for s, e in admit) - _overlap_ns(admit, busy)
    return 100.0 * idle_ns / 1e9 / tr.window_s
